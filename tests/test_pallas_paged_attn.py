"""Pallas paged decode-attention: numerics vs the XLA virtual-column
path, cursor/scratch masking invariants, GQA head mapping, and input
validation — all in interpret mode so CPU CI runs the exact kernel code.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu.models import generate, llama
from k8s_distributed_deeplearning_tpu.ops.pallas_paged_attn import (
    paged_decode_attention)
from k8s_distributed_deeplearning_tpu.serve import Request, ServeEngine


def _ref(q, pool_k, pool_v, tables, positions, scale=None):
    """The XLA path the kernel replaces: gather the virtual sequence,
    mask columns beyond each query's cursor, plain softmax attention."""
    b, sq, h, hd = q.shape
    bt, kvhd = pool_k.shape[1:]
    hkv = kvhd // hd
    group = h // hkv
    s_virt = tables.shape[1] * bt
    k = pool_k[tables].reshape(b, s_virt, hkv, hd).astype(np.float32)
    v = pool_v[tables].reshape(b, s_virt, hkv, hd).astype(np.float32)
    scale = hd ** -0.5 if scale is None else scale
    col = np.arange(s_virt)
    out = np.zeros((b, sq, h, hd), np.float32)
    for bi in range(b):
        for i in range(sq):
            allow = col <= positions[bi, i]
            for qi in range(h):
                s = (k[bi, :, qi // group] @ q[bi, i, qi].astype(
                    np.float32)) * scale
                s = np.where(allow, s, -np.inf)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[bi, i, qi] = p @ v[bi, :, qi // group]
    return out


def _case(rng, b, sq, h, hkv, pages, bt, nb):
    """Random pools + per-row tables mapping every block below the cursor
    to a distinct real page; positions cover the whole virtual range."""
    hd = 8
    q = rng.standard_normal((b, sq, h, hd)).astype(np.float32)
    pool_k = rng.standard_normal((pages, bt, hkv * hd)).astype(np.float32)
    pool_v = rng.standard_normal((pages, bt, hkv * hd)).astype(np.float32)
    perm = rng.permutation(np.arange(1, pages))[:b * nb]
    tables = perm.reshape(b, nb).astype(np.int32)
    base = rng.integers(sq - 1, nb * bt, size=b)
    positions = (base[:, None] - (sq - 1) + np.arange(sq)[None, :]).astype(
        np.int32)
    return q, pool_k, pool_v, tables, positions


@pytest.mark.parametrize("b,sq,h,hkv,pages,bt,nb", [
    (2, 1, 4, 2, 16, 8, 4),      # classic single-token decode, GQA 2:1
    (3, 5, 4, 4, 32, 16, 3),     # speculative verify window, MHA
    (2, 3, 8, 2, 64, 4, 6),      # wide window, GQA 4:1, small pages
])
def test_kernel_matches_xla_reference(b, sq, h, hkv, pages, bt, nb):
    rng = np.random.default_rng(b * 100 + sq * 10 + h)
    q, pk, pv, tables, pos = _case(rng, b, sq, h, hkv, pages, bt, nb)
    out = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(tables), jnp.asarray(pos), interpret=True))
    np.testing.assert_allclose(out, _ref(q, pk, pv, tables, pos),
                               atol=2e-5, rtol=2e-5)


def test_explicit_softmax_scale():
    rng = np.random.default_rng(5)
    q, pk, pv, tables, pos = _case(rng, 2, 2, 4, 2, 16, 8, 3)
    out = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(tables), jnp.asarray(pos), softmax_scale=0.25,
        interpret=True))
    np.testing.assert_allclose(out, _ref(q, pk, pv, tables, pos, scale=0.25),
                               atol=2e-5, rtol=2e-5)


def test_stale_kv_beyond_cursor_never_attended():
    """The rollback guarantee speculative decoding leans on: rewriting
    every pool token BEYOND each row's cursor (rejected drafts, freed-slot
    garbage) must not change a single output bit."""
    rng = np.random.default_rng(11)
    q, pk, pv, tables, pos = _case(rng, 3, 2, 4, 2, 32, 8, 4)
    args = (jnp.asarray(q), jnp.asarray(tables), jnp.asarray(pos))
    out = np.asarray(paged_decode_attention(
        args[0], jnp.asarray(pk), jnp.asarray(pv), args[1], args[2],
        interpret=True))
    bt = pk.shape[1]
    pk2, pv2 = pk.copy(), pv.copy()
    for bi in range(tables.shape[0]):
        cursor = int(pos[bi].max())
        for blk in range(tables.shape[1]):
            page = tables[bi, blk]
            lo = blk * bt
            for t in range(bt):
                if lo + t > cursor:
                    pk2[page, t] = 1e4
                    pv2[page, t] = -1e4
    out2 = np.asarray(paged_decode_attention(
        args[0], jnp.asarray(pk2), jnp.asarray(pv2), args[1], args[2],
        interpret=True))
    np.testing.assert_array_equal(out, out2)


def test_scratch_page_blocks_are_inert():
    """Table entries past the live length point at scratch page 0; giving
    those blocks real (huge-valued) pages instead must change nothing,
    because the cursor mask already excludes every column they cover."""
    rng = np.random.default_rng(13)
    b, sq, hd = 2, 1, 8
    pages, bt, nb = 16, 8, 4
    q = rng.standard_normal((b, sq, 4, hd)).astype(np.float32)
    pool_k = rng.standard_normal((pages, bt, 2 * hd)).astype(np.float32)
    pool_v = rng.standard_normal((pages, bt, 2 * hd)).astype(np.float32)
    pool_k[7] = 1e4                    # the "garbage" page
    pool_v[7] = -1e4
    pos = np.array([[11], [5]], np.int32)   # live blocks: 2 and 1
    t_scratch = np.array([[1, 2, 0, 0], [3, 0, 0, 0]], np.int32)
    t_garbage = np.array([[1, 2, 7, 7], [3, 7, 7, 7]], np.int32)
    outs = [np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray(t), jnp.asarray(pos), interpret=True))
        for t in (t_scratch, t_garbage)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_allclose(
        outs[0], _ref(q, pool_k, pool_v, t_scratch, pos),
        atol=2e-5, rtol=2e-5)


def test_input_validation():
    q = jnp.zeros((2, 1, 4, 8), jnp.float32)
    pk = jnp.zeros((8, 4, 16), jnp.float32)
    tables = jnp.zeros((2, 3), jnp.int32)
    pos = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match=r"q must be"):
        paged_decode_attention(q[0], pk, pk, tables, pos)
    with pytest.raises(ValueError, match=r"identical"):
        paged_decode_attention(q, pk, pk[:, :, :8], tables, pos)
    with pytest.raises(ValueError, match=r"multiple of head_dim"):
        paged_decode_attention(q, jnp.zeros((8, 4, 12)),
                               jnp.zeros((8, 4, 12)), tables, pos)
    with pytest.raises(ValueError, match=r"not divisible"):
        paged_decode_attention(jnp.zeros((2, 1, 3, 8)),
                               pk, pk, tables, pos)
    with pytest.raises(ValueError, match=r"block_tables"):
        paged_decode_attention(q, pk, pk, tables[:1], pos)
    with pytest.raises(ValueError, match=r"positions"):
        paged_decode_attention(q, pk, pk, tables, pos[:, :0])


def test_serving_engine_parity_on_kernel_path():
    """End to end through the ServeEngine: a model pinned to
    ``attention_impl="paged_flash"`` (the interpret-mode kernel on CPU)
    emits the SAME greedy tokens as the default XLA-gather model — the
    kernel is a drop-in for the whole decode branch, not just a matching
    matmul."""
    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=64)
    model = llama.LlamaLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    kcfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=64,
                             attention_impl="paged_flash")
    kmodel = llama.LlamaLM(kcfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(4, 14))).astype(np.int32)
               for _ in range(4)]

    def run(m):
        eng = ServeEngine(m, params, num_slots=2, eos_id=None)
        reqs = [Request(prompt=p, max_new_tokens=6) for p in prompts]
        outs = {o.request_id: o for o in eng.run(reqs)}
        return [outs[r.request_id].tokens for r in reqs]

    assert run(kmodel) == run(model)


def test_auto_selection_rule_is_shape_and_platform():
    """``attention_impl="auto"`` for block-table calls: the kernel on TPU at
    the query widths it serves (decode, verify window, a 128-token prefill
    chunk), the XLA gather for wider prefill buckets and off TPU — and the
    engine reports, per program, exactly what the model will trace."""
    from k8s_distributed_deeplearning_tpu.models.transformer import (
        paged_attention_impl)
    from k8s_distributed_deeplearning_tpu.ops.pallas_paged_attn import (
        MAX_QUERY_TOKENS, default_impl)
    assert MAX_QUERY_TOKENS == 128
    for sq in (1, 5, 128):
        assert default_impl(sq, platform="tpu") == "paged_flash"
    assert default_impl(256, platform="tpu") == "xla"
    assert default_impl(1024, platform="tpu") == "xla"
    assert default_impl(1, platform="cpu") == "xla"
    assert default_impl(1) == "xla"                     # CI runs on CPU

    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=256)
    forced = llama.config_tiny(dtype=jnp.float32, max_seq_len=256,
                               attention_impl="paged_flash")
    assert paged_attention_impl(cfg, 1) == "xla"
    assert paged_attention_impl(forced, 1024) == "paged_flash"
    assert paged_attention_impl(
        llama.config_tiny(attention_impl="flash"), 1) == "xla"

    model = llama.LlamaLM(forced)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServeEngine(model, params, num_slots=2, prefill_chunk_tokens=64)
    assert eng.attention_impls() == {
        "decode": "paged_flash", "chunk_64": "paged_flash",
        "final_chunk_32": "paged_flash", "final_chunk_64": "paged_flash"}
    plain = ServeEngine(llama.LlamaLM(cfg), params, num_slots=2)
    assert plain.attention_impls() == {
        "decode": "xla", "final_chunk_32": "xla", "final_chunk_64": "xla",
        "final_chunk_128": "xla", "final_chunk_256": "xla"}


def test_kernel_carries_the_name_it_was_given():
    """The kernel's events in a device trace are found by name: the
    ``pallas_call`` names itself ``paged_attn`` and does not inherit the
    scope of whichever module calls it."""
    q, pool_k, pool_v, tables, positions = _case(
        np.random.default_rng(3), 2, 1, 4, 2, 16, 8, 4)

    def caller(*a):
        with jax.named_scope("some_module"):
            return paged_decode_attention(*a, interpret=True)
    args = [jnp.asarray(x) for x in (q, pool_k, pool_v, tables, positions)]
    jaxpr = str(jax.make_jaxpr(caller)(*args))
    assert "name=paged_attn" in jaxpr
    lowered = jax.jit(caller).lower(*args).as_text(debug_info=True)
    assert "some_module/paged_attn" in lowered
