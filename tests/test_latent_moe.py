"""The latent-attention + sparse-expert family at a tiny float32 size on the
CPU: the two forms of latent attention against each other and against the
plain reference, the paged latent pool through the engine's own calls, YaRN's
closed form, the router's conventions, the chip's share of the experts adding
up to the uncut layer, the latent kernel (interpret mode) against plain
``jax.numpy``, and the layer pattern leaving the uniform stacks' parameter
trees as they were."""
import dataclasses
import hashlib
import json
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import family_latent_moe as fam
from benchmarks.harness import manifest as M
from benchmarks.harness import reference_latent_moe as ref
from k8s_distributed_deeplearning_tpu.models import bert, generate, llama, moe
from k8s_distributed_deeplearning_tpu.models import transformer as T
from k8s_distributed_deeplearning_tpu.ops import pallas_latent_attn
from k8s_distributed_deeplearning_tpu.serve.engine import ServeEngine
from k8s_distributed_deeplearning_tpu.serve.request import Request

CELL = "sarvam-105b-ep4-d6.docs-backlog"
SEED = 3_000_000_019


@pytest.fixture(scope="module")
def tiny():
    """(cfg dict, model, params) of the cell's rehearsal size, weights from
    the seed as the benchmark makes them."""
    cell = M.Cell(M.load_manifest(), CELL)
    cell.apply_rehearsal()
    model, params = fam.build_model_and_params(cell.config, 128, SEED)
    return cell.config, model, params


def _ref_logits(cfg, toks):
    lg, _, _ = ref.forward_logits(cfg, SEED, [np.asarray(toks)], [np.arange(len(toks))])
    return lg[0]


def test_expanded_equals_absorbed_equals_reference_one_layer(tiny):
    """One latent layer (the dense leading one): the plain forward (expanded),
    a prefill of 9 tokens (expanded) then single steps through the row cache
    (absorbed), and the reference's expanded form."""
    cfg, _, _ = tiny
    one = dict(cfg, num_hidden_layers=1)
    model, params = fam.build_model_and_params(one, 128, SEED)
    toks = np.random.default_rng(0).integers(0, 256, size=20).astype(np.int32)
    full = np.asarray(model.apply({"params": params}, jnp.asarray(toks)[None])[0])
    lg, cache = generate.prefill(model, params, jnp.asarray(toks[:9])[None])
    steps = [np.asarray(lg[0])]
    for t in toks[9:]:
        l1, cache = generate.decode_step(model, params, cache, jnp.asarray([t]))
        steps.append(np.asarray(l1))
    absorbed = np.concatenate(steps, axis=0)
    want = _ref_logits(one, toks)
    assert np.abs(full - want).max() < 2e-5
    assert np.abs(absorbed - want).max() < 2e-5
    assert np.abs(absorbed - full).max() < 2e-5


def _pool_for(model, params, pages, page_tokens):
    lanes = model.latent.cache_lanes
    return {"transformer": {f"block_{i}": {"attn": {"cached_latent": jnp.zeros(
        (pages, page_tokens, lanes), jnp.float32)}} for i in range(model.cfg.n_layers)}}


@pytest.mark.parametrize("impl", ["xla", "paged_flash"])
def test_paged_chunks_then_slot_decode_equal_reference_logits(tiny, impl):
    """Prefill in chunks of 16 through the block table (expanded, by blocks of
    the pool: in XLA, and in the chunk kernel), then slot decode (absorbed:
    the XLA gather, and the decode kernel) — the kernels in interpret mode —
    against the logits of the reference's ONE full forward."""
    cfg, model, params = tiny
    import dataclasses
    model = model.clone(cfg=dataclasses.replace(model.cfg, attention_impl=impl),
                        latent=model.latent)
    toks = np.random.default_rng(1).integers(0, 256, size=45).astype(np.int32)
    want = _ref_logits(cfg, toks)
    pt, n_blocks = 8, 8
    cache = _pool_for(model, params, 20, pt)
    table = np.zeros((2, n_blocks), np.int32)
    table[1, :6] = [7, 3, 11, 5, 2, 9]            # row 1 is the request, row 0 idle
    got = []
    for a in range(0, 32, 16):
        pos = (a + jnp.arange(16, dtype=jnp.int32))[None]
        lg, cache, counts = generate.prefill_chunk(
            model, params, cache, jnp.asarray(toks[a:a + 16])[None], positions=pos,
            block_tables=jnp.asarray(table[1:2]))
        got.append(np.asarray(lg[0]))
        assert counts.shape == (2, cfg["num_experts"]) and int(counts.sum()) > 0
    for i in range(32, 45):
        lg, cache, counts = generate.slot_decode_step(
            model, params, cache, jnp.asarray([0, toks[i]], jnp.int32),
            jnp.asarray([0, i], jnp.int32), block_tables=jnp.asarray(table))
        got.append(np.asarray(lg[1:2]))
    assert np.abs(np.concatenate(got, axis=0) - want).max() < 5e-5


def test_engine_greedy_equals_generate_with_the_pool_compiled_once(tiny):
    cfg, model, params = tiny
    before = ServeEngine(model, params, num_slots=4, min_bucket=16,
                         prefill_chunk_tokens=16, prefix_block_tokens=8,
                         kv_pool_pages=48, prefix_cache_mb=1)
    rng = np.random.default_rng(2)
    reqs = [Request(prompt=rng.integers(0, 256, size=n).tolist(), max_new_tokens=k,
                    request_id=f"r{i}")
            for i, (n, k) in enumerate([(40, 6), (17, 5), (9, 8), (33, 4), (50, 7), (21, 3)])]
    before.run(reqs[:1])                       # compiles decode + chunk + final chunk
    sizes = (before.decode_cache_size(), before.chunk_cache_size(),
             before.prefill_cache_size())
    outs = {o.request_id: o for o in before.run(reqs[1:])}
    assert (before.decode_cache_size(), before.chunk_cache_size(),
            before.prefill_cache_size()) == sizes
    for r in reqs[1:]:
        want = generate.generate(model, params, jnp.asarray([r.prompt], jnp.int32),
                                 max_new_tokens=r.max_new_tokens)
        assert outs[r.request_id].tokens == np.asarray(want[0]).tolist()
    leaf = before._cache["transformer"]["block_1"]["attn"]["cached_latent"]
    assert leaf.shape == (49, 8, 128)           # pages + scratch, page tokens, lanes
    s = before.stats.summary()
    assert s["moe_assignments"] > 0 and s["moe_experts_touched"] > 0 and s["moe_max_rows"] > 0


def test_every_calls_expert_counts_are_in_a_record_written_after_they_are_known(tiny):
    """A decode step's counts are fields of its own open span. A chunk's span
    has closed before its counts are on the host, so they come in a
    ``prefill_counts`` record: an intermediate chunk's at a fence that lies
    BEHIND it in the device's queue — a later step's, never the fence of the
    decode it was dispatched behind — and a final chunk's as ONE record (with
    ``bucket``, ``tokens``, ``start``, ``request_id``) where its first token
    is taken. What a sink wrote at each close (the ring keeps just that)
    carries them all, and every chunk call's counts are what the same request
    served alone gives (the decode calls count the free slots' rows too, and
    an idle engine's first request waits a call more: their sum is not
    comparable)."""
    from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer
    cfg, model, params = tiny
    mk = lambda tracer: ServeEngine(
        model, params, num_slots=4, min_bucket=16, prefill_chunk_tokens=16,
        prefix_block_tokens=8, kv_pool_pages=48, prefix_cache_mb=1, tracer=tracer)
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, 256, size=n).tolist(), max_new_tokens=4,
                    request_id=f"q{i}") for i, n in enumerate((50, 9, 37))]
    call = lambda s: (s["request_id"], s["start"], s["tokens"], s.get("bucket"),
                      s["moe_assignments"], s["moe_experts_touched"], s["moe_max_rows"])
    alone = []
    for r in reqs:
        tr = Tracer(ring_size=4096)
        mk(tr).run([Request(prompt=r.prompt, max_new_tokens=4, request_id=r.request_id)])
        alone += [call(s) for s in tr.recent_spans() if s["name"] == "prefill_counts"]

    tracer = Tracer(ring_size=4096)
    eng = mk(tracer)
    eng.run(reqs)
    spans = tracer.recent_spans()
    counted = [s for s in spans if "moe_assignments" in s]
    by_name = lambda n: [s for s in spans if s["name"] == n]
    steps = by_name("engine_step")
    step_of = lambda s: next(i for i, e in enumerate(steps) if e["t0"] <= s["t0"] <= e["t1"])
    chunks = [s for s in by_name("prefill") if "chunk" in s]
    finals = [s for s in by_name("prefill") if "bucket" in s]
    assert len(chunks) == 3 + 0 + 2 and len(finals) == 3
    assert not any("moe_assignments" in s for s in chunks + finals)
    assert all("moe_assignments" in s for s in by_name("decode"))
    late = by_name("prefill_counts")
    key = lambda s: (s["request_id"], s["start"], s["tokens"], s.get("bucket"))
    assert sorted(map(key, late)) == sorted(map(key, chunks + finals))   # ONE record a call
    deferred = []
    for s in late:          # written inside a fence of a LATER step than its chunk's
        assert s["parent"] == "device_wait" and s["moe_max_rows"] >= 1
        mine = next(c for c in chunks + finals if key(c) == key(s))
        assert s["t0"] > mine["t1"]
        if "bucket" not in s:
            assert step_of(s) > step_of(mine), (s, mine)
        else:       # at once where no slot was occupied, else a step later
            wait = next(w for w in by_name("device_wait") if w["t0"] <= s["t0"] <= w["t1"])
            assert wait["kind"] == "first_token"
            assert step_of(s) - step_of(mine) in (0, 1)
            deferred.append((step_of(s) - step_of(mine), wait["covered"]))
    assert (0, 0) in deferred and (1, 1) in deferred and (0, 1) not in deferred
    assert {s["name"] for s in counted} == {"decode", "prefill_counts"}
    assert sorted(map(call, late)) == sorted(alone)
    assert sum(s["moe_assignments"] for s in counted) == eng.stats.summary()["moe_assignments"]
    assert not eng._chunk_counts


def test_latent_pages_export_and_import_by_value(tiny):
    """A slot's latent pages leave one engine and resume in another: the
    page shipping follows the pool's leaves, whatever they hold."""
    _, model, params = tiny
    mk = lambda **kw: ServeEngine(model, params, num_slots=2, min_bucket=16,
                                  prefill_chunk_tokens=16, prefix_block_tokens=8,
                                  kv_pool_pages=24, **kw)
    prompt = np.random.default_rng(4).integers(0, 256, size=29).tolist()
    want = np.asarray(generate.generate(model, params, jnp.asarray([prompt], jnp.int32),
                                        max_new_tokens=9)[0]).tolist()
    src = mk(prefill_only=True)
    src.submit(Request(prompt=prompt, max_new_tokens=9, request_id="x"))
    blobs = []
    while not blobs:
        src.step()
        blobs = src.take_exports()
    assert blobs[0]["pages"][0].shape == (4, 8, 128)            # 29 tokens: 4 pages
    dst = mk()
    dst.import_request_kv(blobs[0])
    done = []
    while dst.busy():
        done.extend(dst.step())
    assert done[0].tokens == want


@pytest.mark.parametrize("what, kw", [("kv_quant='int8'", {"kv_quant": "int8"}),
                                      ("tp=1", {"tp": 1})])
def test_engine_refuses_what_a_latent_pool_cannot_take_by_name(tiny, what, kw):
    _, model, params = tiny
    with pytest.raises(ValueError, match="cached_latent.*" + what.split("=")[0]):
        ServeEngine(model, params, num_slots=2, **kw)


def test_yarn_frequencies_and_mscale_against_the_closed_form():
    la = T.LatentAttentionConfig(rope_factor=40.0, rope_original_max=4096, beta_fast=32.0,
                                 beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    inv = np.asarray(T.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0))
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    d = lambda beta: 64 * math.log(4096 / (2 * math.pi * beta)) / (2 * math.log(10000.0))
    lo, hi = math.floor(d(32.0)), math.ceil(d(1.0))
    assert (lo, hi) == (10, 23)
    np.testing.assert_allclose(inv[:lo + 1], plain[:lo + 1], rtol=1e-6)      # fast: as they are
    np.testing.assert_allclose(inv[hi:], plain[hi:] / 40.0, rtol=1e-6)       # slow: over s
    j = 16
    g = (j - lo) / (hi - lo)
    np.testing.assert_allclose(inv[j], plain[j] * ((1 - g) + g / 40.0), rtol=1e-6)
    m = 0.1 * math.log(40.0) + 1.0
    assert abs(m - 1.3689) < 1e-4 and abs(T.yarn_mscale(40.0, 1.0) - m) < 1e-12
    assert abs(la.softmax_scale - 192 ** -0.5 * m * m) < 1e-12
    assert T.yarn_mscale(1.0, 1.0) == 1.0 and la.cache_lanes == 640
    cfg = {"qk_rope_head_dim": 64, "rope_theta": 10000, "rope_scaling": {
        "factor": 40, "original_max_position_embeddings": 4096, "beta_fast": 32, "beta_slow": 1}}
    np.testing.assert_allclose(ref.yarn_inv_freq(cfg), inv, rtol=1e-6)       # the reference's own


def test_router_bias_moves_the_choice_not_the_gate_and_gates_sum_to_the_scale():
    mo = moe.MoEConfig(num_experts=8, top_k=2, score_fn="sigmoid", select_bias=True,
                       routed_scale=2.5)
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -1.0, -2.0, -3.0]])
    _, idx, _, gates = moe._topk_assignments(logits, 2, mo, jnp.zeros(8))
    assert [int(i[0]) for i in idx] == [0, 1]
    s = jax.nn.sigmoid(logits[0])
    np.testing.assert_allclose(np.asarray(gates[:, 0]), 2.5 * np.asarray(s[:2] / (s[0] + s[1])),
                               rtol=1e-6)
    bias = jnp.zeros(8).at[3].set(1.0)                          # lifts expert 3 into the choice
    _, idx_b, _, gates_b = moe._topk_assignments(logits, 2, mo, bias)
    assert [int(i[0]) for i in idx_b] == [3, 0]
    np.testing.assert_allclose(                                 # its gate is its OWN score
        np.asarray(gates_b[:, 0]), 2.5 * np.asarray([s[3], s[0]]) / float(s[3] + s[0]), rtol=1e-6)
    assert abs(float(gates_b.sum()) - 2.5) < 1e-6 and abs(float(gates.sum()) - 2.5) < 1e-6


def _moe_layer(held, offset, shared):
    cfg = T.TransformerConfig(dim=32, n_heads=2, mlp_dim=64, dtype=jnp.float32)
    mo = moe.MoEConfig(num_experts=8, top_k=3, dispatch="ragged", ragged_block_m=8,
                       score_fn="sigmoid", select_bias=True, routed_scale=2.5,
                       shared_experts=shared, expert_mlp_dim=16, experts_held=held,
                       expert_offset=offset)
    return moe.MoEMLP(cfg, mo)


def _share_params(full, lo, n, shared):
    p = {k: v for k, v in full.items() if k != "shared" or shared}
    for k in ("w_gate", "w_up", "w_down"):
        p[k] = full[k][lo:lo + n]
    return p


@pytest.mark.parametrize("rows", [5, 96])       # dense serving form / grouped kernel
def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(rows):
    x = jax.random.normal(jax.random.key(0), (1, rows, 32))
    uncut = _moe_layer(None, 0, 1)
    full = nn.meta.unbox(uncut.init(jax.random.key(1), x)["params"])
    full["router_bias"] = 0.3 * jax.random.normal(jax.random.key(2), (8,))
    want = uncut.apply({"params": full}, x, decode=True)
    assert moe.serving_dispatch(rows, uncut.moe) == ("grouped" if rows == 96 else "dense")
    parts = [_moe_layer(2, lo, 0).apply({"params": _share_params(full, lo, 2, False)}, x,
                                        decode=True) for lo in (0, 2, 4, 6)]
    only_shared = (_moe_layer(2, 0, 1).apply({"params": _share_params(full, 0, 2, True)}, x,
                                             decode=True) - parts[0])
    np.testing.assert_allclose(np.asarray(sum(parts) + only_shared), np.asarray(want),
                               atol=2e-5)
    assert float(jnp.abs(parts[1]).max()) > 0 and float(jnp.abs(only_shared).max()) > 0


def test_the_references_shares_add_up_to_its_uncut_layer(tiny):
    """The same sum in the plain reference: its share is the program's."""
    cfg, _, _ = tiny
    whole = dict(cfg, num_experts=cfg["router_outputs"])        # 16 held of 16
    w = ref.make_layer(whole, SEED, 1)
    x = jax.random.normal(jax.random.key(3), (ref.PAD, cfg["hidden_size"]))
    run = lambda w, **kw: ref.layer_forward(x, w, key=ref._cfg_key(dict(whole, **kw)))[0]
    uncut = run(w)
    cut = lambda lo, n: {k: (v[lo:lo + n] if k in ("mlp/w_gate", "mlp/w_up", "mlp/w_down")
                             else v) for k, v in w.items()}
    after_attention = run(cut(0, 0), shared_expert=False)
    routed = [run(cut(lo, 4), expert_offset=lo, shared_expert=False) - after_attention
              for lo in (0, 4, 8, 12)]
    shared = run(cut(0, 0), shared_expert=True) - after_attention
    np.testing.assert_allclose(np.asarray(after_attention + sum(routed) + shared),
                               np.asarray(uncut), atol=2e-5)


def test_grouped_and_dense_serving_dispatch_agree_for_a_share():
    x = jax.random.normal(jax.random.key(4), (2, 48, 32))
    layer = _moe_layer(4, 2, 1)
    params = nn.meta.unbox(layer.init(jax.random.key(5), x)["params"])
    assert moe.serving_dispatch(96, layer.moe) == "grouped"
    grouped, stats = layer.apply({"params": params}, x, decode=True, mutable=["moe_stats"])
    rows = [layer.apply({"params": params}, x[:, i:i + 4], decode=True) for i in range(0, 48, 4)]
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(jnp.concatenate(rows, axis=1)),
                               atol=2e-5)
    counts = generate.moe_assignments(stats)
    assert counts.shape == (1, 4) and 0 < int(counts.sum()) < 96 * 3


@pytest.mark.parametrize("rows, top_k, experts, dispatch, want", [
    (128, 2, 8, "ragged", "grouped"), (127, 2, 8, "ragged", "dense"),
    (1024, 8, 128, "ragged", "grouped"), (32, 8, 128, "ragged", "dense"),
    (4096, 2, 8, "index", "dense"),
    # 32 experts top-4 (the conv-moe cell): a 128-slot decode has 16 rows an expert — timed on
    # the chip in both forms, dense won (PR 31) — a 256-token chunk exactly 32
    (128, 4, 32, "ragged", "dense"), (256, 4, 32, "ragged", "grouped"),
    (512, 4, 32, "ragged", "grouped")])
def test_serving_dispatch_is_one_stated_rule_on_rows_an_expert(rows, top_k, experts, dispatch, want):
    mo = moe.MoEConfig(num_experts=experts, top_k=top_k, dispatch=dispatch)
    assert moe.serving_dispatch(rows, mo) == want


def test_on_a_tpu_auto_always_takes_the_latent_kernels():
    """The XLA latent paths gather a row's whole table and expand it at once:
    the CPU tests' path and the kernels' reference, never the chip's."""
    assert pallas_latent_attn.default_impl("tpu") == "latent_flash"
    assert pallas_latent_attn.default_impl("cpu") == pallas_latent_attn.default_impl() == "xla"
    cfg = T.TransformerConfig(vocab_size=8, dim=8, n_layers=1, n_heads=1)
    assert cfg.attention_impl == "auto" and T.latent_attention_impl(cfg) == "xla"     # here
    assert T.latent_attention_impl(
        dataclasses.replace(cfg, attention_impl="paged_flash")) == "latent_flash"


@pytest.mark.parametrize("pages_per_cell", [1, 2, 4])
def test_latent_kernel_interpret_against_plain_numpy(pages_per_cell):
    """sq = 1, ragged cursors, idle rows (cursor 0, table all scratch)."""
    b, h, lanes, r, pt, n_blocks = 5, 4, 128, 32, 8, 7
    rng = np.random.default_rng(3)
    pool = rng.normal(size=(30, pt, lanes)).astype(np.float32)
    pool[..., 40:] = 0.0
    q = rng.normal(size=(b, 1, h, lanes)).astype(np.float32)
    q[..., 40:] = 0.0
    cursors = np.array([37, 0, 8, 55, 0], np.int32)
    tables = np.zeros((b, n_blocks), np.int32)
    for i, c in enumerate(cursors):
        if c:
            tables[i, :c // pt + 1] = rng.choice(np.arange(1, 30), size=c // pt + 1,
                                                 replace=False)
    got = np.asarray(pallas_latent_attn.latent_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables), jnp.asarray(cursors)[:, None],
        rank=r, softmax_scale=0.2, pages_per_cell=pages_per_cell, interpret=True))
    for i, c in enumerate(cursors):
        rows = pool[tables[i]].reshape(n_blocks * pt, lanes)[:c + 1]
        s = np.einsum("hl,kl->hk", q[i, 0], rows) * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[:, :r]
        np.testing.assert_allclose(got[i, 0], want, atol=2e-5)


@pytest.mark.parametrize("start, heads_per_cell", [(0, 2), (19, 2), (40, 1)])
def test_latent_chunk_kernel_interpret_against_plain_numpy(start, heads_per_cell):
    """The expanded form over a gathered row: 16 queries at positions
    ``start + [0, 16)`` against 64 cache positions in blocks of 16 (blocks
    past the chunk's last position are neither fetched nor computed)."""
    h, r, dn, dr, dv, sq, s_virt, lanes = 4, 32, 16, 8, 16, 16, 64, 128
    rng = np.random.default_rng(7)
    q_n = rng.normal(size=(1, sq, h, dn)).astype(np.float32)
    q_r = rng.normal(size=(1, sq, h, dr)).astype(np.float32)
    lat = rng.normal(size=(1, s_virt, lanes)).astype(np.float32)
    lat[..., r + dr:] = 0.0
    w_uk = rng.normal(size=(r, h, dn)).astype(np.float32)
    w_uv = rng.normal(size=(r, h, dv)).astype(np.float32)
    pos = (start + np.arange(sq, dtype=np.int32))[None]
    got = np.asarray(pallas_latent_attn.latent_chunk_attention(
        *map(jnp.asarray, (q_n, q_r, lat, w_uk, w_uv, pos)), rank=r, softmax_scale=0.3,
        block_k=16, heads_per_cell=heads_per_cell, interpret=True))
    k_n = np.einsum("kr,rhd->khd", lat[0, :, :r], w_uk)
    v = np.einsum("kr,rhd->khd", lat[0, :, :r], w_uv)
    s = (np.einsum("qhd,khd->hqk", q_n[0], k_n)
         + np.einsum("qhd,kd->hqk", q_r[0], lat[0, :, r:r + dr])) * 0.3
    s = np.where(np.arange(s_virt)[None, None, :] <= pos[0][None, :, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(got[0], want, atol=3e-5)


def _signature(model, *args):
    p = nn.meta.unbox(model.init(jax.random.key(0), *args)["params"])
    flat = jax.tree_util.tree_flatten_with_path(p)[0]
    tree = {"/".join(str(getattr(k, "key", k)) for k in path): list(v.shape)
            for path, v in flat}
    return (hashlib.sha1(json.dumps(tree, sort_keys=True).encode()).hexdigest()[:12], len(tree),
            round(float(sum(jnp.sum(jnp.abs(x)) for x in jax.tree.leaves(p))), 3))


@pytest.mark.parametrize("name, build, want", [
    ("llama scanned", lambda: llama.LlamaLM(llama.config_tiny()), ("3432b1612c8d", 12, 8842.111)),
    ("llama unrolled", lambda: llama.LlamaLM(llama.config_tiny(scan_layers=False)),
     ("6950fd89c015", 21, 8823.259)),
    ("bert scanned", lambda: bert.BertMLM(bert.config_tiny()), ("8d7de61b3db8", 21, 7585.495)),
    ("moe scanned", lambda: moe.MoELM(llama.config_tiny(), moe.MoEConfig(num_experts=4, top_k=2)),
     ("13d84b4b231c", 13, 13259.539))])
def test_uniform_stacks_keep_their_parameter_trees_under_the_layer_pattern(name, build, want):
    """Names, shapes and the initial values (so the RNG paths too) of the
    trees as the commit before the layer pattern made them (PR 26)."""
    got = _signature(build(), jnp.zeros((1, 8), jnp.int32))
    assert got[:2] == want[:2] and abs(got[2] - want[2]) < 0.01


def test_layers_of_different_kinds_refuse_the_scan():
    cfg, la, mo = moe.config_tiny_latent_moe(scan_layers=True)
    with pytest.raises(ValueError, match="cannot be scanned"):
        moe.LatentMoELM(cfg, la, mo).init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
