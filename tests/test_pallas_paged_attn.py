"""Pallas paged decode-attention: numerics vs the XLA virtual-column
path, cursor/scratch masking invariants, GQA head mapping, and input
validation — all in interpret mode so CPU CI runs the exact kernel code.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu.models import generate, llama
from k8s_distributed_deeplearning_tpu.ops import pallas_paged_attn
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


def _cursors(q, pool_k, pool_v, tables, positions, cursors):
    """The same case with each row's LAST query at the given cursor (the
    window's earlier queries just before it), and the table entries past
    the cursor set to the scratch page, as the engine leaves them."""
    sq = positions.shape[1]
    bt = pool_k.shape[1]
    cursors = np.asarray(cursors)
    positions = (cursors[:, None] - (sq - 1)
                 + np.arange(sq)[None, :]).astype(np.int32)
    tables = np.where(np.arange(tables.shape[1])[None, :]
                      > (cursors // bt)[:, None], 0, tables)
    return q, pool_k, pool_v, tables.astype(np.int32), positions


def _run(case, **kw):
    return np.asarray(paged_decode_attention(
        *(jnp.asarray(x) for x in case), interpret=True, **kw))


@pytest.mark.parametrize("b,sq,h,hkv,pages,bt,nb,ppc,cursors", [
    # the rule's own choice (these pools are narrower than a lane tile: one
    # page a cell, read through the block pipeline)
    (2, 1, 4, 2, 16, 8, 4, None, None),     # single-token decode, GQA 2:1
    (3, 5, 4, 4, 32, 16, 3, None, None),    # speculative verify window, MHA
    (2, 3, 8, 2, 64, 4, 6, None, None),     # wide window, GQA 4:1, small pages
    # several cells a row; a cell is ppc pages of bt tokens
    (4, 1, 8, 2, 64, 4, 7, 2, None),        # n_blocks not a multiple of P
    (4, 1, 8, 2, 64, 4, 7, 3, (2, 11, 12, 27)),   # first page; a cell's last
    #                                column; the next cell's first; last page
    (3, 5, 4, 4, 64, 8, 6, 4, (4, 31, 32)),       # window ends in the first
    #                                page; on a cell's edge; straddles cells
    (2, 1, 8, 2, 64, 4, 6, 2, (0, 23)),     # a cursor-0 row beside a full row
    (2, 16, 8, 2, 64, 8, 5, 2, (15, 39)),   # a chunk width; its first chunk
    (2, 16, 4, 4, 64, 8, 5, 3, (31, 24)),   # chunk, MHA, tail cell of 2 pages
    (3, 1, 4, 4, 32, 16, 3, 1, None),       # one page a cell, MHA
    (2, 5, 8, 2, 64, 4, 6, 6, (19, 5)),     # forced P = n_blocks, GQA 4:1
])
def test_kernel_matches_xla_reference(b, sq, h, hkv, pages, bt, nb, ppc,
                                      cursors):
    rng = np.random.default_rng(b * 100 + sq * 10 + h)
    case = _case(rng, b, sq, h, hkv, pages, bt, nb)
    if cursors is not None:
        case = _cursors(*case, cursors)
    np.testing.assert_allclose(_run(case, pages_per_cell=ppc), _ref(*case),
                               atol=2e-5, rtol=2e-5)


def _attend_xla(q, pool_k, pool_v, tables, positions, k_scale=None,
                v_scale=None):
    """The model's own XLA gather-attend (``Attention.__call__``'s block-table
    branch): gather the whole table, mask by cursor, the einsum path."""
    from k8s_distributed_deeplearning_tpu.ops import attention
    b, sq, _, hd = q.shape
    hkv = pool_k.shape[2] // hd
    s_virt = tables.shape[1] * pool_k.shape[1]
    k = pool_k[tables].reshape(b, s_virt, hkv, hd)
    v = pool_v[tables].reshape(b, s_virt, hkv, hd)
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[tables].reshape(b, s_virt, hkv, 1)
        v = v.astype(jnp.float32) * v_scale[tables].reshape(b, s_virt, hkv, 1)
    mask = (jnp.arange(s_virt)[None, None, :] <= positions[:, :, None])[:, None]
    return attention.multi_head_attention(
        q, k.astype(q.dtype), v.astype(q.dtype), causal=False, mask=mask,
        impl="xla")


# A prefill chunk wider than one grid row: (32 q / 8 kv x 64) is lfm2's
# attention (groups of 4: blocks of 128 queries), (32 q / 2 kv x 128)
# nemotron's (groups of 16: blocks of 32), at tiny pools; the third geometry
# is one local hd-64 KV head under tp (a pool narrower than a lane tile: one
# page a cell), the fourth int8 pages with their scales gathered per block.
_LFM2, _NEMOTRON, _NARROW = (32, 8, 64), (32, 2, 128), (3, 1, 64)


@pytest.mark.parametrize("heads,sq,start,quant", [
    (_LFM2, 256, 0, False),          # a request's first chunk
    (_LFM2, 512, 0, False),
    (_LFM2, 256, 37, False),         # a cursor that starts mid-page
    (_LFM2, 512, 168, False),        # a prefix of several pages before it
    (_LFM2, 512, 300, False),        # a final chunk: its pad positions run
    #                                  past the 704-position table
    (_NEMOTRON, 256, 0, False),
    (_NEMOTRON, 512, 37, False),
    (_NEMOTRON, 256, 552, False),    # prefix + pads past the table
    (_NARROW, 256, 37, False),
    (_LFM2, 256, 37, True),
], ids=["lfm2-256", "lfm2-512", "lfm2-256-midpage", "lfm2-512-prefix",
        "lfm2-512-pads-past-table", "nemotron-256", "nemotron-512-midpage",
        "nemotron-256-pads-past-table", "narrow-256-midpage",
        "lfm2-256-int8"])
def test_query_blocks_match_the_xla_gather_attend(heads, sq, start, quant):
    """A call wider than 128 tokens a row attends in blocks of queries — each
    block a grid row with its own cursors and last live block — and gives
    what the XLA gather-attend of the whole table gives, at the rule's own
    block and pages a cell. Rows whose position lies past the table (a final
    chunk's pads) are rows nobody reads: finite, not compared."""
    h, hkv, hd = heads
    bt, nb, pages = 8, 88, 120
    rng = np.random.default_rng(sq + start + h * hkv)
    q = jnp.asarray(rng.standard_normal((1, sq, h, hd)), jnp.float32)
    pool = lambda: rng.standard_normal((pages, bt, hkv * hd))
    tables = jnp.asarray(rng.permutation(np.arange(1, pages))[:nb][None],
                         jnp.int32)
    positions = jnp.asarray(start + np.arange(sq)[None], jnp.int32)
    scales = {}
    if quant:
        pool_k, pool_v = (jnp.asarray(np.clip(np.round(pool() * 40), -127, 127),
                                      jnp.int8) for _ in range(2))
        scales = dict(k_scale=jnp.asarray(rng.uniform(
            0.01, 0.03, (pages, bt, hkv)), jnp.float32), v_scale=jnp.asarray(
                rng.uniform(0.01, 0.03, (pages, bt, hkv)), jnp.float32))
    else:
        pool_k, pool_v = (jnp.asarray(pool(), jnp.float32) for _ in range(2))
    qb = pallas_paged_attn.default_query_block(sq, h // hkv)
    assert qb == {4: 128, 16: 32, 3: 128}[h // hkv] and sq // qb >= 2
    out = np.asarray(paged_decode_attention(
        q, pool_k, pool_v, tables, positions, interpret=True, **scales))
    ref = np.asarray(_attend_xla(q, pool_k, pool_v, tables, positions,
                                 **scales))
    live = min(sq, nb * bt - start)
    np.testing.assert_allclose(out[:, :live], ref[:, :live],
                               atol=3e-5, rtol=3e-5)
    assert np.isfinite(out).all()


def test_a_forced_query_block_is_a_schedule_not_a_result():
    """Any block that divides the call gives the rule's result (to f32
    rounding of where the rescales fall); one that does not is refused."""
    rng = np.random.default_rng(9)
    q, pk, pv, tables, _ = _case(rng, 2, 256, 8, 2, 96, 8, 40)
    pos = np.stack([37 + np.arange(256), np.arange(256)]).astype(np.int32)
    case = (q, pk, pv, tables, pos)
    ruled = _run(case, pages_per_cell=4)
    for qb in (32, 64, 256):
        np.testing.assert_allclose(
            _run(case, pages_per_cell=4, query_block=qb), ruled,
            atol=2e-6, rtol=2e-6)
    with pytest.raises(ValueError, match="query_block"):
        _run(case, query_block=96)


@pytest.mark.parametrize("sq,h,hkv", [(1, 8, 2), (5, 4, 4), (16, 8, 2)])
def test_one_page_a_cell_equals_the_rules_choice(sq, h, hkv):
    """The grid is a schedule, not a result: one page a cell (seven cells a
    row here — the rule's choice for so narrow a pool, and the same forced)
    gives what the whole table in one cell gives, to f32 rounding of the
    extra rescales."""
    rng = np.random.default_rng(sq)
    case = _case(rng, 3, sq, h, hkv, 64, 4, 7)
    ruled = _run(case)
    np.testing.assert_array_equal(_run(case, pages_per_cell=1), ruled)
    np.testing.assert_allclose(_run(case, pages_per_cell=7), ruled,
                               atol=2e-6, rtol=2e-6)


def test_pages_per_cell_out_of_range_is_refused():
    case = _case(np.random.default_rng(0), 2, 1, 4, 2, 16, 8, 4)
    for bad in (0, 5):
        with pytest.raises(ValueError, match="pages_per_cell"):
            _run(case, pages_per_cell=bad)


# The benchmark cell's call shapes (mistral-7b widths: 32 q / 8 kv x 128,
# 32-token pages, a 128-block table) and the smoke model's, fp and int8.
_RULE_SHAPES = [
    dict(heads=32, hd=128, kvhd=1024, page_tokens=32, n_blocks=128),
    dict(heads=12, hd=64, kvhd=256, page_tokens=32, n_blocks=32),
    dict(heads=8, hd=128, kvhd=256, page_tokens=32, n_blocks=128),   # tp = 4
    dict(heads=3, hd=64, kvhd=64, page_tokens=32, n_blocks=32),      # tp = 4
]


@pytest.mark.parametrize("shape", _RULE_SHAPES,
                         ids=["mistral", "small", "mistral-tp4", "small-tp4"])
@pytest.mark.parametrize("sq", [1, 5, 128])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_pages_per_cell_rule(shape, sq, quant):
    """The rule's choice, from what a call can see: at least one page, never
    more than the table holds, than CELL_TOKENS or than SCORE_TILE_ELEMS
    scores a KV head, and inside the VMEM budget by the module's own
    accounting — where one page more would not be, unless the table or one
    of the two targets stopped it first."""
    kw = dict(shape, sq=sq, quant=quant, q_itemsize=2,
              kv_itemsize=1 if quant else 2)
    n_blocks = kw.pop("n_blocks")
    p = pallas_paged_attn.default_pages_per_cell(n_blocks=n_blocks, **kw)
    rows = kw["heads"] // (kw["kvhd"] // kw["hd"]) * sq
    tokens = min(pallas_paged_attn.CELL_TOKENS,
                 pallas_paged_attn.SCORE_TILE_ELEMS // rows)
    assert 1 <= p <= n_blocks
    assert p * kw["page_tokens"] <= tokens
    budget = pallas_paged_attn.VMEM_BUDGET_BYTES
    assert pallas_paged_attn.cell_vmem_bytes(p, **kw) <= budget
    assert budget < pallas_paged_attn.VMEM_LIMIT_BYTES
    if kw["kvhd"] % 128:
        assert p == 1
    elif p < n_blocks and (p + 1) * kw["page_tokens"] <= tokens:
        assert pallas_paged_attn.cell_vmem_bytes(p + 1, **kw) > budget


def test_pages_per_cell_rule_at_the_benchmark_cell():
    """What the backlog cell's three programs get (PERF.md section 6, PR 26):
    decode and a verify window 32 pages = 1024 tokens a cell, 4 cells a row;
    a 128-token chunk 8 pages = 256 tokens, 16 cells."""
    kw = dict(heads=32, hd=128, kvhd=1024, page_tokens=32, n_blocks=128,
              kv_itemsize=2, q_itemsize=2)
    rule = pallas_paged_attn.default_pages_per_cell
    assert [rule(sq=sq, **kw) for sq in (1, 5, 128)] == [32, 32, 8]


def test_pages_per_cell_rule_small_tables_wide_pages_narrow_pools():
    """Bounded by the table; one page when a page alone is the token target
    or more; and one page for a pool narrower than a lane tile (every
    geometry of this file, and one local hd-64 KV head under tp), which the
    kernel then reads through the block pipeline."""
    kw = dict(sq=1, heads=4, hd=128, kvhd=128, kv_itemsize=2, q_itemsize=2)
    rule = pallas_paged_attn.default_pages_per_cell
    assert rule(page_tokens=8, n_blocks=4, **kw) == 4
    assert rule(page_tokens=4, n_blocks=6, **kw) == 6
    assert rule(page_tokens=1024, n_blocks=4, **kw) == 1
    assert rule(page_tokens=2048, n_blocks=4, **kw) == 1
    for kvhd, hd in ((64, 64), (192, 64), (16, 8)):
        assert rule(page_tokens=32, n_blocks=128, sq=1, heads=kvhd // hd * 3,
                    hd=hd, kvhd=kvhd, kv_itemsize=2, q_itemsize=2) == 1


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
    """``attention_impl="auto"`` for block-table calls: the kernel on TPU for
    a call of one grid row a batch row (decode, verify window, a 128-token
    prefill chunk) and for every wider call that is a whole number of query
    blocks, the XLA gather for a width off the block grid and off TPU — and
    the engine reports, per program, exactly what the model will trace."""
    from k8s_distributed_deeplearning_tpu.models.transformer import (
        paged_attention_impl)
    from k8s_distributed_deeplearning_tpu.ops.pallas_paged_attn import (
        MAX_QUERY_TOKENS, default_impl)
    assert MAX_QUERY_TOKENS == 128
    for sq in (1, 5, 128):
        assert default_impl(sq, platform="tpu") == "paged_flash"
    # wider: whole blocks of queries (128 at a group of 4 or fewer, 32 at
    # nemotron's 16) take the kernel, a width off the block grid the gather
    block = pallas_paged_attn.default_query_block
    assert [block(sq, 4) for sq in (1, 128, 256, 512, 1024, 4096, 320)] == [
        1, 128, 128, 128, 128, 128, 320]
    assert [block(sq, g) for sq, g in ((512, 1), (512, 8), (512, 16),
                                       (160, 16), (144, 16))] == [
        128, 64, 32, 32, 144]
    for sq in (256, 512, 1024):
        for group in (1, 4, 16):
            assert default_impl(sq, platform="tpu", group=group) == "paged_flash"
        assert default_impl(sq, platform="cpu", group=4) == "xla"
    assert default_impl(320, platform="tpu", group=4) == "xla"
    assert default_impl(320, platform="tpu", group=16) == "paged_flash"
    assert default_impl(130, platform="tpu", group=16) == "xla"
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
    # 256 tokens in 32-token pages, a pool 32 lanes wide (2 KV heads x 16):
    # narrower than a lane tile, so a page a cell — 8 cells a row.
    assert eng.attention_impls() == {
        "decode": "paged_flash pages_per_cell=1 cells=16",
        "chunk_64": "paged_flash pages_per_cell=1 cells=8",
        "final_chunk_32": "paged_flash pages_per_cell=1 cells=8",
        "final_chunk_64": "paged_flash pages_per_cell=1 cells=8"}
    # Without chunks the buckets run to max_seq_len: the one wider than 128
    # tokens is cut into two blocks of 128 queries, each a grid row.
    mono = ServeEngine(model, params, num_slots=2)
    assert mono.attention_impls() == {
        "decode": "paged_flash pages_per_cell=1 cells=16",
        "final_chunk_32": "paged_flash pages_per_cell=1 cells=8",
        "final_chunk_64": "paged_flash pages_per_cell=1 cells=8",
        "final_chunk_128": "paged_flash pages_per_cell=1 cells=8",
        "final_chunk_256": "paged_flash q_block=128 pages_per_cell=1 cells=16"}
    # The same engine at the benchmark cell's widths and table would report
    # what test_pages_per_cell_rule_at_the_benchmark_cell holds the rule to.
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
