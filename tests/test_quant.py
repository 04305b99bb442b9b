"""graftquant: int8 KV pages + per-channel int8 serving weights.

The quality bar has two halves. Numerics: the Pallas kernel's fused
dequant must match the XLA dequantized reference bit-for-bit (same f32
multiply, different place), and every token the engine serves under
kv_quant+weight_quant must lie within a stated margin of the fp model's
best at its position, given the same prefix.
Mechanics: the scale siblings must ride every page-granular path the
pool already has — prefix-trie sharing, chunked prefill, speculative
rollback, disagg export/import, tp=2 sharding — with zero page leaks,
while a quant-off engine keeps a cache treedef with no scale leaves at
all (bit-identical behavior to the pre-quant engine).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu.models import llama
from k8s_distributed_deeplearning_tpu.ops.pallas_paged_attn import (
    paged_decode_attention)
from k8s_distributed_deeplearning_tpu.serve import (Request, SamplingParams,
                                                    ServeEngine)
from k8s_distributed_deeplearning_tpu.serve import quant
from k8s_distributed_deeplearning_tpu.serve.disagg import (decode_blob,
                                                           encode_blob)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=64)
    model = llama.LlamaLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, cfg


@pytest.fixture(scope="module")
def draft():
    """Independent weights => partial acceptance => spec rollback runs."""
    dcfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=64)
    dmodel = llama.LlamaLM(dcfg)
    dparams = dmodel.init(jax.random.key(7),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    return dmodel, dparams


def _workload(cfg, n, seed=0, p_lo=4, p_hi=17, m_lo=3, m_hi=16):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(p_lo, p_hi))).astype(
                                np.int32) for _ in range(n)]
    max_news = [int(rng.integers(m_lo, m_hi)) for _ in range(n)]
    return prompts, max_news


def _run(model, params, prompts, max_news, **kw):
    kw.setdefault("num_slots", 3)
    eng = ServeEngine(model, params, eos_id=None, **kw)
    reqs = [Request(prompt=p, max_new_tokens=m)
            for p, m in zip(prompts, max_news)]
    outs = {o.request_id: o for o in eng.run(reqs)}
    return eng, [list(outs[r.request_id].tokens) for r in reqs]


def _assert_no_leaks(eng):
    c = eng.pool.counters()
    assert c["pages_used"] == 0, c
    assert eng.pool.reserved == 0


# ------------------------------------------------ weight quant round trip


def test_weight_quant_round_trip_and_leaf_selection(tiny):
    _, params, _ = tiny
    qp, sc = quant.quantize_params(params)
    assert quant.is_quantized((qp, sc))
    assert (jax.tree_util.tree_structure(qp)
            == jax.tree_util.tree_structure(params))
    for (path, q), (_, s), (_, w) in zip(
            jax.tree_util.tree_flatten_with_path(qp)[0],
            jax.tree_util.tree_flatten_with_path(sc)[0],
            jax.tree_util.tree_flatten_with_path(params)[0]):
        name = quant._path_name(path)
        if "kernel" in name and "lm_head" not in name:
            assert q.dtype == jnp.int8, name
            assert s.ndim == w.ndim and s.shape[-1] == w.shape[-1], name
            # Per-channel bound: |w - dq| <= scale/2 everywhere.
            dq = np.asarray(q, np.float32) * np.asarray(s)
            err = np.abs(np.asarray(w, np.float32) - dq)
            assert np.all(err <= np.asarray(s) / 2 + 1e-7), name
        else:
            # Embeddings, norm scales, lm_head: untouched passthrough
            # with the scalar sentinel.
            assert q is w, name
            assert s.ndim == 0 and float(s) == 0.0, name
    # Grid stability: re-quantizing the dequantized params reproduces
    # the identical int8 representation (what disagg/export parity and
    # the tp dequant-at-load path key on).
    dq = quant.dequantize_params(qp, sc)
    qp2, sc2 = quant.quantize_params(dq)
    for a, b in zip(jax.tree.leaves(qp), jax.tree.leaves(qp2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert quant.quantized_nbytes(qp, sc) < quant.params_nbytes(params)


def test_calibration_clips_scales(tiny, tmp_path):
    _, params, _ = tiny
    _, sc = quant.quantize_params(params)
    flat = jax.tree_util.tree_flatten_with_path(sc)[0]
    target = next(quant._path_name(p) for p, s in flat if s.ndim > 0)
    n_ch = next(s.shape[-1] for p, s in flat
                if quant._path_name(p) == target)
    calib = {"weights": {target: [1e-3] * n_ch}}
    path = tmp_path / "calib.json"
    path.write_text(json.dumps(calib))
    loaded = quant.load_calibration(str(path))
    _, sc2 = quant.quantize_params(params, calibration=loaded)
    for (p, a), (_, b) in zip(flat,
                              jax.tree_util.tree_flatten_with_path(sc2)[0]):
        if quant._path_name(p) == target:
            assert np.all(np.asarray(b) <= 1e-3 / 127.0 + 1e-12)
        elif a.ndim > 0:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="calibration"):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"nope": 1}))
        quant.load_calibration(str(bad))


# ------------------------------------------------------- kernel numerics


def _quantize_pool(pool):
    """Per-token-per-head symmetric absmax int8, head_dim folded at 8."""
    pages, bt, kvhd = pool.shape
    hd = 8
    w = pool.reshape(pages, bt, kvhd // hd, hd).astype(np.float32)
    sc = np.max(np.abs(w), axis=-1) / 127.0
    q = np.clip(np.round(w / np.where(sc > 0, sc, 1.0)[..., None]),
                -127, 127).astype(np.int8)
    return q.reshape(pool.shape), sc.astype(np.float32)


@pytest.mark.parametrize("b,sq,h,hkv,pages,bt,nb,ppc,cursors", [
    (2, 1, 4, 2, 16, 8, 4, None, None),     # single-token decode, GQA 2:1
    (3, 5, 4, 4, 32, 16, 3, None, None),    # speculative verify window, MHA
    # several cells a row (a cell is ppc pages of bt tokens): the scale
    # blocks are cut to the same cells as the int8 pages
    (4, 1, 8, 2, 64, 4, 7, 3, (2, 11, 12, 27)),   # tail cell of one page;
    #          first page, a cell's last column, the next's first, last page
    (2, 1, 8, 2, 64, 4, 6, 2, (0, 23)),     # a cursor-0 row beside a full row
    (3, 5, 4, 4, 64, 8, 6, 4, (4, 31, 32)),       # window on a cell's edge
    (2, 16, 8, 2, 64, 8, 5, 2, (15, 39)),   # a chunk width, GQA 4:1
    (3, 1, 4, 4, 32, 16, 3, 1, None),       # one page a cell, MHA
])
def test_kernel_dequant_matches_xla_on_dequantized_pool(
        b, sq, h, hkv, pages, bt, nb, ppc, cursors):
    """The kernel's fused dequant IS the reference dequant: running the
    kernel on (int8 pool, scales) must equal running it on the
    explicitly dequantized fp pool — same f32 multiply, fused into the
    page stream instead of materialized in HBM — and both must equal the
    plain softmax over the dequantized virtual sequence."""
    rng = np.random.default_rng(b * 10 + sq)
    hd = 8
    q = rng.standard_normal((b, sq, h, hd)).astype(np.float32)
    pool_k = rng.standard_normal((pages, bt, hkv * hd)).astype(np.float32)
    pool_v = rng.standard_normal((pages, bt, hkv * hd)).astype(np.float32)
    perm = rng.permutation(np.arange(1, pages))[:b * nb]
    tables = perm.reshape(b, nb).astype(np.int32)
    base = (rng.integers(sq - 1, nb * bt, size=b) if cursors is None
            else np.asarray(cursors))
    if cursors is not None:     # unallocated blocks: the scratch page
        tables[np.arange(nb)[None, :] > (base // bt)[:, None]] = 0
    pos = (base[:, None] - (sq - 1) + np.arange(sq)[None, :]).astype(
        np.int32)
    qk, sk = _quantize_pool(pool_k)
    qv, sv = _quantize_pool(pool_v)
    dk = (qk.reshape(pages, bt, hkv, hd).astype(np.float32)
          * sk[..., None]).reshape(pages, bt, hkv * hd)
    dv = (qv.reshape(pages, bt, hkv, hd).astype(np.float32)
          * sv[..., None]).reshape(pages, bt, hkv * hd)
    out_q = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(qk), jnp.asarray(qv),
        jnp.asarray(tables), jnp.asarray(pos),
        k_scale=jnp.asarray(sk), v_scale=jnp.asarray(sv),
        pages_per_cell=ppc, interpret=True))
    out_ref = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(dk), jnp.asarray(dv),
        jnp.asarray(tables), jnp.asarray(pos), pages_per_cell=ppc,
        interpret=True))
    np.testing.assert_allclose(out_q, out_ref, atol=1e-6, rtol=1e-6)
    k = dk[tables].reshape(b, nb * bt, hkv, hd)
    v = dv[tables].reshape(b, nb * bt, hkv, hd)
    want = np.zeros_like(q)
    for bi, i, qi in np.ndindex(b, sq, h):
        s = (k[bi, :, qi // (h // hkv)] @ q[bi, i, qi]) * hd ** -0.5
        s = np.where(np.arange(nb * bt) <= pos[bi, i], s, -np.inf)
        pr = np.exp(s - s.max())
        want[bi, i, qi] = (pr / pr.sum()) @ v[bi, :, qi // (h // hkv)]
    np.testing.assert_allclose(out_q, want, atol=2e-5, rtol=2e-5)


def test_kernel_scale_validation():
    q = jnp.zeros((2, 1, 4, 8), jnp.float32)
    pk = jnp.zeros((8, 4, 16), jnp.int8)
    sk = jnp.zeros((8, 4, 2), jnp.float32)
    tables = jnp.zeros((2, 3), jnp.int32)
    pos = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match="together"):
        paged_decode_attention(q, pk, pk, tables, pos, k_scale=sk)
    with pytest.raises(ValueError, match="per-token-per-head"):
        paged_decode_attention(q, pk, pk, tables, pos,
                               k_scale=sk[:, :, :1], v_scale=sk)


# ------------------------------------------------------- engine numerics


# The FIXED eval set of the agreement gate. A random-init tiny model has
# argmax near-ties (its top two logits lie a median 0.14 apart, and weight
# quantization moves a logit by up to ~0.07), and ONE flipped near-tie
# changes every later token of that stream: free-running agreement with
# the fp engine reads 124/144 here on a sound path. So the gate is
# cascade-free: each token the ENGINE's int8 path served is scored by the
# fp model GIVEN THE SERVED PREFIX.
_EVAL_SEEDS = (14, 22)


def _gaps_below_fp_best(model, params, prompts, served):
    """How far below the fp model's best logit each served token lies at
    its position, the fp model reading the request's own served prefix
    (one teacher-forced pass a request)."""
    gaps = []
    for prompt, toks in zip(prompts, served):
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
        logits = np.asarray(model.apply({"params": params},
                                        jnp.asarray(seq)[None]))[0]
        rows = logits[len(prompt) - 1:len(seq) - 1]
        gaps.extend(rows.max(axis=-1) - rows[np.arange(len(toks)), toks])
    return np.asarray(gaps)


def test_greedy_agreement_and_bytes_gates(tiny, monkeypatch):
    """The two headline gates in one pass. Agreement: every token served
    under kv+weight int8 on the fixed eval set lies within 0.1 of the fp
    model's best logit given the same prefix, 0.005 in the mean — the path
    reads 0.0066 / 0.00017 here (0.04 / 0.0007 at worst over six seeds:
    the few tokens that are not the fp best are near-ties), and int8
    weights move no logit by more than 0.15
    (test_logit_delta_and_forced_agreement), so a sound path cannot read
    over 0.3. The CONTROL, weight scales off by 2x, reads 2.64 / 0.72 and
    must fail both. Bytes: >= 1.8x fewer bytes per page in the quantized
    pool."""
    model, params, cfg = tiny

    def served_gaps():
        gaps, eng = [], None
        for seed in _EVAL_SEEDS:
            prompts, max_news = _workload(cfg, 8, seed=seed)
            eng, q = _run(model, params, prompts, max_news,
                          kv_quant="int8", weight_quant="int8")
            gaps.append(_gaps_below_fp_best(model, params, prompts, q))
        return np.concatenate(gaps), eng

    gaps, eng = served_gaps()
    assert gaps.size == 144
    assert gaps.max() < 0.1 and gaps.mean() < 0.005, (gaps.max(), gaps.mean())
    fp_page = eng._block_nbytes(eng.page_tokens, kv_quant=None)
    q_page = eng._block_nbytes(eng.page_tokens)
    assert fp_page / q_page >= 1.8, (fp_page, q_page)
    summ = eng.stats.summary()
    assert summ["kv_quant"] == "int8"
    assert summ["weight_quant"] == "int8"
    assert summ["kv_quant_bytes_saved"] > 0
    assert summ["weight_quant_bytes_saved"] > 0
    _assert_no_leaks(eng)

    sound = quant.quantize_params

    def scales_off_by_two(tree):
        qp, sc = sound(tree)
        return qp, jax.tree.map(lambda s: s * 2, sc)

    monkeypatch.setattr(quant, "quantize_params", scales_off_by_two)
    bad, _ = served_gaps()
    assert bad.max() > 1.0 and bad.mean() > 0.2, (bad.max(), bad.mean())


def test_logit_delta_and_forced_agreement(tiny):
    """Cascade-free weight-quant quality: teacher-forced full-sequence
    logits under quantized weights vs fp — bounded max-abs-delta and
    high per-position argmax agreement even on the near-tie-riddled
    random model (measured: delta ~0.07 on logit absmax ~2.7, forced
    agreement ~98.3%; gates at 2x / 95% leave noise headroom)."""
    model, params, cfg = tiny
    dq = quant.dequantize_params(*quant.quantize_params(params))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                    size=(16, 48)).astype(np.int32))
    lf = np.asarray(model.apply({"params": params}, toks))
    lq = np.asarray(model.apply({"params": dq}, toks))
    assert np.max(np.abs(lf - lq)) < 0.15
    forced = (np.argmax(lf, -1) == np.argmax(lq, -1)).mean()
    assert forced >= 0.95, forced


def test_quant_off_cache_has_no_scale_leaves(tiny):
    """kv_quant=None must keep the cache treedef IDENTICAL to the
    pre-quant engine: fp arenas, no *_scale siblings anywhere — the
    quant-off bit-identity guarantee is structural, not numeric."""
    model, params, cfg = tiny
    eng = ServeEngine(model, params, num_slots=2, eos_id=None)
    names = [path[-1].key for path, _ in
             jax.tree_util.tree_flatten_with_path(eng._cache)[0]]
    assert not any(n.endswith("_scale") for n in names)
    assert all(l.dtype == cfg.dtype
               for l in jax.tree.leaves(eng._cache))
    assert eng.stats.summary()["kv_quant"] is None

    qeng = ServeEngine(model, params, num_slots=2, eos_id=None,
                       kv_quant="int8")
    qnames = sorted(path[-1].key for path, _ in
                    jax.tree_util.tree_flatten_with_path(qeng._cache)[0])
    assert [n for n in qnames if n.endswith("_scale")], qnames
    for path, leaf in jax.tree_util.tree_flatten_with_path(qeng._cache)[0]:
        if path[-1].key.endswith("_scale"):
            assert leaf.dtype == jnp.float32
            assert leaf.shape[-1] == cfg.resolved_kv_heads
        else:
            assert leaf.dtype == jnp.int8


def test_ctor_rejects_unknown_modes(tiny):
    model, params, _ = tiny
    with pytest.raises(ValueError, match="kv_quant"):
        ServeEngine(model, params, num_slots=2, kv_quant="fp8")
    with pytest.raises(ValueError, match="weight_quant"):
        ServeEngine(model, params, num_slots=2, weight_quant="int4")


# ------------------------------------------------------------ composition


def test_spec_prefix_chunked_composition_under_quant(tiny, draft):
    """Speculative decoding is bit-exact RELATIVE to its own target
    numerics, so under kv_quant the spec engine must reproduce the
    non-spec quant engine's stream token for token — across prefix-trie
    hits (second pass) and chunked prefill, with zero leaks."""
    model, params, cfg = tiny
    dmodel, dparams = draft
    rng = np.random.default_rng(3)
    shared = rng.integers(1, cfg.vocab_size, size=16).astype(np.int32)
    prompts = [np.concatenate(
        [shared, rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (5, 9, 21)]
    max_news = [8, 6, 10]
    kw = dict(kv_quant="int8", prefix_cache_mb=1.0,
              prefill_chunk_tokens=32)

    def both_passes(**extra):
        eng = ServeEngine(model, params, num_slots=2, eos_id=None,
                          **kw, **extra)
        out = []
        for tag in ("a", "b"):
            reqs = [Request(prompt=p, max_new_tokens=m,
                            request_id=f"{tag}{i}")
                    for i, (p, m) in enumerate(zip(prompts, max_news))]
            outs = {o.request_id: o for o in eng.run(reqs)}
            out.append([list(outs[r.request_id].tokens) for r in reqs])
        return eng, out

    plain_eng, plain = both_passes()
    spec_eng, spec = both_passes(draft_model=dmodel, draft_params=dparams,
                                 spec_k=3)
    assert spec == plain, "spec diverged from non-spec under kv_quant"
    # Trie reuse actually happened on the second pass, under quant.
    assert plain_eng.stats.prefix_hits > 0
    # The independent random draft rarely agrees with the target, which
    # is the point: near-total rejection exercises the rollback path
    # (kv_len AND scale pages rewound) on every verify window.
    assert spec_eng.stats.spec_proposed_tokens > 0
    for eng in (plain_eng, spec_eng):
        while eng.prefix_cache.evict_lru_unpinned():
            pass
        _assert_no_leaks(eng)


def test_disagg_export_import_under_quant(tiny):
    """Prefill-role export -> wire codec -> decode-role import, both
    int8: pages and scale siblings ship by value, adoption is
    bit-identical to the unmigrated quant engine, and the blob's
    kv_quant tag gates adoption (fp pool must refuse int8 pages)."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 3, seed=9, m_lo=6, m_hi=12)
    _, ref = _run(model, params, prompts, max_news, kv_quant="int8")

    src = ServeEngine(model, params, num_slots=2, eos_id=None,
                      kv_quant="int8", prefill_only=True)
    for i, (p, m) in enumerate(zip(prompts, max_news)):
        src.submit(Request(prompt=list(p), max_new_tokens=m,
                           request_id=f"q{i}"))
    blobs = []
    while src.busy() or src.take_exports():
        src.step()
        blobs.extend(src.take_exports())
        if len(blobs) == len(prompts):
            break
    assert len(blobs) == len(prompts)
    assert all(b["kv_quant"] == "int8" for b in blobs)
    _assert_no_leaks(src)

    fp_dst = ServeEngine(model, params, num_slots=2, eos_id=None)
    assert not fp_dst.can_import(blobs[0])
    with pytest.raises(ValueError, match="kv_quant"):
        fp_dst.import_request_kv(blobs[0])

    dst = ServeEngine(model, params, num_slots=3, eos_id=None,
                      kv_quant="int8")
    outs = {}
    for b in blobs:
        rt = decode_blob(json.loads(json.dumps(encode_blob(b))))
        # int8 pages and f32 scales survive the wire bit-for-bit.
        for a, w in zip(b["pages"], rt["pages"]):
            assert a.dtype == w.dtype
            np.testing.assert_array_equal(a, w)
        assert dst.can_import(rt)
        dst.import_request_kv(rt)
    assert dst.pool.owners_summary()["imported"] > 0
    while dst.busy():
        for o in dst.step():
            outs[o.request_id] = list(o.tokens)
    assert [outs[f"q{i}"] for i in range(len(prompts))] == ref
    _assert_no_leaks(dst)


def test_tp2_parity_under_quant():
    """tp=2 with int8 KV: the sharded scale leaves (kv-head lane dim
    split over the mesh) must reproduce the tp=0 quant engine's token
    stream exactly; weight_quant under tp loads fp-at-grid-points, so
    it must match the tp=0 quantized-weights stream too."""
    cfg = llama.config_tiny(max_seq_len=128, dtype=jnp.float32,
                            scan_layers=False)
    model = llama.LlamaLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    prompts, max_news = _workload(cfg, 4, seed=5, m_lo=5, m_hi=10)
    kw = dict(kv_quant="int8", weight_quant="int8", min_bucket=8)
    eng0, t0 = _run(model, params, prompts, max_news, num_slots=2, **kw)
    eng2, t2 = _run(model, params, prompts, max_news, num_slots=2, tp=2,
                    **kw)
    assert t2 == t0, "tp=2 diverged from tp=0 under int8 quant"
    for leaf in jax.tree.leaves(eng2._cache):
        assert leaf.dtype in (jnp.int8, jnp.float32)
    _assert_no_leaks(eng0)
    _assert_no_leaks(eng2)


# ------------------------------------------------- train-loop calibration


def test_train_loop_calibration_dump_round_trip(tiny, tmp_path):
    """The fit(quant_calib=...) dump writes the exact envelope
    quantize_params consumes, keyed by the SAME path names its lookup
    uses — a dump of the true per-channel absmax must reproduce the
    uncalibrated quantization bit-for-bit (the clip is a no-op at the
    natural range), proving the two sides agree on both format and
    naming."""
    from k8s_distributed_deeplearning_tpu.train import loop

    _, params, _ = tiny
    path = tmp_path / "calib.json"
    n = loop.dump_quant_calibration(params, str(path))
    calib = quant.load_calibration(str(path))
    assert n == len(calib["weights"]) > 0
    q1, s1 = quant.quantize_params(params)
    q2, s2 = quant.quantize_params(params, calibration=calib)
    for a, b in zip(jax.tree.leaves(q1), jax.tree.leaves(q2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Every dumped key names a kernel the quantizer selects, and every
    # selected kernel got dumped (no silent naming drift).
    selected = {quant._path_name(p) for p, leaf
                in jax.tree_util.tree_flatten_with_path(params)[0]
                if quant._quantizable(p, leaf)}
    assert set(calib["weights"]) == selected


# --------------------------------------------------- launch render/validate


def _replica_docs(**kw):
    from k8s_distributed_deeplearning_tpu.config import JobConfig
    from k8s_distributed_deeplearning_tpu.launch import render
    return render.render_all(JobConfig(serve_replicas=2, **kw))


def _replica_container(docs):
    rep = next(d for d in docs if d["kind"] == "Job" and
               (d["metadata"].get("labels") or {}).get("role")
               == "serve-replica")
    return rep["spec"]["template"]["spec"]["containers"][0]


def test_launch_renders_quant_env_and_validates():
    """JobConfig.kv_quant/weight_quant ride into the replica manifest as
    TPUJOB_KV_QUANT/TPUJOB_WEIGHT_QUANT (the CLI reads them as flag
    defaults), a coherent manifest validates clean, and absence renders
    no env at all."""
    from k8s_distributed_deeplearning_tpu.launch import validate

    docs = _replica_docs(kv_quant="int8", weight_quant="int8")
    assert validate.validate(docs) == []
    env = {e["name"]: e.get("value") for e in _replica_container(docs)["env"]}
    assert env["TPUJOB_KV_QUANT"] == "int8"
    assert env["TPUJOB_WEIGHT_QUANT"] == "int8"
    names = {e["name"] for e in _replica_container(_replica_docs())["env"]}
    assert "TPUJOB_KV_QUANT" not in names
    assert "TPUJOB_WEIGHT_QUANT" not in names


def test_launch_validate_catches_quant_mode_typo_and_tp_split():
    """A typo'd mode dies in the ServeEngine ctor after a TPU slice was
    scheduled; with tp the scale leaves' per-KV-head lane dim must split
    over the mesh — both caught offline."""
    from k8s_distributed_deeplearning_tpu.launch import validate

    errs = validate.validate(_replica_docs(kv_quant="fp8"))
    assert any("TPUJOB_KV_QUANT" in e and "not a known quant mode" in e
               for e in errs)
    errs = validate.validate(_replica_docs(weight_quant="int4"))
    assert any("TPUJOB_WEIGHT_QUANT" in e for e in errs)
    # tiny preset: num_kv_heads=2; tp=4 can't shard the scale lane dim.
    errs = validate.validate(_replica_docs(kv_quant="int8", serve_tp=4))
    assert any("scale" in e and "num_kv_heads" in e for e in errs)


def test_launch_quant_pool_math_replaces_fp_estimate():
    """Under TPUJOB_KV_QUANT the byte-fit check must use the QUANTIZED
    page cost: a memory limit the fp estimate would reject (tiny preset
    defaults: fp pool ~2 MiB, int8 pool ~0.63 MiB) validates clean with
    int8 KV, while a limit below even the quantized pool still fails
    with the quant-specific error."""
    from k8s_distributed_deeplearning_tpu.launch import validate

    docs = _replica_docs(kv_quant="int8")
    c = _replica_container(docs)
    c.setdefault("resources", {}).setdefault("limits", {})["memory"] = "1Mi"
    assert validate.validate(docs) == []

    docs = _replica_docs()                     # fp pool: 1Mi must fail
    c = _replica_container(docs)
    c.setdefault("resources", {}).setdefault("limits", {})["memory"] = "1Mi"
    errs = validate.validate(docs)
    assert any("KV pool" in e and "exceeds the container memory limit"
               in e for e in errs)

    docs = _replica_docs(kv_quant="int8")
    c = _replica_container(docs)
    c.setdefault("resources", {}).setdefault("limits", {})["memory"] = \
        "512Ki"
    errs = validate.validate(docs)
    assert any("quantized per-shard KV pool" in e for e in errs)


def test_launch_cli_quant_flags():
    """The launch CLI plumbs --kv-quant/--weight-quant into JobConfig:
    render emits the env pair, validate accepts the combo, and a bad mode
    dies at the argparse choices gate before any rendering happens."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = [sys.executable, "-m", "k8s_distributed_deeplearning_tpu.launch"]

    out = subprocess.run(
        base + ["render", "--serve-replicas", "2",
                "--kv-quant", "int8", "--weight-quant", "int8"],
        capture_output=True, text=True, cwd=repo)
    assert out.returncode == 0, out.stderr
    assert "TPUJOB_KV_QUANT" in out.stdout
    assert "TPUJOB_WEIGHT_QUANT" in out.stdout

    out = subprocess.run(
        base + ["validate", "--serve-replicas", "2",
                "--kv-quant", "int8", "--weight-quant", "int8"],
        capture_output=True, text=True, cwd=repo)
    assert out.returncode == 0, out.stderr
    assert "offline validation: OK" in out.stdout

    out = subprocess.run(
        base + ["validate", "--serve-replicas", "2", "--kv-quant", "fp8"],
        capture_output=True, text=True, cwd=repo)
    assert out.returncode != 0
    assert "invalid choice" in out.stderr
