"""Plain reference of the ``window-moe`` family (K-EXAONE layout: sliding-window
attention layers beside global ones with no positions, post-norm blocks, a
leading dense layer, then sparse-expert layers with a shared expert, an untied
head): one full causal forward pass per sequence in float32 at ``highest``
matmul precision — no cache, no ring, no paging, no batching, no kernel, the
held experts by a plain scan with no token dropped, nothing imported from the
program.

The equations, for layer l with input x [T, d] (``configs/k-exaone-236b-a23b-
ep8-d5.json`` names the source; PERF.md section 4):

    x <- x + RMSNorm_post_attn(Attn_l(x))      (no norm on the input)
    x <- x + RMSNorm_post_ffn(FF_l(x))         eps = rms_norm_eps
    Attn:  q, k, v = W_q x, W_k x, W_v x (H x hd, KV x hd, KV x hd); an RMSNorm
           with a learned gain over each query head's and each key head's hd
           lanes; RoPE (theta = rope_theta, all hd lanes) in a
           ``sliding_attention`` layer only; softmax at hd^-1/2, each group of
           H / KV query heads on one KV head; query i sees key j iff j <= i,
           and in a sliding layer also j > i - sliding_window;   W_o
    FF, mlp_layer_types[l] == "dense":  W_2 (silu(W_1 x) * W_3 x)
    FF, "sparse":  s = sigmoid(W_r x) in f32 over router_outputs experts;
           T = top-k of s + b (b: the selection bias, for the choice only);
           g_e = routed_scaling_factor s_e / sum_T s;
           sum_{e in T, e held} g_e SwiGLU_e(x) + SwiGLU_shared(x)
    after the last layer: RMSNorm, then logits = W_head x

ONE CHIP'S SHARE, as ``reference_latent_moe``: the router keeps all its
outputs, the top-k and the normalisation over all k chosen; only experts
``[expert_offset, expert_offset + num_experts)`` are computed and that partial
sum (plus the shared expert, where ``shared_expert``) is the feed-forward's
output — the post-norm takes it as it is, in the program and here alike.

It runs after the engine's weights are freed and never holds the model:
weights are made again from the seed ONE LAYER AT A TIME (``weights.leaf``,
the served type's rounding, then float32), and every sampled sequence goes
through that layer before the next is made. Sequences are padded to ONE
multiple of ``PAD``: every mixer is causal, so a pad never reaches an earlier
position. Attention runs in blocks of ``Q_BLOCK`` query rows (one traced body
for all): a sliding layer's block reads only the ``Q_BLOCK + window`` keys of
its band, a global layer's every key, so a 17 k-token sequence fits.

``score_served`` is ``reference_conv_moe``'s: per served token the gap by
which its logit lies below the reference's best at its position, held to the
limits the configuration's ``served_gap_limits`` names. With ``precision``
below f32 the same pass is the CONTROL (the router stays in float32 there too:
the program keeps it so). ``fault`` plants a window fault in the reference
standing in for the program: ``window_full`` (the sliding layers attend the
whole causal context), ``rope_global`` (the global layers take RoPE).
"""
from __future__ import annotations

import functools
import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import lowp, weights

PAD = 512
Q_BLOCK = 128
HI = jax.lax.Precision.HIGHEST
FAULTS = ("window_full", "rope_global")


def layer_kinds(cfg: dict) -> list[tuple[str, str]]:
    """(mixer, feed-forward) of the layers that are run: the first
    ``num_hidden_layers`` of the published ``layer_types`` and
    ``mlp_layer_types``."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n]))


def attention_shapes(cfg: dict) -> dict:
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return {"attn/q_proj/kernel": (d, h, hd), "attn/k_proj/kernel": (d, kv, hd),
            "attn/v_proj/kernel": (d, kv, hd), "attn/q_norm/scale": (hd,),
            "attn/k_norm/scale": (hd,), "attn/o_proj/kernel": (h, hd, d),
            "attn_post_norm/scale": (d,), "mlp_post_norm/scale": (d,)}


def mlp_shapes(cfg: dict, layer: int) -> dict:
    d = cfg["hidden_size"]
    if layer_kinds(cfg)[layer][1] == "dense":
        f = cfg["intermediate_size"]
        return {"mlp/gate_proj/kernel": (d, f), "mlp/up_proj/kernel": (d, f),
                "mlp/down_proj/kernel": (f, d)}
    f, e, n = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["router_outputs"]
    fs = f * cfg["num_shared_experts"]
    return {"mlp/router": (d, n), "mlp/router_bias": (n,),
            "mlp/w_gate": (e, d, f), "mlp/w_up": (e, d, f), "mlp/w_down": (e, f, d),
            "mlp/shared/gate_proj/kernel": (d, fs), "mlp/shared/up_proj/kernel": (d, fs),
            "mlp/shared/down_proj/kernel": (fs, d)}


def layer_shapes(cfg: dict, layer: int) -> dict:
    return {**attention_shapes(cfg), **mlp_shapes(cfg, layer)}


def outer_shapes(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"transformer/tok_embed/embedding": (v, d),
            "transformer/final_norm/scale": (d,),
            "head/lm_head/kernel": (d, v)}


def _served_dtype(cfg: dict):
    return jnp.dtype(cfg.get("torch_dtype", "bfloat16"))


@functools.partial(jax.jit, static_argnames=("leaves", "dtype"))
def _make(seed_u32, crcs, leaves, dtype):
    """``weights.leaf`` for every ``(name, shape, kind)`` of *leaves*, with the
    names' checksums as a TRACED operand (``crcs``, in the same order): the
    layers are two kinds of shapes (dense, sparse), so two programs make all
    the weights. Held to ``weights.leaf`` value for value by
    ``tests/perfbench/test_perfbench_window_moe.py``."""
    out = {}
    for i, (name, shape, kind) in enumerate(leaves):
        key = jax.random.fold_in(jax.random.key(seed_u32), crcs[i])
        x = jax.random.normal(key, tuple(shape), jnp.float32) * weights.STD
        out[name] = ((1.0 + x) if kind == "scale" else x).astype(dtype).astype(jnp.float32)
    return out


def _made(cfg: dict, seed: int, prefix: str, shapes: dict) -> dict:
    leaves = tuple((n, tuple(shp), weights._kind(prefix + n)) for n, shp in shapes.items())
    crcs = np.array([zlib.crc32((prefix + n).encode()) & 0x7FFFFFFF for n in shapes], np.uint32)
    return _make(weights.seed_operand(seed), crcs, leaves, _served_dtype(cfg))


def make_layer(cfg: dict, seed: int, layer: int) -> dict:
    return _made(cfg, seed, f"transformer/block_{layer}/", layer_shapes(cfg, layer))


def make_outer(cfg: dict, seed: int) -> dict:
    return _made(cfg, seed, "", outer_shapes(cfg))


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x: [T, H, D]; rotate the interleaved pairs (x[2i], x[2i+1]) by
    position * theta^(-2i/D)."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def attention(x, w, mm, *, window: int | None, rope: bool, theta: float, eps: float):
    """Grouped-query attention with per-head q/k RMSNorm: x [T, d] -> [T, d].
    *window*: a sliding layer's band (None: every earlier key)."""
    t = x.shape[0]
    q = _rms_norm(mm("td,dhk->thk", x, w["attn/q_proj/kernel"]), w["attn/q_norm/scale"], eps)
    k = _rms_norm(mm("td,dhk->thk", x, w["attn/k_proj/kernel"]), w["attn/k_norm/scale"], eps)
    v = mm("td,dhk->thk", x, w["attn/v_proj/kernel"])
    if rope:
        q, k = _rope(q, theta), _rope(k, theta)
    kv, hd = k.shape[1], k.shape[2]
    q = q.reshape(t, kv, -1, hd)                                     # [T, KV, G, hd]
    scale = hd ** -0.5
    if window is None:
        def q_block(a):
            qb = jax.lax.dynamic_slice_in_dim(q, a, Q_BLOCK, 0)
            sc = mm("qkgd,tkd->kgqt", qb, k) * scale
            row = a + jnp.arange(Q_BLOCK)[:, None]
            sc = jnp.where(jnp.arange(t)[None, :] <= row, sc, -jnp.inf)
            return mm("kgqt,tkd->qkgd", jax.nn.softmax(sc, axis=-1), v)
    else:
        # keys padded by `window` zeros in front: block a's band is
        # kp[a : a + Q_BLOCK + window], key s there at position a - window + s
        kp = jnp.pad(k, ((window, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((window, 0), (0, 0), (0, 0)))

        def q_block(a):
            qb = jax.lax.dynamic_slice_in_dim(q, a, Q_BLOCK, 0)
            kb = jax.lax.dynamic_slice_in_dim(kp, a, Q_BLOCK + window, 0)
            vb = jax.lax.dynamic_slice_in_dim(vp, a, Q_BLOCK + window, 0)
            sc = mm("qkgd,skd->kgqs", qb, kb) * scale
            i = jnp.arange(Q_BLOCK)[:, None]
            s = jnp.arange(Q_BLOCK + window)[None, :]
            ok = (s > i) & (s <= i + window) & (a - window + s >= 0)
            sc = jnp.where(ok, sc, -jnp.inf)
            return mm("kgqs,skd->qkgd", jax.nn.softmax(sc, axis=-1), vb)
    o = jax.lax.map(q_block, jnp.arange(0, t, Q_BLOCK)).reshape(t, -1, hd)
    return mm("qhk,hkd->qd", o, w["attn/o_proj/kernel"])


def router(h, w_r, bias, k: int, scaling: float):
    """-> (chosen [T, k] expert ids, gates [T, k], margin [T]: by how much
    the k-th selection score leads the next one); float32 throughout."""
    s = jax.nn.sigmoid(jnp.dot(h, w_r, precision=HI))
    top, chosen = jax.lax.top_k(s + bias, k + 1)
    chosen = chosen[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=1)
    return (chosen, scaling * picked / jnp.sum(picked, axis=1, keepdims=True),
            top[:, k - 1] - top[:, k])


def _swiglu(x, wg, wu, wd, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, wg)) * mm("td,df->tf", x, wu), wd)


def feed_forward(x, w, mm, *, top_k: int, scaling: float, offset: int, shared: bool,
                 served_dtype):
    """One layer's feed-forward on x [T, d] -> ([T, d], router flips, router
    margin [T]; infinite in a dense layer). In a sparse layer: the held
    experts' part (experts ``offset + [0, held)`` of the router's outputs),
    plus the shared expert where *shared*."""
    t = x.shape[0]
    if "mlp/router" not in w:
        return (_swiglu(x, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"],
                        w["mlp/down_proj/kernel"], mm),
                jnp.zeros((), jnp.int32), jnp.full((t,), jnp.inf))
    chosen, gates, margin = router(x, w["mlp/router"], w["mlp/router_bias"], top_k, scaling)
    rounded, _, _ = router(x.astype(served_dtype).astype(jnp.float32), w["mlp/router"],
                           w["mlp/router_bias"], top_k, scaling)
    flips = jnp.sum(jnp.any(jnp.sort(chosen, 1) != jnp.sort(rounded, 1), axis=1))

    def one_expert(y, ew):                      # the held experts, one by one
        e, wg, wu, wd = ew
        g = jnp.sum(jnp.where(chosen == offset + e, gates, 0.0), axis=1)    # [T]
        return y + g[:, None] * _swiglu(x, wg, wu, wd, mm), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (jnp.arange(w["mlp/w_gate"].shape[0]), w["mlp/w_gate"],
                         w["mlp/w_up"], w["mlp/w_down"]))
    if shared:
        y = y + _swiglu(x, w["mlp/shared/gate_proj/kernel"], w["mlp/shared/up_proj/kernel"],
                        w["mlp/shared/down_proj/kernel"], mm)
    return y, flips.astype(jnp.int32), margin


def _cfg_key(cfg: dict) -> str:
    """What ``layer_forward`` reads of the configuration beside the weights'
    own shapes, as one hashable (JSON) string for ``jax.jit``."""
    return json.dumps({"rope_theta": float(cfg["rope_parameters"]["rope_theta"]),
                       "rms_norm_eps": float(cfg["rms_norm_eps"]),
                       "num_experts_per_tok": int(cfg["num_experts_per_tok"]),
                       "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
                       "sliding_window": int(cfg["sliding_window"]),
                       "expert_offset": int(cfg.get("expert_offset", 0)),
                       "shared_expert": bool(cfg.get("shared_expert", True)),
                       "torch_dtype": str(_served_dtype(cfg))}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("key", "sliding", "precision", "fault"))
def layer_forward(x, w, *, key: str, sliding: bool, precision: str = "f32", fault=None):
    """x: [T, d] f32 -> ([T, d], router flips in this layer, the router's
    margin at each position — infinite in a dense layer). *sliding*: the
    layer's mixer is a ``sliding_attention`` one."""
    cfg = json.loads(key)
    mm = functools.partial(lowp.einsum, precision=precision)
    eps = cfg["rms_norm_eps"]
    window = cfg["sliding_window"] if sliding and fault != "window_full" else None
    a = attention(x, w, mm, window=window, rope=sliding or fault == "rope_global",
                  theta=cfg["rope_theta"], eps=eps)
    x = x + _rms_norm(a, w["attn_post_norm/scale"], eps)
    y, flips, margin = feed_forward(
        x, w, mm, top_k=cfg["num_experts_per_tok"], scaling=cfg["routed_scaling_factor"],
        offset=cfg["expert_offset"], shared=cfg["shared_expert"],
        served_dtype=jnp.dtype(cfg["torch_dtype"]))
    return x + _rms_norm(y, w["mlp_post_norm/scale"], eps), flips, margin


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x_rows, scale, lm_head, *, eps: float, precision: str):
    return lowp.einsum("td,dv->tv", _rms_norm(x_rows, scale, eps), lm_head, precision)


def forward_logits(cfg: dict, seed: int, seqs: list[np.ndarray], rows: list[np.ndarray],
                   precision: str = "f32", fault=None
                   ) -> tuple[list[np.ndarray], list[np.ndarray], int]:
    """Logits [len(rows_i), V] at positions ``rows[i]`` of each sequence; the
    smallest router margin over the expert layers at those positions; and the
    count of router flips over every position computed (the pads too)."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    key = _cfg_key(cfg)
    flips = 0
    with jax.default_matmul_precision("highest"):
        outer = make_outer(cfg, seed)
        emb = outer["transformer/tok_embed/embedding"]
        # one padded length for all: one compiled layer of each kind
        t = -(-max(len(s) for s in seqs) // PAD) * PAD
        xs = []
        for s in seqs:
            padded = np.zeros(t, np.int32)
            padded[:len(s)] = s
            xs.append(emb[jnp.asarray(padded)])
        del emb
        margins = [np.full(t, np.inf, np.float32) for _ in seqs]
        for l, (mixer, _) in enumerate(layer_kinds(cfg)):
            w = make_layer(cfg, seed, l)
            done = [layer_forward(x, w, key=key, sliding=mixer == "sliding_attention",
                                  precision=precision, fault=fault) for x in xs]
            xs = [x for x, _, _ in done]
            flips += sum(int(f) for _, f, _ in done)
            margins = [np.minimum(m, np.asarray(mg)) for m, (_, _, mg) in zip(margins, done)]
            del w, done
        out = []
        for x, r in zip(xs, rows):
            n = -(-len(r) // 256) * 256
            idx = np.zeros(n, np.int32)
            idx[:len(r)] = r
            lg = _head(x[jnp.asarray(idx)], outer["transformer/final_norm/scale"],
                       outer["head/lm_head/kernel"], eps=float(cfg["rms_norm_eps"]),
                       precision=precision)
            out.append(np.asarray(lg[:len(r)]))
    return out, [m[r] for m, r in zip(margins, rows)], flips


def decided_margin(cfg: dict) -> float:
    """A routing choice counts as DECIDED where the k-th selection score
    leads the next by more than one step of the served type at 1 (bfloat16:
    2^-7; the scores are sigmoids in (0, 1)): a choice decided by less does
    not survive the expert layers computed in that type, whoever computes
    them."""
    return float(jnp.finfo(_served_dtype(cfg)).eps)


def score_served(cfg: dict, seed: int, sample: list[dict], *,
                 precision: str = "f32", fault=None) -> dict:
    """*sample*: ``[{"prompt": int array, "tokens": served token ids}]`` — per
    served token the gap by which its logit lies below the reference's best
    at its position, as three numbers; those the configuration's
    ``served_gap_limits`` names are each held to their limit there, the
    others are printed in the notes only:

    - ``all_max``: the widest gap over EVERY served token — a wrong token
      anywhere (a slot's ring, a page) reads a logit's distance from the best;
    - ``all_mean``: the mean gap over every served token — what precision
      does to all of them at once;
    - ``decided_max``: the widest gap over the tokens whose position's
      routing THIS REFERENCE finds decided (:func:`decided_margin`, in every
      expert layer, from its own float32 scores alone; the control and the
      faults are held to the same positions).

    ``serve_window.correctness`` compares the one key ``logit_gap_max``: the
    LARGEST of the named gaps as a share of its limit, so the cell's limit on
    it is 1 and a run is correct only inside all of them. NaN (never correct)
    where ``decided_max`` is named and no sampled position is decided.
    ``fault="alter"`` alters one served token in eight before scoring; a fault
    of :data:`FAULTS` puts the faulty reference's own best tokens in the
    program's place, as the control does at its precision."""
    seqs, rows, served = [], [], []
    for i, s in enumerate(sample):
        toks = np.asarray(s["tokens"], np.int32)
        if fault == "alter":
            toks = toks.copy()
            toks[i % 8::8] = (toks[i % 8::8] + 1) % cfg["vocab_size"]
        p = np.asarray(s["prompt"], np.int32)
        seqs.append(np.concatenate([p, toks[:-1]]))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(toks)))
        served.append(toks)
    ref, margins, router_flips = forward_logits(cfg, seed, seqs, rows, "f32")
    judged = served
    if precision != "f32" or fault in FAULTS:
        low, _, _ = forward_logits(cfg, seed, seqs, rows, precision,
                                   fault=fault if fault in FAULTS else None)
        judged = [lg.argmax(-1) for lg in low]
    gaps, flips, n = [], 0, 0
    for lg, tok in zip(ref, judged):
        gaps.append(lg.max(-1) - lg[np.arange(len(tok)), tok])
        flips += int((lg.argmax(-1) != tok).sum())
        n += len(tok)
    allg, allm = np.concatenate(gaps), np.concatenate(margins)
    step = decided_margin(cfg)
    decided = allm > step
    limits = cfg["served_gap_limits"]
    parts = {"all_max": float(allg.max()), "all_mean": float(allg.mean()),
             "decided_max": float(allg[decided].max()) if decided.any() else float("nan")}
    shares = {k: parts[k] / limits[k] for k in limits}
    binds = max(shares, key=lambda k: shares[k])
    # a named gap with nothing to measure (no decided position) is never correct
    worst = float("nan") if np.isnan(list(shares.values())).any() else shares[binds]
    by_margin = [[f * step, int((allm > f * step).sum()),
                  float(allg[allm > f * step].max(initial=0.0))]
                 for f in (0.0625, 0.125, 0.25, 0.5, 1.0, 2.0)]
    sparse = sum(1 for _, f in layer_kinds(cfg) if f == "sparse")
    return {"logit_gap_max": worst,
            "gaps": parts, "gap_limits": dict(limits), "gap_shares": shares, "binds": binds,
            "logit_gap_max_all": parts["all_max"], "logit_gap_mean": parts["all_mean"],
            "tokens": n, "tokens_decided": int(decided.sum()),
            "not_reference_best": flips,
            "logit_std": float(np.mean([np.std(lg, axis=-1).mean() for lg in ref])),
            "per_request_max": [float(g[m > step].max(initial=0.0))
                                for g, m in zip(gaps, margins)],
            "per_request_max_all": [float(g.max()) for g in gaps],
            "per_request_mean": [float(g.mean()) for g in gaps],
            "by_margin": by_margin, "router_flips": router_flips,
            "router_choices": len(seqs) * (-(-max(map(len, seqs)) // PAD) * PAD) * sparse}
