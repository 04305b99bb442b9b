"""Plain reference of the ``conv-moe`` family (LFM2-MoE layout: gated short
convolutions beside grouped-query attention, leading dense layers, then
sparse-expert layers with no shared expert, a head tied to the embedding):
one full causal forward pass per sequence in float32 at ``highest`` matmul
precision — no cache, no state carried, no paging, no batching, no kernel,
the experts by a plain scan over all of them with no token dropped, nothing
imported from the program.

The equations, for layer l with input x [T, d] (``configs/lfm2-8b-a1b-d14.json``
names the source; PERF.md section 4):

    h = x + Mixer_l(RMSNorm(x));   y = h + FF_l(RMSNorm(h));   eps = norm_eps
    conv:  [B | C | u] = W_in x^ (d -> 3 d, three equal parts in this order)
           z = B * u;   v[t, c] = sum_{j < L} w[j, c] z[t - (L - 1) + j, c]
           (depthwise, causal, zeros before the sequence, no bias, no
           activation);   out = W_out (C * v)
    full_attention:  q, k, v = W_q x^, W_k x^, W_v x^ (H x hd, KV x hd, KV x hd);
           RMSNorm with a learned gain over each query head's and each key
           head's hd lanes; RoPE over all hd lanes (theta = rope_theta); causal
           softmax at hd^-1/2, each group of H / KV query heads on one KV
           head;   W_o
    FF, l <  num_dense_layers:  W_2 (silu(W_1 h^) * W_3 h^), intermediate_size
    FF, l >= num_dense_layers:  s = sigmoid(W_r h^) in f32;  T = the
           num_experts_per_tok largest of s + b (b: the expert bias, for the
           choice only);  g_e = routed_scaling_factor s_e / (sum_T s + 1e-6);
           sum_{e in T} g_e W_2^e (silu(W_1^e h^) * W_3^e h^)
    after the last layer: RMSNorm, then logits = x E^T (E: the embedding)

Layer l's mixer is ``layer_types[l]``; the first ``num_hidden_layers`` of the
published list are run. RoPE rotates interleaved pairs (x[2i], x[2i+1]) as
the program does — the source rotates the two halves: the same function up
to a fixed permutation of each head's lanes (a departure the configuration's
file lists).

It runs after the engine's weights are freed and never holds the model:
weights are made again from the seed ONE LAYER AT A TIME (``weights.leaf``,
the served type's rounding, then float32), and every sampled sequence goes
through that layer before the next is made. Sequences are padded to ONE
multiple of ``PAD``: causal mixers never let a pad reach an earlier position.

``score_served`` has ``reference_mistral``'s contract — per served token, how
far its logit lies below the reference's best at that position — held to
SEVERAL limits at once (``served_gap_limits`` in the configuration's file
names which), because with random weights the 4th and 5th of 32 selection
scores tie at many positions and one swap moves a position's logits by up to
a logit's spread, at any precision. With ``precision`` below f32 the same pass is the
CONTROL (the router stays in float32 there too: the program keeps it so).
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
Q_BLOCK = 512
HI = jax.lax.Precision.HIGHEST


def layer_types(cfg: dict) -> list[str]:
    """The mixers of the layers that are run: the first ``num_hidden_layers``
    of the published ``layer_types``."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def mixer_shapes(cfg: dict, layer: int) -> dict:
    d = cfg["hidden_size"]
    if layer_types(cfg)[layer] == "conv":
        return {"attn/in_proj/kernel": (d, 3 * d), "attn/conv": (cfg["conv_L_cache"], d),
                "attn/out_proj/kernel": (d, d)}
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return {"attn/q_proj/kernel": (d, h, hd), "attn/k_proj/kernel": (d, kv, hd),
            "attn/v_proj/kernel": (d, kv, hd), "attn/q_norm/scale": (hd,),
            "attn/k_norm/scale": (hd,), "attn/o_proj/kernel": (h, hd, d)}


def mlp_shapes(cfg: dict, layer: int) -> dict:
    d = cfg["hidden_size"]
    if layer < cfg["num_dense_layers"]:
        f = cfg["intermediate_size"]
        return {"mlp/gate_proj/kernel": (d, f), "mlp/up_proj/kernel": (d, f),
                "mlp/down_proj/kernel": (f, d)}
    f, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    shapes = {"mlp/router": (d, e), "mlp/w_gate": (e, d, f), "mlp/w_up": (e, d, f),
              "mlp/w_down": (e, f, d)}
    if cfg["use_expert_bias"]:
        shapes["mlp/router_bias"] = (e,)
    return shapes


def layer_shapes(cfg: dict, layer: int) -> dict:
    d = cfg["hidden_size"]
    return {"attn_norm/scale": (d,), "mlp_norm/scale": (d,),
            **mixer_shapes(cfg, layer), **mlp_shapes(cfg, layer)}


def outer_shapes(cfg: dict) -> dict:
    """The embedding is the head too (``tie_word_embeddings``)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"transformer/tok_embed/embedding": (v, d),
            "transformer/final_norm/scale": (d,)}


def _served_dtype(cfg: dict):
    return jnp.dtype(cfg.get("torch_dtype", "bfloat16"))


@functools.partial(jax.jit, static_argnames=("leaves", "dtype"))
def _make(seed_u32, crcs, leaves, dtype):
    """``weights.leaf`` for every ``(name, shape, kind)`` of *leaves*, with the
    names' checksums as a TRACED operand (``crcs``, in the same order): the
    fourteen layers are three kinds, and a layer's full names differ only in
    its number, so three programs make all the weights where one a layer would
    be compiled (15 s each at the published widths). Held to ``weights.leaf``
    value for value by ``tests/perfbench/test_perfbench_conv_moe.py``."""
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


def short_conv(h, w, mm):
    """The gated short convolution of one layer: h [T, d] (normed) -> [T, d]."""
    d = h.shape[-1]
    bcu = mm("td,dk->tk", h, w["attn/in_proj/kernel"])
    z = bcu[:, :d] * bcu[:, 2 * d:]
    taps = w["attn/conv"]                                   # [L, d]
    width, t = taps.shape[0], h.shape[0]
    zp = jnp.pad(z, ((width - 1, 0), (0, 0)))               # zeros before the sequence
    v = sum(taps[j] * zp[j:j + t] for j in range(width))
    return mm("td,dk->tk", bcu[:, d:2 * d] * v, w["attn/out_proj/kernel"])


def attention(h, w, mm, *, theta: float, eps: float):
    """Grouped-query attention with per-head q/k RMSNorm: h [T, d] -> [T, d]."""
    t = h.shape[0]
    q = _rms_norm(mm("td,dhk->thk", h, w["attn/q_proj/kernel"]), w["attn/q_norm/scale"], eps)
    k = _rms_norm(mm("td,dhk->thk", h, w["attn/k_proj/kernel"]), w["attn/k_norm/scale"], eps)
    v = mm("td,dhk->thk", h, w["attn/v_proj/kernel"])
    q, k = _rope(q, theta), _rope(k, theta)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    outs = []
    for a in range(0, t, Q_BLOCK):
        qb = q[a:a + Q_BLOCK]
        sc = mm("qhk,thk->hqt", qb, k) * (q.shape[-1] ** -0.5)
        row = a + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(t)[None, :] <= row, sc, -jnp.inf)
        outs.append(mm("hqt,thk->qhk", jax.nn.softmax(sc, axis=-1), v))
    return mm("qhk,hkd->qd", jnp.concatenate(outs, axis=0), w["attn/o_proj/kernel"])


def router(h, w_r, bias, k: int, scaling: float):
    """-> (chosen [T, k] expert ids, gates [T, k], margin [T]: by how much
    the k-th selection score leads the next one); float32 throughout."""
    s = jax.nn.sigmoid(jnp.dot(h, w_r, precision=HI))
    top, chosen = jax.lax.top_k(s + bias, k + 1)
    chosen = chosen[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=1)
    return (chosen, scaling * picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-6),
            top[:, k - 1] - top[:, k])


def _swiglu(x, wg, wu, wd, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, wg)) * mm("td,df->tf", x, wu), wd)


_LAYER_KEYS = ("rope_theta", "norm_eps", "num_experts_per_tok", "routed_scaling_factor")


def _cfg_key(cfg: dict) -> str:
    """What ``layer_forward`` reads of the configuration beside the weights'
    own shapes, as one hashable (JSON) string for ``jax.jit``."""
    return json.dumps({**{k: cfg[k] for k in _LAYER_KEYS},
                       "torch_dtype": str(_served_dtype(cfg))}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("key", "precision"))
def layer_forward(x, w, *, key: str, precision: str = "f32"):
    """x: [T, d] f32 -> ([T, d], router flips in this layer, the router's
    margin at each position — infinite in a dense layer). The layer's kind
    is what its weights are: ``attn/conv`` or ``attn/q_proj``; ``mlp/router``
    or ``mlp/gate_proj``."""
    cfg = json.loads(key)
    mm = functools.partial(lowp.einsum, precision=precision)
    eps, t = cfg["norm_eps"], x.shape[0]
    h = _rms_norm(x, w["attn_norm/scale"], eps)
    x = x + (short_conv(h, w, mm) if "attn/conv" in w else
             attention(h, w, mm, theta=float(cfg["rope_theta"]), eps=eps))
    h = _rms_norm(x, w["mlp_norm/scale"], eps)
    if "mlp/router" not in w:
        return (x + _swiglu(h, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"],
                            w["mlp/down_proj/kernel"], mm), jnp.zeros((), jnp.int32),
                jnp.full((t,), jnp.inf))
    k, scaling = cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]
    bias = w.get("mlp/router_bias", jnp.zeros((w["mlp/router"].shape[1],)))
    chosen, gates, margin = router(h, w["mlp/router"], bias, k, scaling)
    rounded, _, _ = router(h.astype(jnp.dtype(cfg["torch_dtype"])).astype(jnp.float32),
                           w["mlp/router"], bias, k, scaling)
    flips = jnp.sum(jnp.any(jnp.sort(chosen, 1) != jnp.sort(rounded, 1), axis=1))

    def one_expert(y, ew):                      # every expert, one by one
        e, wg, wu, wd = ew
        g = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=1)             # [T]
        return y + g[:, None] * _swiglu(h, wg, wu, wd, mm), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (jnp.arange(w["mlp/w_gate"].shape[0]), w["mlp/w_gate"],
                         w["mlp/w_up"], w["mlp/w_down"]))
    return x + y, flips.astype(jnp.int32), margin


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x_rows, scale, embedding, *, eps: float, precision: str):
    return lowp.einsum("td,vd->tv", _rms_norm(x_rows, scale, eps), embedding, precision)


def forward_logits(cfg: dict, seed: int, seqs: list[np.ndarray], rows: list[np.ndarray],
                   precision: str = "f32") -> tuple[list[np.ndarray], list[np.ndarray], int]:
    """Logits [len(rows_i), V] at positions ``rows[i]`` of each sequence; the
    smallest router margin over the expert layers at those positions; and the
    count of router flips over every position computed (the pads too)."""
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
        margins = [np.full(t, np.inf, np.float32) for _ in seqs]
        for l in range(cfg["num_hidden_layers"]):
            w = make_layer(cfg, seed, l)
            done = [layer_forward(x, w, key=key, precision=precision) for x in xs]
            xs = [x for x, _, _ in done]
            flips += sum(int(f) for _, f, _ in done)
            margins = [np.minimum(m, np.asarray(mg)) for m, (_, _, mg) in zip(margins, done)]
            del w, done
        out = []
        for x, r in zip(xs, rows):
            n = -(-len(r) // 256) * 256
            idx = np.zeros(n, np.int32)
            idx[:len(r)] = r
            lg = _head(x[jnp.asarray(idx)], outer["transformer/final_norm/scale"], emb,
                       eps=float(cfg["norm_eps"]), precision=precision)
            out.append(np.asarray(lg[:len(r)]))
    return out, [m[r] for m, r in zip(margins, rows)], flips


def decided_margin(cfg: dict) -> float:
    """A routing choice counts as DECIDED where the k-th selection score
    leads the next by more than one step of the served type at 1 (bfloat16:
    2^-7; the scores are sigmoids in (0, 1)): a choice decided by less does
    not survive twelve expert layers computed in that type, whoever computes
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
      anywhere (a slot's state, a page) reads a logit's distance from the
      best, several spreads; the limit sits above what a routing swap can do;
    - ``all_mean``: the mean gap over every served token — what precision
      does to all of them at once (a swap moves one position, rounding moves
      each);
    - ``decided_max``: the widest gap over the tokens whose position's
      routing THIS REFERENCE finds decided (:func:`decided_margin`, in every
      expert layer; from the reference's own float32 scores alone, never from
      the program's choices; the control is held to the same positions). On
      the chip, over twelve expert layers in bfloat16, it reads 0-0.12 on 18
      seeds and 0.22 on one against the control's 0.58-0.89 (PERF.md 2): too
      heavy a tail for a limit with room on both sides, and ``all_mean``
      separates program from control by 3.96x alone, so the published
      configuration does not name it.

    ``serve_window.correctness`` compares the one key ``logit_gap_max``: the
    LARGEST of the named gaps as a share of its limit, so the cell's limit on
    it is 1 and a run is correct only inside all of them. NaN (never correct)
    where ``decided_max`` is named and no sampled position is decided.
    ``fault="alter"`` alters one served token in eight before scoring."""
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
    if precision != "f32":
        low, _, _ = forward_logits(cfg, seed, seqs, rows, precision)
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
    # how the widest gap falls as the margin asked for rises: [margin, tokens, gap]
    by_margin = [[f * step, int((allm > f * step).sum()),
                  float(allg[allm > f * step].max(initial=0.0))]
                 for f in (0.0625, 0.125, 0.25, 0.5, 1.0, 2.0)]
    sparse = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
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
