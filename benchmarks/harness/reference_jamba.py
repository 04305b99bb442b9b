"""Plain reference of the ``jamba`` family (Mamba-1 mixers, a few multi-query
attention layers with no positional embedding, a dense SwiGLU feed-forward in
every layer, a head tied to the embedding): one full causal forward pass per
sequence in float32 at ``highest`` matmul precision — no cache, no state
arena, no paging, no batching, no kernel, the selective scan a ``lax.scan``
over TOKENS with the state in the published ``[E, N]`` layout, nothing
imported from the program.

The equations, for layer l with input x [T, d] (``configs/ai21-jamba2-3b.json``
names the source; PERF.md section 4); layer l is attention iff
``l % attn_layer_period == attn_layer_offset``, else Mamba:

    x <- x + Mixer(RMSNorm(x));   x <- x + W_down(silu(W_gate h) * W_up h),
    h = RMSNorm(x)   (eps = rms_norm_eps, F = intermediate_size)
    Mamba:  [u | z] = W_in x^              (d -> 2E, E = mamba_expand d, no bias)
            u = silu(conv(u) + b_conv)     (depthwise, causal, mamba_d_conv
                                            taps, zeros before the sequence)
            [r | B | C] = W_x u            (E -> R + 2N, no bias)
            r, B, C = RMSNorm(r), RMSNorm(B), RMSNorm(C)   (learned gains)
            dt = softplus(W_dt r + b_dt) [E];   A = -exp(A_log) [E, N]
            h_t[e, n] = exp(dt_t[e] A[e, n]) h_(t-1)[e, n] + dt_t[e] B_t[n] u_t[e]
            y_t[e] = sum_n h_t[e, n] C_t[n] + D[e] u_t[e]
            out = W_out (y * silu(z))      (no bias, no output norm)
    attention:  q, k, v = W_q x^, W_k x^, W_v x^ (H x hd, 1 x hd, 1 x hd); NO
            positional embedding; causal softmax at hd^-1/2, every query head
            on the one KV head;  W_o
    after the last layer: RMSNorm, then logits = x E^T (the tied embedding)

Weights are made again from the seed ONE LAYER AT A TIME (``weights.leaf``'s
rule — normal, std 0.02, norm gains 1 + the same — except the leaves that
Mamba-1's published initialisation draws otherwise, :func:`leaf_kind`), in
the served type's rounding, then float32; every sampled sequence goes through
that layer before the next is made. Sequences are padded to ONE multiple of
``PAD``: causal layers never let a pad reach an earlier position.

``score_served`` has ``reference_ssm_moe``'s contract (no routing here: no
flips, every position decided). ``precision`` below f32 is the CONTROL (the
recurrence stays float32 there: the program keeps it so).
``fault="alter"`` alters one served token in eight; ``fault="zero_state"``
stands a faulty program in the program's place: the same pass with every
Mamba layer's state set to zero before token ``zero_state_at`` (where a
second prefill chunk would start), its argmax tokens judged against the
reference proper.
"""
from __future__ import annotations

import functools
import json
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import lowp, weights

PAD = 512
Q_BLOCK = 512


def is_attention(cfg: dict, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def mamba_sizes(cfg: dict) -> tuple[int, int, int, int]:
    """(E channels, N state columns, R rank of the step, K taps)."""
    return (cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"],
            cfg["mamba_dt_rank"], cfg["mamba_d_conv"])


def layer_shapes(cfg: dict, layer: int) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    out = {"attn_norm/scale": (d,), "mlp_norm/scale": (d,), "mlp/gate_proj/kernel": (d, f),
           "mlp/up_proj/kernel": (d, f), "mlp/down_proj/kernel": (f, d)}
    if is_attention(cfg, layer):
        h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
        return {**out, "attn/q_proj/kernel": (d, h, hd), "attn/k_proj/kernel": (d, kv, hd),
                "attn/v_proj/kernel": (d, kv, hd), "attn/o_proj/kernel": (h, hd, d)}
    e, n, r, k = mamba_sizes(cfg)
    return {**out, "attn/in_proj/kernel": (d, 2 * e), "attn/conv": (k, e),
            "attn/conv_bias": (e,), "attn/x_proj/kernel": (e, r + 2 * n),
            "attn/dt_norm/scale": (r,), "attn/b_norm/scale": (n,), "attn/c_norm/scale": (n,),
            "attn/dt_proj/kernel": (r, e), "attn/dt_proj/bias": (e,), "attn/A_log": (e, n),
            "attn/D": (e,), "attn/out_proj/kernel": (e, d)}


def outer_shapes(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"transformer/tok_embed/embedding": (v, d), "transformer/final_norm/scale": (d,)}


def _served_dtype(cfg: dict):
    return jnp.dtype(cfg.get("torch_dtype", "bfloat16"))


def leaf_kind(name: str) -> str:
    """How leaf *name* is drawn: ``weights._kind``'s three, and the leaves of
    a Mamba-1 mixer that its published initialisation draws otherwise —
    drawn as N(0, 0.02) a state would be forgotten within a few tokens and no
    comparison would see a state carried wrongly: ``A_log`` (log 1..N on
    every channel), ``dt_proj`` (its bias the inverse softplus of a step
    log-uniform in [time_step_min, time_step_max], floored; its weight
    uniform within dt_rank^-1/2), ``D`` (ones), and the convolution's taps
    and bias (uniform within 1 / sqrt(mamba_d_conv), the framework's default
    for a depthwise convolution)."""
    tail = name.rsplit("/", 1)[-1]
    if tail in ("A_log", "D"):
        return tail
    if name.endswith("dt_proj/bias"):
        return "dt_bias"
    if name.endswith("dt_proj/kernel"):
        return "dt_weight"
    if tail in ("conv", "conv_bias"):
        return "tap"
    return weights._kind(name)


def _consts(cfg: dict) -> tuple:
    return (float(cfg["time_step_min"]), float(cfg["time_step_max"]),
            float(cfg["time_step_floor"]), int(cfg["mamba_d_conv"]),
            int(cfg["mamba_dt_rank"]))


def draw(key, shape, kind: str, consts: tuple):
    """The float32 values of a leaf of *kind* (:func:`leaf_kind`) from *key*."""
    t_min, t_max, t_floor, taps, rank = consts
    shape = tuple(shape)
    if kind == "D":
        return jnp.ones(shape, jnp.float32)
    if kind == "A_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)
    if kind in ("dt_bias", "dt_weight", "tap"):
        u = jax.random.uniform(key, shape, jnp.float32)
        if kind == "dt_weight":
            return (2.0 * u - 1.0) / math.sqrt(rank)
        if kind == "tap":
            return (2.0 * u - 1.0) / math.sqrt(taps)
        step = jnp.maximum(jnp.exp(u * (math.log(t_max) - math.log(t_min))
                                   + math.log(t_min)), t_floor)
        return step + jnp.log(-jnp.expm1(-step))        # softplus^-1
    x = jax.random.normal(key, shape, jnp.float32) * weights.STD
    return 1.0 + x if kind == "scale" else x


def leaf(cfg: dict, seed, name: str, shape, dtype):
    """Leaf *name* for *seed* (a uint32 scalar, ``weights.seed_operand``) in
    *dtype*: what the program's weights are made with too."""
    key = jax.random.fold_in(jax.random.key(seed), zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return draw(key, shape, leaf_kind(name), _consts(cfg)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("leaves", "dtype", "consts"))
def _make(seed_u32, crcs, leaves, dtype, consts):
    """:func:`leaf` for every ``(name, shape, kind)`` of *leaves*, with the
    names' checksums as a TRACED operand: a layer's full names differ only in
    its number, so two programs make every layer's weights."""
    out = {}
    for i, (name, shape, kind) in enumerate(leaves):
        key = jax.random.fold_in(jax.random.key(seed_u32), crcs[i])
        out[name] = draw(key, shape, kind, consts).astype(dtype).astype(jnp.float32)
    return out


def _made(cfg: dict, seed: int, prefix: str, shapes: dict) -> dict:
    leaves = tuple((n, tuple(shp), leaf_kind(prefix + n)) for n, shp in shapes.items())
    crcs = np.array([zlib.crc32((prefix + n).encode()) & 0x7FFFFFFF for n in shapes], np.uint32)
    return _make(weights.seed_operand(seed), crcs, leaves, _served_dtype(cfg), _consts(cfg))


def make_layer(cfg: dict, seed: int, layer: int) -> dict:
    return _made(cfg, seed, f"transformer/block_{layer}/", layer_shapes(cfg, layer))


def make_outer(cfg: dict, seed: int) -> dict:
    return _made(cfg, seed, "", outer_shapes(cfg))


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def mamba(h, w, mm, *, sizes, eps: float, zero_state_at=None, return_state=False):
    """The Mamba-1 mixer of one layer: h [T, d] (normed) -> [T, d] (and, with
    *return_state*, the state [E, N] after the last token). The recurrence is
    a scan over tokens on the state [E, N]. *zero_state_at*: the planted
    fault — the state is set to zero before that token."""
    e, n, r, taps_k = sizes
    t = h.shape[0]
    uz = mm("td,dk->tk", h, w["attn/in_proj/kernel"])
    u, z = uz[:, :e], uz[:, e:]
    taps = w["attn/conv"]
    up = jnp.pad(u, ((taps_k - 1, 0), (0, 0)))              # zeros before the sequence
    u = jax.nn.silu(sum(taps[j] * up[j:j + t] for j in range(taps_k)) + w["attn/conv_bias"])
    rbc = mm("te,ek->tk", u, w["attn/x_proj/kernel"])
    dt_r = _rms_norm(rbc[:, :r], w["attn/dt_norm/scale"], eps)
    bmat = _rms_norm(rbc[:, r:r + n], w["attn/b_norm/scale"], eps)
    cmat = _rms_norm(rbc[:, r + n:], w["attn/c_norm/scale"], eps)
    step = jax.nn.softplus(mm("tr,re->te", dt_r, w["attn/dt_proj/kernel"])
                           + w["attn/dt_proj/bias"])
    a = -jnp.exp(w["attn/A_log"])                            # [E, N]

    def one_token(state, now):                               # state [E, N]
        i, u_t, d_t, b_t, c_t = now
        if zero_state_at is not None:
            state = jnp.where(i == zero_state_at, 0.0, state)
        state = jnp.exp(d_t[:, None] * a) * state + (d_t * u_t)[:, None] * b_t[None, :]
        return state, jnp.sum(state * c_t[None, :], axis=-1)

    state, y = jax.lax.scan(one_token, jnp.zeros((e, n), jnp.float32),
                            (jnp.arange(t), u, step, bmat, cmat))
    y = (y + w["attn/D"] * u) * jax.nn.silu(z)
    out = mm("te,ed->td", y, w["attn/out_proj/kernel"])
    return (out, state) if return_state else out


def attention(h, w, mm):
    """Multi-query attention with no positional embedding: h [T, d] -> [T, d]."""
    t = h.shape[0]
    q = mm("td,dhk->thk", h, w["attn/q_proj/kernel"])
    k = mm("td,dhk->thk", h, w["attn/k_proj/kernel"])
    v = mm("td,dhk->thk", h, w["attn/v_proj/kernel"])
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


def swiglu(h, w, mm):
    g = mm("td,df->tf", h, w["mlp/gate_proj/kernel"])
    u = mm("td,df->tf", h, w["mlp/up_proj/kernel"])
    return mm("tf,fd->td", jax.nn.silu(g) * u, w["mlp/down_proj/kernel"])


_LAYER_KEYS = ("rms_norm_eps", "hidden_size", "mamba_expand", "mamba_d_state",
               "mamba_dt_rank", "mamba_d_conv")


def _cfg_key(cfg: dict) -> str:
    """What ``layer_forward`` reads of the configuration beside the weights'
    own shapes, as one hashable (JSON) string for ``jax.jit``."""
    return json.dumps({k: cfg[k] for k in _LAYER_KEYS}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("key", "precision", "zero_state_at"))
def layer_forward(x, w, *, key: str, precision: str = "f32", zero_state_at=None):
    """x: [T, d] f32 -> [T, d]. The layer's mixer is what its weights are:
    ``attn/A_log`` (Mamba) or ``attn/q_proj`` (attention)."""
    cfg = json.loads(key)
    mm = functools.partial(lowp.einsum, precision=precision)
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, w["attn_norm/scale"], eps)
    if "attn/A_log" in w:
        x = x + mamba(h, w, mm, sizes=mamba_sizes(cfg), eps=eps, zero_state_at=zero_state_at)
    else:
        x = x + attention(h, w, mm)
    return x + swiglu(_rms_norm(x, w["mlp_norm/scale"], eps), w, mm)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x_rows, scale, embedding, *, eps: float, precision: str):
    return lowp.einsum("td,vd->tv", _rms_norm(x_rows, scale, eps), embedding, precision)


def forward_logits(cfg: dict, seed: int, seqs: list[np.ndarray], rows: list[np.ndarray],
                   precision: str = "f32", zero_state_at=None) -> list[np.ndarray]:
    """Logits [len(rows_i), V] at positions ``rows[i]`` of each sequence."""
    key = _cfg_key(cfg)
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
        for l in range(cfg["num_hidden_layers"]):
            w = make_layer(cfg, seed, l)
            xs = [layer_forward(x, w, key=key, precision=precision,
                                zero_state_at=zero_state_at) for x in xs]
            del w
        out = []
        for x, r in zip(xs, rows):
            n = -(-len(r) // 256) * 256
            idx = np.zeros(n, np.int32)
            idx[:len(r)] = r
            lg = _head(x[jnp.asarray(idx)], outer["transformer/final_norm/scale"], emb,
                       eps=float(cfg["rms_norm_eps"]), precision=precision)
            out.append(np.asarray(lg[:len(r)]))
    return out


def score_served(cfg: dict, seed: int, sample: list[dict], *,
                 precision: str = "f32", fault=None) -> dict:
    """*sample*: ``[{"prompt": int array, "tokens": served token ids}]`` — per
    served token the gap by which its logit lies below the reference's best
    at its position, as two numbers: ``all_max`` (the widest: a wrong token
    anywhere) and ``all_mean`` (the mean: what rounding moves everywhere);
    each held to its limit in the configuration's ``served_gap_limits``.
    ``serve_window.correctness`` compares the one key ``logit_gap_max``: the
    LARGER of the two as a share of its limit, so the cell's limit on it is
    1. With ``precision`` below f32, or ``fault="zero_state"``, the tokens
    judged are those a pass so altered would serve."""
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
    ref = forward_logits(cfg, seed, seqs, rows, "f32")
    judged = served
    if precision != "f32" or fault == "zero_state":
        low = forward_logits(
            cfg, seed, seqs, rows, precision,
            zero_state_at=int(cfg["zero_state_at"]) if fault == "zero_state" else None)
        judged = [lg.argmax(-1) for lg in low]
    gaps, flips, n = [], 0, 0
    for lg, tok in zip(ref, judged):
        gaps.append(lg.max(-1) - lg[np.arange(len(tok)), tok])
        flips += int((lg.argmax(-1) != tok).sum())
        n += len(tok)
    allg = np.concatenate(gaps)
    limits = cfg["served_gap_limits"]
    parts = {"all_max": float(allg.max()), "all_mean": float(allg.mean())}
    shares = {k: parts[k] / limits[k] for k in limits}
    binds = max(shares, key=lambda k: shares[k])
    return {"logit_gap_max": shares[binds],
            "gaps": parts, "gap_limits": dict(limits), "gap_shares": shares, "binds": binds,
            "logit_gap_max_all": parts["all_max"], "logit_gap_mean": parts["all_mean"],
            "tokens": n, "tokens_decided": n, "not_reference_best": flips,
            "logit_std": float(np.mean([np.std(lg, axis=-1).mean() for lg in ref])),
            "per_request_max": [float(g.max()) for g in gaps],
            "per_request_max_all": [float(g.max()) for g in gaps],
            "per_request_mean": [float(g.mean()) for g in gaps],
            "router_flips": 0, "router_choices": 0}
