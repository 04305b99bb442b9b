"""Plain reference of the Mistral-family decoder (dense GQA, RoPE, RMSNorm,
SwiGLU, untied head): one full causal forward pass per sequence in float32
at ``highest`` matmul precision — no cache, no paging, no batching, no kernel,
nothing imported from the program.

It runs after the engine's weights are freed, so it never holds the model:
weights are made again from the seed (``weights.leaf``, then the served
type's rounding, then float32) ONE LAYER AT A TIME, and every sampled
sequence goes through that layer before the next is made. Sequences are
padded to a multiple of ``PAD`` (causal attention: padding never reaches an
earlier position), attention runs in blocks of query rows.

``score_served`` returns, for every served token, how far its logit lies
below the reference's best at that position — 0 where the served token IS
the reference's choice. With ``precision`` below f32 the same pass is the
CONTROL: at each position it reports the gap of the token that the lower
precision puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import lowp, weights

PAD = 512
Q_BLOCK = 512


def layer_shapes(cfg: dict) -> dict:
    d, h, kv, hd, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"], cfg["intermediate_size"])
    return {"attn_norm/scale": (d,), "attn/q_proj/kernel": (d, h, hd),
            "attn/k_proj/kernel": (d, kv, hd), "attn/v_proj/kernel": (d, kv, hd),
            "attn/o_proj/kernel": (h, hd, d), "mlp_norm/scale": (d,),
            "mlp/gate_proj/kernel": (d, f), "mlp/up_proj/kernel": (d, f),
            "mlp/down_proj/kernel": (f, d)}


def outer_shapes(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"transformer/tok_embed/embedding": (v, d),
            "transformer/final_norm/scale": (d,),
            "head/lm_head/kernel": (d, v)}


def _served_dtype(cfg: dict):
    return jnp.dtype(cfg.get("torch_dtype", "bfloat16"))


@functools.partial(jax.jit, static_argnames=("names_shapes", "dtype"))
def _make(seed_u32, names_shapes, dtype):
    return {n: weights.leaf(seed_u32, n, shp, dtype).astype(jnp.float32)
            for n, shp in names_shapes}


def make_layer(cfg: dict, seed: int, layer: int) -> dict:
    pre = f"transformer/block_{layer}/"
    ns = tuple((pre + n, shp) for n, shp in layer_shapes(cfg).items())
    made = _make(weights.seed_operand(seed), ns, _served_dtype(cfg))
    return {n[len(pre):]: v for n, v in made.items()}


def make_outer(cfg: dict, seed: int) -> dict:
    ns = tuple(outer_shapes(cfg).items())
    return _make(weights.seed_operand(seed), ns, _served_dtype(cfg))


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


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "eps", "precision"))
def layer_forward(x, w, *, heads: int, kv_heads: int, theta: float, eps: float,
                  precision: str = "f32"):
    """x: [T, d] f32 -> [T, d]."""
    mm = functools.partial(lowp.einsum, precision=precision)
    t = x.shape[0]
    h = _rms_norm(x, w["attn_norm/scale"], eps)
    q = _rope(mm("td,dhk->thk", h, w["attn/q_proj/kernel"]), theta)
    k = _rope(mm("td,dhk->thk", h, w["attn/k_proj/kernel"]), theta)
    v = mm("td,dhk->thk", h, w["attn/v_proj/kernel"])
    rep = heads // kv_heads
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    hd = q.shape[-1]
    outs = []
    for a in range(0, t, Q_BLOCK):
        qb = q[a:a + Q_BLOCK]
        sc = mm("qhk,thk->hqt", qb, k) * (hd ** -0.5)
        row = a + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(t)[None, :] <= row, sc, -jnp.inf)
        outs.append(mm("hqt,thk->qhk", jax.nn.softmax(sc, axis=-1), v))
    o = jnp.concatenate(outs, axis=0)
    x = x + mm("qhk,hkd->qd", o, w["attn/o_proj/kernel"])
    h = _rms_norm(x, w["mlp_norm/scale"], eps)
    g = mm("td,df->tf", h, w["mlp/gate_proj/kernel"])
    u = mm("td,df->tf", h, w["mlp/up_proj/kernel"])
    return x + mm("tf,fd->td", jax.nn.silu(g) * u, w["mlp/down_proj/kernel"])


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x_rows, scale, lm_head, *, eps: float, precision: str):
    h = _rms_norm(x_rows, scale, eps)
    return lowp.einsum("td,dv->tv", h, lm_head, precision)


def forward_logits(cfg: dict, seed: int, seqs: list[np.ndarray], rows: list[np.ndarray],
                   precision: str = "f32") -> list[np.ndarray]:
    """Logits [len(rows_i), V] at positions ``rows[i]`` of each sequence."""
    kw = dict(heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
              theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
              precision=precision)
    with jax.default_matmul_precision("highest"):
        outer = make_outer(cfg, seed)
        emb = outer["transformer/tok_embed/embedding"]
        xs = []
        for s in seqs:
            t = -(-len(s) // PAD) * PAD
            padded = np.zeros(t, np.int32)
            padded[:len(s)] = s
            xs.append(emb[jnp.asarray(padded)])
        del emb
        for l in range(cfg["num_hidden_layers"]):
            w = make_layer(cfg, seed, l)
            xs = [layer_forward(x, w, **kw) for x in xs]
            del w
        out = []
        for x, r in zip(xs, rows):
            n = -(-len(r) // 256) * 256
            idx = np.zeros(n, np.int32)
            idx[:len(r)] = r
            lg = _head(x[jnp.asarray(idx)], outer["transformer/final_norm/scale"],
                       outer["head/lm_head/kernel"], eps=kw["eps"], precision=precision)
            out.append(np.asarray(lg[:len(r)]))
    return out


def score_served(cfg: dict, seed: int, sample: list[dict], *,
                 precision: str = "f32", fault=None) -> dict:
    """*sample*: ``[{"prompt": int array, "tokens": served token ids}]``.

    With ``precision="f32"``: per served token, reference's best logit minus
    the served token's logit (>= 0). With a lower precision (the control):
    the same for the token that precision puts first, judged by the f32
    logits — so the call runs both passes. ``fault="alter"`` alters one
    served token in eight before scoring (a token altered where it is
    produced)."""
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
    if precision != "f32":
        low = forward_logits(cfg, seed, seqs, rows, precision)
        judged = [lg.argmax(-1) for lg in low]
    gaps, flips, n = [], 0, 0
    for lg, tok in zip(ref, judged):
        best = lg.max(-1)
        got = lg[np.arange(len(tok)), tok]
        gaps.append(best - got)
        flips += int((lg.argmax(-1) != tok).sum())
        n += len(tok)
    allg = np.concatenate(gaps)
    spread = float(np.mean([np.std(lg, axis=-1).mean() for lg in ref]))
    return {"logit_gap_max": float(allg.max()), "logit_gap_mean": float(allg.mean()),
            "tokens": n, "not_reference_best": flips, "logit_std": spread,
            "per_request_max": [float(g.max()) for g in gaps]}
