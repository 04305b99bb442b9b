"""Plain reference of the ``latent-moe`` family (multi-head latent attention,
a leading dense layer, then sparse-expert layers with a shared expert): one
full causal forward pass per sequence in float32 at ``highest`` matmul
precision — the EXPANDED form of the attention only (keys and values are
up-projected from the latent for every position; no absorbed form, no cache,
no paging, no kernel), the experts by a plain loop over the held experts with
no token dropped, nothing imported from the program.

The equations (PERF.md section 4 has them with their sources):

    q = W_Q h (H x 192), RMSNorm over each head's 192 lanes (learned gain)
    [c ; k^R] = W_DKV h (512 + 64);  c <- RMSNorm_512(c)
    RoPE (deepseek_yarn frequencies, interleaved pairs) on q^R and k^R
    k^N_i = W_UK,i c,  v_i = W_UV,i c  (128 each, per head i)
    score = (q^N.k^N + q^R.k^R) * 192^-1/2 * m^2,  causal softmax,  W_O
    layer 0: SwiGLU(16384);  layers >= 1:
      s = sigmoid(W_r h) in f32,  T = top-8 of s + b,  g_e = 2.5 s_e / sum_T s
      y = sum_{e in T, e held} g_e E_e(h) + E_shared(h)

ONE CHIP'S SHARE: the router keeps all its outputs, the top-k and the
normalisation over all k chosen; only experts ``[expert_offset, expert_offset
+ num_experts)`` are computed, and that partial sum goes on to the next
layer, as in the program. ``cfg`` may say ``expert_offset`` (default 0) and
``shared_expert`` (default true) so that a test can add the shares up.

It runs after the engine's weights are freed and never holds the model:
weights are made again from the seed ONE LAYER AT A TIME (``weights.leaf``,
the served type's rounding, then float32), and every sampled sequence goes
through that layer before the next is made. Sequences are padded to ONE
multiple of ``PAD`` (itself a multiple of ``Q_BLOCK``); attention runs in blocks
of query rows and the experts in a scan, so that a 17 k-token sequence compiles
in seconds.

``score_served`` is ``reference_mistral``'s — per served token, how far its
logit lies below the reference's best at that position — held to THREE
limits at once, every sampled token to two of them (see there). With
``precision`` below f32 the same pass is the CONTROL (the router stays in
float32 there too: the program keeps it so at any precision). Its notes count
``router_flips``: (token, layer) pairs whose chosen set changes when the
router's input is rounded to the served type — how often a near-tie can
send the program another way than this reference, with nothing learned
from the program.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import lowp, weights

PAD = 512
Q_BLOCK = 256
HI = jax.lax.Precision.HIGHEST


def attn_shapes(cfg: dict) -> dict:
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return {"attn_norm/scale": (d,), "attn/q_proj/kernel": (d, h, dn + dr),
            "attn/q_norm/scale": (dn + dr,), "attn/kv_down/kernel": (d, r + dr),
            "attn/kv_norm/scale": (r,), "attn/kv_up": (r, h, dn + dv),
            "attn/o_proj/kernel": (h, dv, d), "mlp_norm/scale": (d,)}


def mlp_shapes(cfg: dict, layer: int) -> dict:
    d = cfg["hidden_size"]
    if layer < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        return {"mlp/gate_proj/kernel": (d, f), "mlp/up_proj/kernel": (d, f),
                "mlp/down_proj/kernel": (f, d)}
    f, e, n = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["router_outputs"]
    fs = f * cfg["num_shared_experts"]
    return {"mlp/router": (d, n), "mlp/router_bias": (n,),
            "mlp/w_gate": (e, d, f), "mlp/w_up": (e, d, f), "mlp/w_down": (e, f, d),
            "mlp/shared/gate_proj/kernel": (d, fs), "mlp/shared/up_proj/kernel": (d, fs),
            "mlp/shared/down_proj/kernel": (fs, d)}


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
    shapes = {**attn_shapes(cfg), **mlp_shapes(cfg, layer)}
    ns = tuple((pre + n, shp) for n, shp in shapes.items())
    made = _make(weights.seed_operand(seed), ns, _served_dtype(cfg))
    return {n[len(pre):]: v for n, v in made.items()}


def make_outer(cfg: dict, seed: int) -> dict:
    return _make(weights.seed_operand(seed), tuple(outer_shapes(cfg).items()),
                 _served_dtype(cfg))


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """deepseek_yarn: ``theta^(-2j/D)`` for the pairs that turn more than
    ``beta_fast`` times over the original context, divided by ``factor`` for
    those that turn fewer than ``beta_slow`` times, a linear ramp between."""
    dim, theta, rs = cfg["qk_rope_head_dim"], float(cfg["rope_theta"]), cfg["rope_scaling"]
    j = np.arange(dim // 2, dtype=np.float64)
    inv = theta ** (-2.0 * j / dim)
    s = float(rs["factor"])
    if s <= 1.0:
        return inv.astype(np.float32)
    l0 = rs["original_max_position_embeddings"]
    turns = lambda beta: dim * math.log(l0 / (2 * math.pi * beta)) / (2 * math.log(theta))
    lo = max(math.floor(turns(rs["beta_fast"])), 0)
    hi = min(math.ceil(turns(rs["beta_slow"])), dim - 1)
    ramp = np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (inv * ((1.0 - ramp) + ramp / s)).astype(np.float32)


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _rope(x, inv, amp):
    """x: [T, H, D]; rotate the interleaved pairs (x[2i], x[2i+1]) by
    position * inv[i]; cos and sin scaled by *amp*."""
    t = x.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * amp, jnp.sin(ang)[:, None, :] * amp
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


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


_LAYER_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
               "qk_rope_head_dim", "v_head_dim", "rope_theta", "rope_scaling",
               "rms_norm_eps", "num_experts_per_tok", "routed_scaling_factor")


def _cfg_key(cfg: dict) -> str:
    """What ``layer_forward`` reads of the configuration, as one hashable
    (JSON) string for ``jax.jit``'s static argument."""
    return json.dumps({**{k: cfg[k] for k in _LAYER_KEYS},
                       "expert_offset": int(cfg.get("expert_offset", 0)),
                       "shared_expert": bool(cfg.get("shared_expert", True)),
                       "torch_dtype": str(_served_dtype(cfg))}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("key", "precision"))
def layer_forward(x, w, *, key: str, precision: str = "f32"):
    """x: [T, d] f32 -> ([T, d], router flips in this layer, the router's
    margin at each position — infinite in a dense layer)."""
    cfg = json.loads(key)
    mm = functools.partial(lowp.einsum, precision=precision)
    eps, r, dn = cfg["rms_norm_eps"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rs = cfg["rope_scaling"]
    t = x.shape[0]
    h = _rms_norm(x, w["attn_norm/scale"], eps)
    q = _rms_norm(mm("td,dhk->thk", h, w["attn/q_proj/kernel"]), w["attn/q_norm/scale"], eps)
    ckr = mm("td,dk->tk", h, w["attn/kv_down/kernel"])
    c = _rms_norm(ckr[:, :r], w["attn/kv_norm/scale"], eps)
    inv = jnp.asarray(yarn_inv_freq(cfg))
    amp = mscale(rs["factor"], rs["mscale"]) / mscale(rs["factor"], rs["mscale_all_dim"])
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], inv, amp)
    k_r = _rope(ckr[:, None, r:], inv, amp)[:, 0]                   # [T, dr]
    k_n = mm("tr,rhk->thk", c, w["attn/kv_up"][..., :dn])           # [T, H, dn]
    v = mm("tr,rhk->thk", c, w["attn/kv_up"][..., dn:])             # [T, H, dv]
    scale = softmax_scale(cfg)

    def q_block(a):
        """Attention of query rows ``a + [0, Q_BLOCK)`` over every position
        (one traced body for all blocks: the program stays small at 17 k
        tokens)."""
        qn = jax.lax.dynamic_slice_in_dim(q_n, a, Q_BLOCK, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, a, Q_BLOCK, 0)
        sc = (mm("qhk,thk->hqt", qn, k_n) + mm("qhk,tk->hqt", qr, k_r)) * scale
        row = a + jnp.arange(Q_BLOCK)[:, None]
        sc = jnp.where(jnp.arange(t)[None, :] <= row, sc, -jnp.inf)
        return mm("hqt,thk->qhk", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(q_block, jnp.arange(0, t, Q_BLOCK)).reshape(t, -1, v.shape[-1])
    x = x + mm("qhk,hkd->qd", o, w["attn/o_proj/kernel"])

    h = _rms_norm(x, w["mlp_norm/scale"], eps)
    if "mlp/router" not in w:
        return (x + _swiglu(h, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"],
                            w["mlp/down_proj/kernel"], mm), jnp.zeros((), jnp.int32),
                jnp.full((t,), jnp.inf))
    k, off = cfg["num_experts_per_tok"], cfg["expert_offset"]
    chosen, gates, margin = router(h, w["mlp/router"], w["mlp/router_bias"], k,
                                   cfg["routed_scaling_factor"])
    rounded, _, _ = router(h.astype(jnp.dtype(cfg["torch_dtype"])).astype(jnp.float32),
                           w["mlp/router"], w["mlp/router_bias"], k,
                           cfg["routed_scaling_factor"])
    flips = jnp.sum(jnp.any(jnp.sort(chosen, 1) != jnp.sort(rounded, 1), axis=1))
    def one_expert(y, ew):                      # the held experts, one by one
        e, wg, wu, wd = ew
        g = jnp.sum(jnp.where(chosen == off + e, gates, 0.0), axis=1)       # [T]
        return y + g[:, None] * _swiglu(h, wg, wu, wd, mm), None

    held = w["mlp/w_gate"].shape[0]
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (jnp.arange(held), w["mlp/w_gate"], w["mlp/w_up"], w["mlp/w_down"]))
    if cfg["shared_expert"]:
        y = y + _swiglu(h, w["mlp/shared/gate_proj/kernel"], w["mlp/shared/up_proj/kernel"],
                        w["mlp/shared/down_proj/kernel"], mm)
    return x + y, flips.astype(jnp.int32), margin


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x_rows, scale, lm_head, *, eps: float, precision: str):
    return lowp.einsum("td,dv->tv", _rms_norm(x_rows, scale, eps), lm_head, precision)


def forward_logits(cfg: dict, seed: int, seqs: list[np.ndarray], rows: list[np.ndarray],
                   precision: str = "f32") -> tuple[list[np.ndarray], list[np.ndarray], int]:
    """Logits [len(rows_i), V] at positions ``rows[i]`` of each sequence; the
    smallest router margin over the expert layers at those positions; and the
    count of router flips over every position computed (the pads too: they
    follow the sequence and reach nothing before them)."""
    key = _cfg_key(cfg)
    flips = 0
    with jax.default_matmul_precision("highest"):
        outer = make_outer(cfg, seed)
        emb = outer["transformer/tok_embed/embedding"]
        xs = []
        # one padded length for all: one compiled layer of each kind
        t = -(-max(len(s) for s in seqs) // PAD) * PAD
        for s in seqs:
            padded = np.zeros(t, np.int32)
            padded[:len(s)] = s
            xs.append(emb[jnp.asarray(padded)])
        del emb
        margins = [np.full(t, np.inf, np.float32) for _ in seqs]
        for l in range(cfg["num_hidden_layers"]):
            w = make_layer(cfg, seed, l)
            done = [layer_forward(x, w, key=key, precision=precision) for x in xs]
            xs = [x for x, _, _ in done]
            flips += sum(int(f) for _, f, _ in done)
            margins = [np.minimum(m, np.asarray(mg)) for m, (_, _, mg) in zip(margins, done)]
            del w, done
        out = []
        eps = float(cfg["rms_norm_eps"])
        for x, r in zip(xs, rows):
            n = -(-len(r) // 256) * 256
            idx = np.zeros(n, np.int32)
            idx[:len(r)] = r
            lg = _head(x[jnp.asarray(idx)], outer["transformer/final_norm/scale"],
                       outer["head/lm_head/kernel"], eps=eps, precision=precision)
            out.append(np.asarray(lg[:len(r)]))
    return out, [m[r] for m, r in zip(margins, rows)], flips


def decided_margin(cfg: dict) -> float:
    """A routing choice counts as DECIDED where the k-th selection score
    leads the next by more than one step of the served type at 1 (bfloat16:
    2^-7; the scores are sigmoids in (0, 1)): a choice decided by less does
    not survive six layers computed in that type, whoever computes them — on
    the chip the widest gap over positions led by 2^-8..2^-7 was 0.12-0.39,
    by more than 2^-7 at most 0.10 (PERF.md 2) — and with 128 experts nine
    positions in ten have such a choice in some layer."""
    return float(jnp.finfo(_served_dtype(cfg)).eps)


def score_served(cfg: dict, seed: int, sample: list[dict], *,
                 precision: str = "f32", fault=None) -> dict:
    """*sample*: ``[{"prompt": int array, "tokens": served token ids}]`` —
    the contract of ``reference_mistral.score_served``: per served token the
    gap by which its logit lies below the reference's best at its position.

    The family forces more than one number out of those gaps. With random
    weights, swapping the 8th expert of 128 for the 9th moves a position's
    logits by up to a logit's whole spread, and the two scores lie within
    rounding of each other at most positions: the WIDEST gap over all served
    tokens is set by such swaps at any precision, the control's included (on
    the chip: the program 0.52-1.18, the fp8 control 1.33-1.48; PERF.md 2).
    So three numbers are taken, each against a limit of its own from the
    configuration's ``served_gap_limits``:

    - ``all_max``: the widest gap over EVERY served token — a wrong token
      anywhere (a slot's, a page's) reads a logit's distance from the best,
      several spreads; the limit sits above what a routing swap can do;
    - ``all_mean``: the mean gap over every served token — what precision
      does to all of them at once (a swap moves one position, rounding moves
      each); the control fails here on every seed;
    - ``decided_max``: the widest gap over the tokens whose position's
      routing THIS REFERENCE finds decided (:func:`decided_margin`, in every
      expert layer; judged from the reference's own float32 scores alone,
      never from the program's choices; the control is held to the same
      positions) — the precision test on single tokens, where no swap
      excuses a gap.

    ``serve_window.correctness`` compares the one key ``logit_gap_max``: it
    is the LARGEST of the three as a share of its limit, so the cell's limit
    on it is 1 and a run is correct only inside all three. NaN (never
    correct) where no sampled position is decided. The notes give the three
    gaps, their limits and shares, which one binds, and the tokens compared."""
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
        best = lg.max(-1)
        got = lg[np.arange(len(tok)), tok]
        gaps.append(best - got)
        flips += int((lg.argmax(-1) != tok).sum())
        n += len(tok)
    allg, allm = np.concatenate(gaps), np.concatenate(margins)
    decided = allm > decided_margin(cfg)
    limits = cfg["served_gap_limits"]
    parts = {"all_max": float(allg.max()), "all_mean": float(allg.mean()),
             "decided_max": float(allg[decided].max()) if decided.any() else float("nan")}
    shares = {k: parts[k] / limits[k] for k in parts}
    binds = max(shares, key=lambda k: shares[k])
    # how the widest gap falls as the margin asked for rises: [margin, tokens, gap]
    by_margin = [[f * decided_margin(cfg), int((allm > f * decided_margin(cfg)).sum()),
                  float(allg[allm > f * decided_margin(cfg)].max(initial=0.0))]
                 for f in (0.0625, 0.125, 0.25, 0.5, 1.0, 2.0)]
    spread = float(np.mean([np.std(lg, axis=-1).mean() for lg in ref]))
    return {"logit_gap_max": (shares[binds] if decided.any() else float("nan")),
            "gaps": parts, "gap_limits": dict(limits), "gap_shares": shares, "binds": binds,
            "logit_gap_max_all": parts["all_max"], "logit_gap_mean": parts["all_mean"],
            "tokens": n, "tokens_decided": int(decided.sum()),
            "not_reference_best": flips, "logit_std": spread,
            "per_request_max": [float(g[m > decided_margin(cfg)].max(initial=0.0))
                                for g, m in zip(gaps, margins)],
            "per_request_max_all": [float(g.max()) for g in gaps],
            "per_request_mean": [float(g.mean()) for g in gaps],
            "by_margin": by_margin, "router_flips": router_flips,
            "router_choices": len(seqs) * (-(-max(map(len, seqs)) // PAD) * PAD)
            * (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])}
