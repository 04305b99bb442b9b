"""Plain reference of the ``ssm-moe`` family (Nemotron-H layout: Mamba-2
mixers, a few grouped-query attention layers with no positional embedding,
latent sparse-expert layers with non-gated squared-ReLU experts and one shared
expert, every layer ONE sub-layer, an untied head): one full causal forward
pass per sequence in float32 at ``highest`` matmul precision — no cache, no
state arena, no paging, no batching, no kernel, the state-space recurrence as
a ``lax.scan`` over TOKENS (the definition, not the chunked form), the held
experts by a plain scan over all of them with no token dropped, nothing
imported from the program.

The equations, for layer l with input x [T, d] (``configs/nemotron-3-super-
120b-ep4-d11.json`` names the source; PERF.md section 4); the layer's letter
in ``hybrid_override_pattern`` says which ONE it is:

    x <- x + f(RMSNorm(x)),  eps = norm_eps;   x^ = RMSNorm(x)
    M:  [z | xBC | dt] = W_in x^   (d -> I | I + 2 G N | H;  I = H P, no bias)
        xBC <- silu(conv(xBC))     (depthwise, causal, conv_kernel taps, zeros
                                    before the sequence, WITH bias)
        x [H, P], B [G, N], C [G, N] = split(xBC);  head h uses group h // (H / G)
        D_t = softplus(dt_t + dt_bias)  (no clamp);   A = -exp(A_log)
        h_t = exp(D_t A) h_(t-1) + D_t x_t (x) B_t     (h [H, P, N], h_(-1) = 0)
        y_t = h_t C_t + D x_t;   y <- RMSNorm_g(y * silu(z))  (the gate BEFORE
        the norm; the norm over each of the G groups' I / G lanes, one learned
        gain of I);   out = W_out y
    *:  q, k, v = W_q x^, W_k x^, W_v x^ (Hq x hd, KV x hd, KV x hd); NO
        positional embedding; causal softmax at hd^-1/2, each group of
        Hq / KV query heads on one KV head;  W_o
    E:  s = sigmoid(W_r x^) in f32 over ALL router_outputs;  T = the
        num_experts_per_tok largest of s + b (b: the selection bias, for the
        choice only);  g_e = routed_scaling_factor s_e / sum_T s
        u = W_fc1 x^ (d -> moe_latent_size)
        out = W_fc2 (sum_{e in T, e held here} g_e W2_e relu(W1_e u)^2)
              + W2_s relu(W1_s x^)^2          (the shared expert, on x^ itself)
    after the last layer: RMSNorm, then logits = W_head x (untied)

The experts HELD are ``n_routed_experts`` of ``router_outputs``, from
``expert_offset`` (0 in the published file): one chip's share of an
expert-parallel layer — the router, the top-k and the normalisation run over
all ``router_outputs``; the part of the sum this chip's experts give goes on,
as in the program. No code stands in for the absent chips.

Weights are made again from the seed ONE LAYER AT A TIME (``weights.leaf``'s
rule — normal, std 0.02, norm gains 1 + the same — except the five leaves
that the published initialisation does not draw so, :func:`leaf_kind`), in
the served type's rounding, then float32; every sampled sequence goes through
that layer before the next is made. Sequences are padded to ONE multiple of
``PAD``: causal layers never let a pad reach an earlier position.

``score_served`` has ``reference_conv_moe``'s contract. ``precision`` below
f32 is the CONTROL (the router and the recurrence stay float32 there: the
program keeps them so). ``fault="alter"`` alters one served token in eight;
``fault="zero_state"`` stands a faulty program in the program's place: the
same pass with every state-space layer's state set to zero before token
``zero_state_at`` (where a second prefill chunk would start), its argmax
tokens judged against the reference proper.
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
HI = jax.lax.Precision.HIGHEST


def pattern(cfg: dict) -> str:
    """The letters of the layers that are run: the first ``num_hidden_layers``
    of the published ``hybrid_override_pattern``."""
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def mamba_sizes(cfg: dict) -> tuple[int, int, int, int, int]:
    """(H heads, P lanes a head, G groups, N state columns, K taps)."""
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"], cfg["conv_kernel"])


def layer_shapes(cfg: dict, layer: int) -> dict:
    d, kind = cfg["hidden_size"], pattern(cfg)[layer]
    if kind == "M":
        h, p, g, n, k = mamba_sizes(cfg)
        inner, conv = h * p, h * p + 2 * g * n
        return {"attn_norm/scale": (d,), "attn/in_proj/kernel": (d, inner + conv + h),
                "attn/conv": (k, conv), "attn/conv_bias": (conv,), "attn/dt_bias": (h,),
                "attn/A_log": (h,), "attn/D": (h,), "attn/norm/scale": (inner,),
                "attn/out_proj/kernel": (inner, d)}
    if kind == "*":
        h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
        return {"attn_norm/scale": (d,), "attn/q_proj/kernel": (d, h, hd),
                "attn/k_proj/kernel": (d, kv, hd), "attn/v_proj/kernel": (d, kv, hd),
                "attn/o_proj/kernel": (h, hd, d)}
    if kind != "E":
        raise ValueError(f"layer {layer} is {kind!r}; this family runs M, * and E")
    lat, f, held = cfg["moe_latent_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    fs, e = cfg["moe_shared_expert_intermediate_size"], cfg["router_outputs"]
    return {"mlp_norm/scale": (d,), "mlp/router": (d, e), "mlp/router_bias": (e,),
            "mlp/fc1_latent/kernel": (d, lat), "mlp/fc2_latent/kernel": (lat, d),
            "mlp/w_up": (held, lat, f), "mlp/w_down": (held, f, lat),
            "mlp/shared/up_proj/kernel": (d, fs), "mlp/shared/down_proj/kernel": (fs, d)}


def outer_shapes(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"transformer/tok_embed/embedding": (v, d),
            "transformer/final_norm/scale": (d,), "head/lm_head/kernel": (d, v)}


def _served_dtype(cfg: dict):
    return jnp.dtype(cfg.get("torch_dtype", "bfloat16"))


def leaf_kind(name: str) -> str:
    """How leaf *name* is drawn. ``weights._kind``'s three, and the five
    leaves of a Mamba-2 mixer that the published initialisation does not
    draw as N(0, 0.02) — drawn that way a state would be forgotten within two
    tokens and weigh a thousandth of the skip term, and no comparison would
    see a state carried wrongly: ``A_log`` (A uniform in [1, 16]), ``dt_bias``
    (the step log-uniform in [time_step_min, time_step_max], floored, through
    the inverse softplus), ``D`` (ones), and the convolution's taps and bias
    (uniform within 1 / sqrt(conv_kernel), the framework's default for a
    depthwise convolution)."""
    tail = name.rsplit("/", 1)[-1]
    if tail in ("A_log", "dt_bias", "D"):
        return tail
    if tail in ("conv", "conv_bias"):
        return "tap"
    return weights._kind(name)


def _consts(cfg: dict) -> tuple:
    return (float(cfg["time_step_min"]), float(cfg["time_step_max"]),
            float(cfg["time_step_floor"]), int(cfg["conv_kernel"]))


def draw(key, shape, kind: str, consts: tuple):
    """The float32 values of a leaf of *kind* (:func:`leaf_kind`) from *key*."""
    t_min, t_max, t_floor, taps = consts
    shape = tuple(shape)
    if kind == "D":
        return jnp.ones(shape, jnp.float32)
    if kind in ("A_log", "dt_bias", "tap"):
        u = jax.random.uniform(key, shape, jnp.float32)
        if kind == "A_log":
            return jnp.log(1.0 + 15.0 * u)
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
    names' checksums as a TRACED operand (in the same order): the layers are
    three kinds, and a layer's full names differ only in its number, so three
    programs make all the weights."""
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


def mamba(h, w, mm, *, sizes, eps: float, zero_state_at=None):
    """The Mamba-2 mixer of one layer: h [T, d] (normed) -> [T, d]. The
    recurrence is a scan over tokens. *zero_state_at*: the planted fault — the
    state is set to zero before that token."""
    heads, p, g, n, taps_k = sizes
    inner, t = heads * p, h.shape[0]
    conv = inner + 2 * g * n
    zxd = mm("td,dk->tk", h, w["attn/in_proj/kernel"])
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + conv], zxd[:, inner + conv:]
    taps = w["attn/conv"]
    xp = jnp.pad(xbc, ((taps_k - 1, 0), (0, 0)))            # zeros before the sequence
    xbc = jax.nn.silu(sum(taps[j] * xp[j:j + t] for j in range(taps_k)) + w["attn/conv_bias"])
    r = heads // g
    x = xbc[:, :inner].reshape(t, g, r, p)
    bmat = xbc[:, inner:inner + g * n].reshape(t, g, n)
    cmat = xbc[:, inner + g * n:].reshape(t, g, n)
    step = jax.nn.softplus(dt + w["attn/dt_bias"]).reshape(t, g, r)
    a = -jnp.exp(w["attn/A_log"]).reshape(g, r)

    def one_token(state, now):                               # state [G, R, P, N]
        i, x_t, d_t, b_t, c_t = now
        if zero_state_at is not None:
            state = jnp.where(i == zero_state_at, 0.0, state)
        state = (jnp.exp(d_t * a)[..., None, None] * state
                 + (d_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, jnp.sum(state * c_t[:, None, None, :], axis=-1)

    _, y = jax.lax.scan(one_token, jnp.zeros((g, r, p, n), jnp.float32),
                        (jnp.arange(t), x, step, bmat, cmat))
    y = y + w["attn/D"].reshape(g, r)[..., None] * x
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return mm("tk,kd->td", y.reshape(t, inner) * w["attn/norm/scale"],
              w["attn/out_proj/kernel"])


def attention(h, w, mm):
    """Grouped-query attention with no positional embedding: h [T, d] -> [T, d]."""
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


def router(h, w_r, bias, k: int, scaling: float):
    """-> (chosen [T, k] expert ids over ALL the router's outputs, gates
    [T, k], margin [T]: by how much the k-th selection score leads the next
    one); float32 throughout."""
    s = jax.nn.sigmoid(jnp.dot(h, w_r, precision=HI))
    top, chosen = jax.lax.top_k(s + bias, k + 1)
    chosen = chosen[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=1)
    return (chosen, scaling * picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20),
            top[:, k - 1] - top[:, k])


def _relu2(x, w1, w2, mm):
    return mm("tf,fd->td", jnp.square(jax.nn.relu(mm("td,df->tf", x, w1))), w2)


def shared_expert(h, w, mm):
    return _relu2(h, w["mlp/shared/up_proj/kernel"], w["mlp/shared/down_proj/kernel"], mm)


def routed_experts(h, w, mm, *, k: int, scaling: float, offset: int):
    """The held experts' part of the layer, back in the hidden width:
    ``W_fc2 (sum over the chosen experts held here)``; -> (that, the
    router's margin, the experts chosen)."""
    chosen, gates, margin = router(h, w["mlp/router"], w["mlp/router_bias"], k, scaling)
    u = mm("td,dk->tk", h, w["mlp/fc1_latent/kernel"])

    def one_expert(y, ew):                                   # every held expert, one by one
        e, w1, w2 = ew
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=1)           # [T]
        return y + gate[:, None] * _relu2(u, w1, w2, mm), None

    held = w["mlp/w_up"].shape[0]
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                        (offset + jnp.arange(held), w["mlp/w_up"], w["mlp/w_down"]))
    return mm("tk,kd->td", y, w["mlp/fc2_latent/kernel"]), margin, chosen


_LAYER_KEYS = ("norm_eps", "num_experts_per_tok", "routed_scaling_factor", "mamba_num_heads",
               "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel")


def _cfg_key(cfg: dict) -> str:
    """What ``layer_forward`` reads of the configuration beside the weights'
    own shapes, as one hashable (JSON) string for ``jax.jit``."""
    return json.dumps({**{k: cfg[k] for k in _LAYER_KEYS},
                       "expert_offset": int(cfg.get("expert_offset", 0)),
                       "torch_dtype": str(_served_dtype(cfg))}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("key", "precision", "zero_state_at"))
def layer_forward(x, w, *, key: str, precision: str = "f32", zero_state_at=None):
    """x: [T, d] f32 -> ([T, d], router flips in this layer, the router's
    margin at each position — infinite outside an expert layer). The layer's
    kind is what its weights are: ``attn/conv`` (M), ``attn/q_proj`` (*) or
    ``mlp/router`` (E)."""
    cfg = json.loads(key)
    mm = functools.partial(lowp.einsum, precision=precision)
    eps, t = cfg["norm_eps"], x.shape[0]
    none = (jnp.zeros((), jnp.int32), jnp.full((t,), jnp.inf))
    if "attn/conv" in w:
        return (x + mamba(_rms_norm(x, w["attn_norm/scale"], eps), w, mm,
                          sizes=mamba_sizes(cfg), eps=eps, zero_state_at=zero_state_at), *none)
    if "attn/q_proj/kernel" in w:
        return (x + attention(_rms_norm(x, w["attn_norm/scale"], eps), w, mm), *none)
    h = _rms_norm(x, w["mlp_norm/scale"], eps)
    k, scaling = cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]
    y, margin, chosen = routed_experts(h, w, mm, k=k, scaling=scaling,
                                       offset=cfg["expert_offset"])
    rounded, _, _ = router(h.astype(jnp.dtype(cfg["torch_dtype"])).astype(jnp.float32),
                           w["mlp/router"], w["mlp/router_bias"], k, scaling)
    flips = jnp.sum(jnp.any(jnp.sort(chosen, 1) != jnp.sort(rounded, 1), axis=1))
    return x + y + shared_expert(h, w, mm), flips.astype(jnp.int32), margin


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x_rows, scale, kernel, *, eps: float, precision: str):
    return lowp.einsum("td,dv->tv", _rms_norm(x_rows, scale, eps), kernel, precision)


def forward_logits(cfg: dict, seed: int, seqs: list[np.ndarray], rows: list[np.ndarray],
                   precision: str = "f32", zero_state_at=None
                   ) -> tuple[list[np.ndarray], list[np.ndarray], int]:
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
            done = [layer_forward(x, w, key=key, precision=precision,
                                  zero_state_at=zero_state_at) for x in xs]
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
                       outer["head/lm_head/kernel"], eps=float(cfg["norm_eps"]),
                       precision=precision)
            out.append(np.asarray(lg[:len(r)]))
    return out, [m[r] for m, r in zip(margins, rows)], flips


def decided_margin(cfg: dict) -> float:
    """A routing choice counts as DECIDED where the k-th selection score
    leads the next by more than one step of the served type at 1 (bfloat16:
    2^-7; the scores are sigmoids in (0, 1))."""
    return float(jnp.finfo(_served_dtype(cfg)).eps)


def score_served(cfg: dict, seed: int, sample: list[dict], *,
                 precision: str = "f32", fault=None) -> dict:
    """*sample*: ``[{"prompt": int array, "tokens": served token ids}]`` — per
    served token the gap by which its logit lies below the reference's best
    at its position, as three numbers (``all_max``, ``all_mean``,
    ``decided_max``: ``reference_conv_moe.score_served`` says what each
    catches); those the configuration's ``served_gap_limits`` names are each
    held to their limit there, the others are printed in the notes only.
    ``serve_window.correctness`` compares the one key ``logit_gap_max``: the
    LARGEST of the named gaps as a share of its limit, so the cell's limit on
    it is 1. With ``precision`` below f32, or ``fault="zero_state"``, the
    tokens judged are those a pass so altered would serve."""
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
    if precision != "f32" or fault == "zero_state":
        low, _, _ = forward_logits(
            cfg, seed, seqs, rows, precision,
            zero_state_at=int(cfg["zero_state_at"]) if fault == "zero_state" else None)
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
            "router_choices": (len(seqs) * (-(-max(map(len, seqs)) // PAD) * PAD)
                               * pattern(cfg).count("E"))}
