"""Plain reference of the BERT-base MLM training step, as this repo runs it.

Straight ``jax.numpy`` in float32 with ``highest`` matmul precision: no flax,
no scan, no kernel, nothing imported from the program. It follows the
program's equations, which depart from Devlin et al. 2018 where the
configuration file says so (pre-LayerNorm blocks, no token-type embedding, no
embedding LayerNorm, no attention biases, tanh-GELU, LayerNorm eps 1e-6, a
final LayerNorm, dropout off).

Weights come from the seed by ``weights.leaf`` under the names of the
program's parameter tree; the reference makes them itself.

``train_steps`` follows the first steps of the job: MLM masking drawn from
the step's key exactly as the job states it (15 %: 80 % [MASK], 10 % random,
10 % kept), loss = mean CE over masked positions, global-norm clip, AdamW.
Gradients are summed over blocks of rows so that any batch fits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.harness import lowp, weights


def param_shapes(cfg: dict) -> dict:
    d, L, h = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["num_attention_heads"]
    hd, f, v, p = d // h, cfg["intermediate_size"], cfg["vocab_size"], cfg["max_position_embeddings"]
    b = "encoder/blocks/"
    return {
        "encoder/tok_embed/embedding": (v, d),
        "encoder/pos_embed/embedding": (p, d),
        b + "attn_norm/scale": (L, d), b + "attn_norm/bias": (L, d),
        b + "attn/q_proj/kernel": (L, d, h, hd),
        b + "attn/k_proj/kernel": (L, d, h, hd),
        b + "attn/v_proj/kernel": (L, d, h, hd),
        b + "attn/o_proj/kernel": (L, h, hd, d),
        b + "mlp_norm/scale": (L, d), b + "mlp_norm/bias": (L, d),
        b + "mlp/up_proj/kernel": (L, d, f), b + "mlp/up_proj/bias": (L, f),
        b + "mlp/down_proj/kernel": (L, f, d), b + "mlp/down_proj/bias": (L, d),
        "encoder/final_norm/scale": (d,), "encoder/final_norm/bias": (d,),
        "mlm_dense/kernel": (d, d), "mlm_dense/bias": (d,),
        "mlm_norm/scale": (d,), "mlm_norm/bias": (d,),
        "mlm_bias": (v,),
    }


def make_params(cfg: dict, seed) -> dict:
    shapes = param_shapes(cfg)

    @jax.jit
    def make(s):
        return {n: weights.leaf(s, n, shp, jnp.float32) for n, shp in shapes.items()}
    return make(weights.seed_operand(seed))


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def forward(cfg: dict, p: dict, inputs, precision: str = "f32"):
    """[B, S] int32 -> [B, S, V] f32 logits."""
    mm = functools.partial(lowp.einsum, precision=precision)
    eps = cfg["layer_norm_eps"]
    L = cfg["num_hidden_layers"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    s = inputs.shape[1]
    x = p["encoder/tok_embed/embedding"][inputs] + p["encoder/pos_embed/embedding"][:s][None]
    b = "encoder/blocks/"
    for l in range(L):
        h = _layer_norm(x, p[b + "attn_norm/scale"][l], p[b + "attn_norm/bias"][l], eps)
        q = mm("bsd,dhk->bshk", h, p[b + "attn/q_proj/kernel"][l])
        k = mm("bsd,dhk->bshk", h, p[b + "attn/k_proj/kernel"][l])
        v = mm("bsd,dhk->bshk", h, p[b + "attn/v_proj/kernel"][l])
        sc = mm("bqhk,bthk->bhqt", q, k) * (hd ** -0.5)
        pr = jax.nn.softmax(sc, axis=-1)
        o = mm("bhqt,bthk->bqhk", pr, v)
        x = x + mm("bqhk,hkd->bqd", o, p[b + "attn/o_proj/kernel"][l])
        h = _layer_norm(x, p[b + "mlp_norm/scale"][l], p[b + "mlp_norm/bias"][l], eps)
        u = mm("bsd,df->bsf", h, p[b + "mlp/up_proj/kernel"][l]) + p[b + "mlp/up_proj/bias"][l]
        x = x + mm("bsf,fd->bsd", _gelu_tanh(u), p[b + "mlp/down_proj/kernel"][l]) \
            + p[b + "mlp/down_proj/bias"][l]
    x = _layer_norm(x, p["encoder/final_norm/scale"], p["encoder/final_norm/bias"], eps)
    x = mm("bsd,de->bse", x, p["mlm_dense/kernel"]) + p["mlm_dense/bias"]
    x = _gelu_tanh(x)
    x = _layer_norm(x, p["mlm_norm/scale"], p["mlm_norm/bias"], eps)
    return mm("bsd,vd->bsv", x, p["encoder/tok_embed/embedding"]) + p["mlm_bias"]


def mask_tokens(tokens, key, *, vocab_size: int, mask_id: int, mask_prob: float):
    """The job's masking rule: select ``mask_prob`` of the positions; of
    those 80 % become [MASK], 10 % a random id, 10 % stay. The three draws
    come from ``split(key, 3)`` in this order: selection, action, random id."""
    r1, r2, r3 = jax.random.split(key, 3)
    selected = jax.random.uniform(r1, tokens.shape) < mask_prob
    action = jax.random.uniform(r2, tokens.shape)
    random_tok = jax.random.randint(r3, tokens.shape, 0, vocab_size)
    inputs = jnp.where(selected & (action < 0.8), mask_id, tokens)
    inputs = jnp.where(selected & (action >= 0.8) & (action < 0.9), random_tok, inputs)
    return inputs, tokens, selected.astype(jnp.float32)


def _block_sum_ce(cfg, p, inputs, targets, w, *, precision):
    logits = forward(cfg, p, inputs, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - picked) * w)


def loss_and_grad(cfg: dict, p: dict, inputs, targets, w, *, block_rows: int,
                  precision: str = "f32"):
    """Mean CE over the masked positions of the whole batch, and its
    gradient, accumulated over blocks of ``block_rows`` rows."""
    vg = _value_and_grad(cfg_key(cfg), precision)
    n = inputs.shape[0]
    total = jnp.zeros((), jnp.float32)
    grads = None
    for i in range(0, n, block_rows):
        sl = slice(i, i + block_rows)
        val, g = vg(p, inputs[sl], targets[sl], w[sl])
        total = total + val
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    denom = jnp.maximum(jnp.sum(w), 1.0)
    return total / denom, jax.tree.map(lambda g: g / denom, grads)


class cfg_key(dict):
    """A hashable view of the config dict (so jit can close over it)."""
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@functools.lru_cache(maxsize=None)
def _value_and_grad(cfg: cfg_key, precision: str):
    return jax.jit(jax.value_and_grad(
        functools.partial(_block_sum_ce, cfg, precision=precision)))


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


@jax.jit
def _clip(grads, max_norm):
    gn = _global_norm(grads)
    scale = jnp.where(gn < max_norm, 1.0, max_norm / gn)   # optax.clip_by_global_norm
    return jax.tree.map(lambda g: g * scale, grads)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps"))
def _adamw(p, g, m, v, t, lr, wd, *, b1=0.9, b2=0.999, eps=1e-8):
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    p = jax.tree.map(
        lambda p_, m_, v_: p_ - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps) + wd * p_),
        p, m, v)
    return p, m, v


def leaf_norms(tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in tree.items()}


def train_steps(cfg: dict, job: dict, seed: int, batches: list, step_keys: list, *,
                steps: int = 3, block_rows: int = 4, precision: str = "f32",
                fault: str | None = None) -> dict:
    """Follow the job's first *steps* steps from the seed's weights.

    *batches*: the token rows [B, S] each step is fed (the same feed the
    program got); *step_keys*: the key each step masks with. Returns the
    losses, the per-leaf norm of the first gradient as the optimizer gets it
    (after the clip), and the per-leaf norm of the parameters' change.

    *fault* plants one of the faults the cell can have into this reference,
    so that it can be put in the program's place: ``half_batch`` (second
    half of the rows left out, the mean taken over the rest),
    ``no_exchange:<n>`` (only the first of *n* equal shards' gradient is
    applied — the exchange between chips left out).
    """
    with jax.default_matmul_precision("highest"):
        p0 = make_params(cfg, seed)
        p = p0
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        losses, first_grad = [], None
        for t in range(1, steps + 1):
            tokens = jnp.asarray(batches[t - 1], jnp.int32)
            inputs, targets, w = mask_tokens(
                tokens, step_keys[t - 1], vocab_size=cfg["vocab_size"],
                mask_id=job["mask_id"], mask_prob=job["mask_prob"])
            if fault == "half_batch":
                keep = tokens.shape[0] // 2
                inputs, targets, w = inputs[:keep], targets[:keep], w[:keep]
            elif fault and fault.startswith("no_exchange:"):
                keep = tokens.shape[0] // int(fault.split(":")[1])
                inputs, targets, w = inputs[:keep], targets[:keep], w[:keep]
            loss, g = loss_and_grad(cfg, p, inputs, targets, w,
                                    block_rows=min(block_rows, inputs.shape[0]),
                                    precision=precision)
            g = _clip(g, jnp.float32(job["grad_clip"]))
            if t == 1:
                first_grad = leaf_norms(g)
            p, m, v = _adamw(p, g, m, v, jnp.float32(t), jnp.float32(job["lr"]),
                             jnp.float32(job["weight_decay"]))
            losses.append(float(loss))
        change = leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0))
    return {"losses": losses, "first_grad": first_grad, "change": change}
