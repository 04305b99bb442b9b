"""Mesh-sharded attention (ops.attention.make_mesh_attention_fn) + the
act_embed activation-sharding rule — the two round-5 multi-chip fixes.

Both defects were invisible to correctness tests (GSPMD replication and
a silently-pruned batch axis change only per-device memory/compute), so
these tests pin the SHARDING facts, not just values: outputs must carry
batch over (data, fsdp) and heads over tensor.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from k8s_distributed_deeplearning_tpu.ops import attention as att
from k8s_distributed_deeplearning_tpu.parallel import mesh as mesh_lib
from k8s_distributed_deeplearning_tpu.parallel import sharding


@pytest.fixture(scope="module")
def mesh3():
    return mesh_lib.make_mesh({"data": 2, "fsdp": 2, "tensor": 2})


def _qkv(b=4, s=64, h=8, hkv=4, d=32, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_mesh_attention_matches_unwrapped(mesh3, impl):
    dtype = jnp.bfloat16 if impl == "flash" else jnp.float32
    q, k, v = _qkv(dtype=dtype)
    fn = att.make_mesh_attention_fn(mesh3, impl=impl)
    ref = att.multi_head_attention(q, k, v, causal=True, impl=impl)
    out = jax.jit(lambda a, b_, c: fn(a, b_, c, causal=True))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-5 if impl == "xla" else 2e-2, atol=1e-5 if impl == "xla"
        else 1e-2)
    # The sharding fact the fix exists for: batch over data x fsdp,
    # heads over tensor — NOT replicated.
    assert out.sharding.spec == P(("data", "fsdp"), None, "tensor")


def test_mesh_attention_segments_and_grads(mesh3):
    q, k, v = _qkv()
    b, s = q.shape[:2]
    seg = jnp.concatenate([jnp.ones((b, s // 2), jnp.int32),
                           2 * jnp.ones((b, s // 2), jnp.int32)], axis=1)
    fn = att.make_mesh_attention_fn(mesh3, impl="xla")

    def loss(f, q, k, v):
        return f(q, k, v, causal=True,
                 segment_ids=seg).astype(jnp.float32).sum()

    ref = jax.grad(lambda *a: loss(
        lambda *x, **kw: att.multi_head_attention(*x, impl="xla", **kw),
        *a), argnums=(0, 1, 2))(q, k, v)
    got = jax.jit(jax.grad(lambda *a: loss(fn, *a),
                           argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-5)


def test_mesh_attention_indivisible_falls_back(mesh3):
    # b=3 does not divide the 4-way batch factor: must still be correct
    # (the wrapper falls back to the unwrapped op, never errors).
    q, k, v = _qkv(b=3)
    fn = att.make_mesh_attention_fn(mesh3, impl="xla")
    ref = att.multi_head_attention(q, k, v, causal=True, impl="xla")
    out = jax.jit(lambda a, b_, c: fn(a, b_, c, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=1e-5)


def test_mesh_attention_trivial_mesh_is_plain():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    fn = att.make_mesh_attention_fn(mesh, impl="xla")
    q, k, v = _qkv(b=2, s=16)
    ref = att.multi_head_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(fn(q, k, v, causal=True)),
                               np.asarray(ref), rtol=2e-5, atol=1e-5)


def test_mesh_attention_general_mask(mesh3):
    q, k, v = _qkv()
    b, s = q.shape[:2]
    row = jnp.arange(s)[:, None]
    col = jnp.arange(s)[None, :]
    pmask = jnp.broadcast_to(((col < s // 2) | (row >= col))[None, None],
                             (b, 1, s, s))
    fn = att.make_mesh_attention_fn(mesh3, impl="xla")
    ref = att.multi_head_attention(q, k, v, mask=pmask, impl="xla")
    out = jax.jit(lambda a, b_, c, m: fn(a, b_, c, mask=m))(q, k, v, pmask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=1e-5)


def test_llama_loss_parity_with_mesh_attention(mesh3):
    """Full-model check: the shard_map'd attention slots into the scanned,
    remat'd stack (attention_fn as a static Block attribute) and changes
    nothing numerically."""
    from k8s_distributed_deeplearning_tpu.models import llama

    cfg = llama.config_tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                            dtype=jnp.float32, remat=True)
    model = llama.LlamaLM(cfg)
    toks = jax.random.randint(jax.random.key(1), (4, 17), 0, cfg.vocab_size)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    base, _ = llama.loss_fn(model, params, {"tokens": toks})
    fn = att.make_mesh_attention_fn(mesh3, impl="xla")
    with mesh3:
        wrapped, _ = jax.jit(lambda p, b: llama.loss_fn(
            model, p, b, attention_fn=fn))(params, {"tokens": toks})
    np.testing.assert_allclose(float(wrapped), float(base), rtol=2e-5)


def test_act_embed_rule_keeps_batch_on_both_axes():
    """The act_embed regression: an activation constrained
    ("batch", "seq", "act_embed") on a data x fsdp mesh must shard batch
    over BOTH axes — the old ("batch", "seq", "embed") constraint lost
    fsdp to flax's duplicate-axis prune and replicated activations
    fsdp-fold-x."""
    import flax.linen as nn

    mesh = mesh_lib.make_mesh({"data": 2, "fsdp": 4})
    rules = sharding.resolve_rules(mesh)

    def f(x):
        with nn.logical_axis_rules(rules):
            return nn.with_logical_constraint(
                x * 2, ("batch", "seq", "act_embed"))

    x = jax.device_put(jnp.ones((8, 16, 32)),
                       NamedSharding(mesh, P(("data", "fsdp"))))
    with mesh:
        y = jax.jit(f)(x)
    assert y.sharding.spec == P(("data", "fsdp"),)


def test_mesh_attention_broadcast_batch_mask(mesh3):
    """A mask carrying a size-1 batch dim ([1, 1, s, s] — the common
    'same additive mask for every row' shape) must ride the SHARDED path:
    broadcast dims are replicated by the spec builder, so batch
    divisibility doesn't apply to them. Before the fix this shape fell
    back to unwrapped attention (1 % bfac != 0)."""
    q, k, v = _qkv()
    s = q.shape[1]
    row = jnp.arange(s)[:, None]
    col = jnp.arange(s)[None, :]
    pmask = (((col < s // 2) | (row >= col))[None, None]).astype(jnp.bool_)
    assert pmask.shape == (1, 1, s, s)
    fn = att.make_mesh_attention_fn(mesh3, impl="xla")
    ref = att.multi_head_attention(q, k, v, mask=pmask, impl="xla")
    out = jax.jit(lambda a, b_, c, m: fn(a, b_, c, mask=m))(q, k, v, pmask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=1e-5)
    # The sharded path actually ran: output lands batch-over-data x fsdp,
    # heads-over-tensor, not the fallback's unsharded layout.
    assert out.sharding.spec == P(("data", "fsdp"), None, "tensor")
