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


def _tiny_bert_step(axes, impl, n_devices=4):
    """One SGD step of a tiny BERT through ShardedTrainer, built the way the
    benchmark builds it (no ``attention_fn``): -> (loss, new parameters,
    the step's jaxpr, its compiled text)."""
    import optax

    from k8s_distributed_deeplearning_tpu.models import bert

    mesh = mesh_lib.make_mesh(axes, devices=jax.devices()[:n_devices])
    cfg = bert.config_tiny(dim=256, n_heads=4, n_layers=2, mlp_dim=256,
                           max_seq_len=128, attention_impl=impl,
                           dtype=jnp.float32)
    model = bert.BertMLM(cfg)

    def loss(p, batch, r):
        inputs, targets, w = bert.mask_tokens(
            batch["tokens"], r, vocab_size=cfg.vocab_size, mask_id=3,
            mask_prob=0.15)
        return bert.loss_fn(model, p, {"inputs": inputs, "targets": targets,
                                       "weights": w})
    trainer = sharding.ShardedTrainer(loss, optax.sgd(0.1), mesh)
    state = trainer.init(
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.key(0))
    step = trainer.make_step(donate=False)
    tokens = jax.random.randint(jax.random.key(1), (8, 128), 4, cfg.vocab_size)
    args = (state, trainer.shard_batch({"tokens": tokens}), jax.random.key(2))
    new, value, _ = step(*args)
    return (float(value), jax.tree.leaves(sharding.unbox(new.params)),
            jax.make_jaxpr(step)(*args).jaxpr,
            step.lower(*args).compile().as_text())


def _kernel_operands(jaxpr, inside_shard_map=False) -> list:
    """(name, q operand's shape, traced under a shard_map) per kernel call."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], eqn.invars[0].aval.shape,
                          inside_shard_map))
        inner = inside_shard_map or eqn.primitive.name == "shard_map"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_operands(sub, inner)
    return found


@pytest.mark.parametrize("axes,rows,lanes", [
    ({"data": 4}, 2, 256), ({"data": 2, "tensor": 2}, 4, 128)],
    ids=["data4", "data2-tensor2"])
def test_sharded_trainer_splits_the_flash_kernel(axes, rows, lanes):
    """``ShardedTrainer`` announces its mesh to attention at trace time
    (``ops.attention.program_mesh``), so a step built with NO
    ``attention_fn`` runs the flash kernels on each device's own rows (and
    heads): same loss and parameters as the einsum path on the same mesh and
    as one device, every kernel call under a shard_map with a per-device
    operand, and nothing gathered for it."""
    one_loss, one_params, _, _ = _tiny_bert_step({"data": 1}, "xla", 1)
    ref_loss, ref_params, _, ref_text = _tiny_bert_step(axes, "xla")
    loss, params, jaxpr, text = _tiny_bert_step(axes, "flash")
    assert loss == pytest.approx(ref_loss, rel=1e-6)
    assert loss == pytest.approx(one_loss, rel=1e-6)
    for got, want, single in zip(params, ref_params, one_params):
        np.testing.assert_allclose(got, want, atol=2e-6)
        np.testing.assert_allclose(got, single, atol=2e-6)
    kernels = _kernel_operands(jaxpr)
    assert {name for name, _, _ in kernels} == {"flash_attn_fwd",
                                                "flash_attn_bwd"}
    # 8 rows of 4 heads x 64 lanes globally
    assert all(shape == (rows, 128, lanes) and wrapped
               for _, shape, wrapped in kernels), kernels
    assert text.count("all-gather") <= ref_text.count("all-gather")
    if "tensor" not in axes:
        assert "all-gather" not in text


def test_flash_that_does_not_divide_the_mesh_takes_the_einsum_path():
    """3 rows on a 4-way batch axis: no kernel (it would be replicated), the
    einsum path, which GSPMD partitions as it can. A trivial mesh: the
    kernel, unwrapped."""
    mesh = mesh_lib.make_mesh({"data": 4}, devices=jax.devices()[:4])
    q, k, v = _qkv(b=3, h=4, hkv=4, d=64)

    def names(mesh):
        with att.program_mesh(mesh):
            jaxpr = jax.make_jaxpr(lambda q, k, v: att.multi_head_attention(
                q, k, v, impl="flash"))(q, k, v).jaxpr
        return [name for name, _, _ in _kernel_operands(jaxpr)]
    assert names(mesh) == []
    assert names(mesh_lib.make_mesh({"data": 1}, devices=jax.devices()[:1])) == [
        "flash_attn_fwd"]
    with att.program_mesh(mesh):
        out = att.multi_head_attention(q, k, v, impl="flash")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(att.dot_product_attention(q, k, v)),
        rtol=2e-5, atol=1e-5)
