"""Grouped-GEMM (ragged matmul) kernel tests — ops/pallas_gmm.

Run in interpret mode on the CPU mesh (conftest), exercising the exact
code path TPUs compile (pallas_flash convention). Covers the layout
builder (block-aligned spans, empty groups, tail blocks), forward parity
against the dense reference, both custom-VJP gradients, and the serving
form whose epilogue makes the experts' hidden activation (``gmm_act``)
alone and inside both grouped serving dispatches.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu.ops import pallas_gmm as g


def _random_case(seed, sizes, e, k, n, bm, dtype=jnp.float32):
    sizes = jnp.asarray(sizes, jnp.int32)
    total = int(sizes.sum())
    layout = g.grouped_layout(sizes, total, block_m=bm)
    rng = np.random.default_rng(seed)
    lhs = np.zeros((layout.m_pad, k), np.float32)
    off = np.asarray(layout.row_offset)
    for i, s in enumerate(np.asarray(sizes)):
        lhs[off[i]:off[i] + s] = rng.standard_normal((s, k))
    rhs = rng.standard_normal((e, k, n))
    return (layout, jnp.asarray(lhs, dtype), jnp.asarray(rhs, dtype))


def test_layout_spans_and_flags():
    sizes = jnp.array([100, 0, 300, 57], jnp.int32)
    lay = g.grouped_layout(sizes, 512, block_m=128)
    # Spans: ceil(100/128)=1, max(1,0)=1, ceil(300/128)=3, ceil(57/128)=1
    assert lay.m_pad == (512 // 128 + 4) * 128
    np.testing.assert_array_equal(lay.row_offset, [0, 128, 256, 640])
    np.testing.assert_array_equal(lay.block_expert, [0, 1, 2, 2, 2, 3, 3, 3])
    # Block 1 is the empty group's mandatory dead block; tail blocks dead.
    np.testing.assert_array_equal(lay.block_live, [1, 0, 1, 1, 1, 1, 0, 0])
    np.testing.assert_array_equal(lay.block_first, [1, 1, 1, 0, 0, 1, 0, 0])


def test_layout_all_one_expert():
    """Worst-case imbalance: every row lands in one group."""
    sizes = jnp.array([0, 256, 0, 0], jnp.int32)
    lay = g.grouped_layout(sizes, 256, block_m=128)
    assert int(lay.block_live.sum()) == 2   # exactly the real blocks
    assert int(lay.block_first.sum()) == 4  # every group initializes


@pytest.mark.parametrize("sizes", [[100, 0, 300, 57], [0, 0, 0, 512],
                                   [128, 128, 128, 128]])
def test_gmm_forward_matches_reference(sizes):
    layout, lhs, rhs = _random_case(0, sizes, 4, 128, 256, 128)
    out = jax.jit(lambda l, r: g.gmm(l, r, layout))(lhs, rhs)
    ref = g.gmm_reference(lhs, rhs, layout)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_gmm_grads_match_reference():
    layout, lhs, rhs = _random_case(1, [64, 200, 0, 248], 4, 128, 256, 128)

    def loss(fn, l, r):
        return jnp.sum(fn(l, r) ** 2)

    ga = jax.grad(lambda l, r: loss(lambda a, b: g.gmm(a, b, layout), l, r),
                  argnums=(0, 1))(lhs, rhs)
    gr = jax.grad(lambda l, r: loss(
        lambda a, b: g.gmm_reference(a, b, layout), l, r),
        argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(ga[0]), np.asarray(gr[0]),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(ga[1]), np.asarray(gr[1]),
                               rtol=1e-4, atol=1e-3)


def test_gmm_bf16_runs_and_is_close():
    layout, lhs, rhs = _random_case(2, [128, 128, 256, 0], 4, 128, 256, 128,
                                    dtype=jnp.bfloat16)
    out = jax.jit(lambda l, r: g.gmm(l, r, layout))(lhs, rhs)
    assert out.dtype == jnp.bfloat16
    ref = g.gmm_reference(lhs.astype(jnp.float32), rhs.astype(jnp.float32),
                          layout)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.1, atol=0.5)


def test_dead_rows_do_not_leak():
    """Padding rows must come out zero (live-flag skip writes zeros)."""
    layout, lhs, rhs = _random_case(3, [100, 0, 300, 57], 4, 128, 256, 128)
    out = jax.jit(lambda l, r: g.gmm(l, r, layout))(lhs, rhs)
    off = np.asarray(layout.row_offset)
    sizes = [100, 0, 300, 57]
    live = np.zeros(layout.m_pad, bool)
    for i, s in enumerate(sizes):
        live[off[i]:off[i] + s] = True
    # Fully-dead BLOCKS are zeroed by the kernel; partially-live blocks
    # compute zero rows (zero lhs x weights) — all padding rows end zero.
    assert float(jnp.abs(out[~live]).max()) == 0.0


def _activation_of(act, products):
    """The XLA activation the serving path applied before ``gmm_act``."""
    if act == "relu2":
        return jnp.square(jax.nn.relu(products[0]))
    return jax.nn.silu(products[0]) * products[1]


# (sizes, N, weight-block budget in bytes or None for the module's): a whole-N
# column block; three column blocks of 128 with an empty expert and dead tail
# blocks; every row on one expert (the others' one dead block each).
ACT_CASES = [([100, 0, 300, 57], 256, None),
             ([100, 0, 300, 57], 384, 2 * 2 * 128 * 128 * 4),
             ([0, 256, 0, 0], 384, 2 * 2 * 128 * 128 * 4)]


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize("sizes,n,budget", ACT_CASES,
                         ids=["whole-n", "three-column-blocks", "one-expert"])
def test_gmm_act_matches_the_two_products_and_the_reference(act, sizes, n, budget,
                                                            monkeypatch):
    """The activation made inside the grouped product equals the two-call form
    (``gmm`` per weight, then XLA's activation) and the dense reference, at one
    column block and at several; every padding row — round-up slack, an empty
    expert's dead block, the tail — comes out zero."""
    if budget is not None:
        monkeypatch.setattr(g, "_WIDE_BLOCK_BYTES", budget)
    nw = g.ACT_WEIGHTS[act]
    assert g.wide_block(n, 128, 4, nw) == (n if budget is None else 128)
    layout, lhs, rhs = _random_case(4, sizes, 4, 128, n, 128)
    weights = (rhs, jnp.flip(rhs, axis=2))[:nw]
    out = jax.jit(lambda l, ws: g.gmm_act(l, ws, layout, act))(lhs, weights)
    two_calls = _activation_of(act, [g.gmm(lhs, w, layout) for w in weights])
    ref = _activation_of(act, [g.gmm_reference(lhs, w, layout) for w in weights])
    for want in (two_calls, ref):
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-3)
    off = np.asarray(layout.row_offset)
    live = np.zeros(layout.m_pad, bool)
    for i, s in enumerate(sizes):
        live[off[i]:off[i] + s] = True
    assert float(jnp.abs(out[~live]).max()) == 0.0
    assert float(jnp.abs(out[live]).max()) > 0.0


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
def test_gmm_act_in_bf16_rounds_once(act):
    """In bf16 the products meet the activation in f32 and are rounded once:
    no farther from the float32 reference than the two-call form, which
    rounds each product before the activation and the activation again."""
    layout, lhs, rhs = _random_case(5, [128, 128, 256, 0], 4, 128, 256, 128,
                                    dtype=jnp.bfloat16)
    weights = (rhs, jnp.flip(rhs, axis=2))[:g.ACT_WEIGHTS[act]]
    out = jax.jit(lambda l, ws: g.gmm_act(l, ws, layout, act))(lhs, weights)
    assert out.dtype == jnp.bfloat16
    f32 = lambda a: a.astype(jnp.float32)
    ref = _activation_of(act, [g.gmm_reference(f32(lhs), f32(w), layout)
                               for w in weights])
    two_calls = _activation_of(act, [g.gmm(lhs, w, layout) for w in weights])
    err = lambda a: float(jnp.abs(f32(a) - ref).mean())
    assert err(out) <= err(two_calls)
    np.testing.assert_allclose(np.asarray(f32(out)), np.asarray(ref),
                               rtol=0.02, atol=0.02 * float(jnp.abs(ref).max()))


def test_gmm_act_refuses_a_weight_count_its_activation_does_not_take():
    layout, lhs, rhs = _random_case(6, [64, 64], 2, 128, 128, 128)
    with pytest.raises(ValueError, match="'swiglu' takes 2 weights"):
        g.gmm_act(lhs, (rhs,), layout, "swiglu")
    with pytest.raises(ValueError, match="'relu2' takes 1 weights"):
        g.gmm_act(lhs, (rhs, rhs), layout, "relu2")


def test_the_column_block_counts_every_weight_block_double_buffered():
    """The three expert cells' chunk shapes: the whole width where two (or
    one) double-buffered weight blocks fit the budget, else the widest
    divisor that does — and the power-of-two rule where 128 divides nothing."""
    assert g.wide_block(1792, 2048, 2, weights=2) == 1792     # lfm2: 2 x 7.3 MB
    assert g.wide_block(2688, 1024, 2, weights=1) == 2688     # nemotron
    assert g.wide_block(2048, 4096, 2, weights=2) == 1024     # sarvam: 2 x 8.4 MB
    assert g.wide_block(1024, 2688, 2) == 1024                # nemotron's W_down
    assert g.wide_block(48, 16, 4, weights=2) == 16


def _serving_layer(act, top_k, held):
    from k8s_distributed_deeplearning_tpu.models import moe
    from k8s_distributed_deeplearning_tpu.models.transformer import TransformerConfig
    cfg = TransformerConfig(dim=128, n_heads=2, mlp_dim=256, dtype=jnp.bfloat16)
    mo = moe.MoEConfig(num_experts=16, top_k=top_k, dispatch="ragged",
                       ragged_block_m=8, expert_act=act, expert_mlp_dim=256,
                       experts_held=held, score_fn="sigmoid", select_bias=True)
    return moe, moe.MoEMLP(cfg, mo)


@pytest.mark.parametrize("act,top_k,held", [
    ("swiglu", 2, None), ("relu2", 2, None),       # _ragged_core(decode=True)
    ("swiglu", 10, 8), ("relu2", 10, 8)],          # _ragged_serving_one_pass
    ids=["core-swiglu", "core-relu2", "one-pass-swiglu", "one-pass-relu2"])
def test_the_serving_dispatches_take_gmm_act_and_serve_the_two_call_forms_rows(
        act, top_k, held, monkeypatch):
    """Both grouped serving dispatches make the hidden activation in ONE
    ``moe_gmm_act`` call and give, within bf16 rounding, what the parent's
    two-call form (grouped products + XLA's activation) gives; training keeps
    the differentiable products and XLA's activation."""
    import flax.linen as nn
    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm
    moe, layer = _serving_layer(act, top_k, held)
    x = jax.random.normal(jax.random.key(7), (2, 32, 128), jnp.bfloat16)
    params = nn.meta.unbox(layer.init(jax.random.key(8), x, decode=True)["params"])
    assert moe.serving_dispatch(64, layer.moe) == "grouped"
    # a new function each time: jax caches a trace by the function traced
    serve = lambda: lambda p, x: layer.apply({"params": p}, x, decode=True)
    assert str(jax.make_jaxpr(serve())(params, x)).count("moe_gmm_act") == 1
    fused = jax.jit(serve())(params, x)
    monkeypatch.setattr(moe, "_serving_hidden", lambda mo, xs, wg, wu, layout: (
        moe._experts_hidden(lambda a, w: pallas_gmm.gmm(a, w, layout), xs, wg, wu)))
    assert "moe_gmm_act" not in str(jax.make_jaxpr(serve())(params, x))
    two_calls = jax.jit(serve())(params, x)
    f32 = lambda a: np.asarray(a, np.float32)
    scale = float(np.abs(f32(two_calls)).max())
    assert scale > 0
    np.testing.assert_allclose(f32(fused), f32(two_calls), rtol=0.03, atol=0.03 * scale)
    if held is None:                   # a whole layer also trains
        train = str(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(layer.apply(
            {"params": p}, x.astype(jnp.float32))[0].astype(jnp.float32))))(params))
        assert "moe_gmm" in train and "moe_gmm_act" not in train
