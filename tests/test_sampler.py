"""The serving sampler (``serve/engine.py::_sample_slots``): what a sampling
row needs runs under one ``lax.cond`` on ``any(temps > 0)``, and whichever
side runs, tokens and keys are those of the unconditional body it replaced
(kept here as the plain reference)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu.serve.engine import _sample_slots

V = 97


def _reference(logits, temps, top_ks, top_ps, keys):
    """The sampler as it was before the branch: every row sorted, filtered
    and drawn, greedy rows overwritten at the end."""
    v = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    k_eff = jnp.where(top_ks <= 0, v, jnp.clip(top_ks, 1, v))
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
    filt = jnp.where(scaled < kth, -jnp.inf, scaled)
    sorted_k = jnp.where(jnp.arange(v)[None, :] < k_eff[:, None],
                         sorted_desc, -jnp.inf)
    probs = jax.nn.softmax(sorted_k, axis=-1)
    exclusive = jnp.cumsum(probs, axis=-1) - probs
    n_keep = jnp.maximum(
        jnp.sum(exclusive < top_ps[:, None], axis=-1, keepdims=True), 1)
    thresh = jnp.take_along_axis(sorted_k, n_keep - 1, axis=-1)
    filt = jnp.where(filt < thresh, -jnp.inf, filt)

    def one(key, row):
        new, sub = jax.random.split(key)
        return new, jax.random.categorical(sub, row)

    new_keys, sampled = jax.vmap(one)(keys, filt)
    toks = jnp.where(temps <= 0.0, greedy_tok, sampled).astype(jnp.int32)
    return new_keys, toks


def _operands(temps, top_ks=None, top_ps=None, seed=0):
    b = len(temps)
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(0.0, 2.0, (b, V)), jnp.float32)
    keys = jnp.asarray(rng.integers(0, 2**32, (b, 2), dtype=np.uint64),
                       jnp.uint32)
    return (logits, jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks if top_ks is not None else [0] * b, jnp.int32),
            jnp.asarray(top_ps if top_ps is not None else [1.0] * b,
                        jnp.float32), keys)


_MIXES = {
    "all_greedy": dict(temps=[0.0] * 6),
    "all_sampling": dict(temps=[0.7, 1.0, 1.3, 0.2, 2.0, 0.9],
                         top_ks=[0, 12, 40, 0, 12, 40],
                         top_ps=[1.0, 0.9, 0.7, 0.7, 1.0, 0.9]),
    "mixed": dict(temps=[0.0, 0.8, 0.0, 1.5, 0.0, -1.0],
                  top_ks=[0, 12, 40, 0, 12, 0],
                  top_ps=[1.0, 0.9, 0.7, 0.9, 0.7, 1.0]),
    "one_greedy_row": dict(temps=[0.0]),
    "one_sampling_row": dict(temps=[0.9], top_ks=[12], top_ps=[0.9]),
}


def _assert_same(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("mix", sorted(_MIXES))
def test_tokens_and_keys_are_the_unconditional_samplers(mix):
    """Bit for bit, for both sides of the branch and for the ``[1, V]`` call
    the final chunk makes; over a few rounds, the keys chained."""
    ops = _operands(**_MIXES[mix])
    fn, ref = jax.jit(_sample_slots), jax.jit(_reference)
    keys = ops[4]
    for _ in range(3):
        got, want = fn(*ops[:4], keys), ref(*ops[:4], keys)
        _assert_same(got, want)
        keys = got[0]
    if "sampling" in mix:
        assert not np.array_equal(np.asarray(want[1]),
                                  np.argmax(np.asarray(ops[0]), axis=-1))


@pytest.mark.parametrize("mix", ["all_greedy", "mixed"])
def test_inside_a_scan_the_chain_is_the_unconditional_samplers(mix):
    """Spec-verify's use: the sampler as a ``lax.scan`` body over window
    positions, keys carried and every state kept."""
    _, temps, top_ks, top_ps, keys = _operands(**_MIXES[mix])
    rows = jnp.asarray(np.random.default_rng(3).normal(
        0.0, 2.0, (4, len(temps), V)), jnp.float32)

    def chain(sampler):
        def body(k, row_logits):
            new, toks = sampler(row_logits, temps, top_ks, top_ps, k)
            return new, (toks, new)
        return jax.jit(lambda: jax.lax.scan(body, keys, rows))()

    got, want = chain(_sample_slots), chain(_reference)
    _assert_same(jax.tree_util.tree_leaves(got),
                 jax.tree_util.tree_leaves(want))


def _primitives(jaxpr):
    """Names of the primitives of *jaxpr*, sub-jaxprs (a nested ``jit``'s, a
    ``cond``'s branches) included."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_primitives(sub))
    return names


def _inlined(jaxpr):
    """The equations of *jaxpr* with every nested ``jit`` (``jnp.sort``,
    ``jnp.where`` and the like trace as one) opened in place; a ``cond``
    stays one equation."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("jit", "pjit"):
            yield from _inlined(eqn.params["jaxpr"].jaxpr)
        else:
            yield eqn


def test_the_sort_lives_in_one_branch_of_the_one_cond():
    """No ``sort`` outside the conditional; exactly one ``cond``, one branch
    of which holds the ``sort`` (and the cumsum and the draw) and the other
    none of them. The argmax and the key split stay outside."""
    jaxpr = jax.make_jaxpr(_sample_slots)(*_operands(temps=[0.0] * 4)).jaxpr
    top = list(_inlined(jaxpr))
    names = [e.primitive.name for e in top]
    assert "sort" not in names and "cumsum" not in names
    assert names.count("cond") == 1
    assert "argmax" in names and "random_split" in names
    cond = top[names.index("cond")]
    sides = [set(_primitives(b.jaxpr)) for b in cond.params["branches"]]
    assert len(sides) == 2
    with_sort = [s for s in sides if "sort" in s]
    assert len(with_sort) == 1
    assert {"cumsum", "random_bits", "div"} <= with_sort[0]
    (greedy,) = [s for s in sides if "sort" not in s]
    assert not greedy & {"sort", "cumsum", "random_bits", "exp", "div"}
