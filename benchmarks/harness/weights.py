"""Weights from ``--seed``: one rule, used by the program's initialiser and
by the plain references alike (the reference takes nothing the program made).

A leaf is named by its path in the program's parameter tree, joined with
``/`` (``encoder/blocks/attn/q_proj/kernel``); its values are

    normal(fold_in(key(seed), crc32(name))) * std(name)      (+ 1 for scales)

so any one leaf — or one layer of a leaf stacked over layers — can be made
again later, alone, with the same values: the Mistral reference makes its
weights layer by layer, after the engine's are freed.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

STD = 0.02          # BERT's initializer_range; Mistral's too


def _kind(name: str) -> str:
    tail = name.rsplit("/", 1)[-1]
    if tail == "scale":
        return "scale"          # norm gains: 1 + noise
    if tail == "bias" or name.endswith("mlm_bias"):
        return "bias"
    return "weight"


def leaf(seed: int, name: str, shape, dtype=jnp.float32):
    """The values of leaf *name* for *seed* (f32 draw, then cast). *seed* is
    a uint32 scalar — :func:`seed_operand` — so that it can be a traced
    operand: one compiled initialiser serves every seed."""
    key = jax.random.fold_in(jax.random.key(seed),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(key, tuple(shape), jnp.float32) * STD
    if _kind(name) == "scale":
        x = 1.0 + x
    return x.astype(dtype)


def seed_operand(seed: int):
    """``--seed`` (any whole number up to a little over 2**31) as the uint32
    scalar the generators take."""
    import numpy as np
    return np.uint32(int(seed) % (2 ** 32))


def path_name(path) -> str:
    parts = []
    for p in path:
        k = getattr(p, "key", None)
        if k is None:
            k = getattr(p, "name", None)
        if k is None:
            k = getattr(p, "idx", None)
        if k is None or k == "value":      # flax Partitioned box
            continue
        parts.append(str(k))
    return "/".join(parts)


def fill_like(seed: int, abstract_tree, dtype=None):
    """A tree shaped like *abstract_tree* (``jax.eval_shape`` of the
    program's ``model.init``; flax ``Partitioned`` boxes are kept) with every
    leaf from :func:`leaf`. Call inside ``jax.jit`` so that all weights are
    made on the device in one program."""
    def one(path, a):
        return leaf(seed, path_name(path), a.shape, dtype or a.dtype)
    return jax.tree_util.tree_map_with_path(one, abstract_tree)


def named_leaves(tree) -> dict:
    """``{name: array}`` of a parameter tree, by :func:`path_name`."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {path_name(p): v for p, v in flat}
