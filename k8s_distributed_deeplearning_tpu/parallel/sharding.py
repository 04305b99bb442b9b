"""Logical-axis sharding rules + the unified multi-axis sharded trainer.

This is the TPU-native generalization of the reference's single parallelism
strategy (synchronous DP via Horovod allreduce, ``tensorflow_mnist.py:133``) to
the full matrix: DP, FSDP (ZeRO-3-style param sharding), Megatron-style tensor
parallelism, sequence sharding, expert sharding — all expressed as **one rule
table** mapping logical weight/activation axes (declared by the models via
``nn.with_logical_partitioning`` / ``nn.with_logical_constraint``) onto mesh
axes. ``jit`` + XLA SPMD then *derives* the communication:

- FSDP: params sharded over "fsdp" => XLA all-gathers weights before use and
  reduce-scatters gradients (exactly the ZeRO-3 schedule, but compiler-placed
  and overlapped with compute);
- TP: "heads"/"mlp" sharded over "tensor" => column/row-parallel matmuls with
  a psum after the row-parallel projection;
- DP: batch sharded over ("data","fsdp") => gradient all-reduce.

There is no hand-written collective in this file — that is the point. The
explicit-collective engine (``parallel/data_parallel.py``, shard_map-based)
remains for the Horovod-parity path (Adasum, explicit bucketing); this engine
is the scale-out path for the BASELINE.json configs.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import optax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from k8s_distributed_deeplearning_tpu.ops import attention as attention_ops
from k8s_distributed_deeplearning_tpu.parallel.data_parallel import TrainState

PyTree = Any
Rules = Sequence[tuple[str, Any]]

# Canonical rule table (maxtext/t5x-style). Axes missing from the mesh are
# filtered out by resolve_rules(), so one table serves every topology from
# {"data": N} to {"data","fsdp","tensor","sequence","expert"}.
DEFAULT_RULES: Rules = (
    ("batch", ("data", "fsdp")),     # DP over data, and over fsdp (ZeRO data axis)
    ("seq", "sequence"),             # activation sequence sharding (CP)
    ("embed", "fsdp"),               # FSDP weight shard axis
    # Activations name their feature dim "act_embed", NOT "embed": flax
    # prunes duplicate mesh axes when resolving a constraint, so
    # ("batch", "seq", "embed") on an fsdp mesh handed fsdp to the embed
    # dim and silently STRIPPED it from batch — residuals then shard
    # batch only over "data" and every unsharded-dim tensor (mlp hidden,
    # attention internals) replicates fsdp-fold-×. Found by the 8B
    # memory analysis (round 5): together with the shard_map'd attention
    # (ops.attention.make_mesh_attention_fn) per-layer temp dropped
    # 4.81 -> 0.81 GB/device on the dp8×fsdp8 virtual v5p-64.
    # Activations stay unsharded on features (ZeRO shards WEIGHTS, not
    # activations); batch owns data×fsdp.
    ("act_embed", None),
    ("embed_out", None),             # square-projection output dim (dedup)
    ("mlp", "tensor"),               # Megatron column-parallel
    ("heads", "tensor"),             # attention-head parallel
    ("kv", "tensor"),
    ("head_dim", None),
    ("vocab", "tensor"),             # sharded LM head / embedding
    ("expert", "expert"),            # MoE expert parallelism
    ("expert_mlp", "tensor"),
    ("layers", None),                # scan-stacked layer axis (pipeline slices it)
)


# --------------------------------------------------------------- serving TP
# Axis + rule table for the serving engine's tensor-parallel decode
# (serve/engine.py, "graftmesh"): a 1-D ("tp",) mesh, Megatron column/row
# sharding on the attention and MLP weights, everything else REPLICATED.
# Unlike the training tables above, vocab/embed stay UNSHARDED on purpose:
# with the embedding and LM head full on every shard, each shard computes
# the complete [B, vocab] logits after the last row-parallel psum, so
# sampling is replicated and the decode path needs no gather at all.
SERVE_TP_AXIS = "tp"
SERVE_TP_RULES: Rules = (
    ("heads", SERVE_TP_AXIS),   # column-parallel q (and o_proj rows)
    ("kv", SERVE_TP_AXIS),      # column-parallel k/v (GQA head groups)
    ("mlp", SERVE_TP_AXIS),     # column-parallel gate/up (down_proj rows)
)


def serve_tp_param_specs(abstract_params: PyTree) -> PyTree:
    """PartitionSpecs for serving TP: the params' logical axis metadata
    mapped through SERVE_TP_RULES; axes without a rule replicate.

    The result has one ``P`` leaf per *boxed* param, so it works as a
    pytree prefix of both boxed (LogicallyPartitioned) and plain param
    trees — usable directly as shard_map in_specs or (wrapped in
    NamedSharding) as device_put shardings.
    """
    logical = nn.get_partition_spec(abstract_params)
    table = dict(SERVE_TP_RULES)

    def one(spec):
        if not isinstance(spec, P):
            return P()
        return P(*(table.get(ax) for ax in spec))

    return jax.tree.map(one, logical, is_leaf=lambda x: isinstance(x, P))


def serve_tp_cache_specs(cache: PyTree) -> PyTree:
    """PartitionSpecs for the paged KV pool under serving TP: every leaf
    shards its LAST dim over the tp axis. Pool leaves fold heads as
    ``[num_pages, page_tokens, kv_heads * head_dim]`` with kv outermost,
    so a contiguous 1/tp slice of the lane dim IS a whole-head slice —
    each shard holds its ``kv_heads/tp`` heads' pages; page indices,
    block tables, and cursors stay common to all shards."""
    def one(leaf):
        nd = leaf.ndim if hasattr(leaf, "ndim") else len(leaf.shape)
        return P(*((None,) * (nd - 1) + (SERVE_TP_AXIS,)))
    return jax.tree.map(one, cache)


def resolve_rules(mesh: Mesh, rules: Rules = DEFAULT_RULES) -> list[tuple[str, Any]]:
    """Drop mesh axes the current mesh doesn't have (or has at size 1), so the
    same rule table works on every topology."""
    valid = {n for n, s in zip(mesh.axis_names, mesh.devices.shape) if s > 1}
    out = []
    for logical, target in rules:
        if target is None:
            out.append((logical, None))
        elif isinstance(target, (tuple, list)):
            kept = tuple(t for t in target if t in valid)
            out.append((logical, kept if kept else None))
        else:
            out.append((logical, target if target in valid else None))
    return out


def batch_sharding(mesh: Mesh, rules: Rules | None = None) -> NamedSharding:
    """Sharding for data batches: leading axis over the "batch" rule axes."""
    rules = resolve_rules(mesh, rules or DEFAULT_RULES)
    target = dict(rules).get("batch")
    return NamedSharding(mesh, P(target))


def state_shardings(abstract_state: PyTree, mesh: Mesh,
                    rules: Rules | None = None) -> PyTree:
    """NamedShardings for a (possibly boxed) state pytree: flax Partitioned
    leaves carry their logical axes; unboxed leaves replicate.

    Dims that a rule would shard but whose size the mesh axis doesn't divide
    (e.g. 2 KV heads over tensor=8 under GQA) fall back to replicated for
    that dim — sharding is an optimization, never a correctness constraint.
    """
    rules = resolve_rules(mesh, rules or DEFAULT_RULES)
    specs = nn.get_partition_spec(abstract_state)
    shardings = nn.logical_to_mesh_sharding(specs, mesh, rules)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fit(leaf, sh):
        if not isinstance(sh, NamedSharding) or not hasattr(leaf, "shape"):
            return sh
        spec = list(sh.spec) + [None] * (len(leaf.shape) - len(sh.spec))
        out = []
        for dim, entry in zip(leaf.shape, spec):
            axes = (entry,) if isinstance(entry, str) else (entry or ())
            n = 1
            for a in axes:
                n *= sizes[a]
            out.append(entry if n and dim % n == 0 else None)
        return NamedSharding(mesh, P(*out))

    leaves = jax.tree.leaves(abstract_state)
    sh_leaves = jax.tree.leaves(shardings)
    fitted = [fit(l, s) for l, s in zip(leaves, sh_leaves)]
    return jax.tree.unflatten(jax.tree.structure(abstract_state), fitted)


class ShardedTrainer:
    """Init + train step for an arbitrary logically-annotated model over an
    arbitrary mesh. The BASELINE.json ViT ("mixed data+tensor sharding") and
    Llama ("FSDP-style param shard") configs are both instances of this class
    with different meshes/rule tables.

    ``loss_fn(params, batch, rng) -> (loss, aux)`` sees *boxed* params
    (``nn.Partitioned`` leaves) — ``model.apply`` unboxes transparently, and
    keeping the boxes means the optimizer state inherits the partitioning
    metadata, so one ``nn.get_partition_spec`` covers the whole TrainState.
    """

    def __init__(self, loss_fn: Callable, optimizer: optax.GradientTransformation,
                 mesh: Mesh, rules: Rules | None = None):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.rules = resolve_rules(mesh, rules or DEFAULT_RULES)
        self._step = None
        self._state_sh = None

    def init(self, init_params_fn: Callable[[jax.Array], PyTree],
             rng: jax.Array) -> TrainState:
        """Build the TrainState sharded-at-birth: eval_shape discovers the
        partitioning metadata, then a jitted init materializes every shard
        directly on its device (no host round-trip — this is how an 8B-param
        state fits when no single host could hold it)."""
        import jax.numpy as jnp

        def make_state(r):
            params = init_params_fn(r)
            return TrainState(params=params,
                              opt_state=self.optimizer.init(params),
                              step=jnp.zeros((), jnp.int32))

        with self.mesh, nn.logical_axis_rules(self.rules):
            abstract = jax.eval_shape(make_state, rng)
            self._state_sh = state_shardings(abstract, self.mesh, self.rules)
            state = jax.jit(make_state, out_shardings=self._state_sh)(rng)
        return state

    def shardings_for(self, state: TrainState) -> PyTree:
        if self._state_sh is None:
            self._state_sh = state_shardings(
                jax.eval_shape(lambda: state), self.mesh, self.rules)
        return self._state_sh

    def make_step(self, donate: bool = True, microbatches: int = 1) -> Callable:
        """Jitted step(state, batch, rng) -> (state, loss, aux).

        ``microbatches`` > 1 turns on gradient accumulation: the global batch
        is split along its leading axis and scanned sequentially, trading step
        latency for 1/N activation memory (the XLA collectives FSDP/TP insert
        run per microbatch; the optimizer update stays once per step).
        """
        from k8s_distributed_deeplearning_tpu.parallel.data_parallel import (
            accumulate_gradients)

        rules, mesh, opt = self.rules, self.mesh, self.optimizer
        loss_fn = self.loss_fn

        batch_target = dict(rules).get("batch")
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        axes = ((batch_target,) if isinstance(batch_target, str)
                else tuple(batch_target or ()))
        shard_count = 1
        for a in axes:
            shard_count *= sizes[a]

        def constrain(tree: PyTree) -> PyTree:
            # Pin microbatches [m, B/m, ...] with the batch dim sharded — but
            # only where B/m divides the shard count; an indivisible pin makes
            # XLA fully rematerialize the tree per microbatch (observed as
            # "involuntary full rematerialization" resharding), so those
            # leaves fall back to the unpinned layout (mirrors state_shardings'
            # divisibility fallback).
            def one(x):
                ok = x.ndim >= 2 and x.shape[1] % shard_count == 0
                spec = P(None, batch_target) if ok else P(None)
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, spec))
            return jax.tree.map(one, tree)

        def step(state: TrainState, batch: PyTree, rng: jax.Array):
            # The two scopes are stable names in the device trace (every
            # operation's ``tf_op`` starts with one of them): device time
            # splits into the model's passes and the optimizer's update.
            # Trace-time context: the rules, and the mesh announced to the
            # one op with no partition rule of its own (the flash kernel).
            with nn.logical_axis_rules(rules), \
                    attention_ops.program_mesh(mesh):
                with jax.named_scope("forward_backward"):
                    (loss, aux), grads = accumulate_gradients(
                        loss_fn, state.params, batch, rng, microbatches,
                        constrain=constrain if microbatches > 1 else None)
                with jax.named_scope("optimizer"):
                    updates, opt_state = opt.update(grads, state.opt_state,
                                                    state.params)
                    params = optax.apply_updates(state.params, updates)
                return (TrainState(params, opt_state, state.step + 1),
                        loss, aux)

        bsh = batch_sharding(mesh, rules)
        out_sh = (self._state_sh, NamedSharding(mesh, P()), None)
        self._step = jax.jit(
            step,
            in_shardings=(self._state_sh, bsh, None),
            out_shardings=out_sh if self._state_sh is not None else None,
            donate_argnums=(0,) if donate else (),
        )
        return self._step

    def shard_batch(self, batch: PyTree) -> PyTree:
        """Place a host-global batch with the trainer's batch sharding.
        Multi-host: leaves are each process's local slice."""
        sh = batch_sharding(self.mesh, self.rules)
        if jax.process_count() == 1:
            return jax.device_put(batch, sh)
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(sh, x), batch)


def unbox(tree: PyTree) -> PyTree:
    """Strip flax Partitioned boxes (for checkpointing / inspection)."""
    return nn.meta.unbox(tree)
