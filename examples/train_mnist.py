"""Distributed MNIST training — the TPU-native ``tensorflow_mnist.py``.

Single-program, rank-parameterized: the same script runs on every host of the
slice (parity with the reference where mpirun launches one copy per rank,
``deploy_stack.sh:64-84``); the K8s-injected env wires the world
(``parallel/distributed.py``), the device mesh replaces the MPI communicator,
and all per-step communication is XLA collectives on ICI.

Flags are the reference's (``tensorflow_mnist.py:30-35``,
``tensorflow_mnist_gpu.py:36``): --lr, --num-steps, --use-adasum, --batch-size.

Run single-host:   python examples/train_mnist.py --num-steps 200
Fake an 8-chip DP mesh on CPU:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_mnist.py --num-steps 100
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from k8s_distributed_deeplearning_tpu import backend, config as cfg
from k8s_distributed_deeplearning_tpu.models import mnist
from k8s_distributed_deeplearning_tpu.parallel import (
    data_parallel as dp,
    distributed,
    mesh as mesh_lib,
)
from k8s_distributed_deeplearning_tpu.train import (
    Checkpointer,
    ShardedBatcher,
    data as data_lib,
    loop,
    optim,
    prefetch,
)
from k8s_distributed_deeplearning_tpu.utils.metrics import MetricsLogger


def main(argv: list[str] | None = None) -> dict:
    parser = argparse.ArgumentParser(description=__doc__)
    cfg.add_train_flags(parser)
    args = parser.parse_args(argv)
    conf = cfg.train_config_from_args(args)
    backend.use_compile_cache()

    # Form the multi-host world before any device use (hvd.init() parity,
    # tensorflow_mnist.py:90).
    distributed.initialize_from_env()
    topo = mesh_lib.topology()
    mesh = mesh_lib.make_mesh({mesh_lib.AXIS_DATA: -1})
    world = topo.world_size

    dtype = jnp.bfloat16 if conf.dtype == "bfloat16" else jnp.float32
    model = mnist.MNISTConvNet(dropout_rate=conf.dropout, dtype=dtype)

    # LR × world (or Adasum rule) and steps ÷ world — tensorflow_mnist.py:123-130,146.
    lr = conf.scaled_lr(world, topo.local_size,
                        mesh_lib.fast_interconnect_available())
    num_steps = conf.steps_for_world(world)
    optimizer = optim.make_optimizer("adam", lr,
                                     grad_clip=args.grad_clip or None)
    reduction = dp.Reduction.ADASUM if conf.use_adasum else dp.Reduction.AVERAGE

    rng = jax.random.key(conf.seed)
    rng, init_rng = jax.random.split(rng)
    params = model.init(init_rng, jnp.zeros((1, 28, 28, 1)), train=False)["params"]
    params = dp.replicate(params, mesh)
    # Broadcast initial state from replica 0 (BroadcastGlobalVariablesHook(0)
    # parity, :143). Identical-seed SPMD already guarantees this; the explicit
    # collective guards against host divergence.
    params = dp.broadcast_params(params, mesh)
    state = dp.init_state(params, optimizer, mesh)

    step_fn = dp.make_train_step(
        lambda p, b, r: mnist.loss_fn(model, p, b, r),
        optimizer, mesh, reduction=reduction, microbatches=conf.grad_accum)

    images, labels = data_lib.load_or_synthesize(conf.data_dir, "train",
                                                 seed=conf.seed)
    # Per-host batch = per-replica batch × local replicas; global = × world.
    local_replicas = topo.num_devices // topo.num_processes
    batcher = ShardedBatcher(images, labels,
                             batch_size=conf.batch_size * local_replicas,
                             seed=conf.seed,
                             process_index=topo.process_index,
                             num_processes=topo.num_processes)

    if conf.keep_best and not conf.eval_every:
        raise ValueError("--keep-best needs --eval-every to produce the "
                         "metric it ranks checkpoints by")

    metrics = MetricsLogger(enabled=distributed.is_primary(), job="mnist")
    ckpt = Checkpointer(conf.checkpoint_dir,
                        max_to_keep=conf.max_checkpoints_to_keep,
                        keep_best_metric="accuracy" if conf.keep_best else None,
                        best_mode="max",
                        async_save=conf.async_checkpoint)

    # Mid-training validation hook (Keras per-epoch eval parity,
    # tensorflow_mnist_gpu.py:173-182); feeds best-checkpoint retention.
    eval_fn = None
    if conf.eval_every:
        val_x, val_y = data_lib.load_or_synthesize(conf.data_dir, "test",
                                                   seed=conf.seed)
        val_step = jax.jit(lambda p, b: mnist.eval_fn(model, p, b))
        n_val = min(len(val_x), 1000)

        def eval_fn(state):
            return loop.evaluate(
                val_step, state.params,
                iter(ShardedBatcher(val_x[:n_val], val_y[:n_val], 200,
                                    seed=conf.seed)),
                num_batches=max(1, n_val // 200))
    metrics.emit("start", world_size=world, num_steps=num_steps, lr=lr,
                 reduction=reduction.value, **topo.device_fields())

    # Assemble host-local batches into global sharded arrays (multi-host
    # safe); resumable from any step for replay-free checkpoint restore.
    # A host thread stages --prefetch batches ahead (train/prefetch.py).
    prefetchers: list = []

    def global_batches(start_step: int):
        return prefetch.maybe(batcher.iter_from(start_step),
                              lambda b: dp.make_global_batch(b, mesh),
                              args.prefetch, prefetchers)

    try:
        state = loop.fit(
            step_fn, state, global_batches, num_steps, rng,
            metrics=metrics, checkpointer=ckpt,
            checkpoint_every=conf.checkpoint_every, log_every=conf.log_every,
            global_batch_size=conf.batch_size * world,
            flops_per_example=mnist.flops_per_example(),
            peak_flops=mesh_lib.peak_flops_per_device(conf.dtype),
            eval_every=conf.eval_every, eval_fn=eval_fn,
        )

        result: dict = {"num_steps": num_steps, "world_size": world}
        if conf.eval_final:
            # Every process runs eval (params live on the global mesh, so all
            # processes must participate in the jitted computation); identical
            # replicated inputs on each host; only the primary emits/reports —
            # the rank-0 discipline of tensorflow_mnist_gpu.py:184-188.
            test_x, test_y = data_lib.load_or_synthesize(conf.data_dir, "test",
                                                         seed=conf.seed)
            eval_step = jax.jit(lambda p, b: mnist.eval_fn(model, p, b))
            # Real data: the full held-out split (the >=99% gate must cover
            # all 10k test examples); synthetic: capped for smoke speed.
            n = len(test_x) if conf.data_dir else min(len(test_x), 2000)
            bs = 200
            ev = loop.evaluate(eval_step, state.params,
                               iter(ShardedBatcher(test_x[:n], test_y[:n], bs,
                                                   seed=conf.seed)),
                               num_batches=max(1, n // bs))
            ev["eval_examples"] = (n // bs) * bs
            metrics.emit("eval", **{k: float(v) for k, v in ev.items()})
            if distributed.is_primary():
                result.update(ev)
    finally:
        prefetch.close_all(prefetchers)
        ckpt.close()
        metrics.close()
    return result


def run_accuracy_gate(data_dir: str, checkpoint_dir: str,
                      steps: int | None = None) -> float:
    """The single source of truth for the >=99% north-star gate: train the
    reference's deployed config (batch 100, Adam 1e-3 x world, default
    20000 // world steps — ``tensorflow_mnist.py:33-34,123,146``) on real
    MNIST through the DP engine, evaluate the FULL 10k test split, and
    assert >= 0.99. Called by ``tests/test_mnist_convergence.py``.
    *checkpoint_dir* must be fresh — a stale dir would restore a finished
    run and certify params the current code never trained. Returns the
    measured accuracy."""
    if steps is None:
        steps = int(os.environ.get("MNIST_STEPS", "20000"))
    if os.path.isdir(checkpoint_dir) and os.listdir(checkpoint_dir):
        raise ValueError(
            f"checkpoint_dir {checkpoint_dir!r} is non-empty: the gate "
            "would resume a finished run instead of training")
    result = main([
        "--data-dir", data_dir,
        "--num-steps", str(steps),
        "--batch-size", "100",
        "--lr", "0.001",
        "--checkpoint-dir", checkpoint_dir,
        "--log-every", "500",
    ])
    # RuntimeError, not assert: gate checks must survive `python -O`
    # (assertions compile away and the gate would silently pass).
    if result.get("eval_examples") != 10_000:
        raise RuntimeError(
            f"gate must cover the full test split, got {result!r}")
    acc = float(result["accuracy"])
    if acc < 0.99:
        raise RuntimeError(f"north-star gate FAILED: {acc:.4f} < 0.99")
    return acc


def run_digits_gate(checkpoint_dir: str, steps: int | None = None,
                    threshold: float = 0.97) -> float:
    """Real-data convergence gate that EXECUTES in zero-egress
    environments: the UCI hand-written digits bundled with scikit-learn
    (real scanned digits — see ``data.make_digits_fixture``), through the
    IDENTICAL pipeline the ≥99% MNIST gate drives (idx files on disk →
    ``--data-dir`` → ShardedBatcher → DP engine → full held-out split
    eval). The reference's deployed hyperparameters (batch 100, Adam
    1e-3 × world). This is NOT the MNIST north star — that gate stays
    honestly "skipped" without the canonical idx files — it is the
    executed proof that the training engine converges on real data.
    Returns the measured accuracy; asserts ≥ *threshold* (0.97 — the
    ConvNet clears it with margin; kNN baselines on this set sit ~0.98).
    """
    if steps is None:
        steps = int(os.environ.get("DIGITS_STEPS", "1500"))
    if os.path.isdir(checkpoint_dir) and os.listdir(checkpoint_dir):
        raise ValueError(
            f"checkpoint_dir {checkpoint_dir!r} is non-empty: the gate "
            "would resume a finished run instead of training")
    import tempfile

    from k8s_distributed_deeplearning_tpu.train import data as data_lib
    fixture = data_lib.make_digits_fixture(
        tempfile.mkdtemp(prefix="digits_fixture_"))
    result = main([
        "--data-dir", fixture,
        "--num-steps", str(steps),
        "--batch-size", "100",
        "--lr", "0.001",
        "--checkpoint-dir", checkpoint_dir,
        "--log-every", "500",
    ])
    # RuntimeError, not assert: must survive `python -O` (see
    # run_accuracy_gate).
    if result.get("eval_examples") != 400:
        raise RuntimeError(
            f"gate must cover the full held-out split, got {result!r}")
    acc = float(result["accuracy"])
    if acc < threshold:
        raise RuntimeError(
            f"real-digits convergence gate FAILED: {acc:.4f} < {threshold}")
    return acc


if __name__ == "__main__":
    main(sys.argv[1:])
