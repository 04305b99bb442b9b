"""Train any model-zoo member — one rank-parameterized script for the whole zoo.

  python examples/train_zoo.py --model resnet18 --num-steps 100
  python examples/train_zoo.py --model vit --dp 2 --tp 4
  python examples/train_zoo.py --model bert --fsdp 8
  python examples/train_zoo.py --model moe --dp 2 --expert 4

Transformer-family members (vit, bert, moe) run on the unified
:class:`~parallel.sharding.ShardedTrainer`; the ResNets carry BatchNorm
statistics through a custom DP step that pmean-syncs them across replicas
every step (better than the reference, whose Horovod BN stats stay
rank-local and rank 0's are what gets checkpointed).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from typing import Any, NamedTuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from k8s_distributed_deeplearning_tpu import backend, config as cfg
from k8s_distributed_deeplearning_tpu.models import bert, moe, resnet, vit
from k8s_distributed_deeplearning_tpu.models import llama as llama_lib
from k8s_distributed_deeplearning_tpu.parallel import (
    data_parallel as dp, distributed, mesh as mesh_lib, sharding)
from k8s_distributed_deeplearning_tpu.train import (
    Checkpointer, ShardedBatcher, data as data_lib, loop, optim, prefetch)
from k8s_distributed_deeplearning_tpu.utils.metrics import MetricsLogger

MODELS = ("resnet18", "resnet50", "vit", "vit-l", "bert", "bert-base", "moe")

PyTree = Any


class ResNetState(NamedTuple):
    params: PyTree
    batch_stats: PyTree
    opt_state: PyTree
    step: jax.Array


def make_resnet_step(model, optimizer, mesh):
    """DP step carrying BN stats; grads and stats both pmean over data."""

    def step(state: ResNetState, batch, rng):
        def lossf(p):
            return resnet.loss_fn(
                model, {"params": p, "batch_stats": state.batch_stats},
                batch, rng)
        (loss, aux), grads = jax.value_and_grad(lossf, has_aux=True)(
            state.params)
        grads = jax.tree.map(lambda g: lax.pmean(g, "data"), grads)
        stats = jax.tree.map(lambda s: lax.pmean(s, "data"),
                             aux.pop("batch_stats"))
        loss = lax.pmean(loss, "data")
        aux = jax.tree.map(lambda x: lax.pmean(x, "data"), aux)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        return (ResNetState(params, stats, opt_state, state.step + 1),
                loss, aux)

    sharded = jax.shard_map(step, mesh=mesh,
                            in_specs=(P(), P("data"), P()),
                            out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,))


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    cfg.add_train_flags(ap)
    ap.add_argument("--model", choices=MODELS, required=True)
    ap.add_argument("--dp", type=int, default=-1)
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--expert", type=int, default=1)
    ap.add_argument("--image-size", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--optimizer", choices=optim.OPTIMIZERS, default="adamw")
    ap.add_argument("--moment-dtype", choices=["float32", "bfloat16"],
                    default=None,
                    help="first-moment storage dtype (adam/adamw mu, "
                    "lion's moment, sgd's momentum trace)")
    ap.add_argument("--schedule", choices=optim.SCHEDULES, default="constant")
    ap.add_argument("--warmup-steps", type=int, default=0)
    ap.set_defaults(grad_clip=1.0)       # transformer-training default
    args = ap.parse_args(argv)
    conf = cfg.train_config_from_args(args)
    backend.use_compile_cache()

    distributed.initialize_from_env()
    topo = mesh_lib.topology()
    mesh = mesh_lib.make_mesh(cfg.MeshConfig(
        data=args.dp, fsdp=args.fsdp, tensor=args.tp,
        expert=args.expert).to_axis_sizes())
    # Each model family gets its own checkpoint namespace: a foreign
    # checkpoint in a shared default dir would fail restore-on-start.
    if conf.checkpoint_dir == cfg.TrainConfig().checkpoint_dir:
        conf = dataclasses.replace(
            conf, checkpoint_dir=os.path.join(conf.checkpoint_dir,
                                              f"zoo-{args.model}"))
    num_steps = conf.num_steps
    lr = optim.make_schedule(args.schedule, conf.lr, num_steps,
                             args.warmup_steps)
    optimizer = optim.make_optimizer(args.optimizer, lr,
                                     grad_clip=args.grad_clip or None,
                                     moment_dtype=args.moment_dtype)

    # batch_size is PER-REPLICA (TrainConfig contract): the batch only shards
    # over the data(+fsdp) axes, so scale by those — not by all local devices,
    # which would silently inflate the per-replica batch under tp/expert.
    # Validated BEFORE any resource construction (metrics stream, orbax
    # manager) so a config error can't leak them.
    batch_shards = (mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1))
    global_batch = conf.batch_size * batch_shards
    if global_batch % topo.num_processes:
        raise ValueError(
            f"global batch {global_batch} (= batch_size {conf.batch_size} x "
            f"{batch_shards} data/fsdp shards) must divide evenly across "
            f"{topo.num_processes} processes — adjust --batch-size")
    per_host = global_batch // topo.num_processes

    if conf.eval_every or conf.keep_best:
        # Honest guard (ADVICE r2): accepting-and-ignoring these flags would
        # mislead users into thinking best-checkpoint retention is active.
        raise ValueError(
            "--eval-every/--keep-best are not wired into the zoo driver "
            "(its model families train on synthetic batches with no "
            "held-out split); use train_llama.py or train_mnist.py for "
            "eval-gated best-checkpoint retention")

    metrics = MetricsLogger(enabled=distributed.is_primary(),
                            job=f"zoo-{args.model}")
    ckpt = Checkpointer(conf.checkpoint_dir,
                        max_to_keep=conf.max_checkpoints_to_keep,
                        async_save=conf.async_checkpoint)
    rng = jax.random.key(conf.seed)
    prefetchers: list = []

    def _maybe_prefetch(it, place):
        return prefetch.maybe(it, place, args.prefetch, prefetchers)

    if args.model.startswith("resnet"):
        size = args.image_size or (224 if args.model == "resnet50" else 32)
        classes = 1000 if args.model == "resnet50" else 10
        model = (resnet.resnet50() if args.model == "resnet50"
                 else resnet.resnet18_cifar())
        variables = model.init(rng, jnp.zeros((1, size, size, 3)),
                               train=False)
        variables = dp.replicate(variables, mesh)
        state = ResNetState(variables["params"], variables.get("batch_stats", {}),
                            optimizer.init(variables["params"]),
                            jnp.zeros((), jnp.int32))
        state = jax.device_put(state, jax.sharding.NamedSharding(mesh, P()))
        step_fn = make_resnet_step(model, optimizer, mesh)
        x, y = data_lib.synthetic_images(4096, size=size,
                                         num_classes=classes, seed=conf.seed)
        batcher = ShardedBatcher(x, y, per_host, seed=conf.seed,
                                 process_index=topo.process_index,
                                 num_processes=topo.num_processes)

        place = lambda b: dp.make_global_batch(b, mesh)

        def global_batches(start):
            return _maybe_prefetch(batcher.iter_from(start), place)
    else:
        if args.model in ("vit", "vit-l"):
            mcfg = (vit.config_vit_l16() if args.model == "vit-l"
                    else vit.config_tiny(dtype=jnp.float32))
            size = args.image_size or (224 if args.model == "vit-l" else 32)
            patch = 16 if args.model == "vit-l" else 8
            classes = 1000 if args.model == "vit-l" else 10
            model = vit.ViT(mcfg, patch_size=patch, num_classes=classes)
            loss = lambda p, b, r: vit.loss_fn(model, p, b, r)
            init = lambda r: model.init(
                r, jnp.zeros((1, size, size, 3)))["params"]
            x, y = data_lib.synthetic_images(4096, size=size,
                                             num_classes=classes,
                                             seed=conf.seed)
            batcher = ShardedBatcher(x, y, per_host, seed=conf.seed,
                                     process_index=topo.process_index,
                                     num_processes=topo.num_processes)
        elif args.model in ("bert", "bert-base"):
            mcfg = (bert.config_bert_base() if args.model == "bert-base"
                    else bert.config_tiny(dtype=jnp.float32))
            model = bert.BertMLM(mcfg)
            mask_id = mcfg.vocab_size - 1

            def loss(p, b, r):
                inputs, targets, weights = bert.mask_tokens(
                    b["tokens"][:, :-1], r, vocab_size=mcfg.vocab_size,
                    mask_id=mask_id)
                return bert.loss_fn(model, p, {"inputs": inputs,
                                               "targets": targets,
                                               "weights": weights})
            init = lambda r: model.init(
                r, jnp.zeros((1, 8), jnp.int32))["params"]
            toks = data_lib.synthetic_tokens(vocab_size=mcfg.vocab_size,
                                             seed=conf.seed)
            batcher = data_lib.TokenBatcher(
                toks, per_host, min(args.seq_len, mcfg.max_seq_len - 1),
                seed=conf.seed, process_index=topo.process_index,
                num_processes=topo.num_processes)
        else:  # moe
            mcfg = llama_lib.config_tiny(dtype=jnp.float32)
            moecfg = moe.MoEConfig(num_experts=max(args.expert, 2) * 2,
                                   top_k=2, capacity_factor=2.0)
            model = moe.MoELM(mcfg, moecfg)
            loss = lambda p, b, r: moe.loss_fn(model, moecfg, p, b, r)
            init = lambda r: model.init(
                r, jnp.zeros((1, 8), jnp.int32))["params"]
            toks = data_lib.synthetic_tokens(vocab_size=mcfg.vocab_size,
                                             seed=conf.seed)
            batcher = data_lib.TokenBatcher(
                toks, per_host, min(args.seq_len, mcfg.max_seq_len - 1),
                seed=conf.seed, process_index=topo.process_index,
                num_processes=topo.num_processes)

        trainer = sharding.ShardedTrainer(loss, optimizer, mesh)
        state = trainer.init(init, rng)
        step_fn = trainer.make_step(donate=True, microbatches=conf.grad_accum)

        def global_batches(start):
            return _maybe_prefetch(batcher.iter_from(start),
                                   trainer.shard_batch)

    metrics.emit("start", model=args.model, world_size=topo.world_size,
                 num_steps=num_steps, optimizer=args.optimizer,
                 schedule=args.schedule, global_batch_size=global_batch,
                 mesh={k: int(v) for k, v in
                       zip(mesh.axis_names, mesh.devices.shape)},
                 **topo.device_fields())
    try:
        state = loop.fit(step_fn, state, global_batches, num_steps, rng,
                         metrics=metrics, checkpointer=ckpt,
                         checkpoint_every=conf.checkpoint_every,
                         log_every=conf.log_every,
                         global_batch_size=global_batch)

        final = {"num_steps": int(jax.device_get(state.step)),
                 "world_size": topo.world_size, "model": args.model}
    finally:
        prefetch.close_all(prefetchers)
        ckpt.close()
        metrics.close()
    return final


if __name__ == "__main__":
    main(sys.argv[1:])
