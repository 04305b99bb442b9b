"""Glue between a ``bert-mlm`` configuration file and the program: builds
the system under test (``models/bert.py`` on ``parallel.sharding
.ShardedTrainer``, as ``examples/train_zoo.py --model bert-base`` does) and
names the plain reference that goes with it. Only this file and the window
driver import the program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness import reference_bert, weights

reference = reference_bert


def program_config(cfg: dict):
    from k8s_distributed_deeplearning_tpu.models import bert
    return bert.config_bert_base(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        mlp_dim=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg.get("compute_dtype", "bfloat16")))


def build_trainer(cfg: dict, job: dict, mesh):
    """-> (trainer, init_fn(seed_u32) -> params, model). The loss closure is
    train_zoo's: mask on the fly from the step's key, MLM loss."""
    from k8s_distributed_deeplearning_tpu.models import bert
    from k8s_distributed_deeplearning_tpu.parallel import sharding
    from k8s_distributed_deeplearning_tpu.train import optim

    mcfg = program_config(cfg)
    model = bert.BertMLM(mcfg)
    mask_id = job["mask_id"]

    def loss(p, b, r):
        inputs, targets, w = bert.mask_tokens(
            b["tokens"][:, :-1], r, vocab_size=mcfg.vocab_size,
            mask_id=mask_id, mask_prob=job["mask_prob"])
        return bert.loss_fn(model, p, {"inputs": inputs, "targets": targets,
                                       "weights": w})

    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])

    def init(seed_u32):
        return weights.fill_like(seed_u32, abstract)

    lr = optim.make_schedule(job.get("schedule", "constant"), job["lr"],
                             job.get("total_steps", 1_000_000),
                             job.get("warmup_steps", 0))
    optimizer = optim.make_optimizer(job["optimizer"], lr,
                                     weight_decay=job["weight_decay"],
                                     grad_clip=job["grad_clip"] or None)
    return sharding.ShardedTrainer(loss, optimizer, mesh), init, model


def flops_per_token(cfg: dict, seq_len: int) -> float:
    from benchmarks.harness import counts
    return counts.bert_train_flops_per_token(cfg, seq_len)
