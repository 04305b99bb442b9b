"""Generate text (tokens) from a train_llama.py checkpoint.

The inference half of the flagship path — restores the newest Orbax
checkpoint written by ``train_llama.py`` and runs the jitted KV-cache decode
loop (``models/generate.py``).

  python examples/generate_llama.py --preset tiny \
      --checkpoint-dir ./checkpoints --max-new-tokens 64 --temperature 0.7
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from k8s_distributed_deeplearning_tpu import backend
from k8s_distributed_deeplearning_tpu.models import generate as gen_lib
from k8s_distributed_deeplearning_tpu.models import llama
from k8s_distributed_deeplearning_tpu.train import Checkpointer

from train_llama import PRESETS, build_config


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", choices=sorted(PRESETS), default="tiny")
    ap.add_argument("--checkpoint-dir", default="./checkpoints")
    ap.add_argument("--prompt", type=str, default="",
                    help="prompt bytes (byte-level vocab); empty -> BOS-less "
                         "single zero token")
    ap.add_argument("--max-new-tokens", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=None,
                    help="sample only from the k most likely tokens")
    ap.add_argument("--top-p", type=float, default=None,
                    help="nucleus sampling: smallest set reaching this "
                         "probability mass")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--remat", action="store_true")
    args = ap.parse_args(argv)
    backend.use_compile_cache()
    # Decode always uses the XLA attention path against the KV cache; the
    # training-time attention impl is irrelevant here (build_config compat).
    args.attention = "xla"

    cfg = build_config(args)
    model = llama.LlamaLM(cfg)

    # Params-only restore: tree shape comes from checkpoint metadata,
    # optimizer moments are skipped entirely (ocp.PLACEHOLDER) — no skeleton,
    # no knowledge of the training run's optimizer, no moment memory.
    ck = Checkpointer(args.checkpoint_dir)
    restored = ck.restore_params()
    if restored is None:
        raise FileNotFoundError(
            f"no checkpoint under {args.checkpoint_dir!r} — run "
            "train_llama.py first")
    params, step = restored

    if args.prompt:
        prompt = jnp.asarray([[b % cfg.vocab_size
                               for b in args.prompt.encode()]], jnp.int32)
    else:
        prompt = jnp.zeros((1, 1), jnp.int32)

    out = gen_lib.generate(model, params, prompt,
                           max_new_tokens=args.max_new_tokens,
                           temperature=args.temperature,
                           top_k=args.top_k, top_p=args.top_p,
                           rng=jax.random.key(args.seed))
    toks = np.asarray(out)[0].tolist()
    text = bytes(t % 256 for t in toks).decode("utf-8", errors="replace")
    print({"checkpoint_step": step, "tokens": toks, "text": text})
    return {"step": step, "tokens": toks}


if __name__ == "__main__":
    main(sys.argv[1:])
