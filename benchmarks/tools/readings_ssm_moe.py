"""Readings and sweeps for a cell of the ``ssm-moe`` family — run on the chip,
by hand (the benchmark's own runs never run this):

    python benchmarks/tools/readings_ssm_moe.py --workload <cell> \
        --variants '[{"name": "chunk256", "seed": 11, "engine": {"prefill_chunk_tokens": 256}}]' \
        [--seconds 25] [--out chiprun_out/variants.json]

One process, one variant after another, each through the benchmark's own
``serve_window.setup`` / ``window`` / ``release``. A variant is a JSON object:

``seed``            its seed (give each run a seed of its own)
``engine``          engine options laid over the cell's (a sweep of the chunk,
                    the bucket, the page size, the pool)
``config``          configuration keys laid over the file's (``{"ssm_state_dtype":
                    "bfloat16"}``: the program with its state in bfloat16)
``grouped_from``    ``models.moe.GROUPED_MIN_ROWS_PER_EXPERT`` for this variant:
                    the other choice of ``serving_dispatch``, timed
``zero_state``      ``"first"`` / ``"final"``: the PROGRAM's own fault — every
                    request's slot has its ``ssm_state`` rows set to zero after its
                    first prefill chunk / before its final one
``score``           list of what the reference scores the served sample as:
                    ``program`` (default), ``control`` (``control_precision``),
                    ``alter`` (a served token in eight), ``zero_state`` (the
                    reference standing in for a program that lost its state);
                    ``[]`` skips the reference (a timing variant)

Per variant one JSON line: tokens per second, the gaps' percentiles, occupancy,
the queue at the close, the spans' counts, the expert dispatch each program
took, and the scores.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest as manifest_lib  # noqa: E402


def _plant_zero_state(engine, where: str) -> None:
    """Wrap the engine's chunk dispatches so that a request's slot has its
    ``ssm_state`` rows set to zero (in place, donated) between two of its
    chunks: *where* ``"first"`` — after its FIRST chunk (a long prompt has
    forgotten it by its first served token) — or ``"final"`` — before its
    final chunk (its last 1-512 prompt tokens and every served token follow)."""
    import jax

    def zero(cache, slot):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: (leaf.at[slot].set(0.0)
                                if getattr(path[-1], "key", None) == "ssm_state" else leaf),
            cache)
    zero = jax.jit(zero, donate_argnums=0)
    chunk_step, final_step = engine._chunk_step, engine._final_chunk_step

    def after_first(chunk, table, start, *, slot=None, draft=False):
        cache, moe = chunk_step(chunk, table, start, slot=slot, draft=draft)
        if int(start) == 0 and slot is not None:
            cache = zero(cache, slot)
        return cache, moe

    def before_final(chunk, table, start, *args, slot=None):
        if int(start) > 0 and slot is not None:
            engine._cache = zero(engine._cache, slot)
        return final_step(chunk, table, start, *args, slot=slot)
    if where == "first":
        engine._chunk_step = after_first
    elif where == "final":
        engine._final_chunk_step = before_final
    else:
        raise ValueError(f"zero_state is 'first' or 'final', got {where!r}")


def run_variant(cell, variant: dict, seconds: float) -> dict:
    from benchmarks.harness import serve_window
    from k8s_distributed_deeplearning_tpu.models import moe as moe_lib
    cell = copy.copy(cell)
    cell.config = {**cell.config, **variant.get("config", {})}
    cell.options = {**cell.options,
                    "engine": {**cell.options["engine"], **variant.get("engine", {})}}
    seed = int(variant["seed"])
    constant = moe_lib.GROUPED_MIN_ROWS_PER_EXPERT
    moe_lib.GROUPED_MIN_ROWS_PER_EXPERT = variant.get("grouped_from", constant)
    try:
        t0 = time.perf_counter()
        split: dict = {}
        sut = serve_window.setup(cell, seed, split)
        if variant.get("zero_state"):
            _plant_zero_state(sut["engine"], variant["zero_state"])
        setup_s = time.perf_counter() - t0
        win = serve_window.window(cell, sut, seconds, None)
        dec = [r for r in win["tracer"].records if r[0] == "decode" and r[1] >= win["t_open"]]
        pre = [r for r in win["tracer"].records if r[0] == "prefill" and r[1] >= win["t_open"]]
        steps = [r for r in win["tracer"].records
                 if r[0] == "engine_step" and r[1] >= win["t_open"]]
        out = {"variant": variant, "setup_s": setup_s, "split": split,
               "serve_out_tokens_per_s": win["out_tokens"] / win["window_s"],
               **{k: win[k] for k in ("itl_p50_ms", "itl_p95_ms", "itl_p99_ms", "failed",
                                      "attempted", "compiles_in_window", "queue_len_close",
                                      "requests_finished_in_window", "decode_spans",
                                      "prefill_spans", "attention_impls")},
               "occupancy": win["counters"]["occupancy"],
               "step_ms_mean": 1e3 * sum(r[2] - r[1] for r in steps) / max(len(steps), 1),
               "decode_span_ms_mean": 1e3 * sum(r[2] - r[1] for r in dec) / max(len(dec), 1),
               "state_rows_mean": (sum(r[3].get("state_rows", 0) for r in dec)
                                   / max(len(dec), 1)),
               "prefill_tokens": sum(r[3].get("tokens", 0) for r in pre)}
        serve_window.release(sut)
        what = variant.get("score", ["program"])
        if what:
            sample = serve_window.pick_sample(cell, seed, win["requests"])
            ref = sut["family"].reference
            low = cell.config.get("control_precision", "fp8")
            kw = {"program": {}, "control": {"precision": low}, "alter": {"fault": "alter"},
                  "zero_state": {"fault": "zero_state"}}
            out["sample"] = [(s["id"], len(s["prompt"]), len(s["tokens"])) for s in sample]
            for w in what:
                sc = ref.score_served(cell.config, seed, sample, **kw[w])
                out[w] = {k: sc[k] for k in ("logit_gap_max", "gaps", "binds", "tokens",
                                             "tokens_decided", "not_reference_best",
                                             "logit_std", "per_request_max_all",
                                             "per_request_mean", "router_flips",
                                             "router_choices")}
        return out
    finally:
        moe_lib.GROUPED_MIN_ROWS_PER_EXPERT = constant


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variants", required=True, help="a JSON list, or @file")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    text = args.variants
    if text.startswith("@"):
        with open(text[1:]) as f:
            text = f.read()
    variants = json.loads(text)
    cell = manifest_lib.Cell(manifest_lib.load_manifest(), args.workload)
    jax, _ = manifest_lib.start_jax(cell, args.rehearsal)
    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("readings_ssm_moe.py: no TPU", file=sys.stderr)
        return 3
    from benchmarks.harness import serve_window
    serve_window.import_program()
    rows = []
    for variant in variants:
        rows.append(run_variant(cell, variant, args.seconds))
        print(json.dumps(rows[-1], default=str), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
