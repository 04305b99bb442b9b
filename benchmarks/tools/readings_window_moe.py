"""Readings for the limits of a ``window-moe`` cell — run on the chip, by hand
(the benchmark's own runs never run this):

    python benchmarks/tools/readings_window_moe.py --workload <cell> \
        --variants '[{"seed": 11, "score": ["program", "control", "window_full"]}]' \
        [--seconds 25] [--out readings_window_moe.json]

One process, one variant after another, each through the benchmark's own
``serve_window.setup`` / ``window`` / ``release``. A variant is a JSON object:

``seed``            its seed (give each run a seed of its own)
``ring_unwritten``  true: the PROGRAM's own fault — the decode program leaves
                    every window layer's ring as it was (no token of a decode
                    step enters it)
``score``           list of what the reference scores the served sample as:
                    ``program`` (default), ``control`` (``control_precision``),
                    ``alter`` (a served token in eight), ``window_full`` (the
                    reference standing in for a program whose window layers
                    attend the whole context), ``rope_global`` (one whose
                    global layers take RoPE)

Per variant one JSON line: tokens per second, the gaps' percentiles, the queue
at the close, the memory peak, and the scores.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest as manifest_lib  # noqa: E402

KW = {"program": {}, "alter": {"fault": "alter"}, "window_full": {"fault": "window_full"},
      "rope_global": {"fault": "rope_global"}}


def _ring_unwritten(engine_mod):
    """The engine's ``_keep_idle_state`` with every ring leaf handed back as it
    was before the decode step: the PROGRAM's planted fault. Returns the
    original, to put back."""
    keep = engine_mod._keep_idle_state

    def stale(cache, new, kv_lens):
        import jax
        kept = keep(cache, new, kv_lens)
        return jax.tree_util.tree_map_with_path(
            lambda path, old, now: (old if engine_mod._leaf_name(path) in engine_mod._RING_LEAVES
                                    else now), cache, kept)
    engine_mod._keep_idle_state = stale
    return keep


def run_variant(cell, variant: dict, seconds: float) -> dict:
    import jax
    from benchmarks.harness import serve_window
    from k8s_distributed_deeplearning_tpu.serve import engine as engine_mod
    seed = int(variant["seed"])
    keep = _ring_unwritten(engine_mod) if variant.get("ring_unwritten") else None
    engine_mod._decode_program.clear_cache()
    try:
        t0 = time.perf_counter()
        sut = serve_window.setup(cell, seed, {})
        setup_s = time.perf_counter() - t0
        win = serve_window.window(cell, sut, seconds, None)
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        out = {"variant": variant, "setup_s": setup_s,
               "serve_out_tokens_per_s": win["out_tokens"] / win["window_s"],
               **{k: win[k] for k in ("itl_p50_ms", "itl_p95_ms", "failed", "attempted",
                                      "queue_len_close", "requests_finished_in_window",
                                      "attention_impls")},
               "occupancy": win["counters"]["occupancy"],
               "memory_peak_bytes": (int(stats.get("peak_bytes_in_use", 0))
                                     + int(stats.get("peak_bytes_reserved", 0)))}
        serve_window.release(sut)
        sample = serve_window.pick_sample(cell, seed, win["requests"])
        ref = sut["family"].reference
        kw = dict(KW, control={"precision": cell.config.get("control_precision", "fp8")})
        out["sample"] = [(s["id"], len(s["prompt"]), len(s["tokens"])) for s in sample]
        for w in variant.get("score", ["program"]):
            t0 = time.perf_counter()
            sc = ref.score_served(cell.config, seed, sample, **kw[w])
            out[w] = {k: sc[k] for k in ("logit_gap_max", "gaps", "binds", "tokens",
                                         "tokens_decided", "not_reference_best", "logit_std",
                                         "per_request_max_all", "per_request_mean",
                                         "router_flips", "router_choices")}
            out[w]["seconds"] = time.perf_counter() - t0
        return out
    finally:
        if keep is not None:
            engine_mod._keep_idle_state = keep
            engine_mod._decode_program.clear_cache()


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
        print("readings_window_moe.py: no TPU", file=sys.stderr)
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
