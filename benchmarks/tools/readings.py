"""Readings for the limits of ``correct`` — run on the chip, by hand:

    python benchmarks/tools/readings.py --workload <cell> --seeds 11,12,... \
        [--control-seeds 3] [--seconds 40] [--out chiprun_out/readings.json]

For every seed the program's numbers against the plain reference (the lower
reading is their largest); for the first ``--control-seeds`` seeds also the
control's — the reference at the nearest precision below the configuration's,
put in the program's place — and each plantable fault's (the upper reading is
their smallest). One process, one set-up of the chip. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest as manifest_lib  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = manifest_lib.Cell(manifest_lib.load_manifest(), args.workload)
    jax, _ = manifest_lib.start_jax(cell, args.rehearsal)
    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("readings.py: no TPU", file=sys.stderr)
        return 3
    driver = importlib.import_module(f"benchmarks.harness.{cell.driver}_window")
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = driver.readings(cell, seed, control=i < args.control_seeds,
                            seconds=args.seconds)
        rows.append({"seed": seed, "seconds": time.perf_counter() - t0, **r})
        print(json.dumps(rows[-1]), flush=True)
    summary: dict = {}
    for row in rows:
        for who, numbers in row.items():
            if not isinstance(numbers, dict):
                continue
            for k, v in numbers.items():
                lo_hi = summary.setdefault(who, {}).setdefault(k, [v, v])
                lo_hi[0], lo_hi[1] = min(lo_hi[0], v), max(lo_hi[1], v)
    print(json.dumps({"event": "summary_min_max", "device": jax.devices()[0].device_kind,
                      "summary": summary}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
