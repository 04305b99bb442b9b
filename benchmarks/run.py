"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Stays off JAX until it knows the cell, runs everything in one process, fails
(never falls back) when JAX finds no TPU or fewer chips than the cell asks
for, and prints the result as the last line of stdout. Earlier lines (JSON,
one ``"event"`` each) carry the set-up split, the count of compilations
inside the window and what a slow run lost its time to.

``--rehearsal`` runs the cell end to end at the tiny size its files give
under ``rehearsal``, on whatever backend JAX has (the CPU): it marks its line
as not a measurement and prints no metric and no device.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest as manifest_lib  # noqa: E402


def _say(event: str, **fields) -> None:
    print(json.dumps({"event": event, **fields}, default=str), flush=True)


RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def result_line(correct, attempted, failed, metrics, device, breakdown, compared) -> dict:
    """The last line of stdout: exactly the keys the driver reads, the
    optional ``breakdown`` of a traced run, and — last — the numbers that were
    compared, each beside its limit."""
    line = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--bench-dir", default=None,
                    help="where the cell's traffic/ and cells/ files are (default: benchmarks/)")
    ap.add_argument("--keep-trace", default=None,
                    help="also write the extracted trace (JSON) to this file")
    args = ap.parse_args(argv)

    man = manifest_lib.load_manifest(args.manifest)
    cell = manifest_lib.Cell(man, args.workload,
                             **({"bench_dir": args.bench_dir} if args.bench_dir else {}))
    split: dict = {}

    t0 = time.perf_counter()
    jax, cache_dir = manifest_lib.start_jax(cell, args.rehearsal)
    devs = jax.devices()
    platform = devs[0].platform
    if not args.rehearsal and (platform != "tpu" or len(devs) < cell.chips):
        print(f"benchmarks/run.py: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} x {platform} ({devs[0].device_kind}). "
              "No result.", file=sys.stderr)
        return 3
    used = devs[:cell.chips]
    split["attach"] = time.perf_counter() - t0
    split["imports"] = t0 - _T_START

    t0 = time.perf_counter()
    from benchmarks.harness import compare
    driver = importlib.import_module(f"benchmarks.harness.{cell.driver}_window")
    driver.import_program()
    split["program_imports"] = time.perf_counter() - t0

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", f"{cell.name}-{os.getpid()}")
        os.makedirs(trace_dir, exist_ok=True)

    # Set-up runs up to the window's opening (a server's ramp included); the
    # window follows at once, and the split is printed after it.
    sut = driver.setup(cell, args.seed, split, rehearsal=args.rehearsal)
    setup_s = time.perf_counter() - _T_START
    win = driver.window(cell, sut, args.seconds, trace_dir)
    _say("setup_split", workload=cell.name, setup_s=setup_s, cache_dir=cache_dir,
         split=split)
    e2e = driver.end_to_end(cell, sut, win)
    e2e["setup_s"] = setup_s
    _say("window", **{k: v for k, v in win.items()
                      if k not in ("tracer", "requests", "t_open", "t_close")})

    # The peak on the fullest chip, as JAX's allocator reports it: the peak of
    # the arrays in use plus the peak of what it holds in reserve for the
    # loaded programs' temporaries. (On the TPU `peak_bytes_in_use` alone
    # leaves a program's temporaries out — probed on the chip, PERF.md 2 —
    # and a train step's saved activations are most of its footprint.)
    peak, mem_stats = 0, {}
    for d in used:
        stats = d.memory_stats() or {}
        here = int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))
        if here >= peak:
            peak, mem_stats = here, stats
    _say("memory", memory_peak_bytes=peak, allocator=mem_stats)

    # The reference runs only now: window closed, peak read, state freed.
    driver.release(sut)
    t0 = time.perf_counter()
    numbers, notes, attempted, failed = driver.correctness(cell, args.seed, sut, win)
    # Numbers the cell's file names as not compared (no upper reading could be
    # found for them on the chip; PERF.md gives the readings) are reported in
    # the notes and judged by nothing.
    skipped = cell.options.get("not_compared", {})
    notes["not_compared"] = {k: numbers.pop(k) for k in list(numbers) if k in skipped}
    correct, table = compare.judge(numbers, cell.options.get("limits", {}))
    correct = correct and failed == 0
    _say("reference", seconds=time.perf_counter() - t0, notes=notes)

    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if args.trace:
        from benchmarks.harness import trace_reduce
        t0 = time.perf_counter()
        red = trace_reduce.reduce_dir(trace_dir, n_devices=cell.chips,
                                      keep=args.keep_trace)
        trace_reduce.cleanup(trace_dir)
        run = {"cell": cell, "win": win, "sut": sut, "trace": red,
               "end_to_end": e2e, "device_kind": devs[0].device_kind,
               "rehearsal": args.rehearsal}
        for m in cell.per_layer():
            value = manifest_lib.load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"][:10],
                         "idle_gaps": red["idle_gaps"][:10]}
        _say("trace_reduced", seconds=time.perf_counter() - t0,
             traced_end_to_end=e2e)
    else:
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    compared = {k: [v["value"], v["limit"]] for k, v in table.items()}
    for k, v in table.items():
        print(f"compared {k}: {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if v['ok'] else 'OVER'}", file=sys.stderr)
    print(f"correct={correct} attempted={attempted} failed={failed}",
          file=sys.stderr, flush=True)

    if args.rehearsal:
        line = {"rehearsal": True, "not_a_measurement": True, "correct": correct,
                "attempted": attempted, "failed": failed,
                "metrics_reported": sorted(metrics), "compared": compared}
    else:
        line = result_line(correct, attempted, failed, metrics, device, breakdown, compared)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
