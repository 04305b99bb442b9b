"""From a profiler trace to numbers — the benchmark's own reduction.

``extract`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
lists (read with ``jax.profiler.ProfileData``, nothing else); ``reduce`` works
on those lists alone, so it can be checked against a small recorded trace
kept as JSON.

What ``reduce`` reports, per traced window:

- ``busy_s``: seconds in which an operation ran on the device — the union of
  the intervals on the device's operation line — averaged over the devices
  used; ``window_s``: first operation's start to last operation's end.
- ``device_ops``: operations by total seconds, under stable names (the HLO
  instruction's name without its number, ``fusion.123`` -> ``fusion``,
  behind the program whose run contains it: ``jit_step/fusion``).
- ``programs``: per jitted program (``XLA Modules`` line), calls and seconds.
- ``idle_gaps``: the idle time between operations, attributed to what the
  host was doing — the ``program:*`` / ``bench:*`` span on the profiler's
  clock that covers most of the gap, else ``within_<program>`` when the gap
  lies inside one program's run, else ``unattributed``.
- ``collective_s`` and ``collective_exposed_s``: time of the collective
  operations, and the part of it during which no other operation ran on
  that device; worst device.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import shutil

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all|send|recv)")
HOST_SPAN = re.compile(r"^(program:|bench:)")
# a host span the window driver emits when the traced stretch ends: what the
# device does after it (a drain, the profiler's own stop) is not read
END_MARKER = "traced_window_end"
KEEP_STATS = ("hlo_module", "hlo_op", "program_id", "run_id", "tf_op",
              "hlo_category", "long_name", "name")


def profiler_options():
    """Device operations and our own host annotations; no Python call
    tracing (it slows the host and swells the trace)."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 2
    return o


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def extract(xplane_path: str, *, host_pattern=HOST_SPAN) -> dict:
    """Plain lists from the trace: every device line whole, host lines cut
    down to the spans of our own."""
    import jax
    pd = jax.profiler.ProfileData.from_file(xplane_path)
    planes = []
    for pl in pd.planes:
        device = pl.name.startswith("/device:")
        lines = []
        for ln in pl.lines:
            events = []
            for e in ln.events:
                if not device and not host_pattern.match(e.name):
                    continue
                stats = {}
                if device:
                    for k, v in e.stats:
                        if k in KEEP_STATS:
                            stats[k] = v if isinstance(v, (int, float)) else str(v)[:120]
                events.append([e.name, float(e.start_ns), float(e.duration_ns), stats])
            if events:
                lines.append({"name": ln.name, "events": events})
        if lines:
            planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


_RESULT = re.compile(r" = \(?([a-z][a-z0-9]*\[[0-9,]*\])")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def stable_name(name: str) -> str:
    """A name that survives recompilation. The TPU's operation events carry
    the HLO instruction's text: ``%fusion.123 = bf16[16,512]{...} fusion(...)``
    -> ``fusion:bf16[16,512]`` (the instruction's name without its number,
    and the first result's type and shape). ``fusion.123`` -> ``fusion``;
    ``jit_step(7)`` -> ``jit_step``."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"\(\d+\)$", "", head.lstrip("%"))
    base = re.sub(r"[.\-_]\d+$", "", re.sub(r"\.\d+(?=\.|$)", "", base))
    m = _RESULT.match(" = " + rest) if rest else None
    return base + (":" + m.group(1) if m else "")


def opcode(name: str) -> str:
    """The HLO opcode of an operation event (``while``, ``fusion``,
    ``custom-call``, ``all-reduce-start`` ...); the bare name where the event
    carries no instruction text."""
    _, _, rest = name.partition(" = ")
    m = _OPCODE.search(" " + rest) if rest else None
    return m.group(1) if m else re.sub(r"[.\-_]\d+$", "", name.lstrip("%"))


def is_container(name: str) -> bool:
    """``while`` / ``conditional`` / ``call``: they span the operations they
    contain, so they count for busy time but not in the per-operation sums."""
    return opcode(name) in CONTAINERS


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _covered(merged: list[tuple[float, float]], a: float, b: float) -> float:
    """Length of [a, b] covered by the merged intervals."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged if y > a and x < b)


def _device_planes(ex: dict) -> list[dict]:
    devs = [p for p in ex["planes"] if p["name"].startswith("/device:")
            and any(l["name"] == OPS_LINE for l in p["lines"])]
    return sorted(devs, key=lambda p: p["name"])


def _line(plane: dict, name: str) -> list:
    for l in plane["lines"]:
        if l["name"] == name:
            return l["events"]
    return []


def reduce(ex: dict, n_devices: int = 1) -> dict | None:
    devs = _device_planes(ex)[:n_devices]
    if not devs:
        return None
    host_spans = []
    for p in ex["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for l in p["lines"]:
            for name, s, d, _ in l["events"]:
                if HOST_SPAN.match(name):
                    host_spans.append((s, s + d, name))
    host_spans.sort()
    ends = [a for a, _, n in host_spans if n == "bench:" + END_MARKER]
    t_clip = min(ends) if ends else float("inf")
    host_spans = [h for h in host_spans if h[2] != "bench:" + END_MARKER]

    per_dev = []
    ops_total: dict[str, float] = {}
    ops_calls: dict[str, int] = {}
    programs: dict[str, dict] = {}
    gaps_total: dict[str, float] = {}
    for di, plane in enumerate(devs):
        ops = [e for e in _line(plane, OPS_LINE) if e[1] < t_clip]
        mods = [e for e in _line(plane, MODULES_LINE) if e[1] < t_clip]
        if not ops:
            continue
        t_lo = min(s for _, s, _, _ in ops)
        t_hi = max(s + d for _, s, d, _ in ops)
        busy = _merge([(s, s + d) for _, s, d, _ in ops if d > 0])
        busy_ns = sum(b - a for a, b in busy)
        # Collectives: the asynchronous ones span start to done on the
        # "Async XLA Ops" line; synchronous ones sit on the operation line.
        coll = [(s, s + d) for n, s, d, _ in ops + _line(plane, ASYNC_LINE)
                if s < t_clip and COLLECTIVE.match(opcode(n))]
        comp = _merge([(s, s + d) for n, s, d, _ in ops
                       if d > 0 and not COLLECTIVE.match(opcode(n))
                       and not is_container(n)])
        coll_ns = sum(b - a for a, b in _merge(coll))
        exposed_ns = sum((b - a) - _covered(comp, a, b) for a, b in _merge(coll))
        per_dev.append({"plane": plane["name"], "window_ns": t_hi - t_lo,
                        "busy_ns": busy_ns, "collective_ns": coll_ns,
                        "collective_exposed_ns": exposed_ns})
        mod_iv = sorted((s, s + d, stable_name(n)) for n, s, d, _ in mods)
        if di == 0:
            starts = [a for a, _, _ in mod_iv]
            for n, s, d, st in ops:
                if is_container(n):
                    continue
                # an operation belongs to the program whose run contains it
                i = bisect.bisect_right(starts, s) - 1
                prog = (mod_iv[i][2] if i >= 0 and s < mod_iv[i][1]
                        else stable_name(str(st.get("hlo_module", "?"))))
                key = prog + "/" + stable_name(n)
                ops_total[key] = ops_total.get(key, 0.0) + d
                ops_calls[key] = ops_calls.get(key, 0) + 1
            for a, b, n in mod_iv:
                pr = programs.setdefault(n, {"calls": 0, "seconds": 0.0})
                pr["calls"] += 1
                pr["seconds"] += (b - a) / 1e9
            # idle gaps, by what the host was doing
            edges = [(t_lo, t_lo)] + busy + [(t_hi, t_hi)]
            for (_, a), (b, _) in zip(edges, edges[1:]):
                if b - a <= 0:
                    continue
                # the innermost host span that covers most of the gap: spans
                # nest (bench:engine_step around program:decode), and the
                # shortest one that still covers half the gap names it best
                label, best, best_len = None, 0.0, float("inf")
                for s, e, n in host_spans:
                    if s >= b:
                        break
                    ov = min(b, e) - max(a, s)
                    if ov >= 0.5 * (b - a) and (e - s) < best_len:
                        best, best_len, label = ov, e - s, n
                if label is None:
                    inside = [n for s, e, n in mod_iv if s <= a and b <= e]
                    if inside:
                        label = "within_" + inside[0]
                    elif label is None:
                        label = "unattributed"
                gaps_total[label] = gaps_total.get(label, 0.0) + (b - a)
    if not per_dev:
        return None
    n = len(per_dev)
    worst = max(per_dev, key=lambda d: d["collective_exposed_ns"])
    return {
        "devices": n,
        "busy_s": sum(d["busy_ns"] for d in per_dev) / n / 1e9,
        "window_s": sum(d["window_ns"] for d in per_dev) / n / 1e9,
        "per_device": per_dev,
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(ops_total.items(), key=lambda kv: -kv[1])],
        "device_op_calls": ops_calls,
        "programs": programs,
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(gaps_total.items(), key=lambda kv: -kv[1])],
        "collective_s": worst["collective_ns"] / 1e9,
        "collective_exposed_s": worst["collective_exposed_ns"] / 1e9,
        "collective_window_s": worst["window_ns"] / 1e9,
    }


def op_seconds(red: dict, pattern: str) -> tuple[float, int]:
    """Seconds and calls (first device) of the operations whose stable name
    matches *pattern*."""
    rx = re.compile(pattern)
    secs = sum(v for k, v in red["device_ops"] if rx.search(k))
    calls = sum(c for k, c in red["device_op_calls"].items() if rx.search(k))
    return secs, calls


def program_stats(red: dict, pattern: str) -> tuple[float, int]:
    rx = re.compile(pattern)
    hits = [v for k, v in red["programs"].items() if rx.search(k)]
    return sum(h["seconds"] for h in hits), sum(h["calls"] for h in hits)


def reduce_dir(trace_dir: str, n_devices: int = 1, keep: str | None = None) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    ex = extract(path)
    if keep:
        os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
        with open(keep, "w") as f:
            json.dump(ex, f)
    return reduce(ex, n_devices)


def cleanup(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)
