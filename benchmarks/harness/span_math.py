"""Arithmetic on the host records of one window.

A tracer's ``records`` are ``(name, t0, t1, fields)`` on the
``time.perf_counter`` clock, in closing order; the window is
``[win["t_open"], win["t_close"]]`` on the same clock. Everything here is
plain Python on those tuples, so a reader can be checked against hand-made
records with a known answer.
"""
from __future__ import annotations

def records_of(run) -> tuple[list, float, float] | None:
    """(records, t_open, t_close) of the run's window; None without one."""
    win = run.get("win") or {}
    tr = win.get("tracer")
    if tr is None or "t_open" not in win or "t_close" not in win:
        return None
    return tr.records, win["t_open"], win["t_close"]


def inside(records, name: str, t_lo: float, t_hi: float) -> list[tuple]:
    """Spans *name* that lie wholly inside [t_lo, t_hi], by start."""
    return sorted((r for r in records
                   if r[0] == name and t_lo <= r[1] and r[2] <= t_hi),
                  key=lambda r: r[1])


def seconds(spans) -> float:
    return sum(t1 - t0 for _, t0, t1, _ in spans)


def engine_steps(run) -> tuple[list, list, float, float] | None:
    """(records, steps, lo, hi): the ``engine_step`` spans wholly inside the
    run's window and the stretch [first start, last end] they cover — steps
    follow each other on one thread, so any span of a step's phases that
    lies in that stretch belongs to one of them. None where there is no
    window or the program opens no such span."""
    got = records_of(run)
    if got is None:
        return None
    records, t_open, t_close = got
    steps = inside(records, "engine_step", t_open, t_close)
    if not steps:
        return None
    return records, steps, steps[0][1], steps[-1][2]


def sync_restarts(records, t_lo: float, t_hi: float) -> list[float]:
    """Per ``log_sync`` closed in the window: seconds from its close (the
    device has just finished everything it was given) to the close of the
    next ``step`` span (the next program is in the device's queue)."""
    steps = inside(records, "step", t_lo, float("inf"))
    out = []
    for _, _, t_sync, _ in inside(records, "log_sync", t_lo, t_hi):
        nxt = next((t1 for _, t0, t1, _ in steps if t0 >= t_sync), None)
        if nxt is not None and nxt <= t_hi:
            out.append(nxt - t_sync)
    return out
