"""The serving window: drives ``ServeEngine.submit`` / ``step`` on the real
clock, open loop.

One thread does what ``ServeEngine.run`` does — submit what is due, step —
so the generator can be late by at most one engine step; how late it ran is
reported. Every token's time is taken in the request's ``on_token`` callback.
TTFT counts from the time a request was DUE. Set-up ends with a ramp of the
same mix (a multiset of its own) so that the window opens on staggered,
steady traffic.

After the window: requests still owed a first token are waited for (up to
``drain_seconds``), the memory peak is read, the engine's weights and pool
are freed, and a sample of the finished requests, drawn from the seed with
the longest in it, is scored by the plain reference.
"""
from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmarks.harness import spans as spans_lib
from benchmarks.harness import counts, trace_reduce, traffic


class _Rec:
    __slots__ = ("spec", "sent", "times", "tokens", "done", "reason", "out")

    def __init__(self, spec):
        self.spec, self.sent, self.times, self.tokens = spec, None, [], []
        self.done, self.reason, self.out = False, None, None


class _Loop:
    """submit-what-is-due, then step — on the clock of ``time.perf_counter``."""

    def __init__(self, engine, tracer):
        from k8s_distributed_deeplearning_tpu.serve.request import QueueFull, Request
        self.Request, self.QueueFull = Request, QueueFull
        self.engine, self.tracer = engine, tracer
        self.recs: dict[str, _Rec] = {}
        self.pending: list[_Rec] = []
        self.t_zero = None           # perf_counter of the window's opening
        self.refused = 0
        self.annotate = False

    def add(self, specs):
        for s in specs:
            r = _Rec(s)
            self.recs[s["id"]] = r
            self.pending.append(r)
        self.pending.sort(key=lambda r: r.spec["due"])

    def _submit_due(self, now: float):
        while self.pending and self.t_zero + self.pending[0].spec["due"] <= now:
            r = self.pending.pop(0)
            times, toks = r.times, r.tokens

            def on_token(tok, times=times, toks=toks):
                times.append(time.perf_counter())
                toks.append(tok)
            req = self.Request(prompt=r.spec["prompt"],
                               max_new_tokens=r.spec["max_new_tokens"],
                               request_id=r.spec["id"], on_token=on_token)
            r.sent = time.perf_counter()
            try:
                self.engine.submit(req)
            except (self.QueueFull, ValueError) as e:      # refused: it failed
                r.done, r.reason = True, f"refused:{type(e).__name__}"
                self.refused += 1

    def run_until(self, t_end: float, *, stop_when=None):
        eng = self.engine
        while True:
            now = time.perf_counter()
            if now >= t_end or (stop_when is not None and stop_when()):
                return
            self._submit_due(now)
            if eng.busy():
                with spans_lib.bench_annotation("engine_step", self.annotate):
                    outs = eng.step()
                for o in outs:
                    r = self.recs.get(o.request_id)
                    if r is not None:
                        r.done, r.reason, r.out = True, o.finish_reason, o
            elif self.pending:
                nxt = self.t_zero + self.pending[0].spec["due"]
                time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
            else:
                time.sleep(min(0.001, max(0.0, t_end - time.perf_counter())))


def setup(cell, seed: int, split: dict, *, rehearsal: bool = False) -> dict:
    import jax
    from k8s_distributed_deeplearning_tpu.serve.engine import ServeEngine
    from k8s_distributed_deeplearning_tpu.utils.metrics import ServingStats

    cfg, mix, opt = cell.config, cell.traffic, cell.options
    fam = cell.family()
    eo = dict(opt["engine"])
    t0 = time.perf_counter()
    model, params = fam.build_model_and_params(cfg, eo["max_seq_len"], seed)
    jax.block_until_ready(params)
    split["weight_init"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    stats = ServingStats()
    tracer = spans_lib.BenchTracer()
    engine = ServeEngine(
        model, params, num_slots=eo["num_slots"], max_queue=eo["max_queue"],
        eos_id=None, min_bucket=eo["min_bucket"],
        prefill_chunk_tokens=eo["prefill_chunk_tokens"],
        prefix_cache_mb=eo.get("prefix_cache_mb"),
        prefix_block_tokens=eo.get("page_tokens"),
        kv_pool_pages=eo.get("kv_pool_pages"), stats=stats, tracer=tracer)
    split["engine_init"] = time.perf_counter() - t0

    # Warm-up: the programs this mix can reach and no others — decode, the
    # 128-token chunk, and each final-chunk bucket — by three requests whose
    # prompts end in each bucket. Their compile (first run) or load (later
    # runs) is set-up.
    t0 = time.perf_counter()
    rng = np.random.default_rng([seed % (2 ** 63), 7])
    c, mb = eo["prefill_chunk_tokens"], eo["min_bucket"]
    loop = _Loop(engine, tracer)
    loop.t_zero = time.perf_counter()
    warm, b = [], mb
    while b <= c:
        warm.append({"id": f"warm-{b}", "due": 0.0, "max_new_tokens": 3,
                     "prompt": rng.integers(0, cfg["vocab_size"], size=c + b, dtype=np.int32)})
        b *= 2
    loop.add(warm)
    loop.run_until(time.perf_counter() + 3600.0,
                   stop_when=lambda: not loop.pending and not engine.busy())
    split["warm_up"] = time.perf_counter() - t0

    sched = traffic.schedule(mix, opt, float(cell.manifest["run_seconds"])
                             if not rehearsal else float(mix.get("rehearsal_seconds", 3.0)),
                             seed, cfg["vocab_size"])
    # Ramp — set-up the traffic needs: the same mix (a multiset of its own)
    # runs for ramp_seconds, so the window opens on staggered, steady traffic.
    t0 = time.perf_counter()
    ramp_s = float(mix["ramp_seconds"])
    loop.t_zero = t0 + ramp_s
    loop.add(sched["ramp"])
    loop.add(sched["window"])
    loop.run_until(loop.t_zero)
    split["ramp"] = time.perf_counter() - t0
    return {"engine": engine, "stats": stats, "tracer": tracer, "loop": loop,
            "schedule": sched, "family": fam, "params": params, "model": model,
            "impls": engine.attention_impls()}


def _pct(xs, q):
    if not xs:
        return None
    xs = sorted(xs)
    k = (len(xs) - 1) * q
    lo, hi = int(np.floor(k)), int(np.ceil(k))
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def window(cell, sut: dict, seconds: float, trace_dir: str | None) -> dict:
    import jax
    mix = cell.traffic
    engine, loop, stats, tracer = sut["engine"], sut["loop"], sut["stats"], sut["tracer"]
    sched = sut["schedule"]
    # the schedule was cut for the manifest's run_seconds; a shorter --seconds
    # (a trial) sends only what is due inside it
    win_specs = [s for s in sched["window"] if s["due"] < seconds]
    keep = {s["id"] for s in win_specs}
    loop.pending = [r for r in loop.pending
                    if not r.spec["id"].startswith("w-") or r.spec["id"] in keep]

    compiles = spans_lib.CompileCounter.install()
    gc.collect()
    gc.freeze()
    load0 = os.getloadavg()
    steps0, occ0, dec0 = stats.steps, stats.occupancy_sum, stats.decode_tokens
    hit0, look0 = stats.prefix_hit_tokens, stats.prefix_lookup_tokens
    n_span0 = len(tracer.records)
    t_open = loop.t_zero
    t_end = t_open + seconds
    tr = {"t0": None, "t1": None}
    trace_len = float(mix.get("trace_seconds", 4.0))
    compiles.start()
    with spans_lib.GcCounter() as gcs:
        if trace_dir is None:
            loop.run_until(t_end)
        else:
            # The traced stretch is the window's last trace_seconds; the
            # profiler is stopped only after the window has closed and what
            # is owed has been waited for (stopping takes seconds).
            loop.run_until(t_end - trace_len)
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=trace_reduce.profiler_options())
            tracer.annotate = loop.annotate = True
            tr["t0"] = time.perf_counter()
            loop.run_until(t_end)
            tr["t1"] = time.perf_counter()
            with spans_lib.bench_annotation(trace_reduce.END_MARKER, True):
                pass
        t_close = time.perf_counter()
    n_compiles = compiles.stop()
    gc.unfreeze()
    steps = stats.steps - steps0
    occ = (stats.occupancy_sum - occ0) / steps if steps else None
    counters = {"steps": steps, "occupancy": occ,
                "decode_tokens": stats.decode_tokens - dec0,
                "prefix_hit_tokens": stats.prefix_hit_tokens - hit0,
                "prefix_lookup_tokens": stats.prefix_lookup_tokens - look0,
                "kv_pages_used": stats.kv_pages_used,
                "kv_pages_total": stats.kv_pages_total}

    # Requests due in the window that still owe a first token: wait for it —
    # an answer that comes late is late, and the latency counts the wait.
    # Above capacity (backlog) a queue stands by design: what is still queued
    # at the close was not attempted, and nothing is waited for.
    backlog = mix["arrivals"] == "backlog"
    due_ids = [s["id"] for s in win_specs]
    loop.pending = []                                      # nothing new is sent
    t0 = time.perf_counter()
    if not backlog:
        loop.run_until(t0 + float(mix.get("drain_seconds", 60.0)),
                       stop_when=lambda: all(loop.recs[i].times or loop.recs[i].done
                                             for i in due_ids))
    drain_s = time.perf_counter() - t0
    if trace_dir is not None:
        tracer.annotate = loop.annotate = False
        jax.profiler.stop_trace()

    window_s = t_close - t_open
    out_tokens, gaps, ttft, late, queue_wait = 0, [], [], [], []
    failed = attempted = 0
    for rid, r in loop.recs.items():
        ts = r.times
        in_win = sum(1 for t in ts if t_open <= t <= t_close)
        out_tokens += in_win
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if t_open <= b <= t_close)
        bad = r.done and r.reason != "length"          # refused, aborted, timed out
        if backlog:
            # attempted: what the engine served in the window
            if in_win or (bad and rid.startswith("w-")):
                attempted += 1
                failed += 1 if bad else 0
            if rid.startswith("w-") and ts and ts[0] <= t_close:
                ttft.append(ts[0] - t_open)
        elif rid.startswith("w-") and r.spec["due"] < seconds:
            # attempted: every request due in the window; one whose first
            # token never comes, or that ends otherwise than by its length,
            # has failed and counts as an infinite TTFT
            attempted += 1
            due_t = t_open + r.spec["due"]
            if ts and not bad:
                ttft.append(ts[0] - due_t)
            else:
                ttft.append(float("inf"))
                failed += 1
        if rid.startswith("w-") and r.sent is not None:
            late.append(r.sent - (t_open + r.spec["due"]))
        if rid.startswith("w-") and r.out is not None:
            queue_wait.append(r.out.queue_s)
    finished = [r for r in loop.recs.values()
                if r.done and r.reason == "length" and r.times
                and t_open <= r.times[-1] <= t_close]
    spans = tracer.records[n_span0:]
    cfg = cell.config
    # Operations of every token processed in the window (counts.py): decode
    # tokens at their real context, the prompts of requests whose first
    # token fell in the window, the head wherever logits are computed.
    model_flops = 0.0
    # What the decode steps of the traced stretch attended, in all: one
    # (position) per output token handed over while the trace was on.
    traced = {"tokens": 0, "positions": 0}
    for r in loop.recs.values():
        p = r.spec["prompt_len"] if "prompt_len" in r.spec else len(r.spec["prompt"])
        ts = r.times
        if ts and t_open <= ts[0] <= t_close:
            h, hd = cfg["num_attention_heads"], cfg["head_dim"]
            model_flops += (p * counts.gqa_forward_flops_per_token(cfg, 0, lm_head=False)
                            + cfg["num_hidden_layers"] * 2 * 2 * h * hd * p * (p + 1) / 2
                            + 2 * cfg["hidden_size"] * cfg["vocab_size"])
        for k in range(1, len(ts)):
            if t_open <= ts[k] <= t_close:
                model_flops += counts.gqa_forward_flops_per_token(cfg, p + k, lm_head=True)
            if tr["t0"] is not None and tr["t0"] < ts[k] <= tr["t1"]:
                traced["tokens"] += 1
                traced["positions"] += p + k
    return {
        "model_flops": model_flops,
        "traced_decode": traced if traced["tokens"] else None,
        "t_open": t_open, "t_close": t_close, "window_s": window_s,
        "requests_due": len(due_ids), "attempted": attempted,
        "requests_finished_in_window": len(finished),
        "out_tokens": out_tokens, "itl_samples": len(gaps),
        "itl_p50_ms": 1e3 * _pct(gaps, 0.5) if gaps else None,
        "itl_p95_ms": 1e3 * _pct(gaps, 0.95) if gaps else None,
        "itl_p99_ms": 1e3 * _pct(gaps, 0.99) if gaps else None,
        "ttft_p50_ms": 1e3 * _pct(ttft, 0.5) if ttft else None,
        "ttft_p90_ms": 1e3 * _pct(ttft, 0.9) if ttft else None,
        "ttft_max_ms": 1e3 * max(ttft) if ttft else None,
        "generator_lateness_p95_ms": 1e3 * _pct(late, 0.95) if late else None,
        "queue_wait_p50_ms": 1e3 * _pct(queue_wait, 0.5) if queue_wait else None,
        "counters": counters, "failed": failed, "refused": loop.refused,
        "compiles_in_window": n_compiles, "compile_events": list(compiles.events),
        "gc_collections": gcs.n, "gc_seconds": gcs.seconds,
        "loadavg_open": load0, "loadavg_close": os.getloadavg(),
        "drain_s": drain_s, "queue_len_close": len(engine.queue),
        "prefill_spans": sum(1 for n, *_ in spans if n == "prefill"),
        "decode_spans": sum(1 for n, *_ in spans if n == "decode"),
        "requests": finished, "tracer": tracer,
        "trace": ({"t0": tr["t0"], "t1": tr["t1"]} if tr["t0"] is not None else None),
        "attention_impls": sut["impls"],
    }


def end_to_end(cell, sut: dict, win: dict) -> dict:
    return {"serve_out_tokens_per_s": win["out_tokens"] / win["window_s"],
            "ttft_p50_ms": win["ttft_p50_ms"], "itl_p95_ms": win["itl_p95_ms"]}


def release(sut: dict) -> None:
    """Free the engine's weights and pool (before the reference)."""
    import jax
    eng = sut.pop("engine", None)
    if eng is not None:
        eng.shutdown()
        for leaf in jax.tree.leaves(eng._cache) + jax.tree.leaves(sut.pop("params", None)):
            if not leaf.is_deleted():
                leaf.delete()
        eng._cache = None
        eng.params = None
    sut.pop("loop", None)
    sut.pop("model", None)
    gc.collect()


def pick_sample(cell, seed: int, finished: list) -> list[dict]:
    """The longest finished request and a few more drawn from the seed."""
    if not finished:
        return []
    k = int(cell.traffic.get("sample_requests", 4))
    by_len = sorted(finished, key=lambda r: -(r.spec["prompt_len"] + len(r.tokens)))
    rng = np.random.default_rng([seed % (2 ** 63), 99])
    rest = by_len[1:]
    idx = rng.permutation(len(rest))[:max(0, k - 1)]
    chosen = [by_len[0]] + [rest[i] for i in sorted(idx)]
    return [{"id": r.spec["id"], "prompt": np.asarray(r.spec["prompt"]),
             "tokens": np.asarray(r.tokens, np.int32)} for r in chosen]


def correctness(cell, seed: int, sut: dict, win: dict):
    fam = sut["family"]
    sample = pick_sample(cell, seed, win["requests"])
    attempted = win["attempted"]
    if not sample:
        return {}, {"error": "no request finished in the window"}, attempted, max(1, win["failed"])
    sut["sample"] = sample
    sc = fam.reference.score_served(cell.config, seed, sample)
    numbers = {"logit_gap_max": sc["logit_gap_max"]}
    notes = {k: v for k, v in sc.items() if k != "logit_gap_max"}
    notes["sample"] = [(s["id"], len(s["prompt"]), len(s["tokens"])) for s in sample]
    return numbers, notes, attempted, win["failed"]


def readings(cell, seed: int, *, control: bool, seconds: float = 40.0) -> dict:
    """One seed's numbers for setting limits: a short window at the cell's
    own load, then the reference; with *control* also the same prompts and
    tokens through the reference at the nearest precision below, and with a
    served token altered."""
    sut = setup(cell, seed, {})
    win = window(cell, sut, seconds, None)
    release(sut)
    numbers, notes, _, _ = correctness(cell, seed, sut, win)
    out = {"program": numbers, "program_notes": {k: notes.get(k) for k in
                                                  ("tokens", "not_reference_best", "logit_std",
                                                   "logit_gap_mean", "per_request_max", "sample")}}
    if control and sut.get("sample"):
        fam = sut["family"]
        low = cell.config.get("control_precision", "fp8")
        c = fam.reference.score_served(cell.config, seed, sut["sample"], precision=low)
        out[f"control_{low}"] = {"logit_gap_max": c["logit_gap_max"]}
        out[f"control_{low}_notes"] = {"not_reference_best": c["not_reference_best"],
                                       "logit_gap_mean": c["logit_gap_mean"],
                                       "per_request_max": c["per_request_max"]}
        f = fam.reference.score_served(cell.config, seed, sut["sample"], fault="alter")
        out["fault_alter"] = {"logit_gap_max": f["logit_gap_max"]}
    return out


def import_program() -> None:
    """The program's modules this driver uses (their import is set-up)."""
    from k8s_distributed_deeplearning_tpu.models import llama  # noqa: F401
    from k8s_distributed_deeplearning_tpu.serve import engine, request  # noqa: F401
