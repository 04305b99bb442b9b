"""Structured metrics — the Loki/Promtail/Grafana-facing surface.

The reference's observability story (its signature feature, ``README.md:9-15``)
is: apps print loss to stdout every 10 steps (``LoggingTensorHook``,
``tensorflow_mnist.py:148-149``), Promtail tails pod stdout into Loki, Grafana
queries Loki. The app side needs zero integration beyond *printing*.

This module keeps that contract but emits **structured JSON lines** (one
object per event) so Grafana/LogQL can parse fields instead of regexing free
text — and adds the quantities the reference never measured (§6): step time,
images/sec/chip, MFU. Cross-replica metric averaging happens inside the jitted
train step via ``pmean`` (parity: ``MetricAverageCallback``,
``tensorflow_mnist_gpu.py:153``), so what lands here is already global.
"""
from __future__ import annotations

import functools
import json
import sys
import threading
import time
from typing import Any, IO


class MetricsLogger:
    """Emit JSONL metric events to stdout (→ Promtail → Loki) and optionally a file.

    Only the primary process should construct one with ``enabled=True`` — the
    rank-0 logging discipline (``tensorflow_mnist.py:148-149,159``).
    """

    def __init__(self, enabled: bool = True, stream: IO[str] | None = None,
                 path: str | None = None, job: str = "train"):
        self.enabled = enabled
        self.stream = stream if stream is not None else sys.stdout
        self.job = job
        self._file = open(path, "a") if (path and enabled) else None
        self._t0 = time.monotonic()
        self._emit_warned = False

    def emit(self, event: str, **fields: Any) -> None:
        if not self.enabled:
            return
        rec = {"event": event, "job": self.job,
               "elapsed_s": round(time.monotonic() - self._t0, 3)}
        for k, v in fields.items():
            try:
                if hasattr(v, "item"):
                    v = v.item()
                if isinstance(v, float):
                    v = round(v, 6)
            except Exception:
                # A metric value must never kill a training step: a device
                # array mid-donation, a lazy object whose .item() raises —
                # fall through and let the repr fallback below record it.
                pass
            rec[k] = v
        # default=repr: non-JSON-serializable values degrade to their repr
        # string instead of raising — the event still lands in Loki.
        line = json.dumps(rec, default=repr)
        try:
            print(line, file=self.stream, flush=True)
            if self._file:
                self._file.write(line + "\n")
                self._file.flush()
        except Exception as e:   # noqa: BLE001 — a broken pipe or full
            # disk under the metrics sink must degrade observability, not
            # the training step that emitted the event.
            if not self._emit_warned:
                self._emit_warned = True
                try:
                    print(f"metrics emit failed (suppressing further "
                          f"warnings): {e!r}", file=sys.stderr)
                except Exception:
                    pass

    def train_step(self, step: int, loss: float, step_time_ms: float,
                   examples_per_sec: float, per_chip: float,
                   mfu: float | None = None, **extra: Any) -> None:
        self.emit("train_step", step=step, loss=loss, step_time_ms=step_time_ms,
                  examples_per_sec=examples_per_sec,
                  examples_per_sec_per_chip=per_chip,
                  **({"mfu": mfu} if mfu is not None else {}), **extra)

    def close(self) -> None:
        if self._file:
            self._file.close()
            self._file = None


def _locked(method):
    """Run *method* under ``self._lock``. ServingStats is written by the
    engine/gateway step path and read mid-step by exporter collector
    threads (``summary()``, the bridge's per-counter reads); the RLock
    makes each record/summary atomic — RLock, not Lock, because
    ``summary()`` reads the ``total_tokens`` property, which takes the
    lock again on the same thread."""
    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        with self._lock:
            return method(self, *args, **kwargs)
    return wrapper


class ServingStats:
    """Aggregates the serving engine's per-iteration observations into the
    quantities a capacity planner actually reads: aggregate tokens/sec,
    time-to-first-token and per-request latency percentiles, and mean slot
    occupancy (the fraction of decode-batch rows doing useful work — the
    number continuous batching exists to raise).

    The clock starts at the first recorded event and advances with each
    one, so ``summary()`` measures the active serving window, not object
    lifetime. One emitted token per admission (the prefill-sampled first
    token) plus one per active slot per decode step.
    """

    def __init__(self):
        self._lock = threading.RLock()
        self.t_start: float | None = None
        self.t_last: float | None = None
        self.steps = 0
        self.decode_tokens = 0
        self.occupancy_sum = 0.0
        self.admitted = 0
        self.completed = 0
        self.prompt_tokens = 0
        self.queue_s: list[float] = []
        self.ttft_s: list[float] = []
        self.latency_s: list[float] = []
        self.finish_reasons: dict[str, int] = {}
        # Prefix-reuse KV cache: one lookup per admission (hit = matched
        # >= 1 block); token counts measure how much prefill was skipped.
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_tokens = 0
        self.prefix_lookup_tokens = 0
        self.prefix_evictions = 0
        # Sampled end-to-end request_trace events emitted (graftscope).
        self.request_traces = 0
        # Paged KV pool utilization gauges (latest snapshot, not rates):
        # total usable pages, pages with >= 1 holder, pages with >= 2
        # holders (trie+slot or multi-slot sharing — the copy-free wins).
        self.kv_pages_total = 0
        self.kv_pages_used = 0
        self.kv_pages_shared = 0
        # Page-ledger attribution: owner class -> live pages (slot/trie/
        # draft + the reservation headroom). Feeds the per-owner gauge
        # family and the flight recorder's pool snapshot.
        self.kv_pages_by_owner: dict[str, int] = {}
        # Failover gateway (serve/gateway.py): request dispatches to a
        # replica, in-flight migrations off sick/draining replicas,
        # speculative hedge dispatches, and circuit-breaker trips.
        self.gateway_dispatches = 0
        self.gateway_migrations = 0
        self.gateway_hedges = 0
        self.gateway_breaker_trips = 0
        self.gateway_poisoned = 0
        # Remote-replica transport (serve/transport.py): transient-call
        # retries, idempotent submits the replica server deduplicated
        # (the ambiguous-failure path working as designed), and token
        # streams resumed from their cursor after failed polls.
        self.transport_retries = 0
        self.transport_dedup_hits = 0
        self.transport_reconnects = 0
        # Speculative decoding (draft-and-verify): draft tokens proposed
        # vs accepted-and-emitted, spec iterations run, and a histogram
        # of accepted-draft count per slot-iteration (key 0..spec_k — the
        # shape of the acceptance distribution, not just its mean).
        self.spec_steps = 0
        self.spec_proposed_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_accept_hist: dict[int, int] = {}
        # Disaggregated prefill/decode (serve/disagg.py): KV exports
        # staged off this engine, imports adopted into it, bytes shipped
        # each way, and unified-path fallbacks the coordinator took when
        # no prefill worker was healthy. Depth gauges are the
        # coordinator's latest per-role backlog snapshot.
        self.disagg_exports = 0
        self.disagg_imports = 0
        self.disagg_bytes_shipped = 0
        self.disagg_fallbacks = 0
        self.disagg_prefill_depth = 0
        self.disagg_decode_depth = 0
        # Quantized serving (graftquant): active modes (None = fp) and
        # the HBM bytes the quantized representation saves vs fp — KV
        # pool (full-arena fp-equivalent minus int8+scales) plus int8
        # weights (fp params minus int8+scales). Gauges, set once at
        # engine construction.
        self.kv_quant: str | None = None
        self.weight_quant: str | None = None
        self.kv_quant_bytes_saved = 0
        self.weight_quant_bytes_saved = 0
        # Expert layers (models/moe.py), per compiled call summed: picks
        # that landed on held experts, held experts with at least one row
        # (summed over layers), and the fullest expert any call has seen.
        self.moe_assignments = 0
        self.moe_experts_touched = 0
        self.moe_max_rows = 0
        # Per-slot state beside the page pool (a ShortConv's tail, a Mamba2's
        # tail and state): slots a request holds now, their bytes, and the
        # rows the last decode step advanced. Gauges; 0 for a model of
        # pages only.
        self.state_slots = 0
        self.state_bytes = 0
        self.state_update_rows = 0
        # The engine's blocking reads of device results (its ``device_wait``
        # spans), and those of them made with a later program already
        # dispatched behind the awaited one: the device had work queued
        # while the host waited.
        self.fences = 0
        self.fences_covered = 0
        # Decode and spec-verify dispatches, and those of them with a
        # sampling row (temperature > 0) in the register file: the steps
        # whose sampler sorts the vocabulary.
        self.sampler_steps = 0
        self.sampler_sort_steps = 0

    def _tick(self) -> None:
        now = time.perf_counter()
        if self.t_start is None:
            self.t_start = now
        self.t_last = now

    @_locked
    def record_admission(self, queue_s: float, prompt_len: int) -> None:
        self._tick()
        self.admitted += 1
        self.prompt_tokens += prompt_len
        self.queue_s.append(queue_s)

    @_locked
    def record_first_token(self, ttft_s: float) -> None:
        self._tick()
        self.ttft_s.append(ttft_s)

    @_locked
    def record_step(self, active_slots: int, num_slots: int,
                    tokens: int | None = None) -> None:
        """One decode iteration. ``tokens`` overrides the emitted-token
        count for the step (a speculative iteration emits between 1 and
        spec_k + 1 tokens per active slot); None keeps the classic
        one-per-active-slot accounting."""
        self._tick()
        self.steps += 1
        self.decode_tokens += active_slots if tokens is None else int(tokens)
        self.occupancy_sum += active_slots / max(num_slots, 1)

    @_locked
    def record_spec_step(self, proposed: int,
                         accepted_counts: "list[int] | tuple[int, ...]"
                         ) -> None:
        """One speculative iteration: ``proposed`` draft tokens were
        generated in total and ``accepted_counts`` holds each active
        slot's accepted-and-emitted draft count (0..spec_k), binned into
        the per-slot-step acceptance histogram."""
        self._tick()
        self.spec_steps += 1
        self.spec_proposed_tokens += int(proposed)
        for a in accepted_counts:
            a = int(a)
            self.spec_accepted_tokens += a
            self.spec_accept_hist[a] = self.spec_accept_hist.get(a, 0) + 1

    @_locked
    def record_prefix_lookup(self, hit_tokens: int,
                             prompt_tokens: int) -> None:
        """One prefix-cache lookup at admission: ``hit_tokens`` of the
        ``prompt_tokens``-long prompt were served from cached KV."""
        self._tick()
        if hit_tokens > 0:
            self.prefix_hits += 1
        else:
            self.prefix_misses += 1
        self.prefix_hit_tokens += hit_tokens
        self.prefix_lookup_tokens += prompt_tokens

    @_locked
    def record_prefix_evictions(self, n_blocks: int) -> None:
        self._tick()
        self.prefix_evictions += n_blocks

    @_locked
    def record_request_trace(self) -> None:
        """One sampled ``request_trace`` lifecycle event was emitted."""
        self._tick()
        self.request_traces += 1

    @_locked
    def record_kv_pool(self, pages_total: int, pages_used: int,
                       pages_shared: int,
                       by_owner: dict | None = None) -> None:
        """Latest paged-KV pool utilization snapshot. Deliberately NO
        ``_tick()``: a gauge refresh is not serving activity and must not
        stretch the elapsed window the throughput rates divide by.
        ``by_owner`` carries the page ledger's owner attribution
        (slot/trie/draft/reserved); None leaves the last value in place."""
        self.kv_pages_total = int(pages_total)
        self.kv_pages_used = int(pages_used)
        self.kv_pages_shared = int(pages_shared)
        if by_owner is not None:
            self.kv_pages_by_owner = {k: int(v) for k, v in by_owner.items()}

    @_locked
    def record_gateway_dispatch(self) -> None:
        """One gateway request dispatch (first placement, a migration
        resubmit, or a hedge) landed on a replica."""
        self._tick()
        self.gateway_dispatches += 1

    @_locked
    def record_gateway_migration(self) -> None:
        """One live request was migrated off a tripped/draining replica
        and resubmitted (prompt + emitted tokens) to a healthy one."""
        self._tick()
        self.gateway_migrations += 1

    @_locked
    def record_gateway_hedge(self) -> None:
        """One speculative duplicate dispatch for a straggling prefill."""
        self._tick()
        self.gateway_hedges += 1

    @_locked
    def record_gateway_breaker_trip(self) -> None:
        """One per-replica circuit breaker opened (consecutive dispatch
        failures or a failed half-open probe)."""
        self._tick()
        self.gateway_breaker_trips += 1

    @_locked
    def record_gateway_poisoned(self) -> None:
        """One request quarantined: it exhausted the gateway's
        ``max_migrations`` budget (its replicas keep dying under it) and
        was finished terminally with reason "poisoned"."""
        self._tick()
        self.gateway_poisoned += 1

    @_locked
    def record_transport_retry(self) -> None:
        """One remote-replica transport call retried after a transient
        failure (connection error / timeout / injected network fault)."""
        self._tick()
        self.transport_retries += 1

    @_locked
    def record_transport_dedup(self) -> None:
        """One retried submit was deduplicated by the replica server —
        the request had landed but its response was lost (the ambiguous
        failure idempotent submit exists for)."""
        self._tick()
        self.transport_dedup_hits += 1

    @_locked
    def record_transport_reconnect(self) -> None:
        """One token stream resumed from its emitted-token cursor after
        one or more failed polls (exactly-once splice held)."""
        self._tick()
        self.transport_reconnects += 1

    @_locked
    def record_disagg_export(self, pages: int, nbytes: int) -> None:
        """One request's KV pages were staged off this engine (prefill
        worker handoff, or live page-shipping migration)."""
        self._tick()
        self.disagg_exports += 1
        self.disagg_bytes_shipped += int(nbytes)

    @_locked
    def record_disagg_import(self, pages: int, nbytes: int) -> None:
        """One exported request was adopted into this engine's pool
        (pages tagged ``imported``) and resumed decoding."""
        self._tick()
        self.disagg_imports += 1
        self.disagg_bytes_shipped += int(nbytes)

    @_locked
    def record_disagg_fallback(self) -> None:
        """The coordinator routed one prompt down the unified decode-local
        prefill path because no prefill worker was healthy (or a shipped
        transfer failed and the request resumed by token re-prefill)."""
        self._tick()
        self.disagg_fallbacks += 1

    @_locked
    def record_disagg_depth(self, prefill: int, decode: int) -> None:
        """Latest per-role backlog snapshot (coordinator view). NO
        ``_tick()`` — a gauge refresh is not serving activity."""
        self.disagg_prefill_depth = int(prefill)
        self.disagg_decode_depth = int(decode)

    @_locked
    def record_quant(self, kv_quant: str | None, weight_quant: str | None,
                     kv_bytes_saved: int, weight_bytes_saved: int) -> None:
        """Quantization configuration gauge, set once when the engine
        builds its pool/params. NO ``_tick()`` — construction is not
        serving activity."""
        self.kv_quant = kv_quant
        self.weight_quant = weight_quant
        self.kv_quant_bytes_saved = int(kv_bytes_saved)
        self.weight_quant_bytes_saved = int(weight_bytes_saved)

    @_locked
    def record_moe(self, assignments: int, experts_touched: int,
                   max_rows: int) -> None:
        self.moe_assignments += int(assignments)
        self.moe_experts_touched += int(experts_touched)
        self.moe_max_rows = max(self.moe_max_rows, int(max_rows))

    @_locked
    def record_state(self, state_slots: int, state_bytes: int) -> None:
        """Latest state-arena occupancy. A gauge: no ``_tick()``."""
        self.state_slots = int(state_slots)
        self.state_bytes = int(state_bytes)

    @_locked
    def record_state_update(self, rows: int) -> None:
        """Rows whose state the last decode step advanced. A gauge."""
        self.state_update_rows = int(rows)

    @_locked
    def record_fence(self, covered: int) -> None:
        self.fences += 1
        self.fences_covered += int(covered)

    @_locked
    def record_sampler_step(self, sampled_rows: int) -> None:
        self.sampler_steps += 1
        self.sampler_sort_steps += int(sampled_rows > 0)

    @_locked
    def record_completion(self, latency_s: float, n_tokens: int,
                          reason: str) -> None:
        self._tick()
        self.completed += 1
        self.latency_s.append(latency_s)
        self.finish_reasons[reason] = self.finish_reasons.get(reason, 0) + 1

    @property
    @_locked
    def total_tokens(self) -> int:
        """Emitted tokens: one per admission + one per active slot-step."""
        return self.decode_tokens + len(self.ttft_s)

    @staticmethod
    def _pct(xs: list[float], q: float) -> float | None:
        if not xs:
            return None
        s = sorted(xs)
        return s[min(len(s) - 1, int(round(q * (len(s) - 1))))]

    @_locked
    def summary(self) -> dict:
        elapsed = ((self.t_last - self.t_start)
                   if self.t_start is not None and self.t_last is not None
                   else 0.0)
        return {
            "elapsed_s": round(elapsed, 4),
            "requests_admitted": self.admitted,
            "requests_completed": self.completed,
            "finish_reasons": dict(self.finish_reasons),
            "total_tokens": self.total_tokens,
            "prompt_tokens": self.prompt_tokens,
            "tokens_per_sec": (round(self.total_tokens / elapsed, 1)
                               if elapsed > 0 else None),
            "decode_steps": self.steps,
            "mean_slot_occupancy": (round(self.occupancy_sum / self.steps, 4)
                                    if self.steps else None),
            "ttft_p50_ms": _ms(self._pct(self.ttft_s, 0.5)),
            "ttft_p95_ms": _ms(self._pct(self.ttft_s, 0.95)),
            "queue_p50_ms": _ms(self._pct(self.queue_s, 0.5)),
            "queue_p95_ms": _ms(self._pct(self.queue_s, 0.95)),
            "latency_p50_ms": _ms(self._pct(self.latency_s, 0.5)),
            "latency_p95_ms": _ms(self._pct(self.latency_s, 0.95)),
            "prefix_cache_hits": self.prefix_hits,
            "prefix_cache_misses": self.prefix_misses,
            "prefix_cache_evictions": self.prefix_evictions,
            "kv_pages_total": self.kv_pages_total,
            "kv_pages_used": self.kv_pages_used,
            "kv_pages_shared": self.kv_pages_shared,
            "kv_pages_by_owner": dict(self.kv_pages_by_owner),
            "request_traces_sampled": self.request_traces,
            "gateway_dispatches": self.gateway_dispatches,
            "gateway_migrations": self.gateway_migrations,
            "gateway_hedges": self.gateway_hedges,
            "gateway_breaker_trips": self.gateway_breaker_trips,
            "gateway_poisoned": self.gateway_poisoned,
            "transport_retries": self.transport_retries,
            "transport_dedup_hits": self.transport_dedup_hits,
            "transport_reconnects": self.transport_reconnects,
            "disagg_exports": self.disagg_exports,
            "disagg_imports": self.disagg_imports,
            "disagg_bytes_shipped": self.disagg_bytes_shipped,
            "disagg_fallbacks": self.disagg_fallbacks,
            "disagg_prefill_depth": self.disagg_prefill_depth,
            "disagg_decode_depth": self.disagg_decode_depth,
            "kv_quant": self.kv_quant,
            "weight_quant": self.weight_quant,
            "kv_quant_bytes_saved": self.kv_quant_bytes_saved,
            "weight_quant_bytes_saved": self.weight_quant_bytes_saved,
            "moe_assignments": self.moe_assignments,
            "moe_experts_touched": self.moe_experts_touched,
            "moe_max_rows": self.moe_max_rows,
            "state_slots": self.state_slots,
            "state_bytes": self.state_bytes,
            "state_update_rows": self.state_update_rows,
            "fences": self.fences,
            "fences_covered": self.fences_covered,
            # Share of the blocking reads that had a program queued behind
            # the awaited one (None until the first read).
            "fence_covered_share": (
                round(self.fences_covered / self.fences, 4)
                if self.fences else None),
            "sampler_steps": self.sampler_steps,
            "sampler_sort_steps": self.sampler_sort_steps,
            # Share of the decode / spec-verify dispatches whose sampler
            # sorted (None until the first one).
            "sampler_sort_share": (
                round(self.sampler_sort_steps / self.sampler_steps, 4)
                if self.sampler_steps else None),
            "spec_steps": self.spec_steps,
            "spec_proposed_tokens": self.spec_proposed_tokens,
            "spec_accepted_tokens": self.spec_accepted_tokens,
            # Fraction of proposed drafts accepted AND emitted (None
            # until the first speculative iteration).
            "spec_acceptance_rate": (
                round(self.spec_accepted_tokens / self.spec_proposed_tokens,
                      4) if self.spec_proposed_tokens else None),
            "spec_accept_hist": {str(k): v for k, v in
                                 sorted(self.spec_accept_hist.items())},
            # Fraction of looked-up prompt tokens served from cached KV
            # (None until the first lookup, i.e. cache disabled or idle).
            "prefix_hit_rate": (
                round(self.prefix_hit_tokens / self.prefix_lookup_tokens, 4)
                if self.prefix_lookup_tokens else None),
        }


def _ms(s: float | None) -> float | None:
    return round(s * 1e3, 3) if s is not None else None


def mfu(flops_per_example: float, examples_per_sec: float, num_devices: int,
        peak_flops_per_device: float) -> float:
    """Model FLOPs utilization: achieved model FLOP/s over peak hardware FLOP/s."""
    if peak_flops_per_device <= 0 or num_devices <= 0:
        return 0.0
    return (flops_per_example * examples_per_sec) / (peak_flops_per_device * num_devices)
