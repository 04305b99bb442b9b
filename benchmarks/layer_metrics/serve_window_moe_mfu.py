"""Whole serving step against the chip's peak, for the ``window-moe`` family:
the operations of every token the window's ``decode`` and ``prefill`` spans
processed (their ``rows``, ``context_tokens``, ``window_tokens``, ``tokens``,
``start``, ``window_pairs`` and ``moe_assignments``; ``counts_window_moe``:
this chip's work — the attention projections, attention at the real context
in the global layer and inside the band in the window layers, the dense
layer, the routers and the shared experts whole, every pick that landed on a
HELD expert, the vocabulary slice) over window seconds times the bf16 peak.
An intermediate chunk's counts come in its ``prefill_counts`` record, a final
chunk's in the one written where its first token is taken: each call is read
where its counts are. The expert layers count every row a call computed; the
share of them that were real (a final chunk's ``tokens`` of its ``bucket``, a
step's live ``rows`` of the slots) is what is counted here. ``None`` where the
spans carry no window fields (a program with no window layer)."""
from benchmarks.harness import counts_window_moe as C
from benchmarks.harness import peaks, span_math


def read(run):
    got = span_math.records_of(run)
    if run["rehearsal"] or got is None:
        return None
    records, t_open, t_close = got
    cfg = run["cell"].config
    slots = run["cell"].options["engine"]["num_slots"]
    flops, seen = 0.0, False
    for _, _, _, f in span_math.inside(records, "decode", t_open, t_close):
        if "moe_assignments" not in f or "window_tokens" not in f:
            continue
        seen = True
        flops += C.decode_step_flops(cfg, f["rows"], f["context_tokens"], f["window_tokens"],
                                     f["moe_assignments"] * f["rows"] / slots)
    for _, _, _, f in (span_math.inside(records, "prefill", t_open, t_close)
                       + span_math.inside(records, "prefill_counts", t_open, t_close)):
        if "moe_assignments" not in f or "window_pairs" not in f:
            continue
        seen = True
        width = f.get("bucket") or f.get("chunk")
        flops += C.prefill_flops(cfg, f["tokens"], f["start"], f["window_pairs"],
                                 f["moe_assignments"] * f["tokens"] / width,
                                 head="bucket" in f)
    if not seen:
        return None
    return (100.0 * flops / (t_close - t_open)
            / peaks.peaks_for(run["device_kind"])["bf16_flops"])
