"""Whole serving step against the chip's peak, for the ``ssm-moe`` family:
the operations of every token the window's ``decode`` and ``prefill`` spans
processed (their ``rows``, ``context_tokens``, ``tokens``, ``start`` and
``moe_assignments``; ``counts_ssm_moe``: this chip's work — the Mamba-2
projections and the recurrence's own update, attention in the attention layer
only, the router, both latent projections and the shared expert whole, every
pick that landed on a HELD expert, the vocabulary slice) over window seconds
times the bf16 peak. An intermediate chunk's counts come in its
``prefill_counts`` record, a final chunk's in the one written where its first
token is taken: each call is read where its counts are. The expert layers
count every row a call computed; the share of them that were real (a final
chunk's ``tokens`` of its ``bucket``, a step's live ``rows`` of the slots) is
what is counted here."""
from benchmarks.harness import counts_ssm_moe as C
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
        if "moe_assignments" not in f or "state_rows" not in f:
            continue
        seen = True
        flops += C.decode_step_flops(cfg, f["rows"], f["context_tokens"],
                                     f["moe_assignments"] * f["rows"] / slots)
    for _, _, _, f in (span_math.inside(records, "prefill", t_open, t_close)
                       + span_math.inside(records, "prefill_counts", t_open, t_close)):
        if "moe_assignments" not in f or "state_from" not in f:
            continue
        seen = True
        width = f.get("bucket") or f.get("chunk")
        flops += C.prefill_flops(cfg, f["tokens"], f["start"],
                                 f["moe_assignments"] * f["tokens"] / width,
                                 head="bucket" in f)
    if not seen:
        return None
    return (100.0 * flops / (t_close - t_open)
            / peaks.peaks_for(run["device_kind"])["bf16_flops"])
