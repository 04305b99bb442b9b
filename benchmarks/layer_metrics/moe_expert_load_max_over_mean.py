"""How uneven the routed load is where it costs: over the window's prefill
chunk calls, the rows of the fullest held expert (``moe_max_rows``) over the
mean rows a held expert got in that call (``moe_assignments`` / held experts
/ expert layers) — the straggler a grouped product waits for. 1 = even. A call's
counts are on its ``prefill`` span (a final chunk) or on the ``prefill_counts``
record the program writes at the step's next fence (an intermediate chunk)."""
from benchmarks.harness import span_math


def read(run):
    got = span_math.records_of(run)
    if got is None:
        return None
    records, t_open, t_close = got
    cfg = run["cell"].config
    cells = cfg["num_experts"] * (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])
    ratios = [f["moe_max_rows"] * cells / f["moe_assignments"]
              for name in ("prefill", "prefill_counts")
              for _, _, _, f in span_math.inside(records, name, t_open, t_close)
              if f.get("moe_assignments")]
    return sum(ratios) / len(ratios) if ratios else None
