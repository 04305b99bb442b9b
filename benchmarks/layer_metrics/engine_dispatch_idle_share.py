"""The chip's idle while the host was getting a program into its queue, as a
share of the traced stretch: the idle gaps of the run's trace reduction that
are labelled with a dispatch-side span of the engine — ``program:prefill``
and what lies inside it (``chunk_operands``, ``first_key``, ``chunk_call``,
``trie_adopt``), ``program:decode`` and its ``decode_call``,
``program:admission`` — over ``window_s``. A gap carries the name of the
SHORTEST host span that covers half of it (``trace_reduce.reduce``), so the
outer names hold only what no inner span covers.

``None`` without a trace, in a rehearsal (a CPU has no idle share), and for a
program that opens none of the inner spans (a parent commit, a training
cell): there the outer labels alone would read as this metric."""
from benchmarks.harness import span_math

LABELS = ("prefill", "chunk_operands", "first_key", "chunk_call", "trie_adopt",
          "decode", "decode_call", "admission")
WITNESS = ("decode_call", "chunk_call")


def idle_under(run, labels, witness):
    """Seconds of the traced stretch's idle gaps labelled ``program:<one of
    labels>``; None where there is no reduction to read or the window's
    records hold no span named in *witness*."""
    red, got = run.get("trace"), span_math.records_of(run)
    if not red or run.get("rehearsal") or got is None:
        return None
    if not any(r[0] in witness for r in got[0]):
        return None
    want = {"program:" + name for name in labels}
    return sum(secs for label, secs in red["idle_gaps"] if label in want)


def read(run):
    idle = idle_under(run, LABELS, WITNESS)
    if idle is None or not run["trace"].get("window_s"):
        return None
    return 100.0 * idle / run["trace"]["window_s"]
