"""Whole serving step against the chip's peak: the operations of every token
the engine processed in the window — prompt tokens through prefill, output
tokens through decode, attention at each token's real context, the head where
logits are computed (``counts.py``, summed by the window driver) — over
window seconds times the bf16 peak."""
from benchmarks.harness import peaks


def read(run):
    win = run["win"]
    if run["rehearsal"] or not win.get("model_flops"):
        return None
    return (100.0 * win["model_flops"] / win["window_s"]
            / peaks.peaks_for(run["device_kind"])["bf16_flops"])
