"""The training step's flash-attention kernels' share of their roofline: the
least time the chip could take for the attention of the step programs' runs
in the traced stretch — the larger of operations / 197 TFLOP/s and bytes /
819 GB/s — over the device time of the ``flash_attn_*`` kernels inside
``jit_step`` on the first device.

The count is the MODEL's, per layer and step, whatever implements it: the
forward's two products of S x S x d a head (``4 B H S^2 d`` operations) and
the backward's four (twice that), ``12 B H S^2 d`` in all — a kernel's
recomputation of the scores is NOT counted, so with 14 to 18 such products
executed the share cannot pass 86 %. Bytes: q, k, v in and o out once in the
forward; q, k, v, o, dO in and dq, dk, dv out once in the backward. B is one
chip's rows. The step programs' calls come from the trace's ``XLA Modules``
line, as ``train_step_device_ms`` takes them."""
from benchmarks.harness import counts, peaks, trace_reduce

KERNEL = r"^jit_step/.*flash_attn"


def attention_step(cfg: dict, rows: int, seq_len: int, itemsize: int = 2) -> dict:
    """One layer's attention in one train step on one chip, forward + backward."""
    h = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // h
    tensor = rows * seq_len * h * d
    return {"flops": 12 * rows * h * seq_len * seq_len * d,
            "bytes": (4 + 8) * tensor * itemsize}


def read(run):
    red = run.get("trace")
    if not red or run["rehearsal"] or "steps" not in run["win"]:
        return None
    secs, _ = trace_reduce.op_seconds(red, KERNEL)
    _, steps = trace_reduce.program_stats(red, r"^jit_step")
    if not secs or not steps:
        return None
    cfg, job = run["cell"].config, run["cell"].traffic
    c = attention_step(cfg, job["rows_per_chip"], job["seq_len"])
    least, _ = counts.roofline_seconds(c["flops"], c["bytes"],
                                       peaks.peaks_for(run["device_kind"]))
    return 100.0 * cfg["num_hidden_layers"] * steps * least / secs
