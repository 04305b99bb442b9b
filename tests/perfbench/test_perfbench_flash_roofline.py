"""``flash_attn_roofline`` on hand-made reductions: the share from known
kernel seconds and step calls against hand arithmetic at the published widths,
and ``None`` wherever there is nothing to read (the parent commit, whose step
has no such kernel; a run without a device trace; a rehearsal)."""
import pytest

from benchmarks.harness import manifest as M

MAN = M.load_manifest()
CELLS = ["bert-base.mlm-s512", "bert-base.mlm-s512-dp4"]


def _run(cell, device_ops, steps=35, rehearsal=False, training=True):
    red = None
    if device_ops is not None:
        red = {"device_ops": [[k, v] for k, v in device_ops.items()],
               "device_op_calls": {k: 12 * steps for k in device_ops},
               "programs": {"jit_step": {"calls": steps, "seconds": 0.0857 * steps},
                            "jit__norms": {"calls": 1, "seconds": 0.001}}}
    return {"cell": M.Cell(MAN, cell), "win": {"steps": 521} if training else {},
            "trace": red, "rehearsal": rehearsal, "device_kind": "TPU v5 lite",
            "sut": {}, "end_to_end": {}}


KERNELS = {"jit_step/flash_attn_fwd:bf16[16,512,768]": 0.120,
           "jit_step/flash_attn_bwd:bf16[16,512,768]": 0.300,
           "jit_step/fusion:f32[16,12,512]": 0.3,                  # not a kernel
           "jit__norms/flash_attn_fwd:bf16[16,512,768]": 9.0}      # not the step


@pytest.mark.parametrize("cell", CELLS)
def test_share_from_known_kernel_seconds(cell):
    """One chip's 16 rows in both cells: 12 x 16 x 12 x 512^2 x 64 operations
    a layer and step bind (0.196 ms against 0.184 ms for the bytes)."""
    read = M.load_reader("flash_attn_roofline")
    flops = 12 * 16 * 12 * 512 * 512 * 64
    bytes_ = 12 * 16 * 512 * 12 * 64 * 2
    assert flops == 38_654_705_664 and bytes_ == 150_994_944
    assert flops / 197e12 > bytes_ / 819e9
    got = read(_run(cell, KERNELS))
    assert got == pytest.approx(100 * 12 * 35 * (flops / 197e12) / 0.420)
    assert 19.5 < got < 19.7
    # the two-kernel backward of a streamed sequence is read by the same name rule
    split = {"jit_step/flash_attn_fwd:bf16[16,512,768]": 0.1,
             "jit_step/flash_attn_dq:bf16[16,512,768]": 0.15,
             "jit_step/flash_attn_dkv:bf16[16,512,768]": 0.17}
    assert read(_run(cell, split)) == pytest.approx(100 * 12 * 35 * (flops / 197e12) / 0.42)


def test_bytes_bind_where_the_sequence_is_short():
    mod_read = M.load_reader("flash_attn_roofline")
    count = mod_read.__globals__["attention_step"]
    cfg = M.Cell(MAN, CELLS[0]).config
    c = count(cfg, 16, 128)
    assert c == {"flops": 12 * 16 * 12 * 128 * 128 * 64, "bytes": 12 * 16 * 128 * 768 * 2}
    assert c["flops"] / 197e12 < c["bytes"] / 819e9


@pytest.mark.parametrize("run", [
    _run(CELLS[0], {"jit_step/fusion:f32[16,12,512]": 0.3}),    # the parent: no kernel ran
    _run(CELLS[0], None),                                        # no device trace
    _run(CELLS[0], KERNELS, rehearsal=True),
    _run(CELLS[0], KERNELS, steps=0),                            # no step in the stretch
    _run(CELLS[0], KERNELS, training=False),                     # not a training window
], ids=["no-kernel", "no-trace", "rehearsal", "no-steps", "not-training"])
def test_none_where_there_is_nothing_to_read(run):
    assert M.load_reader("flash_attn_roofline")(run) is None


def test_listed_for_the_two_training_cells_alone():
    entry = next(m for m in MAN["per_layer"] if m["name"] == "flash_attn_roofline")
    assert entry == {"name": "flash_attn_roofline", "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "train_tokens_per_s_per_chip", "workloads": CELLS}
    assert MAN["per_layer"][-1] is entry                          # appended, nothing moved
