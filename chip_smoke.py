#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the main path once through the entry points a user calls, on the TPU,
and checks what comes out. Pass/fail and set-up seconds only — not a
benchmark: no tokens/s, no MFU.

    python3 chip_smoke.py          # every phase; exit 0 only if all passed
    python3 chip_smoke.py serve    # probe + the named phase(s): debugging

Phases, each ONE child process, one after another (a chip has one owner at a
time; this parent never imports jax or the package):

  probe        what JAX sees: platform, device_kind, device count, coords
  kernels      every Pallas kernel, interpret=False, against its XLA
               reference on the chip (paged decode fp+int8, flash fwd+bwd,
               gmm/tgmm)
  train-bert   examples/train_zoo.py --model bert-base: train, checkpoint,
               then a FRESH process restores and continues
  train-llama  examples/train_llama.py --preset small at S=2048 (Pallas flash
               fwd+bwd inside the trainer) with a profiler window; a CPU
               child then opens the .xplane.pb
  serve        launch serve --preset small: chunked prefill, every final
               bucket, decode, a prefix-trie hit — all through the paged
               branch; a follow-up child checks ServeEngine's greedy tokens
               against generate()'s dense path
  four chips   (probe found >= 4 devices, else "skipped: N devices") bert-base
               dp=4, llama-small fsdp2 x tp2, serve --tp 4; every device must
               hold a real share of memory

Every chip child gets JAX_PLATFORMS=tpu, so a missing or busy chip is an
error, never a CPU run; each child's events must say platform == "tpu". The
compile cache is $JAX_COMPILATION_CACHE_DIR if set, else <here>/.jax_cache
(k8s_distributed_deeplearning_tpu.backend.use_compile_cache — the children
resolve it themselves). Checkpoints, traces and logs go under
chiprun_out/chip_smoke/, wiped at start: a trainer restores whatever it
finds, so a reused directory would "pass" on a finished run.

stdout on a pass of every phase, two lines, each one JSON object:
  {"event": "chip_smoke_report", "ok": true, "device": {...}, "date": ...,
   "cache": {...}, "phases": {name: {ok, wall_s, setup_s, ...}}}
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
The LAST line is the result, exactly those keys, the device as JAX reports
it. On any failure the report (ok false) goes to STDERR, stdout carries
nothing, and the exit code is 1. A run of named phases prints its report but
no result line: only a run of everything is the smoke passing.
"""
from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
PKG = "k8s_distributed_deeplearning_tpu"

# Sizes. BERT-base at B=8, S=512 compiles to 7.4 GiB of the chip's 16 (state
# 1.2 + temp 6.2, XLA's own memory analysis for a v5e); llama-small at B=8,
# S=2048 to 7.3 GiB.
BERT_ARGS = ["--model", "bert-base", "--seq-len", "512", "--batch-size", "8",
             "--log-every", "2", "--checkpoint-every", "6"]
BERT_STEPS, BERT_RESUME_STEPS = 12, 18
LLAMA_ARGS = ["--preset", "small", "--dtype", "bfloat16", "--seq-len", "2048",
              "--batch-size", "8", "--log-every", "2", "--no-eval"]
LLAMA_STEPS = 16                 # the profiler window is steps 10..15
SERVE_MAX_SEQ, SERVE_SLOTS, SERVE_CHUNK, SERVE_TRIE_MB = 1024, 8, 128, 64
SERVE_ARGS = ["--preset", "small", "--max-seq-len", str(SERVE_MAX_SEQ),
              "--slots", str(SERVE_SLOTS), "--requests", "16",
              "--prompt-len", "32", "512", "--out-len", "16", "64",
              "--shared-prefix-len", "64",
              "--prefix-cache-mb", str(SERVE_TRIE_MB),
              "--prefill-chunk-tokens", str(SERVE_CHUNK)]
PHASE_TIMEOUT_S = 900
# Kernel cases: (q heads, kv heads, head_dim) of what `small` serves and of
# config_llama3_8b; the trainer's sequence length; the MoE bench dims.
HEAD_SHAPES = ((12, 4, 64), (32, 8, 128))
PAGED_SQ_BATCH = ((1, SERVE_SLOTS), (5, SERVE_SLOTS), (SERVE_CHUNK, 2))
PAGED_TABLE = (32, 128)          # (page tokens, blocks a row): 4096 positions
# The latent kernel over the docs-backlog cell's table: (heads, latent rank,
# rope lanes, page tokens, blocks a row, rows); query widths 1 and 4.
LATENT_TABLE = (64, 512, 64, 64, 272, 8)
LATENT_SQ = (1, 4)
# Flash forward + backward: (batch, seq, head shape, causal) — the trainer's
# causal S = 2,048 at both head shapes, and the BERT cells' step
# (bert-base.mlm-s512: pairs of 64-lane heads, the whole sequence resident).
FLASH_CASES = tuple((2, 2048, hs, True) for hs in HEAD_SHAPES) + (
    (16, 512, (12, 12, 64), False),)
GMM_EXPERTS, GMM_ROWS, GMM_BLOCK_M = 8, 16384, 512
GMM_DIMS = ((768, 2048), (2048, 768))


# ------------------------------------------------------------------ parent

def _env(platform: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform
    # Cache every program, however quick to compile: with JAX's 1 s floor a
    # program near the floor is written on some runs and not others, and
    # "the second run adds no entries" stops being a property of the code.
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _cache_dir() -> str:
    # Same rule as backend.compile_cache_dir(), restated because this
    # process does not import the package.
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(HERE, ".jax_cache"))


def _cache_entries() -> int:
    try:
        return sum(1 for n in os.listdir(_cache_dir()) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def _run(name: str, argv: list[str], *, platform: str = "tpu",
         first_event: str | None = None,
         timeout: int = PHASE_TIMEOUT_S) -> dict:
    """Run one child to its end in its own process group (killed as a group
    afterwards, so nothing it started outlives it). stdout is teed to
    OUT/<name>.out and parsed as JSONL; stderr goes to OUT/<name>.err.
    ``setup_s`` is this parent's clock from launch to the first
    *first_event* line: process start, chip attach, init and compile."""
    t0 = time.monotonic()
    events: list[dict] = []
    setup_s = None
    err_path = os.path.join(OUT, name + ".err")
    with open(err_path, "w") as errf, \
            open(os.path.join(OUT, name + ".out"), "w") as outf:
        proc = subprocess.Popen(argv, cwd=HERE, env=_env(platform),
                                stdout=subprocess.PIPE, stderr=errf,
                                text=True, start_new_session=True)
        timer = threading.Timer(timeout, _kill_group, [proc])
        timer.start()
        try:
            for line in proc.stdout:
                outf.write(line)
                if not line.startswith("{"):
                    continue
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                events.append(ev)
                if setup_s is None and ev.get("event") == first_event:
                    setup_s = round(time.monotonic() - t0, 1)
            rc = proc.wait()
        finally:
            timer.cancel()
            _kill_group(proc)
    with open(err_path, errors="replace") as f:
        stderr = f.read()
    return {"rc": rc, "events": events, "stderr": stderr,
            "wall_s": round(time.monotonic() - t0, 1), "setup_s": setup_s}


def result_line(device: dict) -> str:
    """The last line of stdout of a passing run: exactly ``ok`` and
    ``device``, the device exactly ``platform``, ``kind`` and ``count``."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def _named(events: list[dict], name: str) -> list[dict]:
    return [e for e in events if e.get("event") == name]


class Phase:
    """One phase's verdict: the first failed ``need`` is the reason."""

    def __init__(self, run: dict):
        self.run = run
        self.why: str | None = None
        self.info: dict = {}
        self.need(run["rc"] == 0,
                  f"exit code {run['rc']}: {run['stderr'][-1500:]}")

    def need(self, cond, why: str) -> bool:
        if not cond and self.why is None:
            self.why = why
        return bool(cond)

    def on_tpu(self, ev: dict | None, what: str) -> None:
        if self.need(ev is not None, f"no {what} event"):
            self.info.update(platform=ev.get("platform"),
                             device_kind=ev.get("device_kind"),
                             device_count=ev.get("device_count"))
            self.need(ev.get("platform") == "tpu",
                      f"{what} ran on platform {ev.get('platform')!r}")

    def result(self) -> dict:
        out = {"ok": self.why is None, "wall_s": self.run["wall_s"],
               "setup_s": self.run["setup_s"], **self.info}
        if self.why is not None:
            out["why"] = self.why
        return out


def _shares(ph: Phase, bytes_in_use, n_used: int) -> None:
    """Each of the *n_used* devices the phase lays its state over holds a
    real share: none empty, none under half of the fullest (dp replicates,
    fsdp/tp split evenly — no layout here should leave a chip idle)."""
    ph.info["device_bytes_in_use"] = bytes_in_use
    ok = (isinstance(bytes_in_use, list) and len(bytes_in_use) >= n_used
          and all(isinstance(b, int) and b > 0
                  for b in bytes_in_use[:n_used]))
    if ph.need(ok, f"per-device bytes_in_use missing or empty on the "
                   f"first {n_used} device(s): {bytes_in_use}"):
        used = bytes_in_use[:n_used]
        ph.need(min(used) * 2 >= max(used),
                f"uneven device memory: {bytes_in_use}")


def _train_checks(ph: Phase, n_devices: int, *,
                  restored_at: int | None = None) -> None:
    ev = ph.run["events"]
    ph.on_tpu(next(iter(_named(ev, "start")), None), "start")
    steps = _named(ev, "train_step")
    losses = [e["loss"] for e in steps]
    ph.info["loss_first_last"] = losses[:1] + losses[-1:]
    if ph.need(len(losses) >= 2, f"{len(losses)} train_step events"):
        ph.need(all(isinstance(x, float) and x == x and abs(x) < 1e9
                    for x in losses), f"non-finite loss in {losses}")
    ph.need(_named(ev, "checkpoint"), "no checkpoint event")
    if restored_at is None:
        ph.need(losses and losses[-1] < losses[0],
                f"loss did not fall: {losses[:1]} -> {losses[-1:]}")
    else:       # three log points after a resume say nothing about a trend
        got = [e.get("step") for e in _named(ev, "restore")]
        ph.need(got == [restored_at],
                f"restore events {got}, wanted one at step {restored_at}")
        ph.need(steps and steps[0]["step"] > restored_at,
                f"first step after the restore: {steps[:1]}")
    mem = _named(ev, "device_memory")
    if ph.need(mem, "no device_memory event"):
        _shares(ph, mem[-1].get("bytes_in_use"), n_devices)
    ph.info["gspmd_full_remat_warning"] = (
        "rematerialization" in ph.run["stderr"])


def phase_train_bert(name: str, n_devices: int) -> list[tuple[str, dict]]:
    ckpt = os.path.join(OUT, f"ckpt-{name}")
    cmd = [sys.executable, "examples/train_zoo.py", *BERT_ARGS,
           "--checkpoint-dir", ckpt]
    first = Phase(_run(name, cmd + ["--num-steps", str(BERT_STEPS)],
                       first_event="train_step"))
    _train_checks(first, n_devices)
    first.need(len(_named(first.run["events"], "checkpoint")) >= 2,
               "wanted a mid-run and a final checkpoint")
    out = [(name, first.result())]
    if first.why is None:
        # The production resume path: a FRESH process finds the directory.
        again = Phase(_run(name + "-resume",
                           cmd + ["--num-steps", str(BERT_RESUME_STEPS)],
                           first_event="train_step"))
        _train_checks(again, n_devices, restored_at=BERT_STEPS)
        out.append((name + "-resume", again.result()))
    shutil.rmtree(ckpt, ignore_errors=True)     # 1.3 GB a step: not kept
    return out


def phase_train_llama(name: str, n_devices: int,
                      extra: list[str]) -> list[tuple[str, dict]]:
    ckpt = os.path.join(OUT, f"ckpt-{name}")
    prof = os.path.join(OUT, f"profile-{name}")
    cmd = [sys.executable, "examples/train_llama.py", *LLAMA_ARGS, *extra,
           "--num-steps", str(LLAMA_STEPS), "--checkpoint-dir", ckpt,
           "--profile-dir", prof]
    ph = Phase(_run(name, cmd, first_event="train_step"))
    _train_checks(ph, n_devices)
    out = [(name, ph.result())]
    if ph.why is None:
        # Needs jax but no chip: read the trace on the CPU backend.
        rd = Phase(_run(name + "-profile", [sys.executable, __file__,
                                            "--child", "profile", prof],
                        platform="cpu"))
        got = next(iter(_named(rd.run["events"], "profile")), {})
        rd.info.update(device_planes=got.get("device_planes"),
                       device_events=got.get("device_events"))
        rd.need(got.get("device_events", 0) > 0,
                f"no device plane with events in the trace: {got}")
        out.append((name + "-profile", rd.result()))
    shutil.rmtree(ckpt, ignore_errors=True)
    shutil.rmtree(prof, ignore_errors=True)     # tens of MB: read, not kept
    return out


def phase_serve(name: str, n_devices: int,
                extra: list[str]) -> list[tuple[str, dict]]:
    cmd = [sys.executable, "-m", f"{PKG}.launch", "serve", *SERVE_ARGS,
           *extra]
    ph = Phase(_run(name, cmd, first_event="serve_request"))
    ev = ph.run["events"]
    summ = next(iter(_named(ev, "serve_summary")), None)
    ph.on_tpu(summ, "serve_summary")
    reqs = _named(ev, "serve_request")
    ph.need(len(reqs) == 16, f"{len(reqs)} of 16 requests completed")
    reasons = sorted({r.get("finish_reason") for r in reqs})
    ph.need(reasons == ["length"], f"finish reasons {reasons}")
    ph.need(any(r.get("cached_prompt_tokens", 0) > 0 for r in reqs),
            "no request was served from the prefix trie")
    if summ is not None:
        # What the trie retains is a cache, not a leak (the engine's own
        # drain check says the same): every page still held is the trie's.
        owners = summ.get("kv_pages_by_owner", {})
        held = {k: v for k, v in owners.items() if k != "trie" and v}
        ph.need(not held and summ.get("kv_pages_used") == owners.get("trie"),
                f"pages still held at the end: used "
                f"{summ.get('kv_pages_used')}, by owner {owners}")
        impls = summ.get("attention_impls", {})
        ph.info["attention_impls"] = impls
        ph.need(impls and {v.split()[0] for v in impls.values()}
                == {"paged_flash"},
                f"serving programs did not all resolve to the Pallas "
                f"kernel: {impls}")
        _shares(ph, summ.get("device_bytes_in_use"), n_devices)
    out = [(name, ph.result())]
    if ph.why is None:
        par = Phase(_run(name + "-parity",
                         [sys.executable, __file__, "--child", "serve-parity",
                          *extra]))
        got = next(iter(_named(par.run["events"], "serve_parity")), None)
        par.on_tpu(got, "serve_parity")
        if got is not None:
            par.run["setup_s"] = got.get("setup_s")
            par.info.update({k: got.get(k) for k in (
                "tokens_compared", "tokens_equal", "near_tie_divergences",
                "latent_moe_tokens_equal")})
            par.need(got.get("ok"), f"greedy tokens disagree: {got}")
        out.append((name + "-parity", par.result()))
    return out


PHASES = ("kernels", "train-bert", "train-llama", "serve", "four-chips")


def main(only: list[str]) -> int:
    """Run every phase, or just the ones named in *only* (the probe always
    runs). Chip time is budgeted; re-running one phase is how a failure gets
    debugged. Only a run of everything is the smoke passing."""
    unknown = sorted(set(only) - set(PHASES))
    if unknown:
        raise SystemExit(f"unknown phase(s) {unknown}; phases: {PHASES}")
    want = set(only) or set(PHASES)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    entries_before = _cache_entries()
    phases: dict[str, dict] = {}
    device = None

    def finish() -> int:
        ok = all(p["ok"] or "skipped" in p for p in phases.values())
        ok = ok and device is not None and device["platform"] == "tpu"
        report = {"event": "chip_smoke_report", "ok": ok, "device": device,
                  "date": time.strftime("%Y-%m-%d"),
                  **({"only": sorted(only)} if only else {}),
                  "cache": {"dir": _cache_dir(),
                            "entries_before": entries_before,
                            "entries_after": _cache_entries()},
                  "phases": phases}
        with open(os.path.join(OUT, "report.json"), "w") as f:
            json.dump(report, f, indent=1)
        # A failed run prints nothing on stdout; a partial one no result.
        print(json.dumps(report), file=sys.stdout if ok else sys.stderr,
              flush=True)
        if ok and not only:
            print(result_line(device), flush=True)
        return 0 if ok else 1

    def record(results) -> None:
        for name, res in results:
            phases[name] = res
            print(f"chip_smoke: {name}: "
                  f"{'ok' if res['ok'] else 'FAILED — ' + res['why']} "
                  f"({res['wall_s']} s, set-up {res['setup_s']} s)",
                  file=sys.stderr, flush=True)

    probe = Phase(_run("probe", [sys.executable, __file__, "--child",
                                 "probe"], timeout=300))
    got = next(iter(_named(probe.run["events"], "probe")), None)
    probe.on_tpu(got, "probe")
    if got is not None:
        probe.info.update(devices=got.get("devices"),
                          native_available=got.get("native_available"),
                          cache_dir=got.get("cache_dir"))
        device = {"platform": got.get("platform"),
                  "kind": got.get("device_kind"),
                  "count": got.get("device_count")}
    record([("probe", probe.result())])
    if probe.why is not None:
        return finish()             # no chip: nothing below can mean anything
    n = device["count"]
    four = "four-chips" in want and n >= 4

    if "kernels" in want:
        kern = Phase(_run("kernels", [sys.executable, __file__, "--child",
                                      "kernels"]))
        cases = _named(kern.run["events"], "kernel_case")
        done = next(iter(_named(kern.run["events"], "kernels_done")), None)
        kern.on_tpu(done, "kernels_done")
        if done is not None:
            kern.run["setup_s"] = done.get("setup_s")
        bad = [c for c in cases
               if not (c.get("ok") and c.get("interpret") is False)]
        kern.info.update(cases=len(cases), max_err_over_tol=round(max(
            (c["max_err"] / c["tol"] for c in cases if c.get("max_err")),
            default=0.0), 3))
        kern.need(done is not None and len(cases) == done.get("cases"),
                  "kernels child did not finish its case list")
        kern.need(not bad, f"kernel cases failed: {bad}")
        record([("kernels", kern.result())])
    # The trainers fill every visible device (--dp -1): on a four-chip host
    # these two ARE the dp=4 runs.
    if "train-bert" in want or four:
        record(phase_train_bert("train-bert", n))
    if "train-llama" in want:
        record(phase_train_llama("train-llama", n, []))
    if "serve" in want:
        record(phase_serve("serve", 1, []))
    if "four-chips" in want:
        if n < 4:
            phases["four-chips"] = {"ok": False, "skipped": f"{n} devices"}
            print(f"chip_smoke: four-chips: skipped: {n} devices",
                  file=sys.stderr, flush=True)
        else:                       # one process drives all four chips
            record(phase_train_llama("train-llama-fsdp2-tp2", n,
                                     ["--fsdp", "2", "--tp", "2"]))
            record(phase_serve("serve-tp4", 4, ["--tp", "4"]))
    return finish()


# ---------------------------------------------------------------- children
# Everything below runs in a child process; jax and the package are imported
# here and nowhere above.

def _emit(event: str, **fields) -> None:
    print(json.dumps({"event": event, **fields}), flush=True)


def _device_fields() -> dict:
    from k8s_distributed_deeplearning_tpu.parallel import mesh as mesh_lib
    return mesh_lib.topology().device_fields()


def child_probe() -> int:
    import jax

    from k8s_distributed_deeplearning_tpu import backend
    from k8s_distributed_deeplearning_tpu.runtime import fusion
    cache_dir = backend.use_compile_cache()
    _emit("probe", **_device_fields(), cache_dir=cache_dir,
          native_available=fusion.native_available(),
          devices=[{"id": d.id, "process_index": d.process_index,
                    "coords": list(getattr(d, "coords", ())),
                    "core_on_chip": getattr(d, "core_on_chip", None)}
                   for d in jax.devices()])
    return 0


def child_profile(log_dir: str) -> int:
    """Open the trace with nothing but JAX (xprof is not installed) and count
    the events on device planes."""
    import glob

    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    planes, n_events = [], 0
    for path in paths:
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if not plane.name.startswith("/device:"):
                continue
            count = sum(1 for line in plane.lines for _ in line.events)
            planes.append({"name": plane.name, "events": count})
            n_events += count
    _emit("profile", files=len(paths), device_planes=planes,
          device_events=n_events)
    return 0


def child_kernels() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_distributed_deeplearning_tpu import backend
    from k8s_distributed_deeplearning_tpu.ops import (attention, pallas_flash,
                                                      pallas_gmm,
                                                      pallas_latent_attn,
                                                      pallas_paged_attn)
    backend.use_compile_cache()
    if not backend.on_tpu():
        raise RuntimeError(f"kernels phase needs a TPU, got {jax.devices()}")
    f32, bf16 = jnp.float32, jnp.bfloat16
    tol = 4 * float(jnp.finfo(bf16).eps)
    setup_s = 0.0
    n_cases = 0

    @jax.jit
    def rel_err(g, w):          # reduced on the device: only scalars come back
        g, w = g.astype(f32), w.astype(f32)
        err = jnp.max(jnp.abs(g - w)) / jnp.maximum(1.0, jnp.max(jnp.abs(w)))
        return jnp.where(jnp.all(jnp.isfinite(g)), err, jnp.inf)

    def check(case: str, got, want) -> None:
        """Outputs are bf16 and the reference is f32 at highest matmul
        precision, so the bound is a few bf16 ulps of the largest value."""
        nonlocal n_cases
        worst = max(float(rel_err(g, w)) for g, w in zip(
            jax.tree.leaves(got), jax.tree.leaves(want)))
        n_cases += 1
        _emit("kernel_case", case=case, interpret=False,
              max_err=round(worst, 6) if worst < float("inf") else None,
              tol=tol, ok=worst <= tol)

    def timed(fn, *args):
        nonlocal setup_s
        t0 = time.monotonic()
        out = jax.block_until_ready(fn(*args))
        setup_s += time.monotonic() - t0
        return out

    def reference(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    # ---- paged decode attention over the benchmark cell's table: 128
    # blocks of 32 tokens, so a row spans several of the kernel's grid cells
    # (ragged cursors; one row idle, as a free slot is: cursor sq-1, every
    # table entry the scratch page)
    page_tokens, n_blocks = PAGED_TABLE

    def paged_ref(q, pk, pv, tables, pos, ks, vs):
        b, sq, h, hd = q.shape
        hkv = pk.shape[2] // hd
        s_virt = n_blocks * page_tokens
        k = pk[tables].reshape(b, s_virt, hkv, hd).astype(f32)
        v = pv[tables].reshape(b, s_virt, hkv, hd).astype(f32)
        if ks is not None:
            k = k * ks[tables].reshape(b, s_virt, hkv)[..., None]
            v = v * vs[tables].reshape(b, s_virt, hkv)[..., None]
        mask = (jnp.arange(s_virt)[None, None, :] <= pos[:, :, None])[:, None]
        return attention.dot_product_attention(q.astype(f32), k, v, mask=mask)

    for h, hkv, hd in HEAD_SHAPES:
        for sq, b in PAGED_SQ_BATCH:
            rng = np.random.default_rng(h * 1000 + sq)
            pages = b * n_blocks + 1
            q = jnp.asarray(rng.standard_normal((b, sq, h, hd)), bf16)
            kf = rng.standard_normal((pages, page_tokens, hkv, hd))
            vf = rng.standard_normal((pages, page_tokens, hkv, hd))
            cursor = rng.integers(sq - 1, n_blocks * page_tokens, size=b)
            cursor[0] = sq - 1
            pos = (cursor[:, None] - (sq - 1)
                   + np.arange(sq)[None, :]).astype(np.int32)
            tables = rng.permutation(np.arange(1, pages)).reshape(
                b, n_blocks).astype(np.int32)
            # Blocks past a row's cursor are unallocated in the engine:
            # table entry 0, the never-attended scratch page.
            tables[np.arange(n_blocks)[None, :]
                   > (cursor // page_tokens)[:, None]] = 0
            tables[0] = 0
            tables, pos = jnp.asarray(tables), jnp.asarray(pos)
            fold = lambda x: x.reshape(pages, page_tokens, hkv * hd)
            for quant in (False, True):
                if quant:           # per-token-per-head absmax, as written
                    ks = np.abs(kf).max(-1) / 127.0
                    vs = np.abs(vf).max(-1) / 127.0
                    pk = jnp.asarray(fold(np.round(kf / ks[..., None])),
                                     jnp.int8)
                    pv = jnp.asarray(fold(np.round(vf / vs[..., None])),
                                     jnp.int8)
                    ks, vs = jnp.asarray(ks, f32), jnp.asarray(vs, f32)
                else:
                    pk, pv = jnp.asarray(fold(kf), bf16), jnp.asarray(
                        fold(vf), bf16)
                    ks = vs = None
                kernel = jax.jit(lambda q, pk, pv, t, p, ks, vs:
                                 pallas_paged_attn.paged_decode_attention(
                                     q, pk, pv, t, p, k_scale=ks, v_scale=vs,
                                     interpret=False))
                args = (q, pk, pv, tables, pos, ks, vs)
                check(f"paged {'int8' if quant else 'bf16'} {h}q/{hkv}kv "
                      f"hd{hd} sq{sq}", timed(kernel, *args),
                      reference(paged_ref)(*args))

    # ---- absorbed latent decode attention over the docs-backlog cell's
    # table: 272 blocks of 64 tokens, one 640-lane row a token (ragged
    # cursors; row 0 idle)
    h, rank, rope, page_tokens_l, n_blocks_l, b = LATENT_TABLE
    lanes = -(-(rank + rope) // 128) * 128

    def latent_ref(q, pool, tables, pos):
        s_virt = n_blocks_l * page_tokens_l
        lat = pool[tables].reshape(b, s_virt, lanes).astype(f32)
        sc = jnp.einsum("bqhl,bkl->bhqk", q.astype(f32), lat) * 0.135
        allow = jnp.arange(s_virt)[None, None, :] <= pos[:, :, None]
        p = jax.nn.softmax(jnp.where(allow[:, None], sc, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkr->bqhr", p, lat[..., :rank])

    for sq in LATENT_SQ:
        rng = np.random.default_rng(27 + sq)
        pages = b * n_blocks_l + 1
        pad = lambda x: np.concatenate(
            [x, np.zeros(x.shape[:-1] + (lanes - rank - rope,))], axis=-1)
        q = jnp.asarray(pad(rng.standard_normal((b, sq, h, rank + rope))), bf16)
        pool = jnp.asarray(pad(rng.standard_normal(
            (pages, page_tokens_l, rank + rope))), bf16)
        cursor = rng.integers(sq - 1, n_blocks_l * page_tokens_l, size=b)
        cursor[0] = sq - 1
        pos = (cursor[:, None] - (sq - 1)
               + np.arange(sq)[None, :]).astype(np.int32)
        tables = rng.permutation(np.arange(1, pages)).reshape(
            b, n_blocks_l).astype(np.int32)
        tables[np.arange(n_blocks_l)[None, :]
               > (cursor // page_tokens_l)[:, None]] = 0
        tables[0] = 0
        kernel = jax.jit(lambda q, pool, t, p:
                         pallas_latent_attn.latent_decode_attention(
                             q, pool, t, p, rank=rank, softmax_scale=0.135,
                             interpret=False))
        args = (q, pool, jnp.asarray(tables), jnp.asarray(pos))
        check(f"latent bf16 {h}q rank{rank}+{rope} sq{sq}",
              timed(kernel, *args), reference(latent_ref)(*args))

    # ---- flash attention fwd + bwd at the trainer's sequence length
    # (Arrays go in as arguments: a closed-over array is baked into the
    # program as a constant, and into its cache entry.)
    for b, s, (h, hkv, hd), causal in FLASH_CASES:
        rng = np.random.default_rng(h)
        q = jnp.asarray(rng.standard_normal((b, s, h, hd)), bf16)
        k = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), bf16)
        v = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), bf16)
        w = jnp.asarray(rng.standard_normal((b, s, h, hd)), f32)

        def flash_loss(q, k, v, w):
            o = pallas_flash.flash_attention(q, k, v, causal=causal,
                                             interpret=False)
            return jnp.sum(o.astype(f32) * w), o

        def ref_loss(q, k, v, w):
            o = attention.dot_product_attention(
                q.astype(f32), k.astype(f32), v.astype(f32), causal=causal)
            return jnp.sum(o * w), o

        grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))
        got_g, got_o = timed(grad(flash_loss), q, k, v, w)
        want_g, want_o = reference(grad(ref_loss))(q, k, v, w)
        check(f"flash fwd {h}q/{hkv}kv hd{hd} S{s}", got_o, want_o)
        check(f"flash bwd {h}q/{hkv}kv hd{hd} S{s}", got_g, want_g)

    # ---- grouped matmul fwd + bwd (gmm on the transposed weight, tgmm) at
    # the MoE bench dims: 8 experts, 16384 rows, 768 <-> 2048
    e, m, bm = GMM_EXPERTS, GMM_ROWS, GMM_BLOCK_M

    def rows(sizes):
        """(layout, owning expert per row, row holds a real token)."""
        layout = pallas_gmm.grouped_layout(sizes, m, bm)
        row_e = jnp.repeat(layout.block_expert, bm)
        live = (jnp.arange(layout.m_pad) - layout.row_offset[row_e]
                < sizes[row_e])[:, None]
        return layout, row_e, live

    def gmm_loss(lhs, rhs, w, sizes):
        o = pallas_gmm.gmm(lhs, rhs, rows(sizes)[0], interpret=False)
        return jnp.sum(o.astype(f32) * w), o

    def gmm_ref_loss(lhs, rhs, w, sizes):
        row_e = rows(sizes)[1]
        o = sum(jnp.where(row_e[:, None] == i,
                          lhs.astype(f32) @ rhs[i].astype(f32), 0)
                for i in range(e))
        return jnp.sum(o * w), o

    for kdim, ndim in GMM_DIMS:
        rng = np.random.default_rng(kdim)
        probs = np.array([0.0] + [1.0] * (e - 1)) / (e - 1)   # one empty
        sizes = jnp.asarray(rng.multinomial(m, probs), jnp.int32)
        layout, _, live = rows(sizes)
        # Rows that hold no token are zero by construction in the MoE layer.
        lhs = jnp.where(live, jnp.asarray(
            rng.standard_normal((layout.m_pad, kdim)), bf16), 0)
        rhs = jnp.asarray(rng.standard_normal((e, kdim, ndim))
                          / np.sqrt(kdim), bf16)
        w = jnp.where(live, jnp.asarray(
            rng.standard_normal((layout.m_pad, ndim)), f32), 0)
        grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1), has_aux=True))
        (got_dl, got_dr), got_o = timed(grad(gmm_loss), lhs, rhs, w, sizes)
        (want_dl, want_dr), want_o = reference(grad(gmm_ref_loss))(
            lhs, rhs, w, sizes)
        check(f"gmm fwd {kdim}->{ndim}", got_o, want_o)
        # Padding rows inside a live block carry whatever g @ rhs^T gives;
        # only live rows are gathered back, so only they are compared.
        check(f"gmm bwd dlhs {kdim}->{ndim}",
              jnp.where(live, got_dl, 0), jnp.where(live, want_dl, 0))
        check(f"tgmm bwd drhs {kdim}->{ndim}", got_dr, want_dr)

    _emit("kernels_done", **_device_fields(), cases=n_cases,
          setup_s=round(setup_s, 1))
    return 0


def child_serve_parity(argv: list[str]) -> int:
    """ServeEngine (paged branch, chunked prefill, the Pallas kernel) against
    generate()'s dense path, greedy, same preset and seed as the CLI.

    The preset's weights are random and its dtype is bf16, so the logits are
    nearly flat and two numerically different routes to the same attention
    can pick different tokens at a near-tie. Tokens must agree up to the
    first position where they do not; there the dense path's own logits
    must show the two candidates within a few bf16 ulps of each other, or
    the phase fails. With ``--tp N`` the tp=N engine is held to the same
    standard against both the tp=0 engine and the dense path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_distributed_deeplearning_tpu import backend
    from k8s_distributed_deeplearning_tpu.models import generate, llama, moe
    from k8s_distributed_deeplearning_tpu.serve import Request, ServeEngine
    from k8s_distributed_deeplearning_tpu.serve.cli import preset_config
    backend.use_compile_cache()
    tp = int(argv[argv.index("--tp") + 1]) if "--tp" in argv else 0
    t0 = time.monotonic()
    cfg = preset_config("small", SERVE_MAX_SEQ)
    model = llama.LlamaLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    n_prompts, prompt_len, new = 4, 300, 24
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(n_prompts, prompt_len)).astype(np.int32)

    def engine_tokens(tp: int, model=model, params=params,
                      prompts=prompts) -> np.ndarray:
        eng = ServeEngine(model, params, num_slots=SERVE_SLOTS,
                          prefill_chunk_tokens=SERVE_CHUNK,
                          prefix_cache_mb=SERVE_TRIE_MB, tp=tp)
        reqs = [Request(prompt=p, max_new_tokens=new, seed=i)
                for i, p in enumerate(prompts)]
        outs = {o.request_id: o.tokens for o in eng.run(reqs)}
        return np.asarray([outs[r.request_id] for r in reqs], np.int32)

    dense = np.asarray(generate.generate(model, params, jnp.asarray(prompts),
                                         max_new_tokens=new))
    routes = {"tp0": engine_tokens(0)}
    if tp:
        routes[f"tp{tp}"] = engine_tokens(tp)
    # The tiny latent-MoE preset (latent pool, its kernel, expert layers with
    # a share of the experts' routing) through the same engine, against its
    # own generate(), to the same near-tie standard (float32 parameters, but
    # the chip's default matmul precision is bf16's).
    lm_cfg, lm_latent, lm_moe = moe.config_tiny_latent_moe(
        max_seq_len=SERVE_MAX_SEQ)
    lm = moe.LatentMoELM(lm_cfg, lm_latent, lm_moe)
    lm_params = lm.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    lm_prompts = prompts % lm_cfg.vocab_size
    lm_dense = np.asarray(generate.generate(
        lm, lm_params, jnp.asarray(lm_prompts), max_new_tokens=new))
    lm_served = engine_tokens(0, lm, lm_params, lm_prompts)
    setup_s = round(time.monotonic() - t0, 1)

    tol = 4 * float(jnp.finfo(cfg.dtype).eps)
    compared = equal = 0
    near_ties, wrong = [], []

    def compare(name: str, got: np.ndarray, want: np.ndarray, model=model,
                params=params, prompts=prompts) -> None:
        nonlocal compared, equal
        for i in range(n_prompts):
            diff = np.nonzero(got[i] != want[i])[0]
            upto = int(diff[0]) if diff.size else new
            compared += new
            equal += upto
            if not diff.size:
                continue
            # The dense path's logits for the shared prefix decide whether
            # this is a near-tie or a wrong answer.
            prefix = np.concatenate([prompts[i], want[i][:upto]])[None]
            logits, _ = generate.prefill(model, params, jnp.asarray(prefix))
            last = np.asarray(logits[0, -1], np.float32)
            a, b = int(got[i][upto]), int(want[i][upto])
            gap = abs(float(last[a] - last[b]))
            bound = tol * max(1.0, float(np.max(np.abs(last))))
            rec = {"routes": name, "prompt": i, "position": upto,
                   "tokens": [a, b], "logit_gap": round(gap, 5),
                   "bound": round(bound, 5)}
            (near_ties if gap <= bound else wrong).append(rec)

    for name, toks in routes.items():
        compare(f"{name} vs dense", toks, dense)
    if tp:
        compare(f"tp{tp} vs tp0", routes[f"tp{tp}"], routes["tp0"])
    before = equal
    compare("latent-moe vs dense", lm_served, lm_dense, lm, lm_params,
            lm_prompts)
    _emit("serve_parity", **_device_fields(), tp=tp, setup_s=setup_s,
          tokens_compared=compared, tokens_equal=equal,
          near_tie_divergences=near_ties, wrong=wrong,
          latent_moe_tokens_equal=[equal - before, lm_dense.size],
          ok=not wrong)
    return 0


def child(argv: list[str]) -> int:
    sys.path.insert(0, HERE)
    name, rest = argv[0], argv[1:]
    if name == "probe":
        return child_probe()
    if name == "kernels":
        return child_kernels()
    if name == "profile":
        return child_profile(rest[0])
    if name == "serve-parity":
        return child_serve_parity(rest)
    raise SystemExit(f"unknown child {name!r}")


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        sys.exit(child(sys.argv[2:]))
    sys.exit(main(sys.argv[1:]))
