"""The training window: drives ``train.loop.fit`` for ``--seconds``.

One object — the compiled step with its state — is built in set-up, driven
from the seed through its first three steps by ``fit`` itself (the window's
own call and feed), and handed on to the window. What those three steps read
(each loss, the first gradient as the optimizer got it, the parameters'
change) is compared with the plain reference once the window has closed, the
memory peak has been read and the program's state is freed.

Inside the window nothing writes to disk or stdout: metrics go to memory, no
heartbeat, exporter, checkpointer or eval. ``fit`` syncs with the device at
its log cadence; the window closes through ``fit``'s own preemption flag on
the first sync after which the next one would fall beyond ``--seconds``, and
its length is the real interval from open to the completion of the last step.
"""
from __future__ import annotations

import gc
import os
import statistics
import time

import numpy as np

from benchmarks.harness import spans as spans_lib
from benchmarks.harness import trace_reduce
from benchmarks.harness import weights

CHECK_STEPS = 3


class _MemoryMetrics:
    """What ``fit`` asks of a ``MetricsLogger``, kept in memory. ``on_sync``
    is called at each log-cadence sync with (step, now)."""

    enabled = True

    def __init__(self, on_sync=None):
        self.syncs: list[tuple[int, float, float]] = []   # step, t, loss
        self.events: list[tuple[str, dict]] = []
        self.on_sync = on_sync

    def emit(self, event, **fields):
        self.events.append((event, fields))

    def train_step(self, step, loss, *a, **kw):
        now = time.perf_counter()
        self.syncs.append((step, now, loss))
        if self.on_sync is not None:
            self.on_sync(step, now)

    def close(self):
        pass


def _find_mu(opt_state):
    import jax
    found = []

    def visit(x):
        if hasattr(x, "mu") and hasattr(x, "nu"):
            found.append(x.mu)
        elif isinstance(x, (tuple, list)):
            for y in x:
                visit(y)
    visit(opt_state)
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer, found {len(found)}")
    return found[0]


def setup(cell, seed: int, split: dict, *, rehearsal: bool = False) -> dict:
    """Build the system under test and drive its first steps. *split*
    collects the set-up phases' seconds."""
    import jax
    import jax.numpy as jnp
    from k8s_distributed_deeplearning_tpu.parallel import mesh as mesh_lib
    from k8s_distributed_deeplearning_tpu.train import data as data_lib
    from k8s_distributed_deeplearning_tpu.train import loop, prefetch

    cfg, job = cell.config, cell.traffic
    fam = cell.family()
    t0 = time.perf_counter()
    devices = jax.devices()[:cell.chips]
    mesh = mesh_lib.make_mesh({"data": cell.chips}, devices=devices)
    trainer, init_fn, _ = fam.build_trainer(cfg, job, mesh)
    state = trainer.init(init_fn, weights.seed_operand(seed))
    step_fn = trainer.make_step(donate=True)
    jax.block_until_ready(state)
    split["weight_init"] = time.perf_counter() - t0

    # The feed: the repo's batcher over a corpus drawn from the seed (every
    # row differs), placed by the trainer, prefetched by train/prefetch.py.
    t0 = time.perf_counter()
    seq, rows = job["seq_len"], job["rows_per_chip"] * cell.chips
    rng_np = np.random.default_rng(seed)
    corpus = rng_np.integers(0, cfg["vocab_size"] - 1,
                             size=job["corpus_rows"] * cell.chips * seq + 1,
                             dtype=np.int32)
    batcher = data_lib.TokenBatcher(corpus, rows, seq, seed=seed % (2 ** 32))
    fed: list = []        # the first steps' rows, for the reference

    def source():
        for i, b in enumerate(batcher.iter_from(0)):
            if i < CHECK_STEPS:
                fed.append(np.array(b["tokens"][:, :-1]))
            yield b
    feeder = prefetch.Prefetcher(source(), place_fn=trainer.shard_batch,
                                 depth=job.get("prefetch", 2))
    split["feed"] = time.perf_counter() - t0

    # First steps, through fit. The probe rides fit's eval hook: after step 1
    # it reads the first gradient out of Adam's first moment, after step 3
    # the parameters' change. (Norms per leaf; computed on the device.)
    t0 = time.perf_counter()
    names = list(weights.named_leaves(jax.eval_shape(lambda: state.params)))

    @jax.jit
    def _norms(tree):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree.leaves(tree)]

    @jax.jit
    def _change_norms(p, p0):
        return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))
                for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))]

    p0 = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(jnp.float32) + 0, t))(state.params)
    got: dict = {}
    b1 = 0.9

    def probe(st):
        n = len(got.setdefault("calls", []))
        got["calls"].append(n)
        if n == 0:
            mu = _norms(_find_mu(st.opt_state))
            got["first_grad"] = {k: float(v) / (1 - b1) for k, v in zip(names, mu)}
        if n == CHECK_STEPS - 1:
            ch = _change_norms(st.params, p0)
            got["change"] = dict(zip(names, (float(v) for v in ch)))
        return {}

    key = jax.random.key(seed % (2 ** 32))
    check_rng = jax.random.fold_in(key, 0)
    mem = _MemoryMetrics()
    state = loop.fit(step_fn, state, feeder, CHECK_STEPS, check_rng,
                     metrics=mem, log_every=1, eval_every=1, eval_fn=probe,
                     global_batch_size=rows)
    got["losses"] = [l for _, _, l in mem.syncs]
    got["step_keys"] = [jax.random.fold_in(check_rng, s) for s in range(CHECK_STEPS)]
    del p0
    split["first_steps"] = time.perf_counter() - t0

    # Warm-up: a short fit with the window's own arguments, so that every
    # program the window runs (the step at log_every's cadence, fold_in, the
    # loss's conversion at the sync) is compiled or loaded now.
    t0 = time.perf_counter()
    warm_rng = jax.random.fold_in(key, 2)
    log_every = job.get("log_every", 10)
    state = loop.fit(step_fn, state, feeder, job.get("warmup_steps_run", 2 * log_every),
                     warm_rng, metrics=_MemoryMetrics(), log_every=log_every,
                     tracer=spans_lib.BenchTracer(), global_batch_size=rows,
                     preemption=_handler())
    jax.block_until_ready(state)
    split["warm_up"] = time.perf_counter() - t0
    return {"state": state, "step_fn": step_fn, "feeder": feeder, "fed": fed,
            "got": got, "rows": rows, "seq": seq, "key": key,
            "log_every": log_every, "family": fam, "devices": devices}


def _handler():
    from k8s_distributed_deeplearning_tpu.train.preemption import PreemptionHandler
    return PreemptionHandler()


def window(cell, sut: dict, seconds: float, trace_dir: str | None) -> dict:
    """The measured window. Returns counts, times and the spans."""
    import jax
    from k8s_distributed_deeplearning_tpu.train import loop

    tracer = spans_lib.BenchTracer()
    stopper = _handler()
    log_every = sut["log_every"]
    tr = {"on": False, "t0": None, "t1": None}
    trace_len = cell.traffic.get("trace_seconds", 3.0)
    opened = {}

    def on_sync(step, now):
        el = now - opened["t"]
        per_sync = el / len(mem.syncs)
        # The traced stretch is the window's last trace_seconds: the profiler
        # is started at a sync and stopped only after the window has closed
        # (stopping takes seconds, which are then nobody's).
        if (trace_dir is not None and not tr["on"]
                and el >= seconds - trace_len - per_sync):
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace_reduce.profiler_options())
            tracer.annotate = True
            tr.update(on=True, t0=time.perf_counter())
        if time.perf_counter() - opened["t"] + per_sync > seconds:
            stopper.request()

    mem = _MemoryMetrics(on_sync)
    compiles = spans_lib.CompileCounter.install()
    win_rng = jax.random.fold_in(sut["key"], 1)
    gc.collect()
    gc.freeze()
    load0 = os.getloadavg()
    jax.block_until_ready(sut["state"])
    compiles.start()
    with spans_lib.GcCounter() as gcs:
        opened["t"] = t_open = time.perf_counter()
        state = loop.fit(sut["step_fn"], sut["state"], sut["feeder"], 10 ** 9, win_rng,
                         metrics=mem, log_every=log_every, tracer=tracer,
                         global_batch_size=sut["rows"], preemption=stopper)
        jax.block_until_ready(state)
        t_close = time.perf_counter()
    n_compiles = compiles.stop()
    if tr["on"]:
        tracer.annotate = False
        jax.profiler.stop_trace()
        tr.update(on=False, t1=t_close)
    gc.unfreeze()
    sut["state"] = state
    steps = sum(1 for n, *_ in tracer.records if n == "step")
    window_s = t_close - t_open
    sync_t = [t_open] + [t for _, t, _ in mem.syncs]
    gaps = [(b - a, a - t_open) for a, b in zip(sync_t, sync_t[1:])]
    longest = sorted(gaps, reverse=True)[:3]
    return {
        "t_open": t_open, "t_close": t_close, "window_s": window_s,
        "steps": steps, "tokens": steps * sut["rows"] * sut["seq"],
        "tracer": tracer, "compiles_in_window": n_compiles,
        "compile_events": list(compiles.events),
        "gc_collections": gcs.n, "gc_seconds": gcs.seconds,
        "loadavg_open": load0, "loadavg_close": os.getloadavg(),
        "sync_gap_median_s": statistics.median(g for g, _ in gaps) if gaps else None,
        "longest_sync_gaps": [{"gap_s": g, "at_s": at} for g, at in longest],
        "log_every": log_every, "last_loss": mem.syncs[-1][2] if mem.syncs else None,
        "trace": ({"t0": tr["t0"], "t1": tr["t1"]} if tr["t0"] is not None else None),
    }


def end_to_end(cell, sut: dict, win: dict) -> dict:
    return {"train_tokens_per_s_per_chip":
            win["tokens"] / win["window_s"] / cell.chips}


def release(sut: dict) -> None:
    """Stop the feed and free the program's state (before the reference)."""
    import jax
    sut["feeder"].close()
    for k in ("state", "step_fn", "feeder"):
        sut.pop(k, None)
    gc.collect()
    jax.clear_caches()


def check(cell, seed: int, sut: dict, *, precision: str = "f32",
          fault: str | None = None) -> dict:
    """The reference's first three steps (run now, after the window)."""
    fam = sut["family"]
    job = cell.traffic
    return fam.reference.train_steps(
        cell.config, job, seed, sut["fed"], sut["got"]["step_keys"],
        steps=CHECK_STEPS, block_rows=job.get("reference_block_rows", 4),
        precision=precision, fault=fault)


def correctness(cell, seed: int, sut: dict, win: dict):
    """-> (numbers, notes, attempted, failed): the window's steps are what
    was attempted; a step whose loss is not finite has failed."""
    from benchmarks.harness import compare
    ref = check(cell, seed, sut)
    numbers, notes = compare.training_numbers(sut["got"], ref)
    last = win.get("last_loss")
    failed = 0 if (last is not None and last == last and abs(last) != float("inf")) else 1
    return numbers, notes, win["steps"], failed


def readings(cell, seed: int, *, control: bool, seconds: float = 0.0) -> dict:
    """One seed's numbers for setting limits (``tools/readings.py``): the
    program's against the reference and — with *control* — the control's and
    each plantable fault's, each being the reference put in the program's
    place (lower precision; a fault planted). Training's readings need no
    measured window."""
    from benchmarks.harness import compare
    sut = setup(cell, seed, {})
    release(sut)
    ref = check(cell, seed, sut)
    out = {"program": compare.training_numbers(sut["got"], ref)[0]}
    if control:
        low = cell.config.get("control_precision", "fp8")
        out[f"control_{low}"] = compare.training_numbers(
            check(cell, seed, sut, precision=low), ref)[0]
        faults = ["half_batch"]
        if cell.chips > 1:
            faults.append(f"no_exchange:{cell.chips}")
        for f in faults:
            out["fault_" + f] = compare.training_numbers(
                check(cell, seed, sut, fault=f), ref)[0]
    return out


def import_program() -> None:
    """The program's modules this driver uses (their import is set-up)."""
    from k8s_distributed_deeplearning_tpu.models import bert  # noqa: F401
    from k8s_distributed_deeplearning_tpu.parallel import mesh, sharding  # noqa: F401
    from k8s_distributed_deeplearning_tpu.train import data, loop, optim, prefetch  # noqa: F401
