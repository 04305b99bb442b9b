"""The training loop — ``MonitoredTrainingSession`` capability, TPU-native.

Reference hot loop (``tensorflow_mnist.py:165-171``): while not should_stop,
pull a host batch, run the train op; hooks provide stop-at-step (``:146``),
periodic loss logging (``:148-149``), broadcast-at-start (``:143``), and
rank-0 checkpointing with restore-on-start (``:157-167``).

Here the loop is host-side Python around one fully-jitted SPMD step: the
device never waits on Python control flow, batches stream in asynchronously
(JAX dispatch is async; we only block on the loss when logging), and all hook
behavior is explicit and testable.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Iterator

import jax

from k8s_distributed_deeplearning_tpu import backend
from k8s_distributed_deeplearning_tpu import faults as _faults
from k8s_distributed_deeplearning_tpu.parallel import distributed
from k8s_distributed_deeplearning_tpu.telemetry.heartbeat import (
    HeartbeatWriter)
from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer
from k8s_distributed_deeplearning_tpu.train.checkpoint import Checkpointer
from k8s_distributed_deeplearning_tpu.train.preemption import PreemptionHandler
from k8s_distributed_deeplearning_tpu.utils.metrics import MetricsLogger, mfu
from k8s_distributed_deeplearning_tpu.utils.profiling import StepProfiler

PyTree = Any

_NULL_TRACER = Tracer(enabled=False)


def dump_quant_calibration(params: PyTree, path: str) -> int:
    """Write per-channel absmax stats for every quantizable kernel leaf
    as the JSON envelope ``serve.quant.load_calibration`` reads —
    ``{"weights": {param_path: [per-output-channel absmax]}}`` with keys
    from the SAME path naming ``quantize_params`` uses for its lookup,
    so a dump from the training run clips the serving scales without any
    name translation. Returns the number of entries written."""
    import json

    import numpy as np

    from k8s_distributed_deeplearning_tpu.serve import quant as quant_lib

    weights = {}
    for p, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if not quant_lib._quantizable(p, leaf):
            continue
        # graftlint: disable=host-sync — calibration is an end-of-run
        # dump, not hot-loop work.
        w = np.asarray(leaf, np.float32)
        absmax = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)))
        weights[quant_lib._path_name(p)] = absmax.reshape(-1).tolist()
    with open(path, "w") as f:
        json.dump({"weights": weights}, f)
    return len(weights)


# graftlint: hot-path
def fit(
    step_fn: Callable,                # (state, batch, rng) -> (state, loss, aux)
    state: PyTree,                    # TrainState (step counter at .step)
    batches: Iterator[PyTree] | Callable[[int], Iterator[PyTree]],
    num_steps: int,                   # already divided by world size (config.steps_for_world)
    rng: jax.Array,
    metrics: MetricsLogger | None = None,
    checkpointer: Checkpointer | None = None,
    checkpoint_every: int = 0,
    log_every: int = 10,
    global_batch_size: int | None = None,
    flops_per_example: float | None = None,
    peak_flops: float | None = None,
    preemption: PreemptionHandler | None = None,
    preemption_sync_every: int = 10,
    profiler: StepProfiler | None = None,
    eval_every: int = 0,
    eval_fn: Callable[[PyTree], dict] | None = None,
    tracer: Tracer | None = None,
    heartbeat: HeartbeatWriter | None = None,
    telemetry: "Any | None" = None,   # telemetry.bridge.TrainTelemetry
    quant_calib: str | None = None,   # JSON path for graftquant stats
) -> PyTree:
    """Run synchronous training for ``num_steps``; returns the final state.

    Restore-on-start: if *checkpointer* holds a checkpoint, training resumes
    from its step (``MonitoredTrainingSession`` parity,
    ``tensorflow_mnist.py:162-167``). Resume is replay-free: pass *batches* as
    a callable ``start_step -> iterator`` (e.g. ``ShardedBatcher.iter_from``)
    so the data schedule continues where it left off, and the per-step RNG is
    ``fold_in(rng, step)`` — a pure function of the step — so dropout keys
    don't repeat after restore either. Checkpoint writes happen on every
    ``checkpoint_every`` steps and at the end; Orbax coordinates multi-host
    writes, and only the primary logs (``:148-149,:159``).

    *preemption*: a :class:`PreemptionHandler`; when it triggers (SIGTERM from
    K8s eviction), the loop checkpoints at the step boundary and returns early
    — the next run resumes from that step. Multi-process jobs reach consensus
    via ``preemption.agreed()`` every *preemption_sync_every* steps (a host
    all-gather), so all processes branch identically even when only some pods
    were signalled; single-process jobs react on the next step. *profiler*: a
    :class:`~utils.profiling.StepProfiler` tracing a steady-state step window.

    *eval_fn(state) -> {metric: value}* with *eval_every* adds mid-training
    evaluation (the Keras variant's per-epoch validation,
    ``tensorflow_mnist_gpu.py:173-182``); results are emitted as "eval"
    events, and when *checkpointer* tracks a best metric
    (``keep_best_metric=``) each eval also saves a metric-carrying checkpoint
    so the best model — not merely the newest — survives ``max_to_keep``
    (``ModelCheckpoint save_best_only`` parity, ``:160-163``).

    *tracer*: a :class:`telemetry.trace.Tracer` adding the loop's built-in
    spans, all at depth 0 and with ``step=`` — per step ``data_wait`` (host
    blocked on the batch source), ``rng`` (the ``fold_in`` dispatch),
    ``step`` (dispatch of the jitted step; async, so this measures
    host-side cost unless the step blocks) and ``hooks`` (heartbeat,
    preemption check; a second one before ``data_wait`` around the fault
    and profiler hooks where a run has either); at the log cadence ``log_sync`` (only the fence on
    the loss) and ``log`` (the metrics line and gauges); ``eval`` and
    ``checkpoint`` around those calls. *heartbeat*:
    a :class:`telemetry.heartbeat.HeartbeatWriter` beaten every step with
    the current step and the tracer's last-completed span — ``launch
    watch --heartbeat-dir`` turns a stale file into a named stalled rank.
    *telemetry*: a :class:`telemetry.bridge.TrainTelemetry` whose gauges
    update at the ``log_every`` cadence for the ``/metrics`` scrape.

    *quant_calib*: path for a graftquant calibration dump — on normal
    completion the primary writes the final params' per-channel absmax
    stats as JSON (:func:`dump_quant_calibration`), which
    ``serve.quant.quantize_params(calibration=...)`` uses to clip its
    int8 scales. Preempted runs skip the dump: half-trained stats would
    silently mis-calibrate the serving weights.
    """
    inj = _faults.active()
    start_step = 0
    if checkpointer is not None:
        restored = checkpointer.restore_latest(state)
        if restored is not None:
            state, start_step = restored
            if metrics:
                metrics.emit("restore", step=start_step)

    batch_iter = batches(start_step) if callable(batches) else batches
    tr = tracer if tracer is not None else _NULL_TRACER
    if tr.enabled:
        # The process's first ``fold_in`` traces and compiles it: paid here,
        # so that the first step's ``rng`` span times a dispatch like every
        # other step's (graftscope would name that step a straggler).
        jax.random.fold_in(rng, start_step)
    n_dev = jax.device_count()
    t_last = time.monotonic()
    step_last = start_step  # steps actually in the current timing window
    step = start_step
    for step in range(start_step, num_steps):
        if inj is not None or profiler is not None:
            with tr.span("hooks", step=step):
                if inj is not None:
                    inj.fire("step", step=step)
                if profiler is not None:
                    profiler.step_hook(step)
        # Every span is a depth-0 sibling and carries step=: graftscope
        # (telemetry/timeline.py) aligns ranks on the step number (per-rank
        # JSONL clocks start at different t0s) and sums a step's depth-0
        # spans into its components, so no span wraps the iteration.
        with tr.span("data_wait", step=step):
            if inj is not None:
                inj.fire("data_wait", step=step)
            batch = next(batch_iter)
        with tr.span("rng", step=step):
            step_rng = jax.random.fold_in(rng, step)
        with tr.span("step", step=step):
            state, loss, aux = step_fn(state, batch, step_rng)
        with tr.span("hooks", step=step):
            if heartbeat is not None and (
                    inj is None
                    or not inj.suppressed("heartbeat", step=step + 1)):
                heartbeat.beat(step + 1, last_span=tr.last_span)
            # Single process: react immediately on the local flag. Multi-
            # process: ONLY branch on the collective agreement (same step on
            # every process) — a local-flag branch would diverge the SPMD
            # programs and deadlock (see preemption.py).
            if preemption is None:
                stop = False
            elif jax.process_count() == 1:
                stop = preemption.triggered
            else:
                stop = ((step + 1) % preemption_sync_every == 0
                        and preemption.agreed())
        if stop:
            if checkpointer is not None:
                with tr.span("checkpoint", step=step + 1):
                    checkpointer.save(step + 1, state, force=True)
            if metrics:
                metrics.emit("preempted", step=step + 1,
                             checkpointed=checkpointer is not None)
            if profiler is not None:
                profiler.stop()
            return state

        if metrics and log_every and (step + 1) % log_every == 0:
            # ``log_sync`` holds the fence and nothing else: the time the
            # host waits for the device to finish the step just dispatched.
            # When it returns the device's queue is empty, and stays empty
            # until the next ``step`` dispatch — what follows up to there is
            # time the device has nothing to do.
            with tr.span("log_sync", step=step):
                # graftlint: disable=host-sync — the one intentional sync,
                # at log cadence only: everything between logs stays async.
                loss_f = float(loss)  # blocks: this is the host sync point
                extra = {}
                for k, v in (aux or {}).items():
                    # graftlint: disable=host-sync — rides the same sync
                    extra[k] = float(v)
            with tr.span("log", step=step):
                now = time.monotonic()
                window = step + 1 - step_last
                dt_ms = (now - t_last) * 1e3 / window
                t_last = now
                step_last = step + 1
                eps = ((global_batch_size or 0) / (dt_ms / 1e3)
                       if global_batch_size else 0.0)
                m = None
                if flops_per_example and peak_flops:
                    m = mfu(flops_per_example, eps, n_dev, peak_flops)
                metrics.train_step(step + 1, loss_f, dt_ms, eps,
                                   eps / n_dev if n_dev else 0.0, mfu=m,
                                   **extra)
                if telemetry is not None:
                    telemetry.on_log(steps_in_window=window, loss=loss_f,
                                     step_time_ms=dt_ms,
                                     examples_per_sec=eps, mfu=m)

        if eval_fn is not None and eval_every and (step + 1) % eval_every == 0:
            with tr.span("eval", step=step):
                # graftlint: disable=host-sync — eval results are read at
                # eval cadence; blocking here is the point.
                ev = {k: float(v) for k, v in eval_fn(state).items()}
            if metrics:
                metrics.emit("eval", step=step + 1, **ev)
            if (checkpointer is not None
                    and checkpointer.keep_best_metric is not None):
                with tr.span("checkpoint", step=step + 1):
                    checkpointer.save(step + 1, state, metrics=ev)
                if metrics:
                    metrics.emit("checkpoint", step=step + 1, best_tracked=True)
                if telemetry is not None:
                    telemetry.on_checkpoint()

        if (checkpointer is not None and checkpoint_every
                and (step + 1) % checkpoint_every == 0):
            with tr.span("checkpoint", step=step + 1):
                checkpointer.save(step + 1, state)
            if metrics:
                metrics.emit("checkpoint", step=step + 1)
            if telemetry is not None:
                telemetry.on_checkpoint()
            if inj is not None:
                checkpointer.wait()
                inj.fire("checkpoint_saved", step=step + 1,
                         path=checkpointer.directory)

    if profiler is not None:
        profiler.stop()
    if (checkpointer is not None and num_steps > start_step
            and checkpointer.latest_step() != num_steps):
        with tr.span("checkpoint", step=num_steps):
            checkpointer.save(num_steps, state, force=True)
        if metrics:
            metrics.emit("checkpoint", step=num_steps, final=True)
        if telemetry is not None:
            telemetry.on_checkpoint()
        if inj is not None:
            checkpointer.wait()
            inj.fire("checkpoint_saved", step=num_steps,
                     path=checkpointer.directory)
    if metrics:
        # Per device, with the trained state still resident: on a multi-chip
        # host this is where a layout that left a chip empty shows.
        metrics.emit("device_memory", step=max(start_step, num_steps),
                     bytes_in_use=backend.device_bytes_in_use())
        metrics.emit("compile", step=max(start_step, num_steps),
                     **backend.compile_log().summary())
    if quant_calib is not None and distributed.is_primary():
        n = dump_quant_calibration(getattr(state, "params", state),
                                   quant_calib)
        if metrics:
            metrics.emit("quant_calib", step=num_steps, path=quant_calib,
                         entries=n)
    return state


def evaluate(eval_step: Callable, params: PyTree, batches: Iterator[PyTree],
             num_batches: int) -> dict[str, float]:
    """Average *eval_step(params, batch) -> dict* over ``num_batches`` batches.

    Improvement over the reference TF1 path, which never evaluates; the Keras
    variant evaluates on rank 0 only (``tensorflow_mnist_gpu.py:184-188``) —
    call this under ``distributed.is_primary()`` for the same discipline.
    """
    totals: dict[str, float] = {}
    for _ in range(num_batches):
        out = eval_step(params, next(batches))
        for k, v in out.items():
            totals[k] = totals.get(k, 0.0) + float(v)
    return {k: v / num_batches for k, v in totals.items()}


def should_log() -> bool:
    return distributed.is_primary()
