"""Metrics, logging, profiling, retry, and checkpoint-path utilities.

Re-exports are lazy (PEP 562): :mod:`utils.profiling` imports jax, but the
jax-free submodules (:mod:`utils.retry`, :mod:`utils.ckpt`,
:mod:`utils.metrics`) are consumed by ``launch/`` and ``faults/``, which
must import without pulling a jax backend into control-plane processes.
"""

_LAZY = {
    "MetricsLogger": ("k8s_distributed_deeplearning_tpu.utils.metrics",
                      "MetricsLogger"),
    "StepProfiler": ("k8s_distributed_deeplearning_tpu.utils.profiling",
                     "StepProfiler"),
    "retry_transient": ("k8s_distributed_deeplearning_tpu.utils.retry",
                        "retry_transient"),
}


def __getattr__(name: str):
    if name in _LAZY:
        import importlib
        module, attr = _LAZY[name]
        return getattr(importlib.import_module(module), attr)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(_LAZY)
