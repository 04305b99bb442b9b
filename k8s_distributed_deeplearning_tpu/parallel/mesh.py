"""Device mesh construction and topology introspection.

TPU-native replacement for the reference's process-group wiring: where Horovod
derives ``rank/size/local_rank`` from MPI (``tensorflow_mnist.py:90,153-155``)
and probes the transport with ``hvd.nccl_built()`` (``:127``), here the unit of
parallelism is a :class:`jax.sharding.Mesh` over ``jax.devices()`` and the
"fast transport" probe is backend/ICI introspection.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

from k8s_distributed_deeplearning_tpu import backend

# Canonical mesh axis names, outermost (slowest-varying, crosses DCN first)
# to innermost (rides ICI). Order matters: JAX lays devices out row-major, so
# putting "data" outermost keeps per-step gradient collectives on ICI within a
# slice and only the (rare) cross-slice traffic on DCN.
AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_TENSOR = "tensor"
AXIS_SEQ = "sequence"
AXIS_EXPERT = "expert"
AXIS_PIPE = "pipeline"


def make_mesh(axis_sizes=None,
              devices: list[jax.Device] | None = None) -> Mesh:
    """Build a named device mesh.

    ``axis_sizes`` maps axis name -> size (a ``config.MeshConfig`` is also
    accepted); at most one axis may be -1 ("fill with remaining devices").
    Default: a 1-D ``data`` mesh over every visible device — the moral
    equivalent of the reference's flat MPI world (``mpirun -np N``,
    ``deploy_stack.sh:66-67``).
    """
    if axis_sizes is not None and hasattr(axis_sizes, "to_axis_sizes"):
        axis_sizes = axis_sizes.to_axis_sizes()
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if not axis_sizes:
        axis_sizes = {AXIS_DATA: n}
    names = tuple(axis_sizes)
    sizes = dict(axis_sizes)
    wild = [k for k, v in sizes.items() if v == -1]
    if len(wild) > 1:
        raise ValueError(f"at most one mesh axis may be -1, got {wild}")
    if wild:
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if n % fixed:
            raise ValueError(f"{n} devices not divisible by fixed axes {sizes}")
        sizes[wild[0]] = n // fixed
    total = math.prod(sizes.values())
    if total != n:
        raise ValueError(f"mesh {sizes} needs {total} devices, have {n}")
    dev_array = np.asarray(devices).reshape(tuple(sizes[k] for k in names))
    return Mesh(dev_array, names)


@dataclass(frozen=True)
class Topology:
    """What the cluster looks like — the ``hvd.rank()/size()/local_rank()``
    surface (``tensorflow_mnist.py:90,153``) plus device identity."""

    num_devices: int
    num_local_devices: int
    num_processes: int
    process_index: int
    platform: str
    device_kind: str

    @property
    def world_size(self) -> int:  # hvd.size()
        return self.num_devices

    @property
    def local_size(self) -> int:  # hvd.local_size()
        return self.num_local_devices

    def device_fields(self) -> dict:
        """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
        them — every entry point stamps these into its first (training) or
        summary (serving) event, so a run that fell back to the CPU says
        so."""
        return {"platform": self.platform, "device_kind": self.device_kind,
                "device_count": self.num_devices}


def topology() -> Topology:
    devs = jax.devices()
    return Topology(
        num_devices=len(devs),
        num_local_devices=jax.local_device_count(),
        num_processes=jax.process_count(),
        process_index=jax.process_index(),
        platform=devs[0].platform,
        device_kind=devs[0].device_kind,
    )


def fast_interconnect_available() -> bool:
    """``hvd.nccl_built()`` analog (``tensorflow_mnist.py:127``): True when
    collectives ride a dedicated accelerator interconnect (TPU ICI) rather
    than host TCP. Governs the Adasum learning-rate scaling rule."""
    return backend.on_tpu()


def interconnect_bandwidth_estimate() -> float:
    """Bytes/sec estimate of the per-link collective bandwidth for the
    current backend — the beta term for collective cost models. TPU
    collectives ride ICI (public per-link figures below); on CPU backends
    collectives move through host memory, so the host memcpy probe is the
    honest estimate there.
    """
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        kind = dev.device_kind.lower()
        table = {  # per-link ICI bandwidth, bytes/sec (public figures)
            "tpu v4": 1.2e11,
            "tpu v5 lite": 4.0e10,
            "tpu v5e": 4.0e10,
            "tpu v5": 1.2e11,
            "tpu v5p": 1.2e11,
            "tpu v6": 1.8e11,
        }
        # Longest key first: "tpu v5" would otherwise shadow "tpu v5p".
        for key in sorted(table, key=len, reverse=True):
            if key in kind:
                return table[key]
        raise ValueError(
            f"no ICI bandwidth figure for TPU kind {dev.device_kind!r}; "
            f"add it to the table (known: {sorted(table)})")
    from k8s_distributed_deeplearning_tpu.runtime.fusion import (
        probe_memcpy_bandwidth)
    return probe_memcpy_bandwidth()


def peak_flops_per_device(dtype: str = "bfloat16") -> float | None:
    """Peak matmul FLOP/s for the local device kind, for MFU accounting.

    Values are public peak numbers. The CPU has no peak — None, and
    ``train.loop.fit`` then reports no MFU. An accelerator that is not in
    the table is an error, not a default: a made-up peak makes a made-up
    utilization.
    """
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    kind = dev.device_kind.lower()
    table = {
        # bf16 peak per chip
        "tpu v4": 275e12,
        "tpu v5 lite": 197e12,
        "tpu v5e": 197e12,
        "tpu v5": 459e12,
        "tpu v5p": 459e12,
        "tpu v6 lite": 918e12,
        "tpu v6e": 918e12,
    }
    # Longest key first: "tpu v5" would otherwise shadow "tpu v5p" etc.
    for key in sorted(table, key=len, reverse=True):
        if key in kind:
            val = table[key]
            return val if dtype == "bfloat16" else val / 2
    raise ValueError(
        f"no peak FLOP/s figure for device kind {dev.device_kind!r} "
        f"(platform {dev.platform!r}); add it to the table "
        f"(known: {sorted(table)})")
