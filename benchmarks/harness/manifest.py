"""The manifest (``BENCHMARK.json``) and the files a cell is made of.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric sits in a file of its own that is found BY NAME from the
manifest entry:

- ``benchmarks/configs/<config>.json``     sizes as run, source, reduced
- ``benchmarks/traffic/<traffic>.json``    the mix or training job; names its
                                           ``driver`` (``train`` | ``serve``)
- ``benchmarks/cells/<cell>.json``         (optional) what only this pairing
                                           fixes: a frozen arrival rate,
                                           engine options, ``correct`` limits
- ``benchmarks/layer_metrics/<metric>.py`` one reader, ``read(run)``

so a later PR adds a cell by adding files and appending manifest entries.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One ``workloads`` entry with its files loaded."""

    def __init__(self, manifest: dict, name: str, *, bench_dir: str = BENCH_DIR):
        entries = {w["name"]: w for w in manifest["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r} in the manifest; have "
                           f"{sorted(entries)}")
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.bench_dir = bench_dir
        cfgs = {c["name"]: c for c in manifest["configs"]}
        self.config_entry = cfgs[self.entry["config"]]
        # The manifest names the config's file; traffic and cell files are
        # found by name.
        # (that path is relative to the root of the checkout)
        self.config = _load_json(os.path.join(ROOT, self.config_entry["file"]))
        self.traffic = _load_json(os.path.join(
            bench_dir, "traffic", self.entry["traffic"] + ".json"))
        cell_path = os.path.join(bench_dir, "cells", name + ".json")
        self.options = _load_json(cell_path) if os.path.exists(cell_path) else {}
        self.driver = self.traffic["driver"]
        self.manifest = manifest

    def apply_rehearsal(self) -> None:
        """Swap in the tiny sizes the cell's files give under ``rehearsal``."""
        for part in (self.config, self.traffic, self.options):
            part.update(part.get("rehearsal", {}))

    def family(self):
        """``harness/family_<family>.py`` of the cell's configuration: the
        only glue that imports the program for that family."""
        return importlib.import_module(
            "benchmarks.harness.family_" + self.config["family"].replace("-", "_"))

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list[dict]:
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    def per_layer(self) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.manifest["per_layer"]
                if self._reports(m) and m["moves"] in e2e]


def start_jax(cell: Cell, rehearsal: bool):
    """Import JAX for this cell and point its persistent compile cache where
    the program's own rule says: ``$JAX_COMPILATION_CACHE_DIR`` if the machine
    sets it, else ``<checkout>/.jax_cache`` — a fixed path inside the checkout.
    Every program is kept, however small or quick to compile. A rehearsal is
    pinned to the CPU with as many virtual devices as the cell has chips.
    -> (jax, cache_dir)"""
    if rehearsal:
        cell.apply_rehearsal()
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell.chips}").strip()
    from k8s_distributed_deeplearning_tpu import backend
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    return jax, backend.use_compile_cache()


def load_manifest(path: str | None = None) -> dict:
    return _load_json(path or os.path.join(ROOT, "BENCHMARK.json"))


def load_reader(metric_name: str, *, bench_dir: str = BENCH_DIR):
    """The reader of a per-layer metric: ``layer_metrics/<name>.py`` with a
    ``read(run) -> float | None`` function. ``None`` = nothing to read here;
    the harness then leaves the metric out of the line."""
    path = os.path.join(bench_dir, "layer_metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_layer_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
