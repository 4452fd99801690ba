"""What every cell shares: finding its files by name, the compile cache,
the compile counter, and the host spans of a traced run.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to one configuration, traffic mix or per-layer metric is a
file of its own, found from the names there:

* ``configs[].file``: the configuration (sizes, codec, cache budgets);
* ``chipbench/traffic/<traffic>.json``: the mix's parameters, whose
  ``kind`` names the runner ``chipbench/traffic/<kind>.py``;
* ``chipbench/metrics/<metric>.py``: the reader of one per-layer metric.

So a later change adds a cell, a mix, a configuration or a metric with
new files and a new entry, and edits nothing that is here.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys

from chipbench import ROOT

BENCH_FILE = "BENCHMARK.json"
#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout, so only a cell's first run in a checkout compiles
JAX_CACHE_DIR = os.path.join(ROOT, "chipbench", ".jax_cache")


def enable_compile_cache() -> str:
    """Keep every compiled program in :data:`JAX_CACHE_DIR`, small ones
    too (JAX's default skips programs that compile in under a second).

    The cache is unbounded: with a size limit JAX evicts under a file
    lock that threads of one process do not exclude each other from, and
    the system compiles on more than one thread (the stream's staging
    thread and the caller's), which then loses entries."""
    import jax

    os.makedirs(JAX_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return JAX_CACHE_DIR


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file of the benchmark by its path (names may hold dots)."""
    name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload with everything it names resolved to files."""

    name: str
    chips: int
    config_name: str
    config: dict
    mix: dict
    kind: object                 # the traffic runner module
    end_to_end: list             # BENCHMARK.json metric entries
    per_layer: list
    readers: dict                # per-layer metric name -> reader module


def _reports(metric: dict, cell: str, reported: set) -> bool:
    """Whether ``cell`` reports ``metric``: named in its ``workloads``,
    or, without that key, wherever the metric it moves (if any) is."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def resolve(workload: str, root: str = ROOT) -> Cell:
    """Resolve the cell ``workload`` of ``<root>/BENCHMARK.json``."""
    bench = load_json(os.path.join(root, BENCH_FILE))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in {BENCH_FILE}; have "
                       f"{', '.join(sorted(by_name))}")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    mix = load_json(os.path.join(root, "chipbench", "traffic",
                                 w["traffic"] + ".json"))
    kind = load_module(os.path.join(root, "chipbench", "traffic",
                                    mix["kind"] + ".py"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, workload, reported)]
    readers = {m["name"]: load_module(os.path.join(
        root, "chipbench", "metrics", m["name"] + ".py")) for m in layer}
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"],
                config=load_json(os.path.join(root, cfg_entry["file"])),
                mix=mix, kind=kind,
                end_to_end=e2e, per_layer=layer, readers=readers)


class CompileCounter:
    """XLA compiles (each compile request that missed the process's own
    cache, persistent-cache hits included), from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration


class Spans:
    """Host spans the benchmark puts around its calls into each layer.

    In a traced run each is a ``jax.profiler.TraceAnnotation``, so it
    lands in the profiler's own trace on the device's clock; otherwise
    it costs nothing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("chipbench." + name)


def say(tag: str, **fields) -> None:
    """An earlier line of the run's output: ``[tag] key=value ...``."""
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def warn(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
