"""A copy of the benchmark's data files at a size the CPU runs in seconds.

The real configuration is cut to scale 10, with a small hot set so the
lookup path's hot set has something to do; the lookup mixes warm up for
half a second.  Everything else (the traffic runners, the metric
readers, the comparison) is the benchmark's own, found by name in the
copy.

The copy also holds the cells that ``PERF.md`` keeps for later (open-loop
lookups, the load over a four-device mesh): their code is in the
benchmark, so their paths keep their tests here until their cells are
proved on the chip.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**31 + 11          # larger than 32 signed bits hold

SMALL_CONFIGS = {"kron24": {"scale": 10, "bytes_per_id": 2,
                            "hotset_bytes": 1 << 20}}


def _lookup_mix(pick: str) -> dict:
    mix = {"kind": "lookup_open_loop", "access": "serve",
           "rate_per_s": 20.0, "pick": pick, "shape_seed": 1200,
           "warmup_s": 0.5, "drain_s": 30.0, "verify_requests": 256,
           "why": "test"}
    mix.update({"vertices_per_request": 32, "zipf_a": 1.2}
               if pick == "zipf" else {"vertices_per_request": 1024})
    return mix


#: mixes, cells and metrics of the cells kept for later
LATER_MIXES = {"lookup-zipf-32": _lookup_mix("zipf"),
               "lookup-uniform-1024": _lookup_mix("uniform")}
LATER_CELLS = [("kron24-lookup-zipf", "kron24", "lookup-zipf-32", 1),
               ("kron24-lookup-uniform", "kron24", "lookup-uniform-1024", 1),
               ("kron24-load-4chip", "kron24", "load-repeat-mesh", 4)]
LOOKUP_CELLS = [c for c, _, _, _ in LATER_CELLS if "lookup" in c]
LATER_E2E = [{"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
              "source": "host_clock", "workloads": LOOKUP_CELLS}
             for n in ("lookup_p95_ms", "lookup_p50_ms")]
LATER_LAYER = [
    {"name": name, "unit": unit, "better": better, "source": source,
     "layer": layer, "moves": "lookup_p95_ms", "workloads": LOOKUP_CELLS}
    for name, unit, better, source, layer in (
        ("gen_late_p99_ms.lookup", "ms", "lower", "host_clock",
         "load generator"),
        ("batch_ms.lookup", "ms", "lower", "program_span", "engine gather"),
        ("hotset_hit_rate.lookup", "%", "higher", "program_counter",
         "hot set"),
        ("pgfuse_hit_rate.lookup", "%", "higher", "program_counter",
         "storage cache"),
        ("device_idle.lookup", "%", "lower", "device_trace", "device"),
        ("window_compiles.lookup", "count", "lower", "program_counter",
         "entry and set-up"))]


def one_chip_cells() -> list:
    """The one-chip cells of BENCHMARK.json, then those kept for later."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [w["name"] for w in bench["workloads"] if w["chips"] == 1] + [
        c for c, _, _, chips in LATER_CELLS if chips == 1]


def _edit(path: str, changes: dict) -> None:
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


def make_root(dst: str) -> str:
    """``dst`` laid out as a checkout's benchmark, at the small size,
    with the cells kept for later and a table of peaks that knows the
    CPU."""
    os.makedirs(dst, exist_ok=True)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "chipbench", d),
                        os.path.join(dst, "chipbench", d), dirs_exist_ok=True)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        _edit(os.path.join(dst, c["file"]), SMALL_CONFIGS.get(c["name"], {}))
    for name, mix in LATER_MIXES.items():
        with open(os.path.join(dst, "chipbench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(mix, f)
    bench["end_to_end"] += LATER_E2E
    bench["per_layer"] += LATER_LAYER
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for name, config, traffic, chips in LATER_CELLS:
        add_cell(dst, name, config, traffic, chips=chips,
                 like=None if "lookup" in name else "kron24-load")
    with open(os.path.join(dst, "peaks_cpu.json"), "w") as f:
        json.dump({"cpu": {"hbm_bytes_per_s": 1e10, "source": "test"}}, f)
    return dst


def run_small(root: str, workload: str, *, trace: bool = False,
              seed: int = SEED, seconds: float = 1.0) -> dict:
    """One run of ``workload`` from ``root`` on this process's devices:
    everything the command does after it has found the chip."""
    import time

    import jax

    from chipbench import gen, harness, run

    cell = harness.resolve(workload, root)
    paths, gen_s = gen.ensure(cell.config, cell.config_name, seed,
                              platform=jax.devices()[0].platform,
                              cache_dir=os.path.join(root, "graphs"))
    return run.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                        devices=jax.devices()[:cell.chips], paths=paths,
                        gen_s=gen_s, t_start=time.perf_counter(),
                        peaks_file=os.path.join(root, "peaks_cpu.json"))


def add_cell(root: str, name: str, config: str, traffic: str,
             like, chips: int = 1) -> None:
    """A new entry in ``root``'s BENCHMARK.json that reports every metric
    the cell ``like`` reports (``None``: the metrics name it already)."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": chips,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(name)
    with open(path, "w") as f:
        json.dump(bench, f)
