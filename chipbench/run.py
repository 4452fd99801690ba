"""Run one cell of the benchmark once.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The run generates (or finds cached) the
cell's graph for ``--seed``, starts JAX on the chip, builds the cell's
traffic runner, warms it up, measures for ``--seconds``, and then checks
what the timed path produced against the plain reference.  ``setup_s``
runs from the end of generation to the window's start: a user of the
system already has the graph file, so generating it is the benchmark's
own scaffolding, reported apart on the ``[gen]`` line.  Earlier lines
of standard output are free-form (``[tag] key=value``); the last is one
JSON object::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the same kind of window
under the JAX profiler.  ``checks`` holds each number compared, beside
its limit, and is printed again as the last lines of standard error.

A run on any platform other than ``tpu``, or on fewer chips than the cell
asks for, exits non-zero and prints no result.
"""

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

from chipbench import ROOT, gen, harness
from chipbench.harness import say, warn

PEAKS_FILE = os.path.join(ROOT, "chipbench", "peaks.json")
TRACE_DIR = os.path.join(ROOT, "chipbench", ".traces")


@dataclasses.dataclass
class Env:
    """What a traffic runner is given."""

    cell: harness.Cell
    paths: dict               # the graph file and the reference's files
    devices: list             # the cell's devices
    seed: int
    ref: object               # chipbench.ref.RefCSR
    tracer: object            # repro.obs.trace.Tracer in a traced run
    span: harness.Spans
    say = staticmethod(say)
    warn = staticmethod(warn)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader is given."""

    cell: harness.Cell
    counters: dict            # the runner's counts and the program's stats
    trace: object             # chipbench.trace_reduce.Reduced, or None
    peaks: dict               # chipbench/peaks.json entry of this device
    devices: int
    window_compiles: int


def _traced_window(runner, seconds: float, span, trace_dir: str):
    """The measured window under the JAX profiler; returns the window's
    start on ``time.perf_counter``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with span("window"):
            t_w = time.perf_counter()
            runner.measure(seconds)
    finally:
        jax.profiler.stop_trace()
    return t_w


def run_cell(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
             devices: list, paths: dict, gen_s: float, t_start: float,
             peaks_file: str = PEAKS_FILE) -> dict:
    """Everything after generation and the platform check; returns the
    result object.  ``t_start`` (``time.perf_counter``) is where set-up
    began: the end of generation."""
    from chipbench.ref import RefCSR

    harness.enable_compile_cache()
    compiles = harness.CompileCounter()
    ref = RefCSR(paths["offsets"], paths["neighbors"])
    tracer = None
    if trace:
        from repro.obs.trace import Tracer

        tracer = Tracer(clock=time.perf_counter, max_traces=1 << 24)
    span = harness.Spans(trace)
    env = Env(cell=cell, paths=paths, devices=devices, seed=seed, ref=ref,
              tracer=tracer, span=span)
    runner = cell.kind.Runner(env)
    try:
        runner.warm_up(seconds)
        setup_s = time.perf_counter() - t_start
        say("setup", seconds=setup_s, generation_s=gen_s,
            compiles=compiles.n, compile_s=compiles.seconds)
        n0 = compiles.n
        trace_dir = os.path.join(TRACE_DIR, f"{cell.name}-{seed}")
        if trace:
            t_w = _traced_window(runner, seconds, span, trace_dir)
        else:
            runner.measure(seconds)
        window_compiles = compiles.n - n0
        say("window", compiles=window_compiles)
        e2e = runner.end_to_end()
        counters = runner.counters()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        attempted, failed = runner.attempted(), runner.failed()
        runner.read_back()
    finally:
        runner.close()
    checks = runner.checks(ref)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": all(v <= lim for v, lim in checks.values())
              and failed == 0,
              "attempted": attempted, "failed": failed}
    if not trace:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = dict(e2e, setup_s=setup_s)
        result["metrics"] = {k: {"value": values[k], "unit": units[k]}
                             for k in units}
    else:
        from chipbench import trace_reduce

        red = trace_reduce.reduce_dir(trace_dir)
        red.add_host_spans(
            [(s.t0, s.t1, s.name) for root in counters.get("spans", [])
             for s in root.iter_spans()], t_w)
        shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = harness.load_json(peaks_file)
        if dev.device_kind not in peaks:
            raise KeyError(f"no peaks for device kind {dev.device_kind!r} "
                           f"in {peaks_file}")
        reading = Reading(cell=cell, counters=counters, trace=red,
                          peaks=peaks[dev.device_kind], devices=len(devices),
                          window_compiles=window_compiles)
        metrics = {}
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = red.mean_busy_s()
        device["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": red.device_ops(),
                               "idle_gaps": red.idle_gaps()}
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.resolve(args.workload)
    # generation runs first, in a child process that refuses any platform
    # but the TPU, so this process has not touched the chip while the
    # child needs it
    try:
        paths, gen_s = gen.ensure(cell.config, cell.config_name, args.seed,
                                  platform="tpu")
    except RuntimeError as e:
        warn(f"chipbench: {e}")
        return 1
    t_start = time.perf_counter()
    say("gen", config=cell.config_name, seed=args.seed, seconds=gen_s,
        cached=gen_s == 0.0)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        warn(f"chipbench: JAX found no TPU (platform {devices[0].platform!r})")
        return 1
    if len(devices) < cell.chips:
        warn(f"chipbench: {args.workload} needs {cell.chips} chips, JAX "
             f"found {len(devices)}")
        return 1
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices[:cell.chips],
                      paths=paths, gen_s=gen_s, t_start=t_start)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
