"""Find a lookup cell's knee: the highest offered rate whose backlog does
not grow through the window.

    python -m chipbench.sweep --workload <lookup cell> --seed <n> \
        --rates 1,2,4,8 --seconds 20

One process sets the cell up once (graph, engine, warm-up at the mix's
own rate), then offers the mix's traffic open loop at each rate in turn
for ``--seconds``, and prints one line per rate: requests, p50 and p95
from due to answer, how many were answered by the window's close, and the
median latency of the last quarter of requests over that of the first (a
backlog that grows shows as a ratio well above 1).  The knee is read off
this table by hand, once, and written into the mix's file as
``rate_per_s``.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from chipbench import gen, harness
from chipbench.harness import say, warn


def sweep(runner, rates, seconds: float) -> list:
    rows = []
    for rate in rates:
        runner.rate = float(rate)
        out = runner._run(runner._schedule(seconds,
                                           int(runner.mix["shape_seed"])),
                          seconds, keep=set())
        lat = 1e3 * (out["done"] - out["due"])
        q = max(1, len(lat) // 4)
        row = {"rate_per_s": rate, "requests": len(lat),
               "failed": int(out["failed"].sum()),
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "answered_by_close": int((out["done"] <= out["close"]).sum()),
               "last_over_first_quarter": float(np.median(lat[-q:])
                                                / np.median(lat[:q])),
               "gen_late_p99_ms": 1e3 * float(np.percentile(
                   out["sent"] - out["due"], 99))}
        say("sweep", **row)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench.run import Env
    from chipbench.ref import RefCSR

    cell = harness.resolve(args.workload)
    paths, gen_s = gen.ensure(cell.config, cell.config_name, args.seed,
                              platform="tpu")
    import jax

    if jax.devices()[0].platform != "tpu":
        warn("chipbench.sweep: JAX found no TPU")
        return 1
    harness.enable_compile_cache()
    env = Env(cell=cell, paths=paths, devices=jax.devices()[:cell.chips],
              seed=args.seed, ref=RefCSR(paths["offsets"], paths["neighbors"]),
              tracer=None, span=harness.Spans(False))
    runner = cell.kind.Runner(env)
    try:
        runner.warm_up(args.seconds)
        sweep(runner, [float(r) for r in args.rates.split(",")], args.seconds)
    finally:
        runner.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
