"""The control of ``correct``, and many seeds of a cell in one process.

Every configuration states one guarantee: answers are exact, byte for
byte.  The control breaks it the way a later change might be tempted to:
vertex ids come out of every decode path of the system (the device
decode of the stream and of the query engine, and the engine's host
decode) in ``b - 1`` bytes, the top byte dropped, as a narrower id type
would give.  A sound comparison must read that as not correct.

    python -m chipbench.control --workload <name> --seeds 1,2,3 \
        --seconds 20 [--control 0|1] [--trace 0|1]

runs the cell's timed path once per seed in one process (generation of
every missing seed first, in one child), with the control switched on or
off, and prints each run's compared numbers and its result line.  The
benchmark's own runs never switch it on.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

from chipbench import gen, harness
from chipbench.harness import say, warn


@contextlib.contextmanager
def narrow_ids():
    """Every id the system decodes keeps only its low ``b - 1`` bytes."""
    import jax.numpy as jnp

    from repro.core import compbin
    from repro.kernels import compbin_decode as pkg
    from repro.kernels.compbin_decode import ops

    dev_decode, host_decode = ops.compbin_decode, compbin.decode_ids

    def narrow_device(packed, b, **kw):
        return dev_decode(packed, b, **kw) & jnp.int32((1 << 8 * (b - 1)) - 1)

    def narrow_host(packed, b):
        out = host_decode(packed, b)
        return out & out.dtype.type((1 << 8 * (b - 1)) - 1)

    pkg.compbin_decode = ops.compbin_decode = narrow_device
    compbin.decode_ids = narrow_host
    try:
        yield
    finally:
        pkg.compbin_decode = ops.compbin_decode = dev_decode
        compbin.decode_ids = host_decode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.run import run_cell

    cell = harness.resolve(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    gen_s = gen.ensure_all(cell.config, cell.config_name, seeds,
                           platform="tpu")
    say("gen", seeds=len(seeds), seconds=gen_s)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        warn("chipbench.control: the cell's chips were not found")
        return 1
    for seed in seeds:
        ctx = narrow_ids() if args.control else contextlib.nullcontext()
        with ctx:
            result = run_cell(
                cell, seed=seed, seconds=args.seconds, trace=bool(args.trace),
                devices=devices[:cell.chips],
                paths=gen.cache_paths(cell.config_name, seed), gen_s=0.0,
                t_start=time.perf_counter())
        say("control" if args.control else "sound", seed=seed,
            correct=result["correct"],
            **{k: c["value"] for k, c in result["checks"].items()})
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
