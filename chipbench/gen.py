"""Graph generators of the benchmark's configurations, and the per-seed cache.

The random draws run on the device with ``jax.random`` from ``--seed``;
the host sorts, de-duplicates and compacts the edges into a CSR, and
writes three files per configuration and seed under the cache directory:

* ``<config>-<seed>.cbin``: the graph in the configuration's codec,
  written by the system's own ``save_graph`` (a user stores graphs so);
* ``<config>-<seed>.offsets.npy`` and ``.neighbors.npy``: the generator's
  CSR itself, which :mod:`chipbench.ref` reads as the plain reference.

A run whose three files exist generates nothing.  Generation runs in a
child process (``python -m chipbench.gen``) before the benchmark's own
process touches JAX, so the device memory it takes never shows in the
run's peak and the chip is free again when the run starts.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import ROOT

CACHE_DIR = os.path.join(ROOT, "chipbench", ".graphs")


def _key(seed: int):
    """A threefry key from all 64 bits of ``seed`` (seeds exceed 2**31)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned number")
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


@functools.partial(jax.jit, static_argnames=("scale", "n_edges", "thresholds"))
def _kronecker_edges(key, scale: int, n_edges: int, thresholds: tuple):
    """Graph500 Kronecker edges (source, target), unsorted.  Each level
    draws one 32-bit uniform per edge and picks a quadrant by A, A+B,
    A+B+C."""
    t_a, t_ab, t_abc = (jnp.uint32(t) for t in thresholds)

    def level(i, carry):
        src, dst = carry
        u = jax.random.bits(jax.random.fold_in(key, i), (n_edges,),
                            jnp.uint32)
        down = u >= t_ab                                  # quadrant C or D
        right = ((u >= t_a) & (u < t_ab)) | (u >= t_abc)  # quadrant B or D
        bit = jnp.uint32(i)
        return (src | (down.astype(jnp.uint32) << bit),
                dst | (right.astype(jnp.uint32) << bit))

    zeros = jnp.zeros((n_edges,), jnp.uint32)
    return jax.lax.fori_loop(0, scale, level, (zeros, zeros))


@functools.partial(jax.jit, static_argnames=("scale", "n_edges", "thresholds"))
def _graph500_edges(key, scale: int, n_edges: int, thresholds: tuple):
    """Kronecker edges with the vertex labels scrambled by one random
    permutation of ``[0, 2**scale)``, as the Graph500 generator does, so
    the hubs are spread over the id range instead of sitting in its low
    ids."""
    k_draw, k_perm = jax.random.split(key)
    src, dst = _kronecker_edges(k_draw, scale, n_edges, thresholds)
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.uint32)
    return perm[src], perm[dst]


def kronecker(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Directed, de-duplicated Graph500 Kronecker graph with permuted
    vertex labels: (offsets, neighbors).

    The draws and the permutation run on the device in one program; the
    host sorts one 64-bit key per edge (source above target) and drops
    repeats, which is faster there than a two-key sort on the chip."""
    scale = int(cfg["scale"])
    n = 1 << scale
    a, b, c = float(cfg["a"]), float(cfg["b"]), float(cfg["c"])
    thresholds = tuple(int(round(p * 2**32)) for p in (a, a + b, a + b + c))
    src, dst = _graph500_edges(_key(seed), scale,
                               int(cfg["edge_factor"]) << scale, thresholds)
    key = np.asarray(src).astype(np.uint64) << np.uint64(scale)
    key |= np.asarray(dst)
    key.sort()
    keep = np.empty(len(key), bool)
    keep[:1] = True
    np.not_equal(key[1:], key[:-1], out=keep[1:])
    key = key[keep]
    neighbors = (key & np.uint64(n - 1)).astype(np.int32)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount((key >> np.uint64(scale)).astype(np.int64),
                          minlength=n), out=offsets[1:])
    return offsets, neighbors


GENERATORS = {"kronecker": kronecker}


def cache_paths(config_name: str, seed: int, cache_dir: str = CACHE_DIR
                ) -> dict:
    stem = os.path.join(cache_dir, f"{config_name}-{seed}")
    return {"graph": stem + ".cbin", "offsets": stem + ".offsets.npy",
            "neighbors": stem + ".neighbors.npy"}


def is_cached(paths: dict) -> bool:
    return all(os.path.exists(p) for p in paths.values())


def generate(cfg: dict, config_name: str, seed: int,
             cache_dir: str = CACHE_DIR) -> dict:
    """Generate and write one configuration's graph for ``seed``; returns
    counts and seconds.  Files are renamed into place last, so a run that
    is cut leaves nothing a later run would take for a finished file."""
    from repro.core.csr import CSR
    from repro.core.paragrapher import open_graph, save_graph

    t0 = time.perf_counter()
    offsets, neighbors = GENERATORS[cfg["generator"]](cfg, seed)
    t_gen = time.perf_counter() - t0
    paths = cache_paths(config_name, seed, cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    tmp = {k: p + f".tmp{os.getpid()}" for k, p in paths.items()}
    save_graph(tmp["graph"], CSR(offsets=offsets, neighbors=neighbors),
               format=cfg["codec"])
    with open_graph(tmp["graph"]) as g:
        b = g.bytes_per_id
    if b != int(cfg["bytes_per_id"]):
        raise ValueError(f"{config_name}: the codec stored {b} bytes per id, "
                         f"the configuration states {cfg['bytes_per_id']}")
    for key in ("offsets", "neighbors"):
        with open(tmp[key], "wb") as f:
            np.save(f, offsets if key == "offsets" else neighbors)
    for k in paths:
        # flushed here, so the kernel's write-back of these files does not
        # land in the first run's measured window
        with open(tmp[k], "rb+") as f:
            os.fsync(f.fileno())
        os.replace(tmp[k], paths[k])
    return {"vertices": len(offsets) - 1, "edges": len(neighbors),
            "file_bytes": os.path.getsize(paths["graph"]),
            "draw_s": t_gen, "seconds": time.perf_counter() - t0}


def ensure(cfg: dict, config_name: str, seed: int, *, platform: str,
           cache_dir: str = CACHE_DIR) -> tuple[dict, float]:
    """The cached files of (config, seed), generating them first in a
    child process that must find ``platform``.  Returns (paths, seconds
    spent generating; 0.0 when the files were there)."""
    seconds = ensure_all(cfg, config_name, [seed], platform=platform,
                         cache_dir=cache_dir)
    return cache_paths(config_name, seed, cache_dir), seconds


def ensure_all(cfg: dict, config_name: str, seeds, *, platform: str,
               cache_dir: str = CACHE_DIR) -> float:
    """Generate the seeds whose files are missing, all in one child
    process; returns the seconds it took (0.0 when none was missing)."""
    missing = [s for s in seeds
               if not is_cached(cache_paths(config_name, s, cache_dir))]
    if not missing:
        return 0.0
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, "-m", "chipbench.gen", "--config", json.dumps(cfg),
         "--name", config_name, "--platform", platform,
         "--cache-dir", cache_dir, "--seed", *map(str, missing)],
        cwd=ROOT, stdout=subprocess.PIPE, text=True)
    sys.stdout.write(r.stdout)
    if r.returncode != 0 or not all(
            is_cached(cache_paths(config_name, s, cache_dir)) for s in missing):
        raise RuntimeError(f"generating {config_name} for seeds {missing} "
                           f"failed (exit {r.returncode})")
    return time.perf_counter() - t0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="generate one cached graph")
    ap.add_argument("--config", required=True, help="configuration as JSON")
    ap.add_argument("--name", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--platform", required=True)
    ap.add_argument("--cache-dir", default=CACHE_DIR)
    args = ap.parse_args(argv)

    from chipbench.harness import enable_compile_cache

    found = jax.devices()[0].platform
    if found != args.platform:
        print(f"chipbench.gen: platform {found!r}, not {args.platform!r}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    for seed in args.seed:
        info = generate(json.loads(args.config), args.name, seed,
                        args.cache_dir)
        print(f"[gen] seed={seed} "
              + " ".join(f"{k}={v}" for k, v in info.items()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
