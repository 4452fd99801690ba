#!/usr/bin/env python3
"""Smoke run of the CompBin load-and-serve path on a TPU.

    python chip_smoke.py               # one chip, the whole path
    python chip_smoke.py --chips 4     # the four-chip streamed load only

Everything is generated from ``--seed`` into a fresh temporary
directory, in one process:

* data  — a Graph500 Kronecker graph (``repro.graph.rmat``, A,B,C =
  0.57/0.19/0.19, edge factor 16) at scale 22: ~4.2 M vertices, ~64 M
  edges after dedupe, written as CompBin (b = 3);
* load  — ``open_graph(use_pgfuse=True)`` -> ``stream_partitions`` with
  the Pallas decode on the device; every shard must equal the generated
  CSR byte for byte, with no byte decoded on the host;
* serve — ``NeighborQueryEngine(decode="device")`` answers zipf-chosen
  vertex batches, then again with a device-resident hot set, and
  ``TraversalService`` answers 2-hop requests; every answer must equal
  the in-memory CSR's;
* ``--chips 4`` instead streams the graph over a 4-device ``"data"`` mesh
  and compares each shard with the unsplit host decode of the same
  partition.

Times, compile counts and peak device memory are printed as single smoke
readings, not benchmark results.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase and
every comparison passed; any failure, or a platform other than ``tpu``,
exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.core import policy  # noqa: E402
from repro.core.paragrapher import open_graph, save_graph  # noqa: E402
from repro.data import stream_partitions  # noqa: E402
from repro.graph import rmat  # noqa: E402
from repro.query import NeighborQueryEngine, TraversalService  # noqa: E402

SCALE = 22
EDGE_FACTOR = 16
ZIPF_A = 1.2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer or took a path it must not."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileCounter:
    """Backend compiles and their seconds, from JAX's monitoring events."""

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


def _open(path: str, workload: str):
    """The graph over PG-Fuse, configured for ``workload``'s access mode."""
    access = policy.choose_access_mode(workload)
    return open_graph(path, use_pgfuse=True,
                      pgfuse_readahead=access.readahead,
                      pgfuse_eviction=access.eviction)


def _check_shard(csr, shard, what: str) -> None:
    """One streamed shard against the reference CSR: neighbours byte for
    byte, rebased offsets by value (the device holds them as int32)."""
    o0, o1 = int(csr.offsets[shard.v0]), int(csr.offsets[shard.v1])
    offs = np.asarray(shard.offsets)
    nbrs = np.asarray(shard.neighbors)
    ref_offs = csr.offsets[shard.v0:shard.v1 + 1] - o0
    check(np.array_equal(offs, ref_offs),
          f"{what} [{shard.v0},{shard.v1}): offsets differ from the CSR")
    check(nbrs.dtype == csr.neighbors.dtype
          and np.array_equal(nbrs, csr.neighbors[o0:o1]),
          f"{what} [{shard.v0},{shard.v1}): neighbors differ from the CSR")


def _check_cover(csr, keys, what: str) -> None:
    bounds = sorted(keys)
    check(bool(bounds) and bounds[0][0] == 0
          and bounds[-1][1] == csr.n_vertices
          and all(a[1] == b[0] for a, b in zip(bounds, bounds[1:])),
          f"{what}: shards do not tile [0, {csr.n_vertices})")


def phase_data(workdir: str, *, scale: int = SCALE, seed: int = 0):
    """Generate the Kronecker graph and write it as CompBin."""
    t0 = time.perf_counter()
    csr = rmat(scale, EDGE_FACTOR, seed=seed)
    path = os.path.join(workdir, f"kron{scale}.cbin")
    save_graph(path, csr, format="compbin")
    with open_graph(path) as g:
        b = g.bytes_per_id
    say("data", scale=scale, vertices=csr.n_vertices, edges=csr.n_edges,
        b=b, file_bytes=os.path.getsize(path),
        seconds=time.perf_counter() - t0)
    return csr, path


def phase_load(csr, path: str) -> dict:
    """Stream every partition to the device and decode it there."""
    with _open(path, "stream") as g:
        t0 = time.perf_counter()
        with stream_partitions(g) as stream:
            shards = list(stream)
        seconds = time.perf_counter() - t0
    st = stream.stats
    say("load", decode_mode=st.decode_mode,
        host_decode_bytes=st.host_decode_bytes, partitions=st.partitions,
        edges=st.edges, bytes_h2d=st.bytes_h2d,
        seconds=f"{seconds} (single smoke reading, not a benchmark)")
    check(st.decode_mode == "device",
          f"stream decoded on {st.decode_mode!r}, not on the device "
          f"({st.decode_reason})")
    check(st.host_decode_bytes == 0,
          f"{st.host_decode_bytes} packed bytes were decoded on the host")
    check(st.edges == csr.n_edges,
          f"streamed {st.edges} edges of {csr.n_edges}")
    _check_cover(csr, [(s.v0, s.v1) for s in shards], "load")
    for s in shards:
        _check_shard(csr, s, "load")
    say("load", compared_shards=len(shards), equal_to_csr=True)
    return {"seconds": seconds, "partitions": st.partitions}


def _zipf_batches(csr, rng, n_batches: int, batch: int) -> list:
    """Zipf-popular vertices, the most popular being the highest-degree
    ones (hubs draw the traffic)."""
    by_degree = np.argsort(-csr.degrees(), kind="stable")
    return [by_degree[(rng.zipf(ZIPF_A, batch) - 1) % csr.n_vertices]
            for _ in range(n_batches)]


def _check_answers(csr, vertices, answers, what: str) -> None:
    check(len(answers) == len(vertices),
          f"{what}: {len(answers)} answers for {len(vertices)} vertices")
    for v, ans in zip(vertices, answers):
        check(np.array_equal(ans, csr.neighbors_of(int(v))),
              f"{what}: neighbors of {int(v)} differ from the CSR")


def ref_khop(csr, seed: int, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Plain k-hop visit over the in-memory CSR: vertices hop by hop,
    ascending within a hop, with the hop each was reached at."""
    visited = np.array([seed], dtype=np.int64)
    frontier = visited
    verts, depths = [visited], [np.zeros(1, np.int64)]
    for hop in range(1, k + 1):
        if frontier.size == 0:
            break
        reached = np.concatenate([csr.neighbors_of(int(v))
                                  for v in frontier]).astype(np.int64)
        new = np.setdiff1d(reached, visited)
        verts.append(new)
        depths.append(np.full(new.size, hop, np.int64))
        visited = np.union1d(visited, new)
        frontier = new
    return np.concatenate(verts), np.concatenate(depths)


def phase_serve(csr, path: str, *, seed: int = 0, n_batches: int = 8,
                batch: int = 1024, hotset_bytes: int = 64 << 20,
                n_khop: int = 4) -> dict:
    """Neighbour lookups with device decode, then through a device hot
    set, then 2-hop traversals; every answer against the CSR."""
    rng = np.random.default_rng(seed)
    batches = _zipf_batches(csr, rng, n_batches, batch)
    with _open(path, "serve") as g:
        with NeighborQueryEngine(g, decode="device") as engine:
            t0 = time.perf_counter()
            answers = [engine.neighbors_batch(vs) for vs in batches]
            seconds = time.perf_counter() - t0
            st = engine.stats
        say("serve", batches=st.batches, requests=st.requests,
            device_batches=st.device_batches, edges=st.edges_returned,
            bytes_h2d=st.bytes_h2d,
            seconds=f"{seconds} (single smoke reading, not a benchmark)")
        check(st.device_batches == n_batches,
              f"{st.device_batches} of {n_batches} batches decoded on "
              f"the device")
        for vs, ans in zip(batches, answers):
            _check_answers(csr, vs, ans, "lookup")

        with NeighborQueryEngine(g, decode="device",
                                 hotset=hotset_bytes) as hot_engine:
            hot = hot_engine.hotset
            check(hot.plan.place == "device",
                  f"hot set placed on {hot.plan.place!r}: {hot.plan.reason}")
            for _ in range(2):   # the first pass fills, the second hits
                for vs in batches:
                    _check_answers(csr, vs, hot_engine.neighbors_batch(vs),
                                   "hot-set lookup")
            hs = hot.stats
            say("serve", hotset_place=hot.plan.place, hotset_hits=hs.hits,
                hotset_lookups=hs.lookups,
                hotset_resident_bytes=hot.resident_bytes,
                device_batches=hot_engine.stats.device_batches)
            check(hs.hits > 0, "the hot set answered no lookup")
            check(hot_engine.stats.device_batches > 0,
                  "no hot-set batch decoded on the device")

            svc = TraversalService(hot_engine)
            seeds = rng.integers(0, csr.n_vertices, n_khop)
            visited = 0
            for s in seeds:
                res = svc.khop([int(s)], k=2, max_edges=csr.n_edges)
                ref_v, ref_d = ref_khop(csr, int(s), 2)
                check(not res.truncated, f"khop from {int(s)} truncated")
                check(np.array_equal(res.vertices, ref_v)
                      and np.array_equal(res.depths, ref_d),
                      f"khop from {int(s)} differs from the CSR visit")
                visited += res.n_visited
            say("serve", khop_requests=len(seeds), khop_visited=visited,
                equal_to_csr=True)
    return {"seconds": seconds, "device_batches": st.device_batches}


def phase_shard4(csr, path: str, devices) -> dict:
    """Stream over a ``"data"`` mesh of ``devices`` and compare every
    shard with the unsplit host decode of the same partition."""
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devices), ("data",))
    n_parts = policy.choose_stream_parts(len(devices))
    with _open(path, "stream") as g:
        t0 = time.perf_counter()
        with stream_partitions(g, mesh, n_parts=n_parts) as stream:
            shards = {(s.v0, s.v1): s for s in stream}
        seconds = time.perf_counter() - t0
        host = policy.StreamDecodePlan("host", "reference: unsplit host decode")
        with stream_partitions(g, n_parts=n_parts, decode_plan=host) as ref:
            ref_shards = {(s.v0, s.v1): s for s in ref}
    st = stream.stats
    # an edgeless partition's empty array sits on one device
    split = sum(s.n_edges == 0
                or len(s.neighbors.sharding.device_set) == len(devices)
                for s in shards.values())
    say("shard4", devices=len(devices), decode_mode=st.decode_mode,
        host_decode_bytes=st.host_decode_bytes, partitions=len(shards),
        shards_on_every_device=split, edges=st.edges,
        seconds=f"{seconds} (single smoke reading, not a benchmark)")
    check(st.decode_mode == "device" and st.host_decode_bytes == 0,
          f"mesh stream decoded on {st.decode_mode!r} "
          f"({st.host_decode_bytes} host bytes)")
    check(split == len(shards),
          f"{len(shards) - split} shards did not land on all "
          f"{len(devices)} devices")
    check(sorted(shards) == sorted(ref_shards),
          "mesh and reference streams cut different partitions")
    _check_cover(csr, shards, "shard4")
    for key, s in shards.items():
        r = ref_shards[key]
        for field in ("offsets", "neighbors"):
            a = np.asarray(getattr(s, field))
            b = np.asarray(getattr(r, field))
            check(a.dtype == b.dtype and np.array_equal(a, b),
                  f"shard4 {key}: {field} differ from the host decode")
        _check_shard(csr, s, "shard4")
    say("shard4", compared_shards=len(shards), equal_to_host_decode=True)
    return {"seconds": seconds, "partitions": len(shards)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: stream over a 4-device mesh and run nothing "
                         "else")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=SCALE,
                    help="Kronecker scale; below 22 only to rehearse")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), compile_cache=enable_compile_cache())
    compiles = CompileCounter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        csr, path = phase_data(work, scale=args.scale, seed=args.seed)
        if args.chips == 4:
            phase_shard4(csr, path, devices[:4])
        else:
            phase_load(csr, path)
            phase_serve(csr, path, seed=args.seed)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:args.chips]]
    say("totals", backend_compiles=compiles.n,
        compile_seconds=f"{compiles.seconds} (single smoke reading)",
        peak_bytes_in_use=peaks)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
