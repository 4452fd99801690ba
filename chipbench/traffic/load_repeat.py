"""Whole-graph loads into HBM, run back to back (mix kind ``load_repeat``).

Each load opens the graph file anew through ``open_graph(use_pgfuse=True)``
(PG-Fuse starts empty, as it does for a user's job), streams every
partition through ``stream_partitions`` and blocks until every shard is
resident in HBM.  On more than one chip the stream goes over a ``"data"``
mesh of the cell's chips, with the partition count the system's policy
picks for that many devices.

The window begins loads until its time is up; every load begun runs to
its end.  ``load_edges_per_s`` is the edges of every load begun, over the
time from the first load's start to the last load's end.  A sample of
``verify_loads`` loads, drawn from ``--seed`` by reservoir sampling, keeps
its shards on the device until the window has closed; they are then read
back and compared with the reference, shard by shard.  The other loads
release their shards as soon as they end, as a user's job would.  Every
load's placement over the mesh is checked as it ends.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import policy
from repro.core.paragrapher import open_graph
from repro.data import stream_partitions


class Runner:
    def __init__(self, env):
        self.env = env
        cfg, mix = env.cell.config, env.cell.mix
        self.access = policy.choose_access_mode(mix["access"])
        self.open_kw = dict(
            use_pgfuse=True, pgfuse_block_size=int(cfg["pgfuse_block_bytes"]),
            pgfuse_readahead=self.access.readahead,
            pgfuse_eviction=self.access.eviction)
        self.budget = cfg.get("pgfuse_file_budget_bytes")
        self.mesh, self.n_parts = None, None
        if env.cell.chips > 1:
            from jax.sharding import Mesh

            self.mesh = Mesh(np.asarray(env.devices), ("data",))
            self.n_parts = policy.choose_stream_parts(env.cell.chips)
        self.stats: list = []          # StreamStats per load
        self.kept: list = []           # the sampled loads' shards
        self.off_mesh = 0
        self.n_failed = 0
        self.t_first = self.t_last = 0.0

    def _load(self):
        span = self.env.span
        with span("open_graph"):
            g = open_graph(self.env.paths["graph"], **self.open_kw)
        try:
            if self.budget is not None:
                g.fs.set_file_budget(g.path, int(self.budget))
            with span("stream_partitions"):
                stream = stream_partitions(g, self.mesh, n_parts=self.n_parts)
            shards = []
            with stream:
                while True:
                    with span("next_shard"):
                        shard = next(stream, None)
                    if shard is None:
                        break
                    shards.append(shard)
            with span("block_until_ready"):
                for s in shards:
                    s.offsets.block_until_ready()
                    s.neighbors.block_until_ready()
        finally:
            with span("close_graph"):
                g.close()
        return shards, stream.stats

    def warm_up(self, seconds: float) -> None:
        """One whole load: every program the window's loads run compiles
        here, and the file is in the OS page cache."""
        shards, st = self._load()
        del shards
        self.env.say("warm_up", partitions=st.partitions, edges=st.edges,
                     decode_mode=st.decode_mode, wall_s=st.wall_s)

    def _count_off_mesh(self, shards) -> int:
        """Shards whose neighbours do not lie over every device of the
        mesh (an edgeless shard's empty array sits on one device)."""
        if self.mesh is None:
            return 0
        devices = set(self.env.devices)
        return sum(bool(s.n_edges) and
                   set(s.neighbors.sharding.device_set) != devices
                   for s in shards)

    def measure(self, seconds: float) -> None:
        rng = np.random.default_rng(self.env.seed)
        k = int(self.env.cell.mix["verify_loads"])
        self.t_first = time.perf_counter()
        end = self.t_first + seconds
        while time.perf_counter() < end:
            try:
                shards, st = self._load()
            except Exception as e:   # a failed load is counted and reported
                self.n_failed += 1
                self.env.warn(f"load failed: {e!r}")
                continue
            self.off_mesh += self._count_off_mesh(shards)
            if len(self.kept) < k:
                self.kept.append(shards)
            else:
                j = int(rng.integers(0, len(self.stats) + 1))
                if j < k:
                    self.kept[j] = shards
            self.stats.append(st)
            del shards
        self.t_last = time.perf_counter()

    def attempted(self) -> int:
        return len(self.stats) + self.n_failed

    def failed(self) -> int:
        return self.n_failed

    def end_to_end(self) -> dict:
        edges = sum(st.edges for st in self.stats)
        self.env.say("window", loads=len(self.stats), failed=self.n_failed,
                     edges=edges, seconds=self.t_last - self.t_first,
                     verified_loads=len(self.kept))
        return {"load_edges_per_s": edges / (self.t_last - self.t_first)}

    def counters(self) -> dict:
        return {"stream_stats": self.stats}

    def read_back(self) -> None:
        """Move the sampled loads' shards to the host; free the device."""
        self.host_loads = [[(s.v0, s.v1, np.asarray(s.offsets),
                             np.asarray(s.neighbors)) for s in shards]
                           for shards in self.kept]
        self.kept = []

    def checks(self, ref) -> dict:
        from chipbench.ref import compare_load

        wrong = uncovered = 0
        for host in self.host_loads:
            c = compare_load(ref, host)
            wrong += c["wrong_shards"]
            uncovered += c["uncovered_vertices"]
        out = {"wrong_shards": (wrong, 0), "uncovered_vertices": (uncovered, 0),
               "failed_loads": (self.n_failed, 0)}
        if self.mesh is not None:
            out["shards_off_mesh"] = (self.off_mesh, 0)
        return out

    def close(self) -> None:
        self.kept = []
