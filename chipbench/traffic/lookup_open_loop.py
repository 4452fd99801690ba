"""Open-loop neighbour lookups (mix kind ``lookup_open_loop``).

Requests arrive on a schedule, whether or not earlier ones have finished,
and go through ``NeighborQueryEngine.submit``.  Each asks for the
neighbour lists of ``vertices_per_request`` vertices, drawn either by
zipf (``zipf_a``) over degree rank, the highest degree first, or
uniformly over all vertices.

The work is fixed by the mix, the same for every seed in another order:
``round(rate_per_s * seconds)`` requests are due in the window, at times
drawn once from ``shape_seed`` (a Poisson process conditioned on that
count), and zipf ranks are drawn once from ``shape_seed`` too; ``--seed``
then deals the ranks out to requests in its own order.  The graph itself
comes from ``--seed``, so the vertex a rank names does too.

Each request is timed from when it was due to when its answer was
returned, both ends on this file's own ``time.perf_counter`` stamps: a
collector thread watches every request in flight and stamps each as it
finds it done, within ``POLL_S``, whatever order they finish in.  No
time the engine reports is read.  A request with no answer by
``drain_s`` after the window closed, or with an error, counts as failed,
at the latency it had reached by then.

Warm-up first compiles the device decode for every stream bucket a
micro-batch of this traffic can reach (from the system's smallest
device batch up to twice the largest request of the window), then runs
the same kind of traffic for ``warmup_s`` at the same rate, from other
draws, so PG-Fuse and the hot set are filled.  What still compiles in
the window is what the system compiles anew per call (it slices each
decoded batch to its exact length), and ``window_compiles.lookup``
counts it.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from repro.core import policy
from repro.core.paragrapher import open_graph
from repro.query import NeighborQueryEngine

#: how often the collector looks for answered requests; a request's
#: stamp is late by at most this
POLL_S = 0.0005


class Runner:
    def __init__(self, env):
        self.env = env
        cfg, mix = env.cell.config, env.cell.mix
        self.mix = mix
        self.rate = float(mix["rate_per_s"])
        self.k = int(mix["vertices_per_request"])
        access = policy.choose_access_mode(mix["access"])
        self.graph = open_graph(
            env.paths["graph"], use_pgfuse=True,
            pgfuse_block_size=int(cfg["pgfuse_block_bytes"]),
            pgfuse_readahead=access.readahead,
            pgfuse_eviction=access.eviction)
        if cfg.get("pgfuse_file_budget_bytes") is not None:
            self.graph.fs.set_file_budget(
                self.graph.path, int(cfg["pgfuse_file_budget_bytes"]))
        self.engine = NeighborQueryEngine(
            self.graph, decode=cfg["decode"],
            hotset=cfg.get("hotset_bytes"), tracer=env.tracer)
        self.by_degree = np.argsort(-env.ref.degrees(), kind="stable")
        self.n = len(self.by_degree)
        self.rng = np.random.default_rng(env.seed)

    # -- traffic -----------------------------------------------------------
    def _schedule(self, seconds: float, shape_seed: int):
        """Due times (seconds from the start) and vertex sets of one run
        of the mix over ``seconds``."""
        shape = np.random.default_rng(shape_seed)
        n_req = max(1, int(round(self.rate * seconds)))
        due = np.sort(shape.uniform(0.0, seconds, n_req))
        if self.mix["pick"] == "zipf":
            ranks = shape.zipf(float(self.mix["zipf_a"]), n_req * self.k)
            ranks = self.rng.permutation(ranks)
            picks = self.by_degree[(ranks - 1) % self.n]
        else:
            picks = self.rng.integers(0, self.n, n_req * self.k)
        return due, picks.reshape(n_req, self.k)

    def _run(self, schedule, seconds: float, keep) -> dict:
        """Offer ``schedule`` open loop over ``seconds``; gather timings
        (and the answers of requests in ``keep``) as they resolve."""
        due, reqs = schedule
        n_req = len(due)
        span = self.env.span
        futs: list = [None] * n_req
        sent = np.zeros(n_req)
        posted = threading.Semaphore(0)
        done_t = np.full(n_req, np.nan)
        answers: dict = {}
        errors = [0]
        drain_s = float(self.mix["drain_s"])
        t0 = time.perf_counter() + 0.001

        def collect():
            deadline = t0 + seconds + drain_s
            pending: dict = {}
            n_posted = 0
            while n_posted < n_req or pending:
                while n_posted < n_req and posted.acquire(blocking=False):
                    f, futs[n_posted] = futs[n_posted], None
                    if f is not None:      # None: the sender stopped
                        pending[n_posted] = f
                    n_posted += 1
                for i, f in list(pending.items()):
                    if not f.done:
                        continue
                    done_t[i] = time.perf_counter()
                    del pending[i]
                    try:
                        res = f.result(timeout=0)
                    except Exception:
                        errors[0] += 1
                        done_t[i] = np.nan
                        continue
                    if i in keep:
                        answers[i] = res
                if time.perf_counter() > deadline:
                    break                  # the rest count as failed
                time.sleep(POLL_S)

        collector = threading.Thread(target=collect, name="chipbench-collect")
        collector.start()
        n_sent = 0
        try:
            for i in range(n_req):
                wait = t0 + due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                sent[i] = time.perf_counter()
                with span("submit"):
                    futs[i] = self.engine.submit(reqs[i])
                posted.release()
                n_sent += 1
        finally:
            for _ in range(n_req - n_sent):
                posted.release()
            collector.join()
        close = t0 + seconds
        failed = np.isnan(done_t)
        done_t[failed] = max(time.perf_counter(), close + drain_s)
        return {"due": t0 + due, "sent": sent, "done": done_t,
                "failed": failed, "errors": errors[0], "close": close,
                "requests": reqs, "answers": answers}

    def _compile_decode_buckets(self, hi_edges: int) -> int:
        """Compile the device decode of every stream bucket from the
        smallest batch the system decodes on the device to ``hi_edges``."""
        if self.env.cell.config["decode"] == "host":
            return 0
        from repro.kernels.compbin_decode.ops import (decode_packed_stream,
                                                      stream_bucket_ids)

        b = int(self.env.cell.config["bytes_per_id"])
        n, buckets = policy.QUERY_DEVICE_MIN_EDGES, 0
        while n <= hi_edges:
            bucket = stream_bucket_ids(n)
            decode_packed_stream(np.zeros(bucket * b, np.uint8), b)
            buckets += 1
            n = bucket + 1
        return buckets

    # -- the runner interface ------------------------------------------------
    def warm_up(self, seconds: float) -> None:
        shape_seed = int(self.mix["shape_seed"])
        self.window = self._schedule(seconds, shape_seed)
        warm = self._schedule(float(self.mix["warmup_s"]), shape_seed + 1)
        deg = self.env.ref.degrees()
        hi = 2 * int(max(deg[r].sum(axis=1).max() for _, r in
                         (self.window, warm)))
        t = time.perf_counter()
        n = self._compile_decode_buckets(hi)
        self.env.say("warm_up", decode_buckets=n, up_to_edges=hi,
                     seconds=time.perf_counter() - t)
        out = self._run(warm, float(self.mix["warmup_s"]), keep=set())
        lat = out["done"] - out["due"]
        self.env.say("warm_up", requests=len(lat),
                     failed=int(out["failed"].sum()),
                     p50_ms=1e3 * float(np.median(lat)))
        if self.env.tracer is not None:
            self.env.tracer.drain()

    def measure(self, seconds: float) -> None:
        n_req = len(self.window[0])
        n_keep = min(n_req, int(self.mix["verify_requests"]))
        keep = set(np.random.default_rng(self.env.seed).choice(
            n_req, n_keep, replace=False).tolist())
        hot = self.engine.hotset
        self.hot0 = (hot.stats.hits, hot.stats.lookups) if hot else None
        pg = self.graph.pgfuse_file_stats()
        self.pg0 = (pg.cache_hits, pg.cache_misses)
        self.out = self._run(self.window, seconds, keep)
        o = self.out
        lat = o["done"] - o["due"]
        self.env.say("window", requests=len(lat),
                     failed=int(o["failed"].sum()), errors=o["errors"],
                     answered_by_close=int((o["done"] <= o["close"]).sum()),
                     late_p99_ms=1e3 * float(np.percentile(
                         o["sent"] - o["due"], 99)),
                     last_due_to_close_ms=1e3 * float(o["close"] - o["due"][-1]))

    def attempted(self) -> int:
        return len(self.out["due"])

    def failed(self) -> int:
        return int(self.out["failed"].sum())

    def end_to_end(self) -> dict:
        lat_ms = 1e3 * (self.out["done"] - self.out["due"])
        return {"lookup_p95_ms": float(np.percentile(lat_ms, 95)),
                "lookup_p50_ms": float(np.percentile(lat_ms, 50))}

    def counters(self) -> dict:
        hot = self.engine.hotset
        pg = self.graph.pgfuse_file_stats()
        out = {"gen_late_s": self.out["sent"] - self.out["due"],
               "pgfuse_hits": pg.cache_hits - self.pg0[0],
               "pgfuse_misses": pg.cache_misses - self.pg0[1]}
        if hot is not None:
            out["hotset_hits"] = hot.stats.hits - self.hot0[0]
            out["hotset_lookups"] = hot.stats.lookups - self.hot0[1]
        if self.env.tracer is not None:
            out["spans"] = self.env.tracer.drain()
        return out

    def read_back(self) -> None:
        self.close()

    def checks(self, ref) -> dict:
        from chipbench.ref import compare_answers

        keep = sorted(self.out["answers"])
        wrong = compare_answers(ref, [self.out["requests"][i] for i in keep],
                                [self.out["answers"][i] for i in keep])
        return {"wrong_answers": (wrong, 0),
                "unanswered": (self.failed(), 0)}

    def close(self) -> None:
        if self.engine is not None:
            self.engine.close()
            self.graph.close()
            self.engine = None
