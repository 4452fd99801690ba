"""Serving driver: batched LM decode, DIN CTR scoring, online GNN
inference, or multi-hop graph traversals over the random-access graph
query engine (CPU-scale).

    python -m repro.launch.serve --arch smollm-360m --reduced --tokens 32
    python -m repro.launch.serve --arch din --reduced --requests 4
    python -m repro.launch.serve --arch gcn-cora --reduced --requests 8
    python -m repro.launch.serve --arch gcn-cora --reduced --traversal \\
        --requests 32
"""

from __future__ import annotations

import argparse
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache

log = logging.getLogger("repro.serve")


def serve_lm(cfg, *, batch: int, prompt_len: int, n_tokens: int) -> None:
    from repro.models import transformer as tf
    params = tf.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (batch, prompt_len)))
    max_len = prompt_len + n_tokens

    prefill = jax.jit(lambda p, t: tf.prefill(p, t, cfg, max_len=max_len))
    decode = jax.jit(lambda p, t, c: tf.decode_step(p, t, c, cfg),
                     donate_argnums=(2,))

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    toks = jnp.argmax(logits, -1)[:, None]
    outs = [toks]
    t0 = time.perf_counter()
    for _ in range(n_tokens - 1):
        logits, cache = decode(params, toks, cache)
        toks = jnp.argmax(logits, -1)[:, None]
        outs.append(toks)
    jax.block_until_ready(outs[-1])
    t_decode = time.perf_counter() - t0
    total = batch * (n_tokens - 1)
    log.info("prefill %.1f ms (%d x %d); decode %.2f ms/token/batch "
             "(%.0f tok/s)", t_prefill * 1e3, batch, prompt_len,
             t_decode / max(1, n_tokens - 1) * 1e3,
             total / max(t_decode, 1e-9))


def serve_din(cfg, *, batch: int, n_requests: int) -> None:
    from repro.models.recsys import din as m_din
    params = m_din.init_params(cfg, jax.random.key(0))
    fwd = jax.jit(lambda p, b: m_din.forward(p, b, cfg))
    rng = np.random.default_rng(0)
    lat = []
    for _ in range(n_requests):
        b = {
            "hist_items": jnp.asarray(rng.integers(-1, cfg.n_items, (batch, cfg.seq_len))),
            "hist_cates": jnp.asarray(rng.integers(0, cfg.n_cates, (batch, cfg.seq_len))),
            "cand_item": jnp.asarray(rng.integers(0, cfg.n_items, batch)),
            "cand_cate": jnp.asarray(rng.integers(0, cfg.n_cates, batch)),
        }
        t0 = time.perf_counter()
        fwd(params, b).block_until_ready()
        lat.append(time.perf_counter() - t0)
    lat_ms = np.array(lat[1:]) * 1e3  # drop compile
    log.info("DIN batch=%d: p50 %.2f ms p99 %.2f ms (%d reqs)",
             batch, np.percentile(lat_ms, 50), np.percentile(lat_ms, 99),
             len(lat_ms))


def collect_service_metrics(service) -> "MetricsRegistry":
    """Fold EVERY stats surface the serving stack exposes into one
    :class:`repro.obs.metrics.MetricsRegistry` — the ``--metrics-json``
    snapshot and the Prometheus text both render from this.

    Works for either backend shape behind a
    :class:`repro.query.TraversalService`: a single
    :class:`repro.query.NeighborQueryEngine` (its ``query.*`` stats plus
    its mount's ``pgfuse.*``), or a
    :class:`repro.query.ShardedQueryService` (fleet-folded ``query.*``
    already, plus ``router.*`` and every replica mount's ``pgfuse.*``
    folded by re-registration — the registry's fold matches
    ``PGFuseStats.merge``, so per-shard sums equal these totals).
    """
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    d = service.as_dict()
    reg.register_stats("traversal", d["traversal"])
    reg.register_stats("query", d["query"])
    if "hotset" in d:
        reg.register_stats("hotset", d["hotset"])
    backend = service.engine
    if hasattr(backend, "router"):          # ShardedQueryService
        reg.register_stats("router", backend.router.as_dict())
        for row in backend.replicas:
            for rep in row:
                pg = rep.graph.pgfuse_stats()
                if pg is not None:
                    reg.register_stats("pgfuse", pg.as_dict())
    else:
        pg = backend.graph.pgfuse_stats()
        if pg is not None:
            reg.register_stats("pgfuse", pg.as_dict())
    return reg


def _emit_metrics(reg, tracer, metrics_json) -> None:
    """Shared exposition tail: log Prometheus text + the per-tier
    bottleneck report for any sampled traces, then persist the JSON
    snapshot when ``--metrics-json`` asked for one."""
    from repro.obs.report import render_report

    if tracer is not None:
        traces = tracer.drain()
        reg.set("obs.sampled_traces", len(traces))
        reg.set("obs.dropped_traces", tracer.dropped_traces)
        if traces:
            log.info("trace report (%d sampled requests):\n%s",
                     len(traces), render_report(traces))
    log.info("metrics snapshot:\n%s", reg.to_prometheus())
    if metrics_json:
        reg.write_json(metrics_json)
        log.info("wrote metrics snapshot to %s", metrics_json)


def make_gnn_server(arch_id: str, cfg, workdir: str, *,
                    fanouts=(5, 5), use_pgfuse: bool = True,
                    seed: int = 0, decode: str = "auto",
                    fs=None, engine_name: str = None,
                    engine_budget: int = None,
                    hotset_bytes: int = None,
                    tracer=None):
    """Build the end-to-end GNN inference server over CompBin storage.

    Returns ``(answer, engine, close)``: ``answer(vertex_ids)`` runs one
    request batch — k-hop fanout sample through the
    :class:`repro.query.NeighborQueryEngine` (deduplicated, coalesced
    random access; ``decode`` places eq. (1) per micro-batch —
    "auto" routes large fanouts to the Pallas device kernel, one H2D of
    merged packed runs per batch), feature gather from the column-family
    store on the SAME PG-Fuse mount, GCN forward — and returns the
    seeds' logits as a numpy array; the whole batch crosses to the
    device as ONE transfer (``data_gnn.device_batch``).  The mount runs
    the random-access policy
    (:func:`repro.core.policy.choose_access_mode`): readahead off, clock
    eviction, feature churn capped so the hot offset blocks stay
    resident.  The sampler is seeded, so a given request stream is
    reproducible — tests replay it against an in-memory CSR and demand
    byte-identical answers.

    Multi-tenant: pass ``fs=`` (a shared
    :class:`repro.core.pgfuse.PGFuseFS` mount) plus ``engine_name`` /
    ``engine_budget`` and this server's files join ONE
    :class:`~repro.core.pgfuse.EngineShare` — several models then serve
    from one budget without evicting each other's warm sets.

    ``hotset_bytes`` adds the HBM-resident hot-set tier
    (:class:`repro.query.HotSetCache`, sized by
    :func:`repro.core.policy.choose_hotset_admission`): hub
    neighborhoods are answered from resident decoded runs and skip the
    packed-byte path entirely, byte-identically (docs/architecture.md).
    """
    import jax

    from repro.core import featstore, paragrapher, policy
    from repro.graph import NeighborSampler
    from repro.launch.data_gnn import ensure_gnn_assets, sampled_store_batch
    from repro.launch.steps import _GNN_MODULES
    from repro.query import NeighborQueryEngine

    d_in = getattr(cfg, "d_in", getattr(cfg, "d_node_in", 16))
    n_classes = getattr(cfg, "n_classes", 7)
    block_size = 1 << 16
    gp, fp, _ = ensure_gnn_assets(workdir, d_in, n_classes,
                                  block_size=block_size)
    amode = policy.choose_access_mode("serve")
    budget = engine_budget if engine_budget is not None else 256 * block_size
    share = None
    if fs is not None:
        # default share name is keyed by the asset dir, NOT just the
        # arch: two same-arch tenants on one mount must land in two
        # distinct shares (register_engine by an existing name returns —
        # and resizes — that share)
        share = fs.register_engine(
            engine_name or f"{arch_id}:{os.path.abspath(workdir)}", budget)
        g = paragrapher.open_graph(
            gp, pgfuse_fs=fs, pgfuse_readahead=amode.readahead,
            pgfuse_engine=share)
    else:
        g = paragrapher.open_graph(
            gp, use_pgfuse=use_pgfuse, pgfuse_block_size=block_size,
            pgfuse_readahead=amode.readahead, pgfuse_eviction=amode.eviction,
            pgfuse_max_resident_bytes=budget if use_pgfuse else None)
    churn_cap = (int(amode.churn_budget_fraction * budget)
                 if amode.churn_budget_fraction else None)
    feats = featstore.open_featstore(fp, fs=g.fs,
                                     pgfuse_file_budget=churn_cap,
                                     pgfuse_file_readahead=0,
                                     pgfuse_engine=share)
    engine = NeighborQueryEngine(g, decode=decode, hotset=hotset_bytes,
                                 tracer=tracer)
    sampler = NeighborSampler(engine, fanouts=fanouts, seed=seed)
    mod = _GNN_MODULES[arch_id]
    params = mod.init_params(cfg, jax.random.key(0))
    fwd = jax.jit(lambda p, b: mod.forward(p, b, cfg))

    def answer(vertex_ids) -> np.ndarray:
        """One inference request batch: logits for ``vertex_ids``."""
        block = sampler.sample(np.asarray(vertex_ids, dtype=np.int64))
        batch = sampled_store_batch(arch_id, cfg, block, feats)
        logits = fwd(params, batch)
        return np.asarray(logits[:len(block.seeds)])

    def close() -> None:
        # both handles hold refcounted retains on the (possibly shared)
        # mount: each close releases its own file, and a file other
        # tenants still retain stays warm for them
        engine.close()
        feats.close()
        g.close()

    return answer, engine, close


def make_traversal_server(workdir: str, *, decode: str = "auto",
                          slo_s: float = 0.5,
                          edge_budget: int = 1 << 16,
                          service_edges_per_s: float = 5.0e6,
                          servers: int = 2, seed: int = 1,
                          shards: int = 1, replication: int = 1,
                          hotset_bytes: int = None,
                          tracer=None):
    """The traversal request type next to GNN inference: a
    :class:`repro.query.TraversalService` over the SAME CompBin bytes
    (and the same random-access PG-Fuse policy) the inference server
    reads.  Returns ``(service, close)``; answer requests with
    ``service.khop(...)`` / ``service.bfs_visit(...)`` /
    ``service.shortest_path(...)`` or ``service.submit(request)``.

    The admission gate is sized by
    :func:`repro.core.policy.choose_admission` from the latency SLO
    and the per-request edge budget — overload sheds immediately
    (:class:`repro.query.TraversalShed`) instead of queueing into SLO
    violations.

    ``shards > 1`` (or ``replication > 1``) scales out: the frontier
    backend becomes a :class:`repro.query.ShardedQueryService` with
    ``shards`` vertex-range shards × ``replication`` replicas, each a
    simulated process with its own PG-Fuse mount, and the admission
    gate is re-sized for the scaled aggregate service rate
    (``service_edges_per_s * shards`` across ``servers * shards``
    executors).  Traversal answers stay byte-identical to ``shards=1``
    (see docs/sharded_serving.md).

    ``hotset_bytes`` gives each engine (the single backend, or every
    shard replica) an HBM-resident hot-set tier of that byte budget —
    frontier hub vertices then skip the storage gather
    (docs/architecture.md).
    """
    from repro.core import paragrapher, policy
    from repro.launch.data_gnn import ensure_gnn_assets
    from repro.query import (NeighborQueryEngine, ShardedQueryService,
                             TraversalService)

    block_size = 1 << 16
    gp, _, _ = ensure_gnn_assets(workdir, 16, 7, block_size=block_size,
                                 seed=seed)
    amode = policy.choose_access_mode("serve")
    if shards > 1 or replication > 1:
        # each shard replica mounts its own cache slice of the same
        # budget one mount would have had (the locality the split buys)
        backend = ShardedQueryService(
            gp, n_shards=shards, replication=replication, decode=decode,
            hotset_bytes=hotset_bytes, tracer=tracer,
            open_kwargs=dict(
                pgfuse_block_size=block_size,
                pgfuse_max_resident_bytes=max(
                    block_size, 256 * block_size // max(1, shards))))
        engine = None
        plan = policy.choose_admission(
            slo_s, edge_budget=edge_budget,
            service_edges_per_s=service_edges_per_s * shards,
            servers=servers * shards)
    else:
        g = paragrapher.open_graph(
            gp, use_pgfuse=True, pgfuse_block_size=block_size,
            pgfuse_readahead=amode.readahead, pgfuse_eviction=amode.eviction,
            pgfuse_max_resident_bytes=256 * block_size)
        engine = NeighborQueryEngine(g, decode=decode, hotset=hotset_bytes,
                                     tracer=tracer)
        backend = engine
        plan = policy.choose_admission(
            slo_s, edge_budget=edge_budget,
            service_edges_per_s=service_edges_per_s, servers=servers)
    service = TraversalService(backend, admission=plan,
                               default_max_edges=edge_budget,
                               tracer=tracer)

    def close() -> None:
        service.close()
        if engine is not None:
            engine.close()
            g.close()
        else:
            backend.close()

    return service, close


def serve_traversal(*, n_requests: int, batch: int, workdir: str,
                    shards: int = 1, replication: int = 1,
                    hotset_bytes: int = None,
                    metrics_json: str = None,
                    trace_sample: int = 0) -> None:
    """Synthetic zipf traversal traffic against
    :func:`make_traversal_server`: k-hop neighborhoods, bounded BFS
    visits and shortest paths over hub-biased seeds.

    ``trace_sample=N`` turns on span tracing for every Nth request
    (:class:`repro.obs.Tracer`); the per-tier attribution report is
    logged on exit.  ``metrics_json`` persists the folded
    :func:`collect_service_metrics` snapshot there on exit."""
    from repro.obs import Tracer
    from repro.query import TraversalShed

    tracer = Tracer(sample_every=trace_sample) if trace_sample else None
    service, close = make_traversal_server(workdir, shards=shards,
                                           replication=replication,
                                           hotset_bytes=hotset_bytes,
                                           tracer=tracer)
    try:
        n = service.n_vertices
        rng = np.random.default_rng(0)
        t0 = time.perf_counter()
        shed = 0
        for i in range(n_requests):
            hot = rng.integers(0, max(1, n // 16), batch)
            cold = rng.integers(0, n, batch)
            seeds = np.where(rng.random(batch) < 0.5, hot, cold)
            try:
                if i % 3 == 0:
                    service.khop(seeds, k=2)
                elif i % 3 == 1:
                    service.bfs_visit(seeds[:1], max_vertices=4 * batch)
                else:
                    service.shortest_path(int(seeds[0]), int(seeds[1]))
            except TraversalShed:
                shed += 1
        wall = time.perf_counter() - t0
        st = service.stats
        qs = service.engine.stats
        log.info("traversal serve: %d reqs in %.2fs (%.0f req/s); "
                 "p50 %.3f ms p99 %.3f ms, shed %d (%.1f%%); "
                 "%d frontier batches, %d edges scanned, "
                 "engine dedup %.2fx, %d/%d device batches",
                 st.completed, wall, st.completed / max(wall, 1e-9),
                 st.p50_s * 1e3, st.p99_s * 1e3, shed,
                 100 * st.shed_rate, st.frontier_batches,
                 st.edges_scanned, qs.dedup_ratio, qs.device_batches,
                 qs.batches)
        hs = service.as_dict().get("hotset")
        if hs:
            log.info("hot set: hit rate %.2f (%d/%d lookups), "
                     "%d resident entries (%.1f KiB), %d pinned",
                     hs["hit_rate"], hs["hits"], hs["lookups"],
                     hs["resident_entries"], hs["resident_bytes"] / 1024,
                     hs["pinned"])
        if metrics_json or tracer is not None:
            _emit_metrics(collect_service_metrics(service), tracer,
                          metrics_json)
    finally:
        close()


def serve_gnn(arch_id: str, cfg, *, batch: int, n_requests: int,
              workdir: str, hotset_bytes: int = None,
              metrics_json: str = None, trace_sample: int = 0) -> None:
    """Synthetic user-inference traffic against :func:`make_gnn_server`.

    Requests draw vertices zipf-style (a hot head, like real user
    traffic), so consecutive batches share neighborhoods — the dedup
    ratio and cache hit rate below are the quantities the engine exists
    to maximize.
    """
    from repro.obs import Tracer

    tracer = Tracer(sample_every=trace_sample) if trace_sample else None
    answer, engine, close = make_gnn_server(arch_id, cfg, workdir,
                                            hotset_bytes=hotset_bytes,
                                            tracer=tracer)
    try:
        n = engine.n_vertices
        rng = np.random.default_rng(0)
        lat = []
        for _ in range(n_requests):
            # zipf-ish: half the traffic hits the top ~1/16 of vertices
            hot = rng.integers(0, max(1, n // 16), batch)
            cold = rng.integers(0, n, batch)
            seeds = np.where(rng.random(batch) < 0.5, hot, cold)
            t0 = time.perf_counter()
            logits = answer(seeds)
            lat.append(time.perf_counter() - t0)
            assert logits.shape[0] == batch
        lat_ms = np.array(lat[1:] or lat) * 1e3  # drop compile
        st = engine.stats
        pg = engine.graph.pgfuse_stats()
        hit = (pg.cache_hits / max(1, pg.cache_hits + pg.cache_misses)
               if pg else 0.0)
        log.info("GNN serve batch=%d: p50 %.2f ms p99 %.2f ms (%d reqs); "
                 "query dedup %.2fx, %d blocks touched, %d coalesced "
                 "reads, cache hit rate %.2f; %d/%d batches device-"
                 "decoded (%.1f KiB H2D), window closes %s",
                 batch, np.percentile(lat_ms, 50), np.percentile(lat_ms, 99),
                 len(lat_ms), st.dedup_ratio, st.blocks_touched,
                 st.coalesced_reads, hit, st.device_batches, st.batches,
                 st.bytes_h2d / 1024, st.close_reasons)
        if engine.hotset is not None:
            hs = engine.hotset.stats
            log.info("hot set: hit rate %.2f (%d/%d lookups), "
                     "%d resident entries (%.1f KiB), %d pinned",
                     hs.hit_rate, hs.hits, hs.lookups,
                     hs.resident_entries, hs.resident_bytes / 1024,
                     hs.pinned)
        if metrics_json or tracer is not None:
            from repro.obs.metrics import MetricsRegistry
            reg = MetricsRegistry()
            reg.register_stats("query", st.as_dict())
            if pg is not None:
                reg.register_stats("pgfuse", pg.as_dict())
            if engine.hotset is not None:
                reg.register_stats("hotset", engine.hotset.stats.as_dict())
            _emit_metrics(reg, tracer, metrics_json)
    finally:
        close()


def main() -> None:
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--workdir", default="/tmp/repro_serve")
    ap.add_argument("--traversal", action="store_true",
                    help="serve multi-hop traversal requests (k-hop / "
                         "BFS visit / shortest path) over the graph "
                         "assets instead of model inference")
    ap.add_argument("--shards", type=int, default=1,
                    help="vertex-range shards for --traversal serving "
                         "(each a simulated process with its own "
                         "PG-Fuse mount; answers stay byte-identical)")
    ap.add_argument("--replication", type=int, default=1,
                    help="replicas per shard for --traversal serving "
                         "(round-robin load balancing + failover)")
    ap.add_argument("--hotset-bytes", type=int, default=None,
                    help="byte budget for the HBM-resident hot-set tier "
                         "of decoded hub runs (gnn/traversal serving; "
                         "default: no hot set). Admission is degree-"
                         "aware — see policy.choose_hotset_admission")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write a JSON metrics snapshot on exit (every "
                         "stats surface folded across all shards into "
                         "the repro.obs.metrics namespace; gnn/"
                         "traversal serving)")
    ap.add_argument("--trace-sample", type=int, default=0, metavar="N",
                    help="span-trace every Nth request through the full "
                         "stack (route/gather/storage/decode/H2D) and "
                         "log the per-tier attribution report on exit "
                         "(0: tracing off, the no-op tracer)")
    args = ap.parse_args()
    enable_compile_cache()

    spec = get_arch(args.arch)
    cfg = spec.make_reduced() if args.reduced else spec.make_config()
    if args.traversal:
        if spec.family != "gnn":
            raise SystemExit("--traversal serves graph requests; pick a "
                             "gnn arch for its graph assets")
        serve_traversal(n_requests=args.requests, batch=args.batch,
                        workdir=args.workdir, shards=args.shards,
                        replication=args.replication,
                        hotset_bytes=args.hotset_bytes,
                        metrics_json=args.metrics_json,
                        trace_sample=args.trace_sample)
        return
    if spec.family == "lm":
        serve_lm(cfg, batch=args.batch, prompt_len=args.prompt_len,
                 n_tokens=args.tokens)
    elif spec.family == "recsys":
        serve_din(cfg, batch=args.batch, n_requests=args.requests)
    elif spec.family == "gnn":
        serve_gnn(args.arch, cfg, batch=args.batch,
                  n_requests=args.requests, workdir=args.workdir,
                  hotset_bytes=args.hotset_bytes,
                  metrics_json=args.metrics_json,
                  trace_sample=args.trace_sample)
    else:
        raise SystemExit(f"unknown family {spec.family!r}")


if __name__ == "__main__":
    main()
