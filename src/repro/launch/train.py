"""End-to-end training driver (CPU-scale here; same step as the dry-run).

Wires together every substrate: ParaGrapher/CompBin/PG-Fuse data loading,
the model zoo, AdamW(+ZeRO specs on a real mesh), async checkpointing with
restart-from-latest, straggler monitoring, and optional error-feedback
gradient compression on the data axis (shard_map path).

    python -m repro.launch.train --arch smollm-360m --steps 50 --reduced
    python -m repro.launch.train --arch gcn-cora --steps 100 --reduced
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.distributed.fault_tolerance import ResilientTrainer, StragglerMonitor
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         ef_compress_psum, ef_state_init)

log = logging.getLogger("repro.train")


# ---------------------------------------------------------------------------
# data generators (reduced-scale synthetic; real runs pass shard paths)
# ---------------------------------------------------------------------------

def _lm_batches(cfg, batch: int, seq: int, tmpdir: str, use_pgfuse: bool):
    """Token batches from a CompBin-packed shard through PG-Fuse."""
    from repro.data import PrefetchIterator, TokenShardReader, write_token_shard
    path = os.path.join(tmpdir, "tokens.ctok")
    if not os.path.exists(path):
        rng = np.random.default_rng(0)
        write_token_shard(path, rng.integers(0, cfg.vocab, 200_000), cfg.vocab)
    reader = TokenShardReader(path, use_pgfuse=use_pgfuse,
                              pgfuse_block_size=1 << 16)
    raw = reader.batches(batch, seq, seed=0)
    return PrefetchIterator(
        ({"tokens": jnp.asarray(b[:, :-1]), "labels": jnp.asarray(b[:, 1:])}
         for b in raw), depth=2)


def _gnn_batches(arch_id: str, cfg, tmpdir: str, use_pgfuse: bool):
    """Minibatch sampling through the ParaGrapher API over CompBin."""
    from repro.core import paragrapher
    from repro.graph import NeighborSampler, rmat
    from repro.launch.data_gnn import block_to_batch

    path = os.path.join(tmpdir, "graph.cbin")
    csr = rmat(10, 8, seed=1)
    if not os.path.exists(path):
        paragrapher.save_graph(path, csr, format="compbin")
    g = paragrapher.open_graph(path, use_pgfuse=use_pgfuse,
                               pgfuse_block_size=1 << 16)
    sampler = NeighborSampler(g, fanouts=(5, 5), seed=0)
    rng = np.random.default_rng(0)

    def gen():
        while True:
            block = sampler.sample(rng.integers(0, csr.n_vertices, 64))
            yield block_to_batch(arch_id, cfg, block, rng)

    return gen()


def _gnn_sampled_batches(arch_id: str, cfg, tmpdir: str, use_pgfuse: bool,
                         batch_seeds: int = 64, fanouts=(5, 5)):
    """``--sampled``: minibatch training through the random-access query
    engine.  Adjacency comes from :class:`repro.query.NeighborQueryEngine`
    (deduplicated, block-coalesced CompBin reads), features and seed
    labels from the two column-family stores on the SAME PG-Fuse mount —
    all three byte streams share one memory budget under the
    random-access policy (:func:`repro.core.policy.choose_access_mode`:
    readahead off, clock eviction, churn capped), and nothing in the
    batch is synthesized on the host.
    """
    from repro.core import featstore, paragrapher, policy
    from repro.graph import NeighborSampler
    from repro.launch.data_gnn import ensure_gnn_assets, sampled_store_batch
    from repro.query import NeighborQueryEngine

    d_in = getattr(cfg, "d_in", getattr(cfg, "d_node_in", 16))
    n_classes = getattr(cfg, "n_classes", 7)
    block_size = 1 << 16
    gp, fp, lp = ensure_gnn_assets(tmpdir, d_in, n_classes,
                                   block_size=block_size)
    amode = policy.choose_access_mode("sample")
    budget = 256 * block_size
    g = paragrapher.open_graph(
        gp, use_pgfuse=use_pgfuse, pgfuse_block_size=block_size,
        pgfuse_readahead=amode.readahead, pgfuse_eviction=amode.eviction,
        pgfuse_max_resident_bytes=budget if use_pgfuse else None)
    churn_cap = (int(amode.churn_budget_fraction * budget)
                 if amode.churn_budget_fraction else None)
    feats = featstore.open_featstore(fp, fs=g.fs,
                                     pgfuse_file_budget=churn_cap,
                                     pgfuse_file_readahead=0)
    labels = featstore.open_featstore(lp, fs=g.fs, pgfuse_file_readahead=0)
    # "auto" decode: each layer's frontier batch picks host vs device by
    # its exact edge mass (policy.choose_query_decode) — large sampler
    # fanouts ship ONE H2D of merged packed runs to the Pallas kernel
    engine = NeighborQueryEngine(g, decode="auto")
    sampler = NeighborSampler(engine, fanouts=fanouts, seed=0)
    rng = np.random.default_rng(0)
    n = g.n_vertices
    log.info("sampled mode: %s over %s (|V|=%d); %s", arch_id, gp, n,
             amode.reason)

    def gen():
        step = 0
        while True:
            block = sampler.sample(rng.integers(0, n, batch_seeds))
            yield sampled_store_batch(arch_id, cfg, block, feats, labels)
            step += 1
            if step % 50 == 0:
                st = engine.stats
                log.info("query engine after %d batches: dedup %.2fx, "
                         "%d blocks touched, p50 %.2f ms, %d device-"
                         "decoded (%.1f KiB H2D)",
                         st.batches, st.dedup_ratio, st.blocks_touched,
                         st.p50_s * 1e3, st.device_batches,
                         st.bytes_h2d / 1024)

    return gen()


def _gnn_full_graph_batches(arch_id: str, cfg, tmpdir: str, use_pgfuse: bool,
                            hosts: int):
    """Full-graph mode: storage -> PG-Fuse -> packed CompBin + FeatStore
    rows -> device decode -> :func:`streamed_graph_batch`, on ``hosts``
    simulated processes.  The whole graph becomes ONE device-resident
    batch; every step is a full-batch epoch (the classic Cora/ogbn
    regime).  Neighbor IDs, feature rows, AND the label/mask column
    family all come off storage through the same PG-Fuse mount — the
    batch carries zero synthetic tensors.
    """
    from repro.core import paragrapher, policy
    from repro.data.multihost import (aggregate_stats, all_shards,
                                      simulate_hosts)
    from repro.launch.data_gnn import ensure_gnn_assets, streamed_graph_batch

    block_size = 1 << 16
    d_in = getattr(cfg, "d_in", getattr(cfg, "d_node_in", 16))
    # the converters: real deployments convert their raw feature/label
    # dumps once; benchmark graphs get the deterministic synthesized ones
    path, feat_path, label_path = ensure_gnn_assets(
        tmpdir, d_in, getattr(cfg, "n_classes", 7), block_size=block_size)
    open_kwargs = dict(use_pgfuse=use_pgfuse, pgfuse_block_size=block_size,
                       pgfuse_readahead=2)
    with paragrapher.open_graph(path) as g:
        align = policy.choose_feature_align(block_size, d_in * 4,
                                            g.n_vertices, hosts)
    results = simulate_hosts(path, hosts, open_kwargs=open_kwargs,
                             feature_path=feat_path, label_path=label_path,
                             align=align)
    for r in results:
        st = r.stats
        log.info("host %d/%d: vertices [%d,%d) %d partitions %d edges "
                 "[%s decode] %.1f KiB H2D, %d cache hits, %d storage "
                 "reads, %.1f KiB features (hit rate %.2f)",
                 r.process_index, hosts, *r.host_range, st.partitions,
                 st.edges, st.decode_mode, st.bytes_h2d / 1024,
                 st.cache_hits, st.underlying_reads,
                 st.feature_bytes / 1024, st.feature_hit_rate)
    agg = aggregate_stats(results)
    log.info("streamed %d edges + %d feature rows (%.1f KiB) over %d "
             "host(s): %.1f KiB H2D total, %d host-decoded bytes",
             agg.edges, agg.feature_rows, agg.feature_bytes / 1024, hosts,
             (agg.bytes_h2d + agg.feature_bytes_h2d) / 1024,
             agg.host_decode_bytes)
    if agg.feature_rows != results[0].n_vertices:
        raise RuntimeError(
            f"feature stream incomplete: {agg.feature_rows} rows for "
            f"{results[0].n_vertices} vertices")
    batch = streamed_graph_batch(arch_id, cfg, all_shards(results),
                                 np.random.default_rng(0),
                                 n_classes=getattr(cfg, "n_classes", 7),
                                 n_vertices=results[0].n_vertices)

    def gen():
        while True:
            yield batch

    return gen()


def _din_batches(cfg, batch: int):
    rng = np.random.default_rng(0)
    while True:
        yield {
            "hist_items": jnp.asarray(rng.integers(-1, cfg.n_items, (batch, cfg.seq_len))),
            "hist_cates": jnp.asarray(rng.integers(0, cfg.n_cates, (batch, cfg.seq_len))),
            "cand_item": jnp.asarray(rng.integers(0, cfg.n_items, batch)),
            "cand_cate": jnp.asarray(rng.integers(0, cfg.n_cates, batch)),
            "labels": jnp.asarray(rng.integers(0, 2, batch).astype(np.float32)),
        }


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def _make_step(arch_id: str, cfg, opt_cfg: AdamWConfig, family: str,
               compress_grads: bool):
    if family == "lm":
        from repro.models import transformer as tf
        loss_fn = lambda p, b: tf.loss_fn(p, b["tokens"], b["labels"], cfg)
        init_fn = lambda key: tf.init_params(cfg, key)
    elif family == "gnn":
        from repro.launch.steps import _GNN_MODULES
        mod = _GNN_MODULES[arch_id]
        loss_fn = lambda p, b: mod.loss_fn(p, b, cfg)
        init_fn = lambda key: mod.init_params(cfg, key)
    else:
        from repro.models.recsys import din as m_din
        loss_fn = lambda p, b: m_din.loss_fn(p, b, cfg)
        init_fn = lambda key: m_din.init_params(cfg, key)

    if compress_grads:
        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        from jax.sharding import PartitionSpec as P

        def step(state, batch):
            def shard_step(state, batch):
                def loss_local(p):
                    return loss_fn(p, batch)
                l, g = jax.value_and_grad(loss_local)(state["params"])
                g, ef = ef_compress_psum(g, state["ef"], "data",
                                         axis_size=mesh.devices.size)
                l = jax.lax.pmean(l, "data")
                params, opt, met = adamw_update(state["params"], g,
                                                state["opt"], opt_cfg)
                return ({"params": params, "opt": opt, "ef": ef},
                        {**met, "loss": l})

            from repro.distributed.sharding import shard_map
            batch_spec = jax.tree.map(lambda _: P("data"), batch)
            return shard_map(
                shard_step, mesh=mesh,
                in_specs=(P(), batch_spec), out_specs=(P(), P()))(state, batch)

        return init_fn, jax.jit(step)

    def step(state, batch):
        l, g = jax.value_and_grad(lambda p: loss_fn(p, batch))(state["params"])
        params, opt, met = adamw_update(state["params"], g, state["opt"], opt_cfg)
        return {"params": params, "opt": opt}, {**met, "loss": l}

    return init_fn, jax.jit(step)


def main() -> None:
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--use-pgfuse", action="store_true", default=True)
    ap.add_argument("--full-graph", action="store_true",
                    help="GNN archs: train full-batch on the streamed "
                         "partition->device pipeline instead of sampled "
                         "minibatches")
    ap.add_argument("--sampled", action="store_true",
                    help="GNN archs: sampled minibatches drawn through "
                         "the random-access query engine (repro.query), "
                         "features+labels gathered from the column-family "
                         "stores on the shared PG-Fuse mount")
    ap.add_argument("--hosts", type=int, default=1,
                    help="simulated processes for --full-graph streaming "
                         "(data/multihost.py)")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    args = ap.parse_args()
    enable_compile_cache()

    os.makedirs(args.workdir, exist_ok=True)
    spec = get_arch(args.arch)
    cfg = spec.make_reduced() if args.reduced else spec.make_config()
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps,
                          master_f32=True)

    if spec.family == "lm":
        batches = _lm_batches(cfg, args.batch, args.seq, args.workdir,
                              args.use_pgfuse)
    elif spec.family == "gnn":
        if args.full_graph and args.sampled:
            ap.error("--full-graph and --sampled are mutually exclusive")
        if args.full_graph:
            batches = _gnn_full_graph_batches(args.arch, cfg, args.workdir,
                                              args.use_pgfuse, args.hosts)
        elif args.sampled:
            batches = _gnn_sampled_batches(args.arch, cfg, args.workdir,
                                           args.use_pgfuse)
        else:
            batches = _gnn_batches(args.arch, cfg, args.workdir,
                                   args.use_pgfuse)
    else:
        batches = _din_batches(cfg, args.batch)

    init_fn, step_fn = _make_step(args.arch, cfg, opt_cfg, spec.family,
                                  args.compress_grads)
    params = init_fn(jax.random.key(0))
    state = {"params": params, "opt": adamw_init(params, opt_cfg)}
    if args.compress_grads:
        state["ef"] = ef_state_init(params)

    ckpt_dir = args.ckpt_dir or os.path.join(args.workdir, f"ckpt_{args.arch}")
    trainer = ResilientTrainer(step_fn, state, ckpt_dir=ckpt_dir,
                               ckpt_every=args.ckpt_every)
    monitor = StragglerMonitor(n_hosts=1)
    losses = []

    def on_metrics(step, met):
        monitor.record(0, met["step_time_s"])
        losses.append(float(met["loss"]))
        if step % 10 == 0 or step == args.steps:
            log.info("step %d loss %.4f grad_norm %.3f lr %.2e (%.0f ms)",
                     step, float(met["loss"]), float(met["grad_norm"]),
                     float(met["lr"]), met["step_time_s"] * 1e3)

    trainer.run(batches, n_steps=args.steps, on_metrics=on_metrics,
                inject_failure_at=args.inject_failure_at)
    log.info("done: first-10 mean loss %.4f -> last-10 mean loss %.4f",
             float(np.mean(losses[:10])), float(np.mean(losses[-10:])))


if __name__ == "__main__":
    main()
