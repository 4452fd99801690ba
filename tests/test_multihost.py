"""Multi-host streamed loading (data/multihost.py simulator) and the
full storage -> PG-Fuse -> packed CompBin -> device decode -> train loop.

Tier-1 (fast) on purpose: the simulator is the only way the multi-host
path gets exercised without a real multi-process JAX cluster, so it must
run on every PR."""

import dataclasses
import os

import numpy as np
import pytest

from repro.core import compbin, paragrapher
from repro.data.graph_stream import (StreamStats, assemble_csr, merge_stats,
                                     stream_partitions)
from repro.data.multihost import aggregate_stats, all_shards, simulate_hosts
from repro.graph import rmat

OPEN_KW = dict(use_pgfuse=True, pgfuse_block_size=1 << 14,
               pgfuse_readahead=2)


@pytest.fixture(scope="module")
def graph_file(tmp_path_factory):
    d = tmp_path_factory.mktemp("mh")
    csr = rmat(9, 6, seed=3)
    p = str(d / "g.cbin")
    paragrapher.save_graph(p, csr, format="compbin")
    return p, csr


# ---------------------------------------------------------------------------
# the simulator: coverage, determinism, stats aggregation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hosts", [2, 3])
def test_hosts_cover_graph_disjointly_and_reassemble(graph_file, hosts):
    path, csr = graph_file
    results = simulate_hosts(path, hosts, open_kwargs=OPEN_KW, n_parts=8)
    assert [r.process_index for r in results] == list(range(hosts))
    # ranges: contiguous, disjoint, covering [0, |V|)
    cursor = 0
    for r in results:
        if not r.plan:
            continue
        assert r.host_range[0] == cursor
        cursor = r.host_range[1]
    assert cursor == csr.n_vertices
    # the union of every host's device shards is the whole graph, byte-exact
    assert assemble_csr(all_shards(results)) == csr


def test_multihost_zero_host_decode_for_compbin(graph_file):
    path, csr = graph_file
    before = compbin.host_decoded_bytes()
    results = simulate_hosts(path, 2, open_kwargs=OPEN_KW, n_parts=8)
    assert compbin.host_decoded_bytes() - before == 0
    for r in results:
        assert r.stats.decode_mode == "device"
        assert r.stats.host_decode_bytes == 0


def test_per_host_stats_sum_to_single_host_totals(graph_file):
    """The acceptance invariant: per-process StreamStats are reported per
    host and their merge reproduces the single-host totals — exactly for
    plan/shard/transfer counters, and exactly for total block
    acquisitions (hits + misses), which is a pure function of the reads
    issued no matter how they are split across private caches."""
    path, csr = graph_file
    results = simulate_hosts(path, 2, open_kwargs=OPEN_KW, n_parts=8)
    single = simulate_hosts(path, 1, open_kwargs=OPEN_KW, n_parts=8)[0]
    agg = aggregate_stats(results)
    one = single.stats

    for r in results:  # reported per process, each with real traffic
        assert r.stats.partitions > 0
        assert r.stats.bytes_h2d > 0
        assert r.stats.cache_hits + r.stats.cache_misses > 0
    assert agg.partitions == one.partitions > 1
    assert agg.vertices == one.vertices == csr.n_vertices
    assert agg.edges == one.edges == csr.n_edges
    assert agg.bytes_h2d == one.bytes_h2d
    assert agg.host_decode_bytes == one.host_decode_bytes == 0
    assert (agg.cache_hits + agg.cache_misses
            == one.cache_hits + one.cache_misses)


def test_host_decode_stats_are_per_stream_under_concurrency(graph_file):
    """Forced host decode on concurrent simulated hosts: each host's
    host_decode_bytes must count only ITS packed bytes (a process-global
    counter delta would cross-contaminate overlapping hosts) and sum
    exactly to the single-host total (= n_edges * bytes_per_id)."""
    from repro.core import policy

    path, csr = graph_file
    plan = policy.StreamDecodePlan("host", "test: force host decode")
    results = simulate_hosts(path, 2, open_kwargs=OPEN_KW, n_parts=8,
                             decode_plan=plan)
    single = simulate_hosts(path, 1, open_kwargs=OPEN_KW, n_parts=8,
                            decode_plan=plan)[0]
    with paragrapher.open_graph(path) as g:
        b = g.bytes_per_id
    for r in results:
        assert r.stats.host_decode_bytes == r.stats.edges * b
    agg = aggregate_stats(results)
    assert agg.host_decode_bytes == single.stats.host_decode_bytes \
        == csr.n_edges * b


def test_sequential_equals_concurrent_simulation(graph_file):
    path, csr = graph_file
    conc = simulate_hosts(path, 2, open_kwargs=OPEN_KW, n_parts=8)
    seq = simulate_hosts(path, 2, open_kwargs=OPEN_KW, n_parts=8,
                         concurrent=False)
    for a, b in zip(conc, seq):
        assert a.plan == b.plan
        assert a.host_range == b.host_range
        assert assemble_csr(a.shards) == assemble_csr(b.shards)
        assert a.stats.bytes_h2d == b.stats.bytes_h2d


def test_more_hosts_than_partitions(graph_file):
    path, csr = graph_file
    results = simulate_hosts(path, 5, open_kwargs=OPEN_KW, n_parts=3)
    assert assemble_csr(all_shards(results)) == csr
    empty = [r for r in results if not r.plan]
    for r in empty:  # hosts with nothing to stream report quietly
        assert r.shards == []
        assert r.stats.partitions == 0
        assert r.stats.decode_edges_per_s == 0.0


def test_stream_process_args_validated(graph_file):
    path, _ = graph_file
    with paragrapher.open_graph(path) as g:
        with pytest.raises(ValueError):
            stream_partitions(g, None, process_index=2, process_count=2)
    with pytest.raises(ValueError):
        simulate_hosts(path, 0)


# ---------------------------------------------------------------------------
# StreamStats: zero-duration guards + associative merge
# ---------------------------------------------------------------------------

def test_stream_stats_zero_duration_guards():
    s = StreamStats(edges=1000, bytes_h2d=4096, decode_s=0.0, wall_s=0.0)
    assert s.decode_edges_per_s == 0.0
    assert s.h2d_bytes_per_s == 0.0
    assert s.edges_per_s == 0.0
    d = s.as_dict()
    assert d["decode_edges_per_s"] == 0.0 and d["h2d_bytes_per_s"] == 0.0
    live = StreamStats(edges=1000, decode_s=0.5, wall_s=2.0, bytes_h2d=4096,
                       h2d_s=0.25)
    assert live.decode_edges_per_s == 2000.0
    # the link rate: bytes over the seconds spent transferring them
    assert live.h2d_bytes_per_s == 16384.0


def test_stream_stats_merge_associative_and_commutative_totals():
    from tests._prop import Draw, prop

    @prop(n_cases=50)
    def check(draw: Draw):
        def rand_stats():
            # durations drawn as multiples of 1/4 so float addition is
            # exact and associativity can be asserted with ==
            return StreamStats(
                partitions=draw.int(0, 5), vertices=draw.int(0, 100),
                edges=draw.int(0, 1000), cache_hits=draw.int(0, 50),
                cache_misses=draw.int(0, 50), bytes_h2d=draw.int(0, 4096),
                underlying_reads=draw.int(0, 9),
                underlying_bytes=draw.int(0, 1 << 16),
                readahead_blocks=draw.int(0, 9),
                host_decode_bytes=draw.int(0, 512),
                decode_s=draw.int(0, 8) / 4, wall_s=draw.int(0, 8) / 4,
                decode_mode=draw.choice(["device", "host"]))

        a, b, c = rand_stats(), rand_stats(), rand_stats()
        assert a.merge(b).merge(c) == a.merge(b.merge(c))
        # totals are order-independent even where tie-break strings differ
        x, y = a.merge(b), b.merge(a)
        assert dataclasses.replace(x, decode_mode="", decode_reason="") == \
            dataclasses.replace(y, decode_mode="", decode_reason="")

    check()


def test_merge_stats_fold_and_mode_collapse():
    dev = StreamStats(edges=5, decode_mode="device", wall_s=1.0)
    host = StreamStats(edges=7, decode_mode="host", wall_s=3.0)
    m = merge_stats([dev, host])
    assert m.edges == 12
    assert m.decode_mode == "mixed"
    assert m.wall_s == 3.0          # hosts run concurrently: max, not sum
    assert merge_stats([dev]).decode_mode == "device"
    assert merge_stats([]) == StreamStats()


# ---------------------------------------------------------------------------
# the acceptance test: end-to-end gcn-cora full-graph training from
# CompBin through the streamed path on a simulated 2-host mesh
# ---------------------------------------------------------------------------

def test_e2e_gcn_cora_full_graph_train_from_compbin_two_hosts(graph_file):
    import jax
    from jax.sharding import Mesh

    from repro.launch.data_gnn import streamed_graph_batch
    from repro.models.gnn import gcn
    from repro.optim import AdamWConfig, adamw_init, adamw_update

    path, csr = graph_file
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))

    before = compbin.host_decoded_bytes()
    results = simulate_hosts(path, 2, mesh, open_kwargs=OPEN_KW, n_parts=8)
    single = simulate_hosts(path, 1, mesh, open_kwargs=OPEN_KW, n_parts=8)[0]

    # per-host stats reported per process and summing to single-host totals
    agg = aggregate_stats(results)
    for r in results:
        assert r.stats.bytes_h2d > 0
        assert r.stats.cache_hits + r.stats.cache_misses > 0
    assert agg.bytes_h2d == single.stats.bytes_h2d
    assert (agg.cache_hits + agg.cache_misses
            == single.stats.cache_hits + single.stats.cache_misses)
    assert agg.edges == single.stats.edges == csr.n_edges
    assert compbin.host_decoded_bytes() - before == 0  # all device decode

    # the streamed device shards become the full-graph training batch
    shards = all_shards(results)
    for s in shards:
        assert isinstance(s.neighbors, jax.Array)
    cfg = gcn.GCNConfig(n_layers=2, d_hidden=16, d_in=16, n_classes=7)
    assert results[0].n_vertices == csr.n_vertices
    batch = streamed_graph_batch("gcn-cora", cfg, shards,
                                 np.random.default_rng(0),
                                 n_classes=cfg.n_classes,
                                 n_vertices=results[0].n_vertices)
    assert int(batch["x"].shape[0]) == csr.n_vertices
    assert int(batch["edge_src"].shape[0]) == csr.n_edges

    params = gcn.init_params(cfg, jax.random.key(0))
    opt_cfg = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=15)
    opt = adamw_init(params, opt_cfg)

    @jax.jit
    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(gcn.loss_fn)(params, batch, cfg)
        params, opt, _ = adamw_update(params, grads, opt, opt_cfg)
        return params, opt, loss

    losses = []
    for _ in range(15):
        params, opt, loss = step(params, opt, batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses  # full-batch training converges


def test_e2e_missing_host_shards_fail_loudly(graph_file):
    """Full-graph training on HALF the hosts' shards must raise, not
    silently train on a truncated graph."""
    from repro.launch.data_gnn import streamed_graph_batch
    from repro.models.gnn import gcn

    path, csr = graph_file
    results = simulate_hosts(path, 2, open_kwargs=OPEN_KW, n_parts=8)
    cfg = gcn.GCNConfig(n_layers=2, d_hidden=16, d_in=16, n_classes=7)
    with pytest.raises(ValueError, match="every host"):
        # interior/leading gap: host 0's shards missing
        streamed_graph_batch("gcn-cora", cfg, results[1].shards,
                             np.random.default_rng(0))
    with pytest.raises(ValueError, match="every host"):
        # trailing gap: host 1's shards missing — only detectable against
        # the graph's true vertex count
        streamed_graph_batch("gcn-cora", cfg, results[0].shards,
                             np.random.default_rng(0),
                             n_vertices=results[0].n_vertices)
