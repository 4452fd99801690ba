"""Spans and stage counters inside the streamed load
(data/graph_stream.py): one ``stream.load`` tree per stream with one
read/pad/h2d/ready span per partition, stage timers that add up to
``decode_s``, the plan's storage reads counted apart, the same shards
under every tracer, and the spans on the profiler's clock."""

import math

import numpy as np
import pytest

from repro.core import compbin, paragrapher
from repro.data.graph_stream import (StreamStats, assemble_csr,
                                     stream_partitions)
from repro.graph import rmat
from repro.obs import PROFILER_TRACER, Tracer, verify_span_tree

STAGES = ("stream.read", "stream.pad", "stream.h2d", "stream.ready")
NEW_FIELDS = ("plan_s", "plan_underlying_reads", "plan_underlying_bytes",
              "plan_bytes_served", "read_s", "handoff_wait_s", "stage_wait_s", "pad_s",
              "pad_bytes", "h2d_s", "ready_s")


@pytest.fixture(scope="module")
def graph_file(tmp_path_factory):
    csr = rmat(12, 8, seed=7)
    p = str(tmp_path_factory.mktemp("st") / "g.cbin")
    paragrapher.save_graph(p, csr, format="compbin")
    return p, csr


def _mount(path, block_size=1 << 14, cap=None):
    """The graph behind a fresh PG-Fuse mount, its file capped at
    ``cap`` bytes when given."""
    g = paragrapher.open_graph(path, use_pgfuse=True,
                               pgfuse_block_size=block_size,
                               pgfuse_readahead=1)
    if cap is not None:
        g.fs.set_file_budget(g.path, cap)
    return g


def _load(path, tracer=None, n_parts=5, block_size=1 << 14, cap=None):
    """One streamed load behind a fresh PG-Fuse mount: (shards on the
    host in vertex order, stats, plan, device shards)."""
    with _mount(path, block_size, cap) as g:
        with stream_partitions(g, None, n_parts=n_parts,
                               tracer=tracer) as stream:
            shards = list(stream)
        host = sorted((s.v0, s.v1, np.asarray(s.offsets),
                       np.asarray(s.neighbors)) for s in shards)
        return host, stream.stats, stream.plan, shards


def test_one_load_tree_per_stream_with_every_stage_per_partition(graph_file):
    path, _ = graph_file
    tracer = Tracer(max_traces=64)
    plans = [_load(path, tracer)[2] for _ in range(2)]
    roots = tracer.drain()
    assert [r.name for r in roots] == ["stream.load"] * 2
    for root, plan in zip(roots, plans):
        assert root.tier == "load" and root.parent_id is None
        assert verify_span_tree(root) == []
        spans = list(root.iter_spans())[1:]
        for name in STAGES:
            parts = sorted(s.attrs["part"] for s in spans if s.name == name)
            assert parts == list(range(len(plan))), name
        plan_spans = [s for s in spans if s.name == "stream.plan"]
        assert len(plan_spans) == 1 and plan_spans[0].tier == "storage"
        # the stream handed its tracer to its own storage reads: they
        # nest under the plan and under the producers' reads
        for parent in ("stream.plan", "stream.read"):
            assert any(c.name == "pgfuse.read" for s in spans
                       if s.name == parent for c in s.children), parent
        tiers = {s.name: s.tier for s in spans}
        assert tiers["stream.pad"] == "stream"
        assert tiers["stream.h2d"] == "h2d"
        assert tiers["stream.ready"] == "decode"


def test_a_callers_open_span_does_not_take_the_load_root(graph_file):
    """The stream's root lives off the caller's span stack: a caller's
    span closed while the stream is open trips no ordering check."""
    path, _ = graph_file
    tracer = Tracer()
    with paragrapher.open_graph(path, use_pgfuse=True) as g:
        with tracer.span("caller", tier="request"):
            stream = stream_partitions(g, None, n_parts=3, tracer=tracer)
        shards = list(stream)
        stream.close()
    assert len(shards) == len(stream.plan)
    names = sorted(r.name for r in tracer.drain())
    assert names == ["caller", "stream.load"]


def test_stage_timers_add_up_to_decode_s(graph_file):
    path, _ = graph_file
    _, st, plan, _ = _load(path, Tracer())
    assert st.pad_s > 0 and st.h2d_s > 0 and st.ready_s > 0
    assert st.decode_s == pytest.approx(st.pad_s + st.h2d_s + st.ready_s,
                                        abs=1e-3)
    assert st.read_s > 0 and st.plan_s > 0
    assert st.stage_wait_s >= 0 and st.handoff_wait_s >= 0
    assert st.pad_bytes >= 0
    assert st.h2d_bytes_per_s == pytest.approx(st.bytes_h2d / st.h2d_s)


def test_plan_reads_counted_apart_and_the_same_with_any_tracer(graph_file):
    path, _ = graph_file
    _, plain, _, _ = _load(path)
    _, traced, _, _ = _load(path, Tracer())
    assert plain.plan_underlying_reads > 0
    assert plain.plan_underlying_bytes > 0
    assert traced.plan_underlying_reads == plain.plan_underlying_reads
    assert traced.underlying_reads == plain.underlying_reads > 0
    assert traced.underlying_bytes == plain.underlying_bytes


def _whole_array_read(path, block_size, cap=None):
    """The graph file's PG-Fuse storage calls and bytes for reading its
    offsets array whole on a fresh mount (the plan's old way)."""
    with _mount(path, block_size, cap) as g:
        before = g.pgfuse_file_stats()
        with compbin.CompBinFile(g.fs.open(path)) as rdr:
            rdr.offsets()
        after = g.pgfuse_file_stats()
    return (after.underlying_reads - before.underlying_reads,
            after.underlying_bytes - before.underlying_bytes)


@pytest.mark.parametrize("block_size", [1 << 14, 1 << 10])
def test_plan_reads_only_the_offsets_it_probes(graph_file, block_size):
    """The plan bisects the offsets in place: it reads the header, the
    last offset and at most ``ceil(log2(V + 2))`` offsets of 8 bytes a
    cut, while storage sees the calls and bytes a whole-array read of
    the offsets makes on the same file and block size (the offsets span
    3 blocks of 16 KiB, or 33 of 1 KiB)."""
    path, csr = graph_file
    n_parts, n_v = 5, csr.n_vertices
    _, st, _, _ = _load(path, n_parts=n_parts, block_size=block_size)
    bound = compbin.HEADER_SIZE + 8 * (
        1 + n_parts * math.ceil(math.log2(n_v + 2)))
    assert 0 < st.plan_bytes_served <= bound
    assert st.plan_bytes_served * 50 < 8 * (n_v + 1)  # the whole array
    calls, nbytes = _whole_array_read(path, block_size)
    assert st.plan_underlying_reads == calls > 0
    assert st.plan_underlying_bytes == nbytes
    with paragrapher.open_graph(path) as g:
        with stream_partitions(g, None, n_parts=n_parts) as stream:
            list(stream)
    assert stream.stats.plan_bytes_served == 0  # not mounted


def test_a_cap_with_no_room_for_the_offsets_reads_them_whole(graph_file):
    """Under a file cap that cannot hold the offsets' 33 blocks and a
    readahead run, pinned blocks would crowd out the ones not yet
    pinned (24 calls where a whole-array read makes 16): the plan reads
    the array whole and makes the same calls."""
    path, csr = graph_file
    block, cap = 1 << 10, 16 << 10
    _, st, plan, _ = _load(path, block_size=block, cap=cap)
    assert st.plan_bytes_served >= 8 * (csr.n_vertices + 1)
    calls, nbytes = _whole_array_read(path, block, cap)
    assert (st.plan_underlying_reads, st.plan_underlying_bytes) == \
        (calls, nbytes)
    _, uncapped, uncapped_plan, _ = _load(path, block_size=block)
    assert plan == uncapped_plan


def test_shards_are_byte_equal_under_every_tracer(graph_file):
    path, csr = graph_file
    loads = [_load(path, t) for t in
             (None, PROFILER_TRACER, Tracer())]
    ref = loads[0][0]
    assert assemble_csr(loads[0][3]) == csr
    for host, _, _, _ in loads[1:]:
        assert len(host) == len(ref)
        for (a0, a1, ao, an), (b0, b1, bo, bn) in zip(ref, host):
            assert (a0, a1) == (b0, b1)
            assert ao.tobytes() == bo.tobytes()
            assert an.dtype == bn.dtype and an.tobytes() == bn.tobytes()


def test_two_streams_with_two_tracers_on_one_graph(graph_file):
    """Each stream's storage reads go to its own tracer, and the shared
    mount keeps no tracer of either, so later readers of the mount are
    not traced by them."""
    path, csr = graph_file
    tracers = [Tracer(), Tracer()]
    # a cap of two blocks evicts between the streams, so the second one
    # reads from storage again
    with paragrapher.open_graph(path, use_pgfuse=True,
                                pgfuse_block_size=1 << 14,
                                pgfuse_max_resident_bytes=2 << 14,
                                pgfuse_readahead=0) as g:
        for tracer in tracers:
            with stream_partitions(g, None, n_parts=4, n_workers=1,
                                   tracer=tracer) as stream:
                assert assemble_csr(list(stream)) == csr
            assert g.fs.tracer is None
            assert g.fs.mount(g.path).tracer is None
    for tracer in tracers:
        (root,) = tracer.drain()
        assert verify_span_tree(root) == []
        parents = [s.name for s in root.iter_spans() for c in s.children
                   if c.name == "pgfuse.read"]
        assert "stream.plan" in parents and "stream.read" in parents
        assert set(parents) == {"stream.plan", "stream.read"}


def test_merge_sums_the_stage_counters():
    a = StreamStats(**{f: 1 for f in NEW_FIELDS}, wall_s=2.0)
    b = StreamStats(**{f: 3 for f in NEW_FIELDS}, wall_s=1.0)
    m = a.merge(b)
    assert all(getattr(m, f) == 4 for f in NEW_FIELDS)
    assert m.wall_s == 2.0


def test_unsampled_load_roots_suppress_their_whole_tree(graph_file):
    """``sample_every=2`` keeps the first load's tree and drops every
    span of the second, on every thread."""
    path, _ = graph_file
    tracer = Tracer(sample_every=2)
    for _ in range(2):
        _load(path, tracer)
    roots = tracer.drain()
    assert [r.name for r in roots] == ["stream.load"]


def test_the_profiler_tracer_keeps_nothing():
    with PROFILER_TRACER.span("stream.pad", tier="stream", part=1) as sp:
        sp.set(x=1)
        sp.event("e")
    root = PROFILER_TRACER.open_root("stream.load", tier="load")
    with PROFILER_TRACER.attach(root):
        PROFILER_TRACER.event("e")
    PROFILER_TRACER.close_root(root)
    assert root is None and PROFILER_TRACER.current is None
    assert PROFILER_TRACER.drain() == [] and PROFILER_TRACER.traces == ()


@pytest.mark.parametrize("explicit", [False, True],
                         ids=["default", "explicit"])
def test_spans_reach_the_profiler_inside_the_window(graph_file, tmp_path,
                                                    explicit):
    """With the default tracer, or ``PROFILER_TRACER`` passed by name,
    the stream's spans land in the profiler's own trace, inside the
    window annotation, with no clock offset."""
    import jax

    from chipbench import trace_reduce

    path, _ = graph_file
    tracer = PROFILER_TRACER if explicit else None
    _load(path)      # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            _load(path, tracer)
    finally:
        jax.profiler.stop_trace()
    red = trace_reduce.reduce_dir(str(tmp_path))
    inside = {name for s, e, name in red.host_spans
              if red.t0 <= s and e <= red.t1}
    for name in ("stream.plan", "stream.read", "stream.pad", "stream.h2d",
                 "stream.ready", "pgfuse.read"):
        assert name in inside, name


def test_producer_counters_lose_no_update_under_contention(graph_file):
    """Eight producers over 32 partitions, a one-slot raw queue and a
    tiny switch interval: every read is counted once, in its span and in
    ``read_s``, which brackets each span."""
    import sys

    path, csr = graph_file
    tracer = Tracer()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with paragrapher.open_graph(path, use_pgfuse=True,
                                    pgfuse_block_size=1 << 12) as g:
            with stream_partitions(g, None, n_parts=32, n_workers=8,
                                   n_buffers=8, readahead=1,
                                   tracer=tracer) as stream:
                out = assemble_csr(list(stream))
    finally:
        sys.setswitchinterval(old)
    assert out == csr
    (root,) = tracer.drain()
    reads = [s for s in root.iter_spans() if s.name == "stream.read"]
    assert sorted(s.attrs["part"] for s in reads) == \
        list(range(len(stream.plan)))
    st = stream.stats
    assert st.read_s >= sum(s.duration_s for s in reads)
    handoffs = [s for s in root.iter_spans() if s.name == "stream.handoff"]
    assert st.handoff_wait_s >= sum(s.duration_s for s in handoffs)


def test_the_storage_layer_runs_its_spans_without_jax(graph_file):
    """ParaGrapher's reads default to the profiler tracer, which imports
    jax only once something else has: a storage-only process reads
    through their spans without loading jax."""
    import os
    import subprocess
    import sys

    path, _ = graph_file
    code = (
        "import sys\n"
        "from repro.core import paragrapher\n"
        f"g = paragrapher.open_graph({path!r}, use_pgfuse=True)\n"
        "plan, got = g.partition_plan(4), []\n"
        "g.read_async(plan, lambda b: got.append(b.error)).wait(60)\n"
        "g.close()\n"
        "assert got == [None] * len(plan)\n"
        "assert 'jax' not in sys.modules\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=src))
    assert r.returncode == 0, r.stderr[-2000:]
