"""Storage-fault injection over PG-Fuse (tests/conftest.py FaultyStorage):
transient EIO, short reads, and latency must surface deterministically —
never hang a reader, never hand truncated bytes downstream — and the
readahead path must keep running through injected latency."""

import errno
import os

import numpy as np
import pytest

from repro.core import paragrapher, pgfuse
from repro.data.graph_stream import assemble_csr, stream_partitions
from repro.graph import erdos_renyi


BLOCK = 1024


@pytest.fixture
def data_file(tmp_path):
    rng = np.random.default_rng(7)
    payload = rng.integers(0, 256, 4 * BLOCK, dtype=np.uint8).tobytes()
    p = str(tmp_path / "blob.bin")
    with open(p, "wb") as f:
        f.write(payload)
    return p, payload


@pytest.fixture
def graph_file(tmp_path):
    csr = erdos_renyi(1 << 9, 1 << 13, seed=11)
    p = str(tmp_path / "g.cbin")
    paragrapher.save_graph(p, csr, format="compbin")
    return p, csr


def test_transient_eio_surfaces_then_recovers(data_file, faulty_storage):
    path, payload = data_file
    cf = pgfuse.CachedFile(path, block_size=BLOCK)
    try:
        faulty_storage.fail_at[1] = OSError(errno.EIO, "flaky OST")
        faulty_storage.install(cf)
        with pytest.raises(OSError) as exc:
            cf.pread(0, len(payload))
        assert exc.value.errno == errno.EIO
        # transient: the claim reverted (-2 -> -1), so the retry reloads
        # the same blocks and succeeds with byte-exact data
        assert cf.pread(0, len(payload)) == payload
    finally:
        cf.close()


def test_short_read_of_requested_block_raises_not_hangs(data_file,
                                                        faulty_storage):
    path, payload = data_file
    cf = pgfuse.CachedFile(path, block_size=BLOCK)
    try:
        faulty_storage.truncate_at[1] = 100  # < one block
        faulty_storage.install(cf)
        # must raise (silent truncation would corrupt every future reader;
        # installing the stub would spin pread forever on a 0-byte take)
        with pytest.raises(IOError, match="short read"):
            cf.pread(0, len(payload))
        assert cf.pread(0, len(payload)) == payload  # fault was transient
    finally:
        cf.close()


def test_short_read_drops_readahead_blocks_only(data_file, faulty_storage):
    path, payload = data_file
    cf = pgfuse.CachedFile(path, block_size=BLOCK, readahead=3)
    try:
        # call 1 claims blocks 0..3 in ONE enlarged request but storage
        # returns just block 0: the requested block installs, the three
        # readahead claims revert silently (paper: readahead is advisory)
        faulty_storage.truncate_at[1] = BLOCK
        faulty_storage.install(cf)
        assert cf.pread(0, len(payload)) == payload
        assert faulty_storage.n_calls == 2  # blocks 1..3 refetched as a run
        assert cf.stats.readahead_blocks == 2  # call 2: b=1 + ahead {2,3}
    finally:
        cf.close()


def test_async_read_surfaces_storage_error(graph_file, faulty_storage):
    path, csr = graph_file
    with paragrapher.open_graph(path, use_pgfuse=True,
                                pgfuse_block_size=BLOCK) as g:
        plan = g.partition_plan(4)
        faulty_storage.fail_at[1] = OSError(errno.EIO, "flaky OST")
        faulty_storage.install_graph(g)
        got = []
        ar = g.read_async(plan, lambda buf: got.append(buf.error),
                          n_workers=1)
        with pytest.raises(OSError):
            ar.wait(timeout=30)  # surfaces the EIO, does NOT time out
        assert ar.done
        assert any(isinstance(e, OSError) for e in got)


def test_stream_surfaces_storage_error_not_hang(graph_file, faulty_storage):
    path, csr = graph_file
    with paragrapher.open_graph(path, use_pgfuse=True,
                                pgfuse_block_size=BLOCK) as g:
        # this plan loads every block of the offsets array before the
        # fault is armed; the stream's plan pins those blocks again and
        # probes a few offsets in them, so it calls storage for nothing
        # and the first call storage sees is a producer's (arming the
        # fault after the stream starts raced them)
        g.partition_plan(4)
        faulty_storage.fail_at[1] = OSError(errno.EIO, "flaky OST")
        faulty_storage.install_graph(g)
        stream = stream_partitions(g, None, n_parts=4, n_workers=1)
        assert stream.stats.plan_underlying_reads == 0
        with pytest.raises(OSError):
            with stream:
                list(stream)
        assert faulty_storage.calls[0][3] == -1


def test_stream_recovers_after_transient_error(graph_file, faulty_storage):
    path, csr = graph_file
    with paragrapher.open_graph(path, use_pgfuse=True,
                                pgfuse_block_size=BLOCK) as g:
        faulty_storage.fail_at[1] = OSError(errno.EIO, "flaky OST")
        faulty_storage.install_graph(g)
        with pytest.raises(OSError):
            with stream_partitions(g, None, n_parts=4, n_workers=1) as s:
                list(s)
        # the fault was transient and all block claims reverted: a fresh
        # stream over the SAME handle reassembles the graph byte-exactly
        with stream_partitions(g, None, n_parts=4) as stream:
            assert assemble_csr(list(stream)) == csr


def test_retry_policy_absorbs_transient_eio(data_file, faulty_storage):
    """With retries=N a transient EIO never reaches the consumer: the
    bounded-retry wrapper goes back to storage (deterministic backoff)
    and the SAME pread succeeds.  The retry sits above the underlying-
    read funnel, so the injected fault exercises the real policy."""
    path, payload = data_file
    cf = pgfuse.CachedFile(path, block_size=BLOCK, retries=2,
                           retry_backoff_s=1e-4)
    try:
        faulty_storage.fail_at[1] = OSError(errno.EIO, "flaky OST")
        faulty_storage.install(cf)
        assert cf.pread(0, len(payload)) == payload  # no exception escapes
        assert cf.stats.retried_reads == 1
        assert faulty_storage.n_calls >= 2  # the retry really hit storage
    finally:
        cf.close()


def test_retry_policy_is_bounded(data_file, faulty_storage):
    """More consecutive EIOs than retries= allows must surface — a dead
    OST is not a transient fault, and unbounded retry would hang the
    loader instead of failing it over."""
    path, payload = data_file
    cf = pgfuse.CachedFile(path, block_size=BLOCK, retries=1,
                           retry_backoff_s=1e-4)
    try:
        for i in (1, 2):  # first attempt AND its one retry both fail
            faulty_storage.fail_at[i] = OSError(errno.EIO, "dead OST")
        faulty_storage.install(cf)
        with pytest.raises(OSError) as exc:
            cf.pread(0, len(payload))
        assert exc.value.errno == errno.EIO
        assert cf.stats.retried_reads == 1  # exactly one retry was spent
        # claims reverted through the state machine: a later read works
        assert cf.pread(0, len(payload)) == payload
    finally:
        cf.close()


def test_retry_policy_through_graph_stream(graph_file, faulty_storage):
    """End to end: a streamed load over a retrying mount survives an
    injected transient EIO that would otherwise kill the stream."""
    path, csr = graph_file
    with paragrapher.open_graph(path, use_pgfuse=True,
                                pgfuse_block_size=BLOCK,
                                pgfuse_retries=2,
                                pgfuse_retry_backoff_s=1e-4) as g:
        faulty_storage.fail_at[1] = OSError(errno.EIO, "flaky OST")
        faulty_storage.install_graph(g)
        with stream_partitions(g, None, n_parts=4) as stream:
            assert assemble_csr(list(stream)) == csr
        assert g.pgfuse_stats().retried_reads == 1


def test_retry_does_not_mask_short_reads(data_file, faulty_storage):
    """Short reads are NOT retried by the policy (they surface through
    the strict short-read path): retrying would re-read a block the
    filesystem claims is shorter than the header says, hiding
    truncation behind latency."""
    path, payload = data_file
    cf = pgfuse.CachedFile(path, block_size=BLOCK, retries=3,
                           retry_backoff_s=1e-4)
    try:
        faulty_storage.truncate_at[1] = 100
        faulty_storage.install(cf)
        with pytest.raises(IOError, match="short read"):
            cf.pread(0, len(payload))
        assert cf.stats.retried_reads == 0
    finally:
        cf.close()


def test_readahead_runs_through_injected_latency(graph_file):
    """Under a per-request latency floor the readahead path must stay
    active (enlarged multi-block fetches) and cut underlying requests."""
    from tests.conftest import FaultyStorage

    path, csr = graph_file
    calls = {}
    for ra in (0, 4):
        with paragrapher.open_graph(path, use_pgfuse=True,
                                    pgfuse_block_size=BLOCK,
                                    pgfuse_readahead=ra) as g:
            fs = FaultyStorage(latency_s=5e-4)
            fs.install_graph(g)
            with stream_partitions(g, None, n_parts=4) as stream:
                assert assemble_csr(list(stream)) == csr
            calls[ra] = fs.n_calls
            if ra:
                assert stream.stats.readahead_blocks > 0
    assert calls[4] < calls[0], calls
