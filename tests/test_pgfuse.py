"""PG-Fuse (paper §III): byte-correct caching, state machine, eviction."""

import os
import threading

import numpy as np
import pytest

from repro.core import pgfuse
from tests._prop import prop


@pytest.fixture
def datafile(tmp_path):
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    p = tmp_path / "blob.bin"
    p.write_bytes(data)
    return str(p), data


def test_basic_reads_and_hits(datafile):
    path, data = datafile
    fs = pgfuse.PGFuseFS(block_size=4096)
    cf = fs.mount(path)
    assert cf.pread(0, 100) == data[:100]
    assert cf.pread(50, 100) == data[50:150]          # same block -> hit
    assert cf.pread(len(data) - 10, 100) == data[-10:]  # clipped at EOF
    st = fs.stats()
    assert st.cache_hits >= 1
    assert st.underlying_bytes >= 4096  # large-granularity request
    fs.unmount()


@prop(10)
def test_random_read_schedule_byte_identical(draw):
    import tempfile
    data = draw.rng.integers(0, 256, draw.int(1, 100_000), dtype=np.uint8).tobytes()
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "f.bin")
        with open(p, "wb") as f:
            f.write(data)
        bs = draw.choice([1, 7, 512, 4096, 1 << 16])
        budget = draw.choice([None, 8 * bs])
        with pgfuse.PGFuseFS(block_size=bs, max_resident_bytes=budget) as fs:
            cf = fs.mount(p)
            for _ in range(30):
                off = draw.int(0, max(0, len(data)))
                n = draw.int(0, 5000)
                assert cf.pread(off, n) == data[off:off + n], (off, n, bs)


def test_handle_interface(datafile):
    path, data = datafile
    with pgfuse.PGFuseFS(block_size=1024) as fs:
        h = fs.open(path)
        h.seek(1000)
        assert h.read(64) == data[1000:1064]
        assert h.tell() == 1064
        h.seek(-8, os.SEEK_END)
        assert h.read() == data[-8:]


def test_eviction_respects_budget_and_recency(datafile):
    path, data = datafile
    bs = 4096
    with pgfuse.PGFuseFS(block_size=bs, max_resident_bytes=3 * bs) as fs:
        cf = fs.mount(path)
        for b in range(8):
            cf.pread(b * bs, 10)
        assert fs.resident_bytes <= 3 * bs
        assert fs.stats().evictions >= 5
        # most recently used block should still be resident
        resident = set(cf.resident_blocks().tolist())
        assert 7 in resident


def test_state_machine_transitions(datafile):
    path, _ = datafile
    with pgfuse.PGFuseFS(block_size=4096) as fs:
        cf = fs.mount(path)
        st = cf._statuses
        assert st.load(0) == pgfuse.NOT_LOADED
        data = cf.acquire_block(0)
        assert st.load(0) == 1            # one pinned reader
        cf.acquire_block(0)
        assert st.load(0) == 2            # counter semantics
        cf.release_block(0)
        cf.release_block(0)
        assert st.load(0) == pgfuse.LOADED
        # pinned blocks cannot be revoked
        cf.acquire_block(0)
        assert cf.try_revoke(0) == 0
        cf.release_block(0)
        assert cf.try_revoke(0) > 0
        assert st.load(0) == pgfuse.NOT_LOADED


def test_concurrent_reader_stress(datafile):
    """Many threads, random reads, small cache: data must stay
    byte-identical and the status array must end fully idle."""
    path, data = datafile
    bs = 2048
    with pgfuse.PGFuseFS(block_size=bs, max_resident_bytes=4 * bs) as fs:
        cf = fs.mount(path)
        errors = []

        def worker(seed):
            rng = np.random.default_rng(seed)
            try:
                for _ in range(200):
                    off = int(rng.integers(0, len(data)))
                    n = int(rng.integers(1, 3 * bs))
                    if cf.pread(off, n) != data[off:off + n]:
                        errors.append((seed, off, n))
            except Exception as e:  # pragma: no cover
                errors.append((seed, repr(e)))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        snap = cf._statuses.snapshot()
        assert ((snap == pgfuse.LOADED) | (snap == pgfuse.NOT_LOADED)).all()


def test_eviction_vs_acquisition_stress(datafile):
    """Fig. 1 state machine under fire: N threads hammer pread over a tiny
    max_resident_bytes budget so eviction (0 -> -3 -> -1) races acquisition
    (-1 -> -2 -> 1) on every block.  Required invariants: no deadlock, no
    stale bytes served, statuses fully idle at the end, and the FS-level
    resident_bytes accounting agrees exactly with what is actually cached."""
    path, data = datafile
    bs = 1024
    n_threads = 12
    with pgfuse.PGFuseFS(block_size=bs, max_resident_bytes=2 * bs) as fs:
        cf = fs.mount(path)
        errors = []
        start = threading.Barrier(n_threads)

        def worker(seed):
            rng = np.random.default_rng(seed)
            start.wait()
            try:
                for _ in range(150):
                    off = int(rng.integers(0, len(data)))
                    n = int(rng.integers(1, 4 * bs))
                    got = cf.pread(off, n)
                    if got != data[off:off + n]:
                        errors.append(("stale", seed, off, n))
            except Exception as e:
                errors.append(("raised", seed, repr(e)))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads), "deadlocked workers"
        assert not errors, errors[:5]
        snap = cf._statuses.snapshot()
        assert ((snap == pgfuse.LOADED) | (snap == pgfuse.NOT_LOADED)).all()
        # accounting must agree with reality, not drift under races
        actual = sum(len(cf._blocks[b]) for b in cf.resident_blocks())
        assert fs.resident_bytes == actual
        assert fs.resident_bytes <= 2 * bs


def test_close_races_concurrent_readers(datafile):
    """close() must drain readers through status transitions, not free
    pinned blocks from under them (the seed freed unconditionally)."""
    path, data = datafile
    bs = 4096
    for _ in range(5):
        fs = pgfuse.PGFuseFS(block_size=bs)
        cf = fs.mount(path)
        errors = []
        stop = threading.Event()

        def reader(seed):
            rng = np.random.default_rng(seed)
            try:
                while not stop.is_set():
                    off = int(rng.integers(0, len(data) - 1))
                    n = int(rng.integers(1, 2 * bs))
                    got = cf.pread(off, n)
                    if got != data[off:off + min(n, len(data) - off)]:
                        errors.append(("stale", off, n))
            except ValueError:
                return  # read on closed CachedFile: the expected signal
            except Exception as e:
                errors.append(("raised", repr(e)))

        threads = [threading.Thread(target=reader, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        cf.pread(0, 100)  # ensure some blocks are resident before closing
        fs.unmount()
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), "reader hung on close"
        assert not errors, errors[:5]
        assert fs.resident_bytes == 0, "close leaked resident accounting"


def test_async_read_error_recording_is_locked():
    """AsyncRead must collect producer errors under a lock (the seed
    appended bare from N threads) and still surface the first one."""
    from repro.core import paragrapher

    class Boom(RuntimeError):
        pass

    g = type("G", (), {})()  # duck-typed handle: every read raises

    def read_partition(v0, v1):
        raise Boom(f"{v0}:{v1}")

    g.read_partition = read_partition
    ar = paragrapher.AsyncRead(g, [(i, i + 1) for i in range(32)],
                               lambda buf: None, n_buffers=4, n_workers=8)
    with pytest.raises(Boom):
        ar.wait(30)
    with ar._err_lock:
        assert len(ar._errors) == 32


def test_sequential_readahead_reduces_underlying_reads(datafile):
    """readahead=r must cut underlying calls ~(1+r)x on a sequential scan
    and serve byte-identical data."""
    path, data = datafile
    bs = 4096
    counts = {}
    for ra in (0, 3):
        with pgfuse.PGFuseFS(block_size=bs, readahead=ra) as fs:
            cf = fs.mount(path)
            out = b"".join(cf.pread(off, 1000)
                           for off in range(0, len(data), 1000))
            assert out == data
            counts[ra] = fs.stats().underlying_reads
            if ra:
                assert fs.stats().readahead_blocks > 0
    n_blocks = -(-len(data) // bs)
    assert counts[0] == n_blocks
    assert counts[3] <= -(-n_blocks // 4) + 1, counts


def test_readahead_under_eviction_budget(datafile):
    """Readahead + tiny budget: prefetched blocks are evictable (status 0)
    and the budget still holds."""
    path, data = datafile
    bs = 2048
    with pgfuse.PGFuseFS(block_size=bs, readahead=4,
                         max_resident_bytes=3 * bs) as fs:
        cf = fs.mount(path)
        for off in range(0, len(data), bs):
            assert cf.pread(off, 100) == data[off:off + 100]
        assert fs.resident_bytes <= 3 * bs


@pytest.mark.parametrize("readahead", [0, 2])
def test_pinned_span_makes_preads_requests_and_holds_its_blocks(datafile,
                                                                readahead):
    """Pinning a span asks storage for what a pread of it asks, serves no
    bytes, and keeps every block resident under a cap with room for the
    span and one readahead run, even while reads elsewhere fill the cap,
    so reads inside it in any order cost no further call."""
    path, data = datafile
    bs, lo, hi = 2048, 5000, 40000
    cap = ((hi - 1) // bs - lo // bs + 1 + readahead) * bs
    files = [pgfuse.CachedFile(path, block_size=bs, readahead=readahead,
                               max_resident_bytes=cap) for _ in range(2)]
    whole, pinned = files
    try:
        assert whole.pread(lo, hi - lo) == data[lo:hi]
        with pinned.pinned(lo, hi - lo) as held:
            assert held
            assert pinned.stats.underlying_reads == \
                whole.stats.underlying_reads
            assert pinned.stats.underlying_bytes == \
                whole.stats.underlying_bytes
            assert pinned.stats.bytes_served == 0
            # a read past the span puts the file over its cap: the
            # sweep must find its victims outside the pinned blocks
            far = hi + 8 * bs
            assert pinned.pread(far, 8) == data[far:far + 8]
            calls = pinned.stats.underlying_reads
            for off in range(hi - 8, lo, -997):
                assert pinned.pread(off, 8) == data[off:off + 8]
            assert pinned.stats.underlying_reads == calls
        assert not (pinned._statuses.snapshot() > 0).any()  # all released
        # a cap with no room pins nothing and fetches nothing
        tight = pgfuse.CachedFile(path, block_size=bs, readahead=readahead,
                                  max_resident_bytes=cap - bs)
        files.append(tight)
        with tight.pinned(lo, hi - lo) as held:
            assert not held and tight.stats.underlying_reads == 0
    finally:
        for cf in files:
            cf.close()


def test_underlying_read_count_vs_naive(datafile):
    """The point of §III: far fewer underlying calls than consumer reads."""
    path, data = datafile
    with pgfuse.PGFuseFS(block_size=1 << 16) as fs:
        cf = fs.mount(path)
        n_consumer_reads = 500
        rng = np.random.default_rng(0)
        for _ in range(n_consumer_reads):
            off = int(rng.integers(0, len(data) - 128))
            cf.pread(off, 128)
        st = fs.stats()
        assert st.underlying_reads <= cf.n_blocks
        assert st.underlying_reads < n_consumer_reads / 10
