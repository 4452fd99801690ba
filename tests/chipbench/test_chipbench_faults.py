"""``correct`` comes out false when the timed path is broken underneath:
the control (ids in one byte fewer), an answer altered where it is
produced, and half of the work left out.  The rest of each run is the
benchmark's own."""

import numpy as np
import pytest

from chipbench_small import one_chip_cells, run_small

ONE_CHIP = one_chip_cells()


def _broken(result):
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_the_control_reads_not_correct(small_root, workload):
    from chipbench.control import narrow_ids

    with narrow_ids():
        _broken(run_small(small_root, workload))


@pytest.fixture
def altered_decode(monkeypatch):
    """One id of every decoded run changed where the decode produces it,
    on the device path and on the host path alike."""
    from repro.core import compbin
    from repro.kernels import compbin_decode as pkg
    from repro.kernels.compbin_decode import ops

    dev, host = ops.compbin_decode, compbin.decode_ids

    def alter_dev(packed, b, **kw):
        return dev(packed, b, **kw).at[0].add(1)

    def alter_host(packed, b):
        out = host(packed, b).copy()
        out[:1] += 1
        return out

    monkeypatch.setattr(pkg, "compbin_decode", alter_dev)
    monkeypatch.setattr(ops, "compbin_decode", alter_dev)
    monkeypatch.setattr(compbin, "decode_ids", alter_host)


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_an_altered_answer_reads_not_correct(small_root, workload,
                                             altered_decode):
    _broken(run_small(small_root, workload))


@pytest.fixture
def half_left_out(monkeypatch):
    """The stream yields every other shard; the engine answers the first
    half of each batch and leaves the rest empty."""
    from repro.data.graph_stream import GraphStream
    from repro.query.engine import NeighborQueryEngine

    nxt, batch = GraphStream.__next__, NeighborQueryEngine.neighbors_batch

    def every_other(self):
        nxt(self)
        return nxt(self)

    def first_half(self, vertices, **kw):
        out = batch(self, vertices, **kw)
        half = len(out) // 2
        return out[:half] + [np.zeros(0, np.int64)] * (len(out) - half)

    monkeypatch.setattr(GraphStream, "__next__", every_other)
    monkeypatch.setattr(NeighborQueryEngine, "neighbors_batch", first_half)


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_half_of_the_work_left_out_reads_not_correct(small_root, workload,
                                                     half_left_out):
    _broken(run_small(small_root, workload))
