"""ParaGrapher API (paper §II-A): full/partition/async loading, formats."""

import numpy as np
import pytest

from repro.core import paragrapher as pg
from repro.core.csr import csr_from_edges
from tests._prop import Draw


@pytest.fixture(params=["compbin", "webgraph"])
def graph_file(request, tmp_path):
    rng = np.random.default_rng(3)
    nv, ne = 2000, 16000
    csr = csr_from_edges(rng.integers(0, nv, ne), rng.integers(0, nv, ne),
                         nv, dedupe=True)
    path = tmp_path / f"g.{request.param}"
    pg.save_graph(path, csr, format=request.param)
    return str(path), csr, request.param


def test_format_autodetect(graph_file):
    path, csr, fmt = graph_file
    g = pg.open_graph(path)
    assert g.format == fmt
    assert (g.n_vertices, g.n_edges) == (csr.n_vertices, csr.n_edges)
    g.close()


def test_read_full_and_partition(graph_file):
    path, csr, _ = graph_file
    with pg.open_graph(path) as g:
        full = g.read_full()
        assert np.array_equal(full.offsets, csr.offsets)
        np.testing.assert_array_equal(full.neighbors.astype(np.int64),
                                      csr.neighbors.astype(np.int64))
        offs, nbrs = g.read_partition(17, 1333)
        exp = csr.neighbors[csr.offsets[17]:csr.offsets[1333]]
        np.testing.assert_array_equal(nbrs.astype(np.int64), exp.astype(np.int64))
        assert offs[-1] == len(nbrs)


def test_async_read_covers_all_partitions(graph_file):
    path, csr, _ = graph_file
    with pg.open_graph(path, use_pgfuse=True, pgfuse_block_size=8192) as g:
        plan = g.partition_plan(9)
        assert plan[0][0] == 0 and plan[-1][1] == csr.n_vertices
        assert all(a < b for a, b in plan)
        got = {}

        def cb(buf):
            assert buf.error is None
            got[(buf.v0, buf.v1)] = buf.neighbors.copy()

        ar = g.read_async(plan, cb, n_buffers=2, n_workers=3)
        ar.wait(60)
        assert ar.done
        joined = np.concatenate([got[p] for p in sorted(got)])
        np.testing.assert_array_equal(joined.astype(np.int64),
                                      csr.neighbors.astype(np.int64))
        st = g.pgfuse_stats()
        assert st is not None and st.cache_hits > 0


def test_async_error_surfaces(graph_file):
    path, _, _ = graph_file
    with pg.open_graph(path) as g:
        def bad_cb(buf):
            raise RuntimeError("consumer exploded")

        ar = g.read_async([(0, 10)], bad_cb)
        with pytest.raises(RuntimeError, match="consumer exploded"):
            ar.wait(30)


def test_closed_graph_rejects_reads(graph_file):
    path, _, _ = graph_file
    g = pg.open_graph(path)
    g.close()
    with pytest.raises(ValueError):
        g.read_full()


@pytest.mark.parametrize("case", range(5))
@pytest.mark.parametrize("fmt", ["compbin", "logcsr"])
def test_partition_plan_edge_balance(fmt, case, tmp_path):
    draw = Draw(np.random.default_rng(1000 + case))
    nv = draw.int(100, 3000)
    ne = draw.int(nv, 20000)
    csr = csr_from_edges(draw.ints(0, nv - 1, ne), draw.ints(0, nv - 1, ne),
                         nv, dedupe=True)
    path = str(tmp_path / f"g.{fmt}")
    pg.save_graph(path, csr, format=fmt)
    with pg.open_graph(path) as g:
        n_parts = draw.int(2, 16)
        plan = g.partition_plan(n_parts)
        sizes = [int(csr.offsets[b] - csr.offsets[a]) for a, b in plan]
        assert sum(sizes) == csr.n_edges
        # no partition grossly above the fair share (+1 vertex slack)
        fair = csr.n_edges / len(plan)
        max_deg = int(np.max(csr.degrees())) if csr.n_edges else 0
        assert max(sizes) <= fair + max_deg + 1


def _whole_array_plan(offsets, n_parts):
    """The reference: the cut rule over the whole offsets array, by
    ``np.searchsorted``, as the plan computed it before it bisected."""
    n_vertices = len(offsets) - 1
    total = int(offsets[-1])
    targets = [(total * (i + 1)) // n_parts for i in range(n_parts)]
    cuts = np.clip(np.searchsorted(offsets, targets, side="left"),
                   1, n_vertices)
    bounds = [0] + sorted(set(int(c) for c in cuts))
    if bounds[-1] != n_vertices:
        bounds.append(n_vertices)
    return [(bounds[i], bounds[i + 1]) for i in range(len(bounds) - 1)]


def _edges(draw, nv, ne, src_lo=0, src_hi=None):
    src_hi = nv - 1 if src_hi is None else src_hi
    return csr_from_edges(draw.ints(src_lo, src_hi, ne),
                          draw.ints(0, nv - 1, ne), nv, dedupe=True)


def _hub(draw):
    """Vertex 37 holds about two thirds of the edges."""
    nv = 300
    src = np.concatenate([np.full(nv, 37), draw.ints(0, nv - 1, 150)])
    dst = np.concatenate([np.arange(nv), draw.ints(0, nv - 1, 150)])
    return csr_from_edges(src, dst, nv, dedupe=True)


#: graph and the n_parts to plan it into, by case
PLAN_CASES = {
    "zero_edges": (lambda d: _edges(d, 90, 0), (1, 2, 7, 200)),
    "no_vertices": (lambda d: _edges(d, 0, 0), (1, 3)),
    "more_parts_than_vertices": (lambda d: _edges(d, 6, 20), (7, 13, 64)),
    "hub": (_hub, (2, 3, 8, 31)),
    "isolated_ends": (lambda d: _edges(d, 500, 1500, 150, 320),
                      (2, 5, 16, 499)),
    "one_part": (lambda d: _edges(d, 400, 2000), (1,)),
    "random": (lambda d: _edges(d, d.int(1, 3000), d.int(0, 12000)),
               (2, 3, 9, 64)),
}


@pytest.mark.parametrize("case", list(PLAN_CASES))
@pytest.mark.parametrize("mounted", [False, True], ids=["plain", "pgfuse"])
@pytest.mark.parametrize("fmt", ["compbin", "logcsr"])
def test_partition_plan_equals_the_whole_array_rule(fmt, mounted, case,
                                                    tmp_path):
    """Bisecting the offsets in place gives the plan the whole-array rule
    gives, bit for bit, on a plain file and on a PG-Fuse mount whose
    small blocks split the offsets; multi-host processes rely on it."""
    make, n_parts_list = PLAN_CASES[case]
    for seed in range(3):
        draw = Draw(np.random.default_rng(2000 + seed))
        csr = make(draw)
        path = str(tmp_path / f"g{seed}.{fmt}")
        pg.save_graph(path, csr, format=fmt)
        with pg.open_graph(path, use_pgfuse=mounted, pgfuse_block_size=64,
                           pgfuse_readahead=1) as g:
            for n_parts in n_parts_list + (draw.int(1, 40),):
                assert g.partition_plan(n_parts) == \
                    _whole_array_plan(csr.offsets, n_parts), n_parts
