"""The benchmark's generators and their per-seed file cache."""

import os

import numpy as np
import pytest

from chipbench_small import SEED

KRON = {"generator": "kronecker", "scale": 10, "edge_factor": 16,
        "a": 0.57, "b": 0.19, "c": 0.19, "codec": "compbin",
        "bytes_per_id": 2}


def test_kronecker_quadrant_frequencies_match_a_b_c():
    """At every level of the recursion an edge falls in quadrant A, B, C
    or D with the configured probabilities."""
    from chipbench import gen

    scale, n_edges = KRON["scale"], KRON["edge_factor"] << KRON["scale"]
    t = tuple(int(round(p * 2**32)) for p in (0.57, 0.76, 0.95))
    src, dst = (np.asarray(x).astype(np.int64) for x in
                gen._kronecker_edges(gen._key(SEED), scale, n_edges, t))
    for level in range(scale):
        down, right = (src >> level) & 1, (dst >> level) & 1
        freq = [np.mean((down == d) & (right == r))
                for d, r in ((0, 0), (0, 1), (1, 0), (1, 1))]
        np.testing.assert_allclose(freq, [0.57, 0.19, 0.19, 0.05], atol=0.01)


def _rows_sorted_and_unique(offsets, neighbors):
    for v in range(len(offsets) - 1):
        row = neighbors[offsets[v]:offsets[v + 1]]
        assert np.all(np.diff(row.astype(np.int64)) > 0)


def test_kronecker_csr_is_deduplicated_and_sorted():
    from chipbench import gen

    offsets, neighbors = gen.kronecker(KRON, SEED)
    assert offsets[-1] == len(neighbors) < KRON["edge_factor"] << 10
    assert neighbors.dtype == np.int32 and neighbors.max() < 1 << 10
    _rows_sorted_and_unique(offsets, neighbors)


def test_graph500_labels_are_one_permutation_of_the_draws():
    """Both ends of every edge go through the same random permutation of
    the vertex ids, so the hub the draws put at id 0 lands elsewhere."""
    import jax

    from chipbench import gen

    scale, n_edges = KRON["scale"], KRON["edge_factor"] << KRON["scale"]
    t = tuple(int(round(p * 2**32)) for p in (0.57, 0.76, 0.95))
    k_draw, k_perm = jax.random.split(gen._key(SEED))
    src0, dst0 = (np.asarray(x) for x in
                  gen._kronecker_edges(k_draw, scale, n_edges, t))
    src, dst = (np.asarray(x) for x in
                gen._graph500_edges(gen._key(SEED), scale, n_edges, t))
    perm = np.asarray(jax.random.permutation(k_perm, 1 << scale))
    assert np.array_equal(np.sort(perm), np.arange(1 << scale))
    assert np.array_equal(src, perm[src0]) and np.array_equal(dst, perm[dst0])
    hub0 = np.bincount(src0, minlength=1 << scale).argmax()
    hub = np.bincount(src, minlength=1 << scale).argmax()
    assert hub0 == 0 and hub == perm[0] != 0


@pytest.mark.parametrize("cfg", [KRON], ids=["kronecker"])
def test_cache_returns_the_same_bytes_for_the_same_seed(tmp_path, cfg):
    from chipbench import gen

    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    paths, first_s = gen.ensure(cfg, "g", SEED, platform="cpu", cache_dir=a)
    assert first_s > 0
    data = {k: open(p, "rb").read() for k, p in paths.items()}
    again, again_s = gen.ensure(cfg, "g", SEED, platform="cpu", cache_dir=a)
    assert again == paths and again_s == 0.0          # nothing generated
    other = gen.cache_paths("g", SEED, b)
    gen.generate(cfg, "g", SEED, cache_dir=b)
    assert {k: open(p, "rb").read() for k, p in other.items()} == data
    gen.generate(cfg, "g", SEED + 1, cache_dir=b)
    assert open(gen.cache_paths("g", SEED + 1, b)["graph"], "rb").read() \
        != data["graph"]
    assert sorted(os.listdir(a)) == sorted(os.path.basename(p)
                                           for p in paths.values())
