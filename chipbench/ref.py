"""The plain reference: the generator's own CSR, and the comparisons that
decide ``correct``.

It imports nothing of the system under test and reads nothing the system
made: the offsets and neighbours come from the ``.npy`` files the
generator wrote beside the graph file (:mod:`chipbench.gen`).  The
guarantee every configuration states is that answers are exact, so each
comparison is exact and its limit is 0.
"""

from __future__ import annotations

import numpy as np


class RefCSR:
    """The generator's CSR, memory-mapped from its ``.npy`` files."""

    def __init__(self, offsets_path: str, neighbors_path: str):
        self.offsets = np.load(offsets_path, mmap_mode="r")
        self.neighbors = np.load(neighbors_path, mmap_mode="r")
        if int(self.offsets[-1]) != len(self.neighbors):
            raise ValueError("reference offsets and neighbours disagree")

    @property
    def n_vertices(self) -> int:
        return len(self.offsets) - 1

    @property
    def n_edges(self) -> int:
        return len(self.neighbors)

    def degrees(self) -> np.ndarray:
        return np.diff(self.offsets)

    def neighbors_of(self, v: int) -> np.ndarray:
        return self.neighbors[int(self.offsets[v]):int(self.offsets[v + 1])]


def compare_load(ref: RefCSR, shards) -> dict:
    """One whole-graph load against the reference.

    ``shards`` is a list of ``(v0, v1, offsets, neighbors)`` as numpy
    arrays read back from the device.  Returns the number of shards whose
    rebased offsets or neighbours differ, and the number of vertices the
    shards fail to cover exactly once.
    """
    wrong = 0
    covered = np.zeros(ref.n_vertices + 1, np.int64)
    for v0, v1, offs, nbrs in shards:
        covered[v0] += 1
        covered[v1] -= 1
        o0, o1 = int(ref.offsets[v0]), int(ref.offsets[v1])
        if not (np.array_equal(np.asarray(offs, np.int64),
                               ref.offsets[v0:v1 + 1] - o0)
                and np.array_equal(np.asarray(nbrs, np.int64),
                                   ref.neighbors[o0:o1])):
            wrong += 1
    uncovered = int(np.count_nonzero(np.cumsum(covered[:-1]) != 1))
    return {"wrong_shards": wrong, "uncovered_vertices": uncovered}


def compare_answers(ref: RefCSR, requests, answers) -> int:
    """Requests whose answer is not, list by list, the reference's
    neighbours of the vertices asked for."""
    wrong = 0
    for vertices, lists in zip(requests, answers):
        if len(lists) != len(vertices) or not all(
                np.array_equal(np.asarray(got, np.int64), ref.neighbors_of(v))
                for v, got in zip(vertices, lists)):
            wrong += 1
    return wrong
