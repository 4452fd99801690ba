"""Share of the graph file's PG-Fuse block acquisitions in the window
that found the block resident (``PGFuseStats`` hits over hits plus
misses), in percent."""


def read(r):
    hits = r.counters.get("pgfuse_hits", 0)
    total = hits + r.counters.get("pgfuse_misses", 0)
    if not total:
        return None
    return 100.0 * hits / total
