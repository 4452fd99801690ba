"""Calls into the underlying file system per whole-graph load
(``StreamStats.underlying_reads``): PG-Fuse's enlarged reads."""


def read(r):
    loads = r.counters.get("stream_stats")
    if not loads:
        return None
    return sum(st.underlying_reads for st in loads) / len(loads)
