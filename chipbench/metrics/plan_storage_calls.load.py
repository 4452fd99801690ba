"""Calls into the underlying file system that the stream's partition
plan makes per whole-graph load (``StreamStats.plan_underlying_reads``,
the graph file's PG-Fuse delta across the plan; ``storage_calls.load``
counts the calls after it)."""


def read(r):
    loads = r.counters.get("stream_stats")
    if not loads or not hasattr(loads[0], "plan_underlying_reads"):
        return None
    return sum(st.plan_underlying_reads for st in loads) / len(loads)
