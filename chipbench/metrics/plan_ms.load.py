"""Time of the stream's partition plan per whole-graph load
(``StreamStats.plan_s``, the host clock around ``partition_plan`` and
``split_plan`` on the caller's thread, the ``stream.plan`` span), in
milliseconds."""


def read(r):
    loads = r.counters.get("stream_stats")
    if not loads or not hasattr(loads[0], "plan_s"):
        return None
    return 1e3 * sum(st.plan_s for st in loads) / len(loads)
