"""Time the thread that feeds the device spends waiting on storage per
whole-graph load: the stream's staging thread with no partition read
yet (``StreamStats.stage_wait_s``, the ``stream.wait`` spans), in
milliseconds."""


def read(r):
    loads = r.counters.get("stream_stats")
    if not loads or not hasattr(loads[0], "stage_wait_s"):
        return None
    return 1e3 * sum(st.stage_wait_s for st in loads) / len(loads)
