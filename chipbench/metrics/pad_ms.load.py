"""Time the stream's staging thread spends padding the packed bytes per
whole-graph load (``StreamStats.pad_s``, the ``stream.pad`` spans), in
milliseconds."""


def read(r):
    loads = r.counters.get("stream_stats")
    if not loads or not hasattr(loads[0], "pad_s"):
        return None
    return 1e3 * sum(st.pad_s for st in loads) / len(loads)
