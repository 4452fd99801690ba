"""Time of the stream's staging-and-decode stage per whole-graph load
(``StreamStats.decode_s``, the host clock around padding, H2D, the
decode and ``block_until_ready`` of each partition), in milliseconds."""


def read(r):
    loads = r.counters.get("stream_stats")
    if not loads:
        return None
    return 1e3 * sum(st.decode_s for st in loads) / len(loads)
