"""Time the stream's staging thread spends in ``device_put`` of the
packed bytes and offsets per whole-graph load: placement, the staging
copy and the transfer's issue, the host side of the transfer
(``StreamStats.h2d_s``, the ``stream.h2d`` spans), in milliseconds.
The transfer's tail, if any, is waited for in ``stream.ready``."""


def read(r):
    loads = r.counters.get("stream_stats")
    if not loads or not hasattr(loads[0], "h2d_s"):
        return None
    return 1e3 * sum(st.h2d_s for st in loads) / len(loads)
