"""Share of device 0's idle time in the traced window that the
program's own spans cannot explain, in percent: of the idle gaps of at
least ``trace_reduce.MIN_GAP_NS``, the time in those whose middle no
span of the program (``stream.*``, ``pgfuse.*``, on any thread) covers.
Nothing is read where the trace has no device, or no span of the
program at all."""

from chipbench.trace_reduce import MIN_GAP_NS, gaps_ns

PROGRAM = ("stream.", "pgfuse.")


def read(r):
    red = r.trace
    if red is None or not red.devices:
        return None
    spans = [(s, e) for s, e, name in red.host_spans
             if name.startswith(PROGRAM)]
    if not spans:
        return None
    d = red.devices[0]
    ivs = [(s, e) for s, e, _, _ in d.ops] or \
          [(s, e) for s, e, _ in d.modules]
    idle = unexplained = 0.0
    for s, e in gaps_ns(ivs, red.t0, red.t1):
        if e - s < MIN_GAP_NS:
            continue
        idle += e - s
        mid = (s + e) / 2
        if not any(hs <= mid <= he for hs, he in spans):
            unexplained += e - s
    if idle <= 0:
        return None
    return 100.0 * unexplained / idle
