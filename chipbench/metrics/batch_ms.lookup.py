"""Mean duration of the engine's ``query.batch`` spans in the window, in
milliseconds: one coalesced micro-batch from dedup through gather, hot
set, PG-Fuse reads and decode."""


def read(r):
    spans = [s for s in r.counters.get("spans", []) if s.name == "query.batch"]
    if not spans:
        return None
    return 1e3 * sum(s.duration_s for s in spans) / len(spans)
