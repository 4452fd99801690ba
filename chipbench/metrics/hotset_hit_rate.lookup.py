"""Share of the hot set's lookups in the window that it answered
(``HotSetStats`` hits over lookups), in percent."""


def read(r):
    lookups = r.counters.get("hotset_lookups", 0)
    if not lookups:
        return None
    return 100.0 * r.counters["hotset_hits"] / lookups
