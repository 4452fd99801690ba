"""Share of its roofline that the whole decode program reached in the
window's loads, in percent.

The work is fixed by what is decoded: ``b`` bytes read and 4 bytes
written per id, for every edge of every load.  The least time is that
work over the HBM peak of the cell's chips.  The device time is that of
the whole named decode program (``_decode_impl``, or ``_decode_sharded``
on a mesh): the planar staging and the Pallas kernel together, so
folding one into the other still reads the same work."""

from chipbench.trace_reduce import decode_roofline_pct

PROGRAM = r"_decode_(impl|sharded)$"


def read(r):
    loads = r.counters.get("stream_stats")
    if not loads or r.trace is None:
        return None
    decode_s = r.trace.module_s(PROGRAM)
    if decode_s <= 0:
        return None
    return decode_roofline_pct(sum(st.edges for st in loads),
                               int(r.cell.config["bytes_per_id"]), decode_s,
                               float(r.peaks["hbm_bytes_per_s"]), r.devices)
