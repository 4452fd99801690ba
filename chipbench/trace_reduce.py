"""Profiler trace -> the numbers the per-layer readers take.

The traced run wraps its measured window in the host span
``chipbench.window`` (a ``jax.profiler.TraceAnnotation``).  From the
trace written by ``jax.profiler`` this module keeps, inside that window:

* for each device plane, the operations that ran on it (from the
  ``XLA Ops`` line) and the programs they belong to (``XLA Modules``);
* the host spans (``TraceAnnotation`` and JAX's own trace events; the
  Python tracer's ``$file:line`` events are left out).

It derives: the seconds in which some operation ran on a device (the
union of the operation intervals), the device time of each program by a
stable name (the name without its numeric ids), and the idle gaps between
operations, each put down to the innermost host span that covers its
middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "chipbench.window"
#: a gap shorter than this between two operations is not idle time worth
#: naming (it is still idle in the busy share)
MIN_GAP_NS = 10_000

_IDS = re.compile(r"(\(\d+\)|\.\d+)+$")
_HLO = re.compile(r"^%([^\s=]+)\s*=")


def stable_name(name: str) -> str:
    """``jit__decode_impl(12345)`` -> ``jit__decode_impl``;
    ``%copy.1 = u8[...] copy(...)`` (an operation as the TPU trace names
    it) -> ``copy``."""
    m = _HLO.match(name)
    return _IDS.sub("", m.group(1) if m else name)


def union_ns(intervals, t0: float, t1: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[t0, t1]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, t0: float, t1: float) -> list:
    """The ``(start, end)`` stretches of ``[t0, t1]`` that no interval covers."""
    out, t = [], t0
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, t1)))
        t = max(t, e)
        if t >= t1:
            break
    if t < t1:
        out.append((t, t1))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Device:
    index: int
    ops: list        # (start_ns, end_ns, stable op name, stable module name)
    modules: list    # (start_ns, end_ns, stable module name)


@dataclasses.dataclass
class Reduced:
    t0: float                  # window, in the trace's nanoseconds
    t1: float
    devices: list
    host_spans: list           # (start_ns, end_ns, name)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_s(self, dev: Device) -> float:
        ivs = [(s, e) for s, e, _, _ in dev.ops] or \
              [(s, e) for s, e, _ in dev.modules]
        return union_ns(ivs, self.t0, self.t1) / 1e9

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def module_s(self, pattern: str) -> float:
        """Mean over devices of the device seconds of the programs whose
        stable name matches ``pattern`` (a regular expression)."""
        rx = re.compile(pattern)
        if not self.devices:
            return 0.0
        return sum(
            sum(min(e, self.t1) - max(s, self.t0)
                for s, e, name in d.modules
                if rx.search(name) and min(e, self.t1) > max(s, self.t0))
            for d in self.devices) / len(self.devices) / 1e9

    def device_ops(self, top: int = 10) -> list:
        """``[[program/op, seconds]]``, the most device time first (mean
        over devices)."""
        acc: dict = {}
        for d in self.devices:
            for s, e, op, mod in d.ops:
                dur = min(e, self.t1) - max(s, self.t0)
                if dur > 0:
                    key = f"{mod}/{op}" if mod else op
                    acc[key] = acc.get(key, 0.0) + dur
        n = max(1, len(self.devices))
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n / 1e9] for k, v in ranked]

    def idle_gaps(self, top: int = 10) -> list:
        """``[[host span, seconds]]``: device 0's idle time in gaps of at
        least :data:`MIN_GAP_NS`, summed by the innermost host span that
        covers each gap's middle, the most idle time first."""
        if not self.devices:
            return []
        d = self.devices[0]
        ivs = [(s, e) for s, e, _, _ in d.ops] or \
              [(s, e) for s, e, _ in d.modules]
        acc: dict = {}
        for s, e in gaps_ns(ivs, self.t0, self.t1):
            if e - s < MIN_GAP_NS:
                continue
            mid = (s + e) / 2
            covering = [(he - hs, name) for hs, he, name in self.host_spans
                        if hs <= mid <= he and name != WINDOW_SPAN]
            label = min(covering)[1] if covering else "no host span"
            acc[label] = acc.get(label, 0.0) + (e - s)
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / 1e9] for k, v in ranked]

    def add_host_spans(self, spans, perf_t0: float) -> None:
        """Add ``(start_s, end_s, name)`` spans taken on the host's
        ``time.perf_counter``, aligned so that ``perf_t0`` (the window's
        start on that clock) falls on the window's start in the trace."""
        for s, e, name in spans:
            self.host_spans.append((self.t0 + (s - perf_t0) * 1e9,
                                    self.t0 + (e - perf_t0) * 1e9, name))


def _events(line):
    for ev in line.events:
        yield float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev


def _in_module(ops, mods) -> list:
    """Give each operation the stable name of the program it ran in (the
    module whose interval holds its start on the same device)."""
    mods = sorted(mods)
    starts = [m[0] for m in mods]
    out = []
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else ""
        out.append((s, e, name, mod))
    return out


def reduce_profile(pd) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` to the window's numbers."""
    devices, host = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(s, e, stable_name(ev.name))
                            for s, e, ev in _events(line)]
                elif line.name == MODULES_LINE:
                    mods += [(s, e, stable_name(ev.name))
                             for s, e, ev in _events(line)]
            devices.append(Device(int(m.group(1)), _in_module(ops, mods),
                                  mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(s, e, ev.name) for s, e, ev in _events(line)
                         if not ev.name.startswith("$")]
    windows = [(s, e) for s, e, name in host if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    t0, t1 = windows[0]
    devices.sort(key=lambda d: d.index)
    return Reduced(t0=t0, t1=t1, devices=devices,
                   host_spans=[h for h in host if h[1] > t0 and h[0] < t1])


def reduce_dir(trace_dir: str) -> Reduced:
    """Reduce the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return reduce_profile(ProfileData.from_file(files[-1]))


def decode_roofline_pct(ids: int, b: int, decode_s: float,
                        hbm_bytes_per_s: float, devices: int = 1) -> float:
    """Share of its roofline that a decode of ``ids`` ids of ``b`` bytes
    reached: the work is ``b`` bytes read and 4 written per id, the least
    time that work over the HBM peak of ``devices`` chips, the share the
    least time over ``decode_s``, the mean device time of the whole decode
    program.  In percent."""
    least_s = ids * (b + 4) / (hbm_bytes_per_s * devices)
    return 100.0 * least_s / decode_s
