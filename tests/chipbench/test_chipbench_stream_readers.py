"""The readers of the load's stage counters and of the idle that the
program's spans leave unexplained, on synthetic readings: their values,
and nothing read (never an error) where the input is missing, as it is
for a program without those counters or spans."""

import os
import types

import pytest

from chipbench_small import REPO

COUNTER_READERS = {
    # metric: (StreamStats field, scale to the metric's unit)
    "plan_ms.load": ("plan_s", 1e3),
    "plan_storage_calls.load": ("plan_underlying_reads", 1),
    "pad_ms.load": ("pad_s", 1e3),
    "h2d_ms.load": ("h2d_s", 1e3),
    "stage_starved_ms.load": ("stage_wait_s", 1e3),
}


def _reader(name):
    from chipbench import harness

    return harness.load_module(os.path.join(REPO, "chipbench", "metrics",
                                            name + ".py"))


def _reading(loads=None, trace=None):
    from chipbench.run import Reading

    counters = {} if loads is None else {"stream_stats": loads}
    return Reading(cell=None, counters=counters, trace=trace, peaks={},
                   devices=1, window_compiles=0)


@pytest.mark.parametrize("metric", sorted(COUNTER_READERS))
def test_stage_counter_readers_give_the_mean_per_load(metric):
    field, scale = COUNTER_READERS[metric]
    read = _reader(metric).read
    loads = [types.SimpleNamespace(**{field: v}) for v in (2, 4, 9)]
    assert read(_reading(loads)) == pytest.approx(5 * scale)
    assert read(_reading()) is None
    assert read(_reading([])) is None
    # a program whose stats have no such counter reads nothing
    assert read(_reading([types.SimpleNamespace(edges=1)])) is None


def _trace(host_spans, devices=True):
    from chipbench.trace_reduce import Device, Reduced

    # device 0 busy in [0, 100k), [400k, 500k), [505k, 510k), [900k, 1M)
    # of a 1-ms window: gaps of 300k, 5k (under MIN_GAP_NS) and 390k ns
    ops = [(s, e, "op", "mod") for s, e in
           ((0, 100e3), (400e3, 500e3), (505e3, 510e3), (900e3, 1e6))]
    devs = [Device(0, ops, [])] if devices else []
    return Reduced(t0=0.0, t1=1e6, devices=devs, host_spans=host_spans)


def test_idle_unattributed_counts_gaps_no_program_span_covers():
    read = _reader("idle_unattributed.load").read
    spans = [(150e3, 350e3, "stream.h2d"),        # covers the first gap
             (0.0, 1e6, "chipbench.next_shard"),  # not the program's
             (502e3, 503e3, "pgfuse.read")]       # the short gap only
    assert read(_reading(trace=_trace(spans))) == \
        pytest.approx(100 * 390 / 690)
    spans.append((600e3, 800e3, "pgfuse.read"))
    assert read(_reading(trace=_trace(spans))) == pytest.approx(0.0)


def test_idle_unattributed_reads_nothing_without_its_input():
    read = _reader("idle_unattributed.load").read
    assert read(_reading()) is None
    # no device plane (a CPU run)
    assert read(_reading(trace=_trace([(0, 1e6, "stream.wait")],
                                      devices=False))) is None
    # a program that emits no spans of its own
    assert read(_reading(trace=_trace([(0, 1e6, "chipbench.window")]))) \
        is None
