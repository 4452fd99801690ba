"""The trace reduction, on a small trace recorded on one TPU v5e (one
streamed load of a scale-10 graph, inside a ``chipbench.window`` span),
and the roofline arithmetic on known numbers."""

import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "small_load.xplane.pb")


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData

    from chipbench import trace_reduce

    return trace_reduce.reduce_profile(ProfileData.from_file(TRACE))


def test_busy_and_idle_over_the_window(red):
    assert len(red.devices) == 1
    assert red.window_s == pytest.approx(0.054751585)
    busy = red.mean_busy_s()
    assert busy == pytest.approx(0.000345586, rel=1e-6)
    assert 0.99 < 1 - busy / red.window_s < 1.0


def test_op_times_by_stable_name(red):
    ops = dict(red.device_ops())
    assert list(ops)[0] == "jit__decode_impl/reshape"
    assert ops["jit__decode_impl/compbin_decode_planar"] == \
        pytest.approx(3.43e-07)
    assert "jit_dynamic_slice/dynamic_slice" in ops
    decode = red.module_s(r"_decode_(impl|sharded)$")
    assert decode == pytest.approx(0.000342929, rel=1e-6)
    assert decode == pytest.approx(sum(
        v for k, v in ops.items() if k.startswith("jit__decode_impl/")),
        rel=0.05)


def test_idle_gaps_are_put_down_to_host_spans(red):
    gaps = red.idle_gaps()
    assert gaps and all(s > 0 for _, s in gaps)
    assert sum(s for _, s in gaps) <= red.window_s - red.mean_busy_s() + 1e-9


def test_stable_names_and_interval_arithmetic():
    from chipbench import trace_reduce as tr

    assert tr.stable_name("jit__decode_impl(14375823198897202534)") == \
        "jit__decode_impl"
    assert tr.stable_name("%copy.1 = u8[4,384]{1,0} copy(u8[4,384] %b)") \
        == "copy"
    ivs = [(0, 10), (5, 15), (20, 30), (40, 50)]
    assert tr.union_ns(ivs, 0, 45) == 15 + 10 + 5
    assert tr.gaps_ns(ivs, 0, 60) == [(15, 20), (30, 40), (50, 60)]


def test_decode_roofline_arithmetic():
    from chipbench.trace_reduce import decode_roofline_pct

    # 1 M ids of 3 bytes: 7 MB of traffic, 1 ms at 7 GB/s; 4 ms taken
    assert decode_roofline_pct(1_000_000, 3, 0.004, 7e9) == pytest.approx(25)
    # on four chips the same work has four times the bandwidth
    assert decode_roofline_pct(1_000_000, 3, 0.001, 7e9, devices=4) == \
        pytest.approx(25)
