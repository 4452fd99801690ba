"""Every one-chip cell's timed path at the small size on the CPU, with
interpret-mode kernels: it runs, reads correct, and prints the keys the
benchmark's last line carries.  The cells kept for later (the lookups)
run here too."""

import json

import pytest

from chipbench_small import one_chip_cells, run_small

ONE_CHIP = one_chip_cells()
REQUIRED = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("trace", [0, 1], ids=["e2e", "traced"])
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_cell_path_is_correct_and_prints_the_contract_keys(
        small_root, workload, trace):
    from chipbench import harness

    cell = harness.resolve(workload, small_root)
    line = json.loads(json.dumps(run_small(small_root, workload,
                                           trace=bool(trace))))
    keys = list(line)
    assert REQUIRED <= set(keys)
    assert set(keys) - REQUIRED <= {"breakdown", "checks"}
    assert keys[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert "memory_peak_bytes" in dev
    if trace:
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        assert set(line["metrics"]) <= set(units)
        assert "window_compiles." + cell.mix["kind"].split("_")[0] \
            in line["metrics"]
        assert dev["window_s"] > 0 and "busy_s" in dev
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        assert set(line["metrics"]) == set(units)
        assert line["metrics"]["setup_s"]["value"] > 0
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name]
        assert isinstance(m["value"], (int, float))


def test_the_zipf_mix_kept_without_a_cell_still_runs(small_root):
    """The zipf lookups have no cell yet (see PERF.md); a cell that runs
    them reads correct, and its hubs hit the hot set."""
    line = run_small(small_root, "kron24-lookup-zipf", trace=True)
    assert line["correct"] is True
    assert line["metrics"]["hotset_hit_rate.lookup"]["value"] > 0
