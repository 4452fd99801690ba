"""BENCHMARK.json keeps to the benchmark's contract, every cell finds its
files by name, and a new configuration, mix, metric and cell are found
with no edit to any file that is there."""

import json
import os
import re

import pytest

from chipbench_small import LATER_CELLS, REPO, run_small

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    assert len(BENCH["command"]) <= 32
    assert all(TEXT.match(w) for w in BENCH["command"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its time allowance
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entries_keep_their_keys_names_and_units(section):
    entries = BENCH[section]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") \
            else set()
        assert ENTRY_KEYS[section] <= set(e) <= ENTRY_KEYS[section] | extra
        assert NAME.match(e["name"])
        for key in ("why", "layer"):
            if key in e:
                assert TEXT.match(e[key]), (e["name"], key)
        if section == "configs":
            assert TEXT.match(e["source"])
        if "unit" in e:
            assert UNIT.match(e["unit"])
            assert e["better"] in ("lower", "higher")
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")


def test_metrics_cells_and_bounds_agree():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    assert {w["config"] for w in cells.values()} == configs
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == \
        len(cells)
    assert all(w["chips"] in (1, 4) for w in cells.values())
    assert sum(w["chips"] == 4 for w in cells.values()) <= \
        max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", cells)
        layers.setdefault(m["layer"], m["name"])
    for w in cells:
        reported = [m for m in BENCH["end_to_end"]
                    if w in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(w in m.get("workloads", cells) for m in BENCH["per_layer"])


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]]
                         + [c for c, _, _, _ in LATER_CELLS])
def test_every_cell_resolves_its_files_by_name(workload, small_root):
    """The cells of BENCHMARK.json from the checkout; those kept for
    later from the small copy, which holds their mixes and entries."""
    from chipbench import harness

    later = workload not in {w["name"] for w in BENCH["workloads"]}
    cell = harness.resolve(workload, small_root if later else REPO)
    assert hasattr(cell.kind, "Runner")
    assert cell.per_layer and all(
        callable(cell.readers[m["name"]].read) for m in cell.per_layer)
    entry = {c["name"]: c for c in BENCH["configs"]}[cell.config_name]
    assert entry["file"].startswith(BENCH["paths"][0] + "/")
    for key in entry["reduced"]:
        assert NAME.match(key) and key in cell.config["reduced"]
    assert TEXT.match(cell.config["source"])
    assert cell.config["guarantees"]


def test_new_config_mix_metric_and_cell_need_no_edit(small_root, tmp_path):
    """A dummy configuration, mix and per-layer metric, added as files
    with new entries in BENCHMARK.json, run as a cell."""
    import shutil

    root = str(tmp_path / "root")
    shutil.copytree(small_root, root)
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    d = os.path.join(root, "chipbench")
    cfg = json.load(open(os.path.join(d, "configs", "kron24.json")))
    cfg["scale"] = 9
    json.dump(cfg, open(os.path.join(d, "configs", "dummy9.json"), "w"))
    mix = json.load(open(os.path.join(d, "traffic", "load-repeat.json")))
    mix["verify_loads"] = 1
    json.dump(mix, open(os.path.join(d, "traffic", "dummy-mix.json"), "w"))
    with open(os.path.join(d, "metrics", "dummy_edges.load.py"), "w") as f:
        f.write("def read(r):\n"
                "    return float(r.counters['stream_stats'][0].edges)\n")
    bench["configs"].append({"name": "dummy9", "source": "test",
                             "file": "chipbench/configs/dummy9.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy9",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "load_edges_per_s":
            m["workloads"].append("dummy-cell")
    bench["per_layer"].append({"name": "dummy_edges.load", "unit": "edges",
                               "better": "higher", "source":
                               "program_counter", "layer": "stream",
                               "moves": "load_edges_per_s",
                               "workloads": ["dummy-cell"]})
    json.dump(bench, open(bench_path, "w"))
    line = run_small(root, "dummy-cell", trace=True)
    assert line["correct"] is True
    assert line["metrics"]["dummy_edges.load"]["value"] > 0
