"""The command as a check runs it: off a TPU it exits
non-zero and prints no result, and so it does in a directory that holds
only BENCHMARK.json and the benchmark's own directories."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench_small import REPO

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def _bench_only(dst):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, p), os.path.join(dst, p),
                        ignore=shutil.ignore_patterns(
                            ".graphs", ".jax_cache", ".traces",
                            "__pycache__"))
    return dst


@pytest.mark.parametrize("alone", [False, True],
                         ids=["cpu-platform", "benchmark-files-only"])
def test_command_exits_nonzero_without_chip_or_system(tmp_path, alone):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    cwd = _bench_only(str(tmp_path)) if alone else REPO
    workload = BENCH["workloads"][0]["name"]
    r = subprocess.run([sys.executable] + BENCH["command"][1:] + [
        "--workload", workload, "--seed", str(2**31 + 5), "--seconds", "1",
        "--trace", "0"], capture_output=True, text=True, env=env, cwd=cwd,
        timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
