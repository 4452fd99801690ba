"""The four-chip cells (with those kept for later) on four virtual CPU
devices (the device count is
fixed when JAX starts, so each run is a child process): its path reads
correct, and reads not correct when the shards are not placed over the
mesh (the exchange between chips left out)."""

import json
import os
import subprocess
import sys

import pytest

from chipbench_small import LATER_CELLS, REPO

HERE = os.path.dirname(os.path.abspath(__file__))
FOUR_CHIP = [w["name"] for w in json.load(open(os.path.join(
    REPO, "BENCHMARK.json")))["workloads"] if w["chips"] == 4] + [
    c for c, _, _, chips in LATER_CELLS if chips == 4]

CHILD = """
import json, sys
sys.path[:0] = [{here!r}, {repo!r}]
import jax
assert len(jax.devices()) == 4
import chipbench, chipbench_small
if {local!r}:
    import repro.distributed.sharding as sh
    sh.stream_shard_placement = lambda mesh, n, **kw: (None, None)
root = chipbench_small.make_root({root!r})
print(json.dumps(chipbench_small.run_small(root, {workload!r})))
"""


def _run(tmp_path, workload, local):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(here=HERE, repo=REPO, local=local,
                        root=str(tmp_path), workload=workload)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("local", [False, True],
                         ids=["over-the-mesh", "exchange-left-out"])
@pytest.mark.parametrize("workload", FOUR_CHIP)
def test_four_chip_cell_on_virtual_devices(tmp_path, workload, local):
    line = _run(tmp_path, workload, local)
    assert line["device"]["count"] == 4
    if local:
        assert line["correct"] is False
        assert line["checks"]["shards_off_mesh"]["value"] > 0
    else:
        assert line["correct"] is True, line["checks"]
        assert line["checks"]["shards_off_mesh"]["value"] == 0
