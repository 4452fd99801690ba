"""chip_smoke.py's phases on the CPU (interpret mode) at a small scale.

The script runs the load-and-serve path on a TPU; here its phases run
on the CPU backend at scale 10-11 so a wrong path, argument or
comparison shows before any chip time is spent, and its refusal to
report success without a TPU is pinned.
"""

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
OK = '"ok": true'


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def graph(smoke, tmp_path_factory):
    return smoke.phase_data(str(tmp_path_factory.mktemp("smoke")), scale=11,
                            seed=3)


def test_load_phase_decodes_on_device_and_matches_csr(smoke, graph):
    csr, path = graph
    out = smoke.phase_load(csr, path)
    assert out["partitions"] >= 1


def test_serve_phase_matches_csr(smoke, graph):
    csr, path = graph
    out = smoke.phase_serve(csr, path, seed=3, n_batches=3, batch=256,
                            n_khop=2)
    assert out["device_batches"] == 3


def test_ref_khop_is_a_plain_bfs(smoke):
    from repro.core.csr import csr_from_edges

    # 0 -> 1 -> 2 -> 3, 0 -> 2
    csr = csr_from_edges([0, 1, 2, 0], [1, 2, 3, 2], 4)
    verts, depths = smoke.ref_khop(csr, 0, 2)
    assert verts.tolist() == [0, 1, 2, 3]
    assert depths.tolist() == [0, 1, 1, 2]


def test_a_wrong_answer_fails_the_phase(smoke, graph):
    csr, path = graph
    bad = csr.neighbors.copy()
    bad[-1] ^= 1
    wrong = type(csr)(offsets=csr.offsets, neighbors=bad)
    with pytest.raises(smoke.SmokeFailure):
        smoke.phase_load(wrong, path)


def test_four_device_phase_on_virtual_cpus(tmp_path):
    """The ``--chips 4`` phase on 4 virtual CPU devices: the device count
    is fixed when JAX starts, so it runs in a child process."""
    code = ("import importlib.util, jax, sys\n"
            f"spec = importlib.util.spec_from_file_location('s', {SCRIPT!r})\n"
            "s = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(s)\n"
            f"csr, path = s.phase_data({str(tmp_path)!r}, scale=10, seed=5)\n"
            "assert len(jax.devices()) == 4\n"
            "s.phase_shard4(csr, path, jax.devices())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "equal_to_host_decode=True" in r.stdout


def test_main_refuses_a_cpu_platform(smoke, capsys):
    assert smoke.main(["--scale", "10"]) != 0
    assert OK not in capsys.readouterr().out


@pytest.mark.parametrize("alone,reason", [
    (False, "JAX found no TPU"),
    (True, "No module named 'repro'"),
], ids=["cpu-platform", "script-alone"])
def test_script_exits_nonzero_without_chip_or_repo(tmp_path, alone, reason):
    """As a user runs it: on the CPU backend, and copied into a
    directory that holds nothing else of the repository."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    script, cwd = SCRIPT, REPO
    if alone:
        script = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
        cwd = tmp_path
    r = subprocess.run([sys.executable, str(script), "--scale", "10"],
                       capture_output=True, text=True, env=env, cwd=cwd,
                       timeout=300)
    assert r.returncode != 0
    assert OK not in r.stdout
    assert reason in r.stderr
