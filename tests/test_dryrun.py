"""Dry-run harness validation (reduced configs through the REAL harness:
512 fake devices, production meshes, full spec/sharding path).

The full-config 80-cell sweep runs via repro.launch.sweep and is recorded
in EXPERIMENTS.md; these tests prove the machinery itself in CI time."""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_dryrun(arch, shape, mesh, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # a child never competes for the chip
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--mesh", mesh, "--smoke-arch",
         "--no-exact-loops"],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout)


@pytest.mark.slow
@pytest.mark.parametrize("mesh", ["single", "multi"])
def test_dryrun_train_single_and_multi_pod(mesh):
    row = run_dryrun("granite-3-2b", "train_4k", mesh)
    assert row["status"] == "ok"
    assert row["devices"] == (512 if mesh == "multi" else 256)
    assert row["roofline"]["flops_per_device"] > 0
    assert row["memory"]["live_per_device_gib"] >= 0


@pytest.mark.slow
def test_dryrun_decode():
    row = run_dryrun("granite-3-2b", "decode_32k", "single")
    assert row["status"] == "ok"


@pytest.mark.slow
def test_dryrun_skip_long_context_for_full_attention():
    row = run_dryrun("granite-3-2b", "long_500k", "single")
    assert row["status"] == "skipped"
    assert "sub-quadratic" in row["reason"]


def test_mesh_shapes():
    """make_production_mesh contract (checked without touching devices)."""
    import repro.launch.mesh as M
    import inspect

    src = inspect.getsource(M.make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '"pod", "data", "model"' in src.replace("'", '"')


def test_dryrun_sets_xla_flags_first():
    """Spec requirement: the first two statements of dryrun.py set
    XLA_FLAGS before any other import."""
    path = os.path.join(SRC, "repro", "launch", "dryrun.py")
    with open(path) as f:
        lines = [l.strip() for l in f.readlines() if l.strip()]
    assert lines[0] == "import os"
    assert lines[1].startswith('os.environ["XLA_FLAGS"]')
    assert "--xla_force_host_platform_device_count=512" in lines[1]
