"""The four-chip Ludwig cell on four virtual CPU devices, at a tiny size.

One child process (the device count is fixed when JAX starts) runs the
cell's own files with the global lattice cut to 32x32x16 (16x16x16 per
device): a run through ``run_cell``, the readings of the program, the
control and the planted faults, and the halo bytes the entry records.
The tests below judge what it printed."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKLOAD = "ludwig_lc_384x384x192_2x2.timesteps"
LATTICE = (32, 32, 16)
SEED = 2**31 + 17

CHILD = f"""
import json, time, types
from chipbench import run as harness
from chipbench.metrics import ludwig_halo_bytes_per_site
from chipbench.tests import tiny
from chipbench.tools.readings import readings

tiny.allow_cpu_peaks()
spec = harness.load_cell({WORKLOAD!r})
spec["config"] = tiny._merge(spec["config"], {{"lattice": {list(LATTICE)!r},
                                              "check": {{"slab": 4}}}})
spec["traffic"] = tiny._merge(spec["traffic"], {{
    k: v for k, v in tiny.TINY_TRAFFIC.items() if k in spec["traffic"]}})
out = {{"limits": spec["config"]["check"]["limits"]}}
out["result"] = harness.run_cell({WORKLOAD!r}, {SEED}, 1.0, False, spec=spec,
                                 require_tpu=False, t0=time.perf_counter())
(out["readings"],) = readings({WORKLOAD!r}, [{SEED}], [{SEED}], 1.0,
                              spec=spec, require_tpu=False,
                              fault_seeds=[{SEED}])

import importlib
import jax
entry = importlib.import_module("chipbench.entries.ludwig_sharded_step")
ctx = types.SimpleNamespace(seed={SEED}, config=spec["config"],
                            traffic=spec["traffic"], devices=jax.devices(),
                            tracing=False, span=harness._span_factory(False))
rec = entry.prepare(ctx).run_window(0.1)
out["record"] = rec
out["halo_bytes_per_site"] = ludwig_halo_bytes_per_site.read({{"record": rec}})
print("RESULT " + json.dumps(out))
"""


def step_halo_bytes(local):
    """Bytes one device sends per step of the sharded Ludwig step, from the
    padded shapes: q at width 2, the pre-collision dist and the force at
    width 1 padded in every dim (the fused LB launch's halo="pre"
    contract), u at width 1; each exchanged array sends both width-``w``
    faces in each decomposed dim (x, then y), in float32."""
    total = 0
    for ncomp, w, every_dim in [(5, 2, False), (19, 1, True), (3, 1, True),
                                (3, 1, False)]:
        x, y = (n + 2 * w for n in local[:2])
        z = local[2] + 2 * w if every_dim else local[2]
        total += 2 * ncomp * w * (y * z + x * z) * 4
    return total


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(CHILD)],
                          capture_output=True, text=True, timeout=900,
                          env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_sharded_cell_is_correct(child):
    r = child["result"]
    assert r["correct"], r
    assert r["device"]["count"] == 4
    assert r["metrics"]["ludwig_step_ms"]["value"] > 0


def test_program_within_and_control_over_the_limits(child):
    row, limits = child["readings"], child["limits"]
    assert all(row["program"][n] <= limits[n] for n in limits), row
    assert any(row["control"][n] > limits[n] for n in limits), row


@pytest.mark.parametrize("fault", ["dist_unchanged", "no_streaming",
                                   "no_exchange"])
def test_planted_fault_over_a_limit(child, fault):
    row, limits = child["readings"], child["limits"]
    assert any(row["faults"][fault][n] > limits[n] for n in limits), row


def test_halo_bytes_per_site_from_padded_shapes(child):
    local = (LATTICE[0] // 2, LATTICE[1] // 2, LATTICE[2])
    want = step_halo_bytes(local)
    assert want == 179_072
    assert child["record"]["halo_bytes_per_step"] == want
    assert child["halo_bytes_per_site"] == want / (16 * 16 * 16)

