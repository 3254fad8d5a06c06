"""core.tune: the persisted plan autotuner.  Sweep -> JSON table -> warm
hit with zero sweep launches (fresh-process semantics), plan_policy="tuned"
integration, tuned == default numerics, and table robustness."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    Field, LaunchGraph, LoweringPlan, SOA, TargetConfig, aosoa, fuse, tune,
)

LAT = (4, 4, 8)  # 128 sites


@pytest.fixture()
def tune_env(tmp_path, monkeypatch):
    """Isolated tune table per test (env-overridable path is the API)."""
    path = tmp_path / "tune_table.json"
    monkeypatch.setenv(tune.ENV_VAR, str(path))
    tune.clear_table_cache()
    tune.reset_stats()
    yield path
    tune.clear_table_cache()


def _scale_body(v):
    return {"t": 2.0 * v["x"]}


def _graph():
    return LaunchGraph("tune_probe").add(
        _scale_body, {"x": "x"}, {"t": 3})


def _field(rng, lay=SOA):
    arr = rng.normal(size=(3, *LAT)).astype(np.float32)
    return Field.from_numpy("x", arr, LAT, lay)


def test_autotune_sweeps_persists_and_rehits(tune_env, rng):
    """Acceptance probe: write the table in one 'process', drop the
    in-memory cache (what a fresh process sees), re-tune — table hit, ZERO
    sweep launches the second time."""
    fx = _field(rng)
    cfg = TargetConfig("pallas", vvl=64)
    plan, info = tune.autotune_graph(
        _graph(), {"x": fx}, config=cfg, iters=1, warmup=0, max_candidates=4)
    assert not info["cached"]
    assert tune.stats()["sweep_launches"] > 0
    assert tune_env.exists()
    raw = json.loads(tune_env.read_text())
    assert raw["entries"][info["key"]]["plan"] == plan.to_json()
    # every swept candidate was a real, distinct launch
    assert len(info["timings_us"]) == tune.stats()["sweep_launches"]

    # "fresh process": nothing in memory, everything from disk
    tune.clear_table_cache()
    tune.reset_stats()
    plan2, info2 = tune.autotune_graph(
        _graph(), {"x": fx}, config=cfg, iters=1, warmup=0, max_candidates=4)
    assert info2["cached"] and plan2 == plan
    assert tune.stats()["sweep_launches"] == 0, "warm table must not re-sweep"


def test_plan_policy_tuned_round_trip(tune_env, rng):
    """plan_policy='tuned' launches look the persisted winner up by plan
    key and produce the same numerics as the default policy."""
    fx = _field(rng)
    sweep_cfg = TargetConfig("pallas", vvl=64)
    plan, _ = tune.autotune_graph(
        _graph(), {"x": fx}, config=sweep_cfg, iters=1, warmup=0,
        max_candidates=4)

    want = _graph().launch({"x": fx}, config=sweep_cfg)["t"].to_numpy()
    tune.clear_table_cache()  # force the tuned launch to re-read disk
    tune.reset_stats()
    tuned_cfg = TargetConfig("pallas", vvl=64, plan_policy="tuned")
    got = _graph().launch({"x": fx}, config=tuned_cfg)["t"].to_numpy()
    np.testing.assert_array_equal(got, want)
    s = tune.stats()
    assert s["lookups"] == 1 and s["hits"] == 1, s
    assert s["sweep_launches"] == 0


def test_plan_policy_tuned_miss_falls_back_to_default(tune_env, rng):
    """A cold table must never break a launch: tuned policy on a miss uses
    the default heuristics (and records nothing)."""
    fx = _field(rng, aosoa(32))
    cfg = TargetConfig("pallas", vvl=64, plan_policy="tuned")
    fuse.clear_cache()
    fuse.reset_stats()
    out = _graph().launch({"x": fx}, config=cfg)["t"].to_numpy()
    np.testing.assert_allclose(out, 2.0 * fx.to_numpy(), rtol=1e-6)
    s = tune.stats()
    assert s["lookups"] == 1 and s["hits"] == 0, s
    assert not tune_env.exists()
    assert fuse.stats()["pallas_calls"] == 1


def test_explicit_plan_policy_on_config(rng):
    """plan_policy can be a concrete LoweringPlan: every launch under that
    config uses it (here: forced vvl=32, interpret)."""
    fx = _field(rng)
    explicit = LoweringPlan("pallas", vvl=32, interpret=True)
    cfg = TargetConfig("pallas", vvl=64, plan_policy=explicit)
    got = _graph().launch({"x": fx}, config=cfg)["t"].to_numpy()
    np.testing.assert_allclose(got, 2.0 * fx.to_numpy(), rtol=1e-6)
    # a non-conforming explicit plan raises the plan validation error
    bad = TargetConfig("pallas", plan_policy=LoweringPlan("pallas", vvl=7))
    with pytest.raises(ValueError, match="must divide nsites"):
        _graph().launch({"x": fx}, config=bad)


def test_scalars_and_stencil_graph_tuning(tune_env, rng):
    """Tuning covers stencil graphs (bx sweep) and graphs with runtime
    scalars; the tuned launch matches the default-plan launch under its
    plan's contract — bitwise for geometry-only plans, the accuracy-gated
    tolerance when the winner carries a dtype policy (the one candidate
    family whose field outputs are tolerance- rather than bitwise-equal)."""
    from repro.kernels.lb_propagation.ops import collide_propagate_graph

    f0 = (1.0 + 0.1 * rng.normal(size=(19, *LAT))).astype(np.float32)
    frc = (0.01 * rng.normal(size=(3, *LAT))).astype(np.float32)
    d = Field.from_numpy("dist", f0, LAT, SOA)
    g = Field.from_numpy("force", frc, LAT, SOA)
    cfg = TargetConfig("pallas", vvl=128)
    graph = collide_propagate_graph(0.8)
    ins = {"dist": d, "force": g}
    plan, info = tune.autotune_graph(
        graph, ins, config=cfg, outputs=("dist2",), iters=1, warmup=0,
        max_candidates=3)
    assert plan.bx >= 1 and LAT[0] % plan.bx == 0
    want = graph.launch(ins, config=cfg, outputs=("dist2",))["dist2"]
    got = graph.launch(ins, config=cfg, outputs=("dist2",),
                       plan=plan)["dist2"]
    if plan.dtypes:
        err = (np.linalg.norm(got.to_numpy().astype(np.float64)
                              - want.to_numpy())
               / np.linalg.norm(want.to_numpy()))
        assert err <= tune._accuracy_gate_for(plan.dtypes)
    else:
        np.testing.assert_array_equal(got.to_numpy(), want.to_numpy())


def test_pre_halo_tuned_keys_agree(tune_env, rng):
    """halo='pre': autotune keys on the same interior lattice the launch
    keys on, so a tuned-policy pre-exchanged launch hits the table."""
    from repro.core.stencil import halo_pad

    def lap_body(v, gather):
        return {"z": gather("y", (1, 0, 0)) + gather("y", (-1, 0, 0))}

    g = LaunchGraph("pre_tune").add_stencil(
        lap_body, {"y": "x"}, {"z": 3}, width=1)
    x = rng.normal(size=(3, *LAT)).astype(np.float32)
    import jax.numpy as jnp
    xh = halo_pad(jnp.asarray(x), 1, (1, 2, 3))
    fxh = Field.from_canonical("x", xh, tuple(xh.shape[1:]), SOA)
    cfg = TargetConfig("pallas", vvl=64)
    plan, info = tune.autotune_graph(
        g, {"x": fxh}, config=cfg, halo="pre", iters=1, warmup=0,
        max_candidates=2)
    assert LAT[0] % plan.bx == 0  # planned for the interior, not the halo'd X
    tune.reset_stats()
    out = g.launch({"x": fxh},
                   config=TargetConfig("pallas", vvl=64, plan_policy="tuned"),
                   halo="pre")["z"]
    assert out.lattice == LAT
    s = tune.stats()
    assert s["hits"] == 1, f"pre-halo tuned lookup missed the table: {s}"
    want = np.roll(x, 1, axis=1) + np.roll(x, -1, axis=1)
    np.testing.assert_allclose(out.to_numpy(), want, rtol=1e-6)


def test_corrupt_table_yields_empty(tune_env):
    tune_env.write_text("{ not json")
    assert tune.load_table() == {}
    assert tune.lookup("nope") is None


def test_table_is_schema_version_stamped(tune_env, rng):
    """Every persisted table carries the current schema_version (so future
    schema changes can invalidate it) and round-trips through a fresh
    load."""
    fx = _field(rng)
    plan, info = tune.autotune_graph(
        _graph(), {"x": fx}, config=TargetConfig("pallas", vvl=64),
        iters=1, warmup=0, max_candidates=2)
    raw = json.loads(tune_env.read_text())
    assert raw["schema_version"] == tune.SCHEMA_VERSION
    tune.clear_table_cache()
    assert tune.lookup(info["key"]) == plan


def test_unknown_schema_version_degrades_to_misses(tune_env, rng):
    """A table with a missing or unknown schema_version (e.g. a PR-3-era
    file, which wrote a 'version' key before plans gained the overlap halo
    strategy) must behave like an empty table: lookups miss, tuned-policy
    launches fall back to the default heuristics, and a re-tune sweeps
    and re-stamps — stale entries are never mis-decoded."""
    fx = _field(rng)
    cfg = TargetConfig("pallas", vvl=64)
    g = _graph()
    key = g.plan_key({"x": fx}, config=cfg)
    good_entry = {"plan": LoweringPlan("pallas", vvl=64).to_json()}
    for stale in (
        {"version": 1, "entries": {key: good_entry}},          # PR-3 table
        {"schema_version": 99, "entries": {key: good_entry}},  # future table
        {"entries": {key: good_entry}},                        # unstamped
    ):
        tune_env.write_text(json.dumps(stale))
        tune.clear_table_cache()
        assert tune.load_table() == {}
        assert tune.lookup(key) is None
    # tuned policy still launches (default-heuristics fallback)...
    out = _graph().launch(
        {"x": fx},
        config=TargetConfig("pallas", vvl=64, plan_policy="tuned"))["t"]
    np.testing.assert_allclose(out.to_numpy(), 2.0 * fx.to_numpy(), rtol=1e-6)
    # ...and a re-tune re-sweeps (the stale table is not a warm hit) and
    # re-stamps the file with the current version
    tune.reset_stats()
    plan, info = tune.autotune_graph(
        g, {"x": fx}, config=cfg, iters=1, warmup=0, max_candidates=2)
    assert not info["cached"] and tune.stats()["sweep_launches"] > 0
    assert json.loads(tune_env.read_text())["schema_version"] == tune.SCHEMA_VERSION


def test_schema_version_2_table_is_a_clean_miss(tune_env, rng):
    """A version-2 table (pre-rsplit: its plans predate the split-reduction
    axis and the tolerance-vs-bitwise reduction contract) loads as a clean
    miss: lookups return None, and a re-tune sweeps and re-stamps the file
    at the current version with plans that name ``rsplit``."""
    fx = _field(rng)
    cfg = TargetConfig("pallas", vvl=64)
    g = _graph()
    key = g.plan_key({"x": fx}, config=cfg)
    v2_plan = {k: v for k, v in LoweringPlan("pallas", vvl=64).to_json().items()
               if k != "rsplit"}
    tune_env.write_text(json.dumps(
        {"schema_version": 2, "entries": {key: {"plan": v2_plan}}}))
    tune.clear_table_cache()
    assert tune.load_table() == {}
    assert tune.lookup(key) is None
    tune.reset_stats()
    plan, info = tune.autotune_graph(g, {"x": fx}, config=cfg, iters=1,
                                     warmup=0, max_candidates=2)
    assert not info["cached"] and tune.stats()["sweep_launches"] > 0
    raw = json.loads(tune_env.read_text())
    assert raw["schema_version"] == tune.SCHEMA_VERSION
    assert "rsplit" in raw["entries"][info["key"]]["plan"]


def test_schema_version_3_table_is_a_clean_miss(tune_env, rng):
    """A version-3 table (pre-dtype-policy: its plans predate the
    storage/compute/accumulate ``dtypes`` axis and the tuner's accuracy
    gate) loads as a clean miss: lookups return None, and a re-tune sweeps
    and re-stamps the file at the current version with plans that name
    ``dtypes``."""
    fx = _field(rng)
    cfg = TargetConfig("pallas", vvl=64)
    g = _graph()
    key = g.plan_key({"x": fx}, config=cfg)
    v3_plan = {k: v for k, v in LoweringPlan("pallas", vvl=64).to_json().items()
               if k != "dtypes"}
    tune_env.write_text(json.dumps(
        {"schema_version": 3, "entries": {key: {"plan": v3_plan}}}))
    tune.clear_table_cache()
    assert tune.load_table() == {}
    assert tune.lookup(key) is None
    tune.reset_stats()
    plan, info = tune.autotune_graph(g, {"x": fx}, config=cfg, iters=1,
                                     warmup=0, max_candidates=2)
    assert not info["cached"] and tune.stats()["sweep_launches"] > 0
    raw = json.loads(tune_env.read_text())
    assert raw["schema_version"] == tune.SCHEMA_VERSION
    assert "dtypes" in raw["entries"][info["key"]]["plan"]


def test_malformed_entry_is_a_miss_not_a_crash(tune_env, rng):
    """Valid JSON but a structurally broken entry (missing plan, bogus
    engine) must behave like a miss: tuned-policy launches fall back to
    the default heuristics instead of raising."""
    fx = _field(rng)
    cfg = TargetConfig("pallas", vvl=64, plan_policy="tuned")
    g = _graph()
    key = g.plan_key({"x": fx}, config=cfg)
    tune_env.write_text(json.dumps({"version": 1, "entries": {
        key: {"timings_us": {}},                    # no "plan" at all
        "other": {"plan": {"engine": "cuda"}},      # nonsense engine
    }}))
    tune.clear_table_cache()
    assert tune.lookup(key) is None
    assert tune.lookup("other") is None
    out = g.launch({"x": fx}, config=cfg)["t"].to_numpy()
    np.testing.assert_allclose(out, 2.0 * fx.to_numpy(), rtol=1e-6)


def test_sweep_skips_failing_candidates(tune_env, monkeypatch, rng):
    """A candidate whose lowering raises (e.g. over the VMEM budget on a
    real TPU) is recorded as failed and skipped — the sweep completes and
    persists a working winner."""
    fx = _field(rng)
    cfg = TargetConfig("pallas", vvl=64)
    real_launch = LaunchGraph.launch

    def flaky_launch(self, ins, **kw):
        plan = kw.get("plan")
        if plan is not None and plan.vvl == 128:
            raise RuntimeError("RESOURCE_EXHAUSTED: VMEM")
        return real_launch(self, ins, **kw)

    monkeypatch.setattr(LaunchGraph, "launch", flaky_launch)
    plan, info = tune.autotune_graph(
        _graph(), {"x": fx}, config=cfg, iters=1, warmup=0,
        max_candidates=4)
    assert plan.vvl != 128
    assert any("VMEM" in e for e in info["failed"].values()), info
    # the failure is recorded in the persisted entry, not silently dropped
    entry = json.loads(tune_env.read_text())["entries"][info["key"]]
    assert entry["meta"]["failed"]


def test_min_gain_hysteresis_keeps_default(tune_env, monkeypatch, rng):
    """A candidate that is only noisily faster must not dethrone the
    deterministic default plan; a decisively faster one must."""
    fx = _field(rng)
    cfg = TargetConfig("pallas", vvl=64)

    def fake_sweep(graph, ins, launch_kw, cands, iters, warmup):
        # default (first) at 100us; everyone else marginally faster
        return {c: (100e-6 if i == 0 else 97e-6)
                for i, c in enumerate(cands)}, {}

    monkeypatch.setattr(tune, "_sweep", fake_sweep)
    plan, info = tune.autotune_graph(
        _graph(), {"x": fx}, config=cfg, min_gain=0.05)
    assert plan == info["default"], "3% gain must not beat 5% hysteresis"

    def fake_sweep2(graph, ins, launch_kw, cands, iters, warmup):
        return {c: (100e-6 if i == 0 else 50e-6)
                for i, c in enumerate(cands)}, {}

    monkeypatch.setattr(tune, "_sweep", fake_sweep2)
    plan2, info2 = tune.autotune_graph(
        _graph(), {"x": fx}, config=cfg, min_gain=0.05, force=True)
    assert plan2 != info2["default"], "a 2x gain must dethrone the default"


def test_jnp_engine_tunes_to_single_candidate(tune_env, rng):
    """On the jnp engine there is no vvl/slab knob: the sweep degenerates
    to the default plan (and still persists, so the table is a complete
    record of planned launches)."""
    fx = _field(rng)
    plan, info = tune.autotune_graph(
        _graph(), {"x": fx}, config=TargetConfig("jnp"), iters=1, warmup=0)
    assert plan == LoweringPlan("jnp", view="block")  # site-local default
    assert len(info["timings_us"]) == 1


@pytest.mark.slow
def test_table_roundtrip_across_real_processes(tmp_path):
    """The acceptance probe, end to end: sweep + persist in one python
    process, load + hit (zero sweep launches) in a genuinely fresh one."""
    table = tmp_path / "cross_process.json"
    prog = textwrap.dedent("""
        import json, sys
        import numpy as np
        from repro.core import Field, LaunchGraph, SOA, TargetConfig, tune

        def body(v):
            return {"t": 2.0 * v["x"]}

        lat = (4, 4, 8)
        fx = Field.from_numpy(
            "x", np.ones((3, *lat), np.float32), lat, SOA)
        g = LaunchGraph("xproc").add(body, {"x": "x"}, {"t": 3})
        plan, info = tune.autotune_graph(
            g, {"x": fx}, config=TargetConfig("pallas", vvl=64),
            iters=1, warmup=0, max_candidates=3)
        print(json.dumps({"cached": info["cached"],
                          "sweeps": tune.stats()["sweep_launches"],
                          "plan": plan.to_json()}))
    """)
    import os

    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, PYTHONPATH=src, TARGETDP_TUNE_PATH=str(table),
               JAX_PLATFORMS="cpu")  # a child never competes for the chip
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", prog], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr
        outs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    first, second = outs
    assert not first["cached"] and first["sweeps"] > 0
    assert second["cached"] and second["sweeps"] == 0
    assert second["plan"] == first["plan"]
