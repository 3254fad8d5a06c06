"""Native AoSoA stencil lowering (``LoweringPlan.view == "block"``).

The paper's central lever is switching the data layout per architecture
without touching kernel bodies (§3.1); these tests pin the contract that
makes that lever reach halo'd stencil chains: a stencil launch under the
native-block view is **bit-identical** to the same launch under the
staged-nd view — on every halo strategy (periodic / pre / overlap), for
the production graphs (the fused LB step and the fused Wilson normal
operator) and for mixed-layout inputs — while the physical AoSoA arrays
never round-trip through an XLA pack/unpack.  Plan-layer satellites: view
candidates are emitted only for AoSoA inputs, the default policy stays
staged-nd (bit-compat with pre-PR behavior), describe()/persisted tune
entries record the view, and plan keys keyed on different layouts never
share tuned winners.
"""

import numpy as np
import pytest

from repro.core import (
    Field, LaunchGraph, LoweringPlan, SOA, TargetConfig, aosoa, fuse,
)
from repro.core import plan as plan_mod
from repro.core.plan import VIEW_BLOCK, VIEW_STAGED_ND
from repro.core.stencil import halo_pad

PCFG = TargetConfig("pallas", vvl=128)


def _scale_body(v, *, a):
    return {"y": a * v["x"]}


def _lap_body(v, gather, *, c):
    return {"z": c * v["y"] + gather("y", (1, 0, 0)) + gather("y", (-1, 0, 0))}


def _graph():
    return (LaunchGraph("view_g")
            .add(_scale_body, {"x": "x"}, {"y": 3}, params=dict(a=2.0))
            .add_stencil(_lap_body, {"y": "y"}, {"z": 3}, width=1,
                         params=dict(c=-2.0))
            .add_reduce("z", op="sum", name="zt"))


def _plans(bx, halo="periodic"):
    staged = LoweringPlan("pallas", bx=bx, halo=halo, interpret=True,
                          view=VIEW_STAGED_ND)
    return staged, LoweringPlan("pallas", bx=bx, halo=halo, interpret=True,
                                view=VIEW_BLOCK)


# -- bit-identity: block view == staged-nd view --------------------------------

@pytest.mark.parametrize("sal", [2, 4])
@pytest.mark.parametrize("bx", [1, 2, 3])
def test_block_matches_staged_periodic(sal, bx, rng):
    """Single-shard periodic: the field output (physical array!) is
    bitwise equal across views; the on-chip fp sum is tolerance-equal
    (the compiler may reassociate it per view, as for rsplit and tiles)."""
    lat = (6, 4, 8)  # padded inner 6*10=60; sal 2,4 divide 60 and inner 32
    x = rng.normal(size=(3, *lat)).astype(np.float32)
    fx = Field.from_numpy("x", x, lat, aosoa(sal))
    g = _graph()
    staged, block = _plans(bx)
    a = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt"), plan=staged)
    b = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt"), plan=block)
    assert b["z"].layout == aosoa(sal)
    np.testing.assert_array_equal(np.asarray(a["z"].data),
                                  np.asarray(b["z"].data))
    # the periodic Laplacian sums to ~0, so the bound is fp32 rounding
    # relative to the summed magnitudes, per component
    scale = np.abs(a["z"].to_numpy()).reshape(3, -1).sum(axis=1)
    np.testing.assert_array_less(
        np.abs(np.asarray(a["zt"]) - np.asarray(b["zt"])), 1e-6 * scale)
    # and both equal the jnp-engine oracle
    j = g.launch({"x": fx}, config=TargetConfig("jnp"), outputs=("z", "zt"))
    np.testing.assert_allclose(b["z"].to_numpy(), j["z"].to_numpy(),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("halo", ["pre", "overlap"])
def test_block_matches_staged_pre_and_overlap(halo, rng):
    """Pre-exchanged inputs (the sharded drivers' contract): the native
    view stages the caller's physical AoSoA array as-is; overlap splits
    into staged sub-launches and assembles back into AoSoA — all bitwise
    equal to the staged-nd single launch."""
    import jax.numpy as jnp

    lat = (6, 4, 8)
    x = rng.normal(size=(3, *lat)).astype(np.float32)
    xh = np.asarray(halo_pad(jnp.asarray(x), 1, (1, 2, 3)))
    plat = tuple(s + 2 for s in lat)   # inner_h = 6*10 = 60
    fxh = Field.from_numpy("x", xh, plat, aosoa(4))
    g = _graph()
    staged, block = _plans(2, halo=halo)
    a = g.launch({"x": fxh}, config=PCFG, outputs=("z", "zt"), halo=halo,
                 plan=staged)
    b = g.launch({"x": fxh}, config=PCFG, outputs=("z", "zt"), halo=halo,
                 plan=block)
    assert b["z"].layout == aosoa(4)
    np.testing.assert_array_equal(np.asarray(a["z"].data),
                                  np.asarray(b["z"].data))


@pytest.mark.parametrize("sal", [4, 8, 16])
def test_lb_step_block_matches_staged(sal, rng):
    """The production fused LB step (moments+collide+propagate, the paper's
    hottest launch) under native AoSoA at hardware-ish SALs."""
    from repro.kernels.lb_propagation.ops import collide_propagate_graph

    lat = (4, 14, 16)  # inner 224, padded inner 16*18=288: 4/8/16 all align
    f0 = (1.0 + 0.1 * rng.normal(size=(19, *lat))).astype(np.float32)
    frc = (0.01 * rng.normal(size=(3, *lat))).astype(np.float32)
    d = Field.from_numpy("dist", f0, lat, aosoa(sal))
    frcF = Field.from_numpy("force", frc, lat, aosoa(sal))
    g = collide_propagate_graph(0.8)
    staged, block = _plans(2)
    fuse.clear_cache()
    fuse.reset_stats()
    a = g.launch({"dist": d, "force": frcF}, config=PCFG,
                 outputs=("dist2",), plan=staged)
    b = g.launch({"dist": d, "force": frcF}, config=PCFG,
                 outputs=("dist2",), plan=block)
    np.testing.assert_array_equal(np.asarray(a["dist2"].data),
                                  np.asarray(b["dist2"].data))
    # each view is its own single fused pallas_call and its own cache entry
    s = fuse.stats()
    assert s["pallas_calls"] == 2 and s["cache_misses"] == 2, s


def test_wilson_normal_block_matches_staged():
    """The fused MILC normal operator (2 dslash stencils + reduction):
    4-D lattice, ring-2 halos, 72-component gauge input."""
    from repro.apps.milc import MilcConfig, init_problem
    from repro.apps.milc.cg import wilson_normal_graph

    cfg = MilcConfig(lattice=(4, 4, 4, 4), kappa=0.1, layout=aosoa(8))
    u, b = init_problem(cfg, seed=0)  # inner 64, padded inner 512: 8 aligns
    g = wilson_normal_graph(cfg.kappa)
    staged, block = _plans(2)
    a = g.launch({"p": b, "u": u}, config=PCFG, outputs=("ap", "pap"),
                 plan=staged)
    o = g.launch({"p": b, "u": u}, config=PCFG, outputs=("ap", "pap"),
                 plan=block)
    np.testing.assert_array_equal(np.asarray(a["ap"].data),
                                  np.asarray(o["ap"].data))
    np.testing.assert_array_equal(np.asarray(a["pap"]), np.asarray(o["pap"]))


def test_mixed_layouts_native_and_staged_inputs(rng):
    """AoSoA + SOA inputs in one block-view launch: the AoSoA input goes
    native, the SOA input stages canonically, outputs land per out_layouts
    (native AoSoA output next to a packed SOA output)."""
    lat = (6, 4, 8)
    x = rng.normal(size=(3, *lat)).astype(np.float32)
    f = (0.1 * rng.normal(size=(3, *lat))).astype(np.float32)
    fx = Field.from_numpy("x", x, lat, aosoa(4))
    ff = Field.from_numpy("f", f, lat, SOA)
    g = (LaunchGraph("mixed")
         .add(lambda v: {"y": v["x"] + v["f"]}, {"x": "x", "f": "f"},
              {"y": 3})
         .add_stencil(_lap_body, {"y": "y"}, {"z": 3}, width=1,
                      params=dict(c=0.5)))
    staged, block = _plans(3)
    layouts = {"z": SOA}
    a = g.launch({"x": fx, "f": ff}, config=PCFG, outputs=("z",),
                 out_layouts=layouts, plan=staged)
    b = g.launch({"x": fx, "f": ff}, config=PCFG, outputs=("z",),
                 out_layouts=layouts, plan=block)
    assert b["z"].layout == SOA
    np.testing.assert_array_equal(np.asarray(a["z"].data),
                                  np.asarray(b["z"].data))
    # flip the output native too
    a2 = g.launch({"x": fx, "f": ff}, config=PCFG, outputs=("z",),
                  out_layouts={"z": aosoa(4)}, plan=staged)
    b2 = g.launch({"x": fx, "f": ff}, config=PCFG, outputs=("z",),
                  out_layouts={"z": aosoa(4)}, plan=block)
    np.testing.assert_array_equal(np.asarray(a2["z"].data),
                                  np.asarray(b2["z"].data))


# -- alignment / eligibility errors --------------------------------------------

def test_block_view_misaligned_sal_raises(rng):
    """SAL not dividing the halo'd inner-plane count: a clear error naming
    the input, not silent corruption."""
    lat = (6, 4, 8)  # padded inner 60; sal=8 does not divide
    fx = Field.from_numpy(
        "x", rng.normal(size=(3, *lat)).astype(np.float32), lat, aosoa(8))
    _, block = _plans(2)
    with pytest.raises(ValueError, match="halo'd inner-plane"):
        _graph().launch({"x": fx}, config=PCFG, outputs=("z",), plan=block)


def test_block_view_without_aosoa_raises_loudly(rng):
    """No AoSoA in play: an *explicit* block view fails validation (there
    is no native lowering to run), both standalone and at launch."""
    lat = (6, 4, 8)
    fx = Field.from_numpy(
        "x", rng.normal(size=(3, *lat)).astype(np.float32), lat, SOA)
    _, block = _plans(2)
    with pytest.raises(ValueError, match="AoSoA"):
        block.validate(lattice=lat, stencil=True, layouts=[SOA])
    with pytest.raises(ValueError, match="AoSoA"):
        _graph().launch({"x": fx}, config=PCFG, outputs=("z",), plan=block)


def test_legacy_plans_without_view_resolve_to_staged(rng):
    """Backward compat: a hand-built plan that never set view= (the
    dataclass default is the 'auto' sentinel) launches exactly as it did
    before views became a stencil knob — the staged-nd lowering — on SOA
    inputs, on aligned AoSoA inputs (no silent strategy flip), and on
    *misaligned* AoSoA inputs where an explicit block view would be
    rejected."""
    g = _graph()
    legacy = LoweringPlan("pallas", bx=2, interpret=True)  # view defaulted
    staged, block = _plans(2)
    lat = (6, 4, 8)
    x = rng.normal(size=(3, *lat)).astype(np.float32)
    for lay in (SOA, aosoa(4), aosoa(8)):  # aosoa8: halo'd inner 60 % 8 != 0
        fx = Field.from_numpy("x", x, lat, lay)
        a = g.launch({"x": fx}, config=PCFG, outputs=("z",), plan=legacy)
        b = g.launch({"x": fx}, config=PCFG, outputs=("z",), plan=staged)
        np.testing.assert_array_equal(np.asarray(a["z"].data),
                                      np.asarray(b["z"].data),
                                      err_msg=lay.name)
    # ... while the explicit block twin on the misaligned layout refuses
    with pytest.raises(ValueError, match="halo'd inner-plane"):
        g.launch({"x": Field.from_numpy("x", x, lat, aosoa(8))},
                 config=PCFG, outputs=("z",), plan=block)


def test_block_view_misaligned_output_raises(rng):
    """Aligned AoSoA input but an AoSoA output whose SAL splits the interior
    slab rows: rejected with the output named."""
    lat = (6, 4, 8)  # interior inner 32: sal=8 ok for input? 60 % 8 != 0 ->
    # use sal 4 input (aligns) and sal 3 output (32 % 3 != 0)
    fx = Field.from_numpy(
        "x", rng.normal(size=(3, *lat)).astype(np.float32), lat, aosoa(4))
    _, block = _plans(2)
    with pytest.raises(ValueError, match="interior inner-plane"):
        _graph().launch({"x": fx}, config=PCFG, outputs=("z",),
                        out_layouts={"z": aosoa(3)}, plan=block)


# -- planning layer ------------------------------------------------------------

def test_candidate_view_twins_only_for_aosoa_inputs():
    """candidate_plans emits view='block' twins iff an input layout is
    AoSoA; the default (first) candidate is always staged-nd, so the
    default policy is untouched."""
    lat = (8, 4, 8)
    nsites = 8 * 4 * 8
    cfg = TargetConfig("pallas", vvl=128)
    with_a = plan_mod.candidate_plans(
        cfg, nsites=nsites, layouts=[aosoa(4)], stencil=True, lattice=lat)
    assert any(c.view == VIEW_BLOCK for c in with_a)
    assert with_a[0].view == VIEW_STAGED_ND  # default heuristic unchanged
    without = plan_mod.candidate_plans(
        cfg, nsites=nsites, layouts=[SOA], stencil=True, lattice=lat)
    assert not any(c.view == VIEW_BLOCK for c in without)
    # explicit gate overrides the layout heuristic
    gated = plan_mod.candidate_plans(
        cfg, nsites=nsites, layouts=[aosoa(4)], stencil=True, lattice=lat,
        block_view=False)
    assert not any(c.view == VIEW_BLOCK for c in gated)


def test_plan_candidates_for_skips_misaligned_block(rng):
    """tune.plan_candidates_for consults the real halo geometry: an AoSoA
    input whose SAL cannot tile the halo'd planes gets no block twins
    (rather than guaranteed-failing sweep candidates)."""
    from repro.core import tune

    lat = (6, 4, 8)
    g = _graph()
    aligned = {"x": Field.from_numpy(
        "x", rng.normal(size=(3, *lat)).astype(np.float32), lat, aosoa(4))}
    cands = tune.plan_candidates_for(g, aligned, config=PCFG,
                                     outputs=("z", "zt"))
    assert any(c.view == VIEW_BLOCK for c in cands)
    misaligned = {"x": aligned["x"].as_layout(aosoa(8))}  # 8 does not
    cands = tune.plan_candidates_for(g, misaligned, config=PCFG,  # divide 60
                                     outputs=("z", "zt"))
    assert not any(c.view == VIEW_BLOCK for c in cands)


def test_default_policy_stays_staged_nd(rng):
    """Bit-compat guard: with no plan given, an AoSoA stencil launch takes
    the pre-PR staged-nd lowering (view twins are tuner candidates, never
    the default)."""
    lat = (6, 4, 8)
    plan = plan_mod.default_plan(
        TargetConfig("pallas", vvl=64), nsites=6 * 4 * 8,
        layouts=[aosoa(4)], stencil=True, lattice=lat, halo="periodic")
    assert plan.view == VIEW_STAGED_ND


def test_adapt_plan_preserves_stencil_view():
    """A tuned/explicit native-block winner survives adapt_plan (this is
    how the persisted table flips a launch to native AoSoA); jnp stencil
    plans and site-local plans keep their forced views."""
    block = LoweringPlan("pallas", bx=2, halo="pre", view=VIEW_BLOCK)
    assert plan_mod.adapt_plan(block, stencil=True, halo="pre").view \
        == VIEW_BLOCK
    staged = LoweringPlan("pallas", bx=2, halo="pre", view=VIEW_STAGED_ND)
    assert plan_mod.adapt_plan(staged, stencil=True, halo="pre").view \
        == VIEW_STAGED_ND
    jplan = LoweringPlan("jnp", view=VIEW_BLOCK)
    assert plan_mod.adapt_plan(jplan, stencil=True, halo="periodic").view \
        == VIEW_STAGED_ND
    site = LoweringPlan("pallas", vvl=8, view=VIEW_STAGED_ND)
    assert plan_mod.adapt_plan(site, stencil=False, halo="periodic").view \
        == VIEW_BLOCK
    # the 'auto' dataclass default resolves to the pre-view-knob behavior
    auto = LoweringPlan("pallas", bx=2)
    assert auto.view == plan_mod.VIEW_AUTO
    assert plan_mod.adapt_plan(auto, stencil=True, halo="periodic").view \
        == VIEW_STAGED_ND
    assert plan_mod.adapt_plan(auto, stencil=False, halo="periodic").view \
        == VIEW_BLOCK


def test_sub_lattice_plan_forces_staged_nd():
    """Overlap sub-launch windows are SOA slices: the rebased slab plan
    must never claim the native-block view."""
    outer = LoweringPlan("pallas", bx=2, halo="overlap", view=VIEW_BLOCK)
    sub = plan_mod.sub_lattice_plan(outer, TargetConfig("pallas"), (4, 4, 8),
                                    halo="pre")
    assert sub.view == VIEW_STAGED_ND and sub.halo == "pre"


def test_describe_and_persisted_entry_record_view(tmp_path, monkeypatch):
    """Auditable winners: describe() tags native-block plans and a recorded
    tune-table entry round-trips the view."""
    from repro.core import tune

    monkeypatch.setenv(tune.ENV_VAR, str(tmp_path / "t.json"))
    tune.clear_table_cache()
    staged = LoweringPlan("pallas", bx=4, view=VIEW_STAGED_ND)
    block = LoweringPlan("pallas", bx=4, view=VIEW_BLOCK)
    assert staged.describe() != block.describe()
    assert "block" in block.describe()
    tune.record("k_view", block)
    tune.clear_table_cache()  # fresh-process view of the table
    got = tune.lookup("k_view")
    assert got == block and got.view == VIEW_BLOCK


def test_plan_key_distinguishes_layout_views(rng, tmp_path, monkeypatch):
    """A table tuned on one layout must not silently apply to another:
    plan keys incorporate the input layouts, so an AoSoA-keyed native-block
    winner misses for the SOA twin of the same launch."""
    from repro.core import tune

    monkeypatch.setenv(tune.ENV_VAR, str(tmp_path / "t.json"))
    tune.clear_table_cache()
    lat = (6, 4, 8)
    x = rng.normal(size=(3, *lat)).astype(np.float32)
    fa = Field.from_numpy("x", x, lat, aosoa(4))
    g = _graph()
    key_a = g.plan_key({"x": fa}, config=PCFG, outputs=("z", "zt"))
    key_s = g.plan_key({"x": fa.as_layout(SOA)}, config=PCFG,
                       outputs=("z", "zt"))
    assert key_a != key_s
    tune.record(key_a, LoweringPlan("pallas", bx=2, interpret=True,
                                    view=VIEW_BLOCK))
    assert tune.lookup(key_a) is not None
    assert tune.lookup(key_s) is None


def test_tuned_block_winner_degrades_on_misfit(rng, tmp_path, monkeypatch):
    """Tuning must never break a launch: a persisted native-block winner
    meeting an out_layouts override whose SAL cannot tile the interior
    degrades to the default plan (logged), instead of raising."""
    from repro.core import tune

    monkeypatch.setenv(tune.ENV_VAR, str(tmp_path / "t.json"))
    tune.clear_table_cache()
    lat = (6, 4, 8)
    x = rng.normal(size=(3, *lat)).astype(np.float32)
    fx = Field.from_numpy("x", x, lat, aosoa(4))
    g = _graph()
    _, block = _plans(2)
    key = g.plan_key({"x": fx}, config=PCFG, outputs=("z", "zt"),
                     lattice=lat)
    tune.record(key, block)
    tuned_cfg = TargetConfig("pallas", vvl=128, plan_policy="tuned")
    bad_out = {"z": aosoa(3)}  # 3 does not divide the interior inner 32
    got = g.launch({"x": fx}, config=tuned_cfg, outputs=("z", "zt"),
                   out_layouts=bad_out)
    want = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt"),
                    out_layouts=bad_out)
    np.testing.assert_array_equal(np.asarray(got["z"].data),
                                  np.asarray(want["z"].data))
    # an *explicit* misfit plan still fails loudly
    with pytest.raises(ValueError, match="interior inner-plane"):
        g.launch({"x": fx}, config=PCFG, outputs=("z", "zt"),
                 out_layouts=bad_out, plan=block)


def test_tuned_policy_applies_block_winner(rng, tmp_path, monkeypatch):
    """plan_policy='tuned' + a persisted native-block winner: the launch
    executes under the block view (probed via the launch cache — an
    explicit block-plan launch afterwards is a cache HIT, a staged one a
    miss) and stays bit-identical to the default policy."""
    from repro.core import tune

    monkeypatch.setenv(tune.ENV_VAR, str(tmp_path / "t.json"))
    tune.clear_table_cache()
    lat = (6, 4, 8)
    x = rng.normal(size=(3, *lat)).astype(np.float32)
    fx = Field.from_numpy("x", x, lat, aosoa(4))
    g = _graph()
    staged, block = _plans(2)
    key = g.plan_key({"x": fx}, config=PCFG, outputs=("z", "zt"))
    tune.record(key, block)

    tuned_cfg = TargetConfig("pallas", vvl=128, plan_policy="tuned")
    fuse.clear_cache()
    fuse.reset_stats()
    t = g.launch({"x": fx}, config=tuned_cfg, outputs=("z", "zt"))
    assert fuse.stats()["cache_misses"] == 1
    g.launch({"x": fx}, config=PCFG, outputs=("z", "zt"), plan=block)
    assert fuse.stats()["cache_hits"] == 1  # tuned launch == block view
    d = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt"), plan=staged)
    np.testing.assert_array_equal(np.asarray(t["z"].data),
                                  np.asarray(d["z"].data))
