"""Tiled y/z stencil lowering (``LoweringPlan.by``/``bz``) + VMEM budget.

The paper's premise is that lattice kernels saturate memory bandwidth at
*production* local volumes (§3.2, §5); whole-staging bounds the shard by
on-chip memory instead.  These tests pin the contract that removes that
bound: a tiled plan appends sequential y/z grid axes whose per-program
window is the halo'd tile — **bitwise identical** fields to whole-staging
on every engine path (staged-nd and native-block views, periodic/pre/
overlap halos, batched stacks, split reductions), tolerance-equal fp sum
reductions (the rsplit contract: per-tile fold order), and exact max/int
reductions.  Plan-layer satellites: by/bz default to 0 (bit-compat with
every persisted plan), describe() tags tiles and reports the footprint
estimate, validate() rejects non-dividing extents with a clear error, the
VMEM byte budget (TargetConfig.vmem_bytes / $TARGETDP_VMEM_BYTES) makes
default_plan auto-tile over-budget launches and candidate_plans skip+log
over-budget candidates, and sub_lattice_plan inherits tiles into overlap
sub-launches whenever they still divide.
"""

import dataclasses
import logging

import numpy as np
import pytest

from repro.core import (
    Field, LaunchGraph, LoweringPlan, SOA, TargetConfig, aosoa,
)
from repro.core import plan as plan_mod
from repro.core.field import BatchedField
from repro.core.plan import VIEW_BLOCK
from repro.core.stencil import tile_boxes

PCFG = TargetConfig("pallas", vvl=128)
LAT = (6, 4, 8)


def _scale(v, *, a):
    return {"y": a * v["x"]}


def _lap(v, gather, *, c):
    return {"z": (c * v["y"] + gather("y", (1, 0, 0))
                  + gather("y", (0, -1, 0))) ** 2}


def _graph():
    return (LaunchGraph("tile_g")
            .add(_scale, {"x": "x"}, {"y": 3}, params=dict(a=2.0))
            .add_stencil(_lap, {"y": "y"}, {"z": 3}, width=1,
                         params=dict(c=-2.0))
            .add_reduce("z", op="sum", name="zt")
            .add_reduce("z", op="max", name="zm"))


def _field(rng, layout=SOA, lat=LAT):
    x = rng.normal(size=(3, *lat)).astype(np.float32)
    return Field.from_numpy("x", x, lat, layout)


def _check(a, b):
    """Fields bitwise; fp sums tolerance-equal (per-tile fold order); max
    exact."""
    np.testing.assert_array_equal(np.asarray(a["z"].data),
                                  np.asarray(b["z"].data))
    np.testing.assert_allclose(np.asarray(a["zt"]), np.asarray(b["zt"]),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(a["zm"]), np.asarray(b["zm"]))


# -- lowering identity ---------------------------------------------------------

@pytest.mark.parametrize("by,bz", [(2, 0), (0, 4), (2, 4), (1, 2), (4, 8)])
def test_tiled_matches_untiled(by, bz, rng):
    g = _graph()
    fx = _field(rng)
    base = LoweringPlan("pallas", bx=2, interpret=True)
    a = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt", "zm"), plan=base)
    b = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt", "zm"),
                 plan=dataclasses.replace(base, by=by, bz=bz))
    _check(a, b)


@pytest.mark.parametrize("halo", ["pre", "overlap"])
def test_tiled_matches_untiled_pre_and_overlap(halo, rng):
    import jax.numpy as jnp
    from repro.core.stencil import halo_pad

    g = _graph()
    x = rng.normal(size=(3, *LAT)).astype(np.float32)
    xh = np.asarray(halo_pad(jnp.asarray(x), 1, (1, 2, 3)))
    fxh = Field.from_numpy("x", xh, tuple(s + 2 for s in LAT), SOA)
    base = LoweringPlan("pallas", bx=2, halo=halo, interpret=True)
    a = g.launch({"x": fxh}, config=PCFG, outputs=("z", "zt", "zm"),
                 halo=halo, plan=base)
    b = g.launch({"x": fxh}, config=PCFG, outputs=("z", "zt", "zm"),
                 halo=halo, plan=dataclasses.replace(base, by=2, bz=4))
    _check(a, b)


def test_tiled_block_view_matches_untiled(rng):
    """view='block' composes with tiles: the tile is cut from the unpacked
    VMEM window, so edges never split a short array."""
    g = _graph()
    fx = _field(rng, layout=aosoa(4))
    base = LoweringPlan("pallas", bx=2, interpret=True, view=VIEW_BLOCK)
    a = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt", "zm"), plan=base)
    b = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt", "zm"),
                 plan=dataclasses.replace(base, by=2, bz=4))
    assert a["z"].layout == aosoa(4)
    # tiled outputs degrade to canonical tile writes but the requested
    # layout survives packing after the call
    assert b["z"].layout == aosoa(4)
    _check(a, b)


def test_tiled_composes_with_rsplit(rng):
    g = _graph()
    fx = _field(rng)
    base = LoweringPlan("pallas", bx=1, interpret=True)
    a = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt", "zm"), plan=base)
    b = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt", "zm"),
                 plan=dataclasses.replace(base, rsplit=2, by=2, bz=4))
    _check(a, b)


def test_tiled_batched_matches_untiled(rng):
    g = _graph()
    xs = rng.normal(size=(4, 3, *LAT)).astype(np.float32)
    bf = BatchedField.from_canonical("x", xs, LAT, SOA)
    base = LoweringPlan("pallas", bx=2, interpret=True)
    a = g.launch({"x": bf}, config=PCFG, outputs=("z", "zt", "zm"), plan=base)
    b = g.launch({"x": bf}, config=PCFG, outputs=("z", "zt", "zm"),
                 plan=dataclasses.replace(base, by=2, bz=4))
    _check(a, b)


def test_tiled_lb_step_matches_untiled(rng):
    """The production fused LB step under tiles."""
    from repro.kernels.lb_propagation.ops import collide_propagate_graph

    lat = (4, 14, 16)
    f0 = (1.0 + 0.1 * rng.normal(size=(19, *lat))).astype(np.float32)
    frc = (0.01 * rng.normal(size=(3, *lat))).astype(np.float32)
    ins = {"dist": Field.from_numpy("dist", f0, lat, SOA),
           "force": Field.from_numpy("force", frc, lat, SOA)}
    g = collide_propagate_graph(0.8)
    base = LoweringPlan("pallas", bx=2, interpret=True)
    a = g.launch(ins, config=PCFG, outputs=("dist2",), plan=base)
    b = g.launch(ins, config=PCFG, outputs=("dist2",),
                 plan=dataclasses.replace(base, by=7, bz=4))
    np.testing.assert_array_equal(np.asarray(a["dist2"].data),
                                  np.asarray(b["dist2"].data))


# -- the compiled path's DMA kernel, on the TPU interpreter --------------------

@pytest.fixture
def dma_on_interpreter(monkeypatch):
    """Run compiled (interpret=False) plans — the double-buffered DMA
    kernel every compiled stencil launch takes — on Pallas' TPU
    interpreter, which models HBM, VMEM and async copies on the CPU.
    Plans with interpret=True keep the plain interpreter (the whole-staged
    reference)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.core import fuse

    real = pl.pallas_call

    def on_tpu_interpreter(*args, interpret=False, compiler_params=None,
                           **kw):
        return real(*args, interpret=interpret or pltpu.InterpretParams(),
                    **kw)

    monkeypatch.setattr(pl, "pallas_call", on_tpu_interpreter)
    monkeypatch.setattr(plan_mod, "_device_kind", lambda: "TPU v5 lite")
    fuse.clear_cache()
    yield
    fuse.clear_cache()


@pytest.mark.parametrize("lat,plan_kw,batch", [
    ((6, 4, 8), dict(bx=2), 0),                 # x-slab windows, untiled
    ((4, 16, 8), dict(bx=1, by=8), 0),          # sublane tile, window 10->16
    ((6, 4, 8), dict(bx=1, rsplit=2), 0),       # split reduction grid
    ((6, 4, 8), dict(bx=2), 3),                 # batch axis in the chain
])
def test_dma_kernel_matches_staged(lat, plan_kw, batch, rng,
                                   dma_on_interpreter):
    g = _graph()
    if batch:
        xs = rng.normal(size=(batch, 3, *lat)).astype(np.float32)
        fx = BatchedField.from_canonical("x", xs, lat, SOA)
    else:
        fx = _field(rng, lat=lat)
    staged = LoweringPlan("pallas", interpret=True, **plan_kw)
    a = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt", "zm"),
                 plan=staged)
    b = g.launch({"x": fx}, config=PCFG, outputs=("z", "zt", "zm"),
                 plan=dataclasses.replace(staged, interpret=False))
    _check(a, b)


def test_dma_kernel_wilson_normal_4d(dma_on_interpreter):
    """The MILC normal operator (4-D lattice, ring-2 halo, 72-comp gauge
    input) through the DMA kernel, y-tiled: bitwise the staged kernel."""
    from repro.apps.milc import MilcConfig, init_problem
    from repro.apps.milc.cg import wilson_normal_graph

    u, b = init_problem(MilcConfig(lattice=(4, 4, 4, 8)), seed=0)
    g = wilson_normal_graph(0.12)
    staged = LoweringPlan("pallas", bx=2, by=2, interpret=True)
    a = g.launch({"p": b, "u": u}, config=PCFG, outputs=("ap", "pap"),
                 plan=staged)
    d = g.launch({"p": b, "u": u}, config=PCFG, outputs=("ap", "pap"),
                 plan=dataclasses.replace(staged, interpret=False))
    np.testing.assert_array_equal(np.asarray(a["ap"].data),
                                  np.asarray(d["ap"].data))
    np.testing.assert_allclose(np.asarray(a["pap"]), np.asarray(d["pap"]),
                               rtol=1e-6)


# -- plan axis: defaults, describe, validate, persistence ----------------------

def test_by_bz_default_bit_compat():
    """Persisted plans predate by/bz: from_json without them loads the
    untiled default and round-trips."""
    p = LoweringPlan.from_json(
        {"engine": "pallas", "vvl": 64, "bx": 2, "interpret": True})
    assert (p.by, p.bz) == (0, 0)
    q = LoweringPlan("pallas", bx=2, by=2, bz=4)
    assert LoweringPlan.from_json(q.to_json()) == q


def test_describe_tags_tiles_and_footprint():
    p = LoweringPlan("pallas", bx=2, by=2, bz=4)
    d = p.describe()
    assert "/ty2" in d and "/tz4" in d
    assert "KiB/prog" in p.describe(footprint=48 * 1024)
    assert "KiB/prog" not in d
    assert "/ty" not in LoweringPlan("pallas", bx=2).describe()


def test_validate_rejects_bad_tiles():
    n = int(np.prod(LAT))
    with pytest.raises(ValueError, match="by"):
        LoweringPlan("pallas", bx=2, by=3).validate(
            nsites=n, lattice=LAT, stencil=True)
    with pytest.raises(ValueError, match="bz"):
        LoweringPlan("pallas", bx=2, bz=5).validate(
            nsites=n, lattice=LAT, stencil=True)
    with pytest.raises(ValueError):
        LoweringPlan("jnp", by=2).validate(nsites=n, lattice=LAT,
                                           stencil=True)
    with pytest.raises(ValueError):  # site-local chains have no grid tiles
        LoweringPlan("pallas", by=2).validate(nsites=n, stencil=False)
    # dividing tiles pass
    LoweringPlan("pallas", bx=2, by=2, bz=4).validate(
        nsites=n, lattice=LAT, stencil=True)


def test_tile_boxes_cover_and_errors():
    boxes = tile_boxes(LAT, 2, 2, 4)
    assert len(boxes) == 3 * 2 * 2
    sites = set()
    for box in boxes:
        import itertools
        for pt in itertools.product(*[range(s, s + e) for s, e in box]):
            assert pt not in sites
            sites.add(pt)
    assert len(sites) == int(np.prod(LAT))
    with pytest.raises(ValueError, match="divide"):
        tile_boxes(LAT, 2, 3, 0)


# -- VMEM budget ---------------------------------------------------------------

IN_VIEWS = ((3, 1, 4),)   # (ncomp, ring, itemsize)
OUT_VIEWS = ((3, 4),)


def test_estimate_vmem_bytes_model():
    lat = (16, 32, 32)
    untiled = plan_mod.estimate_vmem_bytes(
        LoweringPlan("pallas", bx=1), lattice=lat,
        in_views=IN_VIEWS, out_views=OUT_VIEWS)
    # whole halo'd input + one output slab
    assert untiled == 3 * 18 * 34 * 34 * 4 + 3 * 32 * 32 * 4
    tiled = plan_mod.estimate_vmem_bytes(
        LoweringPlan("pallas", bx=1, by=4, bz=4), lattice=lat,
        in_views=IN_VIEWS, out_views=OUT_VIEWS)
    # two double-buffered windows + one output tile: tile-bounded
    assert tiled == 2 * 3 * 3 * 6 * 6 * 4 + 3 * 4 * 4 * 4
    assert tiled < untiled


def test_choose_tiles():
    lat = (16, 32, 32)
    big = 10 ** 9
    assert plan_mod.choose_tiles(
        lat, 1, in_views=IN_VIEWS, out_views=OUT_VIEWS,
        vmem_bytes=big) == (0, 0)
    by, bz = plan_mod.choose_tiles(
        lat, 1, in_views=IN_VIEWS, out_views=OUT_VIEWS,
        vmem_bytes=64 * 1024)
    assert by or bz
    assert (not by or lat[1] % by == 0) and (not bz or lat[2] % bz == 0)
    p = LoweringPlan("pallas", bx=1, by=by, bz=bz)
    assert plan_mod.estimate_vmem_bytes(
        p, lattice=lat, in_views=IN_VIEWS,
        out_views=OUT_VIEWS) <= 64 * 1024
    # hopeless budget: best-effort finest tile, never an exception
    assert plan_mod.choose_tiles(
        lat, 1, in_views=IN_VIEWS, out_views=OUT_VIEWS,
        vmem_bytes=16) == (1, 1)


def test_resolved_vmem_bytes_precedence(monkeypatch):
    monkeypatch.delenv(plan_mod.VMEM_ENV, raising=False)
    assert plan_mod.resolved_vmem_bytes(PCFG) is None
    monkeypatch.setenv(plan_mod.VMEM_ENV, str(1 << 20))
    assert plan_mod.resolved_vmem_bytes(PCFG) == 1 << 20
    explicit = dataclasses.replace(PCFG, vmem_bytes=1 << 16)
    assert plan_mod.resolved_vmem_bytes(explicit) == 1 << 16
    assert TargetConfig("pallas").resolved_vmem_bytes() == 1 << 20
    monkeypatch.setenv(plan_mod.VMEM_ENV, "not-a-number")
    assert plan_mod.resolved_vmem_bytes(PCFG) is None
    # 0 = explicitly unbounded
    assert plan_mod.resolved_vmem_bytes(
        dataclasses.replace(PCFG, vmem_bytes=0)) is None


def test_default_plan_auto_tiles_over_budget(monkeypatch):
    """The acceptance demo: a lattice whose whole-staging exceeds the
    budget gets a *tiled* default plan, and that plan runs to completion
    bit-identically to the unbudgeted default."""
    monkeypatch.delenv(plan_mod.VMEM_ENV, raising=False)
    lat = (16, 32, 32)
    nsites = int(np.prod(lat))
    kw = dict(nsites=nsites, layouts=[SOA], stencil=True, lattice=lat,
              halo="periodic", vmem_views=(IN_VIEWS, OUT_VIEWS))
    free = plan_mod.default_plan(PCFG, **kw)
    assert (free.by, free.bz) == (0, 0)  # no budget => pre-PR plans
    monkeypatch.setenv(plan_mod.VMEM_ENV, str(64 * 1024))
    tight = plan_mod.default_plan(PCFG, **kw)
    assert tight.by or tight.bz
    fp = plan_mod.estimate_vmem_bytes(
        tight, lattice=lat, in_views=IN_VIEWS, out_views=OUT_VIEWS)
    assert fp <= 64 * 1024

    rng = np.random.default_rng(0)
    g = (LaunchGraph("budget_demo")
         .add(_scale, {"x": "x"}, {"y": 3}, params=dict(a=2.0))
         .add_stencil(_lap, {"y": "y"}, {"z": 3}, width=1,
                      params=dict(c=-2.0)))
    fx = _field(rng, lat=lat)
    run = dataclasses.replace(tight, interpret=True)
    got = g.launch({"x": fx}, config=PCFG, outputs=("z",), plan=run)
    ref = g.launch({"x": fx}, config=PCFG, outputs=("z",),
                   plan=dataclasses.replace(free, interpret=True))
    np.testing.assert_array_equal(np.asarray(got["z"].data),
                                  np.asarray(ref["z"].data))


def test_candidate_plans_skip_and_log_over_budget(monkeypatch, caplog):
    monkeypatch.setenv(plan_mod.VMEM_ENV, str(64 * 1024))
    lat = (16, 32, 32)
    with caplog.at_level(logging.INFO, logger="repro.core.plan"):
        cands = plan_mod.candidate_plans(
            PCFG, nsites=int(np.prod(lat)), layouts=[SOA], stencil=True,
            lattice=lat, halo="periodic",
            vmem_views=(IN_VIEWS, OUT_VIEWS))
    assert cands  # never an empty sweep
    for c in cands:
        if c.engine != "pallas":
            continue
        assert c.by or c.bz, f"over-budget untiled candidate kept: {c}"
    skips = [r for r in caplog.records if "exceeds budget" in r.message]
    assert skips and "KiB/prog" in skips[0].getMessage()


def test_launch_feeds_budget_to_default_plan(monkeypatch, rng):
    """End to end through LaunchGraph.launch: under a tiny env budget the
    default-policy launch lowers tiled (and still matches the jnp oracle)."""
    from repro.core import fuse

    monkeypatch.setenv(plan_mod.VMEM_ENV, str(64 * 1024))
    fuse.clear_cache()
    lat = (16, 32, 32)
    g = (LaunchGraph("budget_launch")
         .add(_scale, {"x": "x"}, {"y": 3}, params=dict(a=2.0))
         .add_stencil(_lap, {"y": "y"}, {"z": 3}, width=1,
                      params=dict(c=-2.0)))
    fx = _field(rng, lat=lat)
    got = g.launch({"x": fx}, config=PCFG, outputs=("z",))
    want = g.launch({"x": fx}, config=TargetConfig("jnp"), outputs=("z",))
    np.testing.assert_allclose(got["z"].to_numpy(), want["z"].to_numpy(),
                               rtol=1e-5, atol=1e-5)


# -- overlap inheritance -------------------------------------------------------

def test_sub_lattice_plan_inherits_dividing_tiles():
    outer = LoweringPlan("pallas", bx=2, halo="overlap", by=2, bz=4)
    sub = plan_mod.sub_lattice_plan(outer, PCFG, (4, 4, 8))
    assert (sub.by, sub.bz) == (2, 4)
    assert sub.halo == "pre"
    # thin boundary slab: y no longer divides -> tile drops to whole-axis
    thin = plan_mod.sub_lattice_plan(outer, PCFG, (1, 3, 8))
    assert (thin.by, thin.bz) == (0, 4)


def test_tune_candidates_carry_budget(monkeypatch, rng):
    """plan_candidates_for derives vmem_views from the graph's ring
    analysis, so the sweep set under a tight budget is tiled-only."""
    from repro.core import tune

    monkeypatch.setenv(plan_mod.VMEM_ENV, str(64 * 1024))
    lat = (16, 32, 32)
    g = (LaunchGraph("budget_tune")
         .add(_scale, {"x": "x"}, {"y": 3}, params=dict(a=2.0))
         .add_stencil(_lap, {"y": "y"}, {"z": 3}, width=1,
                      params=dict(c=-2.0)))
    fx = _field(rng, lat=lat)
    cands = tune.plan_candidates_for(
        g, {"x": fx}, config=PCFG, outputs=("z",))
    assert cands
    for c in cands:
        if c.engine == "pallas":
            assert c.by or c.bz
