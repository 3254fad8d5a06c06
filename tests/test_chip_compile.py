"""Ahead-of-time compile guard for the compiled TPU path, with no chip.

The TPU compiler is installed here and compiles for a v5e that is described,
not attached.  Interpret-mode tests cannot see what Mosaic refuses (a
dynamic_slice of a loaded value, an in-kernel reshape across tiled lattice
axes, a rank-1 reduction, more VMEM than the core has), so these tests
compile the main-path fused launches at the sizes ``chip_smoke.py`` runs:
the Ludwig LB step and LC chain at 128^3, and the MILC normal operator and
CG update at 24^3x48 — plus the plan axes that compile (split reductions,
bf16 storage, batched launches, y tiles), and the whole sharded Ludwig
step of the four-chip benchmark cell on the described 2x2 mesh.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and test workers import every
test file.  ``jax.default_backend()`` stays "cpu" here, so the tests pass
``interpret=False`` explicitly and tell the planner which device kind its
VMEM table should read.
"""

import math

import pytest

import jax
import jax.numpy as jnp

from repro.core import BatchedField, Field, LoweringPlan, SOA, TargetConfig
from repro.core import plan as plan_mod

COMPILED = TargetConfig("pallas", interpret=False)


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-device compile cannot be read back from a persistent
        # cache; keep it out of any cache the environment configured
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield desc
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture
def chip(topo, monkeypatch):
    """A compile helper for one described v5e chip: takes a function of
    fp32 arrays and their shapes, returns the compiled executable.

    ``row_major`` fixes the arguments' and results' layout to row-major,
    as arrays reach a launch inside a step: otherwise the compiler may lay
    out an entry argument whose y is not a multiple of 8 with y minor, and
    copy it to and from the kernel's row-major layout."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    dev = topo.devices[0]
    monkeypatch.setattr(plan_mod, "_device_kind", lambda: dev.device_kind)
    one_chip = SingleDeviceSharding(dev)

    def compile_(fn, *shapes, row_major=False):
        def place(s):
            return (Format(Layout(tuple(range(len(s)))), one_chip)
                    if row_major else one_chip)

        args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=place(s))
                for s in shapes]
        jitted = (jax.jit(fn, out_shardings=place(shapes[0])) if row_major
                  else jax.jit(fn))
        compiled = jitted.lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel
        return compiled

    return compile_


def _lb_launch(cfg, plan=None, target=None):
    from repro.apps.ludwig import driver as lud

    lat = cfg.lattice
    graph = lud.lb_step_graph(cfg)

    def fn(dist, force):
        out = graph.launch(
            {"dist": Field("dist", 19, lat, SOA, dist),
             "force": Field("force", 3, lat, SOA, force)},
            config=target or COMPILED, outputs=("dist2", "u"), plan=plan)
        return out["dist2"].data, out["u"].data

    n = math.prod(lat)
    return fn, (19, n), (3, n)


def _wilson_launch(lat, plan=None, batch=0):
    from repro.apps.milc.cg import wilson_normal_graph

    graph = wilson_normal_graph(0.12)

    def fn(p, u):
        pf = (BatchedField("p", batch, 24, lat, SOA, p) if batch
              else Field("p", 24, lat, SOA, p))
        out = graph.launch({"p": pf, "u": Field("u", 72, lat, SOA, u)},
                           config=COMPILED, outputs=("ap", "pap"), plan=plan)
        return out["ap"].data, out["pap"]

    n = math.prod(lat)
    return fn, ((batch, 24, n) if batch else (24, n)), (72, n)


# -- the main path at chip_smoke sizes -------------------------------------------

def test_lb_step_128_default_plan_compiles(chip):
    """The fused LB step at 128^3: the default plan streams x-slab DMA
    windows that fit the v5e budget, where whole staging would not."""
    from repro.apps.ludwig import driver as lud

    cfg = lud.LudwigConfig(lattice=(128, 128, 128))
    views = (((19, 1, 4), (3, 1, 4)), ((19, 4), (3, 4)))
    plan = plan_mod.default_plan(
        COMPILED, nsites=128 ** 3, layouts=[SOA], stencil=True,
        lattice=cfg.lattice, vmem_views=views)
    budget = plan_mod.resolved_vmem_bytes(COMPILED)
    assert budget == plan_mod.TPU_VMEM_BYTES["TPU v5 lite"] // 2
    fp = plan_mod.estimate_vmem_bytes(plan, lattice=cfg.lattice,
                                      in_views=views[0], out_views=views[1],
                                      tpu=True)
    assert fp <= budget
    # what the untiled whole-staged kernel would have needed
    assert 2 * (19 + 3) * 130 * 136 * 256 * 4 > budget
    chip(*_lb_launch(cfg))


def test_wilson_normal_24x48_default_plan_compiles_tiled(chip):
    lat = (24, 24, 24, 48)
    views = (((24, 2, 4), (72, 2, 4)), ((24, 4),))
    plan = plan_mod.default_plan(
        COMPILED, nsites=math.prod(lat), layouts=[SOA], stencil=True,
        lattice=lat, vmem_views=views)
    assert plan.by or plan.bz
    assert plan.bz in (0, 8, 16, 24)  # whole sublane tiles on z
    chip(*_wilson_launch(lat))


def test_cg_update_24x48_compiles(chip):
    from repro.apps.milc.cg import cg_update_graph

    lat = (24, 24, 24, 48)
    graph = cg_update_graph(24)

    def fn(x, r, p, ap):
        f = lambda name, a: Field(name, 24, lat, SOA, a)  # noqa: E731
        out = graph.launch(
            {"x": f("x", x), "r": f("r", r), "p": f("p", p),
             "ap": f("ap", ap)},
            scalars={"alpha": 0.3, "neg_alpha": -0.3}, config=COMPILED,
            outputs=("x_new", "r_new", "rr"))
        return out["x_new"].data, out["r_new"].data, out["rr"]

    shape = (24, math.prod(lat))
    chip(fn, shape, shape, shape, shape)


def test_ludwig_lc_chain_128_compiles(chip):
    """A site-local Ludwig chain (molecular field -> BE rhs -> Q update)."""
    from repro.apps.ludwig import driver as lud

    lat = (128, 128, 128)
    graph = lud.lc_chain_graph(lud.LudwigConfig(lattice=lat))

    def fn(q, lapq, w, adv):
        f = lambda name, nc, a: Field(name, nc, lat, SOA, a)  # noqa: E731
        out = graph.launch(
            {"q": f("q", 5, q), "lapq": f("lapq", 5, lapq),
             "w": f("w", 9, w), "adv": f("adv", 5, adv)},
            config=COMPILED, outputs=("q_new",))
        return out["q_new"].data

    n = math.prod(lat)
    chip(fn, (5, n), (5, n), (9, n), (5, n))


@pytest.mark.parametrize("lat", [(128, 128, 128), (8, 16, 192),
                                 (196, 196, 192)],
                         ids=["128cube", "z192", "halo196"])
@pytest.mark.parametrize("chain", ["chem_stress", "lc_update"])
def test_ludwig_nd_site_local_chain_compiles(chip, chain, lat):
    """The site-local Ludwig chains on nd-stored fields, lowered on the nd
    block grid under the default VMEM budget: no relayout around the
    kernel, also where the lane axis (192) is not a multiple of 128, and
    on the four-chip cell's halo'd local lattice, whose y (196) is not a
    multiple of 8."""
    from repro.apps.ludwig import driver as lud
    from repro.core import telemetry

    cfg = lud.LudwigConfig(lattice=lat)
    graph, spec, outputs = {
        "chem_stress": (lud.chem_stress_graph(cfg),
                        {"q": 5, "lapq": 5, "dq": 15}, ("h", "sigma")),
        "lc_update": (lud.lc_update_graph(cfg),
                      {"q": 5, "h": 5, "w": 9, "adv": 5}, ("q_new",)),
    }[chain]
    names = list(spec)

    def fn(*arrs):
        out = graph.launch(
            {n: Field.from_nd(n, a) for n, a in zip(names, arrs)},
            config=COMPILED, outputs=outputs)
        return tuple(out[o].data for o in outputs)

    views = (tuple((nc, 0, 4) for nc in spec.values()),
             tuple((graph._produced()[o][0], 4) for o in outputs))
    plan = plan_mod.default_plan(
        COMPILED, nsites=math.prod(lat), layouts=[SOA], stencil=True,
        lattice=lat, vmem_views=views)
    fp = plan_mod.estimate_vmem_bytes(plan, lattice=lat, in_views=views[0],
                                      out_views=views[1], tpu=True)
    assert fp <= plan_mod.resolved_vmem_bytes(COMPILED)
    before = telemetry.counter_value("fuse.nd_site_local")
    compiled = chip(fn, *[(nc,) + lat for nc in spec.values()],
                    row_major=True)
    assert telemetry.counter_value("fuse.nd_site_local") == before + 1
    assert "copy(" not in compiled.as_text()


def test_lb_step_pre_halo_nd_194_compiles(chip):
    """The fused LB launch as the four-chip step feeds it: nd dist and
    force pre-padded by the halo ring to (194, 194, 194) under
    halo="pre", read in place (no relayout) and returned nd."""
    from repro.apps.ludwig import driver as lud
    from repro.core import telemetry

    graph = lud.lb_step_graph(lud.LudwigConfig(lattice=(192, 192, 192)))

    def fn(dist, force):
        out = graph.launch(
            {"dist": Field.from_nd("dist", dist),
             "force": Field.from_nd("force", force)},
            config=COMPILED, outputs=("dist2", "u"), halo="pre")
        assert out["dist2"].nd and out["u"].nd
        return out["dist2"].data, out["u"].data

    before = telemetry.counter_value("field.relayout")
    compiled = chip(fn, (19, 194, 194, 194), (3, 194, 194, 194),
                    row_major=True)
    assert telemetry.counter_value("field.relayout") == before
    assert "copy(" not in compiled.as_text()


def test_ludwig_sharded_step_2x2_compiles(topo, monkeypatch):
    """The whole decomposed Ludwig step of the four-chip benchmark cell:
    384x384x192 over the described 2x2 mesh, 192^3 per chip, halo="pre".
    The exchanges lower to collective-permutes beside the Mosaic kernels,
    the step fits well inside a chip, and the halo counters give the
    bytes each chip sends: q at width 2 on its (5, 196, 196, 192) halo'd
    array, dist and the force at width 1 padded in every dim
    ((19|3, 194, 194, 194)), u at width 1 ((3, 194, 194, 192)); each
    exchanged dim sends its two faces, x first, then y with the x halo.
    The local Fields are stored nd, so the step stages no relayout."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.apps.ludwig import driver as lud
    from repro.core import telemetry
    from repro.lattice import Domain

    monkeypatch.setattr(plan_mod, "_device_kind",
                        lambda: topo.devices[0].device_kind)
    lat = (384, 384, 192)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("x", "y"))
    dom = Domain(global_shape=lat, mesh=mesh, dim_axes=("x", "y", None),
                 halo=2)
    cfg = lud.LudwigConfig(lattice=lat, target=COMPILED)
    args = [jax.ShapeDtypeStruct((nc,) + lat, jnp.float32,
                                 sharding=dom.sharding()) for nc in (19, 5)]
    before = telemetry.counters("halo.")
    nd_before = telemetry.counter_value("fuse.nd_site_local")
    relayouts_before = telemetry.counter_value("field.relayout")
    lowered = lud.make_sharded_step(cfg, dom, halo="pre").lower(*args)
    after = telemetry.counters("halo.")
    assert after["halo.exchanges"] - before.get("halo.exchanges", 0) == 8
    # the local Fields are nd: both chains on the nd grid, no relayout
    assert telemetry.counter_value("fuse.nd_site_local") == nd_before + 2
    assert telemetry.counter_value("field.relayout") == relayouts_before

    def faces(ncomp, w, x, y, z):
        return 2 * ncomp * w * (y * z + x * z) * 4

    want = (faces(5, 2, 196, 196, 192) + faces(19, 1, 194, 194, 194)
            + faces(3, 1, 194, 194, 194) + faces(3, 1, 194, 194, 192))
    assert want == 21_056_896
    assert after["halo.bytes"] - before.get("halo.bytes", 0) == want

    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert per_device < 8e9


# -- plan axes off the default path ------------------------------------------------

def test_rsplit_wilson_normal_compiles(chip):
    chip(*_wilson_launch((8, 8, 8, 8),
                         plan=LoweringPlan("pallas", bx=1, by=1, rsplit=2)))


def test_bf16_storage_lb_step_compiles(chip):
    from repro.apps.ludwig import driver as lud

    cfg = lud.LudwigConfig(lattice=(128, 128, 128), target=COMPILED,
                           storage="bfloat16")
    chip(*_lb_launch(cfg, target=lud._lb_target(cfg)))


def test_batched_wilson_normal_compiles(chip):
    chip(*_wilson_launch((8, 8, 8, 8), batch=4))


def test_y_tiled_lb_step_compiles(chip):
    from repro.apps.ludwig import driver as lud

    cfg = lud.LudwigConfig(lattice=(128, 128, 128))
    chip(*_lb_launch(cfg, plan=LoweringPlan("pallas", bx=2, by=32)))


def test_block_view_is_refused(chip):
    """The native AoSoA block view is the one axis that does not compile:
    its kernel relayouts (rows, ncomp, sal) blocks into the nd window, a
    shape cast across tiled axes that Mosaic cannot lay out.  The tuner
    offers no block twin to compiled plans for this reason; when this
    starts to compile, restore the twin and turn this test around."""
    from repro.apps.ludwig import driver as lud
    from repro.core import aosoa

    lat, lay = (16, 16, 16), aosoa(4)
    graph = lud.lb_step_graph(lud.LudwigConfig(lattice=lat))

    def fn(dist, force):
        out = graph.launch(
            {"dist": Field("dist", 19, lat, lay, dist),
             "force": Field("force", 3, lat, lay, force)},
            config=COMPILED, outputs=("dist2", "u"),
            plan=LoweringPlan("pallas", bx=2, view="block"))
        return out["dist2"].data, out["u"].data

    n = math.prod(lat)
    with pytest.raises(Exception, match="shape cast"):
        chip(fn, (n // 4, 19, 4), (n // 4, 3, 4))
    assert not any(
        c.view == "block" for c in plan_mod.candidate_plans(
            COMPILED, nsites=n, layouts=[lay], stencil=True, lattice=lat))
