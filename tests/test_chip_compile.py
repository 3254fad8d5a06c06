"""Ahead-of-time compile guard for the compiled TPU path, with no chip.

The TPU compiler is installed here and compiles for a v5e that is described,
not attached.  Interpret-mode tests cannot see what Mosaic refuses (a
dynamic_slice of a loaded value, an in-kernel reshape across tiled lattice
axes, a rank-1 reduction, more VMEM than the core has), so these tests
compile the main-path fused launches at the sizes ``chip_smoke.py`` runs:
the Ludwig LB step and LC chain at 128^3, and the MILC normal operator and
CG update at 24^3x48 — plus the plan axes that compile (split reductions,
bf16 storage, batched launches, y tiles).

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
    fp32 arrays and their shapes, returns the compiled executable."""
    from jax.sharding import SingleDeviceSharding

    dev = topo.devices[0]
    monkeypatch.setattr(plan_mod, "_device_kind", lambda: dev.device_kind)
    one_chip = SingleDeviceSharding(dev)

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
                for s in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
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
