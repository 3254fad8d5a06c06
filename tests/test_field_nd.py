"""nd-stored SoA Fields: ``(ncomp, *lattice)`` data with the site axes kept.

An SoA Field stored nd has the same row-major order as the flat
``(ncomp, nsites)`` form, so every view of it equals the flat Field's; what
changes is that stencils and nd-grid launches read it with no relayout.
These tests pin the Field views, the reductions, the launches (site-local
chains on the nd grid, the fused LB stencil launch) against flat inputs,
the Ludwig step on either storage, and the trace-time counters that say
how often a flat<->nd relayout is still traced (``field.relayout``) and how
often a site-local launch lowers on the nd grid (``fuse.nd_site_local``).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Field, LoweringPlan, SOA, TargetConfig, aosoa, target_max, target_sum,
    telemetry, tune,
)
from repro.apps.ludwig import driver as lud

LAT = (4, 6, 8)
JNP = TargetConfig("jnp")
PALLAS = TargetConfig("pallas", vvl=128)


def _arr(rng, ncomp=3, lat=LAT, scale=1.0, offset=0.0):
    return (offset + scale * rng.normal(size=(ncomp,) + lat)).astype(
        np.float32)


def _relayouts(fn, *args):
    """``fn(*args)`` and the flat<->nd relayouts it traced."""
    before = telemetry.counter_value("field.relayout")
    out = fn(*args)
    return out, telemetry.counter_value("field.relayout") - before


# -- the Field views -----------------------------------------------------------

def _view_canonical(f, a):
    np.testing.assert_array_equal(np.asarray(f.canonical()),
                                  a.reshape(a.shape[0], -1))


def _view_canonical_nd(f, a):
    nd, moved = _relayouts(f.canonical_nd)
    assert nd is f.data and moved == 0
    np.testing.assert_array_equal(np.asarray(nd), a)


def _view_to_numpy(f, a):
    np.testing.assert_array_equal(f.to_numpy(), a)


def _view_with_canonical(f, a):
    for new in (2 * a, (2 * a).reshape(a.shape[0], -1)):
        g = f.with_canonical(jnp.asarray(new))
        assert g.nd and g.data.shape == a.shape
        np.testing.assert_array_equal(g.to_numpy(), 2 * a)


def _view_as_layout(f, a):
    flat = Field.from_canonical("x", a, LAT, SOA)
    g = f.as_layout(aosoa(4))
    assert not g.nd
    np.testing.assert_array_equal(np.asarray(g.data),
                                  np.asarray(flat.as_layout(aosoa(4)).data))
    back = g.as_layout(SOA)
    np.testing.assert_array_equal(back.to_numpy(), a)
    assert f.as_layout(SOA) is f


def _view_as_flat(f, a):
    flat = f.as_flat()
    assert not flat.nd and flat.data.shape == (a.shape[0], a[0].size)
    np.testing.assert_array_equal(np.asarray(flat.data),
                                  a.reshape(a.shape[0], -1))
    again = flat.as_nd()
    assert again.nd
    np.testing.assert_array_equal(np.asarray(again.data), a)
    assert f.as_nd() is f and flat.as_flat() is flat


def _view_jit_pytree(f, a):
    leaves, tree = jax.tree_util.tree_flatten(f)
    assert len(leaves) == 1 and leaves[0].shape == a.shape
    g = jax.tree_util.tree_unflatten(tree, leaves)
    assert g.nd and g.lattice == LAT
    h = jax.jit(lambda x: x.with_data(x.data * 2))(f)
    assert h.nd and h.name == f.name and h.lattice == LAT
    np.testing.assert_array_equal(h.to_numpy(), 2 * a)


VIEWS = {
    "canonical": _view_canonical,
    "canonical_nd": _view_canonical_nd,
    "to_numpy": _view_to_numpy,
    "with_canonical": _view_with_canonical,
    "as_layout_aosoa": _view_as_layout,
    "as_flat_as_nd": _view_as_flat,
    "jit_pytree": _view_jit_pytree,
}


@pytest.mark.parametrize("view", sorted(VIEWS))
def test_nd_field_round_trips(view, rng):
    a = _arr(rng)
    f = Field.from_nd("x", a)
    assert f.nd and f.lattice == LAT and f.ncomp == 3 and f.nsites == 192
    VIEWS[view](f, a)


def test_from_nd_keeps_aosoa_flat(rng):
    a = _arr(rng)
    f = Field.from_nd("x", a, aosoa(4))
    assert not f.nd and f.layout == aosoa(4)
    ref = Field.from_canonical("x", a, LAT, aosoa(4))
    np.testing.assert_array_equal(np.asarray(f.data), np.asarray(ref.data))


def test_relayout_counter_counts_flat_nd_reshapes(rng):
    a = _arr(rng)
    flat = Field.from_canonical("x", a.reshape(3, -1), LAT, SOA)
    _, moved = _relayouts(flat.canonical_nd)
    assert moved == 1
    _, moved = _relayouts(flat.canonical)
    assert moved == 0
    _, moved = _relayouts(Field.from_canonical, "x", a, LAT, SOA)
    assert moved == 1
    _, moved = _relayouts(Field.from_nd, "x", a)
    assert moved == 0


@pytest.mark.parametrize("reduce", [target_sum, target_max])
@pytest.mark.parametrize("config", [JNP, PALLAS], ids=["jnp", "pallas"])
def test_reduction_of_nd_field_equals_flat(reduce, config, rng):
    a = _arr(rng)
    nd = reduce(Field.from_nd("x", a), config)
    flat = reduce(Field.from_canonical("x", a, LAT, SOA), config)
    np.testing.assert_array_equal(np.asarray(nd), np.asarray(flat))


# -- launches on nd inputs -----------------------------------------------------

CFG16 = lud.LudwigConfig(lattice=(4, 8, 16))


def _chem(rng, lat):
    return (lud.chem_stress_graph(CFG16),
            {"q": _arr(rng, 5, lat, 1e-2), "lapq": _arr(rng, 5, lat, 1e-3),
             "dq": _arr(rng, 15, lat, 1e-3)}, ("h", "sigma"))


def _lc(rng, lat):
    return (lud.lc_update_graph(CFG16),
            {"q": _arr(rng, 5, lat, 1e-2), "h": _arr(rng, 5, lat, 1e-3),
             "w": _arr(rng, 9, lat, 1e-3), "adv": _arr(rng, 5, lat, 1e-4)},
            ("q_new",))


def _lb(rng, lat):
    return (lud.lb_step_graph(CFG16),
            {"dist": _arr(rng, 19, lat, 1e-3, 1.0 / 19.0),
             "force": _arr(rng, 3, lat, 1e-4)}, ("dist2", "u"))


GRAPHS = {"chem_stress": _chem, "lc_update": _lc, "lb_step": _lb}

# XLA's CPU fusion emitters contract a*b+c into one FMA or not depending on
# the loop shape they emit, so one elementwise body compiled for a
# (5, nsites) and a (5, X, Y, Z) operand may differ in the last bit.  The
# launches are compiled without them, so what is compared is the launch's
# own arithmetic.
_NO_FMA_CHOICE = {"xla_cpu_use_fusion_emitters": False}


def _launch_both(graph, arrays, outputs, config, lat, plan=None,
                 storages=(False, True)):
    """The graph launched on flat and on nd-stored inputs (``storages``):
    {nd?: (the outputs as nd arrays, which of them came back nd-stored)}."""
    names = list(arrays)

    def run(nd, *xs):
        mk = ((lambda n, x: Field.from_nd(n, x)) if nd else
              (lambda n, x: Field(n, x.shape[0], lat, SOA,
                                  x.reshape(x.shape[0], -1))))
        out = graph.launch({n: mk(n, x) for n, x in zip(names, xs)},
                           config=config, outputs=outputs, plan=plan)
        return (tuple(out[o].canonical_nd() for o in outputs),
                tuple(out[o].data.ndim > 2 for o in outputs))

    xs = [jnp.asarray(arrays[n]) for n in names]
    res = {}
    for nd in storages:
        stored = run(nd, *xs)[1]
        fn = jax.jit(lambda *a, _nd=nd: run(_nd, *a)[0])
        res[nd] = (fn.lower(*xs).compile(_NO_FMA_CHOICE)(*xs), stored)
    return res


@pytest.mark.parametrize("name", sorted(GRAPHS))
@pytest.mark.parametrize("config", [JNP, PALLAS], ids=["jnp", "pallas"])
def test_ludwig_graph_nd_equals_flat(name, config, rng):
    lat = (4, 8, 16)
    graph, arrays, outputs = GRAPHS[name](rng, lat)
    res = _launch_both(graph, arrays, outputs, config, lat)
    assert res[True][1] == (True,) * len(outputs)
    assert res[False][1] == (False,) * len(outputs)
    for o, a, b in zip(outputs, res[False][0], res[True][0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=o)


@pytest.fixture
def tpu_interpreter(monkeypatch):
    """Compiled (interpret=False) plans on Pallas' TPU interpreter, as
    tests/test_tile.py runs the DMA kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.core import fuse
    from repro.core import plan as plan_mod

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


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_ludwig_graph_nd_equals_flat_tpu_interpreter(name, rng,
                                                     tpu_interpreter):
    """The compiled lowerings (the flat site-block grid against the nd
    block grid; the DMA stencil kernel with and without relayouts), y-tiled
    where the nd grid allows it, on the TPU interpreter."""
    lat = (2, 16, 16)
    graph, arrays, outputs = GRAPHS[name](rng, lat)
    compiled = TargetConfig("pallas", vvl=128, interpret=False)
    res = _launch_both(graph, arrays, outputs, compiled, lat)
    for o, a, b in zip(outputs, res[False][0], res[True][0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=o)
    if name != "lb_step":  # an nd site-local chain in y tiles of 8
        tiled = _launch_both(graph, arrays, outputs, compiled, lat,
                             plan=LoweringPlan("pallas", bx=1, by=8),
                             storages=(True,))
        for o, a, b in zip(outputs, res[True][0], tiled[True][0]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=o)


def test_mixed_storage_launch_relayouts_to_flat(rng):
    """A launch given nd and flat Fields together lowers as a flat one."""
    lat = (4, 8, 16)
    graph, arrays, outputs = _lc(rng, lat)
    ins = {n: (Field.from_nd(n, a) if n == "q" else
               Field.from_canonical(n, a, lat, SOA))
           for n, a in arrays.items()}
    before = telemetry.counter_value("fuse.nd_site_local")
    out = graph.launch(ins, config=PALLAS, outputs=outputs)
    assert telemetry.counter_value("fuse.nd_site_local") == before
    assert not out["q_new"].nd
    ref = graph.launch({n: Field.from_nd(n, a) for n, a in arrays.items()},
                       config=PALLAS, outputs=outputs)
    np.testing.assert_allclose(out["q_new"].to_numpy(),
                               ref["q_new"].to_numpy(), rtol=1e-6,
                               atol=1e-9)


def test_nd_launch_keys_its_own_tuned_plans(rng):
    lat = (4, 8, 16)
    graph, arrays, outputs = _lc(rng, lat)
    nd = {n: Field.from_nd(n, a) for n, a in arrays.items()}
    flat = {n: f.as_flat() for n, f in nd.items()}
    assert graph.nd_grid(nd) and not graph.nd_grid(flat)
    assert (graph.plan_key(nd, config=PALLAS, outputs=outputs)
            != graph.plan_key(flat, config=PALLAS, outputs=outputs))
    cands = tune.plan_candidates_for(graph, nd, config=PALLAS,
                                     outputs=outputs)
    assert cands and all(c.bx >= 1 and not c.vvl for c in cands)


# -- the Ludwig step -----------------------------------------------------------

def test_ludwig_steps_from_nd_state_match_flat_state():
    cfg = lud.LudwigConfig(lattice=(16, 16, 16), target=PALLAS)
    flat = lud.init_state(cfg, seed=2)
    assert not flat.dist.nd and not flat.q.nd
    nd = lud.LudwigState(dist=flat.dist.as_nd(), q=flat.q.as_nd())
    step = jax.jit(functools.partial(lud.step, cfg=cfg))
    a, b = flat, nd
    for _ in range(3):
        a, b = step(a), step(b)
        assert a.dist.nd and a.q.nd  # a step returns nd-stored state
    np.testing.assert_array_equal(a.dist.to_numpy(), b.dist.to_numpy())
    np.testing.assert_array_equal(a.q.to_numpy(), b.q.to_numpy())


@pytest.mark.parametrize("config", [JNP, PALLAS], ids=["jnp", "pallas"])
def test_jitted_step_on_nd_state_traces_no_relayout(config):
    cfg = lud.LudwigConfig(lattice=(8, 8, 16), target=config)
    flat = lud.init_state(cfg, seed=0)
    nd = lud.LudwigState(dist=flat.dist.as_nd(), q=flat.q.as_nd())
    step = jax.jit(functools.partial(lud.step, cfg=cfg))
    telemetry.reset_counters("field.")
    telemetry.reset_counters("fuse.nd_site_local")
    step.lower(nd)
    assert telemetry.counter_value("field.relayout") == 0
    assert telemetry.counter_value("fuse.nd_site_local") == 2
    # a flat state relayouts once on the way in: dist and q
    telemetry.reset_counters("field.")
    step.lower(flat)
    assert telemetry.counter_value("field.relayout") == 2


def _sharded_step_1x1(cfg):
    """``make_sharded_step`` on a 1x1 mesh that decomposes x and y: every
    halo is the local wrap, and the halo'd local lattices have y + 4
    (chains) and y + 2 (LB launch), which are no multiple of 8."""
    from repro.core.compat import make_mesh
    from repro.lattice import Domain
    dom = Domain(global_shape=cfg.lattice,
                 mesh=make_mesh((1, 1), ("data", "model")),
                 dim_axes=("data", "model", None), halo=2)
    return lud.make_sharded_step(cfg, dom, halo="pre")


def test_sharded_step_stores_local_fields_nd():
    """make_sharded_step stores its halo'd and interior local Fields nd:
    both site-local chains lower on the nd grid, and no launch stages a
    flat<->nd relayout."""
    cfg = lud.LudwigConfig(lattice=(8, 8, 16), target=PALLAS)
    st = lud.init_state(cfg, seed=0)
    d, q = jnp.asarray(st.dist.to_numpy()), jnp.asarray(st.q.to_numpy())
    telemetry.reset_counters("fuse.nd_site_local")
    telemetry.reset_counters("field.")
    _sharded_step_1x1(cfg).lower(d, q)
    assert telemetry.counter_value("fuse.nd_site_local") == 2
    assert telemetry.counter_value("field.relayout") == 0


def test_sharded_step_on_nd_local_fields_matches_step():
    """Two sharded steps on nd local Fields (Pallas) equal two one-chip
    steps, within the sharded-equals-single tolerance."""
    cfg = lud.LudwigConfig(lattice=(8, 8, 16), target=PALLAS)
    st = lud.init_state(cfg, seed=3)
    sstep = _sharded_step_1x1(cfg)
    one = jax.jit(functools.partial(lud.step, cfg=cfg))
    d, q = jnp.asarray(st.dist.to_numpy()), jnp.asarray(st.q.to_numpy())
    for _ in range(2):
        d, q = sstep(d, q)
        st = one(st)
    np.testing.assert_allclose(np.asarray(d), st.dist.to_numpy(),
                               rtol=5e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(q), st.q.to_numpy(),
                               rtol=5e-5, atol=1e-7)


def test_diagnostics_and_tuning_take_nd_state(tmp_path):
    cfg = lud.LudwigConfig(lattice=(8, 8, 16), target=PALLAS)
    flat = lud.init_state(cfg, seed=1)
    nd = lud.step(flat, cfg)
    d_nd = lud.diagnostics(nd, cfg)
    d_flat = lud.diagnostics(
        lud.LudwigState(dist=nd.dist.as_flat(), q=nd.q.as_flat()), cfg)
    for k in d_nd:
        np.testing.assert_array_equal(np.asarray(d_nd[k]),
                                      np.asarray(d_flat[k]))
    table = str(tmp_path / "plans.json")
    res = lud.tune_step_graphs(cfg, nd, path=table, iters=1, warmup=0,
                               max_candidates=2)
    assert set(res) == {"ludwig_chem_stress", "ludwig_lb_step",
                        "ludwig_lc_update"}
    tuned = dataclasses.replace(
        cfg, target=dataclasses.replace(PALLAS, plan_policy="tuned"))
    hits = telemetry.counter_value("tune.hits")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TARGETDP_TUNE_PATH", table)
        out = lud.step(nd, tuned)
    assert telemetry.counter_value("tune.hits") == hits + 3
    np.testing.assert_allclose(out.q.to_numpy(), lud.step(nd, cfg).q.to_numpy(),
                               rtol=1e-6, atol=1e-9)
