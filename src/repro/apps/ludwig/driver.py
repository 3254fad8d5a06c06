"""Ludwig liquid-crystal timestep driver (single-shard and sharded).

One timestep reproduces the paper's kernel decomposition (§2.1.1):

  Order Parameter Gradients   stencil   grad Q, lap Q
  (molecular field)           local     H(Q, lap Q)
  Chemical Stress             local     sigma(Q, H, grad Q)
  (force)                     stencil   F = div sigma
  Collision                   local     BGK + Guo forcing   [fused LB step]
  Propagation                 stencil   streaming           [fused LB step]
  Advection (+ Boundaries)    stencil   upwind div(u Q)
  LC Update                   local     Beris-Edwards       [core.launch]

Site-local stages run through core.target.launch so the engine (jnp vs
pallas) and the data layout are pure configuration — the paper's central
claim, which tests/test_ludwig.py asserts by running both engines step-
for-step.  Adjacent site-local stages are *fused* via core.fuse.LaunchGraph
(molecular field + stress; BE rhs + Q update), and the whole LB half of the
step — moments, BGK collision and the streaming *stencil* — is one halo'd
launch graph (`lb_step_graph`): collision is recomputed on the halo ring so
propagation gathers post-collision neighbours from VMEM, and the
post-collision distributions never round-trip through HBM.

The sharded form (`make_sharded_step`) wraps the same stage functions in
jax.shard_map on a Domain: per step it halo-exchanges Q (width 2), the
pre-collision distributions (width 1) and the velocity field (width 1),
then applies the identical periodic-roll stencils on the halo'd local
arrays and crops — the dimension-by-dimension exchange makes the wrapped
reads land in valid halo, the standard MPI decomposition of both papers'
codes.  The fused LB half-step can run under three halo schedules:
``halo="pre"`` (exchange, then one launch — the legacy behavior, default),
``halo="overlap"`` (core.overlap: the exchange is started, the interior
sub-launch runs on locally-owned data with no dependence on it, and thin
boundary slabs run once the halos land — comms hidden behind compute), or
``halo=None`` (the planning layer — ``plan_policy``/tuned table — picks).
`run_steps` drives the step through core.schedule.StepPipeline (donated
double-buffers, pipelined dispatch) for multi-timestep runs.

Shard size is bounded by *tile* size, not lattice size: when a shard's
whole-staged footprint exceeds the VMEM budget (``TargetConfig.vmem_bytes``
or ``$TARGETDP_VMEM_BYTES``), the planning layer tiles the y/z axes of the
fused LB launch (``LoweringPlan.by``/``bz``, double-buffered tile DMA on a
real TPU) — production-size local volumes run with no driver changes here,
and the overlap scheduler's sub-launches inherit the tiles.

Layouts: every Field a step builds carries ``cfg.layout`` (the paper's
per-architecture layout switch), including the halo'd inputs of the fused
LB launch — so a tuned table whose winner is the native-AoSoA stencil
lowering (``LoweringPlan.view == "block"``, core.plan) applies to the
hottest launch of the step with zero driver changes under
``cfg.target.plan_policy="tuned"``.  Every temporary the step builds —
interior stage outputs and halo'd local Fields alike — goes through the
``tileable_layout`` fallback: the lattice keeps ``cfg.layout`` wherever
the site count is SAL-tileable and degrades to SOA otherwise (in practice
only padded local lattices hit the fallback; interior lattices that are
not tileable already fail at ``init_state``).

In ``step`` SoA Fields are stored nd, ``(ncomp, X, Y, Z)``
(``Field.from_nd``): the stencils read them as they are, the two site-local
chains lower on the nd grid and the LB launch stages them with no relayout,
so a step traces no flat<->nd conversion.  ``step`` takes flat or nd state
and returns nd.  ``make_sharded_step`` stores its halo'd and interior local
Fields nd the same way, so under ``halo="pre"`` it traces no relayout either.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    DtypePolicy, Field, LaunchGraph, Layout, SOA, TargetConfig, compat,
    launch, target_sum, telemetry, tileable_layout,
)
from repro.kernels.lb_collision import ref as lbref
from repro.kernels.lb_collision.ops import collide_kernel
from repro.kernels.lb_propagation import ops as prop_ops
from repro.lattice import Domain
from . import gradients as gr
from . import lc

SITE_DIMS = (1, 2, 3)


@dataclasses.dataclass(frozen=True)
class LudwigConfig:
    lattice: Tuple[int, int, int] = (16, 16, 16)
    tau: float = 0.8            # LB relaxation time; nu = cs2 (tau - 1/2)
    a0: float = 0.01            # Landau-de Gennes bulk scale
    gamma: float = 3.0          # effective temperature (>2.7: nematic)
    kappa: float = 0.01         # elastic constant (one-constant approx.)
    gamma_rot: float = 0.3     # rotational diffusion Gamma
    xi: float = 0.7             # flow-aligning parameter
    dt: float = 1.0
    layout: Layout = SOA
    target: TargetConfig = TargetConfig("jnp", vvl=128)
    # storage dtype for the fused LB half-step's launch ("" = full
    # precision): distributions stream through HBM in this dtype, compute
    # stays fp32 and reductions accumulate wide.  Validated against the
    # full-precision oracle in tests/test_dtype.py.
    storage: str = ""


def _lb_target(cfg: "LudwigConfig") -> TargetConfig:
    """The fused LB launch's config: ``cfg.target`` plus the storage-dtype
    policy when ``cfg.storage`` narrows it."""
    if not cfg.storage:
        return cfg.target
    return dataclasses.replace(
        cfg.target, dtypes=DtypePolicy(storage=cfg.storage,
                                       compute="float32",
                                       accumulate="float64"))


@dataclasses.dataclass
class LudwigState:
    dist: Field   # (19,) distributions
    q: Field      # (5,)  order parameter


jax.tree_util.register_pytree_node(
    LudwigState,
    lambda s: ((s.dist, s.q), None),
    lambda _, ch: LudwigState(dist=ch[0], q=ch[1]),
)


def init_state(cfg: LudwigConfig, seed: int = 0, q_amp: float = 1e-2) -> LudwigState:
    rng = np.random.default_rng(seed)
    nsites = int(np.prod(cfg.lattice))
    rho = jnp.ones((nsites,), jnp.float32)
    u = jnp.zeros((3, nsites), jnp.float32)
    f0 = lbref.equilibrium(rho, u)
    dist = Field.from_canonical("dist", f0, cfg.lattice, cfg.layout)
    q0 = q_amp * rng.normal(size=(5, nsites)).astype(np.float32)
    q = Field.from_canonical("q", jnp.asarray(q0), cfg.lattice, cfg.layout)
    return LudwigState(dist=dist, q=q)


# -- site-local kernel bodies wrapped for core.launch -------------------------

def _mol_field_body(v, *, a0, gamma, kappa):
    return {"h": lc.molecular_field_chunk(v["q"], v["lapq"], a0=a0, gamma=gamma, kappa=kappa)}


def _stress_body(v, *, kappa, xi):
    return {"sigma": lc.stress_chunk(v["q"], v["h"], v["dq"], kappa=kappa, xi=xi)}


def _be_rhs_body(v, *, gamma_rot, xi):
    return {"rhs": lc.beris_edwards_rhs_chunk(v["q"], v["h"], v["w"], gamma_rot=gamma_rot, xi=xi)}


def _q_update_body(v, *, dt):
    return {"q": lc.q_update_chunk(v["q"], v["rhs"], v["adv"], dt=dt)}


def _moments_body(v):
    rho, u = lbref.moments(v["dist"])
    # half-force velocity correction (consistent with Guo forcing)
    u = u + 0.5 * v["force"] / rho[None, :]
    return {"rho": rho[None, :], "u": u}


def _fed_body(v, *, a0, gamma, kappa):
    return {"fed": lc.free_energy_density_chunk(v["q"], v["dq"], a0=a0, gamma=gamma, kappa=kappa)}


def _mkfield(name: str, arr_nd: jnp.ndarray, cfg: LudwigConfig) -> Field:
    lat = tuple(arr_nd.shape[1:])
    return Field.from_nd(name, arr_nd, tileable_layout(cfg.layout, lat))


def _nd_state(state: LudwigState) -> LudwigState:
    """The state with its SoA Fields stored nd (a flat state relayouts
    once; AoS and AoSoA Fields stay as they are)."""
    return LudwigState(dist=state.dist.as_nd(), q=state.q.as_nd())


# -- stage functions (single-shard periodic) ----------------------------------

def stage_gradients(q_nd: jnp.ndarray):
    """Order Parameter Gradients."""
    with telemetry.scope("ludwig/gradients"):
        return gr.grad_central(q_nd), gr.laplacian(q_nd)


# stage stanzas shared by every graph builder below — one definition per
# kernel so the production step and the benchmark/test chains cannot drift
def _add_mol_field(g: LaunchGraph, cfg: LudwigConfig) -> LaunchGraph:
    return g.add(_mol_field_body, {"q": "q", "lapq": "lapq"}, {"h": 5},
                 params=dict(a0=cfg.a0, gamma=cfg.gamma, kappa=cfg.kappa))


def _add_stress(g: LaunchGraph, cfg: LudwigConfig) -> LaunchGraph:
    return g.add(_stress_body, {"q": "q", "h": "h", "dq": "dq"}, {"sigma": 9},
                 params=dict(kappa=cfg.kappa, xi=cfg.xi))


def _add_be_rhs(g: LaunchGraph, cfg: LudwigConfig) -> LaunchGraph:
    return g.add(_be_rhs_body, {"q": "q", "h": "h", "w": "w"}, {"rhs": 5},
                 params=dict(gamma_rot=cfg.gamma_rot, xi=cfg.xi))


def _add_q_update(g: LaunchGraph, cfg: LudwigConfig) -> LaunchGraph:
    return g.add(_q_update_body, {"q": "q", "rhs": "rhs", "adv": "adv"},
                 {"q": 5}, rename={"q": "q_new"}, params=dict(dt=cfg.dt))


def chem_stress_graph(cfg: LudwigConfig) -> LaunchGraph:
    """molecular field -> stress as one fused chain (H also materialized:
    the BE update needs it later in the step)."""
    return _add_stress(_add_mol_field(LaunchGraph("ludwig_chem_stress"), cfg), cfg)


def lc_update_graph(cfg: LudwigConfig) -> LaunchGraph:
    """BE rhs -> Q update as one fused chain; rhs stays in VMEM."""
    return _add_q_update(_add_be_rhs(LaunchGraph("ludwig_lc_update"), cfg), cfg)


def lc_chain_graph(cfg: LudwigConfig) -> LaunchGraph:
    """The 3-kernel LC chain (molecular field -> BE rhs -> Q update) fused
    into one launch — the benchmarks' fused-vs-unfused exhibit; h and rhs
    never touch HBM."""
    g = _add_mol_field(LaunchGraph("ludwig_lc_chain"), cfg)
    return _add_q_update(_add_be_rhs(g, cfg), cfg)


def lb_step_graph(cfg: LudwigConfig) -> LaunchGraph:
    """The whole LB half of a timestep — moments, BGK collision and the
    streaming stencil — as ONE halo'd launch (one pallas_call): dist and
    force stream from HBM once, collision is recomputed on the width-1 halo
    ring, and propagation gathers the post-collision neighbours from the
    VMEM-resident block, so dist1 never materializes in HBM."""
    return (
        LaunchGraph("ludwig_lb_step")
        .add(_moments_body, {"dist": "dist", "force": "force"},
             {"rho": 1, "u": 3})
        .add(collide_kernel, {"dist": "dist", "force": "force"}, {"dist": 19},
             rename={"dist": "dist1"}, params=dict(tau=cfg.tau))
        .add_stencil(prop_ops.propagate_body, {"dist": "dist1"}, {"dist": 19},
                     width=1, rename={"dist": "dist2"})
    )


def stage_chemical_stress(state_q: Field, dq_nd, lapq_nd, cfg: LudwigConfig):
    """molecular field + stress (one fused launch) + force divergence."""
    with telemetry.scope("ludwig/chem_stress"):
        out = chem_stress_graph(cfg).bind(
            config=cfg.target, outputs=("h", "sigma"),
        )({"q": state_q, "lapq": _mkfield("lapq", lapq_nd, cfg),
           "dq": _mkfield("dq", dq_nd, cfg)})
    with telemetry.scope("ludwig/force_divergence"):
        force_nd = gr.divergence(out["sigma"].canonical_nd())
    return out["h"], force_nd


def stage_advection(q_nd, u_nd):
    """Advection (+ periodic boundaries: no correction term)."""
    with telemetry.scope("ludwig/advection"):
        return gr.advective_divergence(q_nd, u_nd)


def stage_lc_update(state_q: Field, h: Field, w_nd, adv_nd, cfg: LudwigConfig) -> Field:
    with telemetry.scope("ludwig/lc_update"):
        q_new = lc_update_graph(cfg).bind(
            config=cfg.target, outputs=("q_new",),
        )({"q": state_q, "h": h, "w": _mkfield("w", w_nd, cfg),
           "adv": _mkfield("adv", adv_nd, cfg)})["q_new"]
    # keep the Field name stable across steps (it is pytree aux data)
    return dataclasses.replace(q_new, name=state_q.name)


def _w_tensor(u_nd: jnp.ndarray) -> jnp.ndarray:
    """W_ab = d u_a / d x_b as (9,) row-major from grad_central layout."""
    g = gr.grad_central(u_nd)  # [d/dx u(3), d/dy u(3), d/dz u(3)] => g[b*3+a]
    return jnp.stack([g[b * 3 + a] for a in range(3) for b in range(3)])


def _cast_state(dist2: Field, like: Field) -> Field:
    """The LB launch's ``dist2`` back in the carried state's dtype and name
    (a no-op cast unless a storage dtype narrowed the launch)."""
    with telemetry.scope("ludwig/state_cast"):
        return dataclasses.replace(
            dist2.with_data(dist2.data.astype(like.data.dtype)),
            name=like.name)


def step(state: LudwigState, cfg: LudwigConfig) -> LudwigState:
    """One full LC-LB timestep (single shard, periodic)."""
    state = _nd_state(state)
    with telemetry.scope("ludwig/gradients"):
        q_nd = state.q.canonical_nd()
    dq_nd, lapq_nd = stage_gradients(q_nd)
    h, force_nd = stage_chemical_stress(state.q, dq_nd, lapq_nd, cfg)

    # moments + collision + streaming fused: one halo'd launch, dist and
    # force stream from HBM once, post-collision dist never touches HBM.
    # Under cfg.storage the launch reads/writes storage-dtype bytes; the
    # carried state is cast back so the step's signature stays fixed
    # (quantization to storage precision already happened in the write).
    with telemetry.scope("ludwig/lb"):
        force = _mkfield("force", force_nd, cfg)
        lb = lb_step_graph(cfg).bind(
            config=_lb_target(cfg), outputs=("dist2", "u"),
        )({"dist": state.dist, "force": force})
        u_nd = lb["u"].canonical_nd().astype(q_nd.dtype)
    dist2 = _cast_state(lb["dist2"], state.dist)

    with telemetry.scope("ludwig/w_tensor"):
        w_nd = _w_tensor(u_nd)
    adv_nd = stage_advection(q_nd, u_nd)

    q_new = stage_lc_update(state.q, h, w_nd, adv_nd, cfg)
    return LudwigState(dist=dist2, q=q_new)


def step_timed(state: LudwigState, cfg: LudwigConfig) -> Tuple[LudwigState, Dict[str, float]]:
    """Unjitted per-kernel wall timings (benchmarks/fig3)."""
    t: Dict[str, float] = {}

    def timed(name, fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        jax.block_until_ready(jax.tree_util.tree_leaves(out))
        t[name] = time.perf_counter() - t0
        return out

    state = _nd_state(state)
    q_nd = state.q.canonical_nd()
    dq_nd, lapq_nd = timed("order_parameter_gradients", stage_gradients, q_nd)
    h, force_nd = timed(
        "chemical_stress", stage_chemical_stress, state.q, dq_nd, lapq_nd, cfg
    )
    force = _mkfield("force", force_nd, cfg)
    # time the same fused LB launch production step() runs; the row name
    # matches the LUDWIG_KERNELS["lb_step"] traffic model (dist+force read
    # once, dist''+u written; dist' and rho never touch HBM)
    lb_bound = lb_step_graph(cfg).bind(config=_lb_target(cfg),
                                       outputs=("dist2", "u"))
    lb = timed("lb_step", lambda: lb_bound({"dist": state.dist,
                                            "force": force}))
    dist2 = _cast_state(lb["dist2"], state.dist)
    u_nd = lb["u"].canonical_nd().astype(q_nd.dtype)
    w_nd = _w_tensor(u_nd)
    adv_nd = timed("advection", stage_advection, q_nd, u_nd)
    q_new = timed("lc_update", stage_lc_update, state.q, h, w_nd, adv_nd, cfg)
    return LudwigState(dist=dist2, q=q_new), t


# -- plan autotuning -----------------------------------------------------------

def tune_step_graphs(cfg: LudwigConfig, state: LudwigState, **tune_kw):
    """Autotune every launch graph a timestep runs (chem-stress chain, the
    fused LB half-step, the LC update chain) and persist the winners, so a
    subsequent run with ``cfg.target.plan_policy="tuned"`` — the same driver
    code, zero application changes — picks the swept plans up from the
    table (paper §3.2.2's per-architecture tuning as a layer, not an edit).

    Returns {graph name: (plan, info)} from core.tune.autotune_graph; a
    warm table short-circuits each sweep (info["cached"])."""
    from repro.core import tune

    state = _nd_state(state)
    q_nd = state.q.canonical_nd()
    dq_nd, lapq_nd = stage_gradients(q_nd)
    results = {}
    g = chem_stress_graph(cfg)
    results[g.name] = tune.autotune_graph(
        g,
        {"q": state.q, "lapq": _mkfield("lapq", lapq_nd, cfg),
         "dq": _mkfield("dq", dq_nd, cfg)},
        config=cfg.target, outputs=("h", "sigma"), **tune_kw)
    h, force_nd = stage_chemical_stress(state.q, dq_nd, lapq_nd, cfg)
    force = _mkfield("force", force_nd, cfg)
    g = lb_step_graph(cfg)
    results[g.name] = tune.autotune_graph(
        g, {"dist": state.dist, "force": force},
        config=cfg.target, outputs=("dist2", "u"), **tune_kw)
    lb = g.launch({"dist": state.dist, "force": force},
                  config=cfg.target, outputs=("dist2", "u"))
    u_nd = lb["u"].canonical_nd()
    w_nd = _w_tensor(u_nd)
    adv_nd = stage_advection(q_nd, u_nd)
    g = lc_update_graph(cfg)
    results[g.name] = tune.autotune_graph(
        g,
        {"q": state.q, "h": h, "w": _mkfield("w", w_nd, cfg),
         "adv": _mkfield("adv", adv_nd, cfg)},
        config=cfg.target, outputs=("q_new",), **tune_kw)
    return results


# -- diagnostics ---------------------------------------------------------------

def diagnostics(state: LudwigState, cfg: LudwigConfig) -> Dict[str, jnp.ndarray]:
    """Total mass, momentum, free energy (targetDP reduction API)."""
    state = _nd_state(state)
    mass = target_sum(state.dist, cfg.target).sum()
    q_nd = state.q.canonical_nd()
    dq_nd = gr.grad_central(q_nd)
    dq = _mkfield("dq", dq_nd, cfg)
    fed = launch(
        _fed_body, {"q": state.q, "dq": dq}, {"fed": 1},
        config=cfg.target,
        params=dict(a0=cfg.a0, gamma=cfg.gamma, kappa=cfg.kappa),
    )["fed"]
    free_energy = target_sum(fed, cfg.target)[0]
    rho, u = lbref.moments(state.dist.canonical())
    mom = jnp.sum(rho[None] * u, axis=1)
    return {"mass": mass, "free_energy": free_energy, "momentum": mom}


# -- sharded driver ------------------------------------------------------------

def make_sharded_step(cfg: LudwigConfig, domain: Domain, halo: str = "pre"):
    """Build a jitted shard_map step over canonical-nd global arrays.

    Takes/returns (dist_nd (19, X, Y, Z), q_nd (5, X, Y, Z)) sharded per
    ``domain.spec()``.  Inside: halo exchanges + the identical periodic
    stencils applied to halo'd local arrays (wrap reads land in valid halo
    because exchanges are dimension-ordered), then crops.

    ``halo`` schedules the fused LB half-step's exchange: "pre" (exchange
    then launch, the legacy schedule), "overlap" (interior/boundary split
    launches via core.overlap — the dist/force exchange overlaps the
    interior collision+streaming compute), or None (planned: the tuned
    table may pick overlap per lattice/backend).  All three are
    bit-identical on the jnp engine (asserted in tests/test_distributed).
    """
    if halo not in (None, "pre", "overlap"):
        raise ValueError(f"halo must be None, 'pre' or 'overlap', got {halo!r}")
    mesh = domain.mesh
    spec = domain.spec()
    WQ = 2  # q halo: grad/lap (1) + stress divergence (1)
    dec = domain.decomposed
    dec_dims = {d for d, _, _ in dec}

    def pad(x, w, every_dim=False):
        # wrap-pad the decomposed dims (exchange overwrites them with
        # neighbour data).  The jnp stencils roll periodically, so a
        # non-decomposed dim needs no halo — and leaving it unpadded keeps
        # the site-local launches on whole (8, 128) tiles.  The fused LB
        # launch's halo="pre" contract pads every dim (``every_dim``): for
        # a non-decomposed dim the wrap IS the periodic halo.
        pads = [(0, 0)] + [(w, w) if (every_dim or d in dec_dims) else (0, 0)
                           for d in range(1, x.ndim)]
        return jnp.pad(x, pads, mode="wrap")

    def crop(x, w):
        idx = [slice(None)] + [slice(w, s - w) if d in dec_dims
                               else slice(None)
                               for d, s in enumerate(x.shape[1:], 1)]
        return x[tuple(idx)]

    def exchange_w(x, w):
        from repro.core import halo as _halo
        return _halo.exchange(x, dec, width=w)

    tgt = cfg.target
    # bound launches: graph + config + outputs (+ halo) fixed once, reused
    # every sharded call — launch(...) kwargs on a raw graph still work
    chem_step = chem_stress_graph(cfg).bind(config=tgt,
                                            outputs=("h", "sigma"))
    lb_pre_step = lb_step_graph(cfg).bind(config=tgt,
                                          outputs=("dist2", "u"), halo="pre")
    lc_step = lc_update_graph(cfg).bind(config=tgt, outputs=("q_new",))

    def local_step(dist_nd, q_nd):
        scope = telemetry.scope
        # ---- Q stencils on width-2 halo
        with scope("ludwig/gradients"):
            qh = exchange_w(pad(q_nd, WQ), WQ)
            dq_h = gr.grad_central(qh)
            lapq_h = gr.laplacian(qh)
        # halo'd local Fields keep cfg.layout whenever the padded lattice
        # stays SAL-tileable (so tuned native-AoSoA plans apply sharded too)
        # and are stored nd as in ``step``: the chains lower on the nd grid
        # and the LB launch stages dist/force with no relayout
        mk = functools.partial(_mkfield, cfg=cfg)
        with scope("ludwig/chem_stress"):
            qF = mk("q", qh)
            cs = chem_step(
                {"q": qF, "lapq": mk("lapq", lapq_h), "dq": mk("dq", dq_h)})
        h_F = cs["h"]
        with scope("ludwig/force_divergence"):
            force_h = gr.divergence(cs["sigma"].canonical_nd())
            force_nd = crop(force_h, WQ)  # interior: ring-1 div reads
            # ring-2 gradients, which wrap locally — so exchange the true
            # force halo

        # ---- fused LB half-step on pre-exchanged halos: the
        # *pre-collision* dist (and the force) is exchanged instead of the
        # seed's post-collision dist, then moments + collision + streaming
        # run as ONE launch — collision recomputed on the neighbour ring
        # from true neighbour dist/force values.  halo="pre" exchanges
        # before the launch; halo="overlap"/None routes through the
        # overlap scheduler (interior sub-launch independent of the
        # exchange, boundary slabs after it — core.overlap).
        with scope("ludwig/lb"):
            if halo == "pre":
                d_h = exchange_w(pad(dist_nd, 1, every_dim=True), 1)
                f_h = exchange_w(pad(force_nd, 1, every_dim=True), 1)
                lb = lb_pre_step(
                    {"dist": mk("dist", d_h), "force": mk("force", f_h)})
            else:
                from repro.core import overlap_launch
                lb = overlap_launch(
                    lb_step_graph(cfg),
                    {"dist": mk("dist", pad(dist_nd, 1, every_dim=True)),
                     "force": mk("force", pad(force_nd, 1, every_dim=True))},
                    decomposed=dec, config=tgt, outputs=("dist2", "u"),
                    halo=halo,
                )
            dist2_nd = lb["dist2"].canonical_nd()
            u_nd = lb["u"].canonical_nd()

        # ---- hydrodynamics from the pre-collision distributions
        with scope("ludwig/w_tensor"):
            uh = exchange_w(pad(u_nd, 1), 1)
            w_nd = crop(_w_tensor(uh), 1)
        # advection: q +-1 from the wide-halo q, u faces from u halo
        with scope("ludwig/advection"):
            qh1 = crop(qh, WQ - 1)
            adv_nd = crop(gr.advective_divergence(qh1, uh), 1)

        # ---- Beris-Edwards update on interior (fused rhs -> update)
        with scope("ludwig/lc_update"):
            qiF = mk("qi", q_nd)
            q_new = lc_step(
                {"q": qiF, "h": mk("h", crop(h_F.canonical_nd(), WQ)),
                 "w": mk("w", w_nd), "adv": mk("adv", adv_nd)})["q_new"]
            return dist2_nd, q_new.canonical_nd()

    sharded = compat.shard_map(
        local_step, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec)
    )
    return jax.jit(sharded)


def run_steps(
    cfg: LudwigConfig,
    domain: Domain,
    dist_nd: jax.Array,
    q_nd: jax.Array,
    steps: int,
    *,
    halo: str = "pre",
    donate=None,
    block: bool = True,
):
    """Multi-timestep sharded pipeline: one jitted sharded step driven by
    core.schedule.StepPipeline — (dist, q) ping-pong between two donated
    device buffers, dispatch stays ahead of the device, and the per-step
    halo exchange runs under the chosen ``halo`` schedule ("overlap" hides
    it behind the interior compute).  Returns (dist_nd, q_nd) after
    ``steps`` steps.

    With donation enabled (non-CPU backends by default) the caller's input
    arrays are consumed — keep a copy if they are needed again.
    """
    from repro.core.schedule import StepPipeline

    pipe = StepPipeline(make_sharded_step(cfg, domain, halo=halo),
                        donate=donate)
    return pipe.run((dist_nd, q_nd), steps, block=block)
