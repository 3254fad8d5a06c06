"""MILC Wilson-CG driver (single-shard and sharded).

Reproduces the UEABS test: invert the Wilson-Dirac operator on a random
SU(3) gauge background with CG on the normal equations.  The sharded form
domain-decomposes the 4-D lattice over mesh axes; each dslash exchanges
the spinor halo (ppermute), the gauge halo is exchanged once per solve —
exactly the MPI structure of the original (the "Shift" kernel is where
MPI lives, §2.1.2).

``solve_sharded`` supports three per-iteration schedules:

* ``halo=None`` — the legacy path: one spinor exchange per dslash, the
  operator unfused (two launches + linear algebra per application).
* ``halo="pre"`` — the fused path: one width-2 spinor exchange, then the
  whole M^dag M application as ONE halo'd launch (wilson_normal_graph).
* ``halo="overlap"`` — the fused path under the comms/compute overlap
  scheduler (core.overlap): the spinor exchange is started, the interior
  of the fused operator runs on locally-owned data with no dependence on
  it, and thin boundary slabs run once the halos land.  Bit-identical to
  ``halo="pre"`` (the CG inner products are computed from the assembled
  Fields through the same producer-independent reduction in both modes),
  asserted under the 8-fake-device harness in tests/test_distributed.py.

The halo'd spinor/gauge Fields keep ``cfg.layout`` whenever the padded
local lattice stays SAL-tileable (falling back to SOA otherwise,
``tileable_layout``), so a tuned native-AoSoA stencil plan
(``LoweringPlan.view == "block"``) reaches the fused per-iteration
operator under ``cfg.target.plan_policy="tuned"`` with no driver edits.
The same goes for tiled plans (``LoweringPlan.by``/``bz``): when a
shard's whole-staged M^dag M footprint exceeds the VMEM budget
(``TargetConfig.vmem_bytes`` / ``$TARGETDP_VMEM_BYTES``), the planning
layer tiles the y/z axes of the fused operator — per-device local volume
is bounded by the tile, not the lattice, which is what lets the paper's
fig 5 lattice sizes fit a device's pipeline.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import (
    BatchedField, DtypePolicy, Field, Layout, SOA, TargetConfig, compat,
    overlap_launch, telemetry, tileable_layout,
)
from repro.core import halo as halo_mod
from repro.kernels.wilson_dslash.ops import dslash_halo
from repro.lattice import Domain
from .cg import (
    BatchedCGResult, CGResult, cg, cg_batched, cg_refined, dot,
    make_fused_normal, make_wilson_op, wilson_normal_graph,
)
from . import fields


@dataclasses.dataclass(frozen=True)
class MilcConfig:
    lattice: Tuple[int, int, int, int] = (8, 8, 8, 8)
    kappa: float = 0.12
    tol: float = 1e-10
    max_iter: int = 1000
    hot: float = 0.6           # gauge disorder (1 = hot start)
    layout: Layout = SOA
    target: TargetConfig = TargetConfig("jnp", vvl=128)
    # mixed precision: storage dtype for the bandwidth-dominant operator
    # launches ("" = full precision), and the iterative-refinement /
    # reliable-update knobs that keep the solve correct under it.
    # refine_k = 0 picks a default (50) whenever storage is narrowed.
    storage: str = ""
    refine_k: int = 0
    reliable: float = 0.0


def _storage_target(cfg: MilcConfig) -> TargetConfig:
    """The operator-launch config: ``cfg.target`` with the storage-dtype
    policy attached when ``cfg.storage`` narrows it (compute stays fp32,
    terminal reductions accumulate in fp64 — compensated fp32 where fp64
    is unavailable)."""
    if not cfg.storage:
        return cfg.target
    return dataclasses.replace(
        cfg.target, dtypes=DtypePolicy(storage=cfg.storage,
                                       compute="float32",
                                       accumulate="float64"))


def _hi_target(cfg: MilcConfig) -> TargetConfig:
    """The reference-operator config for true-residual recomputes: any
    dtype policy stripped and the deterministic default plans, so the
    residual the refinement loop trusts is policy-independent."""
    return dataclasses.replace(cfg.target, plan_policy="default",
                               dtypes=None)


def _refine_k(cfg: MilcConfig) -> int:
    return cfg.refine_k or (50 if cfg.storage else 0)


def init_problem(cfg: MilcConfig, seed: int = 0):
    """Random SU(3) gauge Field (72,) + gaussian source Field (24,)."""
    u_np = fields.random_su3_gauge(cfg.lattice, seed=seed, hot=cfg.hot)
    assert fields.unitarity_violation(u_np) < 1e-5
    b_np = fields.random_spinor(cfg.lattice, seed=seed + 1)
    u = Field.from_numpy("u", u_np, cfg.lattice, cfg.layout)
    b = Field.from_numpy("b", b_np, cfg.lattice, cfg.layout)
    return u, b


def solve(cfg: MilcConfig, u: Field, b: Field) -> CGResult:
    """Single-shard CG solve of M x = b via the normal equations.

    The operator application runs through the fused dslash+axpy+dot graph
    (one pallas_call), the update chain through the fused axpy+residual-norm
    graph (one more): two launches per CG iteration.

    With ``cfg.storage`` narrowed (or ``cfg.refine_k`` set) the solve runs
    :func:`repro.apps.milc.cg.cg_refined`: the per-iteration operator
    launches move storage-dtype bytes while iterative-refinement restarts
    against the policy-free operator recover the working-precision
    tolerance."""
    apply_m, apply_mdag, apply_normal = make_wilson_op(u, cfg.kappa, cfg.target)
    with telemetry.scope("milc/rhs"):
        rhs = apply_mdag(b)
    rk = _refine_k(cfg)
    if rk > 0:
        return cg_refined(
            make_fused_normal(u, cfg.kappa, _storage_target(cfg)), rhs,
            config=cfg.target, tol=cfg.tol, max_iter=cfg.max_iter,
            refine_k=rk, reliable=cfg.reliable or 1e-4,
            apply_a_dot_hi=make_fused_normal(u, cfg.kappa, _hi_target(cfg)))
    res = cg(apply_normal, rhs, config=cfg.target, tol=cfg.tol,
             max_iter=cfg.max_iter,
             apply_a_dot=make_fused_normal(u, cfg.kappa,
                                           _storage_target(cfg)))
    return res


def solve_batched(cfg: MilcConfig, u: Field, bs) -> BatchedCGResult:
    """CG-solve a stack of sources against ONE shared gauge field through
    batched launches: per iteration, one fused operator pallas_call and one
    fused masked-update pallas_call cover the whole batch.

    ``bs`` is a sequence of same-lattice source Fields or an already-stacked
    BatchedField.  Each slot's trajectory — rhs, every alpha/beta, the
    iteration count, the final x — is bit-identical to ``solve(cfg, u, b)``
    on that source alone: the rhs is computed per request through the
    single-lattice M^dag path before stacking, and converged slots are
    frozen by select-masking, never arithmetic (see cg._masked_fma_body)."""
    _, apply_mdag, _ = make_wilson_op(u, cfg.kappa, cfg.target)
    with telemetry.scope("milc/rhs"):
        if isinstance(bs, BatchedField):
            bs = bs.unstack()
        rhs = BatchedField.stack([apply_mdag(b) for b in bs], name="rhs")
    rk = _refine_k(cfg)
    return cg_batched(
        make_fused_normal(u, cfg.kappa, _storage_target(cfg)), rhs,
        config=cfg.target, tol=cfg.tol, max_iter=cfg.max_iter,
        refine_every=rk,
        apply_a_dot_hi=(make_fused_normal(u, cfg.kappa, _hi_target(cfg))
                        if rk > 0 else None))


def solver_cost_model(cfg: MilcConfig, u: Field, b: Field, *,
                      tol: float = 1e-6, cap: Optional[int] = None):
    """The convergence-aware tuner cost for the fused normal-operator
    graph: a callable mapping a candidate plan to its measured
    iterations-to-tolerance (memoized per plan), so
    :func:`repro.core.tune.autotune_graph` ranks candidates by
    time-per-iteration × iterations — time-to-solution — instead of raw
    launch time.  Dtype-policy candidates are measured through the
    iterative-refinement solve (how they would actually deploy); full
    precision candidates through plain CG."""
    _, apply_mdag, _ = make_wilson_op(u, cfg.kappa, cfg.target)
    rhs = apply_mdag(b)
    cap = cap or cfg.max_iter
    hi_op = make_fused_normal(u, cfg.kappa, _hi_target(cfg))
    cache = {}

    def iterations(plan):
        tgt = dataclasses.replace(cfg.target, plan_policy=plan)
        op = make_fused_normal(u, cfg.kappa, tgt)
        if plan.dtypes:
            res = cg_refined(op, rhs, config=cfg.target, tol=tol,
                             max_iter=cap, refine_k=cfg.refine_k or 50,
                             reliable=cfg.reliable or 1e-4,
                             apply_a_dot_hi=hi_op)
        else:
            res = cg(None, rhs, config=cfg.target, tol=tol, max_iter=cap,
                     apply_a_dot=op)
        return float(max(int(res.iterations), 1))

    def cost(plan):
        if plan not in cache:
            cache[plan] = iterations(plan)
        return cache[plan]

    return cost


def tune_solve_graphs(cfg: MilcConfig, u: Field, b: Field,
                      convergence_cost: bool = False, **tune_kw):
    """Autotune the two launch graphs a CG iteration runs — the fused
    normal-operator application (dslash+dslash+xpay/g5 + <p,Ap>) and the
    fused update chain (+ residual norm) — persisting the winners so a
    later ``cfg.target.plan_policy="tuned"`` solve loads them instead of
    re-sweeping.  Returns {graph name: (plan, info)}.

    ``convergence_cost=True`` ranks the normal-operator candidates by
    measured time-to-solution (:func:`solver_cost_model`) rather than raw
    launch time — required for a fair sweep once dtype-policy twins are in
    the candidate set, since a cheaper-per-iteration plan may need more
    iterations."""
    from repro.core import tune

    from .cg import cg_update_graph, wilson_normal_graph

    results = {}
    g = wilson_normal_graph(float(cfg.kappa))
    op_kw = dict(tune_kw)
    if convergence_cost and "cost_model" not in op_kw:
        op_kw["cost_model"] = solver_cost_model(cfg, u, b)
    results[g.name] = tune.autotune_graph(
        g, {"p": b, "u": u}, config=cfg.target, outputs=("ap", "pap"),
        **op_kw)
    g = cg_update_graph(b.ncomp)
    results[g.name] = tune.autotune_graph(
        g, {"x": b, "r": b, "p": b, "ap": b},
        scalars={"alpha": 0.3, "neg_alpha": -0.3},
        config=cfg.target, outputs=("x_new", "r_new", "rr"), **tune_kw)
    return results


def residual_check(cfg: MilcConfig, u: Field, b: Field, x: Field) -> float:
    """|M x - b| / |b| — independent verification of the solve."""
    apply_m, _, _ = make_wilson_op(u, cfg.kappa, cfg.target)
    mx = apply_m(x)
    num = jnp.linalg.norm(mx.canonical() - b.canonical())
    den = jnp.linalg.norm(b.canonical())
    return float(num / den)


# -- sharded solve ---------------------------------------------------------------

def make_domain(cfg: MilcConfig, mesh, dim_axes) -> Domain:
    return Domain(global_shape=cfg.lattice, mesh=mesh, dim_axes=dim_axes, halo=1)


def make_sharded_solver(
    cfg: MilcConfig, domain: Domain, halo: Optional[str] = None
):
    """Build the jitted sharded CG solver: ``solver(u_nd, b_nd) ->
    (x_nd, iterations, residual)`` over global canonical-nd arrays
    (sharded or to-be-sharded per ``domain.spec()``).

    ``halo`` selects the per-iteration schedule (see the module docstring):
    None (legacy per-dslash exchange, unfused), "pre" (fused normal
    operator on one width-2 pre-exchange) or "overlap" (fused operator
    under the interior/boundary split of core.overlap, hiding the spinor
    exchange behind the interior compute)."""
    if halo not in (None, "pre", "overlap"):
        raise ValueError(f"halo must be None, 'pre' or 'overlap', got {halo!r}")
    mesh = domain.mesh
    spec = domain.spec()
    dec = domain.decomposed
    axes = tuple(ax for _, ax, _ in dec)
    tgt = cfg.target
    WN = 2  # fused normal-operator ring: two width-1 dslash stages

    def pad(x, w=1):
        # wrap-pad all site dims (local periodic); exchange overwrites the
        # decomposed dims' halos with true neighbour data.
        pads = [(0, 0)] + [(w, w)] * (x.ndim - 1)
        return jnp.pad(x, pads, mode="wrap")

    def exchange(x, w=1):
        return halo_mod.exchange(x, dec, width=w)

    def mkF(name, arr):
        lat = tuple(arr.shape[1:])
        return Field.from_canonical(
            name, arr, lat, tileable_layout(cfg.layout, lat))

    def local_solve(u_loc, b_loc):
        lat_loc = u_loc.shape[1:]
        u_h = exchange(pad(u_loc))  # gauge halo once per solve

        def dslash_fn(psi: Field) -> Field:
            psi_h = exchange(pad(psi.canonical_nd()))
            out = dslash_halo(psi_h, u_h, config=tgt, width=1)
            return psi.with_canonical(out.reshape(24, -1))

        bF = mkF("b", b_loc)
        uF = mkF("u", u_loc)
        apply_m, apply_mdag, apply_normal = make_wilson_op(
            uF, cfg.kappa, tgt, dslash_fn=dslash_fn
        )
        rhs = apply_mdag(bF)

        apply_a_dot = None
        if halo is not None:
            # fused M^dag M: dslash+dslash+xpay/g5 as one halo'd graph per
            # iteration.  The gauge halo (ring 2) is exchanged once here.
            graph = wilson_normal_graph(float(cfg.kappa))
            u_h2 = exchange(pad(u_loc, WN), WN)
            uF_h = mkF("u", u_h2)
            # config/outputs/halo bound once; the per-Field output layout
            # is a per-call override (it follows the solve vector)
            normal_pre = graph.bind(config=tgt, outputs=("ap",), halo="pre")

            def apply_a_dot(p: Field):
                p_p = pad(p.canonical_nd(), WN)
                if halo == "pre":
                    p_h = exchange(p_p, WN)
                    pF = mkF("p", p_h)
                    out = normal_pre({"p": pF, "u": uF_h},
                                     out_layouts={"ap": p.layout})
                else:
                    pF = mkF("p", p_p)
                    out = overlap_launch(
                        graph, {"p": pF, "u": uF_h}, decomposed=dec,
                        config=tgt, outputs=("ap",), halo="overlap",
                        exchanged=("u",), out_layouts={"ap": p.layout})
                ap = p.with_data(out["ap"].data)
                # <p, Ap> from the assembled Fields (elementwise product +
                # fold), NOT the graph's fused on-chip reduction: its value
                # is independent of how ap was produced (one launch vs
                # interior/boundary slabs), so the CG trajectory is
                # bit-identical across the "pre" and "overlap" schedules.
                return ap, dot(p, ap, tgt)

        res = cg(apply_normal, rhs, config=tgt, tol=cfg.tol,
                 max_iter=cfg.max_iter, psum_axes=axes,
                 apply_a_dot=apply_a_dot)
        return res.x.canonical_nd(), res.iterations, res.residual

    sharded = compat.shard_map(
        local_solve,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=(spec, jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
    )
    return jax.jit(sharded)


def solve_sharded(
    cfg: MilcConfig,
    domain: Domain,
    u_nd: jax.Array,
    b_nd: jax.Array,
    halo: Optional[str] = None,
):
    """One-shot form of :func:`make_sharded_solver` (builds, jits and runs
    the solver; loops should build the solver once instead)."""
    return make_sharded_solver(cfg, domain, halo)(u_nd, b_nd)
