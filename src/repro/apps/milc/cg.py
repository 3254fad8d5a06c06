"""Conjugate-gradient inversion of the Wilson-Dirac operator (MILC UEABS).

Solves M^dag M x = M^dag b for x (so M x = b), with M = 1 - kappa D and
M^dag = g5 M g5 (gamma5-hermiticity; g5 = diag(1,1,-1,-1) in the DeGrand-
Rossi basis, verified in tests).

The linear algebra is decomposed exactly as the paper's MILC profile
(§2.1.2): "Shift" (neighbour gather, in dslash), "Extract (and Mult)" /
"Insert (and Mult)" (spin projection + SU(3) mult, in dslash), and
"Scalar Mult Add" — the axpy/xpay updates, which run through the
targetDP-JAX launch machinery as site-local kernels so both engines and
all layouts apply (paper C1/C2 for MILC).

Two fused launch graphs cover the whole CG iteration (core.fuse):

* ``wilson_normal_graph`` — the operator application M^dag M p with the
  dslash *stencil* stages fused into the xpay/g5 site-local chain and the
  <p, A p> inner product as a terminal reduction: ONE halo'd pallas_call
  per iteration computes ap and its dot with p (neighbour spinors gather
  from the VMEM-resident halo'd block; the dot's per-site products never
  materialize in HBM).
* ``cg_update_graph`` — the "Scalar Mult Add" chain x+alpha*p, r-alpha*ap
  and the residual norm |r_new|^2 as a terminal reduction, again ONE
  launch (p, ap, x, r stream from HBM once; rr_prod never exists in HBM),
  with the traced alpha passed as a runtime scalar so the launch cache
  stays valid across iterations.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import (
    BatchedField, Field, LaunchGraph, TargetConfig, launch, target_sum,
    telemetry,
)
from repro.kernels.wilson_dslash import dslash
from repro.kernels.wilson_dslash.ops import dslash_stencil_body


# -- site-local linear-algebra kernels (the "Scalar Mult Add" family) ---------

def _axpy_body(v, *, a: float = None):
    return {"out": v["x"] * a + v["y"]}


def axpy(a, x: Field, y: Field, config: TargetConfig) -> Field:
    """a*x + y through the kernel layer (static a)."""
    return launch(_axpy_body, {"x": x, "y": y}, {"out": x.ncomp},
                  config=config, params=dict(a=a))["out"]


def _fma_body(v):
    """y + a*x with a supplied as a runtime (1, 1) scalar input."""
    return {"out": v["y"] + v["a"] * v["x"]}


def _square_body(v):
    return {"out": v["x"] * v["x"]}


def _mul_body(v):
    return {"out": v["x"] * v["y"]}


def _masked_fma_body(v):
    """y + a*x where the per-request mask is set, y (bitwise) elsewhere.

    The frozen branch must be a *select*, not arithmetic masking: y + 0*x
    flips -0.0 to +0.0 and poisons on non-finite x, so a converged
    request's state would drift from its single-solve bits."""
    return {"out": jnp.where(v["m"] > 0, v["y"] + v["a"] * v["x"], v["y"])}


def _m_g5_body(v, *, kappa):
    """g5 (psi - kappa d): one Wilson matvec + gamma5, site-local."""
    t = v["psi"] - kappa * v["d"]
    return {"out": jnp.concatenate([t[:12], -t[12:]], axis=0)}


def fused_xpay(y: Field, a, x: Field, config: TargetConfig) -> Field:
    """y + a*x with traced a (one cached fused launch); keeps x's pytree
    identity (name/layout) so it can ride a lax.while_loop carry."""
    g = LaunchGraph("cg_xpay").add(
        _fma_body, {"x": "x", "y": "y", "a": "a"}, {"out": x.ncomp}
    )
    out = g.launch({"x": x, "y": y}, scalars={"a": a}, config=config,
                   out_layouts={"out": x.layout})["out"]
    # cast back to the carry dtype: under a storage-dtype policy the launch
    # writes (and so quantizes) the output in storage precision, but the
    # while_loop carry must keep a fixed dtype (no-op without a policy)
    return x.with_data(out.data.astype(x.data.dtype))


def cg_update_graph(ncomp: int) -> LaunchGraph:
    """The CG inner-update chain as a LaunchGraph, ending in the residual
    norm as a terminal reduction (also used by the fused benchmarks for
    bytes-moved accounting): rr_prod never materializes in HBM."""
    return (
        LaunchGraph("cg_update")
        .add(_fma_body, {"x": "p", "y": "x", "a": "alpha"}, {"out": ncomp},
             rename={"out": "x_new"})
        .add(_fma_body, {"x": "ap", "y": "r", "a": "neg_alpha"}, {"out": ncomp},
             rename={"out": "r_new"})
        .add(_square_body, {"x": "r_new"}, {"out": ncomp},
             rename={"out": "rr_prod"})
        .add_reduce("rr_prod", op="sum", name="rr")
    )


def fused_cg_update(x: Field, r: Field, p: Field, ap: Field, alpha,
                    config: TargetConfig):
    """The CG "Scalar Mult Add" chain + residual norm as ONE fused launch:

        x_new = x + alpha p,  r_new = r - alpha ap,  rr = sum (r_new)^2

    Unfused this is three kernels plus a reduction pass (p, ap, x, r and
    three intermediates round-tripping HBM); fused, each operand streams in
    once, only x_new/r_new stream out and the squared residual accumulates
    on-chip.  Returns (x_new, r_new, rr) with x/r pytree identity preserved
    and rr a per-component (ncomp,) partial sum (``rr.sum()`` is |r_new|^2).
    """
    out = cg_update_graph(x.ncomp).launch(
        {"x": x, "r": r, "p": p, "ap": ap},
        scalars={"alpha": alpha, "neg_alpha": -alpha},
        config=config,
        outputs=("x_new", "r_new", "rr"),
        out_layouts={"x_new": x.layout, "r_new": r.layout},
    )
    return (x.with_data(out["x_new"].data.astype(x.data.dtype)),
            r.with_data(out["r_new"].data.astype(r.data.dtype)), out["rr"])


def masked_cg_update_graph(ncomp: int) -> LaunchGraph:
    """The batched-serving variant of :func:`cg_update_graph`: the x/r
    updates select per request on the runtime mask scalar ``m`` (1 while
    the request iterates, 0 once converged), so a frozen slot's state and
    residual are bitwise untouched while live slots update exactly as the
    unmasked chain would."""
    return (
        LaunchGraph("cg_update_masked")
        .add(_masked_fma_body, {"x": "p", "y": "x", "a": "alpha", "m": "m"},
             {"out": ncomp}, rename={"out": "x_new"})
        .add(_masked_fma_body, {"x": "ap", "y": "r", "a": "neg_alpha",
                                "m": "m"},
             {"out": ncomp}, rename={"out": "r_new"})
        .add(_square_body, {"x": "r_new"}, {"out": ncomp},
             rename={"out": "rr_prod"})
        .add_reduce("rr_prod", op="sum", name="rr")
    )


def fused_masked_cg_update(x, r, p, ap, alpha, mask, config: TargetConfig):
    """Per-request-masked CG update chain, one fused launch over the whole
    batch.  ``alpha`` and ``mask`` are per-request ``(batch,)`` scalars."""
    out = masked_cg_update_graph(x.ncomp).launch(
        {"x": x, "r": r, "p": p, "ap": ap},
        scalars={"alpha": alpha, "neg_alpha": -alpha, "m": mask},
        config=config,
        outputs=("x_new", "r_new", "rr"),
        out_layouts={"x_new": x.layout, "r_new": r.layout},
    )
    return (x.with_data(out["x_new"].data.astype(x.data.dtype)),
            r.with_data(out["r_new"].data.astype(r.data.dtype)),
            out["rr"])


def fused_masked_xpay(y, a, x, mask, config: TargetConfig):
    """Masked p-update: r + beta*p where the request is live, p bitwise
    frozen elsewhere (the batched form of :func:`fused_xpay`)."""
    g = LaunchGraph("cg_xpay_masked").add(
        _masked_fma_body, {"x": "x", "y": "y", "a": "a", "m": "m"},
        {"out": x.ncomp}
    )
    out = g.launch({"x": x, "y": y}, scalars={"a": a, "m": mask},
                   config=config, out_layouts={"out": x.layout})["out"]
    return x.with_data(out.data.astype(x.data.dtype))


def dot(x: Field, y: Field, config: TargetConfig) -> jnp.ndarray:
    """<x, y> as the real inner product over all components/sites.

    (For split re/im spinor fields this equals Re<x|y> of the complex
    inner product.)  Local reduction via the targetDP reduction API; the
    sharded path psums across the mesh.
    """
    prod = launch(lambda v: {"p": v["x"] * v["y"]}, {"x": x, "y": y},
                  {"p": x.ncomp}, config=config)["p"]
    return target_sum(prod, config).sum()


def batched_dot(x: BatchedField, y: BatchedField,
                config: TargetConfig) -> jnp.ndarray:
    """Per-request <x, y> over a batch, shape (batch,) — each element
    bitwise :func:`dot` of the corresponding slots: the elementwise product
    is lowering-independent and the batched ``target_sum`` folds each batch
    row in the single-Field accumulation order."""
    g = LaunchGraph("dot_prod").add(_mul_body, {"x": "x", "y": "y"},
                                    {"out": x.ncomp}, rename={"out": "p"})
    prod = g.launch({"x": x, "y": y}, config=config,
                    out_layouts={"p": x.layout})["p"]
    return target_sum(prod, config).sum(axis=-1)


def g5(psi: Field, config: TargetConfig) -> Field:
    """gamma5 psi: flips the sign of spin components 2 and 3."""

    def body(v):
        x = v["psi"]
        return {"out": jnp.concatenate([x[:12], -x[12:]], axis=0)}

    return launch(body, {"psi": psi}, {"out": psi.ncomp}, config=config)["out"]


# -- operator application -------------------------------------------------------

def wilson_normal_graph(kappa: float) -> LaunchGraph:
    """M^dag M p with <p, M^dag M p> as a terminal reduction, fused.

    Both dslash applications run as width-1 *stencil* stages (the "Shift"
    neighbour gathers read the VMEM-resident halo'd block — external inputs
    p and u carry a ring-2 halo, consumed one ring per dslash), the xpay/g5
    "Scalar Mult Add" stages run site-local on the same block, and the
    <p, ap> inner product accumulates on-chip: the whole normal-operator
    application is ONE pallas_call per CG iteration."""
    return (
        LaunchGraph("wilson_normal")
        .add_stencil(dslash_stencil_body, {"psi": "p", "u": "u"}, {"d": 24},
                     width=1, rename={"d": "d1"})
        .add(_m_g5_body, {"psi": "p", "d": "d1"}, {"out": 24},
             rename={"out": "t"}, params=dict(kappa=kappa))
        .add_stencil(dslash_stencil_body, {"psi": "t", "u": "u"}, {"d": 24},
                     width=1, rename={"d": "d2"})
        .add(_m_g5_body, {"psi": "t", "d": "d2"}, {"out": 24},
             rename={"out": "ap"}, params=dict(kappa=kappa))
        .add(_mul_body, {"x": "p", "y": "ap"}, {"out": 24},
             rename={"out": "pap_prod"})
        .add_reduce("pap_prod", op="sum", name="pap")
    )


def make_fused_normal(u: Field, kappa: float, config: TargetConfig):
    """Returns apply(p) -> (A p, <p, A p>) through the fused graph
    (A = M^dag M); ap keeps p's pytree identity for the while_loop carry.
    ``p`` may be a BatchedField (the gauge field is shared across the
    batch): ap comes back batched and the inner product per request,
    shape (batch,)."""
    bound = wilson_normal_graph(float(kappa)).bind(
        config=config, outputs=("ap", "pap"))

    def apply(p):
        out = bound({"p": p, "u": u}, out_layouts={"ap": p.layout})
        # axis=-1 folds the per-component partials: a scalar for a Field,
        # (batch,) for a BatchedField — bitwise the 1-D sum either way
        return p.with_data(out["ap"].data), out["pap"].sum(axis=-1)

    return apply


def make_wilson_op(u: Field, kappa: float, config: TargetConfig,
                   dslash_fn: Optional[Callable] = None):
    """Returns apply_m, apply_mdag, apply_normal (M^dag M)."""
    _dslash = dslash_fn or (lambda psi: dslash(psi, u, config=config))

    def apply_m(psi: Field) -> Field:
        d = _dslash(psi)
        return psi.with_canonical(psi.canonical() - kappa * d.canonical())

    def apply_mdag(psi: Field) -> Field:
        return g5(apply_m(g5(psi, config)), config)

    def apply_normal(psi: Field) -> Field:
        return apply_mdag(apply_m(psi))

    return apply_m, apply_mdag, apply_normal


class CGResult(NamedTuple):
    x: Field
    iterations: jnp.ndarray
    residual: jnp.ndarray  # final |r|^2 / |b|^2


def cg(
    apply_a: Callable[[Field], Field],
    b: Field,
    *,
    config: TargetConfig,
    tol: float = 1e-8,
    max_iter: int = 500,
    psum_axes: Tuple[str, ...] = (),
    apply_a_dot: Optional[Callable[[Field], Tuple[Field, jnp.ndarray]]] = None,
) -> CGResult:
    """Standard CG on a positive-definite operator, jax.lax.while_loop based
    so it jits and shards (dots are psum'd over ``psum_axes`` inside
    shard_map).

    apply_a_dot, when given, computes (A p, <p, A p>) in one fused launch
    (see make_fused_normal) — the iteration then runs TWO pallas_calls:
    operator+dot, and update-chain+residual-norm."""

    def psum(d):
        for ax in psum_axes:
            d = jax.lax.psum(d, ax)
        return d

    def gdot(x: Field, y: Field):
        return psum(dot(x, y, config))

    scope = telemetry.scope
    with scope("cg/init"):
        b2 = gdot(b, b)
        x0 = b.with_canonical(jnp.zeros_like(b.canonical()))
        r0 = b
        p0 = b
        rr0 = gdot(r0, r0)

    def cond(carry):
        x, r, p, rr, it = carry
        with scope("cg/scalars"):
            return jnp.logical_and(rr / b2 > tol, it < max_iter)

    def body(carry):
        x, r, p, rr, it = carry
        with scope("cg/normal"):
            if apply_a_dot is not None:
                # dslash + axpy chain + <p, ap> reduction: one fused launch
                ap, pap = apply_a_dot(p)
                pap = psum(pap)
            else:
                ap = apply_a(p)
                pap = gdot(p, ap)
        with scope("cg/scalars"):
            alpha = rr / pap
        # fused "Scalar Mult Add" chain: x/r updates + residual square +
        # its terminal sum in one launch — rr_prod never touches HBM.
        with scope("cg/update"):
            x, r, rr_vec = fused_cg_update(x, r, p, ap, alpha, config)
            rr_new = psum(rr_vec.sum())
        with scope("cg/scalars"):
            beta = rr_new / rr
            it = it + 1
        with scope("cg/xpay"):
            p = fused_xpay(r, beta, p, config)
        return (x, r, p, rr_new, it)

    x, r, p, rr, it = jax.lax.while_loop(cond, body, (x0, r0, p0, rr0, jnp.int32(0)))
    with scope("cg/scalars"):
        return CGResult(x=x, iterations=it, residual=rr / b2)


def cg_refined(
    apply_a_dot,
    b: Field,
    *,
    config: TargetConfig,
    tol: float = 1e-8,
    max_iter: int = 500,
    refine_k: int = 50,
    reliable: float = 1e-4,
    psum_axes: Tuple[str, ...] = (),
    apply_a_dot_hi=None,
) -> CGResult:
    """Iterative-refinement CG: low-precision inner iterations wrapped in
    precision-recovering restarts (the portable-LQCD production recipe).

    The outer loop keeps the solution ``x`` and the *true* residual
    ``r = b - A x`` in working precision.  Each outer step runs an inner CG
    on the correction system ``A d = r`` through ``apply_a_dot`` — whose
    launches may carry a bf16/fp32-storage :class:`DtypePolicy`, so the
    bandwidth-heavy iterations move narrow bytes — capped at ``refine_k``
    iterations or the ``reliable`` relative-residual trigger (the
    reliable-update stop: the inner recurrence residual is not trusted
    below that ratio).  The correction ``x += d`` and the true-residual
    recompute then happen in working precision via ``apply_a_dot_hi``
    (defaults to ``apply_a_dot``; pass the policy-free operator so the
    residual is exact — with an fp64 or compensated-fp32 accumulate where
    fp64 is unavailable).  Converges to the *working*-precision ``tol``
    even though the inner solves are quantized: each restart measures what
    the low-precision pass actually achieved and re-aims the next one.

    ``iterations`` in the result counts the total inner iterations (the
    bandwidth-dominant work), matching :func:`cg`'s accounting.
    """
    hi = apply_a_dot_hi or apply_a_dot
    scope = telemetry.scope

    def psum(d):
        for ax in psum_axes:
            d = jax.lax.psum(d, ax)
        return d

    def norm2(f: Field):
        # working-precision residual norm, independent of any storage
        # policy on `config` (the gate the outer loop trusts)
        c = f.canonical().astype(jnp.float32)
        return psum(jnp.sum(c * c))

    with scope("cg/init"):
        b2 = norm2(b)
        x0 = b.with_canonical(jnp.zeros_like(b.canonical()))

    def true_residual(x):
        ax, _ = hi(x)
        r = b.with_data(b.data - ax.data.astype(b.data.dtype))
        return r, norm2(r)

    def cond(carry):
        _x, _r, rr, it = carry
        with scope("cg/scalars"):
            return jnp.logical_and(rr / b2 > tol, it < max_iter)

    def body(carry):
        x, r, rr, it = carry
        inner = cg(None, r, config=config, tol=reliable,
                   max_iter=refine_k, psum_axes=psum_axes,
                   apply_a_dot=apply_a_dot)
        # x += d in working precision (never through a storage-dtype write)
        with scope("cg/refine"):
            x = x.with_data(x.data + inner.x.data.astype(x.data.dtype))
            r, rr = true_residual(x)
        with scope("cg/scalars"):
            return (x, r, rr, it + inner.iterations)

    x, _r, rr, it = jax.lax.while_loop(
        cond, body, (x0, b, b2, jnp.int32(0)))
    with scope("cg/scalars"):
        return CGResult(x=x, iterations=it, residual=rr / b2)


# -- batched CG (multi-simulation serving) --------------------------------------

class BatchedCGState(NamedTuple):
    """Per-slot CG state for a batch of independent same-lattice solves.

    Slot semantics: ``b2 > 0`` and ``rr / b2 > tol`` and ``it < max_iter``
    means the slot is live; an empty slot (all-zero rhs) has ``b2 == 0``
    and is inert (``0/0`` compares False), so a partially filled batch
    runs without special-casing."""

    x: BatchedField
    r: BatchedField
    p: BatchedField
    rr: jnp.ndarray   # (batch,) |r|^2 per slot
    b2: jnp.ndarray   # (batch,) |rhs|^2 per slot
    it: jnp.ndarray   # (batch,) int32, active iterations taken


class BatchedCGResult(NamedTuple):
    x: BatchedField
    iterations: jnp.ndarray  # (batch,) int32
    residual: jnp.ndarray    # (batch,) final |r|^2 / |b|^2 per slot


def batched_cg_state(rhs: BatchedField, config: TargetConfig) -> BatchedCGState:
    """Initial state: x = 0, r = p = rhs, per-slot norms — each slot set up
    exactly as :func:`cg` sets up a single solve."""
    with telemetry.scope("cg/init"):
        b2 = batched_dot(rhs, rhs, config)
        x0 = rhs.with_data(jnp.zeros_like(rhs.data))
        return BatchedCGState(x=x0, r=rhs, p=rhs, rr=b2, b2=b2,
                              it=jnp.zeros((rhs.batch,), jnp.int32))


def batched_cg_active(state: BatchedCGState, *, tol: float,
                      max_iter: int) -> jnp.ndarray:
    """(batch,) liveness mask — per slot, exactly the single-solve loop
    condition ``rr/b2 > tol and it < max_iter`` (NaN-false for empty
    slots, whose b2 is 0)."""
    return jnp.logical_and(state.rr / state.b2 > tol,
                           state.it < max_iter)


def batched_cg_iteration(
    state: BatchedCGState,
    apply_a_dot,
    *,
    config: TargetConfig,
    tol: float,
    max_iter: int,
) -> BatchedCGState:
    """One convergence-masked CG iteration over the whole batch: the fused
    normal-operator launch and the fused masked update chain each run ONCE
    for the full stack.  A live slot takes exactly the single-solve step
    (bitwise: the masked kernels select the identically computed update);
    a converged/empty slot's x, r, p, rr are bitwise frozen — it stays in
    the batch without perturbing anyone's residuals until the scheduler
    drains it."""
    scope = telemetry.scope
    with scope("cg/scalars"):
        act = batched_cg_active(state, tol=tol, max_iter=max_iter)
        m = act.astype(state.r.dtype)
    with scope("cg/normal"):
        ap, pap = apply_a_dot(state.p)
    with scope("cg/scalars"):
        # guard the frozen lanes' divides (their alpha/beta are never
        # selected)
        alpha = jnp.where(act, state.rr / jnp.where(act, pap, 1.0), 0.0)
    with scope("cg/update"):
        x, r, rr_vec = fused_masked_cg_update(
            state.x, state.r, state.p, ap, alpha, m, config)
    with scope("cg/scalars"):
        rr_new = jnp.where(act, rr_vec.sum(axis=-1), state.rr)
        beta = jnp.where(act, rr_new / jnp.where(act, state.rr, 1.0), 0.0)
        it = state.it + act.astype(state.it.dtype)
    with scope("cg/xpay"):
        p = fused_masked_xpay(r, beta, state.p, m, config)
    return BatchedCGState(x=x, r=r, p=p, rr=rr_new, b2=state.b2, it=it)


def batched_cg_refresh(state: BatchedCGState, rhs: BatchedField,
                       apply_a_dot_hi, *, tol: float, max_iter: int,
                       refine_every: int) -> BatchedCGState:
    """Reliable-update restart for the batched loop: on every slot whose
    active iteration count hits a multiple of ``refine_every``, replace the
    recurrence residual with the *true* residual ``b - A x`` (computed
    through the high-precision operator) and restart the search direction
    there; all other slots are bitwise untouched.  This is what keeps the
    batched/serve path converging to the working-precision tolerance when
    the per-iteration launches run under a bf16/fp32-storage policy — the
    recurrence residual drifts from the truth in low precision, and the
    periodic exact recompute re-aims the iteration."""
    with telemetry.scope("cg/refine"):
        act = batched_cg_active(state, tol=tol, max_iter=max_iter)
        sel = jnp.logical_and(act, state.it % refine_every == 0)
        ax, _ = apply_a_dot_hi(state.x)
        rt = (rhs.data.astype(jnp.float32)
              - ax.data.astype(jnp.float32)).astype(state.r.data.dtype)
        rr_t = state.r.with_data(rt).canonical().astype(jnp.float32)
        rr_t = jnp.sum(rr_t * rr_t, axis=(-2, -1)).astype(state.rr.dtype)
        selb = sel.reshape((-1,) + (1,) * (rt.ndim - 1))
        return BatchedCGState(
            x=state.x,
            r=state.r.with_data(jnp.where(selb, rt, state.r.data)),
            p=state.p.with_data(jnp.where(selb, rt, state.p.data)),
            rr=jnp.where(sel, rr_t, state.rr),
            b2=state.b2, it=state.it)


def cg_batched(
    apply_a_dot,
    rhs: BatchedField,
    *,
    config: TargetConfig,
    tol: float = 1e-8,
    max_iter: int = 500,
    refine_every: int = 0,
    apply_a_dot_hi=None,
) -> BatchedCGResult:
    """CG on a stack of independent right-hand sides under one shared
    operator, per-request convergence-masked: every iteration runs one
    fused operator launch and one fused update launch for the whole batch,
    and each slot's trajectory is bit-identical to :func:`cg` on that slot
    alone (asserted in tests/test_batch.py).  The loop runs until every
    slot has converged or hit max_iter; slots that finish early ride along
    frozen.

    ``refine_every > 0`` enables reliable-update restarts for
    mixed-precision configs (see :func:`batched_cg_refresh`): every that
    many active iterations a slot's residual is recomputed exactly as
    ``b - A x`` through ``apply_a_dot_hi`` (defaults to ``apply_a_dot``;
    pass the policy-free operator) and its search direction restarted.
    With ``refine_every=0`` the loop is bitwise the historical one."""
    hi = apply_a_dot_hi or apply_a_dot
    state0 = batched_cg_state(rhs, config)

    def cond(state):
        return jnp.any(batched_cg_active(state, tol=tol, max_iter=max_iter))

    def trig(state):
        return jnp.logical_and(
            batched_cg_active(state, tol=tol, max_iter=max_iter),
            state.it % refine_every == 0)

    def body(state):
        state = batched_cg_iteration(state, apply_a_dot, config=config,
                                     tol=tol, max_iter=max_iter)
        if refine_every > 0:
            state = jax.lax.cond(
                jnp.any(trig(state)),
                lambda s: batched_cg_refresh(
                    s, rhs, hi, tol=tol, max_iter=max_iter,
                    refine_every=refine_every),
                lambda s: s, state)
        return state

    state = jax.lax.while_loop(cond, body, state0)
    return BatchedCGResult(x=state.x, iterations=state.it,
                           residual=state.rr / state.b2)
