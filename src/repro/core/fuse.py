"""Launch graphs: fuse chains of kernels into one device kernel.

The paper's kernels are memory-bandwidth bound (§4), so the dominant cost of
a multi-kernel timestep is the HBM round-trip between ``__targetLaunch__``es:
every intermediate field is written to HBM by one kernel and re-read by the
next.  A :class:`LaunchGraph` takes an ordered chain of
:class:`~repro.core.target.TargetKernel` stages whose outputs feed later
inputs, traces the composed body once, and lowers it to a **single**
``pl.pallas_call`` — intermediates stay as values in VMEM/VREGs and never
touch HBM.  The jnp engine runs the same composed body over whole-lattice
canonical arrays (and is the fusion oracle).

Three stage kinds (paper §2.1.1 classifies kernels as site-local vs stencil;
§3.2.3 adds reductions):

``add``          site-local ("map") stage: the body sees canonical
                 ``(ncomp, L)`` chunks, one value per site.
``add_stencil``  stencil stage: the body additionally receives a
                 ``gather(name, disp)`` closure returning the input window
                 displaced by ``disp`` (``out(r) = in(r - disp)``,
                 ``|disp| <= width`` per dim).  Neighbour reads resolve from
                 VMEM-resident halo'd blocks, not a separate launch.
``add_reduce``   terminal reduction stage (``target_sum``/``target_max``
                 semantics): each program folds its block into a per-block
                 partial and accumulates it into a single small buffer, so
                 the reduction input never materializes in HBM.

Stencil graphs lower under one of two canonical-view strategies
(``LoweringPlan.view``).  ``"staged-nd"`` (the default) unpacks every input
to a canonical SoA-nd view as XLA ops around the single kernel — layout
round-trips through HBM for AoSoA data.  ``"block"`` is the *native AoSoA*
lowering: a halo'd AoSoA input is staged whole into VMEM in its physical
``(nblocks, ncomp, SAL)`` tile shape, each program rebases its x-slab
window onto the block axis (``SAL | halo'd inner-plane count`` keeps every
window a whole number of short arrays) and un-/re-packs in VMEM, and an
aligned AoSoA output is written back as native blocks — so the paper's
layout sweep (§3.1) reaches the halo'd chains (LB step, fused CG) with no
XLA pack/unpack round-trip.  Both views run the identical composed body on
identical window values: bit-identical outputs, asserted in
tests/test_view.py.

Site-local-only graphs lower over the flat 1-D site-block grid exactly as
before.  Graphs containing a stencil stage lower over **x-slabs of the
halo'd lattice**: every external input is halo-padded by the ring the
backward width analysis (:meth:`LaunchGraph.halo_widths`) assigns it —
periodic single-shard via ``core.stencil.halo_pad`` (``halo="periodic"``),
or pre-exchanged by the caller through ``core.halo`` inside shard_map
(``halo="pre"``) — and staged whole into VMEM (overlapping slab windows are
not expressible as disjoint BlockSpec windows; see
``target.build_halo_in_specs``).  Site-local stages are recomputed on halo
sites so a downstream stencil stage can gather neighbours of an
*intermediate* (e.g. LB collision fused into propagation's gather); each
value carries a shrinking "valid ring" and a stencil stage consuming a
ring-0 value raises a clear error.

Launch cache
------------
Each distinct (kernel chain, layouts, LoweringPlan, out_specs, input
signature) is built and ``jax.jit``-compiled once; repeated launches reuse
the compiled callable, so a timestep loop does not re-trace.  The cache key
is purely structural — stage *params* must be static Python values.  Runtime
scalars (e.g. CG's traced alpha/beta) are passed via ``scalars=``.

Planning
--------
How a graph lowers (vvl for the flat site-block grid, the x-slab ``bx`` for
the halo'd stencil grid, interpret fallback, halo strategy, canonical-view
choice) is a :class:`~repro.core.plan.LoweringPlan`, resolved per launch
from ``config.plan_policy`` ("default" heuristics / persisted "tuned" table
via ``core.tune`` / explicit plan) or overridden with ``launch(...,
plan=...)`` — which is how the autotuner times candidate plans through this
very machinery.

Probes: :func:`stats` counts traces and ``pallas_call`` constructions (each
fused pallas launch builds exactly one), so tests can assert both the
single-kernel lowering and cache hits.

Example (the CG residual loop, stencil + reduction)::

    g = (LaunchGraph("cg_op")
         .add_stencil(dslash_body, {"psi": "p", "u": "u"}, {"d": 24}, width=1)
         .add(xpay_body, ins={"x": "p", "d": "d"}, out_specs={"ap": 24})
         .add(mul_body, ins={"x": "p", "y": "ap"}, out_specs={"prod": 24})
         .add_reduce("prod", op="sum", name="pap"))
    out = g.launch({"p": fp, "u": fu}, config=TargetConfig("pallas"),
                   outputs=("ap", "pap"))
    out["ap"]   # Field (interior lattice)
    out["pap"]  # jnp array (ncomp,) — per-component sum, never in HBM
"""

from __future__ import annotations

import dataclasses
import logging
import math
from collections import OrderedDict
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import plan as plan_mod
from . import telemetry
from .field import BatchedField, Field
from .layout import Layout, LayoutKind
from .plan import VIEW_BLOCK, LoweringPlan
from .stencil import halo_pad, halo_pad_physical
from .target import (
    TargetConfig,
    TargetKernel,
    build_block_out_specs,
    build_halo_in_specs,
    build_in_specs,
    build_out_specs,
    build_reduce_specs,
    build_slab_out_specs,
    build_split_reduce_specs,
    build_tiled_out_specs,
)

__all__ = [
    "LaunchGraph",
    "BoundLaunch",
    "ReduceSpec",
    "fused_launch",
    "kahan_fold",
    "reduce_combine",
    "stats",
    "reset_stats",
    "clear_cache",
]

log = logging.getLogger(__name__)

_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
_CACHE_CAP = 256

# launch-cache counters now live in the core.telemetry registry under the
# "fuse." prefix; stats()/reset_stats() below are back-compat shims over it
_STAT_KEYS = ("traces", "pallas_calls", "cache_hits", "cache_misses")

# reduction monoids, keyed by op name (the single source ReduceSpec wraps)
_RED_COMBINE = {"sum": lambda a, b: a + b, "max": jnp.maximum}
_RED_FOLD = {"sum": jnp.sum, "max": jnp.max}


def stats() -> Dict[str, int]:
    """Launch-cache counters: traces (jit trace-time executions of a fused
    callable), pallas_calls (pallas_call constructions — one per fused pallas
    trace), cache_hits/cache_misses.  Thin view over the ``fuse.*``
    counters of :mod:`repro.core.telemetry` (same keys as ever)."""
    return {k: telemetry.counter_value(f"fuse.{k}") for k in _STAT_KEYS}


def reset_stats() -> None:
    telemetry.reset_counters("fuse.")


def clear_cache() -> None:
    _CACHE.clear()


@dataclasses.dataclass(frozen=True)
class ReduceSpec:
    """One terminal reduction's metadata: the single definition of a
    reduction monoid that the fused lowering, the overlap scheduler's
    per-slab combine and the split-reduction stage-2 combine all consume
    (previously an ad-hoc ``reduce_info()`` string tuple plus a separate
    ``reduce_combine(op)`` lookup plus an inline monoid table).

    op       "sum" | "max".
    source   the graph value being folded (None for a bare-op spec).
    ncomp    per-component width when statically known from the producing
             stage (None when the reduced value is an external input —
             launch resolves it from the input Field).
    dtype    the accumulate dtype (None: the launch's default out dtype).
    """

    op: str
    source: Optional[str] = None
    ncomp: Optional[int] = None
    dtype: Optional[object] = None

    def __post_init__(self):
        if self.op not in _RED_COMBINE:
            raise ValueError(
                f"unknown reduction op {self.op!r}; have {list(_RED_COMBINE)}")

    @property
    def combine(self) -> Callable:
        """The monoid combine fn — how any two partials merge."""
        return _RED_COMBINE[self.op]

    def init(self, shape, dtype) -> jax.Array:
        """Identity-filled accumulator (dtype-aware: integer max starts at
        iinfo.min, not a float -inf cast)."""
        dt = jnp.dtype(dtype)
        if self.op == "max":
            if jnp.issubdtype(dt, jnp.integer):
                return jnp.full(shape, jnp.iinfo(dt).min, dt)
            return jnp.full(shape, -jnp.inf, dt)
        return jnp.zeros(shape, dt)

    def fold(self, x: jax.Array, axis: int = -1) -> jax.Array:
        """Per-block fold along ``axis`` (the site axis)."""
        return _RED_FOLD[self.op](x, axis=axis)

    def combine_partials(self, parts: jax.Array, axis: int = 0) -> jax.Array:
        """The stage-2 combine: fold stage-1 partials along ``axis`` by a
        sequential monoid combine in index order.  Deterministic (fixed
        association for a fixed partial count) — the overlap scheduler's
        slab partials and the split-reduction rsplit rows both combine
        through here, so both strategies share one numerics contract:
        exact for max and integer sums, tolerance-level reassociation
        relative to the unsplit fold for fp sums."""
        n = parts.shape[axis]
        idx = [slice(None)] * parts.ndim
        idx[axis] = 0
        acc = parts[tuple(idx)]
        for k in range(1, n):
            idx[axis] = k
            acc = self.combine(acc, parts[tuple(idx)])
        return acc


def reduce_combine(op: str) -> Callable:
    """The combine function of a reduction monoid (``"sum"``/``"max"``) —
    kept as a thin shim over :class:`ReduceSpec` for existing callers."""
    return ReduceSpec(op=op).combine


def kahan_fold(x: jax.Array, axis: int = -1) -> jax.Array:
    """Compensated (Kahan) summation along ``axis``: a sequential
    sum-plus-compensation scan whose error is O(eps), independent of the
    element count — the fp32 stand-in for fp64 accumulation on targets
    where jax x64 is disabled (``core.plan.resolve_accumulate``).  All
    other axes are carried elementwise, so a (ncomp, nsites) fold costs
    one scan of length nsites with (ncomp,) carries."""
    x = jnp.moveaxis(x, axis, 0)

    def step(carry, xi):
        s, c = carry
        y = xi - c
        t = s + y
        return (t, (t - s) - y), None

    zero = jnp.zeros(x.shape[1:], x.dtype)
    (s, _c), _ = jax.lax.scan(step, (zero, zero), x)
    return s


def _kahan_combine(acc: jax.Array, part: jax.Array) -> jax.Array:
    """Kahan combine for a widened ``(..., ncomp, 2)`` accumulator —
    column 0 the running sum, column 1 the running compensation — folding
    a ``(..., ncomp, 1)`` partial in.  This is the cross-block combine of
    a compensated fused reduction: per-block partials fold plainly in the
    compute dtype, the grid-sequential accumulation across blocks carries
    compensation (the hierarchical contract tests/test_dtype.py pins)."""
    s, c = acc[..., 0:1], acc[..., 1:2]
    y = part - c
    t = s + y
    return jnp.concatenate([t, (t - s) - y], axis=-1)


def _hashable(v) -> bool:
    try:
        hash(v)
    except TypeError:
        return False
    return True


def _block_geometry(
    ordered_ins: Sequence[str],
    in_meta: Sequence[Tuple[int, Layout]],
    in_lats: Sequence[Tuple[int, ...]],
    in_rings: Sequence[int],
    halo: str,
    view: str,
    out_layouts: Mapping[str, Layout],
    field_outputs: Sequence[str],
    lattice: Tuple[int, ...],
    tiled: bool = False,
) -> Tuple[List[Tuple[int, ...]], List[bool]]:
    """Per-input halo'd lattices and native-AoSoA staging flags for a
    stencil lowering.  Under ``view="block"`` this is the launch-time form
    of ``core.plan.block_view_ok``: raises ValueError (naming the offending
    value) when an AoSoA input/output is not block-aligned or when nothing
    in the launch is AoSoA at all.

    ``tiled`` (LoweringPlan.by/.bz set) applies the same discipline per
    tile: *input* alignment is unchanged — native windows still slice whole
    x-planes on the block axis, the y/z tile is cut after the VMEM unpack,
    so SAL-aligned tile edges come for free — but native AoSoA *outputs*
    degrade to canonical tile writes (a y/z tile is not a contiguous block
    run), so the output-alignment check does not apply and an AoSoA input
    is required for the view to pay at all."""
    # in "pre"/"overlap" mode the caller's lattices already carry the halo
    hlats = [
        tuple(s + (2 * ring if halo == "periodic" else 0) for s in lat)
        for lat, ring in zip(in_lats, in_rings)
    ]
    native_in = [False] * len(in_lats)
    if view != VIEW_BLOCK:
        return hlats, native_in
    aosoa_in_play = False
    for idx, ((ncomp, lay), hlat) in enumerate(zip(in_meta, hlats)):
        if lay.kind is not LayoutKind.AOSOA:
            continue
        aosoa_in_play = True
        inner_h = int(math.prod(hlat[1:]))
        if inner_h % lay.sal:
            raise ValueError(
                f"view='block': AoSoA(sal={lay.sal}) input "
                f"{ordered_ins[idx]!r} has halo'd inner-plane site "
                f"count {inner_h} not divisible by sal — x-slab "
                f"windows would split short arrays; use "
                f"view='staged-nd' or a conforming sal "
                f"(core.plan.block_view_ok)")
        native_in[idx] = True
    if tiled:
        if not aosoa_in_play:
            raise ValueError(
                "view='block' under a tiled plan (by/bz) lowers AoSoA "
                "*inputs* natively (tiled outputs always write canonical "
                "tiles), but no input layout of this launch is AoSoA — "
                "use view='staged-nd'")
        return hlats, native_in
    if not aosoa_in_play and not any(
            out_layouts[o].kind is LayoutKind.AOSOA for o in field_outputs):
        raise ValueError(
            "view='block' lowers AoSoA tiles natively, but no "
            "input or output layout of this launch is AoSoA — "
            "use view='staged-nd'")
    inner = int(math.prod(lattice[1:]))
    bad = [o for o in field_outputs
           if out_layouts[o].kind is LayoutKind.AOSOA
           and inner % out_layouts[o].sal]
    if bad:
        raise ValueError(
            f"view='block': AoSoA output(s) {bad} have sal not "
            f"dividing the interior inner-plane site count {inner} "
            f"— slab rows would split short arrays; use "
            f"view='staged-nd' or a conforming sal")
    return hlats, native_in


def _stage_in_cast(storage_dt, compute_dt, in_dtypes):
    """The DtypePolicy stage-in cast over a launch's input arrays: floating
    inputs truncate to the storage dtype (the fidelity cost — and the HBM
    bytes cut — of narrow storage) and upcast to the effective compute
    dtype for kernel arithmetic; non-float inputs pass through bitwise.
    Returns None when the policy casts nothing (the bitwise default)."""
    if storage_dt is None and compute_dt is None:
        return None
    cdt = compute_dt or storage_dt
    floats = tuple(jnp.issubdtype(jnp.dtype(dt), jnp.floating)
                   for dt in in_dtypes)

    def cast(datas):
        out = []
        for d, isf in zip(datas, floats):
            if isf:
                if storage_dt is not None and d.dtype != storage_dt:
                    d = d.astype(storage_dt)
                if d.dtype != cdt:
                    d = d.astype(cdt)
            out.append(d)
        return tuple(out)

    return cast


def _fold_window(op: str, a: jax.Array) -> jax.Array:
    """Fold an ``(ncomp, *window)`` value over its site axes to a
    ``(ncomp, 1)`` per-block partial: the leading site axes elementwise,
    then the sublane axis, then the lane axis with keepdims — the order
    Mosaic lowers (a rank-1 result, or one reduce over both tiled axes,
    aborts its layout pass)."""
    fold = _RED_FOLD[op]
    if a.ndim > 3:
        a = fold(a, axis=tuple(range(1, a.ndim - 2)))
    if a.ndim == 3:
        a = fold(a, axis=1)
    return fold(a, axis=1, keepdims=True)


def _crop_ring(arr: jax.Array, r_from: int, r_to: int) -> jax.Array:
    """Shrink an (ncomp, *window) value from valid ring r_from to r_to."""
    if r_from == r_to:
        return arr
    d = r_from - r_to
    sl = (slice(None),) + tuple(slice(d, s - d) for s in arr.shape[1:])
    return arr[sl]


@dataclasses.dataclass(frozen=True)
class _Stage:
    kernel: Optional[TargetKernel]
    ins: Tuple[Tuple[str, str], ...]              # (body arg, graph value name)
    outs: Tuple[Tuple[str, str, Optional[int], object], ...]
    params: Tuple[Tuple[str, object], ...]
    kind: str = "map"                             # "map" | "stencil" | "reduce"
    width: int = 0                                # stencil halo reach
    op: str = ""                                  # reduce monoid

    def signature(self):
        # keyed on the body *function*, not the TargetKernel wrapper, so
        # graphs rebuilt per call (e.g. per LudwigConfig) still hit the cache
        body = self.kernel.body if self.kernel is not None else None
        name = self.kernel.name if self.kernel is not None else self.op
        return (self.kind, self.width, self.op, body, name, self.ins,
                self.outs, self.params)


class LaunchGraph:
    """An ordered chain of kernel stages fused into one launch."""

    def __init__(self, name: str = "fused"):
        self.name = name
        self._stages: List[_Stage] = []
        # telemetry: bytes_moved is a per-shape constant but a full graph
        # walk — memoized so the launch span costs O(dict lookup), keeping
        # the enabled path under the CI <=1% overhead gate
        self._bytes_memo: Dict[tuple, Dict[str, int]] = {}

    def __repr__(self):  # pragma: no cover - cosmetic
        names = [s.kernel.name if s.kernel else f"reduce:{s.op}"
                 for s in self._stages]
        return f"LaunchGraph({self.name}, stages={names})"

    # -- construction ----------------------------------------------------------

    def _check_not_after_reduce(self, kind: str, name: str) -> None:
        red = [s for s in self._stages if s.kind == "reduce"]
        if red:
            raise ValueError(
                f"{kind} stage {name!r} cannot follow a reduction stage: a "
                f"reduction changes the value shape (per-site lattice -> "
                f"per-component), so only further terminal reductions may "
                f"come after it"
            )

    def _prepare_stage(self, kern, ins, out_specs, params, rename):
        if not isinstance(kern, TargetKernel):
            kern = TargetKernel(kern)
        params = dict(params or {})
        for k, v in params.items():
            # params are baked into the (hashed) cache key: traced values and
            # arrays must go through launch scalars instead
            if isinstance(v, (jax.core.Tracer, jax.Array)) or not _hashable(v):
                raise TypeError(
                    f"stage {kern.name!r} param {k!r} is a traced/array/"
                    f"unhashable value; pass runtime scalars via "
                    f"launch(..., scalars={{...}}) or use a static Python value"
                )
        rename = dict(rename or {})
        produced = {v for st in self._stages for (_, v, _, _) in st.outs}
        outs = []
        for body_key, spec in out_specs.items():
            ncomp, dtype = spec if isinstance(spec, tuple) else (spec, None)
            vname = rename.get(body_key, body_key)
            if vname in produced:
                raise ValueError(
                    f"graph value {vname!r} produced twice; use rename= to "
                    f"give stage {kern.name!r}'s output a fresh name"
                )
            produced.add(vname)
            outs.append((body_key, vname, int(ncomp), dtype))
        return kern, tuple(sorted(ins.items())), tuple(outs), tuple(
            sorted(params.items()))

    def add(
        self,
        kern: Union[TargetKernel, Callable],
        ins: Mapping[str, str],
        out_specs: Mapping[str, Union[int, Tuple[int, object]]],
        *,
        params: Optional[Mapping] = None,
        rename: Optional[Mapping[str, str]] = None,
    ) -> "LaunchGraph":
        """Append a site-local stage.  Returns self (chainable).

        ins        body argument name -> graph value name.
        out_specs  body output key -> ncomp (or (ncomp, dtype)).
        rename     body output key -> graph value name (default: the key).
        params     static keyword arguments baked into the trace (and the
                   cache key).  Traced values must go through launch scalars.
        """
        kern, ins_t, outs, params_t = self._prepare_stage(
            kern, ins, out_specs, params, rename)
        self._check_not_after_reduce("site-local", kern.name)
        self._stages.append(_Stage(kern, ins_t, outs, params_t))
        return self

    def add_stencil(
        self,
        kern: Union[TargetKernel, Callable],
        ins: Mapping[str, str],
        out_specs: Mapping[str, Union[int, Tuple[int, object]]],
        *,
        width: int = 1,
        params: Optional[Mapping] = None,
        rename: Optional[Mapping[str, str]] = None,
    ) -> "LaunchGraph":
        """Append a stencil stage reaching ``width`` sites per lattice dim.

        The body signature gains a gather closure::

            def body(v, gather, **params) -> dict

        ``v[arg]`` is the centered (ncomp, *window) value; ``gather(arg, d)``
        is the same window displaced by ``d`` (``out(r) = in(r - d)``,
        ``|d_j| <= width``).  Bodies see nd windows, not flat chunks, because
        displacement is geometric.  Inputs must be valid on a ring >= width:
        external Fields are halo-padded automatically (periodic) or by the
        caller (``halo="pre"``); intermediates are valid wherever earlier
        stages computed them (site-local stages recompute on halo sites).
        """
        if width < 1:
            raise ValueError(f"stencil stage needs width >= 1, got {width}")
        kern, ins_t, outs, params_t = self._prepare_stage(
            kern, ins, out_specs, params, rename)
        self._check_not_after_reduce("stencil", kern.name)
        self._stages.append(
            _Stage(kern, ins_t, outs, params_t, kind="stencil",
                   width=int(width)))
        return self

    def add_reduce(
        self, value: str, op: str = "sum", *, name: Optional[str] = None
    ) -> "LaunchGraph":
        """Append a terminal reduction of graph value ``value`` over all
        (interior) sites.  The result, named ``name`` (default
        ``"{value}_{op}"``), is returned by launch() as a per-component
        ``(ncomp,)`` jnp array — it is an accumulator, not a Field, and its
        per-site input never touches HBM on the pallas engine."""
        if op not in _RED_COMBINE:
            raise ValueError(
                f"unknown reduction op {op!r}; have {list(_RED_COMBINE)}")
        out_name = name or f"{value}_{op}"
        reduced = {v for st in self._stages if st.kind == "reduce"
                   for (_, v, _, _) in st.outs}
        if value in reduced:
            raise ValueError(
                f"cannot reduce {value!r}: it is itself a reduction result")
        produced = {v for st in self._stages for (_, v, _, _) in st.outs}
        if out_name in produced:
            raise ValueError(f"graph value {out_name!r} produced twice")
        self._stages.append(
            _Stage(None, (("x", value),), (("out", out_name, None, None),),
                   (), kind="reduce", op=op))
        return self

    # -- graph structure -------------------------------------------------------

    @property
    def has_stencil(self) -> bool:
        return any(st.kind == "stencil" for st in self._stages)

    def external_inputs(self) -> List[str]:
        """Value names consumed but never produced by an earlier stage, in
        first-use order — what launch() must be fed as Fields or scalars."""
        produced, ext = set(), []
        for st in self._stages:
            for _, vname in st.ins:
                if vname not in produced and vname not in ext:
                    ext.append(vname)
            for _, vname, _, _ in st.outs:
                produced.add(vname)
        return ext

    def _produced(self) -> Dict[str, Tuple[Optional[int], object]]:
        return {
            vname: (ncomp, dtype)
            for st in self._stages
            for (_, vname, ncomp, dtype) in st.outs
        }

    def _reduce_outputs(self) -> List[str]:
        return [v for st in self._stages if st.kind == "reduce"
                for (_, v, _, _) in st.outs]

    def reduce_specs(self) -> Dict[str, ReduceSpec]:
        """reduce output name -> :class:`ReduceSpec` — the one definition of
        this graph's reduction metadata, consumed by the overlap
        scheduler's per-slab combine and the split-reduction stage-2
        combine.  The mapping is exact per (output, input) pair: a reduce
        stage folds exactly one graph value, and a stage that somehow
        carries several inputs is rejected here rather than silently keyed
        on the last one (which would mis-combine overlap partials).
        ``ncomp`` is filled in when the reduced value is produced by an
        earlier stage (None for reductions of external inputs — launch
        resolves those from the input Field)."""
        prod = self._produced()
        specs: Dict[str, ReduceSpec] = {}
        for st in self._stages:
            if st.kind != "reduce":
                continue
            if len(st.ins) != 1:
                raise ValueError(
                    f"reduce stage producing {[o for (_, o, _, _) in st.outs]} "
                    f"has {len(st.ins)} inputs {[v for (_, v) in st.ins]}; a "
                    f"terminal reduction folds exactly one graph value")
            ((_, vname),) = st.ins
            for (_, out, _, dtype) in st.outs:
                specs[out] = ReduceSpec(
                    op=st.op, source=vname,
                    ncomp=prod.get(vname, (None, None))[0], dtype=dtype)
        return specs

    def reduce_info(self) -> Dict[str, Tuple[str, str]]:
        """reduce output name -> (source graph value, monoid op): the
        legacy string-tuple view of :meth:`reduce_specs`, kept for
        existing callers."""
        return {o: (s.source, s.op) for o, s in self.reduce_specs().items()}

    def _required_rings(self, outputs: Sequence[str]) -> Dict[str, int]:
        """Backward width analysis: minimum valid halo ring each graph value
        needs so the requested outputs are exact on the interior."""
        need: Dict[str, int] = {o: 0 for o in outputs}
        for st in reversed(self._stages):
            if st.kind == "reduce":
                for _, v in st.ins:
                    need[v] = max(need.get(v, 0), 0)
                continue
            r = max((need.get(v, 0) for (_, v, _, _) in st.outs), default=0)
            w = st.width if st.kind == "stencil" else 0
            for _, v in st.ins:
                need[v] = max(need.get(v, 0), r + w)
        return need

    def halo_widths(
        self, outputs: Optional[Sequence[str]] = None
    ) -> Dict[str, int]:
        """Halo ring each external input needs (0 for site-local-only graphs).

        ``halo="periodic"`` pads inputs by exactly these widths via
        ``stencil.halo_pad``; ``halo="pre"`` callers must supply Fields
        already padded (and exchanged via ``core.halo``) by them."""
        if outputs is None:
            outputs = [v for (_, v, _, _) in self._stages[-1].outs]
        need = self._required_rings(tuple(outputs))
        return {n: need.get(n, 0) for n in self.external_inputs()}

    def plan_signature(self):
        """Process-stable structural signature for the autotune-table key:
        kernel *names* plus chain structure, not function objects (which do
        not survive a process boundary the persisted table must cross)."""
        sig = []
        for st in self._stages:
            name = st.kernel.name if st.kernel is not None else st.op
            sig.append((st.kind, name, st.width, st.op, st.ins, st.outs,
                        tuple((k, repr(v)) for k, v in st.params)))
        return (self.name, tuple(sig))

    def plan_key(
        self,
        ins: Mapping[str, Field],
        *,
        config: Optional[TargetConfig] = None,
        outputs: Optional[Sequence[str]] = None,
        halo: str = "periodic",
        lattice: Optional[Tuple[int, ...]] = None,
    ) -> str:
        """The persisted-autotuner key for launching this graph with these
        inputs: (graph signature, input layouts/dtypes, lattice, engine,
        halo, outputs, jax backend) — see core.plan.graph_plan_key."""
        config = config or TargetConfig()
        ext = self.external_inputs()
        ordered_ins = [n for n in ext if n in ins]
        if outputs is None:
            outputs = [v for (_, v, _, _) in self._stages[-1].outs]
        if lattice is None:
            lattice = next(iter(ins.values())).lattice
        inputs = tuple(
            (n, ins[n].ncomp, str(ins[n].dtype), ins[n].layout.name,
             tuple(ins[n].lattice))
            for n in ordered_ins)
        # 'pre' and 'overlap' share the input contract (pre-exchanged
        # halos), so they share table entries: the strategy choice lives in
        # the persisted plan's halo field, not the key
        halo_key = "pre" if halo == "overlap" else halo
        # a batched launch tunes (and persists winners) per batch size and
        # per batched-vs-shared input split; batch=0 keeps pre-batch keys
        batch = max((int(getattr(ins[n], "batch", 0)) for n in ordered_ins),
                    default=0)
        batch_key = 0
        if batch:
            batch_key = (batch,) + tuple(
                int(bool(getattr(ins[n], "batch", 0))) for n in ordered_ins)
        return plan_mod.graph_plan_key(
            self.plan_signature(), engine=config.engine, halo=halo_key,
            outputs=tuple(outputs), inputs=inputs, lattice=tuple(lattice),
            backend=jax.default_backend(), batch=batch_key)

    def bytes_moved(
        self,
        ins_ncomp: Mapping[str, int],
        nsites: int,
        outputs: Optional[Sequence[str]] = None,
        itemsize: int = 4,
        dtypes=None,
    ) -> Dict[str, int]:
        """HBM traffic model of this chain, fused vs unfused (paper Fig. 4
        counting: reads + writes, itemsize bytes per element).  ``dtypes``
        (a :class:`~repro.core.plan.DtypePolicy`) re-prices every element
        at the policy's *storage* dtype itemsize — the traffic a
        mixed-precision plan actually contracts to move.

        unfused: every stage reads all its inputs from and writes all its
        outputs to HBM — including the per-site reduction input a separate
        ``target_sum`` pass would re-read.  fused: each distinct external
        input is read once and only the requested non-reduction graph
        outputs are written (reduction partials are O(ncomp), counted as 0).
        Stencil halo re-reads are not modelled (halo/interior -> 0 with
        lattice size).  Scalars are ignored.
        """
        if dtypes is not None and dtypes.storage:
            itemsize = dtypes.storage_itemsize(itemsize)
        ncomp = dict(ins_ncomp)
        for vname, (nc, _) in self._produced().items():
            ncomp[vname] = 0 if nc is None else nc
        if outputs is None:
            outputs = [v for (_, v, _, _) in self._stages[-1].outs]
        unfused = 0
        for st in self._stages:
            for _, vname in st.ins:
                unfused += ncomp.get(vname, 0)
            for _, vname, nc, _ in st.outs:
                unfused += 0 if nc is None else nc
        fused = sum(ncomp.get(n, 0) for n in self.external_inputs())
        fused += sum(ncomp[o] for o in outputs)
        return {
            "unfused": unfused * nsites * itemsize,
            "fused": fused * nsites * itemsize,
        }

    # -- execution --------------------------------------------------------------

    def bind(
        self,
        *,
        config: Optional[TargetConfig] = None,
        outputs: Optional[Sequence[str]] = None,
        out_layouts: Optional[Mapping[str, Layout]] = None,
        halo: str = "periodic",
        plan: Optional[LoweringPlan] = None,
    ) -> "BoundLaunch":
        """Freeze the launch-site keyword sprawl into a reusable callable.

        Every driver threads the same ``config=/outputs=/out_layouts=/
        halo=`` keywords verbatim through each ``launch`` call; ``bind``
        captures them once and returns a :class:`BoundLaunch` — call it
        with just the input Fields (plus per-call ``scalars=``/``plan=``,
        or keyword overrides).  The raw ``launch(...)`` form keeps working
        unchanged::

            step = graph.bind(config=cfg, outputs=("ap", "pap"))
            out = step({"p": p, "u": u}, scalars={"alpha": a})
        """
        return BoundLaunch(
            self,
            config=config,
            outputs=tuple(outputs) if outputs is not None else None,
            out_layouts=dict(out_layouts) if out_layouts else None,
            halo=halo,
            plan=plan,
        )

    def launch(
        self,
        ins: Dict[str, Field],
        *,
        config: Optional[TargetConfig] = None,
        outputs: Optional[Sequence[str]] = None,
        scalars: Optional[Mapping] = None,
        out_layouts: Optional[Mapping[str, Layout]] = None,
        halo: str = "periodic",
        plan: Optional[LoweringPlan] = None,
    ) -> Dict[str, Union[Field, jax.Array]]:
        """Execute the fused chain (the multi-kernel __targetLaunch__).

        ins         graph value name -> input Field (all sharing a lattice).
        outputs     graph value names to materialize (default: the last
                    stage's outputs).  Intermediates not listed here never
                    touch HBM on the pallas engine.  Reduction outputs come
                    back as (ncomp,) jnp arrays, everything else as Fields.
        scalars     graph value name -> runtime scalar (traced values OK).
        out_layouts graph output name -> Layout (default: first input's).
        halo        stencil graphs only: "periodic" pads external inputs by
                    halo_widths() with periodic wrap (single shard);
                    "pre" expects inputs already padded + exchanged by the
                    caller (core.halo inside shard_map), so the launch
                    composes with the MPI-layer decomposition; "overlap"
                    takes the same pre-exchanged inputs but executes as
                    interior/boundary split sub-launches (core.overlap —
                    a plan with halo="overlap", e.g. a persisted tuner
                    winner, upgrades a "pre" call the same way).
        plan        explicit LoweringPlan for this launch (overrides
                    config.plan_policy — the autotuner's sweep hook).
        """
        if not self._stages:
            raise ValueError("LaunchGraph has no stages")
        if not ins:
            raise ValueError("fused launch needs at least one input Field")
        if halo not in ("periodic", "pre", "overlap"):
            raise ValueError(
                f"halo must be 'periodic', 'pre' or 'overlap', got {halo!r}")
        config = config or TargetConfig()
        scalars = dict(scalars or {})
        stencil = self.has_stencil
        if halo in ("pre", "overlap") and not stencil:
            raise ValueError(
                f"halo={halo!r} only applies to graphs with stencil stages")

        first = next(iter(ins.values()))
        # leading batch axis: BatchedField inputs stack `batch` independent
        # same-shape lattices; plain Fields are shared across the batch
        # (e.g. one gauge field serving many right-hand sides)
        in_batch = {n: int(getattr(f, "batch", 0)) for n, f in ins.items()}
        batch = max(in_batch.values(), default=0)
        if batch:
            bad_b = {n: b for n, b in in_batch.items() if b not in (0, batch)}
            if bad_b:
                raise ValueError(
                    f"batched inputs disagree on the batch size: {bad_b} "
                    f"vs {batch}; every BatchedField in one launch must "
                    f"stack the same number of lattices")
        double = sorted(set(ins) & set(scalars))
        if double:
            raise ValueError(
                f"value(s) {double} supplied as both input Fields and "
                f"scalars; each graph value must have exactly one binding"
            )
        ext = self.external_inputs()
        missing = [n for n in ext if n not in ins and n not in scalars]
        if missing:
            raise ValueError(
                f"graph consumes value(s) {missing} produced by no earlier "
                f"stage and not supplied as inputs or scalars"
            )
        ordered_ins = [n for n in ext if n in ins]
        ordered_scalars = [n for n in ext if n in scalars]

        prod = self._produced()
        if outputs is None:
            outputs = [v for (_, v, _, _) in self._stages[-1].outs]
        outputs = tuple(outputs)
        unknown = [o for o in outputs if o not in prod]
        if unknown:
            raise ValueError(f"requested outputs {unknown} produced by no stage")
        red_names = set(self._reduce_outputs())
        field_outputs = tuple(o for o in outputs if o not in red_names)
        red_outputs = tuple(o for o in outputs if o in red_names)

        # halo rings per external Field input (0 unless a stencil needs it)
        need = self._required_rings(outputs) if stencil else {}
        in_rings = tuple(need.get(n, 0) for n in ordered_ins)

        # interior lattice: what output Fields live on
        if stencil and halo in ("pre", "overlap"):
            interiors = {
                n: tuple(s - 2 * r for s in ins[n].lattice)
                for n, r in zip(ordered_ins, in_rings)
            }
            lattice = interiors[ordered_ins[0]]
            bad = {n: lat for n, lat in interiors.items() if lat != lattice}
            if bad or any(s < 1 for s in lattice):
                raise ValueError(
                    f"pre-halo'd inputs disagree on the interior lattice "
                    f"(lattice - 2*ring per input, rings {dict(zip(ordered_ins, in_rings))}): "
                    f"{ {n: ins[n].lattice for n in ordered_ins} }"
                )
        else:
            lattice = first.lattice
            bad = {k: f.lattice for k, f in ins.items() if f.lattice != lattice}
            if bad:
                raise ValueError(
                    f"all Fields in a fused launch must share nsites and "
                    f"lattice shape: {first.name!r} has {lattice}, "
                    f"mismatched {bad}"
                )
        nsites = int(math.prod(lattice))

        out_layouts = dict(out_layouts or {})
        for o in field_outputs:
            out_layouts.setdefault(o, first.layout)
        # resolve default dtypes (and reduce ncomp) now: part of the cache key
        out_info = {}
        for o in outputs:
            nc, dt = prod[o]
            if nc is None:  # reduction: ncomp of the reduced value
                (src,) = [v for st in self._stages if st.kind == "reduce"
                          for (_, v2, _, _) in st.outs if v2 == o
                          for (_, v) in st.ins]
                src_nc = prod.get(src, (None, None))[0]
                if src_nc is None:
                    src_nc = ins[src].ncomp
                nc = src_nc
            out_info[o] = (int(nc), jnp.dtype(dt or first.dtype))

        # per-site staging shapes for the VMEM budget model: what the
        # planner needs to estimate a candidate's per-program footprint
        # (and auto-tile y/z when whole-staging would blow the budget)
        vmem_views = None
        if stencil:
            vmem_views = (
                tuple((ins[n].ncomp, r, jnp.dtype(ins[n].dtype).itemsize)
                      for n, r in zip(ordered_ins, in_rings)),
                tuple((out_info[o][0], out_info[o][1].itemsize)
                      for o in field_outputs),
            )

        # -- planning: every lowering decision comes from a LoweringPlan ----
        all_layouts = ([ins[n].layout for n in ordered_ins]
                       + [out_layouts[o] for o in field_outputs])
        from_table = False
        if plan is None:
            policy = getattr(config, "plan_policy", "default")
            if isinstance(policy, LoweringPlan):
                plan = policy
            elif policy == "tuned":
                from . import tune
                plan = tune.lookup(self.plan_key(
                    ins, config=config, outputs=outputs, halo=halo,
                    lattice=lattice))
                from_table = plan is not None
            elif policy != "default":
                raise ValueError(
                    f"unknown plan_policy {policy!r}; use 'default', "
                    f"'tuned' or an explicit LoweringPlan")
        if plan is None:  # default policy, or tuned-table miss
            plan = plan_mod.default_plan(
                config, nsites=nsites, layouts=all_layouts,
                stencil=stencil, lattice=lattice, halo=halo,
                vmem_views=vmem_views)
        else:
            plan = plan_mod.adapt_plan(plan, stencil=stencil, halo=halo)
            try:
                plan.validate(nsites=nsites, lattice=lattice,
                              layouts=all_layouts, stencil=stencil)
                if (stencil and plan.engine == "pallas"
                        and plan.view == VIEW_BLOCK):
                    # alignment pre-check: same errors _build_nd would
                    # raise, surfaced here so a stale table entry can
                    # degrade instead of crashing the launch
                    _block_geometry(
                        ordered_ins,
                        [(ins[n].ncomp, ins[n].layout) for n in ordered_ins],
                        [ins[n].lattice for n in ordered_ins],
                        in_rings, halo, plan.view, out_layouts,
                        field_outputs, lattice,
                        tiled=bool(plan.by or plan.bz))
            except ValueError:
                if not from_table:
                    raise
                # tuning must never break a launch (e.g. a persisted
                # native-block winner meeting an out_layouts override
                # whose SAL cannot tile the interior): degrade to the
                # default heuristics, logged not fatal
                log.warning(
                    "tuned plan %s does not fit launch of graph %r "
                    "(lattice %s) — falling back to the default plan",
                    plan.describe(), self.name, lattice, exc_info=True)
                plan = plan_mod.default_plan(
                    config, nsites=nsites, layouts=all_layouts,
                    stencil=stencil, lattice=lattice, halo=halo,
                    vmem_views=vmem_views)

        # -- dtype policy: precision becomes a lowering decision ------------
        # a config-level policy applies when the resolved plan carries none
        # of its own (a tuned/explicit plan's policy wins); with no policy
        # anywhere every path below is bitwise the pre-policy code
        cfg_dtypes = getattr(config, "dtypes", None)
        if cfg_dtypes and plan.dtypes is None:
            plan = dataclasses.replace(plan, dtypes=cfg_dtypes)
        storage_dt = compute_dt = None
        acc_fold = {}  # red output -> (accumulate jnp dtype, compensated?)
        if plan.dtypes:
            pol = plan.dtypes.validate()
            storage_dt = jnp.dtype(pol.storage) if pol.storage else None
            compute_dt = jnp.dtype(pol.compute) if pol.compute else None
            acc_name, acc_comp = plan_mod.resolve_accumulate(pol.accumulate)
            red_ops = {o: s.op for o, s in self.reduce_specs().items()}
            for o in outputs:
                nc, dt = out_info[o]
                # float-only rule: integer fields and max/integer
                # reductions are bitwise exempt from the dtype axis
                if not jnp.issubdtype(dt, jnp.floating):
                    continue
                if o in red_names:
                    if acc_name and red_ops.get(o) == "sum":
                        out_info[o] = (nc, jnp.dtype(acc_name))
                        acc_fold[o] = (jnp.dtype(acc_name), acc_comp)
                elif storage_dt is not None:
                    out_info[o] = (nc, storage_dt)

        if stencil and plan.halo == "overlap":
            # split schedule: interior + boundary sub-launches (each a
            # plain halo="pre" launch through this very machinery)
            from . import overlap as overlap_mod
            return overlap_mod.execute_split(
                self, ins, config=config, outputs=outputs, scalars=scalars,
                out_layouts=out_layouts, plan=plan)

        engine, interpret = plan.engine, plan.interpret
        vvl, bx = plan.vvl, plan.bx

        # launch span (core.telemetry): host-side only — attrs are strings
        # and ints, the traced computation is untouched.  The disabled path
        # costs one predicate; plan.describe() is only built when recording.
        t_override = getattr(config, "telemetry", None)
        tspan = (telemetry.span(
            f"launch/{self.name}",
            override=t_override,
            plan=plan.describe(),
            engine=engine,
            lattice=str(tuple(lattice)),
            batch=batch,
            halo=halo,
            from_tuned_table=from_table,
        ) if telemetry.enabled(t_override)
            else telemetry.NULL_SPAN)

        in_batched = tuple(bool(in_batch[n]) for n in ordered_ins)
        key = (
            plan,
            lattice,
            batch,
            in_batched,
            tuple(st.signature() for st in self._stages),
            tuple(
                (n, ins[n].ncomp, str(ins[n].dtype), ins[n].layout,
                 ins[n].lattice, r)
                for n, r in zip(ordered_ins, in_rings)
            ),
            tuple(ordered_scalars),
            outputs,
            tuple((o, out_layouts.get(o), str(out_info[o][1])) for o in outputs),
        )
        fn = _CACHE.get(key)
        if fn is None:
            telemetry.inc("fuse.cache_misses")
            tspan.set(cache="miss")
            build = self._build_nd if stencil else self._build_flat
            build_kw = dict(
                engine=engine,
                ordered_ins=ordered_ins,
                in_meta=[(ins[n].ncomp, ins[n].layout) for n in ordered_ins],
                in_lats=[ins[n].lattice for n in ordered_ins],
                in_rings=in_rings,
                ordered_scalars=ordered_scalars,
                field_outputs=field_outputs,
                red_outputs=red_outputs,
                out_info=out_info,
                out_layouts=out_layouts,
                lattice=lattice,
                halo=halo,
                vvl=vvl,
                bx=bx,
                interpret=interpret,
                rsplit=plan.rsplit,
                batch=batch,
                in_batched=in_batched,
                by=plan.by,
                bz=plan.bz,
                in_dtypes=tuple(jnp.dtype(ins[n].dtype)
                                for n in ordered_ins),
                storage_dt=storage_dt,
                compute_dt=compute_dt,
                acc_fold=acc_fold,
            )
            if stencil:  # only the stencil lowering is view-sensitive
                build_kw["view"] = plan.view
            fn = build(**build_kw)
            _CACHE[key] = fn
            while len(_CACHE) > _CACHE_CAP:
                _CACHE.popitem(last=False)
        else:
            telemetry.inc("fuse.cache_hits")
            tspan.set(cache="hit")
            _CACHE.move_to_end(key)

        datas = tuple(ins[n].data for n in ordered_ins)
        # scalars join kernel arithmetic, so they cast to the effective
        # compute dtype under a policy (float launches only)
        scalar_dt = first.dtype
        if (compute_dt is not None or storage_dt is not None) and \
                jnp.issubdtype(jnp.dtype(first.dtype), jnp.floating):
            scalar_dt = compute_dt or storage_dt
        if batch:
            # scalars may be per-request, shape (batch,) — e.g. the masked
            # CG's per-slot alpha/beta — or plain scalars broadcast to all
            svals = []
            for n in ordered_scalars:
                v = jnp.asarray(scalars[n], scalar_dt)
                if v.ndim == 0:
                    v = jnp.broadcast_to(v, (batch,))
                elif v.shape != (batch,):
                    raise ValueError(
                        f"batched launch scalar {n!r} must be a scalar or a "
                        f"({batch},) per-request vector, got shape {v.shape}")
                svals.append(v.reshape(batch, 1, 1))
            svals = tuple(svals)
        else:
            svals = tuple(
                jnp.asarray(scalars[n], scalar_dt).reshape(1, 1)
                for n in ordered_scalars
            )
        results = fn(datas, svals)
        if tspan:
            # modeled HBM bytes (the fig3/fig4 counting) over the measured
            # wall interval -> achieved GB/s + live roofline placement.
            # Under a storage dtype policy the per-element byte count is
            # the *storage* itemsize — that is the traffic the policy
            # exists to cut — and the memo is keyed per policy so twin
            # plans never share rows
            itemsize = jnp.dtype(first.dtype).itemsize
            if plan.dtypes and plan.dtypes.storage:
                itemsize = plan.dtypes.storage_itemsize(itemsize)
            bkey = (tuple((n, ins[n].ncomp) for n in ordered_ins), nsites,
                    outputs, itemsize, plan.dtypes)
            bm = self._bytes_memo.get(bkey)
            if bm is None:
                bm = self._bytes_memo[bkey] = self.bytes_moved(
                    {n: ins[n].ncomp for n in ordered_ins}, nsites,
                    outputs=outputs, itemsize=itemsize)
            bfac = max(batch, 1)
            tspan.set(
                bytes_fused=bm["fused"] * bfac,
                bytes_unfused=bm["unfused"] * bfac,
                **telemetry.roofline_placement(
                    bm["fused"] * bfac, tspan.elapsed))
            tspan.end()

        out: Dict[str, Union[Field, jax.Array]] = {}
        ordered_out = list(field_outputs) + list(red_outputs)
        for o, val in zip(ordered_out, results):
            if o in red_names:
                out[o] = val  # (ncomp,) or batched (batch, ncomp)
            elif batch:
                ncomp, _ = out_info[o]
                out[o] = BatchedField(o, batch, ncomp, lattice,
                                      out_layouts[o], val)
            else:
                ncomp, _ = out_info[o]
                out[o] = Field(o, ncomp, lattice, out_layouts[o], val)
        return out

    # -- composed bodies ---------------------------------------------------------

    def _run_stages(self, values: Dict[str, jax.Array]) -> Tuple[
            Dict[str, jax.Array], Dict[str, jax.Array]]:
        """Flat composed body (site-local graphs): one pass over all stages.
        ``values`` maps graph names to (ncomp, L) arrays (L = nsites for jnp,
        vvl inside the pallas kernel) plus (1, 1) scalars.  Returns (values,
        partials) where partials holds per-block reduction folds."""
        partials: Dict[str, jax.Array] = {}
        for st in self._stages:
            if st.kind == "reduce":
                ((_, vname),) = st.ins
                # keepdims: a (ncomp, 1) partial, never a rank-1 value
                # (which Mosaic cannot lay out)
                partials[st.outs[0][1]] = _RED_FOLD[st.op](
                    values[vname], axis=1, keepdims=True)
                continue
            chunks = {arg: values[v] for arg, v in st.ins}
            outs = st.kernel.body(chunks, **dict(st.params))
            for body_key, vname, ncomp, _ in st.outs:
                arr = outs[body_key]
                if arr.shape[0] != ncomp:
                    raise ValueError(
                        f"stage {st.kernel.name!r} output {body_key!r} has "
                        f"ncomp {arr.shape[0]}, declared {ncomp}"
                    )
                values[vname] = arr
        return values, partials

    def _run_stages_nd(
        self,
        values: Dict[str, Tuple[jax.Array, Optional[int]]],
        site_ndim: int,
    ) -> Tuple[Dict[str, Tuple[jax.Array, Optional[int]]],
               Dict[str, jax.Array]]:
        """Stencil composed body: values are (array, ring) pairs where array
        has shape (ncomp, *window) and ring counts valid halo sites around
        the window's interior.  Site-local stages run on the nd window itself
        (bodies are elementwise over the trailing site axes, so no reshape
        merges lattice dims inside a kernel — Mosaic cannot relayout those)
        — recomputing on halo sites so later stencil stages can gather from
        intermediates; stencil stages shrink the ring by their width;
        reductions fold the ring-0 interior into per-block partials."""
        partials: Dict[str, jax.Array] = {}
        for st in self._stages:
            if st.kind == "reduce":
                ((_, vname),) = st.ins
                arr, r = values[vname]
                partials[st.outs[0][1]] = _fold_window(
                    st.op, _crop_ring(arr, r, 0))
                continue

            stage_ins = [(arg, values[v]) for arg, v in st.ins]
            rings = [r for _, (_, r) in stage_ins if r is not None]
            if not rings:
                raise ValueError(
                    f"stage {st.kernel.name!r} has no Field inputs")
            r_in = min(rings)

            if st.kind == "stencil":
                r_out = r_in - st.width
                if r_out < 0:
                    raise ValueError(
                        f"stencil stage {st.kernel.name!r} (width {st.width})"
                        f" consumes a value valid only on ring {r_in}; its "
                        f"inputs need ring >= {st.width} — pad/exchange "
                        f"external inputs by halo_widths(), and do not chain "
                        f"it after a stage that already consumed the halo"
                    )
                by_arg = dict(stage_ins)
                width = st.width

                def gather(name, disp, _by_arg=by_arg, _r_out=r_out,
                           _width=width):
                    if name not in _by_arg:
                        raise KeyError(
                            f"gather({name!r}): not an input of this stage")
                    arr, r = _by_arg[name]
                    if r is None:
                        raise ValueError(
                            f"gather({name!r}): scalars have no geometry")
                    disp = tuple(int(d) for d in disp)
                    if len(disp) != site_ndim:
                        raise ValueError(
                            f"gather({name!r}): disp {disp} must have one "
                            f"entry per lattice dim ({site_ndim})")
                    if any(abs(d) > _width for d in disp):
                        raise ValueError(
                            f"gather({name!r}): |disp|={disp} exceeds stage "
                            f"width {_width}")
                    off = r - _r_out
                    sl = (slice(None),) + tuple(
                        slice(off - d, arr.shape[j + 1] - off - d)
                        for j, d in enumerate(disp)
                    )
                    return arr[sl]

                zeros = (0,) * site_ndim
                chunks = {}
                for arg, (arr, r) in stage_ins:
                    if r is None:  # scalar: broadcast over the nd window
                        chunks[arg] = arr.reshape((1,) * (1 + site_ndim))
                    else:
                        chunks[arg] = gather(arg, zeros)
                outs = st.kernel.body(chunks, gather, **dict(st.params))
                for body_key, vname, ncomp, _ in st.outs:
                    arr = outs[body_key]
                    if arr.shape[0] != ncomp:
                        raise ValueError(
                            f"stage {st.kernel.name!r} output {body_key!r} "
                            f"has ncomp {arr.shape[0]}, declared {ncomp}"
                        )
                    values[vname] = (arr, r_out)
                continue

            # site-local: crop all inputs to the common ring, run on the
            # (ncomp, *window) values directly
            chunks = {}
            for arg, (arr, r) in stage_ins:
                if r is None:  # scalar: broadcast over the nd window
                    chunks[arg] = arr.reshape((1,) * (1 + site_ndim))
                else:
                    chunks[arg] = _crop_ring(arr, r, r_in)
            outs = st.kernel.body(chunks, **dict(st.params))
            for body_key, vname, ncomp, _ in st.outs:
                arr = outs[body_key]
                if arr.shape[0] != ncomp:
                    raise ValueError(
                        f"stage {st.kernel.name!r} output {body_key!r} has "
                        f"ncomp {arr.shape[0]}, declared {ncomp}"
                    )
                values[vname] = (arr, r_in)
        return values, partials

    # -- lowering: flat site-block grid (site-local graphs) ----------------------

    def _build_flat(
        self,
        *,
        engine: str,
        ordered_ins: Sequence[str],
        in_meta: Sequence[Tuple[int, Layout]],
        in_lats,
        in_rings,
        ordered_scalars: Sequence[str],
        field_outputs: Tuple[str, ...],
        red_outputs: Tuple[str, ...],
        out_info: Mapping[str, Tuple[int, object]],
        out_layouts: Mapping[str, Layout],
        lattice: Tuple[int, ...],
        halo: str,
        vvl: int,
        bx: int,
        interpret: bool,
        rsplit: int = 1,
        batch: int = 0,
        in_batched: Sequence[bool] = (),
        by: int = 0,
        bz: int = 0,
        in_dtypes: Sequence[object] = (),
        storage_dt=None,
        compute_dt=None,
        acc_fold: Optional[Mapping[str, Tuple[object, bool]]] = None,
    ) -> Callable:
        # by/bz only drive the stencil (_build_nd) lowering; plan.validate()
        # rejects tiles on site-local chains, so they are always 0 here —
        # accepted so launch() can share one build_kw
        del by, bz
        run_stages = self._run_stages
        nsites = int(math.prod(lattice))
        red_spec = self.reduce_specs()
        acc_fold = dict(acc_fold or {})
        cast_in = _stage_in_cast(storage_dt, compute_dt, in_dtypes)
        if not in_batched:
            in_batched = (False,) * len(ordered_ins)

        def red_partial(o, values, partials):
            """One reduction output's per-launch partial.  Policy-
            accumulated sums refold the (whole-lattice) source in the
            accumulate dtype — Kahan when compensated — instead of casting
            the compute-dtype fold after the fact."""
            if o in acc_fold:
                dt, comp = acc_fold[o]
                src = values[red_spec[o].source].astype(dt)
                return kahan_fold(src, axis=1) if comp \
                    else jnp.sum(src, axis=1)
            return partials[o][:, 0].astype(out_info[o][1])

        if engine == "jnp":

            def one(datas, svals):
                if cast_in is not None:
                    datas = cast_in(datas)
                values = {}
                for n, (_, lay), d in zip(ordered_ins, in_meta, datas):
                    values[n] = lay.unpack(d)
                for n, s in zip(ordered_scalars, svals):
                    values[n] = s
                values, partials = run_stages(values)
                res = [
                    out_layouts[o].pack(values[o].astype(out_info[o][1]))
                    for o in field_outputs
                ]
                res += [red_partial(o, values, partials)
                        for o in red_outputs]
                return tuple(res)

            if batch:
                # one trace, vmapped over the stack; shared (plain Field)
                # inputs broadcast with in_axes=None — the batched analogue
                # of the whole-lattice oracle, element-bitwise identical to
                # running `one` per batch element
                vone = jax.vmap(one, in_axes=(
                    tuple(0 if b else None for b in in_batched), 0))

                def fn(datas, svals):
                    telemetry.inc("fuse.traces")
                    return vone(datas, svals)
            else:

                def fn(datas, svals):
                    telemetry.inc("fuse.traces")
                    return one(datas, svals)

            return jax.jit(fn)

        # pallas: the whole chain is ONE pallas_call over the site-block
        # grid — batched launches grow a leading batch grid axis, so the
        # grid is (batch, nblocks) and every BlockSpec picks its batch
        # row.  A split-reduction plan (rsplit > 1) partitions the block
        # axis into (rsplit, nblocks/rsplit): split segment s covers
        # blocks [s*per, (s+1)*per) in the unsplit order, accumulating its
        # own stage-1 partial row; the stage-2 combine folds the rows in
        # segment order after the call.
        nblocks = nsites // vvl
        per = nblocks // rsplit
        site_grid = (rsplit, per) if rsplit > 1 else (nblocks,)
        grid = ((batch,) + site_grid) if batch else site_grid
        nin, nsc = len(ordered_ins), len(ordered_scalars)
        in_specs = build_in_specs(in_meta, vvl)
        out_shapes, out_block_specs = build_out_specs(
            field_outputs, out_info, out_layouts, nsites, vvl
        )
        # compensated (Kahan) sums widen their accumulator to (ncomp, 2):
        # column 0 the running sum, column 1 the running compensation
        red_widths = {o: 2 for o in red_outputs
                      if o in acc_fold and acc_fold[o][1]}
        if rsplit > 1:
            in_specs = _split_specs(in_specs, per)
            out_block_specs = _split_specs(out_block_specs, per)
            red_shapes, red_block_specs = build_split_reduce_specs(
                red_outputs, out_info, rsplit, red_widths)
        else:
            red_shapes, red_block_specs = build_reduce_specs(
                red_outputs, out_info, red_widths)
        if batch:
            in_specs = _batch_specs(in_specs, in_batched)
            in_specs += [pl.BlockSpec((1, 1, 1), lambda b, *_: (b, 0, 0))
                         for _ in range(nsc)]
            out_shapes = _batch_shapes(out_shapes, batch)
            out_block_specs = _batch_specs(
                out_block_specs, [True] * len(out_block_specs))
            red_shapes = _batch_shapes(red_shapes, batch)
            red_block_specs = _batch_specs(
                red_block_specs, [True] * len(red_block_specs))
        else:
            in_specs += [pl.BlockSpec((1, 1), lambda *_: (0, 0))
                         for _ in range(nsc)]
        out_shapes += red_shapes
        out_block_specs += red_block_specs
        nfield = len(field_outputs)
        name = self.name
        red_axis = len(grid) - 1

        def fused_kernel(*refs):
            in_refs = refs[:nin]
            sc_refs = refs[nin : nin + nsc]
            out_refs = refs[nin + nsc : nin + nsc + nfield]
            acc_refs = refs[nin + nsc + nfield :]
            values = {}
            for n, (ncomp, lay), bat, r in zip(
                    ordered_ins, in_meta, in_batched, in_refs):
                blk = r[...][0] if (batch and bat) else r[...]
                values[n] = lay.block_to_canonical(blk, ncomp, vvl)
            for n, r in zip(ordered_scalars, sc_refs):
                values[n] = r[...][0] if batch else r[...]
            values, partials = run_stages(values)
            for o, r in zip(field_outputs, out_refs):
                ncomp, dtype = out_info[o]
                blk = out_layouts[o].canonical_to_block(
                    values[o].astype(dtype), ncomp, vvl
                )
                r[...] = blk[None] if batch else blk
            for o, r in zip(red_outputs, acc_refs):
                spec = red_spec[o]
                part = partials[o].astype(out_info[o][1])
                while part.ndim < len(r.shape):
                    part = part[None]
                # compensated sums carry (sum, compensation) columns
                # across blocks; per-block partials fold plainly in the
                # compute dtype (the hierarchical Kahan contract)
                comb = _kahan_combine if o in red_widths else spec.combine
                _accumulate(r, comb, spec.init, part,
                            axes=(red_axis,))

        def fn(datas, svals):
            telemetry.inc("fuse.traces")
            telemetry.inc("fuse.pallas_calls")
            if cast_in is not None:
                datas = cast_in(datas)
            call = pl.pallas_call(
                fused_kernel,
                grid=grid,
                in_specs=in_specs,
                out_specs=(
                    out_block_specs if len(out_block_specs) > 1 else out_block_specs[0]
                ),
                out_shape=out_shapes if len(out_shapes) > 1 else out_shapes[0],
                interpret=interpret,
                name=name,
            )
            res = call(*datas, *svals)
            if len(out_shapes) == 1:
                res = (res,)
            # reduction accumulators (..., ncomp, 1) -> (..., ncomp); a
            # split plan's (..., rsplit, ncomp) stage-1 rows go through
            # the stage-2 combine in segment order
            out = []
            for i, r in enumerate(res):
                if i < nfield:
                    out.append(r)
                    continue
                acc = r[..., 0]
                if rsplit > 1:
                    acc = red_spec[red_outputs[i - nfield]].combine_partials(
                        acc, axis=-2)
                out.append(acc)
            return tuple(out)

        return jax.jit(fn)

    # -- lowering: halo'd x-slab grid (stencil graphs) ---------------------------

    def _build_nd(
        self,
        *,
        engine: str,
        ordered_ins: Sequence[str],
        in_meta: Sequence[Tuple[int, Layout]],
        in_lats: Sequence[Tuple[int, ...]],
        in_rings: Sequence[int],
        ordered_scalars: Sequence[str],
        field_outputs: Tuple[str, ...],
        red_outputs: Tuple[str, ...],
        out_info: Mapping[str, Tuple[int, object]],
        out_layouts: Mapping[str, Layout],
        lattice: Tuple[int, ...],
        halo: str,
        vvl: int,
        bx: int,
        interpret: bool,
        view: str,
        rsplit: int = 1,
        batch: int = 0,
        in_batched: Sequence[bool] = (),
        by: int = 0,
        bz: int = 0,
        in_dtypes: Sequence[object] = (),
        storage_dt=None,
        compute_dt=None,
        acc_fold: Optional[Mapping[str, Tuple[object, bool]]] = None,
    ) -> Callable:
        run_nd = self._run_stages_nd
        site_ndim = len(lattice)
        site_dims = tuple(range(1, site_ndim + 1))
        red_spec = self.reduce_specs()
        acc_fold = dict(acc_fold or {})
        cast_in = _stage_in_cast(storage_dt, compute_dt, in_dtypes)
        if not in_batched:
            in_batched = (False,) * len(ordered_ins)

        def to_halo_nd(n, meta, lat, ring, d):
            """Physical data -> canonical (ncomp, *padded_lattice)."""
            ncomp, lay = meta
            nd = lay.unpack(d).reshape((ncomp,) + tuple(lat))
            if halo == "periodic" and ring > 0:
                nd = halo_pad(nd, ring, site_dims)
            return nd

        def red_partial_nd(o, values, partials):
            """As _build_flat's red_partial: policy-accumulated sums refold
            the ring-0 interior of the source in the accumulate dtype."""
            if o in acc_fold:
                dt, comp = acc_fold[o]
                arr, r = values[red_spec[o].source]
                a0 = _crop_ring(arr, r, 0)
                a0 = a0.reshape(a0.shape[0], -1).astype(dt)
                return kahan_fold(a0, axis=1) if comp \
                    else jnp.sum(a0, axis=1)
            return partials[o][:, 0].astype(out_info[o][1])

        if engine == "jnp":

            def one(datas, svals):
                if cast_in is not None:
                    datas = cast_in(datas)
                values = {}
                for n, meta, lat, ring, d in zip(
                        ordered_ins, in_meta, in_lats, in_rings, datas):
                    values[n] = (to_halo_nd(n, meta, lat, ring, d), ring)
                for n, s in zip(ordered_scalars, svals):
                    values[n] = (s, None)
                values, partials = run_nd(values, site_ndim)
                res = []
                for o in field_outputs:
                    arr, r = values[o]
                    a0 = _crop_ring(arr, r, 0)
                    ncomp, dtype = out_info[o]
                    res.append(out_layouts[o].pack(
                        a0.reshape(ncomp, -1).astype(dtype)))
                res += [red_partial_nd(o, values, partials)
                        for o in red_outputs]
                return tuple(res)

            if batch:
                vone = jax.vmap(one, in_axes=(
                    tuple(0 if b else None for b in in_batched), 0))

                def fn(datas, svals):
                    telemetry.inc("fuse.traces")
                    return vone(datas, svals)
            else:

                def fn(datas, svals):
                    telemetry.inc("fuse.traces")
                    return one(datas, svals)

            return jax.jit(fn)

        # pallas: ONE pallas_call over x-slabs of the halo'd lattice.  The
        # halo'd inputs are staged whole into VMEM (overlapping slab windows
        # are not disjoint Blocked windows); each program dynamic-slices its
        # halo'd window out, runs every stage on it, writes its interior
        # slab, and accumulates reduction partials into the shared buffer.
        #
        # view="staged-nd": inputs are unpacked to canonical nd views (XLA
        # ops) before staging and outputs packed after — AoSoA data pays an
        # HBM relayout round-trip on both sides of the kernel.
        # view="block" (native AoSoA): an aligned AoSoA input is staged in
        # its physical (nblocks, ncomp, SAL) tile shape — in "pre" mode the
        # caller's array is used as-is, zero staging ops — the per-program
        # window slice is rebased to the block axis (row_blocks = halo'd
        # inner-plane sites / SAL tiles per x-plane) and unpacked in VMEM;
        # an aligned AoSoA output is packed in VMEM and written as native
        # blocks.  Non-AoSoA values take the staged path either way (SOA
        # staging is a view, AoS a transpose).
        #
        # A *tiled* plan (by/bz > 0) appends one sequential grid axis per
        # tiled lattice dim after the x-slab axis, iterating fastest — each
        # program computes one (bx, by, bz) tile from a halo'd tile window.
        # On the interpret/off-TPU fallback the inputs still stage whole
        # (the window is a dynamic_slice of VMEM-staged data, bitwise
        # identical to the untiled lowering); on a real TPU the inputs stay
        # in HBM and each tile window is DMA'd into one of two VMEM scratch
        # slots while the previous tile computes (double-buffered
        # prefetch), so per-program VMEM is bounded by the tile, not the
        # lattice.
        nslabs = lattice[0] // bx
        per = nslabs // rsplit
        tiled = bool(by or bz)
        nty = (lattice[1] // by) if by else 1
        ntz = (lattice[2] // bz) if bz else 1
        site_grid = (rsplit, per) if rsplit > 1 else (nslabs,)
        if by:
            site_grid += (nty,)
        if bz:
            site_grid += (ntz,)
        grid = ((batch,) + site_grid) if batch else site_grid
        nin, nsc = len(ordered_ins), len(ordered_scalars)
        hlats, native_in = _block_geometry(
            ordered_ins, in_meta, in_lats, in_rings, halo, view,
            out_layouts, field_outputs, lattice, tiled=tiled)
        stage_shapes = []
        for (ncomp, lay), hlat, nat in zip(in_meta, hlats, native_in):
            if nat:
                hsites = int(math.prod(hlat))
                stage_shapes.append((hsites // lay.sal, ncomp, lay.sal))
            else:
                stage_shapes.append((ncomp,) + hlat)
        in_specs = build_halo_in_specs(stage_shapes)
        if tiled:
            # disjoint (bx, by, bz) tiles are directly expressible as
            # Blocked windows; native AoSoA *outputs* degrade to canonical
            # tile writes (a y/z tile is not a contiguous block run)
            out_shapes, out_block_specs = build_tiled_out_specs(
                field_outputs, out_info, lattice, bx, by, bz
            )
            native_out = [False] * len(field_outputs)
        elif view == VIEW_BLOCK:
            # _block_geometry already rejected misaligned AoSoA outputs
            out_shapes, out_block_specs, native_out = build_block_out_specs(
                field_outputs, out_info, out_layouts, lattice, bx
            )
        else:
            out_shapes, out_block_specs = build_slab_out_specs(
                field_outputs, out_info, lattice, bx
            )
            native_out = [False] * len(field_outputs)
        # compensated (Kahan) sums widen their accumulator to (ncomp, 2)
        red_widths = {o: 2 for o in red_outputs
                      if o in acc_fold and acc_fold[o][1]}
        if rsplit > 1:
            in_specs = _split_specs(in_specs, per)
            out_block_specs = _split_specs(out_block_specs, per)
            red_shapes, red_block_specs = build_split_reduce_specs(
                red_outputs, out_info, rsplit, red_widths)
        else:
            red_shapes, red_block_specs = build_reduce_specs(
                red_outputs, out_info, red_widths)
        if batch:
            in_specs = _batch_specs(in_specs, in_batched)
            in_specs += [pl.BlockSpec((1, 1, 1), lambda b, *_: (b, 0, 0))
                         for _ in range(nsc)]
            out_shapes = _batch_shapes(out_shapes, batch)
            out_block_specs = _batch_specs(
                out_block_specs, [True] * len(out_block_specs))
            red_shapes = _batch_shapes(red_shapes, batch)
            red_block_specs = _batch_specs(
                red_block_specs, [True] * len(red_block_specs))
        else:
            in_specs += [pl.BlockSpec((1, 1), lambda *_: (0, 0))
                         for _ in range(nsc)]
        out_shapes += red_shapes
        out_block_specs += red_block_specs
        nfield = len(field_outputs)
        inner_int = int(math.prod(lattice[1:]))
        name = self.name
        axis0 = 1 if batch else 0
        # accumulator rows initialize at the first program of *all* axes
        # addressing one row: the x-slab axis plus any trailing tile axes
        # (batch and split-segment axes select separate buffer rows)
        acc_axes = tuple(range(axis0 + (1 if rsplit > 1 else 0), len(grid)))

        def tile_tail(ys, zs, ring, hlat):
            """(starts, sizes) of a program's halo'd window on the lattice
            dims after x: tiled dims cut a (tile + 2*ring) window at the
            tile origin, untiled dims cover the whole halo'd extent."""
            starts, sizes = [], []
            for d in range(1, site_ndim):
                if d == 1 and by:
                    starts.append(ys)
                    sizes.append(by + 2 * ring)
                elif d == 2 and bz:
                    starts.append(zs)
                    sizes.append(bz + 2 * ring)
                else:
                    starts.append(0)
                    sizes.append(hlat[d])
            return starts, sizes

        def finish_tile(values, sc_refs, out_refs, acc_refs):
            """Shared kernel tail: scalars in, stages, tile writes,
            reduction accumulation — identical for the staged fallback
            and the DMA-pipelined kernel (bitwise-identity lever)."""
            for n, r in zip(ordered_scalars, sc_refs):
                values[n] = (r[...][0] if batch else r[...], None)
            values, partials = run_nd(values, site_ndim)
            for o, nat, r in zip(field_outputs, native_out, out_refs):
                arr, ring = values[o]
                a0 = _crop_ring(arr, ring, 0).astype(out_info[o][1])
                if nat:  # pack the interior slab in VMEM: native blocks out
                    ncomp = out_info[o][0]
                    sal = out_layouts[o].sal
                    a0 = a0.reshape(
                        ncomp, bx * inner_int // sal, sal).transpose(1, 0, 2)
                r[...] = a0[None] if batch else a0
            for o, r in zip(red_outputs, acc_refs):
                spec = red_spec[o]
                part = partials[o].astype(out_info[o][1])
                while part.ndim < len(r.shape):
                    part = part[None]
                comb = _kahan_combine if o in red_widths else spec.combine
                _accumulate(r, comb, spec.init, part, axes=acc_axes)

        def fused_kernel(*refs):
            in_refs = refs[:nin]
            sc_refs = refs[nin : nin + nsc]
            out_refs = refs[nin + nsc : nin + nsc + nfield]
            acc_refs = refs[nin + nsc + nfield :]
            if rsplit > 1:  # x-slab index rebased from the split grid axes
                i = pl.program_id(axis0) * per + pl.program_id(axis0 + 1)
                tax = axis0 + 2
            else:
                i = pl.program_id(axis0)
                tax = axis0 + 1
            jt = 0
            if by:
                jt = pl.program_id(tax)
                tax += 1
            kt = pl.program_id(tax) if bz else 0
            xs = i * bx
            ys = jt * by
            zs = kt * bz
            values = {}
            for n, (ncomp, lay), hlat, ring, nat, bat, r in zip(
                    ordered_ins, in_meta, hlats, in_rings, native_in,
                    in_batched, in_refs):
                # the halo'd window is read straight from the VMEM-staged
                # ref with pl.ds (Mosaic has no dynamic_slice on loaded
                # values); batched refs carry a leading length-1 batch-row
                # axis
                lead = (0,) if (batch and bat) else ()
                rows = bx + 2 * ring
                tstarts, tsizes = tile_tail(ys, zs, ring, hlat)
                if nat:
                    # block-coordinate rebase: each x-plane of the halo'd
                    # lattice is row_blocks whole short arrays, so the
                    # window [xs, xs + rows) is a contiguous run on the
                    # block axis; the canonical nd window is recovered by
                    # the AoSoA unpack on VMEM-resident data (transpose of
                    # a (nblk, ncomp, sal) tile stack — never through HBM).
                    # Under a tiled plan the y/z tile is then cut from the
                    # unpacked canonical window — tile edges never split a
                    # short array, so view="block" composes with any
                    # dividing by/bz (the per-tile block_view_ok
                    # discipline)
                    row_blocks = int(math.prod(hlat[1:])) // lay.sal
                    tile = r[lead + (pl.ds(xs * row_blocks,
                                           rows * row_blocks),)]
                    window = tile.transpose(1, 0, 2).reshape(
                        (ncomp, rows) + hlat[1:])
                    if tiled:
                        window = jax.lax.dynamic_slice(
                            window, (0, 0, *tstarts),
                            (ncomp, rows, *tsizes))
                else:
                    window = r[lead + (slice(None), pl.ds(xs, rows)) + tuple(
                        pl.ds(s, z) for s, z in zip(tstarts, tsizes))]
                values[n] = (window, ring)
            finish_tile(values, sc_refs, out_refs, acc_refs)

        # Double-buffered DMA pipeline (compiled launches): inputs stay in
        # HBM (memory_space=ANY) and each program DMAs its halo'd x-slab or
        # tile window into one of two VMEM scratch slots, starting the copy
        # for tile t+1 before waiting on tile t's — grid axes are
        # sequential on TPU, so tile t+1's transfer overlaps tile t's
        # compute.  A batched launch linearizes its batch axis ahead of the
        # tiles (the prefetch chain runs on across batch rows).  Every
        # compiled launch takes this path — whole staging would put the
        # halo'd lattice in VMEM — except native AoSoA inputs
        # (block-rebased windows are staged whole); interpret mode stages
        # whole (the plain interpreter has no async-copy semantics).
        # Everything downstream of the window (finish_tile) is shared with
        # the staged kernel, so the pipeline is a pure data-movement change.
        use_dma = not interpret and not any(native_in)
        n_tiles = nslabs * nty * ntz
        n_lin = max(batch, 1) * n_tiles
        # window dtypes: staged float inputs were cast to the effective
        # compute dtype, so the DMA window slots match it
        dma_dts = tuple(jnp.dtype(dt) for dt in
                        (in_dtypes or (jnp.float32,) * nin))
        if cast_in is not None:
            cdt = jnp.dtype(compute_dt or storage_dt)
            dma_dts = tuple(cdt if jnp.issubdtype(dt, jnp.floating) else dt
                            for dt in dma_dts)
        # Mosaic slices HBM only in whole (sublane, 128-lane) tiles of the
        # two minor axes, so the DMA window rounds those extents up to a
        # tile, the staged input is zero-padded to keep every window in
        # bounds, and the kernel slices the logical window back out of the
        # slot (a static, offset-0 value slice).
        minor = tuple(range(max(site_ndim - 2, 0), site_ndim))
        win_shapes, dma_wins, stage_pads = [], [], []
        for (ncomp, lay), hlat, ring, dt in zip(in_meta, hlats, in_rings,
                                                dma_dts):
            _, tsz = tile_tail(0, 0, ring, hlat)
            win = (ncomp, bx + 2 * ring) + tuple(tsz)
            win_shapes.append(win)
            dwin, pads = list(win), [0] * site_ndim
            for d in minor:
                m = 128 if d == site_ndim - 1 else 8 * max(1, 4 // dt.itemsize)
                dwin[d + 1] = -(-win[d + 1] // m) * m
                step = {1: by, 2: bz}.get(d, 0)
                last = (lattice[d] // step - 1) * step if step else 0
                pads[d] = max(0, last + dwin[d + 1] - hlat[d])
            dma_wins.append(tuple(dwin))
            stage_pads.append(pads)

        def dma_kernel(*refs):
            from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

            in_refs = refs[:nin]
            sc_refs = refs[nin : nin + nsc]
            out_refs = refs[nin + nsc : nin + nsc + nfield]
            nred = len(red_outputs)
            acc_refs = refs[nin + nsc + nfield : nin + nsc + nfield + nred]
            bufs = refs[nin + nsc + nfield + nred :
                        nin + nsc + nfield + nred + nin]
            sems = refs[nin + nsc + nfield + nred + nin :]
            if rsplit > 1:  # x-slab index rebased from the split grid axes
                i = pl.program_id(axis0) * per + pl.program_id(axis0 + 1)
                tax = axis0 + 2
            else:
                i = pl.program_id(axis0)
                tax = axis0 + 1
            jt = 0
            if by:
                jt = pl.program_id(tax)
                tax += 1
            kt = pl.program_id(tax) if bz else 0
            # linear tile index: the grid iterates the z-tile axis fastest
            # (a split grid visits slabs in the unsplit order)
            t = (i * nty + jt) * ntz + kt
            if batch:
                t = pl.program_id(0) * n_tiles + t

            def copy(tl, slot, idx):
                """Async-copy descriptor for input idx's halo'd window of
                linear tile tl into scratch slot ``slot``."""
                bb = tl // n_tiles
                ii = (tl // (nty * ntz)) % nslabs
                jj = (tl // ntz) % nty
                kk = tl % ntz
                ring = in_rings[idx]
                src = [bb] if (batch and in_batched[idx]) else []
                src += [slice(None), pl.ds(ii * bx, bx + 2 * ring)]
                for d in range(1, site_ndim):
                    ext = dma_wins[idx][d + 1]
                    if d == 1 and by:
                        src.append(pl.ds(jj * by, ext))
                    elif d == 2 and bz:
                        src.append(pl.ds(kk * bz, ext))
                    else:
                        src.append(pl.ds(0, ext))
                return pltpu.make_async_copy(
                    in_refs[idx].at[tuple(src)],
                    bufs[idx].at[slot],
                    sems[idx].at[slot],
                )

            slot = jax.lax.rem(t, 2)

            @pl.when(t == 0)
            def _warm_up():
                for ix in range(nin):
                    copy(t, slot, ix).start()

            @pl.when(t + 1 < n_lin)
            def _prefetch():
                for ix in range(nin):
                    copy(t + 1, 1 - slot, ix).start()

            values = {}
            for ix, (n, ring) in enumerate(zip(ordered_ins, in_rings)):
                copy(t, slot, ix).wait()
                win = bufs[ix][slot]
                if win.shape != win_shapes[ix]:  # drop the tile round-up
                    win = win[tuple(slice(0, e) for e in win_shapes[ix])]
                values[n] = (win, ring)
            finish_tile(values, sc_refs, out_refs, acc_refs)

        def stage_in(n, meta, lat, ring, nat, d):
            if not nat:
                return to_halo_nd(n, meta, lat, ring, d)
            if halo == "periodic" and ring > 0:
                ncomp, lay = meta
                return halo_pad_physical(d, lay, ncomp, lat, ring)
            return d  # "pre": the caller's physical array, staged as-is

        def fn(datas, svals):
            telemetry.inc("fuse.traces")
            telemetry.inc("fuse.pallas_calls")
            if cast_in is not None:
                datas = cast_in(datas)
            staged = []
            for n, meta, lat, ring, nat, bat, d in zip(
                    ordered_ins, in_meta, in_lats, in_rings, native_in,
                    in_batched, datas):
                if batch and bat:  # stage each batch element, stacked
                    staged.append(jax.vmap(
                        lambda x, _n=n, _m=meta, _l=lat, _r=ring, _na=nat:
                        stage_in(_n, _m, _l, _r, _na, x))(d))
                else:
                    staged.append(stage_in(n, meta, lat, ring, nat, d))
            kernel = fused_kernel
            call_kw = dict(in_specs=in_specs)
            if not interpret:
                from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

                # a scoped-VMEM limit that agrees with the planner's
                # device budget (core.plan.vmem_limit_bytes)
                call_kw["compiler_params"] = pltpu.CompilerParams(
                    vmem_limit_bytes=plan_mod.vmem_limit_bytes())
            if use_dma:
                kernel = dma_kernel
                # inputs stay in HBM; two window slots + one DMA
                # semaphore pair of scratch per input
                call_kw["in_specs"] = (
                    [pl.BlockSpec(memory_space=pl.ANY)
                     for _ in range(nin)] + list(in_specs[nin:])
                )
                for ix, pads in enumerate(stage_pads):
                    if any(pads):
                        lead = staged[ix].ndim - site_ndim
                        staged[ix] = jnp.pad(staged[ix], [(0, 0)] * lead + [
                            (0, p) for p in pads])
                call_kw["scratch_shapes"] = [
                    pltpu.VMEM((2,) + w, dt)
                    for w, dt in zip(dma_wins, dma_dts)] + [
                    pltpu.SemaphoreType.DMA((2,)) for _ in range(nin)]
            call = pl.pallas_call(
                kernel,
                grid=grid,
                out_specs=(
                    out_block_specs if len(out_block_specs) > 1 else out_block_specs[0]
                ),
                out_shape=out_shapes if len(out_shapes) > 1 else out_shapes[0],
                interpret=interpret,
                name=name,
                **call_kw,
            )
            res = call(*staged, *svals)
            if len(out_shapes) == 1:
                res = (res,)
            out = []
            for idx, r in enumerate(res):
                if idx >= nfield:  # reduction accumulator (..., ncomp, 1);
                    # split plans fold the (..., rsplit, ncomp) stage-1
                    # rows through the stage-2 combine in segment order
                    acc = r[..., 0]
                    if rsplit > 1:
                        acc = red_spec[red_outputs[idx - nfield]] \
                            .combine_partials(acc, axis=-2)
                    out.append(acc)
                elif native_out[idx]:  # already the physical AoSoA array
                    out.append(r)
                else:  # canonical nd -> requested physical layout
                    o = field_outputs[idx]
                    ncomp, _ = out_info[o]
                    pack = (lambda a, _o=o, _nc=ncomp:
                            out_layouts[_o].pack(a.reshape(_nc, -1)))
                    out.append(jax.vmap(pack)(r) if batch else pack(r))
            return tuple(out)

        return jax.jit(fn)


@dataclasses.dataclass(frozen=True)
class BoundLaunch:
    """A :meth:`LaunchGraph.launch` with its keyword sprawl frozen
    (:meth:`LaunchGraph.bind`): a reusable callable the drivers invoke
    with just the input Fields.  Per-call keywords override the bound
    ones (``out_layouts`` merges, call entries winning), so one bound
    launch serves call sites that differ only in, say, the output
    layout."""

    graph: LaunchGraph
    config: Optional[TargetConfig] = None
    outputs: Optional[Tuple[str, ...]] = None
    out_layouts: Optional[Mapping[str, Layout]] = None
    halo: str = "periodic"
    plan: Optional[LoweringPlan] = None

    def __call__(
        self,
        ins: Dict[str, Field],
        *,
        scalars: Optional[Mapping] = None,
        config: Optional[TargetConfig] = None,
        outputs: Optional[Sequence[str]] = None,
        out_layouts: Optional[Mapping[str, Layout]] = None,
        halo: Optional[str] = None,
        plan: Optional[LoweringPlan] = None,
    ) -> Dict[str, Union[Field, jax.Array]]:
        layouts = dict(self.out_layouts or {})
        if out_layouts:
            layouts.update(out_layouts)
        return self.graph.launch(
            ins,
            config=config if config is not None else self.config,
            outputs=outputs if outputs is not None else self.outputs,
            scalars=scalars,
            out_layouts=layouts or None,
            halo=halo if halo is not None else self.halo,
            plan=plan if plan is not None else self.plan,
        )


def _split_specs(specs, per: int) -> List[pl.BlockSpec]:
    """Grow a leading split-reduction grid axis (``LoweringPlan.rsplit``)
    on single-lattice BlockSpecs: the site-block/x-slab index is rebased
    to ``s * per + i``, so split segment ``s`` covers blocks
    [s*per, (s+1)*per) — the same block order as the unsplit grid, just
    regrouped into rsplit stage-1 partials.  Trailing grid coordinates
    (the y/z tile axes of a tiled stencil plan) pass through unchanged,
    so the split axis composes with tiling."""
    out = []
    for spec in specs:
        shape, m = tuple(spec.block_shape), spec.index_map
        out.append(pl.BlockSpec(
            shape,
            lambda s, i, *rest, _m=m, _p=per: tuple(_m(s * _p + i, *rest))))
    return out


def _batch_specs(specs, batched) -> List[pl.BlockSpec]:
    """Grow a leading batch grid axis on single-lattice BlockSpecs: a
    batched operand gets a length-1 batch-row block selected by the batch
    program id; a shared operand keeps its rank and ignores it.  The
    wrapped index map passes the remaining grid coordinates through, so
    it composes with the split-reduction axis of ``_split_specs``."""
    out = []
    for spec, bat in zip(specs, batched):
        shape, m = tuple(spec.block_shape), spec.index_map
        if bat:
            out.append(pl.BlockSpec(
                (1,) + shape, lambda b, *idx, _m=m: (b,) + tuple(_m(*idx))))
        else:
            out.append(pl.BlockSpec(
                shape, lambda b, *idx, _m=m: tuple(_m(*idx))))
    return out


def _batch_shapes(shapes, batch: int) -> List[jax.ShapeDtypeStruct]:
    return [jax.ShapeDtypeStruct((batch,) + tuple(s.shape), s.dtype)
            for s in shapes]


def _accumulate(ref, combine, init, partial, axes: Sequence[int] = (0,)):
    """Grid-sequential accumulation into a constant-index-map buffer (the
    fused analogue of core.reduce's partial-sum kernel).  ``axes`` are the
    grid axes that together address one accumulator row — the site-block
    (or x-slab) axis plus any trailing y/z tile axes of a tiled stencil
    plan; batch and split-segment axes are excluded because their rows are
    separate buffer blocks selected by the BlockSpec.  The row initializes
    at the program where *every* listed axis is 0 (its first visit)."""
    cond = pl.program_id(axes[0]) == 0
    for a in axes[1:]:
        cond = jnp.logical_and(cond, pl.program_id(a) == 0)

    @pl.when(cond)
    def _init():
        ref[...] = init(ref.shape, ref.dtype)

    ref[...] = combine(ref[...], partial)


def fused_launch(
    stages: Sequence[Tuple],
    ins: Dict[str, Field],
    *,
    config: Optional[TargetConfig] = None,
    outputs: Optional[Sequence[str]] = None,
    scalars: Optional[Mapping] = None,
    out_layouts: Optional[Mapping[str, Layout]] = None,
    name: str = "fused",
) -> Dict[str, Union[Field, jax.Array]]:
    """One-shot form: each stage is (kernel, ins, out_specs[, params[, rename]]).

    Equivalent to building a LaunchGraph of site-local stages and launching
    it; the launch cache keys on the stage bodies, so rebuilt graphs still
    hit."""
    g = LaunchGraph(name)
    for st in stages:
        kern, st_ins, st_outs = st[0], st[1], st[2]
        params = st[3] if len(st) > 3 else None
        rename = st[4] if len(st) > 4 else None
        g.add(kern, st_ins, st_outs, params=params, rename=rename)
    return g.launch(
        ins, config=config, outputs=outputs, scalars=scalars, out_layouts=out_layouts
    )
