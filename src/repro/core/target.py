"""Engine dispatch: one kernel body, two targets (paper §3.2).

targetDP compiles the same source to OpenMP (host C) or CUDA.  Here a kernel
body is a Python function over canonical ``(ncomp, VVL)`` site-chunks and is
*traced* by two engines:

  engine="jnp"     TLP and ILP collapse into whole-lattice array ops — the
                   paper's C/OpenMP build.  Also serves as the oracle.
  engine="pallas"  ``pl.pallas_call`` over a 1-D grid of site blocks; VMEM
                   tiling comes from each Field's Layout via BlockSpec, so
                   the body never sees the layout — the paper's CUDA build,
                   re-tiled for the TPU memory hierarchy (HBM -> VMEM ->
                   (8,128) VREG tiles).

__targetTLP__  -> the pallas grid (site blocks across TensorCores)
__targetILP__  -> the trailing VVL axis of each chunk (VPU lanes)
VVL            -> sites per pallas program; multiples of 128 are the TPU
                  analogue of VVL=4 (AVX) / VVL=8 (IMCI-512).

Site-local kernels only (collision, stress, LC update, MILC linear algebra).
Stencil kernels (propagation, dslash) have bespoke pallas implementations in
``repro.kernels`` and jnp implementations via ``core.stencil``; both engines
remain available for them through their ops.py wrappers.

Chains of site-local launches whose outputs feed later inputs can be fused
into a *single* device kernel (intermediates never round-trip through HBM)
with ``core.fuse.LaunchGraph`` / ``core.fuse.fused_launch``, which shares the
BlockSpec machinery below (``build_in_specs`` / ``build_out_specs``) and adds
a ``jax.jit``-backed launch cache.  A single ``launch`` remains un-cached by
design: its params may be traced values (e.g. CG's alpha under
``lax.while_loop``), which must not enter a cache key.

Every lowering decision (vvl, stencil slab, interpret fallback, halo
strategy, canonical-view choice) is planned in ``core.plan`` — this module
only *executes* a :class:`~repro.core.plan.LoweringPlan`.  ``choose_vvl`` /
``choose_slab`` / ``resolve_vvl`` are re-exported from there for backwards
compatibility; ``TargetConfig.plan_policy`` selects how plans are made
("default" heuristics, the persisted "tuned" table of ``core.tune``, or an
explicit plan).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import telemetry
from .field import Field
from .layout import Layout
from .plan import (  # noqa: F401  (re-exported: the planning layer owns them)
    DtypePolicy,
    LoweringPlan,
    choose_slab,
    choose_vvl,
    plan_for_launch,
    resolve_vvl,
)

__all__ = [
    "TargetConfig",
    "DtypePolicy",
    "kernel",
    "launch",
    "choose_vvl",
    "resolve_vvl",
    "choose_slab",
    "LoweringPlan",
    "TargetKernel",
]


def _on_tpu() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover
        return False


@dataclasses.dataclass(frozen=True)
class TargetConfig:
    """Compile-time configuration (the paper's build options).

    engine       "jnp" (host C / OpenMP analogue) or "pallas" (device analogue)
    vvl          Virtual Vector Length: lattice sites per pallas program.
    interpret    run pallas in interpret mode (True automatically off-TPU).
    plan_policy  how lowering decisions are made (core.plan):
                 "default" — the heuristic plan (largest conforming vvl/slab);
                 "tuned"   — look up the persisted autotune table (core.tune)
                             by the launch's plan key, falling back to the
                             default heuristics on a miss;
                 a LoweringPlan — use exactly this plan (validated per launch).
    vmem_bytes   per-program VMEM byte budget for stencil lowering.  None
                 defers to $TARGETDP_VMEM_BYTES, and an unset/0 budget means
                 unbounded — the pre-budget behavior, default plans stay
                 bit-identical.  With a budget, a stencil launch whose
                 whole-staging footprint exceeds it auto-tiles the y/z axes
                 (LoweringPlan.by/.bz) so per-program VMEM is bounded by the
                 tile, and the tuner skips (and logs) over-budget candidates.
    telemetry    per-launch override of the core.telemetry span recording:
                 None defers to the process switch ($TARGETDP_TELEMETRY /
                 telemetry.enable()); True/False force it for launches made
                 with this config.  Spans are host-side only — flipping this
                 never changes a single bit of any launch output.
    dtypes       mixed-precision DtypePolicy (storage/compute/accumulate —
                 core.plan.DtypePolicy) applied to every launch made with
                 this config whose resolved plan does not already carry its
                 own policy (a tuned/explicit plan's policy wins).  None —
                 the default — changes nothing: lowering stays bit-identical
                 to the pre-policy code.
    """

    engine: str = "jnp"
    vvl: int = 128
    interpret: Optional[bool] = None
    plan_policy: Union[str, LoweringPlan] = "default"
    vmem_bytes: Optional[int] = None
    telemetry: Optional[bool] = None
    dtypes: Optional[DtypePolicy] = None

    def resolved_interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return not _on_tpu()

    def resolved_vmem_bytes(self) -> Optional[int]:
        from .plan import resolved_vmem_bytes
        return resolved_vmem_bytes(self)


def build_halo_in_specs(
    shapes: Sequence[Tuple[int, ...]],
) -> List[pl.BlockSpec]:
    """BlockSpecs for halo'd stencil-graph inputs: overlapping x-slab windows
    are not expressible as disjoint Blocked windows, so each halo'd array is
    staged whole into VMEM (constant index map) and the kernel slices the
    per-program halo'd window out with ``lax.dynamic_slice`` — displacement
    becomes slice arithmetic on VMEM-resident data (see
    kernels/lb_propagation for the single-kernel precedent).  Shapes are
    whatever the staging produced: canonical ``(ncomp, *halo'd_lattice)``
    under ``view="staged-nd"``, or the physical 3-D AoSoA
    ``(nblocks, ncomp, SAL)`` tile stack under the native ``view="block"``
    lowering (the kernel then slices on the *block* axis)."""
    specs = []
    for shp in shapes:
        zeros = (0,) * len(shp)
        # variadic: the site grid may carry trailing y/z tile axes
        # (LoweringPlan.by/.bz) — whole-staged inputs are tile-invariant
        specs.append(pl.BlockSpec(shp, lambda *_i, _z=zeros: _z))
    return specs


def build_slab_out_specs(
    out_names: Sequence[str],
    out_specs: Mapping[str, Tuple[int, object]],
    lattice: Tuple[int, ...],
    bx: int,
) -> Tuple[List[jax.ShapeDtypeStruct], List[pl.BlockSpec]]:
    """(out_shape, BlockSpec) per interior nd output of a stencil graph:
    canonical (ncomp, X, *inner) arrays blocked into disjoint x-slabs."""
    inner = tuple(lattice[1:])
    shapes, specs = [], []
    for k in out_names:
        ncomp, dtype = out_specs[k]
        shapes.append(
            jax.ShapeDtypeStruct((ncomp,) + tuple(lattice), dtype)
        )
        block = (ncomp, bx) + inner
        idx = lambda i: (0, i) + (0,) * len(inner)
        specs.append(pl.BlockSpec(block, idx))
    return shapes, specs


def build_block_out_specs(
    out_names: Sequence[str],
    out_specs: Mapping[str, Tuple[int, object]],
    out_layouts: Mapping[str, Layout],
    lattice: Tuple[int, ...],
    bx: int,
) -> Tuple[List[jax.ShapeDtypeStruct], List[pl.BlockSpec], List[bool]]:
    """(out_shape, BlockSpec, native?) per output of a ``view="block"``
    stencil graph.

    An AoSoA output whose SAL divides the interior inner-plane site count
    is written *natively*: the out_shape is the physical
    ``(nsites/SAL, ncomp, SAL)`` array and each program owns a disjoint
    run of ``bx * inner / SAL`` whole blocks on the leading axis — the
    kernel packs its interior slab in VMEM and no XLA relayout runs after
    the launch.  Anything else falls back to the canonical x-slab spec of
    :func:`build_slab_out_specs` (packing for SoA is a view and for AoS a
    transpose), flagged ``native=False`` so the caller packs as usual."""
    from .layout import LayoutKind

    inner = int(math.prod(lattice[1:]))
    nsites = int(math.prod(lattice))
    shapes, specs, native = [], [], []
    for k in out_names:
        ncomp, dtype = out_specs[k]
        lay = out_layouts[k]
        if lay.kind is LayoutKind.AOSOA and inner % lay.sal == 0:
            sal = lay.sal
            shapes.append(
                jax.ShapeDtypeStruct((nsites // sal, ncomp, sal), dtype))
            specs.append(
                pl.BlockSpec((bx * inner // sal, ncomp, sal),
                             lambda i: (i, 0, 0)))
            native.append(True)
        else:
            s, p = build_slab_out_specs([k], out_specs, lattice, bx)
            shapes += s
            specs += p
            native.append(False)
    return shapes, specs, native


def build_reduce_specs(
    out_names: Sequence[str],
    out_specs: Mapping[str, Tuple[int, object]],
    widths: Optional[Mapping[str, int]] = None,
) -> Tuple[List[jax.ShapeDtypeStruct], List[pl.BlockSpec]]:
    """(out_shape, BlockSpec) per terminal-reduction accumulator: a single
    (ncomp, width) partial buffer with a constant index map, revisited by
    every program (TPU pallas grids execute sequentially per core, so
    cross-block read-modify-write accumulation is well defined — same idiom
    as core.reduce).  ``widths`` widens a buffer's trailing axis (default
    1, the pre-policy shape); compensated (Kahan) accumulation under a
    DtypePolicy uses width 2 — column 0 the running sum, column 1 the
    running compensation."""
    shapes, specs = [], []
    for k in out_names:
        ncomp, dtype = out_specs[k]
        w = (widths or {}).get(k, 1)
        shapes.append(jax.ShapeDtypeStruct((ncomp, w), dtype))
        # variadic: revisited by every program of the (possibly tiled) grid
        specs.append(pl.BlockSpec((ncomp, w), lambda *_i: (0, 0)))
    return shapes, specs


def build_split_reduce_specs(
    out_names: Sequence[str],
    out_specs: Mapping[str, Tuple[int, object]],
    rsplit: int,
    widths: Optional[Mapping[str, int]] = None,
) -> Tuple[List[jax.ShapeDtypeStruct], List[pl.BlockSpec]]:
    """(out_shape, BlockSpec) per terminal-reduction accumulator under a
    split-reduction plan (``LoweringPlan.rsplit > 1``): a ``(rsplit,
    ncomp, width)`` stage-1 partial buffer whose rows are selected by the
    split grid axis — each of the ``rsplit`` grid segments accumulates
    its own row, and the tiny stage-2 combine folds the rows in segment
    order after the call (core.fuse).  ``widths`` as in
    :func:`build_reduce_specs` (compensated accumulation widens to 2)."""
    shapes, specs = [], []
    for k in out_names:
        ncomp, dtype = out_specs[k]
        w = (widths or {}).get(k, 1)
        shapes.append(jax.ShapeDtypeStruct((rsplit, ncomp, w), dtype))
        # variadic beyond the split axis: the per-segment site axis may
        # carry trailing tile axes; the buffer row follows the segment only
        specs.append(pl.BlockSpec((1, ncomp, w), lambda s, *_i: (s, 0, 0)))
    return shapes, specs


def build_tiled_out_specs(
    out_names: Sequence[str],
    out_specs: Mapping[str, Tuple[int, object]],
    lattice: Tuple[int, ...],
    bx: int,
    by: int,
    bz: int,
) -> Tuple[List[jax.ShapeDtypeStruct], List[pl.BlockSpec]]:
    """(out_shape, BlockSpec) per interior nd output of a *tiled* stencil
    graph (``LoweringPlan.by``/``.bz``): canonical ``(ncomp, X, Y, Z, ...)``
    arrays blocked into disjoint ``(bx, by, bz)`` tiles.  Unlike the
    overlapping input windows, output tiles are exactly expressible as
    disjoint Blocked windows — the index map consumes one grid coordinate
    per *active* tile axis (x always; y iff ``by``; z iff ``bz``), matching
    the trailing tile axes core.fuse appends to the site grid."""
    nd = len(lattice)
    tail = []
    for d in range(1, nd):
        if d == 1 and by:
            tail.append(by)
        elif d == 2 and bz:
            tail.append(bz)
        else:
            tail.append(lattice[d])
    tail = tuple(tail)

    def idx(i, *tiles):
        out = [0, i]
        t = iter(tiles)
        for d in range(1, nd):
            if (d == 1 and by) or (d == 2 and bz):
                out.append(next(t))
            else:
                out.append(0)
        return tuple(out)

    shapes, specs = [], []
    for k in out_names:
        ncomp, dtype = out_specs[k]
        shapes.append(jax.ShapeDtypeStruct((ncomp,) + tuple(lattice), dtype))
        specs.append(pl.BlockSpec((ncomp, bx) + tail, idx))
    return shapes, specs


def build_in_specs(
    in_meta: Sequence[Tuple[int, Layout]], vvl: int
) -> List[pl.BlockSpec]:
    """One BlockSpec per (ncomp, Layout) input, derived from its Layout
    (shared by the single-kernel path and the fused launch-graph path)."""
    return [
        pl.BlockSpec(lay.block_shape(ncomp, vvl), lay.block_index_map())
        for ncomp, lay in in_meta
    ]


def build_out_specs(
    out_names: Sequence[str],
    out_specs: Mapping[str, Tuple[int, object]],
    out_layouts: Mapping[str, Layout],
    nsites: int,
    vvl: int,
) -> Tuple[List[jax.ShapeDtypeStruct], List[pl.BlockSpec]]:
    """(out_shape, out BlockSpec) per output, derived from its Layout."""
    shapes, specs = [], []
    for k in out_names:
        ncomp, dtype = out_specs[k]
        lay = out_layouts[k]
        shapes.append(jax.ShapeDtypeStruct(lay.physical_shape(ncomp, nsites), dtype))
        specs.append(pl.BlockSpec(lay.block_shape(ncomp, vvl), lay.block_index_map()))
    return shapes, specs


class TargetKernel:
    """A site-local data-parallel kernel (the paper's __targetEntry__ unit)."""

    def __init__(self, body: Callable, name: Optional[str] = None):
        self.body = body
        self.name = name or getattr(body, "__name__", "kernel")

    def __repr__(self):  # pragma: no cover - cosmetic
        return f"TargetKernel({self.name})"

    # -- engines ---------------------------------------------------------------

    def _run_jnp(self, ins: Dict[str, Field], params: Mapping) -> Dict[str, jax.Array]:
        chunks = {k: f.canonical() for k, f in ins.items()}
        return self.body(chunks, **dict(params))

    def _run_pallas(
        self,
        ins: Dict[str, Field],
        out_specs: Mapping[str, Tuple[int, object]],
        params: Mapping,
        plan: LoweringPlan,
        out_layouts: Mapping[str, Layout],
    ) -> Dict[str, jax.Array]:
        names = list(ins)
        nsites = ins[names[0]].nsites
        for f in ins.values():
            if f.nsites != nsites:
                raise ValueError("all fields in one launch must share nsites")
        vvl, interpret = plan.vvl, plan.interpret
        if nsites % vvl:
            raise ValueError(
                f"vvl={vvl} must divide nsites={nsites} "
                f"(use a conforming plan or pad the lattice)"
            )
        grid = (nsites // vvl,)

        in_block_specs = build_in_specs(
            [(f.ncomp, f.layout) for f in ins.values()], vvl
        )
        out_names = list(out_specs)
        out_shapes, out_block_specs = build_out_specs(
            out_names, out_specs, out_layouts, nsites, vvl
        )

        body = self.body
        static_params = dict(params)
        in_fields = list(ins.values())

        def pallas_kernel(*refs):
            in_refs = refs[: len(in_fields)]
            out_refs = refs[len(in_fields):]
            chunks = {}
            for k, f, r in zip(names, in_fields, in_refs):
                chunks[k] = f.layout.block_to_canonical(r[...], f.ncomp, vvl)
            outs = body(chunks, **static_params)
            for k, r in zip(out_names, out_refs):
                ncomp, _ = out_specs[k]
                r[...] = out_layouts[k].canonical_to_block(outs[k], ncomp, vvl)

        call = pl.pallas_call(
            pallas_kernel,
            grid=grid,
            in_specs=in_block_specs,
            out_specs=(
                out_block_specs if len(out_block_specs) > 1 else out_block_specs[0]
            ),
            out_shape=out_shapes if len(out_shapes) > 1 else out_shapes[0],
            interpret=interpret,
            name=self.name,
        )
        result = call(*[f.data for f in in_fields])
        if len(out_names) == 1:
            result = [result]
        # physical -> canonical
        out = {}
        for k, phys in zip(out_names, result):
            out[k] = out_layouts[k].unpack(phys)
        return out


def kernel(fn: Optional[Callable] = None, *, name: Optional[str] = None):
    """Decorator: register a site-local kernel body.

    Body signature::

        def body(v: dict[str, Array(ncomp, VVL)], **params) -> dict[str, Array]
    """

    def wrap(f):
        return TargetKernel(f, name=name)

    return wrap(fn) if fn is not None else wrap


def _normalize_out_specs(out_specs, ref_dtype):
    norm = {}
    for k, v in out_specs.items():
        if isinstance(v, tuple):
            norm[k] = (int(v[0]), v[1])
        else:
            norm[k] = (int(v), ref_dtype)
    return norm


def launch(
    kern: Union[TargetKernel, Callable],
    ins: Dict[str, Field],
    out_specs: Mapping[str, Union[int, Tuple[int, object]]],
    *,
    config: Optional[TargetConfig] = None,
    params: Optional[Mapping] = None,
    out_layouts: Optional[Mapping[str, Layout]] = None,
) -> Dict[str, Field]:
    """Execute a kernel over the lattice (the paper's __targetLaunch__).

    ins         name -> input Field (all sharing nsites; layouts may differ).
    out_specs   name -> ncomp (or (ncomp, dtype)) of each output Field.
    Returns     name -> output Field (same lattice; layout = out_layouts[name]
                or the first input's layout).
    """
    if not isinstance(kern, TargetKernel):
        kern = TargetKernel(kern)
    config = config or TargetConfig()
    params = params or {}
    # the site-block grid reads flat physical data (nd Fields relayout)
    ins = {k: f.as_flat() for k, f in ins.items()}
    first = next(iter(ins.values()))
    out_specs = _normalize_out_specs(out_specs, first.dtype)
    out_layouts = dict(out_layouts or {})
    for k in out_specs:
        out_layouts.setdefault(k, first.layout)

    # every lowering decision (auto-vvl, interpret fallback, policy) is made
    # by the planning layer; this function only executes the plan
    plan = plan_for_launch(
        config,
        first.nsites,
        [f.layout for f in ins.values()] + [out_layouts[k] for k in out_specs],
    )
    # the launch layer's device ops carry the kernel's scope
    # (core.telemetry), as every LaunchGraph launch's do
    with telemetry.scope(f"launch/{kern.name}"):
        if plan.engine == "jnp":
            outs = kern._run_jnp(ins, params)
        else:  # "pallas" (plan_for_launch validated the engine)
            outs = kern._run_pallas(
                ins, out_specs, params, plan=plan, out_layouts=out_layouts
            )

        fields = {}
        for k, (ncomp, dtype) in out_specs.items():
            arr = outs[k].astype(dtype)
            fields[k] = Field(
                k, ncomp, first.lattice, out_layouts[k],
                out_layouts[k].pack(arr)
            )
        return fields
