"""LoweringPlan: every launch decision in one hashable place (paper §3.2.2).

The paper tunes the Virtual Vector Length *per architecture by hand* and
reports that the optimum differs across CPU, Xeon Phi and GPU; the targetDP
report (Gray & Stratford 2014) frames VVL and friends as per-target
compile-time constants behind a single abstraction.  Before this module the
JAX port scattered those decisions across three call sites — the
single-kernel pallas path, the site-local fused path and the halo'd-stencil
fused path — each re-deriving vvl/slab/interpret inline.  Now every launch
routes through a :class:`LoweringPlan`:

  engine      "jnp" (host C / OpenMP analogue) or "pallas" (device analogue)
  vvl         sites per pallas program (site-local lowering; 0 otherwise)
  bx          x-slab planes per program (halo'd stencil lowering; 0 otherwise)
  interpret   pallas interpret-mode fallback (True automatically off-TPU)
  halo        stencil halo strategy: "periodic" pad, caller-"pre"-exchanged,
              or "overlap" (interior/boundary split launches overlapping the
              halo exchange with interior compute — core.overlap)
  view        canonical-view strategy: "block" (layout pack/unpack inside the
              kernel, per VMEM block) or "staged-nd" (canonical SoA-nd views
              packed/unpacked as XLA ops around the single halo'd kernel).
              Site-local lowerings always use "block" (BlockSpec tiling per
              Layout).  Stencil lowerings default to "staged-nd"; "block" is
              the *native AoSoA* stencil lowering: halo'd AoSoA inputs are
              staged whole as physical ``(nblocks, ncomp, SAL)`` tiles, each
              program slices its halo'd x-slab window on the *block* axis
              and un-/re-packs in VMEM, so the paper's layout sweep reaches
              halo'd chains without an XLA pack/unpack round-trip
              (``block_view_ok`` states the alignment precondition).  The
              dataclass default is the "auto" sentinel: resolved per shape
              by ``adapt_plan`` (block for site-local, staged-nd for
              stencil), so hand-built plans without view= behave as before
              the knob existed

``choose_vvl`` / ``choose_slab`` live here as plan *candidate generators*:
they enumerate the divisors of the lattice extent (memoized — the previous
linear scan was O(nsites) per uncached launch for prime-ish lattices) and
``default_plan`` picks the largest conforming one, reproducing the
pre-plan heuristics bit-for-bit.  ``candidate_plans`` enumerates the whole
conforming set for the autotuner (core.tune), which persists per-(chain,
layout, backend) winners so applications get architecture-specific tuning
without touching kernel or driver code — the paper's central claim, made a
layer instead of a hand edit.

Policy (``TargetConfig.plan_policy``):

  "default"        the heuristic plan (bit-identical to the pre-plan code)
  "tuned"          look up the persisted autotuner table (core.tune) by the
                   launch's plan key; fall back to "default" on a miss
  LoweringPlan     use exactly this plan (validated against the launch)
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import logging
import math
import os
from typing import Optional, Sequence, Tuple

from .layout import Layout, LayoutKind

__all__ = [
    "DtypePolicy",
    "LoweringPlan",
    "dtype_itemsize",
    "resolve_accumulate",
    "divisors",
    "choose_vvl",
    "choose_slab",
    "choose_tiles",
    "resolve_vvl",
    "sal_alignment",
    "block_view_ok",
    "default_plan",
    "plan_for_launch",
    "sub_lattice_plan",
    "candidate_plans",
    "graph_plan_key",
    "tile_extents",
    "estimate_vmem_bytes",
    "resolved_vmem_bytes",
    "tpu_vmem_capacity",
    "vmem_limit_bytes",
]

log = logging.getLogger(__name__)

# environment override for the per-program VMEM byte budget (see
# resolved_vmem_bytes); an unset/empty value means "unbounded", which keeps
# every default plan bit-identical to the pre-budget heuristics
VMEM_ENV = "TARGETDP_VMEM_BYTES"

# Per-core VMEM capacity by jax ``device_kind`` — the one table the compiled
# (non-interpret) pallas path sizes itself from.  Source: Google Cloud TPU
# documentation ("TPU v5e": 128 MiB of VMEM per TensorCore).  A compiled
# stencil launch on a kind missing here is an error, not a guess.
TPU_VMEM_BYTES = {"TPU v5 lite": 128 * 2**20}
# Of that capacity, the planner budgets this share for the staged inputs
# and output blocks it models (estimate_vmem_bytes, TPU-padded), and the
# kernels' scoped-VMEM limit (vmem_limit_bytes) grants this share — the
# rest of the limit holds the values Mosaic keeps live inside the body.
_VMEM_PLAN_SHARE = 2    # budget = capacity / 2
_VMEM_LIMIT_SHARE = 8   # limit  = capacity * 7 / 8

VIEW_BLOCK = "block"
VIEW_STAGED_ND = "staged-nd"
# dataclass default: resolved per lowering shape by adapt_plan (site-local
# -> block, stencil -> staged-nd), so hand-built plans that never set view=
# keep the exact pre-view-knob behavior; requesting the native-AoSoA stencil
# lowering is always an explicit view=VIEW_BLOCK
VIEW_AUTO = "auto"


# -- dtype policy (mixed-precision lowering axis) ------------------------------

# itemsizes for the dtype names a policy may carry, kept as a plain table so
# plan construction / budget estimation never import jax or numpy
_DTYPE_ITEMSIZE = {
    "float64": 8, "float32": 4, "float16": 2, "bfloat16": 2,
    "int64": 8, "int32": 4, "int16": 2, "int8": 1,
}
# compact describe() abbreviations — persisted timing labels use these
_DTYPE_SHORT = {
    "float64": "f64", "float32": "f32", "float16": "f16", "bfloat16": "bf16",
    "compensated": "kf32",
}
# the accumulate slot additionally admits the explicit compensated request
ACCUM_COMPENSATED = "compensated"


def dtype_itemsize(name: str, fallback: int = 4) -> int:
    """Itemsize in bytes of a policy dtype name ('' -> ``fallback``)."""
    return _DTYPE_ITEMSIZE.get(name, fallback) if name else fallback


@dataclasses.dataclass(frozen=True)
class DtypePolicy:
    """The precision triple of a launch — storage, compute, accumulate —
    as a lowering decision (ROADMAP: mixed-precision solvers as a tuned
    plan axis).  Every slot is a dtype *name* ('' = inherit):

      storage      dtype field data is staged in and field outputs are
                   written in ('' = the caller's input dtype).  This is
                   what cuts HBM bytes: bf16 storage nearly halves the
                   traffic of every memory-bound kernel.
      compute      dtype kernel arithmetic runs in ('' = the input/storage
                   dtype).  Inputs are upcast on stage-in, so bf16-stored
                   fields can still multiply in fp32.
      accumulate   dtype terminal sum reductions (fused ReduceSpec sums,
                   rsplit stage-1 partials, standalone target_sum)
                   accumulate in.  '' = the pre-policy behavior (the
                   output dtype).  'float64' requests fp64 accumulation
                   and *degrades to compensated (Kahan) fp32* when the
                   runtime has no fp64 (jax x64 disabled) — see
                   :func:`resolve_accumulate`.  'compensated' requests
                   Kahan fp32 explicitly.  Max and integer reductions
                   ignore this slot and stay bitwise exact.

    The empty policy (all '') — and a plan with ``dtypes=None`` — lowers
    bit-identically to the pre-policy code on every path."""

    storage: str = ""
    compute: str = ""
    accumulate: str = ""

    def __bool__(self) -> bool:
        return bool(self.storage or self.compute or self.accumulate)

    def tag(self) -> str:
        """Compact label component, e.g. ``bf16:f32:f64``."""
        return ":".join(_DTYPE_SHORT.get(s, s) if s else "-"
                        for s in (self.storage, self.compute, self.accumulate))

    def storage_itemsize(self, fallback: int) -> int:
        return dtype_itemsize(self.storage, fallback)

    def validate(self) -> "DtypePolicy":
        for slot, name in (("storage", self.storage),
                           ("compute", self.compute)):
            if name and name not in _DTYPE_ITEMSIZE:
                raise ValueError(
                    f"DtypePolicy.{slot}={name!r} is not a known dtype "
                    f"name; use one of {sorted(_DTYPE_ITEMSIZE)}")
        acc = self.accumulate
        if acc and acc != ACCUM_COMPENSATED and (
                acc not in _DTYPE_ITEMSIZE or not acc.startswith("float")):
            raise ValueError(
                f"DtypePolicy.accumulate={acc!r} must be '', a float dtype "
                f"name, or {ACCUM_COMPENSATED!r}")
        return self


def resolve_accumulate(name: str):
    """Resolve an accumulate request to ``(dtype_name, compensated)``.

    'compensated' -> ('float32', True).  'float64' stays fp64 only when the
    runtime actually has it (``jax.config.jax_enable_x64``); otherwise jnp
    would *silently truncate* the accumulator to fp32, so the request
    degrades to compensated (Kahan) fp32 — strictly more accurate than the
    silent truncation and the documented contract on fp64-less targets.
    '' and any other float name pass through uncompensated."""
    if not name:
        return "", False
    if name == ACCUM_COMPENSATED:
        return "float32", True
    if name == "float64":
        import jax

        if not jax.config.jax_enable_x64:
            return "float32", True
    return name, False


# -- divisor enumeration (memoized candidate generators) -----------------------

@functools.lru_cache(maxsize=4096)
def divisors(n: int) -> Tuple[int, ...]:
    """All divisors of n, ascending.  O(sqrt n) once, then memoized — called
    on every uncached launch, so the old per-launch linear scan mattered for
    prime-ish lattice extents."""
    if n < 1:
        raise ValueError(f"divisors of n >= 1 only, got {n}")
    small, large = [], []
    d = 1
    while d * d <= n:
        if n % d == 0:
            small.append(d)
            if d != n // d:
                large.append(n // d)
        d += 1
    return tuple(small + large[::-1])


@functools.lru_cache(maxsize=4096)
def choose_vvl(nsites: int, preferred: int = 128, multiple_of: int = 1) -> int:
    """Largest divisor of nsites that is <= preferred and a multiple of
    ``multiple_of`` (the lcm of the AoSoA SALs in play, so every VMEM block
    is a whole number of short arrays).  When no such divisor <= preferred
    exists, falls back to ``multiple_of`` itself — correctness (SAL-aligned
    blocks) wins over the preferred block size — and raises only when even
    that cannot divide the lattice."""
    best = 0
    for v in divisors(nsites):
        if v > preferred:
            break
        if v % multiple_of == 0:
            best = v
    if best:
        return best
    if multiple_of <= nsites and nsites % multiple_of == 0:
        return multiple_of
    raise ValueError(
        f"no vvl <= {preferred} divides nsites={nsites} and is a multiple "
        f"of sal alignment {multiple_of}"
    )


@functools.lru_cache(maxsize=4096)
def choose_slab(
    x_dim: int,
    inner_sites: int,
    vvl: int,
    site_bytes: int = 0,
    vmem_bytes: Optional[int] = None,
) -> int:
    """Sites-per-program for a stencil (x-slab) grid: the largest divisor
    ``bx`` of the leading lattice dim whose slab (bx * inner_sites sites)
    stays within the budget.  The stencil analogue of choose_vvl — when
    vvl does not divide the interior block (inner_sites ∤ vvl) the slab
    shrinks to the best conforming divisor instead of raising, and a single
    x-plane (bx=1) is always valid.

    The budget is ``max(vvl, inner_sites)`` sites (the pre-budget heuristic,
    bit-identical when no byte budget is in play), additionally capped by an
    explicit VMEM byte budget when one is configured: ``site_bytes`` is the
    per-site traffic of the launch (sum of input+output ncomp*itemsize) and
    ``vmem_bytes`` the budget (``TargetConfig.vmem_bytes`` /
    ``$TARGETDP_VMEM_BYTES``)."""
    budget = max(int(vvl), inner_sites)
    if vmem_bytes and site_bytes:
        budget = min(budget, max(vmem_bytes // site_bytes, 1))
    best = 1
    for bx in divisors(x_dim):
        if bx * inner_sites <= budget:
            best = bx
    return best


def sal_alignment(layouts: Sequence[Layout]) -> int:
    """lcm of the AoSoA short-array lengths touched by a launch."""
    align = 1
    for lay in layouts:
        if lay.kind is LayoutKind.AOSOA:
            align = align * lay.sal // math.gcd(align, lay.sal)
    return align


def block_view_ok(
    in_views: Sequence[Tuple[Layout, int]],
    out_layouts: Sequence[Layout],
    interior_inner: int,
) -> bool:
    """Whether a stencil launch can lower natively on AoSoA blocks
    (``view="block"``).

    in_views        (layout, halo'd inner-plane site count) per external
                    input — ``prod(halo'd_lattice[1:])``, the site count of
                    one x-plane of the *staged* (halo'd) array.
    out_layouts     layout per field output.
    interior_inner  ``prod(interior_lattice[1:])``.

    True iff at least one *input* is AoSoA (the knob only pays when a halo'd
    input would otherwise round-trip through an XLA unpack) and every AoSoA
    layout in play is block-aligned: an input's SAL must divide its halo'd
    inner-plane count (so every x-slab window is a whole number of short
    arrays and the per-program ``dynamic_slice`` can be rebased to block
    coordinates), and an output's SAL must divide the interior inner-plane
    count (so the disjoint slab BlockSpec rows are whole blocks)."""
    if not any(lay.kind is LayoutKind.AOSOA for lay, _ in in_views):
        return False
    for lay, halo_inner in in_views:
        if lay.kind is LayoutKind.AOSOA and halo_inner % lay.sal:
            return False
    for lay in out_layouts:
        if lay.kind is LayoutKind.AOSOA and interior_inner % lay.sal:
            return False
    return True


def tile_extents(
    lattice: Sequence[int], bx: int, by: int = 0, bz: int = 0
) -> Tuple[int, ...]:
    """Per-dim tile extents of a (possibly) tiled stencil program: ``bx``
    planes on the leading dim, ``by``/``bz`` on the next two when set (0 =
    whole axis), every further dim whole.  The tiles with these extents
    cover the lattice exactly and disjointly (validate() enforces the
    divisibility that makes that true)."""
    ext = [bx or lattice[0]]
    if len(lattice) > 1:
        ext.append(by or lattice[1])
    if len(lattice) > 2:
        ext.append(bz or lattice[2])
    ext.extend(lattice[3:])
    return tuple(ext)


def _device_kind() -> str:
    import jax  # noqa: PLC0415  (plan construction stays jax-free)

    return jax.devices()[0].device_kind


def tpu_vmem_capacity() -> int:
    """Per-core VMEM bytes of the device the compiled kernels target, from
    :data:`TPU_VMEM_BYTES`; raises for a device kind not in the table."""
    kind = _device_kind()
    try:
        return TPU_VMEM_BYTES[kind]
    except KeyError:
        raise ValueError(
            f"no VMEM capacity known for device kind {kind!r}; add it to "
            f"core.plan.TPU_VMEM_BYTES (have {sorted(TPU_VMEM_BYTES)})"
        ) from None


def vmem_limit_bytes() -> int:
    """The scoped-VMEM limit compiled stencil kernels are given: the plan
    budget (capacity / 2) plus room for the values Mosaic keeps live."""
    cap = tpu_vmem_capacity()
    return cap - cap // _VMEM_LIMIT_SHARE


def resolved_vmem_bytes(config) -> Optional[int]:
    """The per-program VMEM byte budget in effect: an explicit
    ``TargetConfig.vmem_bytes`` wins, else ``$TARGETDP_VMEM_BYTES``.  With
    neither set, a compiled (non-interpret) pallas config gets half of the
    device's VMEM (:func:`tpu_vmem_capacity`), so default stencil plans
    tile at sizes whose whole halo'd inputs would not fit; interpret and jnp
    configs stay unbounded (None — the pre-budget behavior)."""
    vb = getattr(config, "vmem_bytes", None)
    if vb is not None:
        return int(vb) or None
    env = os.environ.get(VMEM_ENV, "")
    if env:
        try:
            return int(env) or None
        except ValueError:
            log.warning("ignoring non-integer $%s=%r", VMEM_ENV, env)
    if (getattr(config, "engine", "jnp") == "pallas"
            and not config.resolved_interpret()):
        return tpu_vmem_capacity() // _VMEM_PLAN_SHARE
    return None


def _buffer_bytes(ncomp: int, extents: Sequence[int], isz: int,
                  tpu: bool) -> int:
    """Bytes of one ``(ncomp, *extents)`` VMEM buffer.  ``tpu`` pads the two
    minor axes to the (sublane, 128-lane) tile Mosaic lays them out in —
    a 130-wide halo'd z axis occupies 256 lanes."""
    ext = [int(e) for e in extents]
    if tpu and ext:
        ext[-1] = -(-ext[-1] // 128) * 128
        if len(ext) > 1:
            sub = 8 * 4 // isz if isz < 4 else 8
            ext[-2] = -(-ext[-2] // sub) * sub
    return ncomp * int(math.prod(ext)) * isz


def estimate_vmem_bytes(
    plan: "LoweringPlan",
    *,
    lattice: Sequence[int],
    in_views: Sequence[Tuple[int, int, int]],
    out_views: Sequence[Tuple[int, int]] = (),
    tpu: bool = False,
) -> int:
    """Model the per-program VMEM footprint of a stencil launch in bytes.

    in_views    (ncomp, halo ring, dtype itemsize) per external input
    out_views   (ncomp, dtype itemsize) per field output

    Untiled plans stage every input *whole* (the halo'd array is resident
    for the launch) plus one output slab per program.  Tiled plans hold two
    halo'd tile windows per input (the double-buffered DMA slots pipelining
    tile t+1 against tile t) plus one output tile — which is what bounds a
    shard by the tile, not the lattice.

    A plan carrying a storage :class:`DtypePolicy` is priced at the
    *storage* itemsize (fields are staged and written in the storage
    dtype), so bf16 candidates are budgeted against their real footprint
    rather than the caller's fp32 one.

    ``tpu`` prices the buffers as a compiled TPU kernel lays them out: the
    two minor axes padded to the (8, 128) tile, inputs as the two DMA
    window slots of an x-slab (or tile) whether or not y/z are tiled, and
    output blocks double-buffered by the Pallas pipeline."""
    bx = plan.bx or lattice[0]
    tiled = bool(plan.by or plan.bz)
    if plan.dtypes is not None and plan.dtypes.storage:
        in_views = [(nc, ring, plan.dtypes.storage_itemsize(isz))
                    for nc, ring, isz in in_views]
        out_views = [(nc, plan.dtypes.storage_itemsize(isz))
                     for nc, isz in out_views]
    staged = 2 if tpu else 1
    total = 0
    for ncomp, ring, isz in in_views:
        # a compiled kernel always streams DMA windows (x-slab, or tile)
        if tiled or tpu:
            win = [bx + 2 * ring]
            if len(lattice) > 1:
                win.append((plan.by or lattice[1]) + 2 * ring)
            if len(lattice) > 2:
                win.append((plan.bz or lattice[2]) + 2 * ring)
            win.extend(s + 2 * ring for s in lattice[3:])
            total += 2 * _buffer_bytes(ncomp, win, isz, tpu)
        else:
            total += staged * _buffer_bytes(
                ncomp, [s + 2 * ring for s in lattice], isz, tpu)
    tile = tile_extents(lattice, bx, plan.by, plan.bz)
    for ncomp, isz in out_views:
        total += staged * _buffer_bytes(ncomp, tile, isz, tpu)
    return total


def choose_tiles(
    lattice: Sequence[int],
    bx: int,
    *,
    in_views: Sequence[Tuple[int, int, int]],
    out_views: Sequence[Tuple[int, int]],
    vmem_bytes: int,
    dtypes: Optional["DtypePolicy"] = None,
    tpu: bool = False,
) -> Tuple[int, int]:
    """Pick the largest (by, bz) tile whose estimated footprint fits the
    byte budget, preferring to keep the minor (z) axis whole — tile windows
    stay contiguous along the fast axis, which is what the DMA engine
    wants.  Returns (0, 0) when untiled whole-staging already fits, and the
    finest legal tile (best effort) when even it exceeds the budget.
    ``dtypes`` prices the probe at the policy's storage itemsize, ``tpu``
    at the compiled kernel's padded layout (:func:`estimate_vmem_bytes`)."""

    def fp(by, bz):
        probe = LoweringPlan("pallas", bx=bx, by=by, bz=bz, dtypes=dtypes)
        return estimate_vmem_bytes(
            probe, lattice=lattice, in_views=in_views, out_views=out_views,
            tpu=tpu)

    if fp(0, 0) <= vmem_bytes:
        return (0, 0)
    isz = min((dtypes.storage_itemsize(i) if dtypes and dtypes.storage
               else i for _, i in out_views), default=4)
    sublane = 8 * max(1, 4 // isz)

    def legal(d, ext):
        # a compiled kernel cuts the sublane axis only in whole sublane
        # tiles (its halo'd DMA window is rounded up to one) and never the
        # 128-lane axis: a halo'd lane window cannot be tile-aligned
        minor = len(lattice) - d
        if not tpu or ext == lattice[d] or minor > 2:
            return True
        return minor == 2 and ext % sublane == 0

    bys = ([d for d in divisors(lattice[1]) if legal(1, d)]
           if len(lattice) > 1 else [0])
    bzs = ([d for d in divisors(lattice[2]) if legal(2, d)]
           if len(lattice) > 2 else [0])
    pairs = [(by, bz) for by in bys for bz in bzs]
    # largest tile first; prefer whole-z (bz == lattice[2]) on ties
    pairs.sort(key=lambda p: ((p[0] or 1) * (p[1] or 1), p[1] or 1),
               reverse=True)
    for by, bz in pairs:
        by_eff = 0 if (len(lattice) > 1 and by == lattice[1]) else by
        bz_eff = 0 if (len(lattice) > 2 and bz == lattice[2]) else bz
        if not (by_eff or bz_eff):
            continue  # the untiled probe already failed
        if fp(by_eff, bz_eff) <= vmem_bytes:
            return (by_eff, bz_eff)
    return (bys[0] if len(lattice) > 1 and bys[0] < lattice[1] else 0,
            bzs[0] if len(lattice) > 2 and bzs[0] < lattice[2] else 0)


def resolve_vvl(config, nsites: int, layouts: Sequence[Layout]) -> int:
    """config.vvl when it fits, else the best choose_vvl fallback.

    'Fits' means vvl | nsites and sal | vvl for every AoSoA layout touched by
    the launch; otherwise the largest conforming divisor is substituted, so
    odd lattice sizes launch instead of raising (auto-vvl)."""
    align = sal_alignment(layouts)
    vvl = config.vvl
    if nsites % vvl == 0 and vvl % align == 0:
        return vvl
    return choose_vvl(nsites, vvl, multiple_of=align)


# -- the plan itself -----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LoweringPlan:
    """One launch's worth of lowering decisions, hashable (it is the launch
    cache key's planning component) and JSON-serializable (it is what the
    autotuner persists)."""

    engine: str = "jnp"
    vvl: int = 0
    bx: int = 0
    interpret: bool = False
    halo: str = "periodic"
    view: str = VIEW_AUTO
    # split-reduction factor: 1 lowers terminal reductions as the single
    # grid-sequential accumulator (bit-identical to the pre-rsplit code);
    # rsplit > 1 partitions the site-block (or x-slab) grid into rsplit
    # segments, each accumulating its own stage-1 partial row, combined by
    # a tiny stage-2 fold in segment order.  Deterministic for a fixed
    # rsplit; tolerance-equal (not bitwise) to rsplit=1 for fp sums, exact
    # for max and integer sums.  Pallas engine only.
    rsplit: int = 1
    # y/z tile extents for the halo'd stencil grid (pallas engine only).
    # 0 = whole axis: the pre-tiling x-slab lowering, so every persisted
    # plan (and every hand-built plan that never set them) lowers exactly
    # as before — no tune-table schema bump needed.  When set, each dim's
    # extent must divide the lattice extent, the grid gains a trailing
    # (sequential, fastest-iterating) tile axis per set extent, and each
    # program computes one (bx, by, bz) tile from a halo'd tile window —
    # on a real TPU the window is DMA'd into a double-buffered VMEM
    # scratch slot while the previous tile computes, so per-program VMEM
    # is bounded by the tile, not the lattice.  Field outputs stay bitwise
    # identical to the untiled lowering; terminal fp-sum reductions are
    # tolerance-equal (per-tile partials fold in tile order — the same
    # contract as rsplit), exact for max and integer sums.
    by: int = 0
    bz: int = 0
    # mixed-precision dtype policy (storage/compute/accumulate — see
    # :class:`DtypePolicy`).  None = the pre-policy lowering, bit-identical
    # on every engine/halo/layout path; a set policy is a tuned/explicit
    # opt-in whose field outputs are tolerance-equal (accuracy-gated by the
    # tuner) and whose max/integer reductions stay bitwise exact.  Persisted
    # plans carry it, hence the tune-table schema_version 3 -> 4 bump.
    dtypes: Optional[DtypePolicy] = None

    # -- serialization (core.tune persists plans as JSON) ----------------------

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "LoweringPlan":
        known = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in known}
        if isinstance(d.get("dtypes"), dict):
            d["dtypes"] = DtypePolicy(**{
                k: v for k, v in d["dtypes"].items()
                if k in ("storage", "compute", "accumulate")})
        return cls(**d)

    def describe(self, footprint: Optional[int] = None) -> str:
        """Short human/table label: the knob that distinguishes candidates.
        ``footprint`` (bytes, from :func:`estimate_vmem_bytes`) appends the
        estimated per-program VMEM footprint — the tuner's over-budget skip
        log and the benchmarks pass it; plain labels stay stable."""
        suffix = "/overlap" if self.halo == "overlap" else ""
        # the dtype policy is named whenever it is in play: a tuned
        # mixed-precision winner must be identifiable in persisted timing
        # labels; policy-free labels stay byte-stable
        if self.dtypes:
            suffix += f"/dt={self.dtypes.tag()}"
        fp = f" [~{footprint / 1024:.0f}KiB/prog]" if footprint else ""
        if self.engine != "pallas":
            return self.engine + suffix + fp
        knob = f"bx={self.bx}" if self.bx else f"vvl={self.vvl}"
        # the y/z tile axes are named whenever they are in play, like
        # rsplit: a tuned tiled winner must be identifiable in persisted
        # timing labels; untiled labels stay byte-stable
        tile = ((f"/ty{self.by}" if self.by else "")
                + (f"/tz{self.bz}" if self.bz else ""))
        # stencil plans carry the canonical-view knob (native AoSoA blocks
        # vs staged-nd); site-local plans are always "block", untagged so
        # persisted timing labels stay stable
        view = "/block" if (self.bx and self.view == VIEW_BLOCK) else ""
        # the split-reduction axis is named whenever it is in play — a
        # tuned rsplit>1 winner must be identifiable in the persisted
        # timing labels (its results are tolerance-, not bitwise-equal)
        rs = f"/rs{self.rsplit}" if self.rsplit > 1 else ""
        return (f"pallas/{knob}{tile}{view}{rs}"
                + ("/interpret" if self.interpret else "") + suffix + fp)

    # -- validation -------------------------------------------------------------

    def validate(
        self,
        *,
        nsites: Optional[int] = None,
        lattice: Optional[Tuple[int, ...]] = None,
        layouts: Sequence[Layout] = (),
        stencil: bool = False,
    ) -> "LoweringPlan":
        """Check this plan against a concrete launch; raises ValueError with
        the violated invariant.  Returns self (chainable)."""
        if self.engine not in ("jnp", "pallas"):
            raise ValueError(f"unknown engine {self.engine!r}")
        if self.halo not in ("periodic", "pre", "overlap"):
            raise ValueError(
                f"halo must be 'periodic', 'pre' or 'overlap', "
                f"got {self.halo!r}")
        if self.view not in (VIEW_AUTO, VIEW_BLOCK, VIEW_STAGED_ND):
            raise ValueError(f"unknown canonical-view strategy {self.view!r}")
        if self.halo == "overlap" and not stencil:
            raise ValueError(
                "halo='overlap' applies only to stencil graphs: a "
                "site-local graph has no halo exchange to overlap "
                "(add a stencil stage or use the default halo)")
        if self.rsplit < 1:
            raise ValueError(f"rsplit must be >= 1, got {self.rsplit}")
        if self.by < 0 or self.bz < 0:
            raise ValueError(
                f"tile extents must be >= 0 (0 = whole axis), got "
                f"by={self.by} bz={self.bz}")
        if self.dtypes is not None:
            self.dtypes.validate()
        if self.engine == "jnp":
            if self.rsplit > 1:
                raise ValueError(
                    "rsplit > 1 splits the pallas reduction grid into "
                    "stage-1 partial segments; the jnp engine folds "
                    "whole-lattice arrays and has no grid to split")
            if self.by or self.bz:
                raise ValueError(
                    "by/bz tile the pallas stencil grid; the jnp engine "
                    "folds whole-lattice arrays and has no grid to tile")
            return self
        if stencil:
            if self.by and lattice is not None:
                if len(lattice) < 2:
                    raise ValueError(
                        f"by={self.by} tiles the second lattice dim, but "
                        f"the lattice {lattice} has no y axis")
                if lattice[1] % self.by:
                    raise ValueError(
                        f"by={self.by} must divide the y lattice dim "
                        f"{lattice[1]} so the tile cover is exact and "
                        f"disjoint")
            if self.bz and lattice is not None:
                if len(lattice) < 3:
                    raise ValueError(
                        f"bz={self.bz} tiles the third lattice dim, but "
                        f"the lattice {lattice} has no z axis")
                if lattice[2] % self.bz:
                    raise ValueError(
                        f"bz={self.bz} must divide the z lattice dim "
                        f"{lattice[2]} so the tile cover is exact and "
                        f"disjoint")
            if self.bx < 1:
                raise ValueError(
                    f"stencil lowering needs an x-slab bx >= 1, got plan "
                    f"{self.describe()}")
            if lattice is not None and lattice[0] % self.bx:
                raise ValueError(
                    f"bx={self.bx} must divide the leading lattice dim "
                    f"{lattice[0]}")
            if (self.rsplit > 1 and lattice is not None
                    and (lattice[0] // self.bx) % self.rsplit):
                raise ValueError(
                    f"rsplit={self.rsplit} must divide the x-slab count "
                    f"{lattice[0] // self.bx} (bx={self.bx} over "
                    f"lattice[0]={lattice[0]}) so every stage-1 partial "
                    f"covers a whole number of slabs")
            if self.view == VIEW_BLOCK and layouts and not any(
                    lay.kind is LayoutKind.AOSOA for lay in layouts):
                raise ValueError(
                    "view='block' lowers stencil graphs natively on AoSoA "
                    "tiles, but no launch layout is AoSoA — use "
                    "view='staged-nd' (the per-input block alignment is "
                    "checked at launch, where halo rings are known)")
        else:
            if self.vvl < 1:
                raise ValueError(
                    f"site-local lowering needs vvl >= 1, got plan "
                    f"{self.describe()}")
            if self.bx:
                raise ValueError(
                    f"site-local lowering takes no x-slab (bx={self.bx})")
            if self.by or self.bz:
                raise ValueError(
                    f"site-local lowering takes no y/z tiles "
                    f"(by={self.by}, bz={self.bz}); tiles partition the "
                    f"halo'd stencil grid")
            if nsites is not None and nsites % self.vvl:
                raise ValueError(
                    f"vvl={self.vvl} must divide nsites={nsites} "
                    f"(use a conforming candidate from candidate_plans)")
            if (self.rsplit > 1 and nsites is not None
                    and (nsites // self.vvl) % self.rsplit):
                raise ValueError(
                    f"rsplit={self.rsplit} must divide the site-block "
                    f"count {nsites // self.vvl} (vvl={self.vvl} over "
                    f"nsites={nsites}) so every stage-1 partial covers a "
                    f"whole number of blocks")
            for lay in layouts:
                if lay.kind is LayoutKind.AOSOA and self.vvl % lay.sal:
                    raise ValueError(
                        f"vvl={self.vvl} must be a multiple of AoSoA "
                        f"sal={lay.sal}")
            if self.view not in (VIEW_AUTO, VIEW_BLOCK):
                raise ValueError(
                    "site-local lowering packs/unpacks per-block inside the "
                    "kernel (view='block')")
        return self


def adapt_plan(plan: LoweringPlan, *, stencil: bool, halo: str) -> LoweringPlan:
    """Fit an externally supplied plan (explicit policy or tuned-table entry)
    to a concrete launch: the call-site halo strategy is authoritative (the
    sharded drivers pass halo='pre') and the view must fit the lowering
    shape — site-local lowerings are always 'block'; a *stencil* plan keeps
    an explicitly chosen view (this is how a persisted native-AoSoA winner
    reaches a launch, and an explicit 'block' that cannot lower fails
    loudly at validation), while the 'auto' dataclass default resolves to
    'staged-nd' — so hand-built plans that never set view=, e.g.
    ``LoweringPlan("pallas", bx=2)`` from the pre-view era, launch exactly
    as they always did regardless of layout or alignment.  The jnp stencil
    lowering is staged by construction.  One exception on halo: 'pre' and
    'overlap' are interchangeable strategies for pre-exchanged stencil
    launches (same input contract, different schedule), so a plan that
    chose 'overlap' — e.g. a persisted autotuner winner — upgrades a
    call-site 'pre' launch to the split schedule."""
    eff = halo
    if halo == "pre" and plan.halo == "overlap" and stencil:
        eff = "overlap"
    if not stencil:
        view = VIEW_BLOCK
    elif plan.engine != "pallas" or plan.view == VIEW_AUTO:
        view = VIEW_STAGED_ND
    else:
        view = plan.view
    return dataclasses.replace(plan, halo=eff, view=view)


# -- planners ------------------------------------------------------------------

def _site_bytes(vmem_views) -> int:
    """Per-site traffic (bytes) of a launch from its (in_views, out_views)
    footprint descriptor — the coarse per-site cost choose_slab caps by."""
    in_views, out_views = vmem_views
    return (sum(ncomp * isz for ncomp, _ring, isz in in_views)
            + sum(ncomp * isz for ncomp, isz in out_views))


def default_plan(
    config,
    *,
    nsites: int,
    layouts: Sequence[Layout],
    stencil: bool = False,
    lattice: Optional[Tuple[int, ...]] = None,
    halo: str = "periodic",
    vmem_views=None,
) -> LoweringPlan:
    """The heuristic plan — bit-identical to the pre-plan inline decisions:
    jnp lowers whole-lattice; pallas site-local takes the largest conforming
    vvl divisor; pallas stencil takes the largest conforming x-slab within
    the config.vvl budget; interpret falls back automatically off-TPU.

    When a VMEM byte budget is configured (``TargetConfig.vmem_bytes`` /
    ``$TARGETDP_VMEM_BYTES``) and the launch passes its footprint
    descriptor ``vmem_views = (in_views, out_views)`` (see
    :func:`estimate_vmem_bytes`), a stencil plan whose whole-staging
    footprint exceeds the budget auto-tiles: the largest (by, bz) tile that
    fits is chosen, so a lattice too large to stage whole still launches —
    shard size bounded by the tile, not the lattice.  Without a budget the
    result is byte-identical to the pre-budget heuristics."""
    engine = config.engine
    if engine == "jnp":
        return LoweringPlan(
            "jnp", halo=halo,
            view=VIEW_STAGED_ND if stencil else VIEW_BLOCK)
    if engine != "pallas":
        raise ValueError(f"unknown engine {engine!r}")
    interpret = config.resolved_interpret()
    if stencil:
        if lattice is None:
            raise ValueError("stencil plans need the lattice shape")
        budget = resolved_vmem_bytes(config)
        site_bytes = _site_bytes(vmem_views) if (budget and vmem_views) else 0
        bx = choose_slab(lattice[0], int(math.prod(lattice[1:])), config.vvl,
                         site_bytes, budget if site_bytes else None)
        by = bz = 0
        if budget and vmem_views:
            by, bz = choose_tiles(
                lattice, bx, in_views=vmem_views[0],
                out_views=vmem_views[1], vmem_bytes=budget,
                tpu=not interpret)
        return LoweringPlan("pallas", vvl=0, bx=bx, interpret=interpret,
                            halo=halo, view=VIEW_STAGED_ND, by=by, bz=bz)
    vvl = resolve_vvl(config, nsites, layouts)
    return LoweringPlan("pallas", vvl=vvl, bx=0, interpret=interpret,
                        halo=halo, view=VIEW_BLOCK)


def plan_for_launch(config, nsites: int, layouts: Sequence[Layout]) -> LoweringPlan:
    """Plan a single-kernel site-local launch (core.target.launch and the
    bespoke kernel ops wrappers).  Honors an explicit-plan policy; the
    "tuned" policy falls back to the default heuristics here because single
    launches carry no graph signature to key the table on (wrap the kernel
    in a LaunchGraph to tune it)."""
    policy = getattr(config, "plan_policy", "default")
    if isinstance(policy, LoweringPlan):
        return policy.validate(nsites=nsites, layouts=layouts, stencil=False)
    if policy not in ("default", "tuned"):
        raise ValueError(
            f"unknown plan_policy {policy!r}; use 'default', 'tuned' or an "
            f"explicit LoweringPlan")
    return default_plan(config, nsites=nsites, layouts=layouts, stencil=False)


def interpret_for(config) -> bool:
    """The interpret decision alone, for bespoke pallas kernels whose
    tiling is internal (no vvl/slab planning surface): an explicit-plan
    policy's interpret wins, else the config's off-TPU fallback."""
    policy = getattr(config, "plan_policy", "default")
    if isinstance(policy, LoweringPlan) and policy.engine == "pallas":
        return policy.interpret
    return config.resolved_interpret()


def sub_lattice_plan(
    plan: LoweringPlan, config, lattice: Tuple[int, ...], *, halo: str = "pre"
) -> LoweringPlan:
    """Fit a stencil plan to a sub-lattice — how the overlap scheduler
    (core.overlap) plans its interior/boundary slab sub-launches: keep the
    outer plan's engine/interpret, keep its x-slab ``bx`` when it divides
    the slab's leading extent, otherwise re-choose the largest conforming
    slab for the (thin) sub-lattice.  The view drops to 'staged-nd': the
    scheduler's sliced windows are SOA Fields (arbitrary slab extents do
    not stay block-aligned), so a native-AoSoA outer plan executes its
    sub-launches on staged canonical views — bit-identical arithmetic, the
    relayout happens at assembly.  ``rsplit`` likewise drops to 1: the
    scheduler already combines per-slab reduction partials through the
    stage-2 combine (the slabs *are* the split), and a thin boundary slab's
    block count rarely keeps the outer split factor's divisibility.

    The y/z tile extents ``by``/``bz`` are *inherited* whenever they still
    divide the sub-lattice (the interior box keeps the outer tiling, so a
    >VMEM shard stays tiled under ``halo="overlap"``); a tile that no
    longer divides — thin boundary slabs, usually — drops to 0 (whole
    axis), which is always within budget for slab-thin sub-lattices."""

    def _tiles(lat):
        by = plan.by if (plan.by and len(lat) > 1
                         and lat[1] % plan.by == 0) else 0
        bz = plan.bz if (plan.bz and len(lat) > 2
                         and lat[2] % plan.bz == 0) else 0
        return by, bz

    if plan.engine != "pallas":
        return dataclasses.replace(plan, halo=halo, rsplit=1)
    by, bz = _tiles(lattice)
    if plan.bx >= 1 and lattice[0] % plan.bx == 0:
        return dataclasses.replace(plan, halo=halo, view=VIEW_STAGED_ND,
                                   rsplit=1, by=by, bz=bz)
    bx = choose_slab(
        lattice[0], int(math.prod(lattice[1:])),
        max(int(getattr(config, "vvl", 128)), 1))
    return dataclasses.replace(plan, halo=halo, bx=bx, view=VIEW_STAGED_ND,
                               rsplit=1, by=by, bz=bz)


def _rsplit_factors(nblocks: int, cap: int = 16, k: int = 2):
    """Valid split-reduction twin factors for a grid of ``nblocks``
    programs: up to ``k`` divisors > 1, preferring factors <= ``cap``
    (a split per block is legal but pays stage-2 combine latency for
    nothing).  Empty when the grid has a single program."""
    rs = [r for r in divisors(nblocks) if r > 1]
    capped = [r for r in rs if r <= cap]
    return _spread(capped or rs[:1], k)


def _spread(values, k: int):
    """Deterministic evenly-spaced subset of size <= k (keeps both ends)."""
    if len(values) <= k:
        return list(values)
    if k <= 1:
        return [values[-1]]
    idx = {round(i * (len(values) - 1) / (k - 1)) for i in range(k)}
    return [values[i] for i in sorted(idx)]


def _dtype_twin_policies(in_dtype: Optional[str]):
    """Dtype-policy twins worth sweeping for a launch whose external float
    inputs share ``in_dtype``: narrower storage with full-precision compute
    and fp64 (or compensated — resolve_accumulate degrades at runtime)
    accumulation.  The tuner rejects any twin that misses its accuracy
    gate, so the sweep proposes and the gate disposes."""
    if in_dtype == "float32":
        return [DtypePolicy(storage="bfloat16", compute="float32",
                            accumulate="float64")]
    if in_dtype == "float64":
        return [DtypePolicy(storage="float32", compute="float32",
                            accumulate="float64")]
    return []


def candidate_plans(
    config,
    *,
    nsites: int,
    layouts: Sequence[Layout],
    stencil: bool = False,
    lattice: Optional[Tuple[int, ...]] = None,
    halo: str = "periodic",
    max_candidates: int = 8,
    devices: Optional[int] = None,
    block_view: Optional[bool] = None,
    batch: int = 0,
    reduce: bool = False,
    vmem_views=None,
    in_dtype: Optional[str] = None,
) -> Tuple[LoweringPlan, ...]:
    """Enumerate valid plans for the autotuner sweep, deterministically.

    ``batch`` is the leading batch-axis extent of a batched launch (0 for
    single-lattice launches).  The candidate *geometry* is per batch
    element — vvl/bx tile one lattice, the batch axis is a whole extra grid
    dimension — so the set is the same, but the tuner keys its sweep (and
    persists winners) per batch size via ``graph_plan_key``; a sharded
    overlap twin makes no sense for a packed serving batch, so the
    halo="overlap" twins are dropped when ``batch > 0``.

    Site-local: vvl over the SAL-conforming divisors of nsites (evenly
    spread when more than ``max_candidates``).  Stencil: bx over the
    divisors of the leading lattice dim.  Exploration is bounded to 8x the
    heuristic budget (preferred vvl / slab budget) so the sweep never
    proposes whole-lattice blocks that cannot fit VMEM on a real device;
    the tuner additionally skips (and records) any candidate whose
    lowering fails.  The default (heuristic) plan is always included
    first; every candidate passes :meth:`LoweringPlan.validate` — the
    property tests (tests/test_plan.py, tests/test_property.py) assert
    this for arbitrary nsites/sal/x_dim.

    Sharded stencil launches (``halo="pre"`` and more than one device —
    ``devices`` defaults to ``jax.device_count()``) additionally get two
    ``halo="overlap"`` twins (the default slab and the widest swept one),
    so the tuner can rank the comms/compute-overlap schedule
    (core.overlap) per lattice/backend without sacrificing bx sweep
    resolution.  In-process sweeps time the split *overhead* only (there
    is no live exchange to hide), so the min_gain hysteresis keeps "pre"
    unless overlap wins decisively — a sharded timing harness (or an
    explicitly recorded winner) is what flips launches to the split
    schedule.  On a single device there is no exchange at all and the
    twins are skipped.

    Stencil launches with an AoSoA input additionally get two
    ``view="block"`` twins (the default slab and the widest swept one) —
    the native-AoSoA lowering, so the tuner can rank it against staged-nd
    per lattice/backend.  ``block_view`` gates them: ``None`` emits twins
    whenever some input layout is AoSoA (the tuner skips+records a
    candidate whose alignment fails at launch); callers that know the
    halo'd geometry pass the precise :func:`block_view_ok` verdict
    (``core.tune.plan_candidates_for`` does).

    Launches ending in a terminal reduction (``reduce=True`` —
    ``plan_candidates_for`` passes the graph's verdict) additionally get
    two ``rsplit`` twins: the default geometry with the smallest and
    largest split factor (capped at 16) dividing its block count, so the
    tuner can rank the two-stage split reduction per lattice/backend.  An
    rsplit winner is the first plan axis whose results are
    tolerance-equal rather than bitwise-equal to the default for fp sums
    (deterministic for the fixed factor; exact for max and integer
    sums).

    Stencil lattices with a y (and z) axis additionally get up to two
    tiled twins — the default slab with its y axis split (and with y+z
    split), so the tuner sweeps the tiled lowering and persists tiled
    winners.  When a VMEM byte budget is configured and the launch passes
    ``vmem_views`` (see :func:`estimate_vmem_bytes`), any candidate whose
    estimated per-program footprint exceeds the budget is dropped and
    logged with the estimate; if *no* untiled slab fits, the set degrades
    to tiled-only candidates — the budget-exceeding lattice still gets a
    sweepable, launchable plan set.

    ``in_dtype`` (the shared dtype of the launch's external float inputs,
    as a string) additionally yields dtype-policy twins off the default
    geometry (:func:`_dtype_twin_policies`): bf16 storage for fp32 inputs,
    fp32 storage for fp64 inputs, always with full-precision compute and
    fp64/compensated accumulation.  These are the first candidates whose
    *field outputs* are tolerance- rather than bitwise-equal, so the tuner
    pairs them with a hard accuracy gate (core.tune) and rejects any twin
    that drifts past it — rejected twins are logged and never persisted."""
    default = default_plan(config, nsites=nsites, layouts=layouts,
                           stencil=stencil, lattice=lattice, halo=halo,
                           vmem_views=vmem_views)
    if default.engine != "pallas":
        return (default,)
    if stencil:
        inner = int(math.prod(lattice[1:]))
        budget = max(int(config.vvl), inner)
        vmem_budget = resolved_vmem_bytes(config)
        untiled_default = (default if not (default.by or default.bz)
                           else dataclasses.replace(default, by=0, bz=0))

        def over_budget(c):
            if not (vmem_budget and vmem_views):
                return False
            fp = estimate_vmem_bytes(c, lattice=lattice,
                                     in_views=vmem_views[0],
                                     out_views=vmem_views[1],
                                     tpu=not c.interpret)
            if fp <= vmem_budget:
                return False
            log.info(
                "candidate %s skipped: estimated per-program VMEM %d B "
                "exceeds budget %d B", c.describe(footprint=fp), fp,
                vmem_budget)
            from . import telemetry
            telemetry.event("tune/pruned", plan=c.describe(), footprint=fp,
                            budget=vmem_budget, reason="vmem-budget")
            return True

        bxs = [bx for bx in divisors(lattice[0])
               if bx * inner <= 8 * budget
               and not over_budget(dataclasses.replace(untiled_default,
                                                       bx=bx))]
        bxs = bxs or ([] if (default.by or default.bz) else [default.bx])
        if devices is None:
            import jax
            devices = jax.device_count()
        with_overlap = halo == "pre" and devices > 1 and not batch
        if block_view is None:
            # Mosaic refuses the native block view's in-kernel relayout of
            # AoSoA rows into the nd window, so compiled plans get no twin
            block_view = default.interpret and any(
                lay.kind is LayoutKind.AOSOA for lay in layouts)
        # split-reduction twins come off the default geometry (or the
        # narrowest swept slab when the default lowers the whole extent as
        # one program); computed first so the bx sweep only cedes budget
        # for twins that actually exist
        red_twins = []
        if reduce:
            base = default
            if bxs and lattice[0] // base.bx < 2 and min(bxs) < base.bx:
                base = dataclasses.replace(default, bx=min(bxs))
            red_twins = [dataclasses.replace(base, rsplit=r)
                         for r in _rsplit_factors(lattice[0] // base.bx)]
        # tiled twins: the default slab with y split (and with y+z split),
        # skipping extents with nothing to split and over-budget tiles
        tile_twins = []
        if len(lattice) > 1 and len(divisors(lattice[1])) > 1:
            t1 = dataclasses.replace(default, by=divisors(lattice[1])[-2],
                                     bz=0)
            tile_twins.append(t1)
            if len(lattice) > 2 and len(divisors(lattice[2])) > 1:
                tile_twins.append(dataclasses.replace(
                    t1, bz=divisors(lattice[2])[-2]))
        tile_twins = [t for t in tile_twins
                      if t != default and not over_budget(t)]
        # dtype-policy twins off the default geometry: narrower storage,
        # full-precision compute, fp64/compensated accumulate.  Budget
        # pruning prices them at the storage itemsize (estimate_vmem_bytes
        # is policy-aware), and the tuner's accuracy gate rejects any twin
        # whose results drift past the rel-L2 budget.
        dtype_twins = [dataclasses.replace(default, dtypes=p)
                       for p in _dtype_twin_policies(in_dtype)]
        dtype_twins = [t for t in dtype_twins if not over_budget(t)]
        n_twins = ((2 if with_overlap else 0) + (2 if block_view else 0)
                   + len(red_twins) + len(tile_twins) + len(dtype_twins))
        k = max(1, max_candidates - n_twins)
        spread_bxs = _spread(bxs, k)
        cands = [dataclasses.replace(untiled_default, bx=bx)
                 for bx in spread_bxs]
        twin_bxs = sorted({default.bx, *spread_bxs[-1:]})[:2]
        if with_overlap:
            cands += [dataclasses.replace(default, bx=bx, halo="overlap")
                      for bx in twin_bxs]
        if block_view:
            cands += [dataclasses.replace(default, bx=bx, view=VIEW_BLOCK)
                      for bx in twin_bxs]
        cands += red_twins + tile_twins + dtype_twins
    else:
        align = sal_alignment(layouts)
        cap = 8 * max(int(config.vvl), 128)
        vs = [v for v in divisors(nsites)
              if v % align == 0 and v <= cap] or [default.vvl]
        red_twins = []
        if reduce:
            base = default
            if nsites // base.vvl < 2 and vs and vs[0] < base.vvl:
                base = dataclasses.replace(default, vvl=vs[0])
            red_twins = [dataclasses.replace(base, rsplit=r)
                         for r in _rsplit_factors(nsites // base.vvl)]
        dtype_twins = [dataclasses.replace(default, dtypes=p)
                       for p in _dtype_twin_policies(in_dtype)]
        k = max(1, max_candidates - len(red_twins) - len(dtype_twins))
        cands = [dataclasses.replace(default, vvl=v)
                 for v in _spread(vs, k)]
        cands += red_twins + dtype_twins
    out = [default]
    for c in cands:
        if c not in out:
            out.append(c)
    for c in out:
        c.validate(nsites=nsites, lattice=lattice, layouts=layouts,
                   stencil=stencil)
    return tuple(out[:max_candidates + 1])


# -- tuner keys ----------------------------------------------------------------

def graph_plan_key(
    signature,
    *,
    engine: str,
    halo: str,
    outputs: Sequence[str],
    inputs,
    lattice: Tuple[int, ...],
    backend: str,
    batch=0,
) -> str:
    """Stable string key for the persisted tune table: one entry per
    (graph signature, input layouts/dtypes, lattice shape, engine, halo,
    outputs, backend, batch shape).  The signature must be process-stable
    (kernel *names* and structure, not function objects — see
    LaunchGraph.plan_signature).  ``batch`` is the batched-launch key
    component ((batch size, per-input batched flags) from
    ``LaunchGraph.plan_key``); the falsy default keeps every pre-batch key
    byte-identical, so existing persisted tables stay warm."""
    parts = (signature, engine, halo, tuple(outputs), tuple(inputs),
             tuple(lattice), backend)
    if batch:
        parts = parts + (batch,)
    blob = repr(parts)
    digest = hashlib.sha256(blob.encode()).hexdigest()[:16]
    name = signature[0] if isinstance(signature, tuple) and signature else "g"
    return f"{name}|{backend}|{engine}|{digest}"
