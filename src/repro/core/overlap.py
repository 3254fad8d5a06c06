"""Comms/compute overlap scheduler: interior/boundary split launches.

The paper's scaling story (§5, Fig. 5) composes targetDP with MPI halo
exchange, and per-step exchange becomes the scalability ceiling once the
subdomain thins.  Production lattice codes hide it by overlapping boundary
communication with interior compute — the decomposition the OpenACC LQCD
port of Bonati et al. (arXiv:1701.00426) uses to sustain multi-GPU scaling.
This module makes that schedule a *planned* lowering strategy
(``LoweringPlan.halo == "overlap"``) instead of a driver rewrite:

1. **start** the halo exchange of the boundary slabs (``core.halo`` —
   ppermute over the mesh; on TPU, ICI transfers),
2. run the fused kernel over the **interior** region whose stencil ring
   never reaches exchanged data — this sub-launch reads only locally-owned
   sites, so it has *no data dependence* on (1) and XLA is free to overlap
   the collective with the compute,
3. run thin **boundary-slab** sub-launches once the exchanged halos land,
4. assemble the slab outputs into the interior-lattice result.

Geometry
--------
Let ``R = max`` halo ring over the graph's external inputs and ``L_d`` the
local interior extent of lattice dim ``d``.  Output sites further than
``R`` from every decomposed subdomain face depend only on owned data; the
rest is covered by two thickness-``R`` slabs per decomposed dim (earlier
dims restricted to their interior range, later dims full — a disjoint
cover, so sites are computed exactly once).  Each slab runs the *same*
fused graph via ``LaunchGraph.launch(halo="pre")`` on a sliced window, so
the whole planning/caching machinery applies per sub-launch.

Numerics
--------
Field outputs are assembled from per-slab windows whose per-site
arithmetic is identical to the single ``halo="pre"`` launch — bit-identical
results (asserted under the 8-fake-device harness in
tests/test_distributed.py).  Terminal *reductions* are combined from
per-slab partials in deterministic slab order; that reassociates the
fp accumulation relative to the single-launch fold, so drivers that need
cross-strategy bit-stability (e.g. the CG inner products steering the
iteration) compute their dots from the assembled Fields instead — see
``apps/milc/driver.py``.

Entry points
------------
``execute_split``   called by ``LaunchGraph.launch`` when the resolved
                    plan says ``halo="overlap"``: splits a pre-exchanged
                    launch (all windows read one fully-valid halo'd array;
                    measures the split overhead, e.g. under the autotuner).
``overlap_launch``  the sharded form (inside shard_map): owns the
                    exchange, feeds the interior sub-launch from the
                    *unexchanged* padded arrays and the boundary
                    sub-launches from the exchanged ones — the real
                    comms/compute overlap.
``split_boxes``     the interior/boundary decomposition itself.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from . import halo as halo_mod
from . import plan as plan_mod
from . import telemetry
from .field import BatchedField, Field
from .layout import SOA
from .plan import LoweringPlan
from .target import TargetConfig

__all__ = ["split_boxes", "execute_split", "overlap_launch"]

log = logging.getLogger(__name__)

# (start, stop) per lattice dim, in interior (output) coordinates
Box = Tuple[Tuple[int, int], ...]


def split_boxes(
    lattice: Sequence[int], ring: int, dims: Sequence[int]
) -> Tuple[Optional[Box], List[Box]]:
    """Interior/boundary decomposition of a local lattice.

    lattice  per-shard interior extents
    ring     boundary thickness: the max halo ring of the launch's inputs
    dims     lattice dims whose halos arrive by exchange (decomposed dims)

    Returns ``(interior_box, boundary_boxes)``: the interior box shrinks by
    ``ring`` along every dim in ``dims``; the boundary is covered by two
    thickness-``ring`` slabs per dim (dims earlier in the order restricted
    to their interior range — a disjoint cover).  Returns ``(None, [])``
    when some decomposed dim is too thin to hold an interior slab
    (``L - 2*ring < 1``) — callers fall back to ``halo="pre"``.
    """
    dims = sorted(set(int(d) for d in dims))
    for d in dims:
        if d < 0 or d >= len(lattice):
            raise ValueError(
                f"split dim {d} out of range for lattice {tuple(lattice)}")
    interior = [(0, L) for L in lattice]
    for d in dims:
        if lattice[d] - 2 * ring < 1:
            return None, []
        interior[d] = (ring, lattice[d] - ring)
    boxes: List[Box] = []
    for i, d in enumerate(dims):
        base = [(0, L) for L in lattice]
        for dj in dims[:i]:
            base[dj] = (ring, lattice[dj] - ring)
        lo = list(base)
        lo[d] = (0, ring)
        hi = list(base)
        hi[d] = (lattice[d] - ring, lattice[d])
        boxes.append(tuple(lo))
        boxes.append(tuple(hi))
    return tuple(interior), boxes


def _window(f, box: Box, ring: int):
    """Slice the halo'd window a sub-launch over ``box`` needs from a
    pre-halo'd input Field (ring ``ring``): halo'd coords
    ``[start, stop + 2*ring)`` per dim.  Windows stay SOA — arbitrary slab
    extents do not stay AoSoA-block-aligned, so ``sub_lattice_plan`` pins
    every sub-launch to the staged-nd view (a native-block outer plan still
    assembles into the requested output layout, bit-identically; the
    per-site arithmetic is view-independent).  BatchedField inputs window
    every batch element identically (the box geometry is per-lattice)."""
    nd = f.canonical_nd()
    site_sl = tuple(slice(s, e + 2 * ring) for (s, e) in box)
    if getattr(f, "batch", 0):
        w = nd[(slice(None), slice(None)) + site_sl]
        return BatchedField.from_canonical(f.name, w, tuple(w.shape[2:]), SOA)
    w = nd[(slice(None),) + site_sl]
    return Field.from_canonical(f.name, w, tuple(w.shape[1:]), SOA)


def _sub_plan(outer: LoweringPlan, config, box_lat: Tuple[int, ...]) -> LoweringPlan:
    """The per-slab plan: the outer (overlap) plan rebased onto the slab's
    lattice with halo='pre' (boundary slabs are thin, so the x-slab may
    shrink) — the planning layer owns the slab choice.  A tiled outer plan
    (by/bz) keeps its y/z tiles on every sub-launch whose sub-lattice they
    still divide (the interior always qualifies when tiles divide the
    shard; thin boundary slabs may fall back to whole-axis), so sharded
    ``halo="overlap"`` runs compose with >VMEM tiling."""
    return plan_mod.sub_lattice_plan(outer, config, box_lat, halo="pre")


def _split_launch(
    graph,
    ins_interior: Mapping[str, Field],
    ins_boundary: Mapping[str, Field],
    *,
    dims: Sequence[int],
    config: TargetConfig,
    outputs: Sequence[str],
    scalars: Optional[Mapping],
    out_layouts: Mapping,
    plan: LoweringPlan,
) -> Optional[Dict[str, Union[Field, jax.Array]]]:
    """Run the interior + boundary sub-launches and assemble.

    ``ins_interior`` feeds the interior box (safe to read before the halo
    exchange lands: the window never touches decomposed-dim halo slots);
    ``ins_boundary`` feeds the boundary slabs (must be fully exchanged).
    Returns None when the split is degenerate (caller falls back to pre).
    """
    ext = [n for n in graph.external_inputs() if n in ins_boundary]
    rings = graph.halo_widths(outputs)
    ring = max((rings.get(n, 0) for n in ext), default=0)
    first = ins_boundary[ext[0]]
    r0 = rings.get(ext[0], 0)
    lattice = tuple(s - 2 * r0 for s in first.lattice)
    if ring < 1:
        return None
    interior_box, boundary = split_boxes(lattice, ring, dims)
    if interior_box is None:
        return None

    red_names = set(graph._reduce_outputs())
    field_outputs = tuple(o for o in outputs if o not in red_names)
    red_outputs = tuple(o for o in outputs if o in red_names)
    red_specs = {o: s for o, s in graph.reduce_specs().items()
                 if o in red_outputs}

    out_layouts = dict(out_layouts or {})
    for o in field_outputs:
        out_layouts.setdefault(o, first.layout)

    def launch_box(box: Box, source: Mapping[str, Field]):
        sub_ins = {n: _window(source[n], box, rings.get(n, 0)) for n in ext}
        box_lat = tuple(e - s for (s, e) in box)
        return graph.launch(
            sub_ins,
            config=config,
            outputs=outputs,
            scalars=scalars,
            halo="pre",
            plan=_sub_plan(plan, config, box_lat),
        )

    # dependency order: the interior sub-launch first — it reads only
    # locally-owned sites, so XLA may run it concurrently with the halo
    # exchange the boundary sub-launches depend on.  The interior/boundary
    # spans make the split schedule visible as a trace (core.telemetry);
    # the nested launch/* spans are the sub-launches themselves.
    gname = getattr(graph, "name", "?")
    # the windows and the assembly are the launch layer's staging
    with telemetry.scope(f"launch/{gname}"):
        with telemetry.span("overlap/interior", graph=gname,
                            box=str(interior_box)):
            results = [(interior_box, launch_box(interior_box, ins_interior))]
        for box in boundary:
            with telemetry.span("overlap/boundary", graph=gname, box=str(box)):
                results.append((box, launch_box(box, ins_boundary)))

        batch = max((int(getattr(ins_boundary[n], "batch", 0)) for n in ext),
                    default=0)
        out: Dict[str, Union[Field, jax.Array]] = {}
        for o in field_outputs:
            first_val = results[0][1][o]
            ncomp, dtype = first_val.ncomp, first_val.dtype
            lead = (batch, ncomp) if batch else (ncomp,)
            acc = jnp.zeros(lead + lattice, dtype)
            for box, res in results:
                starts = (0,) * len(lead) + tuple(s for (s, _) in box)
                acc = jax.lax.dynamic_update_slice(
                    acc, res[o].canonical_nd(), starts)
            if batch:
                out[o] = BatchedField.from_canonical(o, acc, lattice,
                                                     out_layouts[o])
            else:
                out[o] = Field.from_canonical(o, acc, lattice, out_layouts[o])
        for o in red_outputs:
            # per-slab partials merge through the shared stage-2 combine
            # (ReduceSpec.combine_partials) — the same deterministic
            # segment-order fold the split-reduction (rsplit) lowering uses,
            # stacked in slab order (interior first, then boundary slabs)
            parts = jnp.stack([res[o] for _, res in results])
            out[o] = red_specs[o].combine_partials(parts, axis=0)
        return out


def execute_split(
    graph,
    ins: Mapping[str, Field],
    *,
    config: TargetConfig,
    outputs: Sequence[str],
    scalars: Optional[Mapping],
    out_layouts: Mapping,
    plan: LoweringPlan,
    dims: Optional[Sequence[int]] = None,
) -> Dict[str, Union[Field, jax.Array]]:
    """Split execution of a pre-exchanged halo'd launch (the
    ``LaunchGraph.launch`` backend for ``plan.halo == "overlap"``).

    All windows read the same fully-valid halo'd inputs, so this measures
    and exercises the split schedule without owning an exchange — the
    sharded form with a live exchange is :func:`overlap_launch`.  ``dims``
    defaults to every lattice dim (the worst-case split).  Falls back to a
    single ``halo="pre"`` launch (logged) when the interior is too thin.
    """
    ext = [n for n in graph.external_inputs() if n in ins]
    rings = graph.halo_widths(outputs)
    r0 = rings.get(ext[0], 0)
    lattice = tuple(s - 2 * r0 for s in ins[ext[0]].lattice)
    if dims is None:
        dims = range(len(lattice))
    out = _split_launch(
        graph, ins, ins, dims=dims, config=config, outputs=outputs,
        scalars=scalars, out_layouts=out_layouts, plan=plan)
    if out is not None:
        return out
    log.warning(
        "halo='overlap' for graph %r: interior of lattice %s too thin for "
        "ring %d along dims %s — falling back to halo='pre'",
        getattr(graph, "name", "?"), lattice,
        max((rings.get(n, 0) for n in ext), default=0), list(dims))
    return graph.launch(
        ins, config=config, outputs=outputs, scalars=scalars,
        out_layouts=out_layouts, halo="pre",
        plan=dataclasses.replace(plan, halo="pre"))


def _resolve_strategy(graph, ins, *, config, outputs, plan):
    """Which halo strategy a sharded launch should use, from the planning
    layer: an explicit plan (or the tuned table, keyed exactly as a
    halo='pre' launch) may choose 'overlap'; the default policy stays
    'pre' (bit-identical to the pre-overlap drivers)."""
    if plan is None:
        policy = getattr(config, "plan_policy", "default")
        if isinstance(policy, LoweringPlan):
            plan = policy
        elif policy == "tuned":
            from . import tune
            plan = tune.lookup(graph.plan_key(
                ins, config=config, outputs=outputs, halo="pre"))
    strategy = "overlap" if (plan is not None and plan.halo == "overlap") \
        else "pre"
    return strategy, plan


def overlap_launch(
    graph,
    ins: Mapping[str, Field],
    *,
    decomposed: Sequence[Tuple[int, str, int]],
    config: Optional[TargetConfig] = None,
    outputs: Optional[Sequence[str]] = None,
    scalars: Optional[Mapping] = None,
    out_layouts: Optional[Mapping] = None,
    halo: Optional[str] = None,
    exchanged: Sequence[str] = (),
    plan: Optional[LoweringPlan] = None,
) -> Dict[str, Union[Field, jax.Array]]:
    """Sharded halo'd launch with comms/compute overlap (inside shard_map).

    ins         graph value -> Field on the *padded* local lattice (every
                dim padded by that input's halo ring, non-decomposed dims
                wrap-filled — the ``halo="pre"`` contract *before* the
                exchange).  This function owns the exchange.
    decomposed  ``Domain.decomposed`` entries: (canonical-nd array dim,
                mesh axis name, mesh axis size) per decomposed lattice dim.
    halo        "pre" (exchange, then one launch — the legacy schedule),
                "overlap" (split schedule), or None: resolve from the
                planning layer (``config.plan_policy`` / tuned table —
                the default policy keeps "pre").
    exchanged   input names whose decomposed-dim halos are already valid
                (e.g. a gauge field exchanged once per solve) — skipped by
                the per-call exchange.

    Under "overlap" the interior sub-launch reads the *unexchanged* arrays
    (it only touches owned sites), so XLA sees no data dependence between
    it and the ppermutes — the collective and the interior compute may run
    concurrently; the boundary slabs read the exchanged arrays.  Falls
    back to "pre" (logged) when the interior is too thin.
    """
    config = config or TargetConfig()
    if not graph.has_stencil:
        raise ValueError(
            "overlap_launch applies only to graphs with stencil stages "
            "(site-local graphs have no halo to exchange)")
    if halo not in (None, "pre", "overlap"):
        raise ValueError(
            f"halo must be None, 'pre' or 'overlap', got {halo!r}")
    if outputs is None:
        outputs = [v for (_, v, _, _) in graph._stages[-1].outs]
    outputs = tuple(outputs)
    rings = graph.halo_widths(outputs)
    ext = [n for n in graph.external_inputs() if n in ins]

    # exchange every input by its ring over the decomposed dims (the
    # dimension-ordered exchange of core.halo, so corners land correctly).
    # The exchange span brackets the ppermute issue — against the
    # interior sub-launch span below, the overlap win is a visible trace
    # gap, not an assertion.
    ex_ins: Dict[str, Field] = {}
    with telemetry.span(
            "overlap/exchange", graph=getattr(graph, "name", "?"),
            inputs=",".join(n for n in ext if n not in exchanged),
            pre_exchanged=",".join(n for n in ext if n in exchanged),
            dims=str([d - 1 for (d, _, _) in decomposed])):
        for n in ext:
            f = ins[n]
            r = rings.get(n, 0)
            if n not in exchanged:
                # layout-preserving: AoSoA-backed shards come back as
                # AoSoA, so a native-block plan's "pre" fallback launch
                # stages them as-is
                ex_ins[n] = halo_mod.exchange_field(f, decomposed, width=r)
            else:
                ex_ins[n] = f

    if halo is None:
        strategy, plan = _resolve_strategy(
            graph, ex_ins, config=config, outputs=outputs, plan=plan)
    else:
        strategy = halo

    if strategy == "overlap":
        if plan is None:
            r0 = rings.get(ext[0], 0)
            lattice = tuple(s - 2 * r0 for s in ins[ext[0]].lattice)
            layouts = [ins[n].layout for n in ext]
            plan = plan_mod.default_plan(
                config, nsites=int(math.prod(lattice)), layouts=layouts,
                stencil=True, lattice=lattice, halo="pre")
        dims = [d - 1 for (d, _, _) in decomposed]
        out = _split_launch(
            graph, ins, ex_ins, dims=dims, config=config, outputs=outputs,
            scalars=scalars, out_layouts=out_layouts or {}, plan=plan)
        if out is not None:
            return out
        log.warning(
            "overlap_launch for graph %r: interior too thin for the halo "
            "ring along decomposed dims %s — falling back to halo='pre'",
            getattr(graph, "name", "?"), [d - 1 for (d, _, _) in decomposed])

    sub_plan = None
    if plan is not None:
        sub_plan = dataclasses.replace(plan, halo="pre")
    return graph.launch(
        ex_ins, config=config, outputs=outputs, scalars=scalars,
        out_layouts=out_layouts, halo="pre", plan=sub_plan)
