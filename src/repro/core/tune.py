"""Persisted per-(chain, layout, backend) plan autotuner (paper §3.2.2).

The paper tunes VVL per architecture by hand; this module does the sweep
the paper's authors did manually and *persists* the winners, so later
sessions (and `plan_policy="tuned"` launches) load the table instead of
re-sweeping.  One entry per plan key — (graph signature, input layouts and
dtypes, lattice shape, engine, halo strategy, requested outputs, jax
backend) — holding the winning :class:`~repro.core.plan.LoweringPlan` plus
the sweep timings for audit.

Table location: ``.targetdp_tune.json`` in the working directory, or the
``TARGETDP_TUNE_PATH`` environment variable.  The in-memory table is cached
per path; :func:`clear_table_cache` drops it (tests use this to simulate a
fresh process — the acceptance probe is *zero sweep launches* on a second
run that hits the persisted table).

The file is stamped with a ``schema_version``; a table whose version is
missing or unknown (e.g. written by an older build whose plans lacked the
``overlap`` halo strategy) degrades to an empty table — every lookup
misses and the tuner re-sweeps, rather than mis-decoding stale entries.

Usage::

    from repro.core import tune
    plan, info = tune.autotune_graph(graph, ins, config=cfg,
                                     outputs=("dist2", "u"))
    # later processes: TargetConfig(..., plan_policy="tuned") makes every
    # LaunchGraph.launch look its plan up in the persisted table.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import plan as plan_mod
from . import telemetry
from .plan import LoweringPlan

__all__ = [
    "DEFAULT_PATH",
    "ENV_VAR",
    "tune_path",
    "load_table",
    "save_table",
    "clear_table_cache",
    "lookup",
    "record",
    "block_view_for",
    "plan_candidates_for",
    "autotune_graph",
    "stats",
    "reset_stats",
]

DEFAULT_PATH = ".targetdp_tune.json"
ENV_VAR = "TARGETDP_TUNE_PATH"
# bumped to 2 when plans gained the "overlap" halo strategy: older tables
# (version 1 wrote a "version" key, no "schema_version") load as empty.
# bumped to 3 when plans gained the split-reduction axis ``rsplit``:
# persisted plan JSON must name the axis (a version-2 entry predates the
# tolerance-vs-bitwise reduction contract), so version-2 tables load as a
# clean miss — every lookup misses, the tuner re-sweeps and re-stamps.
# bumped to 4 when plans gained the mixed-precision ``dtypes`` policy
# (storage/compute/accumulate): a version-3 entry predates the accuracy
# gate, so version-3 tables load as a clean miss and the tuner re-sweeps
# (now with dtype-policy twins) rather than trusting an un-gated winner.
SCHEMA_VERSION = 4

log = logging.getLogger(__name__)

_TABLE: Optional[Dict[str, dict]] = None
_TABLE_PATH: Optional[str] = None

# sweep_launches counts timed candidate launches (incl. warmup): the
# "no re-sweep on a warm table" probe.  lookups/hits instrument the
# plan_policy="tuned" path.  The counters live in the core.telemetry
# registry under the "tune." prefix; stats()/reset_stats() are the
# back-compat shims over it (same keys as ever).
_STAT_KEYS = ("sweep_launches", "lookups", "hits", "tunes")


def stats() -> Dict[str, int]:
    return {k: telemetry.counter_value(f"tune.{k}") for k in _STAT_KEYS}


def reset_stats() -> None:
    telemetry.reset_counters("tune.")


# -- the persisted table -------------------------------------------------------

def tune_path() -> str:
    """Where the table lives: $TARGETDP_TUNE_PATH or ./.targetdp_tune.json."""
    return os.environ.get(ENV_VAR) or DEFAULT_PATH


def load_table(path: Optional[str] = None) -> Dict[str, dict]:
    """The in-memory table for ``path`` (lazy-loaded from disk, cached per
    path).  A missing or corrupt file — or one stamped with an unknown or
    missing ``schema_version`` (pre-overlap tables wrote no stamp) —
    yields an empty table: every lookup misses, so a schema change can
    trigger a re-sweep but never a mis-decoded plan, and tuning must
    never break a launch."""
    global _TABLE, _TABLE_PATH
    path = path or tune_path()
    if _TABLE is None or _TABLE_PATH != path:
        try:
            with open(path) as f:
                raw = json.load(f)
            entries = raw.get("entries", {})
            if raw.get("schema_version") != SCHEMA_VERSION:
                entries = {}
            _TABLE = dict(entries) if isinstance(entries, dict) else {}
        except (FileNotFoundError, json.JSONDecodeError, OSError):
            _TABLE = {}
        _TABLE_PATH = path
    return _TABLE


def clear_table_cache() -> None:
    """Drop the in-memory table so the next access re-reads disk (what a
    fresh process would see)."""
    global _TABLE, _TABLE_PATH
    _TABLE, _TABLE_PATH = None, None


def save_table(path: Optional[str] = None) -> str:
    """Write the in-memory table to disk (atomic replace).  Returns path."""
    path = path or tune_path()
    table = load_table(path)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump({"schema_version": SCHEMA_VERSION, "entries": table}, f,
                  indent=2, sort_keys=True)
    os.replace(tmp, path)
    return path


def lookup(key: str, path: Optional[str] = None) -> Optional[LoweringPlan]:
    """The persisted winner for ``key``, or None (plan_policy="tuned" falls
    back to the default heuristics on a miss).  A structurally malformed
    entry (hand-edited table, truncated write, schema drift) is treated as
    a miss — tuning must never break a launch."""
    telemetry.inc("tune.lookups")
    entry = load_table(path).get(key)
    if entry is None:
        return None
    try:
        plan = LoweringPlan.from_json(dict(entry["plan"]))
        # structural sanity only (launch re-validates against the lattice);
        # stencil entries carry bx>0 or the overlap strategy, so validate
        # in the matching shape
        plan.validate(stencil=plan.bx > 0 or plan.halo == "overlap")
    except (KeyError, TypeError, ValueError):
        return None
    telemetry.inc("tune.hits")
    return plan


def record(
    key: str,
    plan: LoweringPlan,
    *,
    timings_us: Optional[Mapping[str, float]] = None,
    default: Optional[LoweringPlan] = None,
    meta: Optional[Mapping] = None,
    save: bool = True,
    path: Optional[str] = None,
) -> None:
    """Store ``plan`` as the winner for ``key`` (and persist by default)."""
    entry = {"plan": plan.to_json()}
    if timings_us:
        entry["timings_us"] = {k: round(float(v), 3)
                               for k, v in timings_us.items()}
    if default is not None:
        entry["default_plan"] = default.to_json()
    entry["meta"] = dict(meta or {})
    entry["meta"].setdefault("created", time.time())
    load_table(path)[key] = entry
    if save:
        save_table(path)


# -- the sweep -----------------------------------------------------------------

def _sweep(graph, ins, launch_kw, cands, iters: int, warmup: int):
    """Time every candidate: one warmup pass (compile) per candidate, then
    ``iters`` timed *round-robin* rounds — interleaving the candidates so
    machine drift biases them equally — taking the per-candidate min (the
    noise-robust estimator for ranking).  A candidate that raises (e.g. a
    slab over the VMEM budget on a real TPU) is recorded as failed and
    skipped, never aborting the sweep.  Every launch, warmup included,
    counts in the sweep_launches probe.

    Returns (times, failed): candidate -> best seconds / candidate ->
    error repr.  Telemetry: one ``tune/candidate`` span per candidate and
    timed round, a ``tune/failed`` instant per failure, and failures
    logged through the ``repro.core.tune`` logger."""
    def run(plan):
        out = graph.launch(ins, plan=plan, **launch_kw)
        jax.block_until_ready(jax.tree_util.tree_leaves(out))
        telemetry.inc("tune.sweep_launches")

    gname = getattr(graph, "name", "?")

    def fail(cand, e):
        failed[cand] = repr(e)
        log.warning("tune sweep: candidate %s failed for graph %r: %r",
                    cand.describe(), gname, e)
        telemetry.event("tune/failed", graph=gname, plan=cand.describe(),
                        reason=repr(e))

    times: Dict[LoweringPlan, float] = {}
    failed: Dict[LoweringPlan, str] = {}
    sweep_span = telemetry.span("tune/sweep", graph=gname,
                                candidates=len(cands))
    for cand in cands:
        with telemetry.span("tune/candidate", graph=gname,
                            plan=cand.describe(), phase="warmup"):
            try:
                for _ in range(warmup):
                    run(cand)
            except Exception as e:  # noqa: BLE001 - any lowering failure
                fail(cand, e)
    for _ in range(max(1, iters)):
        for cand in cands:
            if cand in failed:
                continue
            cspan = telemetry.span("tune/candidate", graph=gname,
                                   plan=cand.describe(), phase="timed")
            try:
                t0 = time.perf_counter()
                run(cand)
                dt = time.perf_counter() - t0
            except Exception as e:  # noqa: BLE001
                cspan.end(error=repr(e))
                fail(cand, e)
                times.pop(cand, None)
                continue
            cspan.end(best_us=dt * 1e6)
            times[cand] = min(times.get(cand, dt), dt)
    sweep_span.end(timed=len(times), failed=len(failed))
    return times, failed


def _interior_lattice(graph, ins, outputs, halo) -> Tuple[int, ...]:
    """The lattice launch plans are made for: the first input's lattice,
    minus its halo ring when the caller pre-exchanged (halo='pre') — the
    same derivation LaunchGraph.launch performs, so autotune keys and
    tuned-policy lookup keys agree."""
    first_name = next(iter(ins))
    lattice = tuple(ins[first_name].lattice)
    if graph.has_stencil and halo in ("pre", "overlap"):
        ring = graph.halo_widths(outputs).get(first_name, 0)
        lattice = tuple(s - 2 * ring for s in lattice)
    return lattice


def block_view_for(graph, ins, outputs, halo="periodic") -> bool:
    """Precise native-AoSoA eligibility for this launch geometry
    (core.plan.block_view_ok): per-input halo'd inner-plane counts come
    from the graph's ring analysis, output layouts from the launch default
    (the first input's layout) — so the candidate sweep only proposes
    ``view="block"`` plans that will actually lower."""
    if not graph.has_stencil:
        return False
    outs = tuple(outputs) if outputs is not None else None
    rings = graph.halo_widths(outs)
    in_views = []
    for n, f in ins.items():
        r = rings.get(n, 0)
        hlat = (tuple(f.lattice) if halo in ("pre", "overlap")
                else tuple(s + 2 * r for s in f.lattice))
        inner_h = 1
        for s in hlat[1:]:
            inner_h *= s
        in_views.append((f.layout, inner_h))
    interior = _interior_lattice(graph, ins, outs, halo)
    interior_inner = 1
    for s in interior[1:]:
        interior_inner *= s
    first = next(iter(ins.values()))
    return plan_mod.block_view_ok(in_views, [first.layout], interior_inner)


def plan_candidates_for(
    graph,
    ins,
    *,
    config,
    outputs: Optional[Sequence[str]] = None,
    halo: str = "periodic",
    max_candidates: int = 8,
) -> Tuple[LoweringPlan, ...]:
    """Candidate plans for launching ``graph`` with ``ins`` (first entry is
    always the default heuristic plan) — the sweep set of autotune_graph,
    also what benchmarks use to time default-vs-tuned.  Stencil sweeps with
    an aligned AoSoA input include native-block (``view="block"``) twins,
    so a persisted winner can flip the hot halo'd launches to the native
    AoSoA lowering per backend.  Graphs ending in a terminal reduction
    additionally sweep split-reduction (``rsplit``) twins, so a persisted
    winner can flip the reduction to the two-stage partial lowering."""
    lattice = _interior_lattice(graph, ins, outputs, halo)
    nsites = 1
    for s in lattice:
        nsites *= s
    layouts = [f.layout for f in ins.values()]
    batch = max((int(getattr(f, "batch", 0)) for f in ins.values()),
                default=0)
    grid = graph.nd_grid(ins)
    vmem_views = None
    if grid:
        # per-site staging shapes for the VMEM budget model — same
        # derivation LaunchGraph.launch feeds default_plan, so the sweep
        # filters (and logs) exactly the candidates a launch would reject
        outs = tuple(outputs) if outputs is not None else None
        rings = graph.halo_widths(outs)
        first = next(iter(ins.values()))
        prod = graph._produced()
        red = set(graph._reduce_outputs())
        names = outs if outs is not None else tuple(prod)
        out_views = []
        for o in names:
            if o in red or o not in prod:
                continue
            nc, dt = prod[o]
            out_views.append(
                (int(nc), jnp.dtype(dt or first.dtype).itemsize))
        vmem_views = (
            tuple((f.ncomp, rings.get(n, 0), jnp.dtype(f.dtype).itemsize)
                  for n, f in ins.items()),
            tuple(out_views),
        )
    in_dtype = str(jnp.dtype(next(iter(ins.values())).dtype))
    return plan_mod.candidate_plans(
        config, nsites=nsites, layouts=layouts, stencil=grid,
        lattice=lattice, halo=halo, max_candidates=max_candidates,
        block_view=block_view_for(graph, ins, outputs, halo), batch=batch,
        reduce=bool(graph._reduce_outputs()), vmem_views=vmem_views,
        in_dtype=in_dtype)


def _accuracy_gate_for(policy) -> float:
    """Default hard accuracy gate (max rel-L2 vs the fp64-accumulate
    baseline) for a dtype-policy candidate, scaled to how much precision
    its storage dtype throws away: half-precision storage gets a loose
    1e-2 gate, fp32 narrowing 1e-5, anything else (accumulate-only
    policies must be a strict improvement) 1e-6."""
    if policy.storage in ("bfloat16", "float16"):
        return 1e-2
    if policy.storage == "float32":
        return 1e-5
    return 1e-6


def _rel_l2(out, ref) -> float:
    """Relative L2 distance between two launch-output pytrees, pooled over
    every floating-point leaf (fields and reduction scalars alike)."""
    num = den = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        b = jnp.asarray(b)
        if not jnp.issubdtype(b.dtype, jnp.floating):
            continue
        a32 = jnp.asarray(a).astype(jnp.float32)
        b32 = b.astype(jnp.float32)
        num += float(jnp.sum((a32 - b32) ** 2))
        den += float(jnp.sum(b32 ** 2))
    return (num / den) ** 0.5 if den > 0.0 else 0.0


def _gate_policy_candidates(graph, ins, launch_kw, cands, default,
                            accuracy_gate):
    """The hard accuracy constraint: every dtype-policy candidate is probed
    once against the fp64-accumulate baseline (the default plan with
    ``accumulate="float64"`` — resolved to compensated fp32 where fp64 is
    unavailable) and rejected — logged, never timed, never persisted —
    unless its pooled rel-L2 stays under the gate.  Returns
    (surviving candidates, rejected {plan: reason})."""
    pol_cands = [c for c in cands if c.dtypes]
    if not pol_cands:
        return cands, {}
    gname = getattr(graph, "name", "?")
    base = dataclasses.replace(
        default, dtypes=plan_mod.DtypePolicy(accumulate="float64"))
    with telemetry.span("tune/accuracy_baseline", graph=gname,
                        plan=base.describe()):
        ref = graph.launch(ins, plan=base, **launch_kw)
        jax.block_until_ready(jax.tree_util.tree_leaves(ref))
    rejected: Dict[LoweringPlan, str] = {}
    for cand in pol_cands:
        gate = (accuracy_gate if accuracy_gate is not None
                else _accuracy_gate_for(cand.dtypes))
        try:
            out = graph.launch(ins, plan=cand, **launch_kw)
            err = _rel_l2(out, ref)
        except Exception as e:  # noqa: BLE001 - any lowering failure
            rejected[cand] = f"accuracy probe raised: {e!r}"
            log.warning("tune accuracy gate: probe for %s failed on graph "
                        "%r: %r", cand.describe(), gname, e)
            telemetry.event("tune/accuracy_rejected", graph=gname,
                            plan=cand.describe(), reason=repr(e))
            continue
        if err > gate:
            rejected[cand] = f"rel_l2 {err:.3e} > gate {gate:.1e}"
            log.warning("tune accuracy gate: rejecting %s on graph %r: "
                        "rel_l2 %.3e exceeds gate %.1e",
                        cand.describe(), gname, err, gate)
            telemetry.event("tune/accuracy_rejected", graph=gname,
                            plan=cand.describe(), rel_l2=err, gate=gate)
    return [c for c in cands if c not in rejected], rejected


def autotune_graph(
    graph,
    ins,
    *,
    config,
    outputs: Optional[Sequence[str]] = None,
    scalars: Optional[Mapping] = None,
    out_layouts: Optional[Mapping] = None,
    halo: str = "periodic",
    iters: int = 3,
    warmup: int = 1,
    max_candidates: int = 8,
    min_gain: float = 0.05,
    force: bool = False,
    save: bool = True,
    path: Optional[str] = None,
    accuracy_gate: Optional[float] = None,
    cost_model: Optional[Callable[[LoweringPlan], float]] = None,
) -> Tuple[LoweringPlan, dict]:
    """Sweep candidate plans for one LaunchGraph launch and persist the
    winner.  Returns ``(plan, info)`` where info holds the key, whether the
    table already had it (``cached``), the per-candidate timings, and any
    failed candidates.

    A warm table short-circuits the sweep entirely (``info["cached"] is
    True``, zero sweep launches) unless ``force=True``.  Candidates come
    from :func:`repro.core.plan.candidate_plans`; each is timed with the
    ordinary launch machinery (same cache, same probes) in round-robin
    rounds.  ``min_gain`` is hysteresis toward the default heuristic plan:
    a candidate only dethrones it by beating it by more than that relative
    margin, so timing noise cannot persist a plan that is merely noisily
    fast.  Candidates whose lowering fails (e.g. over the VMEM budget) are
    skipped and recorded — logged in ``info["failed"]`` and the table
    entry, not silently dropped.

    Mixed precision: dtype-policy candidates face a *hard accuracy
    constraint* before they are ever timed — each is probed once against
    the fp64-accumulate baseline and rejected (logged to telemetry as
    ``tune/accuracy_rejected``, reported in ``info["rejected"]`` and the
    table entry meta, never persisted as a winner) unless its pooled
    rel-L2 stays under the gate.  ``accuracy_gate`` overrides the
    per-policy default (bf16/f16 storage 1e-2, fp32 storage 1e-5, else
    1e-6).  ``cost_model`` maps a candidate plan to a cost *multiplier*
    applied on top of its measured launch time — for solver graphs pass
    measured iterations-to-tolerance per policy so ranking (and the
    min_gain hysteresis) compares time-to-solution, not raw launch time."""
    lattice = _interior_lattice(graph, ins, outputs, halo)
    key = graph.plan_key(ins, config=config, outputs=outputs, halo=halo,
                         lattice=lattice)
    if not force:
        hit = lookup(key, path)
        if hit is not None:
            return hit, {"key": key, "cached": True}

    cands = plan_candidates_for(
        graph, ins, config=config, outputs=outputs, halo=halo,
        max_candidates=max_candidates)
    default = cands[0]

    launch_kw = dict(config=config, outputs=outputs, scalars=scalars,
                     out_layouts=out_layouts, halo=halo)
    telemetry.inc("tune.tunes")
    cands, rejected = _gate_policy_candidates(
        graph, ins, launch_kw, cands, default, accuracy_gate)
    times, failed = _sweep(graph, ins, launch_kw, cands, iters, warmup)
    if not times:
        raise RuntimeError(
            f"every candidate plan failed for {getattr(graph, 'name', '?')}: "
            f"{ {c.describe(): e for c, e in failed.items()} }")
    # convergence-aware ranking: a cost multiplier (e.g. measured
    # iterations-to-tolerance for a solver graph) scales each candidate's
    # launch time into an effective time-to-solution
    cost = (lambda c: times[c] * float(cost_model(c))) if cost_model \
        else (lambda c: times[c])
    best = min(times, key=lambda c: (cost(c), c.describe()))
    # hysteresis: keep the deterministic default unless the winner is
    # *measurably* better — noise must not persist an unproven plan
    if default in times and cost(best) > cost(default) * (1.0 - min_gain):
        best = default

    timings_us = {c.describe(): t * 1e6 for c, t in times.items()}
    failed_desc = {c.describe(): e for c, e in failed.items()}
    rejected_desc = {c.describe(): e for c, e in rejected.items()}
    record(key, best, timings_us=timings_us, default=default,
           meta={"graph": getattr(graph, "name", "?"),
                 "backend": jax.default_backend(),
                 "lattice": list(lattice),
                 "vmem_bytes": plan_mod.resolved_vmem_bytes(config),
                 "failed": failed_desc,
                 "rejected": rejected_desc},
           save=save, path=path)
    return best, {"key": key, "cached": False, "timings_us": timings_us,
                  "failed": failed_desc, "rejected": rejected_desc,
                  "default": default, "best_us": times[best] * 1e6}
