"""Paper Fig. 3: full-application time decomposed per kernel, plus the
layout x VVL configuration sweep (bottom panel) and the fused-vs-unfused
launch-graph comparison (``--fused``): the Ludwig 3-kernel LC chain, the
MILC CG update chain (with its fused terminal residual reduction), the
fused-*stencil* LB collide->propagate step and the fused Wilson
dslash+axpy+dot normal-operator application — each timed unfused (one
launch per kernel, every intermediate and reduction input through HBM) and
fused (one launch for the chain), with the bytes-moved model from
LaunchGraph.bytes_moved — the Roofline gain of core.fuse measured, not
asserted.

CI mode: ``--smoke --json BENCH_ci.json --gate 0.10`` runs tiny lattices,
writes the rows + structured metrics to JSON, and exits non-zero if any
fused chain is slower than its per-launch unfused baseline beyond the
given relative tolerance — the perf-regression gate wired into
.github/workflows/ci.yml (job: bench-smoke).

``--layout-sweep`` times the fused *stencil* chains (lb_step,
wilson_normal) across SoA/AoS/AoSoA{4,8,16}: the staged-nd lowering
against the native-AoSoA block lowering (``view="block"``) side by side,
gated on bit-identity — the paper's layout sweep finally reaching the
halo'd launches (see README "Layouts in stencil chains").

On this CPU-only container the *measured* numbers are the jnp-engine wall
times (the paper's "host C" build); per-processor *modelled* times come
from each kernel's bytes-per-site over the Table-1 STREAM bandwidths —
valid because every kernel is memory-bound (C4), which is exactly how the
paper reasons about Fig. 3/4.  The layout sweep measures the real effect
of AoS/SoA/AoSoA on the measurable engine (C2) and reports the structural
penalty of each layout for the pallas/TPU target.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import setup_compile_cache
from repro.core import Field, SOA, AOS, TargetConfig, aosoa, launch, target_sum
from repro.apps.ludwig import LudwigConfig, init_state
from repro.apps.ludwig.driver import (
    _be_rhs_body, _mol_field_body, _q_update_body, lc_chain_graph, step_timed,
)
from repro.apps.milc import MilcConfig, init_problem
from repro.apps.milc.cg import (
    _square_body, cg_update_graph, fused_cg_update, make_fused_normal,
    make_wilson_op, wilson_normal_graph, axpy, dot,
)

try:
    from .common import (
        LUDWIG_KERNELS, MILC_KERNELS, PROCESSORS, csv_row, time_fn, traffic_row,
    )
except ImportError:  # run as a script: python benchmarks/fig3_kernels.py
    from common import (
        LUDWIG_KERNELS, MILC_KERNELS, PROCESSORS, csv_row, time_fn, traffic_row,
    )


def ludwig_decomposition(lattice=(16, 16, 16), steps=3):
    cfg = LudwigConfig(lattice=lattice, target=TargetConfig("jnp"))
    state = init_state(cfg, seed=0)
    state, _ = step_timed(state, cfg)  # warmup/compile
    acc = {}
    for _ in range(steps):
        state, t = step_timed(state, cfg)
        for k, v in t.items():
            acc[k] = acc.get(k, 0.0) + v / steps
    nsites = int(np.prod(lattice))
    rows = []
    for k, t in acc.items():
        model = ""
        if k in LUDWIG_KERNELS:
            bps, fps = LUDWIG_KERNELS[k]
            models = {p: nsites * bps / bw
                      for p, (_, bw) in PROCESSORS.items()}
            model = ";".join(f"t_{p}_us={v*1e6:.1f}" for p, v in models.items())
        rows.append(csv_row(f"fig3_ludwig/{k}", t * 1e6, model))
    return rows


def milc_decomposition(lattice=(8, 8, 8, 8)):
    cfg = MilcConfig(lattice=lattice, kappa=0.1)
    u, b = init_problem(cfg, seed=0)
    apply_m, _, _ = make_wilson_op(u, cfg.kappa, cfg.target)
    nsites = int(np.prod(lattice))
    rows = []
    t_mv = time_fn(jax.jit(lambda x: apply_m(x).data), b)
    rows.append(csv_row("fig3_milc/wilson_matvec", t_mv * 1e6,
                        f"sites={nsites}"))
    t_ax = time_fn(jax.jit(lambda x: axpy(0.5, x, x, cfg.target).data), b)
    rows.append(csv_row("fig3_milc/scalar_mult_add", t_ax * 1e6, ""))
    t_dot = time_fn(jax.jit(lambda x: dot(x, x, cfg.target)), b)
    rows.append(csv_row("fig3_milc/dot_reduction", t_dot * 1e6, ""))
    for k, (bps, fps) in MILC_KERNELS.items():
        models = {p: nsites * bps / bw for p, (_, bw) in PROCESSORS.items()}
        rows.append(csv_row(
            f"fig3_milc_model/{k}", 0.0,
            ";".join(f"t_{p}_us={v*1e6:.1f}" for p, v in models.items())))
    return rows


def layout_vvl_sweep(lattice=(16, 16, 16), steps=3):
    """Bottom panel of Fig. 3: configuration sweep on the measurable engine.
    The pallas/TPU structural penalties (tile padding waste) are reported
    as derived columns."""
    rows = []
    base = LudwigConfig(lattice=lattice, target=TargetConfig("jnp"))
    for lay in [SOA, AOS, aosoa(64), aosoa(128)]:
        cfg = dataclasses.replace(base, layout=lay)
        state = init_state(cfg, seed=0)
        state, _ = step_timed(state, cfg)
        tot = 0.0
        for _ in range(steps):
            state, t = step_timed(state, cfg)
            tot += sum(t.values()) / steps
        # structural TPU penalty: minor-dim padding of one (comp, VVL) tile
        if lay.kind.value == "aos":
            pad = 128 / 19  # 19-comp minor dim padded to 128 lanes
        else:
            pad = 1.0
        rows.append(csv_row(f"fig3_sweep/layout={lay.name}", tot * 1e6,
                            f"tpu_tile_pad_factor={pad:.2f}"))
    return rows


def fused_vs_unfused(lattice=(16, 16, 16), milc_lattice=(8, 8, 8, 8),
                     engine="jnp"):
    """Fused launch graphs vs one-launch-per-kernel on the same chains.

    Three rows per chain: ``unfused`` is the seed behavior (one un-cached
    launch per kernel, re-traced every call), ``unfused_jit`` wraps the same
    per-kernel sequence in one jax.jit (the fair launch-cache baseline),
    ``fused`` is the LaunchGraph.  bytes_moved is engine-aware: on the
    pallas engine every pallas_call has mandated HBM I/O, so unfused_jit is
    charged full per-stage traffic; on the jnp engine XLA fuses the
    elementwise chain inside one jit, so unfused_jit is charged the same
    external traffic as fused (the LaunchGraph's traffic win is a property
    of the pallas/TPU target — on jnp its win is the launch cache and the
    guaranteed single kernel).  On a memory-bound kernel set the byte ratio
    IS the roofline-speedup bound (paper §4).

    Returns (rows, metrics): metrics maps chain -> {unfused_s,
    unfused_jit_s, fused_s} wall-clock seconds for the CI gate."""
    rows = []
    metrics = {}
    tgt = TargetConfig(engine, vvl=128)
    rng = np.random.default_rng(0)

    def chain(name, bm_unfused, bm_jit, bm_fused, t_un, t_jit, t_fu):
        metrics[name] = {"unfused_s": t_un, "unfused_jit_s": t_jit,
                         "fused_s": t_fu}
        rows.append(traffic_row(f"fig3_fused/{name}_unfused", t_un, bm_unfused))
        rows.append(traffic_row(f"fig3_fused/{name}_unfused_jit", t_jit, bm_jit))
        rows.append(traffic_row(f"fig3_fused/{name}_fused", t_fu, bm_fused))

    # ---- Ludwig 3-kernel LC chain: molecular field -> BE rhs -> Q update
    cfg = LudwigConfig(lattice=lattice, target=tgt)
    nsites = int(np.prod(lattice))

    def mk(name, ncomp):
        arr = (0.01 * rng.normal(size=(ncomp, *lattice))).astype(np.float32)
        return Field.from_numpy(name, arr, lattice, cfg.layout)

    ins = {"q": mk("q", 5), "lapq": mk("lapq", 5), "w": mk("w", 9),
           "adv": mk("adv", 5)}
    graph = lc_chain_graph(cfg)
    bm = graph.bytes_moved({k: f.ncomp for k, f in ins.items()}, nsites,
                           outputs=("q_new",))
    # XLA fuses a jitted jnp chain, eliding the intermediates pallas_calls
    # must round-trip — charge unfused_jit accordingly
    jit_bytes = bm["unfused"] if engine == "pallas" else bm["fused"]

    def lc_unfused(q, lapq, w, adv):
        h = launch(_mol_field_body, {"q": q, "lapq": lapq}, {"h": 5},
                   config=tgt,
                   params=dict(a0=cfg.a0, gamma=cfg.gamma, kappa=cfg.kappa))["h"]
        rhs = launch(_be_rhs_body, {"q": q, "h": h, "w": w}, {"rhs": 5},
                     config=tgt,
                     params=dict(gamma_rot=cfg.gamma_rot, xi=cfg.xi))["rhs"]
        return launch(_q_update_body, {"q": q, "rhs": rhs, "adv": adv},
                      {"q": 5}, config=tgt, params=dict(dt=cfg.dt))["q"].data

    def lc_fused(q, lapq, w, adv):
        return graph.launch({"q": q, "lapq": lapq, "w": w, "adv": adv},
                            config=tgt, outputs=("q_new",))["q_new"].data

    args = (ins["q"], ins["lapq"], ins["w"], ins["adv"])
    chain("ludwig_lc_chain", bm["unfused"], jit_bytes, bm["fused"],
          time_fn(lc_unfused, *args), time_fn(jax.jit(lc_unfused), *args),
          time_fn(lc_fused, *args))

    # ---- MILC CG update chain: x+alpha p, r-alpha ap, |r_new|^2 — the
    # residual square AND its reduction fuse into the one launch, so the
    # unfused baseline includes the separate target_sum pass that re-reads
    # rr_prod from HBM
    nsites4 = int(np.prod(milc_lattice))

    def mk4(name, ncomp=24):
        arr = rng.normal(size=(ncomp, *milc_lattice)).astype(np.float32)
        return Field.from_numpy(name, arr, milc_lattice, SOA)

    x, r, p, ap = mk4("x"), mk4("r"), mk4("p"), mk4("ap")
    cg_graph = cg_update_graph(24)
    bm4 = cg_graph.bytes_moved({"x": 24, "r": 24, "p": 24, "ap": 24}, nsites4,
                               outputs=("x_new", "r_new", "rr"))

    def cg_unfused(x, r, p, ap):
        xn = axpy(0.3, p, x, tgt)
        rn = axpy(-0.3, ap, r, tgt)
        prod = launch(_square_body, {"x": rn}, {"out": 24}, config=tgt)["out"]
        return xn.data, rn.data, target_sum(prod, tgt)

    def cg_fused(x, r, p, ap):
        xn, rn, rr = fused_cg_update(x, r, p, ap, jnp.float32(0.3), tgt)
        return xn.data, rn.data, rr

    jit_bytes4 = bm4["unfused"] if engine == "pallas" else bm4["fused"]
    chain("milc_cg_update", bm4["unfused"], jit_bytes4, bm4["fused"],
          time_fn(cg_unfused, x, r, p, ap),
          time_fn(jax.jit(cg_unfused), x, r, p, ap),
          time_fn(cg_fused, x, r, p, ap))

    # ---- LB step: collision fused INTO propagation's gather — a stencil
    # stage of the launch graph, so the fused variant is ONE halo'd kernel
    # even on the pallas engine and the post-collision distributions never
    # round-trip HBM (the fused-stencil bytes-moved model)
    from repro.kernels.lb_collision import collide
    from repro.kernels.lb_propagation import propagate
    from repro.kernels.lb_propagation.ops import (
        collide_propagate, collide_propagate_graph,
    )

    dist = mk("dist", 19)
    dist = dist.with_canonical(1.0 + 0.1 * dist.canonical())
    force = mk("force", 3)

    def lb_unfused(d, g):
        return propagate(collide(d, g, tau=0.8, config=tgt), config=tgt).data

    def lb_fused(d, g):
        return collide_propagate(d, g, tau=0.8, config=tgt).data

    lb_bm = collide_propagate_graph(0.8).bytes_moved(
        {"dist": 19, "force": 3}, nsites, outputs=("dist2",))
    chain("lb_step", lb_bm["unfused"],
          lb_bm["unfused"] if engine == "pallas" else lb_bm["fused"],
          lb_bm["fused"],
          time_fn(lb_unfused, dist, force),
          time_fn(jax.jit(lb_unfused), dist, force),
          time_fn(lb_fused, dist, force))

    # ---- MILC normal-operator application: both dslash stencils fused into
    # the xpay/g5 chain with <p, Ap> as a terminal reduction (one halo'd
    # kernel) vs one launch per dslash/axpy plus a separate dot
    cfg4 = MilcConfig(lattice=milc_lattice, kappa=0.1, target=tgt)
    u4, b4 = init_problem(cfg4, seed=0)
    _, _, apply_normal = make_wilson_op(u4, cfg4.kappa, tgt)
    fused_normal = make_fused_normal(u4, cfg4.kappa, tgt)
    wn_bm = wilson_normal_graph(cfg4.kappa).bytes_moved(
        {"p": 24, "u": 72}, nsites4, outputs=("ap", "pap"))

    def wn_unfused(pf):
        ap = apply_normal(pf)
        return ap.data, dot(pf, ap, tgt)

    def wn_fused(pf):
        ap, pap = fused_normal(pf)
        return ap.data, pap

    chain("milc_wilson_normal", wn_bm["unfused"],
          wn_bm["unfused"] if engine == "pallas" else wn_bm["fused"],
          wn_bm["fused"],
          time_fn(wn_unfused, b4), time_fn(jax.jit(wn_unfused), b4),
          time_fn(wn_fused, b4))
    return rows, metrics


def _time_interleaved(run, plan_a, plan_b, iters=5, warmup=2):
    """Best wall seconds for each of two plans, timed in interleaved
    rounds (the same estimator the tuner's sweep uses)."""
    import time as _time

    for _ in range(warmup):
        jax.block_until_ready(run(plan_a))
        jax.block_until_ready(run(plan_b))
    best = [float("inf"), float("inf")]
    for _ in range(iters):
        for i, plan in enumerate((plan_a, plan_b)):
            t0 = _time.perf_counter()
            jax.block_until_ready(run(plan))
            best[i] = min(best[i], _time.perf_counter() - t0)
    return best[0], best[1]


def tuned_vs_default(lattice=(16, 16, 16), milc_lattice=(8, 8, 8, 8),
                     engine="jnp", iters=3, warmup=1, min_gain=0.05):
    """``--tune`` mode: wall-clock per chain under the default heuristic
    plan vs the autotuned plan — the paper's hand-run per-architecture VVL
    sweep (§3.2.2) as a persisted artifact.  The first run sweeps candidate
    plans through core.tune and writes the winners to the tune table
    (``.targetdp_tune.json`` / $TARGETDP_TUNE_PATH); later runs load the
    table and skip the sweep (``cached`` in the metrics).

    Returns (rows, metrics): metrics maps chain -> {default_s, tuned_s,
    default_plan, tuned_plan, cached, key} for the tune-smoke CI gate."""
    from repro.core import tune
    from repro.kernels.lb_propagation.ops import collide_propagate_graph

    tgt = TargetConfig(engine, vvl=128)
    rng = np.random.default_rng(0)
    cfg = LudwigConfig(lattice=lattice, target=tgt)

    def mk(name, ncomp):
        arr = (0.01 * rng.normal(size=(ncomp, *lattice))).astype(np.float32)
        return Field.from_numpy(name, arr, lattice, cfg.layout)

    def mk4(name, ncomp=24):
        arr = rng.normal(size=(ncomp, *milc_lattice)).astype(np.float32)
        return Field.from_numpy(name, arr, milc_lattice, SOA)

    dist = mk("dist", 19)
    dist = dist.with_canonical(1.0 + 0.1 * dist.canonical())
    cfg4 = MilcConfig(lattice=milc_lattice, kappa=0.1, target=tgt)
    u4, b4 = init_problem(cfg4, seed=0)

    # (chain, graph, ins, outputs, scalars) — the four launch graphs the
    # fused comparison times, now swept by the planning layer
    cases = [
        ("ludwig_lc_chain", lc_chain_graph(cfg),
         {"q": mk("q", 5), "lapq": mk("lapq", 5), "w": mk("w", 9),
          "adv": mk("adv", 5)},
         ("q_new",), None),
        ("milc_cg_update", cg_update_graph(24),
         {"x": mk4("x"), "r": mk4("r"), "p": mk4("p"), "ap": mk4("ap")},
         ("x_new", "r_new", "rr"), {"alpha": 0.3, "neg_alpha": -0.3}),
        ("lb_step", collide_propagate_graph(0.8),
         {"dist": dist, "force": mk("force", 3)}, ("dist2",), None),
        ("milc_wilson_normal", wilson_normal_graph(cfg4.kappa),
         {"p": b4, "u": u4}, ("ap", "pap"), None),
    ]

    rows, metrics = [], {}
    for name, graph, gins, outs, sc in cases:
        default = tune.plan_candidates_for(
            graph, gins, config=tgt, outputs=outs)[0]
        tuned, info = tune.autotune_graph(
            graph, gins, config=tgt, outputs=outs, scalars=sc,
            iters=iters, warmup=warmup, min_gain=min_gain)

        def run(plan, _g=graph, _i=gins, _o=outs, _s=sc):
            return jax.tree_util.tree_leaves(
                _g.launch(_i, config=tgt, outputs=_o, scalars=_s, plan=plan))

        # gate timing mirrors the sweep's methodology — interleaved rounds,
        # per-plan min — so machine drift between two sequential median
        # measurements cannot flip the comparison
        t_def, t_tun = _time_interleaved(run, default, tuned)
        metrics[name] = {
            "default_s": t_def, "tuned_s": t_tun,
            "default_plan": default.describe(),
            "tuned_plan": tuned.describe(),
            "cached": bool(info.get("cached")), "key": info["key"],
        }
        rows.append(csv_row(f"fig3_tune/{name}_default", t_def * 1e6,
                            f"plan={default.describe()}"))
        rows.append(csv_row(f"fig3_tune/{name}_tuned", t_tun * 1e6,
                            f"plan={tuned.describe()};cached={info.get('cached')}"))
    return rows, metrics


LAYOUT_SWEEP = ("soa", "aos", "aosoa4", "aosoa8", "aosoa16")


def layout_stencil_sweep(lattice=(8, 14, 16), milc_lattice=(8, 8, 8, 8),
                         engine="pallas"):
    """``--layout-sweep``: the paper's layout switch (§3.1) applied to the
    *fused halo'd stencil chains* — the launches that dominate Figs. 3–5 —
    across SoA/AoS/AoSoA{4,8,16}, timing the staged-nd lowering against the
    native-AoSoA block lowering (``LoweringPlan.view == "block"``,
    core.plan/core.fuse) side by side where the SAL is block-aligned.

    Every native-block launch is checked **bit-identical** to its staged-nd
    twin (field outputs and on-chip reductions) — the CI layout-sweep smoke
    gates on this, so a mismatch in the native lowering fails the build.
    Lattices are chosen so the halo'd inner planes of both chains stay
    SAL-tileable up to AoSoA16 (ineligible combinations are reported as
    such, not silently dropped).

    Returns (rows, metrics): metrics maps "{chain}/{layout}" ->
    {staged_s, native_s, native_eligible, bitwise_equal, plan labels}."""
    from repro.core import tune
    from repro.core import plan as plan_mod
    from repro.core.layout import parse_layout
    from repro.kernels.lb_propagation.ops import collide_propagate_graph

    tgt = TargetConfig(engine, vvl=128)
    rng = np.random.default_rng(0)
    dist_np = (1.0 + 0.1 * rng.normal(size=(19, *lattice))).astype(np.float32)
    force_np = (0.01 * rng.normal(size=(3, *lattice))).astype(np.float32)
    cfg4 = MilcConfig(lattice=milc_lattice, kappa=0.1, target=tgt)
    u4, b4 = init_problem(cfg4, seed=0)

    cases = [
        ("lb_step", collide_propagate_graph(0.8),
         lambda lay: {"dist": Field.from_numpy("dist", dist_np, lattice, lay),
                      "force": Field.from_numpy("force", force_np, lattice,
                                                lay)},
         ("dist2",), int(np.prod(lattice))),
        ("wilson_normal", wilson_normal_graph(cfg4.kappa),
         lambda lay: {"p": b4.as_layout(lay), "u": u4.as_layout(lay)},
         ("ap", "pap"), int(np.prod(milc_lattice))),
    ]
    rows, metrics = [], {}
    for name, graph, mk_ins, outs, nsites in cases:
        for spec in LAYOUT_SWEEP:
            lay = parse_layout(spec)
            label = f"{name}/{lay.name}"
            if not lay.fits(nsites):
                rows.append(csv_row(f"fig3_layout/{label}", 0.0,
                                    "skipped=sal_does_not_tile_lattice"))
                continue
            ins = mk_ins(lay)
            default = tune.plan_candidates_for(
                graph, ins, config=tgt, outputs=outs)[0]

            def run(plan, _g=graph, _i=ins, _o=outs):
                return jax.tree_util.tree_leaves(
                    _g.launch(_i, config=tgt, outputs=_o, plan=plan))

            eligible = (engine == "pallas"
                        and tune.block_view_for(graph, ins, outs))
            m = {"staged_plan": default.describe(), "staged_s": None,
                 "native_s": None, "native_eligible": bool(eligible),
                 "bitwise_equal": None}
            if eligible:
                native = dataclasses.replace(default,
                                             view=plan_mod.VIEW_BLOCK)
                m["native_plan"] = native.describe()
                t_st, t_na = _time_interleaved(run, default, native)
                m["staged_s"], m["native_s"] = t_st, t_na
                a = graph.launch(ins, config=tgt, outputs=outs, plan=default)
                b = graph.launch(ins, config=tgt, outputs=outs, plan=native)
                equal = True
                for o in outs:
                    va = a[o].data if isinstance(a[o], Field) else a[o]
                    vb = b[o].data if isinstance(b[o], Field) else b[o]
                    equal = equal and bool(
                        np.array_equal(np.asarray(va), np.asarray(vb)))
                m["bitwise_equal"] = equal
                rows.append(csv_row(
                    f"fig3_layout/{label}_staged", t_st * 1e6,
                    f"plan={default.describe()}"))
                rows.append(csv_row(
                    f"fig3_layout/{label}_native", t_na * 1e6,
                    f"plan={native.describe()};bitwise_equal={equal}"))
            else:
                m["staged_s"] = time_fn(run, default)
                rows.append(csv_row(
                    f"fig3_layout/{label}_staged", m["staged_s"] * 1e6,
                    f"plan={default.describe()};native=ineligible"))
            metrics[label] = m
    return rows, metrics


def _stencil_vmem_views(graph, ins, outs):
    """(in_views, out_views) for the VMEM footprint model — the same
    derivation LaunchGraph.launch feeds the planner."""
    rings = graph.halo_widths(tuple(outs))
    prod = graph._produced()
    red = set(graph._reduce_outputs())
    first = next(iter(ins.values()))
    in_views = tuple(
        (f.ncomp, rings.get(n, 0), np.dtype(str(f.dtype)).itemsize)
        for n, f in ins.items())
    out_views = tuple(
        (int(prod[o][0]), np.dtype(str(prod[o][1] or first.dtype)).itemsize)
        for o in outs if o not in red)
    return in_views, out_views


def tile_stencil_sweep(lattice=(8, 14, 16), milc_lattice=(8, 8, 8, 8),
                       engine="pallas"):
    """``--tile-sweep``: the tiled y/z lowering (``LoweringPlan.by``/``bz``
    + double-buffered tile DMA on a real TPU) against whole-staging on the
    fused stencil chains — the launches whose per-program VMEM bounds the
    shard size.  Two checks per chain, both CI-gated:

    * identity: the tiled launch's field outputs are **bitwise** equal to
      the whole-staged launch and its fp sum reductions tolerance-equal
      (per-tile fold order — the rsplit contract).  The wall-clock
      regression bound is measured on the *single-tile* plan (by/bz =
      whole axes: same program count through the tiled code path), which
      isolates the lowering overhead; the multi-tile twin's timing is
      reported unbounded — on interpret/CPU more programs cost linearly
      (tiles are a capacity lever here; the DMA overlap win needs a real
      TPU).
    * capacity: a VMEM byte budget sized *below* the chain's whole-staged
      footprint makes ``candidate_plans`` reject every untiled pallas
      candidate (logged with the footprint estimate) while the default
      policy auto-tiles and the launch **runs to completion**, bit-identical
      to the unbudgeted run — the "shard bounded by tile, not lattice"
      acceptance demo.

    Returns (rows, metrics): metrics maps chain -> {whole_s, tiled_s, plan
    labels, fields_bitwise, reductions_close, budget_demo}."""
    from repro.core import plan as plan_mod
    from repro.core import tune
    from repro.kernels.lb_propagation.ops import collide_propagate_graph

    tgt = TargetConfig(engine, vvl=128)
    rng = np.random.default_rng(0)
    dist_np = (1.0 + 0.1 * rng.normal(size=(19, *lattice))).astype(np.float32)
    force_np = (0.01 * rng.normal(size=(3, *lattice))).astype(np.float32)
    cfg4 = MilcConfig(lattice=milc_lattice, kappa=0.1, target=tgt)
    u4, b4 = init_problem(cfg4, seed=0)

    def mid_div(n):  # a proper divisor that actually tiles (n>1 dims)
        divs = [d for d in range(1, n + 1) if n % d == 0]
        return divs[-2] if len(divs) > 1 else 0

    cases = [
        ("lb_step", collide_propagate_graph(0.8),
         {"dist": Field.from_numpy("dist", dist_np, lattice, SOA),
          "force": Field.from_numpy("force", force_np, lattice, SOA)},
         ("dist2",), lattice),
        ("wilson_normal", wilson_normal_graph(cfg4.kappa),
         {"p": b4, "u": u4}, ("ap", "pap"), milc_lattice),
    ]
    rows, metrics = [], {}
    for name, graph, ins, outs, lat in cases:
        whole = tune.plan_candidates_for(
            graph, ins, config=tgt, outputs=outs)[0]
        tiled = dataclasses.replace(
            whole, by=mid_div(lat[1]), bz=mid_div(lat[2]))
        # whole-axis tiles: one program per slab, same as untiled, but
        # through the tiled code path — the overhead the gate bounds
        tiled1 = dataclasses.replace(whole, by=lat[1], bz=lat[2])
        in_views, out_views = _stencil_vmem_views(graph, ins, outs)
        fp_whole = plan_mod.estimate_vmem_bytes(
            whole, lattice=lat, in_views=in_views, out_views=out_views)
        fp_tiled = plan_mod.estimate_vmem_bytes(
            tiled, lattice=lat, in_views=in_views, out_views=out_views)

        def run(plan, _g=graph, _i=ins, _o=outs):
            return jax.tree_util.tree_leaves(
                _g.launch(_i, config=tgt, outputs=_o, plan=plan))

        t_wh, t_t1 = _time_interleaved(run, whole, tiled1)
        _, t_ti = _time_interleaved(run, whole, tiled)
        a = graph.launch(ins, config=tgt, outputs=outs, plan=whole)
        fields_bitwise, reds_close = True, True
        for plan in (tiled, tiled1):
            b = graph.launch(ins, config=tgt, outputs=outs, plan=plan)
            for o in outs:
                if isinstance(a[o], Field):
                    fields_bitwise = fields_bitwise and bool(np.array_equal(
                        np.asarray(a[o].data), np.asarray(b[o].data)))
                else:  # fp reduction: per-tile fold => tolerance contract
                    reds_close = reds_close and bool(np.allclose(
                        np.asarray(a[o]), np.asarray(b[o]),
                        rtol=1e-5, atol=1e-7))

        # capacity demo: budget below the whole-staged footprint
        budget = max(fp_whole // 2, fp_tiled + 1)
        cfg_b = dataclasses.replace(tgt, vmem_bytes=budget)
        cands = tune.plan_candidates_for(
            graph, ins, config=cfg_b, outputs=outs)
        untiled_rejected = all(
            (c.by or c.bz) for c in cands if c.engine == "pallas")
        auto = cands[0]
        try:  # default policy under the budget: must run to completion
            c = graph.launch(ins, config=cfg_b, outputs=outs)
            runs = True
            demo_bitwise = all(
                bool(np.array_equal(np.asarray(a[o].data),
                                    np.asarray(c[o].data)))
                for o in outs if isinstance(a[o], Field))
        except Exception as e:  # surfaced through the gate, not a crash
            runs, demo_bitwise = False, False
            print(f"budget demo launch failed for {name}: {e}",
                  file=sys.stderr)
        metrics[name] = {
            "whole_s": t_wh, "tiled_s": t_ti, "tiled1_s": t_t1,
            "whole_plan": whole.describe(footprint=fp_whole),
            "tiled_plan": tiled.describe(footprint=fp_tiled),
            "tiled1_plan": tiled1.describe(),
            "fields_bitwise": fields_bitwise,
            "reductions_close": reds_close,
            "budget_demo": {
                "vmem_bytes": budget,
                "untiled_rejected": bool(untiled_rejected),
                "auto_plan": auto.describe(),
                "auto_tiled": bool(auto.by or auto.bz),
                "runs": runs,
                "fields_bitwise": demo_bitwise,
            },
        }
        rows.append(csv_row(f"fig3_tile/{name}_whole", t_wh * 1e6,
                            f"plan={whole.describe(footprint=fp_whole)}"))
        rows.append(csv_row(
            f"fig3_tile/{name}_tiled1", t_t1 * 1e6,
            f"plan={tiled1.describe()};bitwise={fields_bitwise}"))
        rows.append(csv_row(
            f"fig3_tile/{name}_tiled", t_ti * 1e6,
            f"plan={tiled.describe(footprint=fp_tiled)};"
            f"bitwise={fields_bitwise}"))
        rows.append(csv_row(
            f"fig3_tile/{name}_budget_demo", 0.0,
            f"vmem_bytes={budget};auto_plan={auto.describe()};runs={runs}"))
    return rows, metrics


def telemetry_trace(path, lattice=(32, 32, 32), engine="jnp", iters=20,
                    warmup=3):
    """``--trace``: the telemetry gate on the fused LB collide->propagate
    step (one Ludwig LB step = one fused halo'd launch), exporting a
    Perfetto-loadable Chrome trace of the run to ``path``.

    Three checks feed the CI gate (``--trace-gate``):

    * overhead — the SAME cached launch timed with per-launch telemetry
      off vs on (``TargetConfig.telemetry``) in interleaved best-of
      rounds, the tuner's estimator, so machine drift cannot favour one
      arm.  The span path must cost <= the gate tolerance (default 1%)
      relative.  The 32^3 jnp row is fixed even under ``--smoke``: the
      span path costs ~10us host-side per launch but launch-to-launch
      wall noise is +-20-30us (profiled: all in block_until_ready, both
      arms hitting the same cached executable), so the row must be long
      enough (~12ms) that 1% clears BOTH — on 8^3-16^3 rows the
      comparison is timer noise, not a measurement.
    * bitwise — the telemetry-on output equals the telemetry-off output
      bit for bit (spans are host-side only; enabling observability may
      never perturb the computation).
    * schema — every recorded ``launch/`` span carries the full
      plan/engine/lattice/cache/bytes field set the README
      Observability glossary documents.

    Returns (rows, metrics)."""
    from repro.core import telemetry
    from repro.kernels.lb_propagation.ops import collide_propagate_graph

    tgt = TargetConfig(engine, vvl=128)
    rng = np.random.default_rng(0)
    dist = Field.from_numpy(
        "dist",
        (1.0 + 0.1 * rng.normal(size=(19, *lattice))).astype(np.float32),
        lattice, SOA)
    force = Field.from_numpy(
        "force", (0.01 * rng.normal(size=(3, *lattice))).astype(np.float32),
        lattice, SOA)
    ins = {"dist": dist, "force": force}
    graph = collide_propagate_graph(0.8)
    cfg_off = dataclasses.replace(tgt, telemetry=False)
    cfg_on = dataclasses.replace(tgt, telemetry=True)

    def run(cfg):
        return graph.launch(ins, config=cfg, outputs=("dist2",))["dist2"].data

    telemetry.reset()
    out_off = np.asarray(run(cfg_off))
    out_on = np.asarray(run(cfg_on))
    bitwise = bool(np.array_equal(out_off, out_on))

    t_off, t_on = _time_interleaved(run, cfg_off, cfg_on, iters=iters,
                                    warmup=warmup)
    overhead = t_on / t_off - 1.0

    spans = telemetry.events("launch/")
    required = ("plan", "engine", "lattice", "cache", "bytes_fused",
                "bytes_unfused")
    missing = sorted({f for s in spans for f in required
                      if f not in s["attrs"]})
    telemetry.export_chrome_trace(path)
    with open(path) as f:
        n_trace = len(json.load(f)["traceEvents"])

    metrics = {"lb_step": {
        "off_s": t_off, "on_s": t_on, "overhead_frac": overhead,
        "bitwise_equal": bitwise, "launch_spans": len(spans),
        "schema_missing": missing, "trace_path": path,
        "trace_events": n_trace,
    }}
    rows = [
        csv_row("fig3_trace/lb_step_telemetry_off", t_off * 1e6, ""),
        csv_row("fig3_trace/lb_step_telemetry_on", t_on * 1e6,
                f"overhead={overhead * 100:+.2f}%;bitwise={bitwise};"
                f"launch_spans={len(spans)}"),
        csv_row("fig3_trace/chrome_trace", 0.0,
                f"path={path};events={n_trace}"),
    ]
    print(telemetry.format_report())
    return rows, metrics


DTYPE_SWEEP_STORAGE = ("float64", "float32", "bfloat16")


def dtype_sweep(lattice=(16, 16, 16), milc_lattice=(8, 8, 8, 8),
                engine="jnp", lb_steps=3):
    """``--dtype-sweep``: the mixed-precision storage sweep on the two
    chains the dtype-policy axis targets — the fused LB step under
    ``LudwigConfig.storage`` and the full Wilson-CG solve under
    ``MilcConfig.storage`` (iterative-refinement restarts, see
    apps/milc/cg.cg_refined) — one row per storage dtype in
    {float64, float32, bfloat16}.

    Each row reports *per-iteration* wall time and *time-to-solution*
    (for the solver: measured wall x measured iterations-to-tolerance —
    narrower storage may need more iterations, which is exactly what the
    tuner's convergence-aware cost model prices), final rel-L2 against the
    fp64-storage baseline row, and the modeled fused HBM bytes per
    application priced at the policy's storage itemsize
    (``LaunchGraph.bytes_moved(..., dtypes=...)``).

    Honesty note: with ``jax_enable_x64`` off (this container) the
    float64-storage row is *emulated* — jax truncates the casts to fp32,
    so its numerics coincide with the float32 row while its modeled bytes
    still price itemsize 8 (flagged ``emulated_fp64`` in the metrics).
    The accumulate leg of the policy falls back to compensated (Kahan)
    fp32 the same way, so the baseline is still the widest-accumulation
    run the platform can execute.

    Returns (rows, metrics): metrics maps chain -> storage -> row dict
    for the dtype-sweep CI gate (``gate_dtype``)."""
    import time as _time

    from repro.apps.ludwig.driver import lb_step_graph
    from repro.apps.milc.driver import residual_check, solve as milc_solve
    from repro.core.plan import DtypePolicy

    tgt = TargetConfig(engine, vvl=128)
    x64 = bool(jax.config.jax_enable_x64)
    rows, metrics = [], {"lb_step": {}, "wilson_normal_cg": {}}

    def policy(storage):
        return DtypePolicy(storage=storage, compute="float32",
                           accumulate="float64")

    # ---- fused LB step: distributions stream through HBM in the storage
    # dtype; the carried state is cast back each step (driver contract)
    nsites = int(np.prod(lattice))
    lb_ref = None
    for storage in DTYPE_SWEEP_STORAGE:
        cfg = LudwigConfig(lattice=lattice, target=tgt, storage=storage)
        state = init_state(cfg, seed=0)
        state, _ = step_timed(state, cfg)  # warmup/compile
        t_lb = 0.0
        for _ in range(lb_steps):
            state, t = step_timed(state, cfg)
            t_lb += t["lb_step"] / lb_steps
        dist = np.asarray(state.dist.canonical(), dtype=np.float64)
        if lb_ref is None:
            lb_ref = dist
        rel = float(np.linalg.norm(dist - lb_ref)
                    / max(float(np.linalg.norm(lb_ref)), 1e-30))
        pol = policy(storage)
        bm = lb_step_graph(cfg).bytes_moved(
            {"dist": 19, "force": 3}, nsites, outputs=("dist2", "u"),
            dtypes=pol)
        metrics["lb_step"][storage] = {
            "per_iter_s": t_lb,
            "time_to_solution_s": t_lb * lb_steps,
            "iterations": lb_steps,
            "rel_l2_vs_baseline": rel,
            "bytes_fused": bm["fused"],
            "storage_itemsize": pol.storage_itemsize(4),
            "emulated_fp64": storage == "float64" and not x64,
        }
        rows.append(csv_row(
            f"fig3_dtype/lb_step@{storage}", t_lb * 1e6,
            f"rel_l2={rel:.2e};bytes_fused={bm['fused']};"
            f"itemsize={pol.storage_itemsize(4)}"))

    # ---- Wilson-CG solve: the per-iteration operator launches move
    # storage-dtype bytes, refinement restarts recover the tolerance
    nsites4 = int(np.prod(milc_lattice))
    x_ref = None
    for storage in DTYPE_SWEEP_STORAGE:
        cfg4 = MilcConfig(lattice=milc_lattice, kappa=0.1, tol=1e-10,
                          target=tgt, storage=storage)
        u4, b4 = init_problem(cfg4, seed=0)
        res = milc_solve(cfg4, u4, b4)  # warmup/compile + the solution
        jax.block_until_ready(res.x.data)
        t0 = _time.perf_counter()
        jax.block_until_ready(milc_solve(cfg4, u4, b4).x.data)
        wall = _time.perf_counter() - t0
        iters = int(res.iterations)
        x = np.asarray(res.x.canonical(), dtype=np.float64)
        if x_ref is None:
            x_ref = x
        rel = float(np.linalg.norm(x - x_ref)
                    / max(float(np.linalg.norm(x_ref)), 1e-30))
        pol = policy(storage)
        bm = wilson_normal_graph(cfg4.kappa).bytes_moved(
            {"p": 24, "u": 72}, nsites4, outputs=("ap", "pap"), dtypes=pol)
        metrics["wilson_normal_cg"][storage] = {
            "per_iter_s": wall / max(iters, 1),
            "time_to_solution_s": wall,
            "iterations": iters,
            "rel_l2_vs_baseline": rel,
            "residual": residual_check(cfg4, u4, b4, res.x),
            "bytes_fused": bm["fused"],
            "storage_itemsize": pol.storage_itemsize(4),
            "emulated_fp64": storage == "float64" and not x64,
        }
        rows.append(csv_row(
            f"fig3_dtype/wilson_normal_cg@{storage}", wall * 1e6,
            f"iters={iters};per_iter_us={wall / max(iters, 1) * 1e6:.1f};"
            f"rel_l2={rel:.2e};bytes_fused={bm['fused']};"
            f"itemsize={pol.storage_itemsize(4)}"))
    return rows, metrics


def gate_dtype(metrics):
    """The dtype-sweep CI gate: accuracy vs the fp64-storage baseline row
    and bytes monotonicity.

    * solver rows: rel-L2 <= 1e-6 (fp32 storage) / 1e-3 (bf16 storage) —
      achievable because iterative refinement recovers the storage
      quantization each restart;
    * LB rows: fp32 <= 1e-6, but the LB step has no refinement loop (a
      single fused kernel whose output is quantized once per step), so
      its bf16 row is gated at the bf16 storage-quantization bound 1e-2 —
      the same accuracy gate the tuner applies to bf16 candidates;
    * modeled fused bytes must strictly shrink with the storage itemsize
      (8 -> 4 -> 2) — the traffic win the policy exists to buy."""
    TOL = {"wilson_normal_cg": {"float32": 1e-6, "bfloat16": 1e-3},
           "lb_step": {"float32": 1e-6, "bfloat16": 1e-2}}
    failures = []
    for chain, per in metrics.items():
        for storage, tol in TOL.get(chain, {}).items():
            m = per.get(storage)
            if m is None:
                failures.append(f"{chain}: missing {storage} row")
                continue
            if m["rel_l2_vs_baseline"] > tol:
                failures.append(
                    f"{chain}@{storage}: rel-L2 "
                    f"{m['rel_l2_vs_baseline']:.2e} vs the fp64-storage "
                    f"baseline exceeds {tol:g}")
        seq = [(s, per[s]) for s in DTYPE_SWEEP_STORAGE if s in per]
        for (sa, a), (sb, b) in zip(seq, seq[1:]):
            if not b["bytes_fused"] < a["bytes_fused"]:
                failures.append(
                    f"{chain}: modeled bytes did not shrink with the "
                    f"storage itemsize ({sa}={a['bytes_fused']} -> "
                    f"{sb}={b['bytes_fused']})")
    return failures


def gate_trace(metrics, tolerance):
    """The trace CI gate: enabling telemetry must cost <= ``tolerance``
    relative on the launch row, never change a bit of the output, and
    every launch span must carry the documented schema."""
    failures = []
    for name, m in metrics.items():
        if tolerance is not None and m["overhead_frac"] > tolerance:
            failures.append(
                f"{name}: telemetry-on {m['on_s']*1e6:.1f}us > "
                f"telemetry-off {m['off_s']*1e6:.1f}us * "
                f"(1+{tolerance:.2f}) — span overhead "
                f"{m['overhead_frac']*100:+.2f}%")
        if not m["bitwise_equal"]:
            failures.append(
                f"{name}: telemetry-on output differs bitwise from "
                f"telemetry-off — observability perturbed the launch")
        if not m["launch_spans"]:
            failures.append(f"{name}: no launch/ spans were recorded")
        if m["schema_missing"]:
            failures.append(
                f"{name}: launch spans missing schema fields "
                f"{m['schema_missing']}")
        if not m["trace_events"]:
            failures.append(f"{name}: exported Chrome trace is empty")
    return failures


def gate_tile(metrics, tolerance):
    """The tile-sweep CI gate: tiled lowering must be bitwise identical on
    fields, tolerance-equal on fp reductions, within the wall-clock bound,
    and the over-budget demo must reject untiled candidates yet run to
    completion through the auto-tiled default."""
    failures = []
    for name, m in metrics.items():
        if not m["fields_bitwise"]:
            failures.append(
                f"{name}: tiled field outputs differ bitwise from "
                f"whole-staging ({m['tiled_plan']} vs {m['whole_plan']})")
        if not m["reductions_close"]:
            failures.append(
                f"{name}: tiled reductions exceed the fp tolerance "
                f"contract ({m['tiled_plan']})")
        if tolerance is not None and m["tiled1_s"] > m["whole_s"] * (1.0 + tolerance):
            failures.append(
                f"{name}: tiled lowering overhead at equal program count "
                f"{m['tiled1_s']*1e6:.1f}us > whole-staged "
                f"{m['whole_s']*1e6:.1f}us * (1+{tolerance:.2f})")
        d = m["budget_demo"]
        if not d["untiled_rejected"]:
            failures.append(
                f"{name}: an untiled pallas candidate survived the "
                f"{d['vmem_bytes']}B budget sweep")
        if not (d["auto_tiled"] and d["runs"] and d["fields_bitwise"]):
            failures.append(
                f"{name}: over-budget demo did not run tiled to completion "
                f"bit-identically (auto_plan={d['auto_plan']}, "
                f"runs={d['runs']}, bitwise={d['fields_bitwise']})")
    return failures


def gate_layout_identity(metrics):
    """The layout-sweep CI gate: every native-block launch must be bitwise
    identical to its staged-nd twin — the view is a data-movement knob,
    never a semantics knob."""
    return [
        f"{label}: native-block output differs bitwise from staged-nd "
        f"(plans {m.get('native_plan')} vs {m['staged_plan']})"
        for label, m in metrics.items()
        if m.get("bitwise_equal") is False
    ]


def gate_tuned(metrics, tolerance):
    """The tune-smoke CI gate: a tuned plan must never be slower than the
    default heuristic plan beyond ``tolerance`` relative (when the sweep
    picked the default plan itself there is nothing to compare)."""
    failures = []
    for name, m in metrics.items():
        if m["tuned_plan"] == m["default_plan"]:
            continue
        if m["tuned_s"] > m["default_s"] * (1.0 + tolerance):
            failures.append(
                f"{name}: tuned plan {m['tuned_plan']} "
                f"{m['tuned_s']*1e6:.1f}us > default {m['default_plan']} "
                f"{m['default_s']*1e6:.1f}us * (1+{tolerance:.2f})"
            )
    return failures


def gate_regressions(metrics, tolerance):
    """The CI perf gate: every fused chain must beat (or tie, within
    ``tolerance`` relative) its per-launch unfused baseline — the seed
    behavior the fusion subsystem exists to improve on."""
    failures = []
    for name, m in metrics.items():
        limit = m["unfused_s"] * (1.0 + tolerance)
        if m["fused_s"] > limit:
            failures.append(
                f"{name}: fused {m['fused_s']*1e6:.1f}us > unfused "
                f"{m['unfused_s']*1e6:.1f}us * (1+{tolerance:.2f})"
            )
    return failures


def main(argv=None):
    setup_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fused", action="store_true",
                    help="only the fused-vs-unfused launch-graph comparison")
    ap.add_argument("--engine", default="jnp", choices=["jnp", "pallas"],
                    help="engine for the fused comparison wall-clock")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny lattices (CI-sized run)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows/metrics/gate results to PATH")
    ap.add_argument("--gate", type=float, default=None, metavar="TOL",
                    help="exit 1 if any fused chain is slower than its "
                         "unfused baseline beyond TOL (e.g. 0.10)")
    ap.add_argument("--tune", action="store_true",
                    help="autotune plans per chain (persisting winners to "
                         "the tune table) and report default-plan vs "
                         "tuned-plan wall-clock instead of fused-vs-unfused")
    ap.add_argument("--tune-gate", type=float, default=None, metavar="TOL",
                    help="with --tune: exit 1 if any tuned plan is slower "
                         "than the default plan beyond TOL (e.g. 0.05)")
    ap.add_argument("--layout-sweep", action="store_true",
                    help="sweep the fused stencil chains across "
                         "SoA/AoS/AoSoA{4,8,16}, native-block vs staged-nd "
                         "side by side, gated on bit-identity")
    ap.add_argument("--tile-sweep", action="store_true",
                    help="tiled (by/bz) vs whole-staged fused stencil "
                         "chains, gated on bit-identity and the over-budget "
                         "auto-tiling demo")
    ap.add_argument("--tile-gate", type=float, default=None, metavar="TOL",
                    help="with --tile-sweep: exit 1 on identity/demo "
                         "failure or if a tiled launch is slower than "
                         "whole-staging beyond TOL (e.g. 0.10)")
    ap.add_argument("--dtype-sweep", action="store_true",
                    help="mixed-precision storage sweep (fp64/fp32/bf16) on "
                         "the fused LB step and the refined Wilson-CG "
                         "solve, gated on rel-L2 vs the fp64-storage "
                         "baseline and on modeled bytes shrinking with the "
                         "storage itemsize")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="telemetry mode: time the fused LB step with "
                         "spans off vs on, write a Perfetto-loadable "
                         "Chrome trace to PATH, and gate on overhead, "
                         "bitwise identity and launch-span schema")
    ap.add_argument("--trace-gate", type=float, default=0.01, metavar="TOL",
                    help="with --trace: max relative span overhead on the "
                         "launch row (default 0.01)")
    args = ap.parse_args(argv)
    sizes = (dict(lattice=(8, 8, 8), milc_lattice=(4, 4, 4, 4))
             if args.smoke else {})
    rows, metrics, failures = [], {}, []
    if args.trace:
        # the trace row keeps its 32^3 lattice under --smoke: the <=1%
        # overhead gate needs a launch long enough to resolve the span cost
        rows, metrics = telemetry_trace(args.trace, engine=args.engine)
        failures += gate_trace(metrics, args.trace_gate)
    elif args.dtype_sweep:
        rows, metrics = dtype_sweep(engine=args.engine,
                                    lb_steps=2 if args.smoke else 3, **sizes)
        failures += gate_dtype(metrics)
    elif args.tile_sweep:
        tsizes = (dict(lattice=(4, 14, 16), milc_lattice=(4, 4, 4, 4))
                  if args.smoke else {})
        rows, metrics = tile_stencil_sweep(engine=args.engine, **tsizes)
        failures += gate_tile(metrics, args.tile_gate)
    elif args.layout_sweep:
        # lattices keep the halo'd inner planes SAL-tileable up to AoSoA16
        lsizes = (dict(lattice=(4, 14, 16), milc_lattice=(4, 4, 4, 4))
                  if args.smoke else {})
        rows, metrics = layout_stencil_sweep(engine=args.engine, **lsizes)
        failures += gate_layout_identity(metrics)
    elif args.tune:
        # smoke lattices are tiny, so per-launch timings are noise-heavy:
        # demand a decisive (25%) swept gain before leaving the default
        # plan, keeping the tuned-vs-default gate deterministic in CI
        rows, metrics = tuned_vs_default(
            engine=args.engine, iters=3 if args.smoke else 5,
            min_gain=0.25 if args.smoke else 0.05, **sizes)
        if args.tune_gate is not None:
            failures += gate_tuned(metrics, args.tune_gate)
    else:
        if not args.fused:
            rows += ludwig_decomposition()
            rows += milc_decomposition()
            rows += layout_vvl_sweep()
        frows, metrics = fused_vs_unfused(engine=args.engine, **sizes)
        rows += frows
        if args.gate is not None:
            failures += gate_regressions(metrics, args.gate)
    for r in rows:
        print(r)
    if args.json:
        mode = ("trace" if args.trace
                else "dtype-sweep" if args.dtype_sweep
                else "tile-sweep" if args.tile_sweep
                else "layout-sweep" if args.layout_sweep
                else "tune" if args.tune else "fused")
        tol = (args.trace_gate if args.trace
               else None if args.dtype_sweep
               else args.tile_gate if args.tile_sweep
               else args.tune_gate if args.tune else args.gate)
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "metrics": metrics,
                       "engine": args.engine, "smoke": args.smoke,
                       "mode": mode,
                       "gate": {"tolerance": tol,
                                "failures": failures}}, f, indent=2)
    if failures:
        print("PERF REGRESSION GATE FAILED:", *failures, sep="\n  ",
              file=sys.stderr)
    return rows, metrics, failures


if __name__ == "__main__":
    _, _, _failures = main()
    sys.exit(1 if _failures else 0)
