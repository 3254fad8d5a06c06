"""Batched-serving smoke benchmark: multi-simulation solve throughput.

Times the batched CG serving path (apps.milc.driver.solve_batched — one
fused operator pallas_call + one fused masked-update pallas_call per
iteration for the WHOLE batch) against the looped single-solve oracle at
batch sizes 1/4/16, and gates on the serving contract: every slot of the
batched solve must be *bitwise identical* to the corresponding dedicated
solve.  Timings off-accelerator are trend-only (interpret-mode CPU); the
bit-identity gate is the CI pass/fail.

CI mode: ``--smoke --json SERVE_ci.json`` runs a tiny lattice at a fixed
iteration count (tol=0, so every batch size does identical per-request
work) and writes the fig3-schema artifact (``rows``/``metrics``/``gate``).

``--rsplit-sweep`` (CI artifact ``SPLIT_ci.json``) instead drives the
small-batch-many-requests serving shape through a *split-reduction*
(rsplit > 1) tuned plan for the fused normal operator and compares
per-request throughput against the unsplit default.  The gate is the
split-reduction contract, not the timing: split solutions must match the
unsplit ones within the documented fp tolerance (the <p, Ap> partials are
reassociated, nothing else is) and replay bitwise-identically.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile

import numpy as np

try:  # runnable both as a module and as a script
    from .common import csv_row, time_fn
except ImportError:
    from common import csv_row, time_fn

from repro.launch.compile_cache import setup_compile_cache
from repro.apps.milc import driver, fields
from repro.core import BatchedField, Field, SOA, TargetConfig, tune

BATCHES = (1, 4, 16)

# split-vs-unsplit solution agreement for the rsplit gate: only the fused
# <p, Ap> accumulation order differs, so the CG trajectories stay within
# a few ulps per iteration (see README "Split reductions")
RSPLIT_REL_TOL = 1e-4


def measured_serving(smoke: bool, engine: str, iters: int):
    lattice = (4, 4, 4, 8) if smoke else (8, 8, 8, 8)
    cfg = driver.MilcConfig(lattice=lattice, kappa=0.10, tol=0.0,
                            max_iter=iters, layout=SOA,
                            target=TargetConfig(engine, vvl=128))
    u, _ = driver.init_problem(cfg, seed=0)
    sources = [Field.from_numpy(
        "b", fields.random_spinor(lattice, seed=100 + i), lattice,
        cfg.layout) for i in range(max(BATCHES))]

    rows, metrics = [], {}
    # looped oracle timing: one solve, scaled — every request is the same
    # work at tol=0, and the loop has no cross-request reuse to measure
    t_single = time_fn(lambda: driver.solve(cfg, u, sources[0]),
                       iters=3, warmup=1)
    for bsz in BATCHES:
        bs = sources[:bsz]
        t_batched = time_fn(lambda _bs=bs: driver.solve_batched(cfg, u, _bs),
                            iters=3, warmup=1)
        res = driver.solve_batched(cfg, u, bs)
        identical = True
        for i, b in enumerate(bs):
            r1 = driver.solve(cfg, u, b)
            identical &= np.array_equal(np.asarray(res.x.element(i).data),
                                        np.asarray(r1.x.data))
            identical &= int(res.iterations[i]) == int(r1.iterations)
            identical &= np.array_equal(np.asarray(res.residual[i]),
                                        np.asarray(r1.residual))
        per_req = t_batched / bsz
        speedup = t_single / per_req if per_req > 0 else 0.0
        name = f"serve_smoke/batched_cg_b{bsz}"
        rows.append(csv_row(
            name, per_req * 1e6,
            f"batch={bsz};iters={iters};vs_loop={speedup:.2f}x;"
            f"bit_identical={identical}"))
        metrics[name] = {
            "batch": bsz, "cg_iters": iters, "engine": engine,
            "lattice": list(lattice), "batched_s": t_batched,
            "single_s": t_single, "per_request_s": per_req,
            "speedup_vs_loop": speedup, "bit_identical": bool(identical),
        }
    return rows, metrics


def measured_rsplit(smoke: bool, engine: str, iters: int):
    """Small-batch-many-requests CG serving, split vs unsplit reduction.

    Records an rsplit>1 winner for the fused normal-operator key into an
    isolated tune table (the ENV_VAR override), then serves ``requests``
    solves in batches of ``bsz`` under plan_policy="tuned" — only the
    wilson_normal launch flips to the split lowering; every other launch
    misses the table and keeps its default plan."""
    from repro.apps.milc.cg import wilson_normal_graph

    lattice = (4, 4, 4, 8) if smoke else (8, 8, 8, 8)
    bsz = 2
    requests = 4 if smoke else 8
    engine = "pallas"  # the split lowering is a pallas grid axis
    tgt = TargetConfig(engine, vvl=128)
    cfg = driver.MilcConfig(lattice=lattice, kappa=0.10, tol=0.0,
                            max_iter=iters, layout=SOA, target=tgt)
    u, b = driver.init_problem(cfg, seed=0)
    sources = [Field.from_numpy(
        "b", fields.random_spinor(lattice, seed=200 + i), lattice,
        cfg.layout) for i in range(requests)]

    g = wilson_normal_graph(float(cfg.kappa))
    # the batched serving launch keys the table per batch size: probe with
    # a bsz-stacked p so the recorded winner is what serving looks up
    probe = {"p": BatchedField.stack([b] * bsz, name="p"), "u": u}
    cands = tune.plan_candidates_for(g, probe, config=tgt,
                                     outputs=("ap", "pap"))
    split_cands = [c for c in cands if c.rsplit > 1]
    if not split_cands:
        raise SystemExit(
            f"no rsplit candidate for lattice {lattice}: sweep offered "
            f"{[c.describe() for c in cands]}")
    split_plan = split_cands[0]
    key = g.plan_key(probe, config=tgt, outputs=("ap", "pap"))

    def serve(run_cfg):
        outs = []
        for i in range(0, requests, bsz):
            outs.append(driver.solve_batched(run_cfg, u,
                                             sources[i:i + bsz]))
        return outs

    def stack_x(outs):
        return np.concatenate(
            [np.asarray(r.x.element(i).canonical())
             for r in outs for i in range(bsz)])

    rows, metrics = [], {}
    runs = {}
    prev_env = os.environ.get(tune.ENV_VAR)
    with tempfile.TemporaryDirectory() as tmp:
        os.environ[tune.ENV_VAR] = os.path.join(tmp, "rsplit_table.json")
        try:
            tune.clear_table_cache()
            tune.record(key, split_plan)
            for label, policy in (("unsplit", "default"), ("split", "tuned")):
                run_cfg = dataclasses.replace(
                    cfg, target=dataclasses.replace(tgt, plan_policy=policy))
                t = time_fn(lambda _c=run_cfg: serve(_c), iters=2, warmup=1)
                res = stack_x(serve(run_cfg))
                replay = stack_x(serve(run_cfg))
                runs[label] = {
                    "x": res, "t": t,
                    "reproducible": bool(np.array_equal(res, replay)),
                }
        finally:
            if prev_env is None:
                os.environ.pop(tune.ENV_VAR, None)
            else:
                os.environ[tune.ENV_VAR] = prev_env
            tune.clear_table_cache()

    rel = float(np.linalg.norm(runs["split"]["x"] - runs["unsplit"]["x"])
                / np.linalg.norm(runs["unsplit"]["x"]))
    for label, run in runs.items():
        per_req = run["t"] / requests
        other = runs["split" if label == "unsplit" else "unsplit"]
        name = f"serve_smoke/rsplit_{label}_b{bsz}"
        rows.append(csv_row(
            name, per_req * 1e6,
            f"requests={requests};iters={iters};plan="
            f"{(split_plan if label == 'split' else cands[0]).describe()};"
            f"vs_other={other['t'] / run['t']:.2f}x;"
            f"reproducible={run['reproducible']}"))
        metrics[name] = {
            "requests": requests, "batch": bsz, "cg_iters": iters,
            "engine": engine, "lattice": list(lattice),
            "plan": (split_plan if label == "split" else cands[0]).describe(),
            "total_s": run["t"], "per_request_s": per_req,
            "rel_l2_vs_unsplit": rel if label == "split" else 0.0,
            "bit_reproducible": run["reproducible"],
        }
    return rows, metrics


def gate_rsplit(metrics):
    """CI pass/fail for the split-reduction serving contract: tolerance
    agreement with the unsplit plan and bitwise replay determinism.
    Throughput is archived for trend review only."""
    failures = []
    for name, m in metrics.items():
        if m["rel_l2_vs_unsplit"] > RSPLIT_REL_TOL:
            failures.append(
                f"{name}: split solution drifted "
                f"rel={m['rel_l2_vs_unsplit']:.2e} > {RSPLIT_REL_TOL}")
        if not m["bit_reproducible"]:
            failures.append(f"{name}: replay was not bitwise identical")
    return failures


def gate_serving(metrics):
    """CI pass/fail: the batched lowering must reproduce the dedicated
    per-request solves bit-for-bit at every batch size (throughput is
    archived for trend review, not gated — off-accelerator timings
    jitter)."""
    return [f"{name}: batched solve diverged from the looped single-solve "
            f"oracle (serving-path regression)"
            for name, m in metrics.items() if not m["bit_identical"]]


def main(argv=None):
    setup_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny lattice (CI-sized run)")
    ap.add_argument("--engine", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--iters", type=int, default=12,
                    help="fixed CG iterations per request (tol=0)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows/metrics/gate to PATH (fig3 schema)")
    ap.add_argument("--rsplit-sweep", action="store_true",
                    help="split-vs-unsplit reduction serving comparison "
                         "(SPLIT_ci.json artifact)")
    args = ap.parse_args(argv)

    if args.rsplit_sweep:
        rows, metrics = measured_rsplit(args.smoke, args.engine, args.iters)
        failures = gate_rsplit(metrics)
        mode, tol = "rsplit", RSPLIT_REL_TOL
        fail_banner = "RSPLIT TOLERANCE GATE FAILED:"
    else:
        rows, metrics = measured_serving(args.smoke, args.engine, args.iters)
        failures = gate_serving(metrics)
        mode, tol = "serving", None
        fail_banner = "SERVING BIT-IDENTITY GATE FAILED:"
    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "metrics": metrics,
                       "smoke": args.smoke, "mode": mode,
                       "gate": {"tolerance": tol, "failures": failures}},
                      f, indent=2)
    if failures:
        print(fail_banner, *failures, sep="\n  ", file=sys.stderr)
        sys.exit(1)
    return rows, metrics, failures


if __name__ == "__main__":
    main()
