"""Paper Fig. 5: strong scaling of Ludwig and MILC on multi-node systems.

The paper's measured Titan/ARCHER curves are reproduced as a first-
principles model on the v5e machine constants, using the real
decomposition geometry of our sharded drivers (per-shard interior bytes
over HBM bandwidth + halo-surface bytes over ICI links, per step/
CG-iteration).  The qualitative claims to recover (C5): near-ideal
scaling while the subdomain is fat, then communication dominance when
halo surface/volume catches up; the crossover arrives later for the
larger problem.

On top of the model curves, ``--smoke``/``--measured`` runs the *measured*
multi-shard check on whatever devices this process has (CI forces 8 fake
host devices): the sharded Ludwig LB step and the fused sharded MILC CG
under ``halo="pre"`` vs ``halo="overlap"`` — the comms/compute overlap
scheduler of core.overlap — timing both schedules through the
StepPipeline runner and asserting they are bit-identical.  A mismatch is
a regression in the split-launch path and fails the run (the bench-smoke
CI gate); the timings land in the JSON artifact for trend review.

``--json PATH`` writes rows + structured metrics in the fig3 top-level
schema (``rows`` / ``metrics`` / ``gate``), uploaded from CI alongside
``BENCH_ci.json``.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from repro.launch.compile_cache import setup_compile_cache
from repro.launch.roofline import HBM_BW, ICI_LINK_BW

try:
    from .common import csv_row, time_fn
except ImportError:  # run as a script: python benchmarks/fig5_scaling.py
    from common import csv_row, time_fn

FP = 4  # fp32 bytes


def _decompose(nodes: int):
    """Factor nodes into a near-square 2-D process grid (our dim_axes map)."""
    a = int(np.sqrt(nodes))
    while nodes % a:
        a -= 1
    return a, nodes // a


def ludwig_step_model(lattice, nodes):
    nx, ny, nz = lattice
    px, py = _decompose(nodes)
    lx, ly = nx // px, ny // py
    interior = lx * ly * nz
    # per step HBM traffic/site: all stage reads+writes (fig4 accounting)
    bytes_site = (19 * 4 + 3 + 19 * 2 + 5 * 10 + 9 + 15) * FP
    t_mem = interior * bytes_site / HBM_BW
    # halo: dist (19) w=1 + q (5) w=2 + u (3) w=1 on 4 faces of the 2-D decomp
    halo_bytes = FP * 2 * ((19 + 3 + 2 * 5) * (ly * nz + lx * nz))
    t_ici = halo_bytes / ICI_LINK_BW
    return t_mem, t_ici


def milc_iter_model(lattice, nodes):
    v = int(np.prod(lattice))
    px, py = _decompose(nodes)
    lx, ly = lattice[0] // px, lattice[1] // py
    interior = v // nodes
    bytes_site = (24 * 6 + 72 * 2) * FP * 2  # two dslash per normal-eq matvec
    t_mem = interior * bytes_site / HBM_BW
    halo_bytes = FP * 2 * 2 * 24 * 2 * (
        ly * lattice[2] * lattice[3] + lx * lattice[2] * lattice[3])
    t_ici = halo_bytes / ICI_LINK_BW
    return t_mem, t_ici


def model_rows():
    """The paper's strong-scaling curves as a machine model, with the
    overlap lower bound max(t_mem, t_ici) — what the core.overlap schedule
    targets — next to the serialized sum the pre-exchange schedule pays."""
    rows = []
    cases = [
        ("ludwig_small", ludwig_step_model, (256, 256, 256)),
        ("ludwig_large", ludwig_step_model, (1024, 1024, 512)),
        ("milc_small", milc_iter_model, (64, 64, 64, 32)),
        ("milc_large", milc_iter_model, (128, 128, 128, 64)),
    ]
    for name, model, lattice in cases:
        crossover = None
        for nodes in [1, 2, 4, 8, 16, 32, 64, 128, 256, 512]:
            if any(l % _decompose(nodes)[i % 2] for i, l in
                   enumerate(lattice[:2])):
                continue
            t_mem, t_ici = model(lattice, nodes)
            t = max(t_mem, t_ici)  # overlap lower bound
            if crossover is None and t_ici > t_mem:
                crossover = nodes
            rows.append(csv_row(
                f"fig5/{name}/nodes={nodes}", t * 1e6,
                f"t_mem_us={t_mem*1e6:.1f};t_halo_us={t_ici*1e6:.1f};"
                f"t_serial_us={(t_mem+t_ici)*1e6:.1f};"
                f"comm_bound={t_ici > t_mem}"))
        rows.append(csv_row(f"fig5/{name}/crossover", 0.0,
                            f"comm_dominates_at_nodes={crossover}"))
    return rows


# -- measured sharded steps: overlap vs pre ------------------------------------

def measured_ludwig(smoke: bool, steps: int = 3):
    """Time the sharded LB step under the pre-exchange and overlap
    schedules on this process's devices (both dims of a near-square mesh
    decomposed), and check the trajectories are bit-identical."""
    import jax
    import jax.numpy as jnp

    from repro.core import TargetConfig
    from repro.core.compat import make_mesh
    from repro.core.schedule import StepPipeline
    from repro.apps.ludwig import LudwigConfig, init_state
    from repro.apps.ludwig.driver import make_sharded_step
    from repro.lattice import Domain

    ndev = jax.device_count()
    px, py = _decompose(ndev)
    mesh = make_mesh((px, py), ("sx", "sy"))
    # locals stay >= 3 (one interior plane + two width-1 boundary slabs),
    # so the overlap split is real, not the thin-interior fallback
    lattice = (4 * px, 4 * py, 8) if smoke else (8 * px, 8 * py, 16)
    cfg = LudwigConfig(lattice=lattice, target=TargetConfig("jnp"))
    dom = Domain(global_shape=lattice, mesh=mesh,
                 dim_axes=("sx", "sy", None), halo=2)
    st0 = init_state(cfg, seed=0)
    sh = dom.sharding()
    d0 = jax.device_put(jnp.asarray(st0.dist.to_numpy()), sh)
    q0 = jax.device_put(jnp.asarray(st0.q.to_numpy()), sh)

    out = {}
    times = {}
    for mode in ("pre", "overlap"):
        # donate=False: both modes start from the same (d0, q0) buffers —
        # donation would consume them on the first mode's first step on
        # accelerator backends
        pipe = StepPipeline(make_sharded_step(cfg, dom, halo=mode),
                            donate=False)
        (d, q), per_step = pipe.run_timed((d0, q0), steps, warmup=1)
        out[mode] = (np.asarray(d), np.asarray(q))
        times[mode] = per_step
    equal = (np.array_equal(out["pre"][0], out["overlap"][0])
             and np.array_equal(out["pre"][1], out["overlap"][1]))
    metrics = {
        "devices": ndev, "lattice": list(lattice),
        "pre_s": times["pre"], "overlap_s": times["overlap"],
        "bit_identical": bool(equal),
    }
    rows = [
        csv_row("fig5_measured/ludwig_lb_step_pre", times["pre"] * 1e6,
                f"devices={ndev};lattice={'x'.join(map(str, lattice))}"),
        csv_row("fig5_measured/ludwig_lb_step_overlap",
                times["overlap"] * 1e6,
                f"devices={ndev};bit_identical={equal}"),
    ]
    return rows, metrics


def measured_milc(smoke: bool, iters: int = 3):
    """Time the fused sharded CG (fixed iteration count) under the
    pre-exchange and overlap schedules; trajectories must be bitwise
    equal (the inner products are producer-independent by construction)."""
    import jax
    import jax.numpy as jnp

    from repro.core import TargetConfig
    from repro.core.compat import make_mesh
    from repro.apps.milc import MilcConfig, init_problem
    from repro.apps.milc.driver import make_sharded_solver
    from repro.lattice import Domain

    ndev = jax.device_count()
    mesh = make_mesh((ndev,), ("mx",))
    # local x-extent 5 = one interior plane between two ring-2 slabs
    lattice = (5 * ndev, 4, 4, 4) if smoke else (6 * ndev, 8, 8, 8)
    cfg = MilcConfig(lattice=lattice, kappa=0.10, tol=0.0, max_iter=iters,
                     target=TargetConfig("jnp"))
    u, b = init_problem(cfg, seed=0)
    dom = Domain(global_shape=lattice, mesh=mesh,
                 dim_axes=("mx", None, None, None), halo=1)
    un, bn = jnp.asarray(u.to_numpy()), jnp.asarray(b.to_numpy())

    out = {}
    times = {}
    for mode in ("pre", "overlap"):
        solver = make_sharded_solver(cfg, dom, halo=mode)
        times[mode] = time_fn(solver, un, bn,
                              iters=3, warmup=1) / max(iters, 1)
        out[mode] = tuple(np.asarray(v) for v in solver(un, bn))
    equal = all(np.array_equal(a, b_) for a, b_ in zip(out["pre"],
                                                       out["overlap"]))
    metrics = {
        "devices": ndev, "lattice": list(lattice), "cg_iters": iters,
        "pre_s": times["pre"], "overlap_s": times["overlap"],
        "bit_identical": bool(equal),
    }
    rows = [
        csv_row("fig5_measured/milc_cg_iter_pre", times["pre"] * 1e6,
                f"devices={ndev};lattice={'x'.join(map(str, lattice))}"),
        csv_row("fig5_measured/milc_cg_iter_overlap", times["overlap"] * 1e6,
                f"devices={ndev};bit_identical={equal}"),
    ]
    return rows, metrics


def gate_measured(metrics):
    """The bench-smoke gate for the split-launch path: the overlap
    schedule must reproduce the pre schedule bit-for-bit (timing on fake
    CPU devices is reported, not gated — there is no real ICI to hide)."""
    failures = []
    for name, m in metrics.items():
        if not m.get("bit_identical", True):
            failures.append(
                f"{name}: halo='overlap' diverged from halo='pre' "
                f"(split-launch regression)")
    return failures


def main(argv=None):
    setup_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny lattices + the measured overlap-vs-pre "
                         "sharded rows (CI-sized run)")
    ap.add_argument("--measured", action="store_true",
                    help="include the measured sharded rows at full size")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows/metrics/gate to PATH (fig3 schema)")
    args = ap.parse_args(argv)

    rows = model_rows()
    metrics, failures = {}, []
    if args.smoke or args.measured:
        lrows, lmet = measured_ludwig(smoke=args.smoke)
        mrows, mmet = measured_milc(smoke=args.smoke)
        rows += lrows + mrows
        metrics = {"ludwig_lb_step": lmet, "milc_cg_iter": mmet}
        failures = gate_measured(metrics)
    for r in rows:
        print(r)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "metrics": metrics,
                       "smoke": args.smoke, "mode": "scaling",
                       "gate": {"tolerance": None, "failures": failures}},
                      f, indent=2)
    if failures:
        print("OVERLAP EQUALITY GATE FAILED:", *failures, sep="\n  ",
              file=sys.stderr)
    return rows, metrics, failures


if __name__ == "__main__":
    _, _, _failures = main()
    sys.exit(1 if _failures else 0)
