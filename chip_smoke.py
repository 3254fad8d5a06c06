"""Chip smoke run: the fused Ludwig and MILC paths on a TPU, end to end.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # four chips: the sharded paths only

One chip runs three phases through the application entry points, with the
Pallas engine under the default plan policy and random inputs made from
``--seed``:

* Ludwig LC-LB at 128^3 fp32: a few jitted ``step`` calls, mass
  conservation, and ``q``/``dist`` against the jnp engine on the same start;
* MILC Wilson CG at 24^3x48 (kappa 0.12, hot 0.6, tol 1e-10): ``solve`` to
  convergence, ``residual_check`` below 1e-3, and the solution and
  iteration count against the jnp engine;
* served solves: a ``SolveServer`` with a 16^3x32 and a 24^3x48 bucket,
  4 slots and 8 requests, each result against a dedicated ``solve``.

``--chips 4`` runs only the domain-decomposed paths on a 2x2 mesh — the
sharded Ludwig step (``halo="pre"`` and ``"overlap"``) and the sharded MILC
solve at the same global lattices — each against a one-device run of the
same problem, and checks that every device holds a quarter of the state.

Every fused launch must resolve to a compiled (non-interpret) plan.  The
times printed are smoke timings of a single run, not benchmark numbers.
The script exits non-zero, without a result line, when JAX finds no TPU or
any check fails; on success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# tolerances the comparisons are held to (fp32 on both engines; the two
# differ only in rounding order inside the fused kernels)
LUDWIG_REL_L2 = 1e-5     # q and dist after the steps, pallas vs jnp
MASS_REL = 1e-6          # |mass_N - mass_0| / mass_0, fp64 host sum
SOLVE_REL_L2 = 1e-4      # CG solutions: pallas vs jnp, served vs dedicated
RESIDUAL_MAX = 1e-3      # independent |Mx - b| / |b|

# the lattices run (assumed sizes, not taken from a published input): a
# Ludwig box whose halo'd LB inputs (193 MB) exceed VMEM, so the fused
# launch must stream windows; a common Wilson-ensemble volume; and the two
# served shape buckets
LUDWIG_LATTICE = (128, 128, 128)
MILC_LATTICE = (24, 24, 24, 48)
SERVE_LATTICES = ((16, 16, 16, 32), MILC_LATTICE)
LUDWIG_STEPS = 4         # timesteps per Ludwig comparison


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def timed_compile(fn, *args):
    """(compiled, seconds): lower + compile, the phase's set-up.  ``fn`` is
    jitted unless it already is.  Telemetry is cleared first, so the launch
    spans that follow are this program's own."""
    import jax

    from repro.core import telemetry

    telemetry.reset()
    t0 = time.perf_counter()
    lower = fn.lower if hasattr(fn, "lower") else jax.jit(fn).lower
    compiled = lower(*args).compile()
    return compiled, time.perf_counter() - t0


def fused_launches(label: str) -> None:
    """Count this phase's fused launches and fail on any interpret plan:
    every launch span carries its plan's describe(), which tags
    interpret-mode plans "/interpret"."""
    from repro.core import telemetry

    spans = telemetry.events("launch/")
    bad = sorted({s["attrs"]["plan"] for s in spans
                  if "/interpret" in s["attrs"].get("plan", "")})
    require(not bad, f"{label}: fused launches resolved to interpret "
                     f"plans {bad}")
    pallas = [s for s in spans if s["attrs"].get("engine") == "pallas"]
    require(bool(pallas), f"{label}: no fused pallas launch was traced")
    plans = sorted({s["attrs"]["plan"] for s in pallas})
    calls = telemetry.counter_value("fuse.pallas_calls")
    log(f"  [{label}] fused pallas launches traced: {len(pallas)}, "
        f"fuse.pallas_calls={calls}, plans={plans}")
    telemetry.reset()


# -- one chip ------------------------------------------------------------------

def ludwig_phase(seed: int, steps: int = LUDWIG_STEPS) -> None:
    import jax
    import numpy as np

    from repro.apps.ludwig import driver as lud
    from repro.core import TargetConfig

    lat = LUDWIG_LATTICE
    cfg_p = lud.LudwigConfig(lattice=lat, target=TargetConfig("pallas"))
    cfg_j = dataclasses.replace(cfg_p, target=TargetConfig("jnp"))
    log(f"Ludwig LC-LB {lat} fp32, {steps} steps")
    state0 = lud.init_state(cfg_p, seed=seed)
    mass0 = float(np.asarray(state0.dist.data, np.float64).sum())

    step_p, t_c = timed_compile(functools.partial(lud.step, cfg=cfg_p),
                                state0)
    fused_launches("ludwig")
    state = step_p(state0)
    jax.block_until_ready(state.dist.data)
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        state = step_p(state)
    jax.block_until_ready(state.dist.data)
    t_step = (time.perf_counter() - t0) / max(steps - 1, 1)
    log(f"  smoke timing: compile {t_c:.1f} s, {t_step * 1e3:.2f} ms/step "
        f"(pallas)")

    step_j, t_cj = timed_compile(functools.partial(lud.step, cfg=cfg_j),
                                 state0)
    ref = state0
    for _ in range(steps):
        ref = step_j(ref)
    jax.block_until_ready(ref.dist.data)

    for name in ("q", "dist"):
        got = getattr(state, name).to_numpy()
        want = getattr(ref, name).to_numpy()
        require(np.isfinite(got).all(), f"ludwig {name} is not finite")
        err = rel_l2(got, want)
        log(f"  {name}: rel-L2 vs jnp engine {err:.3e} "
            f"(limit {LUDWIG_REL_L2:.0e})")
        require(err <= LUDWIG_REL_L2, f"ludwig {name} differs from the jnp "
                                      f"engine: rel-L2 {err:.3e}")
    mass = float(np.asarray(state.dist.data, np.float64).sum())
    drift = abs(mass - mass0) / mass0
    log(f"  mass {mass0:.6f} -> {mass:.6f}, relative drift {drift:.3e} "
        f"(limit {MASS_REL:.0e})")
    require(drift <= MASS_REL, f"ludwig mass not conserved: {drift:.3e}")


def milc_config(lattice, target, tol=1e-10):
    from repro.apps.milc import driver as milc

    return milc.MilcConfig(lattice=lattice, kappa=0.12, tol=tol, hot=0.6,
                           max_iter=2000, target=target)


def milc_phase(seed: int):
    import jax
    import numpy as np

    from repro.apps.milc import driver as milc
    from repro.core import TargetConfig

    lat = MILC_LATTICE
    cfg_p = milc_config(lat, TargetConfig("pallas"))
    cfg_j = dataclasses.replace(cfg_p, target=TargetConfig("jnp"))
    log(f"MILC Wilson CG {lat}, kappa={cfg_p.kappa}, hot={cfg_p.hot}, "
        f"tol={cfg_p.tol}")
    t0 = time.perf_counter()
    u, b = milc.init_problem(cfg_p, seed=seed)
    log(f"  gauge + source set-up {time.perf_counter() - t0:.1f} s")

    solve_p, t_c = timed_compile(functools.partial(milc.solve, cfg_p), u, b)
    fused_launches("milc")
    t0 = time.perf_counter()
    res = solve_p(u, b)
    jax.block_until_ready(res.x.data)
    t_solve = time.perf_counter() - t0
    iters = int(res.iterations)
    log(f"  smoke timing: compile {t_c:.1f} s, solve {t_solve:.2f} s, "
        f"{iters} iterations ({t_solve / max(iters, 1) * 1e3:.2f} ms/iter)")
    rc = milc.residual_check(cfg_p, u, b, res.x)
    log(f"  normal-equation residual {float(res.residual):.3e}, "
        f"independent |Mx-b|/|b| = {rc:.3e} (limit {RESIDUAL_MAX:.0e})")
    require(rc < RESIDUAL_MAX, f"milc residual_check {rc:.3e}")

    solve_j, _ = timed_compile(functools.partial(milc.solve, cfg_j), u, b)
    ref = solve_j(u, b)
    iters_j = int(ref.iterations)
    err = rel_l2(res.x.to_numpy(), ref.x.to_numpy())
    log(f"  solution rel-L2 vs jnp engine {err:.3e} (limit "
        f"{SOLVE_REL_L2:.0e}); iterations pallas {iters} / jnp {iters_j}")
    require(np.isfinite(res.x.to_numpy()).all(), "milc solution not finite")
    require(err <= SOLVE_REL_L2, f"milc solution differs: rel-L2 {err:.3e}")
    require(abs(iters - iters_j) <= max(2, iters_j // 100),
            f"milc iteration counts differ: {iters} vs {iters_j}")
    return u


def serve_phase(seed: int, u_big) -> None:
    import jax

    from repro.apps.milc import driver as milc
    from repro.apps.milc import fields
    from repro.core import Field, TargetConfig, telemetry
    from repro.launch.serve import SolveRequest, SolveServer

    target = TargetConfig("pallas")
    tol = 1e-8
    shapes = SERVE_LATTICES
    log(f"Served solves: buckets {shapes}, 4 slots, 8 requests, tol={tol}")
    server = SolveServer(target, slots=4, tol=tol, max_iter=2000)
    cfgs, sources = {}, {}
    for i, lat in enumerate(shapes):
        cfg = milc_config(lat, target, tol=tol)
        cfgs[lat] = cfg
        if u_big is not None and lat == u_big.lattice:
            u = u_big
        else:
            u, _ = milc.init_problem(cfg, seed=seed + 10 * (i + 1))
        server.register(u, cfg.kappa)
        for j in range(4):
            rid = 10 * i + j
            b = Field.from_numpy(
                "b", fields.random_spinor(lat, seed=seed + 1000 + rid), lat,
                cfg.layout)
            sources[rid] = (u, b)
            server.submit(SolveRequest(rid=rid, b=b))
    telemetry.reset()
    t0 = time.perf_counter()
    results = server.run()
    t_serve = time.perf_counter() - t0
    ticks = {"x".join(map(str, k)): bk.iterations_run
             for k, bk in server.buckets.items()}
    log(f"  smoke timing: {len(results)} requests served in {t_serve:.1f} s "
        f"(compile included; batched ticks per bucket {ticks})")
    fused_launches("serve")
    require(sorted(results) == sorted(sources),
            f"served rids {sorted(results)} != submitted {sorted(sources)}")

    dedicated = {}
    for rid, (u, b) in sorted(sources.items()):
        lat = b.lattice
        if lat not in dedicated:
            dedicated[lat] = jax.jit(functools.partial(milc.solve, cfgs[lat]))
        ref = dedicated[lat](u, b)
        got = results[rid]
        err = rel_l2(got.x.to_numpy(), ref.x.to_numpy())
        bitwise = bool((got.x.to_numpy() == ref.x.to_numpy()).all())
        log(f"  rid={rid} lattice={lat} iterations served {got.iterations} "
            f"/ dedicated {int(ref.iterations)}, rel-L2 {err:.3e}, "
            f"bitwise equal: {bitwise}")
        require(err <= SOLVE_REL_L2,
                f"served rid {rid} differs from a dedicated solve: {err:.3e}")


# -- four chips ----------------------------------------------------------------

def check_quarters(arr, label: str) -> None:
    shards = arr.addressable_shards
    devs = {s.device for s in shards}
    require(len(shards) == 4 and len(devs) == 4,
            f"{label}: {len(shards)} shards on {len(devs)} devices")
    total = arr.size
    for s in shards:
        require(s.data.size * 4 == total,
                f"{label}: device {s.device} holds {s.data.shape} of "
                f"{arr.shape}, not a quarter")
    log(f"  {label}: 4 shards of {shards[0].data.shape} on "
        f"{sorted(d.id for d in devs)}")


def sharded_phase(seed: int, steps: int = LUDWIG_STEPS) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.apps.ludwig import driver as lud
    from repro.apps.milc import driver as milc
    from repro.core import TargetConfig, compat
    from repro.lattice import Domain

    target = TargetConfig("pallas")
    mesh = compat.make_mesh((2, 2), ("x", "y"))

    lat = LUDWIG_LATTICE
    cfg = lud.LudwigConfig(lattice=lat, target=target)
    log(f"Sharded Ludwig {lat} on a 2x2 mesh, {steps} steps")
    state0 = lud.init_state(cfg, seed=seed)
    step_1, t_c = timed_compile(functools.partial(lud.step, cfg=cfg), state0)
    ref = state0
    for _ in range(steps):
        ref = step_1(ref)
    log(f"  one-device reference: compile {t_c:.1f} s")
    dom = Domain(global_shape=lat, mesh=mesh, dim_axes=("x", "y", None),
                 halo=2)
    sh = dom.sharding()
    for halo in ("pre", "overlap"):
        dist = jax.device_put(jnp.asarray(state0.dist.to_numpy()), sh)
        q = jax.device_put(jnp.asarray(state0.q.to_numpy()), sh)
        sstep, t_c = timed_compile(
            lud.make_sharded_step(cfg, dom, halo=halo), dist, q)
        fused_launches(f"ludwig sharded halo={halo}")
        t0 = time.perf_counter()
        for _ in range(steps):
            dist, q = sstep(dist, q)
        jax.block_until_ready(dist)
        t_run = (time.perf_counter() - t0) / steps
        log(f"  halo={halo}: smoke timing compile {t_c:.1f} s, "
            f"{t_run * 1e3:.2f} ms/step (first step included)")
        check_quarters(dist, f"ludwig dist halo={halo}")
        check_quarters(q, f"ludwig q halo={halo}")
        for name, got in (("dist", dist), ("q", q)):
            err = rel_l2(np.asarray(got), getattr(ref, name).to_numpy())
            log(f"  halo={halo} {name}: rel-L2 vs one device {err:.3e} "
                f"(limit {LUDWIG_REL_L2:.0e})")
            require(err <= LUDWIG_REL_L2,
                    f"sharded ludwig halo={halo} {name}: rel-L2 {err:.3e}")

    lat = MILC_LATTICE
    mcfg = milc_config(lat, target)
    log(f"Sharded MILC Wilson CG {lat} on a 2x2 mesh")
    u, b = milc.init_problem(mcfg, seed=seed)
    solve_1, t_c = timed_compile(functools.partial(milc.solve, mcfg), u, b)
    ref = solve_1(u, b)
    log(f"  one-device reference: compile {t_c:.1f} s, "
        f"{int(ref.iterations)} iterations")
    dom = milc.make_domain(mcfg, mesh, ("x", "y", None, None))
    sh = dom.sharding()
    u_nd = jax.device_put(jnp.asarray(u.to_numpy()), sh)
    b_nd = jax.device_put(jnp.asarray(b.to_numpy()), sh)
    solver, t_c = timed_compile(
        milc.make_sharded_solver(mcfg, dom, halo="pre"), u_nd, b_nd)
    fused_launches("milc sharded")
    t0 = time.perf_counter()
    x_nd, iters, resid = solver(u_nd, b_nd)
    jax.block_until_ready(x_nd)
    t_solve = time.perf_counter() - t0
    log(f"  smoke timing: compile {t_c:.1f} s, solve {t_solve:.2f} s, "
        f"{int(iters)} iterations, residual {float(resid):.3e}")
    check_quarters(x_nd, "milc x")
    err = rel_l2(np.asarray(x_nd), ref.x.to_numpy())
    log(f"  solution rel-L2 vs one device {err:.3e} "
        f"(limit {SOLVE_REL_L2:.0e}); iterations {int(iters)} / "
        f"{int(ref.iterations)}")
    require(err <= SOLVE_REL_L2, f"sharded milc differs: rel-L2 {err:.3e}")
    require(abs(int(iters) - int(ref.iterations))
            <= max(2, int(ref.iterations) // 100),
            f"sharded milc iterations {int(iters)} vs {int(ref.iterations)}")


# -- driver --------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: the sharded phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache

    cache = setup_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    count = len(jax.devices())
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {count}", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{count}; "
        f"compile cache: {cache}")

    from repro.core import TargetConfig, telemetry

    require(not TargetConfig("pallas").resolved_interpret(),
            "the pallas engine resolves to interpret mode on this device")
    telemetry.enable()
    if args.chips == 4:
        phases = [("sharded", lambda: sharded_phase(args.seed))]
    else:
        shared = {}
        phases = [
            ("ludwig", lambda: ludwig_phase(args.seed)),
            ("milc", lambda: shared.update(u=milc_phase(args.seed))),
            ("serve", lambda: serve_phase(args.seed, shared.get("u"))),
        ]
    # every phase runs even after one fails, so one run reports them all
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:  # noqa: BLE001 - reported, then fails the run
            traceback.print_exc()
            failed.append(name)
            log(f"phase {name}: FAILED ({type(e).__name__}: {e})")
            continue
        log(f"phase {name}: passed in {time.perf_counter() - t0:.1f} s "
            f"(smoke timing, compile included)")
    if failed:
        print(f"chip_smoke: FAILED phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # noqa: BLE001 - any failure fails the smoke run
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
