"""MILC Wilson-CG solves on one chip: the jitted ``apps.milc.driver.solve``.

Traffic (closed loop): back-to-back solves to the configuration's
tolerance, each synced, cycling through a pool of sources in an order
drawn from the seed.  The gauge field and the pool come from the
configuration's ``data_seed``, not from the run's: a deployment solves
many sources on one ensemble, and at this kappa the iteration count
follows the field and the source, so data drawn per seed would make each
seed a different amount of work.  Every seed solves
the same sources, in another order.  ``correct``: every solve of
the window is judged by its true residual ``|b - M x| / |b|`` under the
plain reference operator, and by the residual the solver reports, which
has to meet the configuration's ``tol`` (a solve that stops early fails
here even where its answer is close).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, inputs


def links(config, device):
    """The configuration's SU(3) links on ``device``, made from its
    ``data_seed``, their unitarity checked."""
    with jax.default_device(device):
        u = inputs.su3_links(inputs.key(int(config["data_seed"]), 1),
                             lattice=tuple(config["lattice"]),
                             hot=float(config["hot"]))
        viol = float(inputs.unitarity_violation(u))
    if not viol < 1e-5:
        raise RuntimeError(f"gauge links not unitary: {viol:.3e}")
    return u


class Solves:
    def __init__(self, ctx):
        from repro.apps.milc import driver as milc
        from repro.core import SOA, Field, TargetConfig

        cfg, tr = ctx.config, ctx.traffic
        self.ctx = ctx
        self.lat = tuple(int(n) for n in cfg["lattice"])
        dev = ctx.devices[0]
        self.mcfg = milc.MilcConfig(
            lattice=self.lat, kappa=float(cfg["kappa"]), tol=float(cfg["tol"]),
            max_iter=int(cfg["max_iter"]), hot=float(cfg["hot"]),
            target=TargetConfig(cfg["engine"]))
        self.u = links(cfg, dev)
        with jax.default_device(dev):
            self.pool = inputs.spinors(inputs.key(int(cfg["data_seed"]), 2),
                                       lattice=self.lat, count=int(tr["pool"]))
        self.uF = Field.from_canonical("u", self.u, self.lat, SOA)
        self.sources = [Field.from_canonical("b", self.pool[i], self.lat, SOA)
                        for i in range(int(tr["pool"]))]
        self.order = np.random.default_rng([ctx.seed, 2]).permutation(
            int(tr["pool"]))
        self.solve = jax.jit(functools.partial(milc.solve, self.mcfg))
        # warm-up: the same program on a zero source, which stops before
        # its first iteration (0/0 is not above tol), so set-up pays the
        # compile and not a whole solve
        zero = self.sources[0].with_data(jnp.zeros_like(self.sources[0].data))
        jax.block_until_ready(self.solve(self.uF, zero).x.data)
        self.kept = []                    # (source index, x, reported)

    def run_window(self, seconds: float) -> dict:
        span = self.ctx.span
        iters, n = [], 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            i = int(self.order[n % len(self.order)])
            with span("dispatch"):
                res = self.solve(self.uF, self.sources[i])
            with span("sync"):
                iters.append(int(res.iterations))
            self.kept.append((i, res.x, res.residual))
            n += 1
        window_s = time.perf_counter() - t0
        failed = sum(it >= self.mcfg.max_iter for it in iters)
        return {"solves": n, "window_s": window_s, "iterations": iters,
                "attempted": n, "failed": failed,
                "sites_per_chip": int(np.prod(self.lat))}

    def release(self) -> None:
        self.sources = None
        self.uF = None

    def check(self) -> list:
        kappa = self.mcfg.kappa
        worst = max(compare.wilson_residual(
            self.u, self.pool[i], x.canonical(), kappa, self.lat)
            for i, x, _ in self.kept)
        reported = max(float(r) for _, _, r in self.kept)
        return [("residual", worst,
                 float(self.ctx.config["check"]["limits"]["residual"])),
                ("reported_rr", reported, self.mcfg.tol)]

    def control(self) -> dict:
        """The reference CG in bfloat16 in the program's place, on the
        sampled solves' sources: the smallest residual it reaches."""
        out = []
        n = int(self.ctx.config["check"]["control_solves"])
        for i, _, _ in self.kept[:n]:
            x, _ = compare.wilson_control_solution(
                self.u, self.pool[i], self.mcfg.kappa, self.lat,
                tol=self.mcfg.tol,
                max_iter=int(self.ctx.config["check"]["control_max_iter"]))
            out.append(compare.wilson_residual(self.u, self.pool[i], x,
                                               self.mcfg.kappa, self.lat))
        return {"residual": min(out)}


def prepare(ctx):
    return Solves(ctx)
