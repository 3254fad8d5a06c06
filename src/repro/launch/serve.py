"""Production serving launcher: batched LM decode and batched lattice-solve
serving.

LM path (``--arch``): batched autoregressive decode against KV/state caches,
as before.

Solve path (``--solve``): a shape-bucketed request scheduler for
multi-simulation serving.  Requests (source Fields) are admitted into
per-lattice-shape queues; each bucket owns a fixed number of batch *slots*
and replays ONE jitted convergence-masked batched CG iteration
(train.serve_step.build_cg_serve_step) over all of its slots — one fused
operator pallas_call + one fused masked-update pallas_call per tick,
regardless of how many requests are packed in.  Completed solves are
drained continuously: a converged (or max_iter'd) slot is harvested and
refilled from the queue at the next tick, while in-flight slots are
untouched — the masking is a bitwise select, so every request's
trajectory is identical to a dedicated single-lattice solve
(tests/test_serve.py asserts bit-identity against apps.milc.driver.solve).

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --smoke-arch
  PYTHONPATH=src python -m repro.launch.serve --solve --requests 6 --slots 2
"""

import argparse
import dataclasses
import time
from collections import deque
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import setup_compile_cache
from repro.core import BatchedField, Field, TargetConfig, telemetry


@dataclasses.dataclass(frozen=True)
class SolveRequest:
    """One inversion request: solve M x = b for the bucket's operator."""
    rid: int
    b: Field


@dataclasses.dataclass(frozen=True)
class SolveOutcome:
    rid: int
    x: Field
    iterations: int
    residual: float


class _Bucket:
    """All state for one lattice shape: the operator, a FIFO admission
    queue, ``slots`` batch slots and the jitted masked-iteration step."""

    def __init__(self, u: Field, kappa: float, config: TargetConfig,
                 slots: int, tol: float, max_iter: int,
                 refine_every: int = 0):
        from repro.apps.milc.cg import make_wilson_op
        from repro.train.serve_step import build_cg_serve_step

        self.u, self.kappa, self.config = u, float(kappa), config
        self.tol, self.max_iter, self.slots = tol, max_iter, slots
        self.refine_every = int(refine_every)
        # refinement recomputes residuals against the high-precision (policy
        # free) operator, so admission must use the same reference operator
        _, self.apply_mdag, _ = make_wilson_op(u, self.kappa, config)
        self.step = build_cg_serve_step(u, self.kappa, config, tol=tol,
                                        max_iter=max_iter,
                                        refine_every=self.refine_every)
        self.queue: deque = deque()
        self.slot_rid: list = [None] * slots
        self.state = None  # lazily shaped from the first admitted source
        self.rhs = None    # per-slot rhs stack (kept for refinement restarts)
        self.iterations_run = 0
        # telemetry: per-shape-bucket metric names + in-flight request spans
        self.label = "x".join(map(str, u.lattice))
        self._req_spans: Dict[int, object] = {}

    # -- slot state ------------------------------------------------------

    def _init_state(self, proto: Field):
        from repro.apps.milc.cg import BatchedCGState

        z = BatchedField.zeros("x", self.slots, proto.ncomp, proto.lattice,
                               proto.layout, dtype=proto.dtype)
        v = jnp.zeros((self.slots,), proto.dtype)
        self.state = BatchedCGState(x=z, r=z, p=z, rr=v, b2=v,
                                    it=jnp.zeros((self.slots,), jnp.int32))
        self.rhs = z

    def _admit(self, slot: int, req: SolveRequest):
        """Pack a request into a free slot: rhs and |rhs|^2 come through the
        single-lattice M^dag / dot path (the exact values a dedicated
        ``cg`` solve would start from), then land in the batch via
        per-slot .at[slot].set writes — in-flight slots' bits never move."""
        from repro.apps.milc.cg import BatchedCGState, dot

        rhs = self.apply_mdag(req.b)
        if self.state is None:
            self._init_state(rhs)
        b2 = dot(rhs, rhs, self.config)
        st = self.state
        x0 = rhs.with_data(jnp.zeros_like(rhs.data))
        self.state = BatchedCGState(
            x=st.x.with_element(slot, x0),
            r=st.r.with_element(slot, rhs),
            p=st.p.with_element(slot, rhs),
            rr=st.rr.at[slot].set(b2),
            b2=st.b2.at[slot].set(b2),
            it=st.it.at[slot].set(0),
        )
        self.rhs = self.rhs.with_element(slot, rhs)
        self.slot_rid[slot] = req.rid
        telemetry.inc("serve.admitted")
        # admission->harvest latency span, closed by _harvest; admit_tick
        # is the bucket tick count BEFORE this tick's masked iteration, so
        # harvest_tick - admit_tick == the request's active iterations
        self._req_spans[req.rid] = telemetry.begin_span(
            "serve/request", rid=req.rid, bucket=self.label, slot=slot,
            admit_tick=self.iterations_run)

    def _harvest(self, slot: int) -> SolveOutcome:
        st = self.state
        out = SolveOutcome(
            rid=self.slot_rid[slot],
            x=st.x.element(slot),
            iterations=int(st.it[slot]),
            residual=float(st.rr[slot] / st.b2[slot]),
        )
        self.slot_rid[slot] = None
        telemetry.inc("serve.harvested")
        rspan = self._req_spans.pop(out.rid, None)
        if rspan is not None:
            rspan.end(harvest_tick=self.iterations_run,
                      iterations=out.iterations, residual=out.residual)
        return out

    # -- scheduler tick --------------------------------------------------

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slot_rid)

    def tick(self) -> Dict[int, SolveOutcome]:
        """Admit into free slots, run one masked batched iteration, drain
        finished slots.  Returns {rid: outcome} for requests that completed
        this tick."""
        from repro.apps.milc.cg import batched_cg_active

        # queue depth sampled before admission, occupancy after: the
        # oracle drain test replays exactly this schedule
        telemetry.sample(f"serve.queue_depth.{self.label}", len(self.queue))
        for slot in range(self.slots):
            if self.slot_rid[slot] is None and self.queue:
                self._admit(slot, self.queue.popleft())
        occupied = sum(r is not None for r in self.slot_rid)
        telemetry.sample(f"serve.slot_occupancy.{self.label}", occupied)
        if not occupied:
            return {}
        with telemetry.span("serve/tick", bucket=self.label,
                            tick=self.iterations_run + 1, occupied=occupied):
            if self.refine_every > 0:
                self.state = self.step(self.state, self.rhs)
            else:
                self.state = self.step(self.state)
        self.iterations_run += 1
        telemetry.inc("serve.ticks")
        telemetry.inc(f"serve.ticks.{self.label}")
        # the host waits here for the tick's device work: its own span,
        # so a served trace tells waiting from dispatch
        with telemetry.span("serve/sync", bucket=self.label,
                            tick=self.iterations_run):
            act = np.asarray(
                batched_cg_active(self.state, tol=self.tol,
                                  max_iter=self.max_iter))
        done = {}
        for slot in range(self.slots):
            if self.slot_rid[slot] is not None and not act[slot]:
                out = self._harvest(slot)
                done[out.rid] = out
        return done


class SolveServer:
    """Shape-bucketed batched solve scheduler.

    ``register(u, kappa)`` declares the operator for requests on
    ``u.lattice``; ``submit`` enqueues sources; ``run`` drains every queue
    to completion, interleaving ticks across buckets so mixed-shape
    request streams make progress together.  Each bucket packs up to
    ``slots`` heterogeneous requests into one batched launch chain."""

    def __init__(self, config: TargetConfig, *, slots: int = 4,
                 tol: float = 1e-8, max_iter: int = 500,
                 refine_every: int = 0):
        self.config = config
        self.slots, self.tol, self.max_iter = slots, tol, max_iter
        self.refine_every = int(refine_every)
        self.buckets: Dict[Tuple[int, ...], _Bucket] = {}

    def register(self, u: Field, kappa: float,
                 slots: Optional[int] = None) -> None:
        """Declare the gauge field + kappa serving ``u.lattice``-shaped
        requests (one operator per shape bucket)."""
        self.buckets[u.lattice] = _Bucket(
            u, kappa, self.config, slots or self.slots, self.tol,
            self.max_iter, self.refine_every)

    def submit(self, req: SolveRequest) -> None:
        if req.b.lattice not in self.buckets:
            raise KeyError(
                f"no operator registered for lattice {req.b.lattice}; "
                f"known: {sorted(self.buckets)}")
        self.buckets[req.b.lattice].queue.append(req)

    def run(self) -> Dict[int, SolveOutcome]:
        """Tick all buckets round-robin until every queue and slot is
        drained.  Returns {rid: SolveOutcome}."""
        results: Dict[int, SolveOutcome] = {}
        with telemetry.span("serve/drain", buckets=len(self.buckets)) as ds:
            while any(b.busy for b in self.buckets.values()):
                for bucket in self.buckets.values():
                    if bucket.busy:
                        results.update(bucket.tick())
            ds.set(requests=len(results))
        return results


# -- CLI -------------------------------------------------------------------

def _main_decode(args):
    from repro.configs import get_arch
    from repro.models import init_params
    from repro.train.serve_step import build_serve_step, generate

    cfg = get_arch(args.arch, smoke=args.smoke_arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(1, cfg.vocab, (args.batch, 8)),
                          jnp.int32)
    jit_step = jax.jit(build_serve_step(cfg))
    t0 = time.perf_counter()
    out = generate(params, cfg, prompts, steps=args.steps,
                   s_max=8 + args.steps + 8, jit_step=jit_step)
    dt = time.perf_counter() - t0
    print(f"{args.batch * args.steps} tokens in {dt:.2f}s")
    print(np.asarray(out)[0].tolist())


def _main_solve(args):
    from repro.apps.milc import driver, fields

    cfg = driver.MilcConfig(
        lattice=(4, 4, 4, 8), kappa=0.10, tol=1e-8, max_iter=args.steps,
        target=TargetConfig(args.engine, vvl=128,
                            plan_policy=args.plan_policy))
    server = SolveServer(cfg.target, slots=args.slots, tol=cfg.tol,
                         max_iter=cfg.max_iter,
                         refine_every=args.refine_every)
    shapes = [(4, 4, 4, 8), (4, 4, 8, 8)]
    for i, lat in enumerate(shapes):
        u = Field.from_numpy(
            "u", fields.random_su3_gauge(lat, seed=i, hot=cfg.hot), lat,
            cfg.layout)
        server.register(u, cfg.kappa)
        for j in range(args.requests // len(shapes)):
            b = Field.from_numpy(
                "b", fields.random_spinor(lat, seed=100 + 10 * i + j), lat,
                cfg.layout)
            server.submit(SolveRequest(rid=10 * i + j, b=b))
    t0 = time.perf_counter()
    results = server.run()
    dt = time.perf_counter() - t0
    ticks = sum(b.iterations_run for b in server.buckets.values())
    print(f"{len(results)} solves in {dt:.2f}s "
          f"({ticks} batched iterations across {len(server.buckets)} buckets)")
    for rid in sorted(results):
        r = results[rid]
        print(f"  rid={rid} lattice={r.x.lattice} iters={r.iterations} "
              f"residual={r.residual:.3e}")


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=None, default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--smoke-arch", action="store_true")
    ap.add_argument("--solve", action="store_true",
                    help="serve batched lattice solves instead of LM decode")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--engine", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--refine-every", type=int, default=0,
                    help="reliable-update period for mixed-precision "
                         "serving: every N active iterations a slot's "
                         "residual is recomputed exactly (b - A x) and "
                         "its search direction restarted; 0 disables")
    ap.add_argument("--plan-policy", default="default",
                    choices=["default", "tuned"],
                    help="lowering-plan policy for serving launches: "
                         "'tuned' picks persisted autotune winners "
                         "(rsplit split reductions included) from the "
                         "TARGETDP_TUNE_PATH table")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable telemetry for the run and write a Chrome "
                         "trace (load at ui.perfetto.dev) to PATH; also "
                         "prints the telemetry report snapshot")
    args = ap.parse_args()
    if args.trace:
        telemetry.enable()
        telemetry.configure_logging()
    if args.solve:
        _main_solve(args)
    else:
        if args.arch is None:
            ap.error("--arch is required unless --solve is given")
        _main_decode(args)
    if args.trace:
        print(telemetry.format_report())
        print(f"chrome trace: {telemetry.export_chrome_trace(args.trace)}")


if __name__ == "__main__":
    main()
