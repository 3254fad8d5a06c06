"""Ludwig LC-LB timesteps on one chip: the jitted ``apps.ludwig.driver.step``.

Traffic (closed loop): back-to-back timesteps, the host blocking every
``sync_every`` steps as a run that writes diagnostics at that interval
would.  ``correct``: ``check_steps`` steps of the window, drawn from the
seed before it opens among its first ``check_within`` steps, are
recomputed by the plain reference from the same input state; the largest
gap of ``dist`` (relative to the largest change the step makes to it) and
of ``q`` (relative to its largest value) is held to the configuration's
limits.
"""

from __future__ import annotations

import functools
import time

import jax
import numpy as np

from chipbench import compare, inputs

PHYSICS = ("a0", "gamma", "kappa", "gamma_rot", "xi", "tau", "dt")


def physics(config: dict) -> dict:
    return {k: float(config["physics"][k]) for k in PHYSICS}


class LudwigLoop:
    """The closed loop of timesteps: ``advance(state) -> state`` is the
    program's step."""

    def __init__(self, ctx, advance, state, to_arrays):
        self.ctx, self.advance, self.state = ctx, advance, state
        self.to_arrays = to_arrays        # state -> (dist_nd, q_nd)
        self.traffic = tr = ctx.traffic
        self.checked = set(compare.drawn(ctx.seed, int(tr["check_steps"]),
                                         int(tr["check_within"])))
        self.kept = []                    # (before, after) of those steps
        for _ in range(int(self.traffic["warmup_steps"])):
            self.state = self.advance(self.state)
        jax.block_until_ready(self.state)

    def run_window(self, seconds: float) -> dict:
        span, every = self.ctx.span, int(self.traffic["sync_every"])
        steps = 0
        t0 = time.perf_counter()
        while True:
            with span("dispatch"):
                new = self.advance(self.state)
            if steps in self.checked:
                self.kept.append((self.state, new))
            self.state = new
            steps += 1
            if steps % every == 0:
                with span("sync"):
                    jax.block_until_ready(self.state)
                if (time.perf_counter() - t0 >= seconds
                        and len(self.kept) == len(self.checked)):
                    break
        window_s = time.perf_counter() - t0
        return {"steps": steps, "window_s": window_s, "attempted": steps,
                "failed": 0,
                "sites_per_chip": self.sites // len(self.ctx.devices)}

    @property
    def sites(self) -> int:
        return int(np.prod(self.ctx.config["lattice"]))

    def release(self) -> None:
        self.state = None

    def _errors(self, produce) -> list:
        """The numbers compared on each kept step, where ``produce(d, q,
        d_out, q_out)`` gives what stands in the program's place."""
        params = physics(self.ctx.config)
        slab = int(self.ctx.config["check"]["slab"])
        dev = self.ctx.devices[0]
        errs = []
        for before, after in self.kept:
            d, q = self.to_arrays(before)
            got = produce(d, q, *self.to_arrays(after))
            errs.append(compare.ludwig_step_errors(d, q, *got, params,
                                                   slab=slab, device=dev))
        return errs

    def check(self) -> list:
        errs = self._errors(lambda d, q, d_out, q_out: (d_out, q_out))
        limits = self.ctx.config["check"]["limits"]
        return [(name, max(e[name] for e in errs), float(limits[name]))
                for name in ("dist_err", "q_err")]

    def _reference(self, **kw):
        params = physics(self.ctx.config)
        slab = int(self.ctx.config["check"]["slab"])
        return lambda d, q, d_out, q_out: compare.ludwig_reference_step(
            d, q, params, slab=slab, device=self.ctx.devices[0], **kw)

    def control(self) -> dict:
        """The readings of the control: the reference computed in bfloat16
        put in the program's place, on the same sampled steps."""
        import jax.numpy as jnp

        errs = self._errors(self._reference(dtype=jnp.bfloat16))
        return {name: min(e[name] for e in errs)
                for name in ("dist_err", "q_err")}

    def faults(self) -> dict:
        """The readings of planted faults on the same sampled steps, the
        smallest per number: the program's step with its ``dist`` left
        unchanged, and the float32 reference with streaming skipped."""
        import jax.numpy as jnp

        planted = {
            "dist_unchanged": lambda d, q, d_out, q_out: (d, q_out),
            "no_streaming": self._reference(dtype=jnp.float32, stream=False),
        }
        return {fault: {name: min(e[name] for e in self._errors(produce))
                        for name in ("dist_err", "q_err")}
                for fault, produce in planted.items()}


def prepare(ctx):
    from repro.apps.ludwig import driver as lud
    from repro.core import SOA, Field, TargetConfig

    cfg = ctx.config
    lat = tuple(int(n) for n in cfg["lattice"])
    lcfg = lud.LudwigConfig(lattice=lat, target=TargetConfig(cfg["engine"]),
                            **physics(cfg))
    with jax.default_device(ctx.devices[0]):
        d, q = inputs.ludwig_state(inputs.key(ctx.seed, 0), lattice=lat,
                                    q_amp=float(cfg["q_amp"]))
    state = lud.LudwigState(dist=Field.from_canonical("dist", d, lat, SOA),
                            q=Field.from_canonical("q", q, lat, SOA))
    step = jax.jit(functools.partial(lud.step, cfg=lcfg))
    return LudwigLoop(ctx, step, state,
                      lambda s: (s.dist.canonical_nd(), s.q.canonical_nd()))
