"""Ludwig LC-LB timesteps decomposed over a mesh of chips: the jitted
``apps.ludwig.driver.make_sharded_step``.

The global lattice is split in the dimensions the configuration's
``dim_axes`` names over a ``mesh`` of the run's chips; every step
exchanges halos with the neighbours (``core/halo.py``) under the
configuration's ``halo`` schedule.  The state is the pair of canonical
``(ncomp, X, Y, Z)`` global arrays, sharded per ``Domain.spec()``, made
from ``--seed`` as the one-chip cell makes its own.

Traffic and ``correct`` are the one-chip cell's (``ludwig_step``): the
kept steps are compared with the plain reference slab by slab, each slab
gathered onto the first chip.  The record also holds
``halo_bytes_per_step``: the ``halo.bytes`` counter over the step's one
trace, the bytes each chip sends per step.  A program without that
counter leaves the key out.
"""

from __future__ import annotations

import jax
import numpy as np

from chipbench import inputs
from chipbench.entries.ludwig_step import LudwigLoop, physics

HALO_WIDTH = 2   # the sharded step's widest exchange (q, for grad and div)


class ShardedLoop(LudwigLoop):
    """The closed loop of global timesteps, and the exchange volume that
    one trace of the step counted."""

    def __init__(self, ctx, make_step, state):
        from repro.core import telemetry

        self.make_step = make_step
        step = make_step()
        # lower() traces the step once; the jitted call reuses that trace
        before = telemetry.counters("halo.")
        step.lower(*state)
        after = telemetry.counters("halo.")
        self.halo_bytes = (after["halo.bytes"] - before.get("halo.bytes", 0)
                           if "halo.bytes" in after else None)
        super().__init__(ctx, lambda s: step(*s), state, lambda s: s)

    def run_window(self, seconds: float) -> dict:
        rec = super().run_window(seconds)
        if self.halo_bytes is not None:
            rec["halo_bytes_per_step"] = self.halo_bytes
        return rec

    def _no_exchange_step(self):
        """The sharded step traced with every halo exchange returning its
        input: each chip's halos keep the wrap of its own sub-domain."""
        from repro.core import halo

        real = halo.exchange
        halo.exchange = lambda x, decomposed, *, width: x
        try:
            return self.make_step().lower(*self.kept[0][0]).compile()
        finally:
            halo.exchange = real

    def faults(self) -> dict:
        """The one-chip cell's planted faults, and ``no_exchange``: the
        step with its halo exchanges left out."""
        out = super().faults()
        broken = self._no_exchange_step()
        errs = self._errors(lambda d, q, d_out, q_out: broken(d, q))
        out["no_exchange"] = {name: min(e[name] for e in errs)
                              for name in ("dist_err", "q_err")}
        return out


def prepare(ctx):
    from jax.sharding import Mesh

    from repro.apps.ludwig import driver as lud
    from repro.core import TargetConfig
    from repro.lattice import Domain

    cfg = ctx.config
    lat = tuple(int(n) for n in cfg["lattice"])
    mesh_shape = tuple(int(n) for n in cfg["mesh"])
    dim_axes = tuple(cfg["dim_axes"])
    axis_names = tuple(a for a in dim_axes if a is not None)
    mesh = Mesh(np.asarray(ctx.devices).reshape(mesh_shape), axis_names)
    dom = Domain(global_shape=lat, mesh=mesh, dim_axes=dim_axes,
                 halo=HALO_WIDTH)
    lcfg = lud.LudwigConfig(lattice=lat, target=TargetConfig(cfg["engine"]),
                            **physics(cfg))
    with jax.default_device(ctx.devices[0]):
        d, q = inputs.ludwig_state(inputs.key(ctx.seed, 0), lattice=lat,
                                    q_amp=float(cfg["q_amp"]))
    state = jax.device_put((d, q), dom.sharding())
    del d, q
    return ShardedLoop(
        ctx, lambda: lud.make_sharded_step(lcfg, dom, halo=cfg["halo"]),
        state)
