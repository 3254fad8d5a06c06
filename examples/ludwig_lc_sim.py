"""Ludwig liquid-crystal simulation: the paper's primary application.

Runs a nematic quench (random Q, gamma = 3 > 2.7 so the nematic phase is
stable) coupled to the LB fluid, printing conservation + free-energy
diagnostics; optionally compares the jnp and pallas engines step-for-step.

    PYTHONPATH=src python examples/ludwig_lc_sim.py [--steps 50] [--check-engines]
"""

import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import setup_compile_cache
from repro.core import TargetConfig
from repro.apps.ludwig import LudwigConfig, init_state, step
from repro.apps.ludwig.driver import diagnostics


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lattice", type=int, nargs=3, default=[16, 16, 16])
    ap.add_argument("--gamma", type=float, default=3.0)
    ap.add_argument("--check-engines", action="store_true")
    args = ap.parse_args()

    cfg = LudwigConfig(lattice=tuple(args.lattice), gamma=args.gamma,
                       target=TargetConfig("jnp"))
    state = init_state(cfg, seed=0, q_amp=2e-2)
    jstep = jax.jit(step, static_argnums=1)

    d0 = diagnostics(state, cfg)
    print(f"step      mass        free_energy     |momentum|")
    t0 = time.perf_counter()
    for i in range(args.steps):
        state = jstep(state, cfg)
        if (i + 1) % max(1, args.steps // 10) == 0:
            d = diagnostics(state, cfg)
            mom = float(np.abs(np.asarray(d["momentum"])).max())
            print(f"{i+1:5d}  {float(d['mass']):12.4f}  "
                  f"{float(d['free_energy']):+.6e}  {mom:.2e}")
    dt = time.perf_counter() - t0
    nsites = int(np.prod(cfg.lattice))
    dev = jax.devices()[0]
    print(f"\n{args.steps} steps, {dt/args.steps*1e3:.1f} ms/step "
          f"({nsites*args.steps/dt/1e6:.1f} Msite-updates/s on "
          f"{dev.platform} {dev.device_kind}; a smoke timing, diagnostics "
          f"included)")
    d = diagnostics(state, cfg)
    assert abs(float(d["mass"]) - float(d0["mass"])) < 1e-2 * float(d0["mass"])
    print("mass conserved; free energy relaxed "
          f"{float(d0['free_energy']):+.3e} -> {float(d['free_energy']):+.3e}")

    if args.check_engines:
        cfgp = LudwigConfig(lattice=tuple(args.lattice), gamma=args.gamma,
                            target=TargetConfig("pallas", vvl=128))
        s_j = step(init_state(cfg, seed=0), cfg)
        s_p = step(init_state(cfgp, seed=0), cfgp)
        np.testing.assert_allclose(s_j.q.to_numpy(), s_p.q.to_numpy(),
                                   rtol=3e-5, atol=1e-7)
        print("jnp and pallas engines agree step-for-step (C1)")


if __name__ == "__main__":
    main()
