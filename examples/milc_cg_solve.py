"""MILC Wilson-Dirac CG inversion: the paper's second application
(UEABS test case).

    PYTHONPATH=src python examples/milc_cg_solve.py [--lattice 8 8 8 8]
"""

import argparse
import time

from repro.launch.compile_cache import setup_compile_cache
from repro.apps.milc import MilcConfig, init_problem, solve
from repro.apps.milc.driver import residual_check


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--lattice", type=int, nargs=4, default=[8, 8, 8, 8])
    ap.add_argument("--kappa", type=float, default=0.12)
    ap.add_argument("--tol", type=float, default=1e-10)
    ap.add_argument("--hot", type=float, default=0.6)
    args = ap.parse_args()

    cfg = MilcConfig(lattice=tuple(args.lattice), kappa=args.kappa,
                     tol=args.tol, hot=args.hot, max_iter=2000)
    print(f"lattice {cfg.lattice}, kappa={cfg.kappa}, hot={cfg.hot}")
    u, b = init_problem(cfg, seed=0)
    t0 = time.perf_counter()
    res = solve(cfg, u, b)
    dt = time.perf_counter() - t0
    iters = int(res.iterations)
    print(f"CG converged in {iters} iterations "
          f"({dt:.2f}s, {dt/max(iters,1)*1e3:.1f} ms/iter)")
    print(f"normal-equation residual: {float(res.residual):.3e}")
    rc = residual_check(cfg, u, b, res.x)
    print(f"independent |Mx-b|/|b| = {rc:.3e}")
    assert rc < 1e-3
    print("solution verified")


if __name__ == "__main__":
    main()
