"""Benchmark aggregator: one module per paper table/figure.

  table1_ridge    Table 1 ridge points (+ TPU v5e)
  fig3_kernels    per-kernel time decomposition + layout/VVL sweep
  fig4_bandwidth  OI + achieved-bandwidth fraction per kernel
  fig5_scaling    strong-scaling model (Titan/ARCHER analogue on v5e)
  lm_roofline     assigned-architecture roofline table from the dry-run

Prints ``name,us_per_call,derived`` CSV.  A table that fails prints an
``ERROR=`` row, the remaining tables still run, and the exit status is 1.
"""

from __future__ import annotations

import sys
import traceback


def main() -> int:
    from . import fig3_kernels, fig4_bandwidth, fig5_scaling, lm_roofline, \
        table1_ridge

    print("name,us_per_call,derived")
    failed = []
    for mod in (table1_ridge, fig3_kernels, fig4_bandwidth, fig5_scaling,
                lm_roofline):
        try:
            mod.main()
        except Exception as e:  # a failing table should not hide the rest
            failed.append(mod.__name__)
            print(f"{mod.__name__},0.0,ERROR={type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        print(f"failed tables: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
