"""Run one cell traced, and split its device time by the program layer that
issued each op (``chipbench/scopes.py``).

    python3 chipbench/tools/scoped_run.py --workload <cell> --seed <n> \
        [--seconds <s>]

The run is the harness's own ``--trace 1`` run (``chipbench/run.py``); the
scope reduction is taken from the same ``.xplane.pb`` before the harness
removes it.  Prints one JSON object: the run's result line, the scope
reduction (seconds per bucket, per leaf scope, the top unscoped ops with
their source line, and the seconds the reduction took) and each bucket's
share of device busy time in percent.  The benchmark's own runs never run
this; it is how the per-layer shares in PERF.md are read.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import run as harness  # noqa: E402
from chipbench import scopes, trace  # noqa: E402


def with_scopes(reduce_dir, into: dict):
    """``reduce_dir`` that also puts the scope reduction of the trace, and
    the buckets' shares of busy time, into ``into``; what it returns is
    left as it was."""

    def wrapped(directory, n_devices):
        out = reduce_dir(directory, n_devices)
        (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True)
        sc = scopes.reduce_file(path, trace.load(path)["spans"], n_devices)
        busy = out["busy_s"]
        into["scopes"] = sc
        into["shares"] = {b: (sc[f"{b}_s"] / busy * 100.0 if busy > 0
                              else None) for b in scopes.BUCKETS}
        into["xla_share"] = out["xla_s"] / busy * 100.0 if busy > 0 else None
        return out

    return wrapped


def scoped_run(workload, seed, seconds, **kw) -> dict:
    """One traced run of the cell through the harness, with the scopes."""
    into: dict = {}
    plain = trace.reduce_dir
    trace.reduce_dir = with_scopes(plain, into)
    try:
        into["result"] = harness.run_cell(workload, seed, seconds, True, **kw)
    finally:
        trace.reduce_dir = plain
    return into


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)
    try:
        out = scoped_run(args.workload, args.seed, args.seconds)
    except harness.NoDevice as e:
        print(f"scoped_run: {e}; nothing was measured", file=sys.stderr)
        return 3
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
