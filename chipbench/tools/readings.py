"""The readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/tools/readings.py --workload <cell> --seconds 5 \
        --seeds 1 2 3 ... --control-seeds 1 2 3 [--fault-seeds 1 2 3]

For each seed, in one process (one compile): the cell's set-up, a short
window at the cell's own size and load, and the comparison with the
reference: the program's readings.  For each control seed, also the
control's readings: the reference computed in bfloat16 put in the
program's place.  For each fault seed, the readings of the faults the
entry plants (where it has a ``faults`` method).  One JSON line per seed;
the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import run as harness  # noqa: E402


def readings(workload, seeds, control_seeds, seconds, spec=None,
             require_tpu=True, fault_seeds=()):
    harness._prepare_environment()
    import importlib

    spec = spec or harness.load_cell(workload)
    devices = harness._devices(int(spec["cell"]["chips"]), require_tpu)
    entry = importlib.import_module(
        f"chipbench.entries.{spec['config']['entry']}")
    out = []
    for seed in sorted(set(seeds) | set(control_seeds) | set(fault_seeds)):
        ctx = types.SimpleNamespace(
            seed=int(seed), config=spec["config"], traffic=spec["traffic"],
            devices=devices, tracing=False,
            span=harness._span_factory(False))
        t0 = time.perf_counter()
        session = entry.prepare(ctx)
        rec = session.run_window(seconds)
        session.release()
        gc.collect()
        row = {"seed": seed, "attempted": rec["attempted"],
               "failed": rec["failed"],
               "program": {n: v for n, v, _ in session.check()}}
        if seed in control_seeds:
            row["control"] = session.control()
        if seed in fault_seeds:
            row["faults"] = session.faults()
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        out.append(row)
        del session
        gc.collect()
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=())
    args = ap.parse_args()
    readings(args.workload, args.seeds, args.control_seeds, args.seconds,
             fault_seeds=args.fault_seeds)


if __name__ == "__main__":
    main()
