"""Dry-run sweep driver: every (arch x shape x mesh) cell in a subprocess.

Subprocess isolation bounds host memory per cell and lets one failing cell
report an error row without killing the sweep.  Results append to a JSONL
file consumed by benchmarks/ and EXPERIMENTS.md.

  PYTHONPATH=src python -m repro.launch.sweep --out results/dryrun.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def cells(meshes=("single", "multi")):
    from repro.configs import ARCH_IDS
    from repro.configs.base import LM_SHAPES

    for mesh in meshes:
        for arch in ARCH_IDS:
            for shape in LM_SHAPES:
                yield arch, shape.name, mesh


def run_cell(arch, shape, mesh, out, timeout=1800):
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", arch, "--shape", shape, "--mesh", mesh, "--out", out]
    if mesh == "multi":
        # multi-pod pass is the shardability proof; the roofline table is
        # single-pod only (spec), so skip the L1/L2 cost probes here.
        cmd.append("--no-exact-loops")
    # the dry run compiles on fake host devices: a child on the CPU never
    # competes for a chip
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, env=env)
        ok = proc.returncode == 0
        if not ok:
            # dryrun already appended an error row unless it crashed hard
            tail = (proc.stdout + proc.stderr)[-2000:]
            if '"status"' not in proc.stdout:
                with open(out, "a") as f:
                    f.write(json.dumps({
                        "arch": arch, "shape": shape, "mesh": mesh,
                        "status": "crash", "error": tail}) + "\n")
    except subprocess.TimeoutExpired:
        with open(out, "a") as f:
            f.write(json.dumps({"arch": arch, "shape": shape, "mesh": mesh,
                                "status": "timeout"}) + "\n")
        ok = False
    return ok, time.time() - t0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--only-arch", default=None)
    args = ap.parse_args(argv)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    done = set()
    if os.path.exists(args.out):
        for line in open(args.out):
            try:
                r = json.loads(line)
                if r.get("status") in ("ok", "skipped"):
                    done.add((r["arch"], r["shape"], r["mesh"]))
            except Exception:
                pass

    meshes = tuple(args.mesh.split(","))
    todo = [c for c in cells(meshes) if c not in done
            and (args.only_arch is None or c[0] == args.only_arch)]
    print(f"{len(todo)} cells to run ({len(done)} already done)")
    for i, (arch, shape, mesh) in enumerate(todo):
        ok, dt = run_cell(arch, shape, mesh, args.out)
        print(f"[{i+1}/{len(todo)}] {arch} x {shape} x {mesh}: "
              f"{'OK' if ok else 'FAIL'} ({dt:.0f}s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
