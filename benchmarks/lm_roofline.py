"""LM architecture roofline table from the dry-run sweep results.

Reads results/dryrun.jsonl (produced by repro.launch.sweep) and prints the
per-cell three-term roofline + dominant bottleneck + useful-flops ratio —
the §Roofline table of EXPERIMENTS.md in CSV form.  Run the sweep first;
rows missing from the file are reported as such rather than recomputed
(a full sweep is ~1h of lowering on this host).
"""

from __future__ import annotations

import json
import os

from .common import csv_row
from repro.launch.compile_cache import setup_compile_cache

RESULTS = os.environ.get("DRYRUN_RESULTS", "results/dryrun.jsonl")


def load_rows(path=RESULTS):
    rows = {}
    if not os.path.exists(path):
        return rows
    for line in open(path):
        try:
            r = json.loads(line)
        except Exception:
            continue
        rows[(r.get("arch"), r.get("shape"), r.get("mesh"))] = r
    return rows


def main():
    setup_compile_cache()
    data = load_rows()
    out = []
    if not data:
        out.append(csv_row("lm_roofline/missing", 0.0,
                           f"run repro.launch.sweep first ({RESULTS})"))
    singles = {k: v for k, v in data.items() if k[2] == "single"}
    for (arch, shape, mesh), r in sorted(singles.items()):
        if r["status"] == "skipped":
            out.append(csv_row(f"lm_roofline/{arch}/{shape}", 0.0,
                               "status=skipped"))
            continue
        if r["status"] != "ok":
            out.append(csv_row(f"lm_roofline/{arch}/{shape}", 0.0,
                               f"status={r['status']}"))
            continue
        t = r["roofline"]
        step_s = max(t["compute_s"], t["memory_s"], t["collective_s"])
        out.append(csv_row(
            f"lm_roofline/{arch}/{shape}", step_s * 1e6,
            f"compute_s={t['compute_s']:.4f};memory_s={t['memory_s']:.4f};"
            f"collective_s={t['collective_s']:.4f};dominant={t['dominant']};"
            f"useful_flops_ratio={r['useful_flops_ratio']:.3f};"
            f"hbm_gib_per_dev={r['memory']['live_per_device_gib']}"))
    multi_ok = sum(1 for k, v in data.items()
                   if k[2] == "multi" and v["status"] == "ok")
    multi_skip = sum(1 for k, v in data.items()
                     if k[2] == "multi" and v["status"] == "skipped")
    out.append(csv_row("lm_roofline/multi_pod_summary", 0.0,
                       f"ok={multi_ok};skipped={multi_skip}"))
    for r in out:
        print(r)
    return out


if __name__ == "__main__":
    main()
