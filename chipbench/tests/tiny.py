"""Tiny versions of the benchmark's cells, for running the harness on the
CPU (Pallas in interpret mode): the cells' own files with the lattices
cut down and the windows short."""

from __future__ import annotations

import copy

from chipbench import peaks
from chipbench.run import ROOT, load_cell

TINY = {
    "ludwig_lc_192.timesteps": {"lattice": [8, 8, 16],
                                "check": {"slab": 4}},
    "milc_wilson_32x64.solves": {"lattice": [4, 4, 4, 8]},
}
TINY_TRAFFIC = {"pool": 3, "warmup_steps": 2, "sync_every": 2,
                "check_within": 4, "trace_seconds": 1}


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) else v
    return out


def spec(workload: str) -> dict:
    """The cell as ``run_cell`` takes it, at a tiny size."""
    s = load_cell(workload, ROOT)
    s["config"] = _merge(s["config"], TINY[workload])
    s["traffic"] = _merge(s["traffic"], {k: v for k, v in TINY_TRAFFIC.items()
                                         if k in s["traffic"]})
    return s


def allow_cpu_peaks() -> None:
    """Give the CPU backend an entry in the peaks table (tests only)."""
    peaks.PEAKS.setdefault("cpu", {"hbm_bytes_per_s": 1e11,
                                   "bf16_flops_per_s": 1e12,
                                   "hbm_bytes": 1e10, "source": "test"})


