"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports.

The trace holds, per device, the operations that ran on it (the "XLA Ops"
line of each ``/device:TPU:<n>`` plane), and, on the host, the spans the
benchmark wrote around its calls into the program (``bench/<layer>``,
with ``bench/window`` around the whole traced window).  Both are on one
clock.  From them:

* busy: the union of the operations' intervals inside the window, per
  device, averaged over the devices;
* per-operation device time, by the operation's name with its numeric
  suffix dropped (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``),
  so that names stay stable from one compile to the next;
* time in Pallas kernels (custom calls to ``tpu_custom_call``, named by
  their launch: ``ludwig_lb_step``, ``wilson_normal``, ...) against time
  in XLA's own operations;
* collective time with no other operation running on that device;
* idle gaps, each named by the innermost host span around its middle.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"(\.\d+)+$")
_COLLECTIVE = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all", "send", "recv")
# operations whose interval spans other operations of the same line (a
# loop and its body): the body's operations are counted, the loop is not
_CONTAINERS = ("while", "conditional", "call")
_MOSAIC = 'custom_call_target="tpu_custom_call"'


def op_name(name: str) -> str:
    """``%name.N = <shape> op(...)`` (the HLO text the TPU trace gives) or
    ``name.N`` -> ``name``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def is_collective(name: str) -> bool:
    return any(name.startswith(c) for c in _COLLECTIVE)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` that no interval of ``b``
    covers."""
    out = []
    b = union(b)
    j = 0
    for lo, hi in union(a):
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def load(path: str) -> dict:
    """Device operations and host spans of one ``.xplane.pb``:
    ``{"devices": {plane: [(op_name, start_ns, end_ns, is_pallas)]},
    "spans": [(name, start_ns, end_ns)]}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name = op_name(e.name)
                    if name in _CONTAINERS:
                        continue
                    ops.append((name, e.start_ns, e.start_ns + e.duration_ns,
                                _MOSAIC in e.name))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return {"devices": devices, "spans": spans}


def reduce(data: dict, n_devices: int, top: int = 10) -> dict:
    """The numbers of a traced window (see the module docstring)."""
    windows = [(a, b) for n, a, b in data["spans"] if n == "bench/window"]
    if not windows:
        raise ValueError("the trace has no bench/window span")
    lo, hi = windows[0]
    window_ns = hi - lo
    planes = sorted(data["devices"])[:n_devices]
    if len(planes) < n_devices:
        raise ValueError(f"the trace has {len(planes)} device planes, the "
                         f"run used {n_devices}")
    busy, xla, exposed = [], [], []
    per_op: Dict[str, float] = {}
    for p in planes:
        ops = data["devices"][p]
        ivs = clip([(a, b) for _, a, b, _ in ops], lo, hi)
        busy.append(length(union(ivs)))
        xla.append(length(union(clip(
            [(a, b) for n, a, b, m in ops if not m], lo, hi))))
        coll = clip([(a, b) for n, a, b, _ in ops if is_collective(n)],
                    lo, hi)
        other = clip([(a, b) for n, a, b, _ in ops if not is_collective(n)],
                     lo, hi)
        exposed.append(length(subtract(coll, other)))
        for n, a, b, _ in ops:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                per_op[n] = per_op.get(n, 0.0) + d
    nd = len(planes)
    gaps = []
    dev0 = union(clip([(a, b) for _, a, b, _ in data["devices"][planes[0]]],
                      lo, hi))
    edges = [lo] + [x for iv in dev0 for x in iv] + [hi]
    inner = [(n, a, b) for n, a, b in data["spans"] if n != "bench/window"]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = 0.5 * (a + b)
            around = [(s, n) for n, s, e in inner if s <= mid <= e]
            name = max(around)[1] if around else "bench/window"
            gaps.append((name, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])
    busy_ns = sum(busy) / nd
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "xla_s": sum(xla) / nd * 1e-9,
        "collective_exposed_s": sum(exposed) / nd * 1e-9,
        "op_s": {n: t / nd * 1e-9 for n, t in ops_sorted},
        "spans": sorted({n for n, _, _ in inner}),
        "breakdown": {
            "device_ops": [[n, t / nd * 1e-9] for n, t in ops_sorted[:top]],
            "idle_gaps": [[n, s] for n, s in gaps[:top]],
        },
    }


def reduce_dir(directory: str, n_devices: int) -> dict:
    """Reduce the one ``.xplane.pb`` the profiler wrote under
    ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {directory}, found "
                         f"{len(paths)}")
    return reduce(load(paths[0]), n_devices)
