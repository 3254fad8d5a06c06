"""Put each device op of a traced window down to the program layer that
issued it.

The program names its layers with ``jax.named_scope`` (through
``repro.core.telemetry.scope``, roots in ``SCOPE_ROOTS``), and the trace
keeps each XLA op's name stack in the op's event metadata (``tf_op``),
with the Python line that issued it (``source``).  ``ProfileData`` hands
out an event's name and times but not that metadata, so this module
parses the ``.xplane.pb`` itself with ``google.protobuf`` (its C parser)
from the few XSpace messages declared below; TensorFlow is not imported.

The ops are those ``trace.load`` reads (the "XLA Ops" line of each device
plane, loops and calls left out, the same Mosaic test).  Each op's time,
clipped to the ``bench/window``, goes to exactly one bucket:

* ``kernel``: a Mosaic custom call (a Pallas launch);
* ``staging``: an XLA op under a ``launch/<graph>`` scope — the launch
  layer's casts, halo and tile pads, relayouts into the kernel's view,
  output folds and unpacks (``core/fuse.py``);
* ``app_glue``: an XLA op under an application root (``ludwig/``,
  ``milc/``, ``cg/``, ``field/``, ``halo/``) and under no launch;
* ``unscoped``: everything else — ops XLA inserts (layout copies, loop
  buffers), and every op of a program that names no scopes.

Within the buckets it keeps seconds per leaf scope (the scope path from
the first root, ``jit(...)`` and loop levels dropped), per leaf scope and
op, and the unscoped ops with their ``source``.

    python3 -m chipbench.scopes <trace.xplane.pb> [--devices N]

prints the reduction of a trace file as JSON.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from collections import defaultdict
from typing import Dict, Sequence, Tuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

from chipbench.trace import _CONTAINERS, _MOSAIC, OPS_LINE, op_name

LAUNCH = "launch"
BUCKETS = ("kernel", "staging", "app_glue", "unscoped")
# name-stack levels of a JAX loop (``while/body``, ``while/cond``), not a
# program layer
_LOOP_LEVELS = ("body", "cond")


def _xspace_class():
    """The XSpace message class, from the fields of ``xplane.proto`` this
    module reads (field numbers as in that file; the rest are skipped)."""
    F = descriptor_pb2.FieldDescriptorProto
    opt, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench.xplane",
        syntax="proto3")

    def message(name, fields, oneof=None, into=None):
        m = (fd.message_type if into is None else into).add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, ftype, label, tname, in_oneof in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if tname:
                f.type_name = ".chipbench.xplane." + tname
            if in_oneof:
                f.oneof_index = 0
        return m

    I64, U64, DBL = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    STR, BYT, MSG = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    message("XSpace", [("planes", 1, MSG, rep, "XPlane", False)])
    plane = message("XPlane", [
        ("id", 1, I64, opt, "", False), ("name", 2, STR, opt, "", False),
        ("lines", 3, MSG, rep, "XLine", False),
        ("event_metadata", 4, MSG, rep, "XPlane.EventMetadataEntry", False),
        ("stat_metadata", 5, MSG, rep, "XPlane.StatMetadataEntry", False)])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        m = message(entry, [("key", 1, I64, opt, "", False),
                            ("value", 2, MSG, opt, value, False)],
                    into=plane.nested_type)
        m.options.map_entry = True
    message("XLine", [
        ("id", 1, I64, opt, "", False), ("name", 2, STR, opt, "", False),
        ("timestamp_ns", 3, I64, opt, "", False),
        ("events", 4, MSG, rep, "XEvent", False)])
    message("XEvent", [
        ("metadata_id", 1, I64, opt, "", False),
        ("offset_ps", 2, I64, opt, "", True),
        ("duration_ps", 3, I64, opt, "", False),
        ("num_occurrences", 5, I64, opt, "", True)], oneof="data")
    message("XStat", [
        ("metadata_id", 1, I64, opt, "", False),
        ("double_value", 2, DBL, opt, "", True),
        ("uint64_value", 3, U64, opt, "", True),
        ("int64_value", 4, I64, opt, "", True),
        ("str_value", 5, STR, opt, "", True),
        ("bytes_value", 6, BYT, opt, "", True),
        ("ref_value", 7, U64, opt, "", True)], oneof="value")
    message("XEventMetadata", [
        ("id", 1, I64, opt, "", False), ("name", 2, STR, opt, "", False),
        ("display_name", 4, STR, opt, "", False),
        ("stats", 5, MSG, rep, "XStat", False)])
    message("XStatMetadata", [
        ("id", 1, I64, opt, "", False), ("name", 2, STR, opt, "", False)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.xplane.XSpace"))


_XSPACE = None


def _op_metadata(plane) -> Dict[int, Tuple[str, str]]:
    """event metadata id -> (tf_op, source) of one device plane."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    out = {}
    for mid, md in plane.event_metadata.items():
        got = {}
        for s in md.stats:
            key = stat_names.get(s.metadata_id)
            if key in ("tf_op", "source"):
                kind = s.WhichOneof("value")
                got[key] = (stat_names.get(s.ref_value, "")
                            if kind == "ref_value" else s.str_value)
        out[mid] = (got.get("tf_op", ""), got.get("source", ""))
    return out


def load(path: str) -> dict:
    """The device ops of one ``.xplane.pb``, as ``trace.load`` gives them
    plus their metadata: ``{"devices": {plane: [(op_name, start_ns,
    end_ns, is_pallas, tf_op, source)]}}``.  Times are whole nanoseconds,
    as ``ProfileData`` rounds them."""
    global _XSPACE
    if _XSPACE is None:
        _XSPACE = _xspace_class()
    space = _XSPACE()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices: Dict[str, list] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        meta = _op_metadata(plane)
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                md = plane.event_metadata[e.metadata_id]
                name = op_name(md.name)
                if name in _CONTAINERS:
                    continue
                start = line.timestamp_ns + e.offset_ps // 1000
                tf_op, source = meta[e.metadata_id]
                ops.append((name, float(start),
                            float(start + e.duration_ps // 1000),
                            _MOSAIC in md.name, tf_op, source))
        devices[plane.name] = ops
    return {"devices": devices}


def scope_roots() -> Tuple[str, ...]:
    """The program's scope roots (``repro.core.telemetry.SCOPE_ROOTS``);
    none for a program that declares none."""
    try:
        from repro.core.telemetry import SCOPE_ROOTS
    except ImportError:
        return ()
    return tuple(SCOPE_ROOTS)


def scope_path(tf_op: str, roots: Sequence[str]) -> str:
    """The scope path of a name stack, from its first root on:
    ``jit(<unknown>)/while/body/cg/normal/launch/wilson_normal/
    jit(wilson_normal)/stage_in/jit(_pad)/pad:`` ->
    ``cg/normal/launch/wilson_normal/stage_in``; "" under no root."""
    head, sep, tail = tf_op.rpartition(":")
    stack = head if sep and "/" not in tail else tf_op
    levels = stack.split("/")[:-1]  # the last level is the op itself
    for i, level in enumerate(levels):
        if level in roots:
            out, prev = [], ""
            for lv in levels[i:]:
                if not ("(" in lv or lv == "while"
                        or (prev == "while" and lv in _LOOP_LEVELS)):
                    out.append(lv)
                prev = lv
            return "/".join(out)
    return ""


def bucket(path: str, is_pallas: bool) -> str:
    if is_pallas:
        return "kernel"
    if not path:
        return "unscoped"
    if LAUNCH in path.split("/"):
        return "staging"
    return "app_glue"


def reduce(data: dict, window: Tuple[float, float], n_devices: int,
           roots: Sequence[str], top: int = 30) -> dict:
    """The buckets, leaf scopes and unscoped ops of the window ``(lo, hi)``
    (ns), averaged over the first ``n_devices`` device planes."""
    lo, hi = window
    planes = sorted(data["devices"])[:n_devices]
    nd = max(len(planes), 1)
    per_bucket = dict.fromkeys(BUCKETS, 0.0)
    leaf: Dict[Tuple[str, str], float] = defaultdict(float)
    leaf_op: Dict[Tuple[str, str], float] = defaultdict(float)
    unscoped: Dict[Tuple[str, str], float] = defaultdict(float)
    for p in planes:
        for name, a, b, pallas, tf_op, source in data["devices"][p]:
            d = min(b, hi) - max(a, lo)
            if d <= 0:
                continue
            path = scope_path(tf_op, roots)
            k = bucket(path, pallas)
            per_bucket[k] += d
            if k == "unscoped":
                unscoped[(name, source)] += d
            else:
                leaf[(k, path)] += d
                leaf_op[(path, name)] += d

    def rows(table, n=None):
        items = sorted(table.items(), key=lambda kv: -kv[1])[:n]
        return [[*key, t / nd * 1e-9] for key, t in items]

    out = {f"{k}_s": t / nd * 1e-9 for k, t in per_bucket.items()}
    out["scoped"] = (per_bucket["staging"] + per_bucket["app_glue"]) > 0
    out["leaf"] = rows(leaf)
    out["leaf_ops"] = rows(leaf_op, top)
    out["unscoped_ops"] = rows(unscoped, top)
    return out


def reduce_file(path: str, spans: Sequence[tuple], n_devices: int) -> dict:
    """The reduction of one trace file over its ``bench/window`` (``spans``
    as ``trace.load`` gives them), with the seconds it took."""
    t0 = time.perf_counter()
    window = next((a, b) for n, a, b in spans if n == "bench/window")
    out = reduce(load(path), window, n_devices, scope_roots())
    out["reduce_s"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    from chipbench import trace

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path")
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args(argv)
    spans = trace.load(args.path)["spans"]
    if not any(n == "bench/window" for n, _, _ in spans):
        # a trace recorded without the harness: its whole extent
        ops = [o for v in load(args.path)["devices"].values() for o in v]
        spans.append(("bench/window", min(o[1] for o in ops),
                      max(o[2] for o in ops)))
    json.dump(reduce_file(args.path, spans, args.devices), sys.stdout,
              indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
