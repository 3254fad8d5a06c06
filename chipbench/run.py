"""Run one benchmark cell once and print its result as one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up in ``BENCHMARK.json``.  Its configuration
(``chipbench/configs/<config>.json``) names the entry it drives
(``chipbench/entries/<entry>.py``) and the plain reference it is checked
against; its traffic mix is ``chipbench/traffic/<traffic>.json``; each
metric it reports is read by ``chipbench/metrics/<metric>.py``.  Adding a
cell adds files and entries and edits none.

A run: set-up (inputs made on the device from the seed, compile, warm-up)
counted as ``setup_s``; a measured window of ``--seconds`` with the
profiler off (``--trace 0``, end-to-end metrics) or a shorter traced
window (``--trace 1``, per-layer metrics from the device trace); the
device's peak memory; then the comparison with the reference that
decides ``correct``.  The last line of standard output is the result; the
numbers compared, each with its limit, are also the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, the run
exits with code 3 and prints no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "chipbench", "_out")


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration and traffic, and the metric entries
    of ``BENCHMARK.json`` that apply to it."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    base = os.path.join(root, "chipbench")
    with open(os.path.join(base, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(base, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def _prepare_environment() -> None:
    """Caches and logs inside the checkout, before JAX is imported."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def _devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def _span_factory(tracing: bool):
    """Host spans around the calls into each layer: profiler annotations
    in a traced run, nothing otherwise."""
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax

    return lambda name: jax.profiler.TraceAnnotation(f"bench/{name}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             spec: dict | None = None, require_tpu: bool = True,
             t0: float | None = None) -> dict:
    """One run of one cell; returns the result object.  ``spec`` replaces
    what ``load_cell`` would read (tests run tiny cells through it)."""
    t0 = _T0 if t0 is None else t0
    _prepare_environment()
    spec = spec or load_cell(workload)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SystemExit("the system under test (src/repro) is not in this "
                         "checkout")
    import jax

    from chipbench import peaks as peaks_mod
    from chipbench import trace as trace_mod

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    devices = _devices(int(cell["chips"]), require_tpu)
    kind = devices[0].device_kind
    peak_table = peaks_mod.peaks(kind)

    entry = importlib.import_module(f"chipbench.entries.{config['entry']}")
    ctx = types.SimpleNamespace(
        seed=int(seed), config=config, traffic=traffic, devices=devices,
        tracing=bool(trace), span=_span_factory(trace))
    session = entry.prepare(ctx)
    setup_s = time.perf_counter() - t0

    reduction = None
    if trace:
        tdir = os.path.join(OUT_DIR, "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        window_s = min(float(seconds), float(traffic["trace_seconds"]))
        jax.profiler.start_trace(tdir)
        try:
            with ctx.span("window"):
                record = session.run_window(window_s)
        finally:
            jax.profiler.stop_trace()
        reduction = trace_mod.reduce_dir(tdir, len(devices))
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        record = session.run_window(float(seconds))

    memory_peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        memory_peak = max(memory_peak, int(stats.get("peak_bytes_in_use", 0)))
    session.release()
    gc.collect()
    checks = session.check()

    run = {"record": record, "setup_s": setup_s, "trace": reduction,
           "config": config, "traffic": traffic, "peaks": peak_table,
           "chips": len(devices)}
    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    correct = correct and record["failed"] == 0
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": int(record["attempted"]),
              "failed": int(record["failed"]), "metrics": metrics,
              "device": device}
    if reduction is not None:
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = reduction["breakdown"]
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoDevice as e:
        print(f"chipbench: {e}; nothing was measured", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
