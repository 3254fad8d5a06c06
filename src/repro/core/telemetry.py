"""Process-wide telemetry: counters, spans, device-op scopes, Chrome-trace
export.

The paper assesses every port "within the context of the Roofline model"
(§5); the device time that assessment divides by comes from a profiler
trace, and this module is what lets such a trace name the program's
layers.  Every hot seam of the stack is instrumented against one
registry:

* **counters** — monotonically increasing named integers.  Always on:
  they are the same dict increments the old ``fuse._STATS`` /
  ``tune._STATS`` probes already paid for (those public ``stats()``
  functions are now thin shims over this registry).
* **gauges** — point-in-time samples (serve queue depth, slot occupancy).
  Recorded only while telemetry is enabled.
* **spans** — timed intervals with attributes (one per ``LaunchGraph``
  launch, tuner candidate, overlap sub-launch, pipeline step, serve
  request).  Launch spans carry the resolved plan label, cache hit/miss
  and the modeled HBM bytes of ``LaunchGraph.bytes_moved``.  While a span
  records it also holds a ``jax.profiler.TraceAnnotation`` of its name, so
  under a profiler trace it lands on the host plane, on the device
  trace's clock.
* **events** — zero-duration instants (pruned/failed tune candidates).
* **scopes** — :func:`scope` names the device ops traced inside it
  (``jax.named_scope``), so a device trace can put each op down to the
  program layer that issued it.  :data:`SCOPE_ROOTS` lists the roots.
  Scopes are HLO metadata, fixed at trace time and free at run time, so
  they are never gated.

Gating: the module switch starts from ``$TARGETDP_TELEMETRY`` (1/true/on
/yes) and is flipped at runtime with :func:`enable` / :func:`disable`;
``TargetConfig.telemetry`` overrides it per launch.  The disabled path is
a no-op closure — ``span()`` hands back a shared null object whose enter/
exit/set do nothing, so instrumented code pays one predicate per site
(the bench-smoke CI gate holds the enabled-vs-disabled overhead of the
fused smoke row under 1%).  Telemetry never touches traced values: every
attribute is a host-side scalar/string, so enabling it cannot perturb a
single bit of any launch output.

Export: :func:`export_chrome_trace` writes the Chrome trace-event JSON
(``{"traceEvents": [...]}``) that Perfetto / ``chrome://tracing`` load
directly.  :func:`report` returns the aggregate snapshot;
:func:`configure_logging` wires every ``repro.*`` child logger through
one stderr handler.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax

__all__ = [
    "ENV_VAR",
    "SCOPE_ROOTS",
    "STAGE_SCOPES",
    "scope",
    "enabled",
    "enable",
    "disable",
    "inc",
    "counter_value",
    "counters",
    "reset_counters",
    "sample",
    "gauges",
    "span",
    "begin_span",
    "event",
    "events",
    "reset",
    "report",
    "format_report",
    "export_chrome_trace",
    "configure_logging",
]

ENV_VAR = "TARGETDP_TELEMETRY"

_TRUTHY = ("1", "true", "on", "yes")


def _env_enabled(value: Optional[str]) -> bool:
    return (value or "").strip().lower() in _TRUTHY


# -- registry state ------------------------------------------------------------

_lock = threading.Lock()
_enabled: bool = _env_enabled(os.environ.get(ENV_VAR))
_counters: Dict[str, int] = {}
_gauges: Dict[str, List[tuple]] = {}  # name -> [(ts, value), ...]
_events: List[dict] = []  # finished spans + instants, in finish order
_T0 = time.perf_counter()  # trace time base (relative perf_counter)
_MAX_EVENTS = 500_000  # hard cap: long serve runs must not grow unbounded
_dropped = 0


def enabled(override: Optional[bool] = None) -> bool:
    """Whether spans/gauges record.  ``override`` (a per-launch
    ``TargetConfig.telemetry``) wins over the process switch when set."""
    if override is not None:
        return bool(override)
    return _enabled


def enable() -> None:
    """Turn span/gauge recording on."""
    global _enabled
    _enabled = True


def disable() -> None:
    """Turn span/gauge recording off (counters keep counting — they are
    the pre-telemetry ``stats()`` probes)."""
    global _enabled
    _enabled = False


# -- counters (always on) ------------------------------------------------------

def inc(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` (created at 0)."""
    _counters[name] = _counters.get(name, 0) + n


def counter_value(name: str) -> int:
    return _counters.get(name, 0)


def counters(prefix: Optional[str] = None) -> Dict[str, int]:
    """Snapshot of the counter registry (optionally only ``prefix``-ed)."""
    if prefix is None:
        return dict(_counters)
    return {k: v for k, v in _counters.items() if k.startswith(prefix)}


def reset_counters(prefix: Optional[str] = None) -> None:
    """Zero every counter (or only those under ``prefix``) — the
    back-compat ``reset_stats()`` shims scope themselves this way."""
    if prefix is None:
        _counters.clear()
        return
    for k in [k for k in _counters if k.startswith(prefix)]:
        _counters[k] = 0


# -- gauges (gated) ------------------------------------------------------------

def sample(name: str, value: float) -> None:
    """Record a point-in-time sample of gauge ``name`` (no-op when
    disabled)."""
    if not _enabled:
        return
    _gauges.setdefault(name, []).append(
        (time.perf_counter() - _T0, float(value)))


def gauges(prefix: Optional[str] = None) -> Dict[str, List[tuple]]:
    if prefix is None:
        return {k: list(v) for k, v in _gauges.items()}
    return {k: list(v) for k, v in _gauges.items() if k.startswith(prefix)}


# -- device-op scopes (never gated) ------------------------------------------

# The roots of every scope name: the launch layer (``launch/<graph>``, with
# ``stage_in``/``stage_out`` inside its jitted body) and the program layers
# around it.  A device trace reduction buckets each op by the roots in its
# name stack, so a name outside these tuples is refused here.
SCOPE_ROOTS = ("launch", "ludwig", "milc", "cg", "field", "halo")
STAGE_SCOPES = ("stage_in", "stage_out")


def scope(name: str):
    """``jax.named_scope(name)``: every op traced inside carries ``name``
    in its metadata (``op_name``, the device trace's ``tf_op``).  Trace-time
    only — nothing runs per call — so it is independent of :func:`enabled`."""
    if name.split("/", 1)[0] not in SCOPE_ROOTS and name not in STAGE_SCOPES:
        raise ValueError(f"scope {name!r} is under no root of SCOPE_ROOTS "
                         f"{SCOPE_ROOTS} and is not one of {STAGE_SCOPES}")
    return jax.named_scope(name)


# -- spans (gated) -------------------------------------------------------------

class Span:
    """One timed interval.  Use as a context manager (``with span(...)``)
    or manually via :func:`begin_span` / :meth:`end`.  ``set()`` attaches
    attributes mid-flight (e.g. cache hit/miss discovered during the
    launch).  From open to :meth:`end` it holds a profiler annotation of
    its name, which needs no lexical nesting: spans may close in any
    order."""

    __slots__ = ("name", "attrs", "t0", "t1", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self._ann = jax.profiler.TraceAnnotation(name)
        self._ann.__enter__()
        self.t0 = time.perf_counter() - _T0
        self.t1: Optional[float] = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self.attrs.setdefault("error", repr(exc))
        self.end()

    def end(self, **attrs) -> None:
        if self.t1 is not None:  # already closed
            return
        self.attrs.update(attrs)
        self.t1 = time.perf_counter() - _T0
        self._ann.__exit__(None, None, None)
        _record({
            "type": "span",
            "name": self.name,
            "ts": self.t0,
            "dur": self.t1 - self.t0,
            "tid": threading.get_ident(),
            "attrs": self.attrs,
        })

    @property
    def elapsed(self) -> float:
        """Seconds since the span opened (closed spans: the duration)."""
        return (self.t1 if self.t1 is not None
                else time.perf_counter() - _T0) - self.t0


class _NullSpan:
    """The disabled path: a shared do-nothing closure.  Every method is a
    no-op returning ``self``, so instrumented code never branches beyond
    the single ``enabled`` predicate inside :func:`span`."""

    __slots__ = ()
    name = None
    attrs: Dict[str, Any] = {}
    elapsed = 0.0

    def set(self, **attrs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None

    def end(self, **attrs) -> None:
        return None

    def __bool__(self) -> bool:
        return False


NULL_SPAN = _NullSpan()


def span(name: str, *, override: Optional[bool] = None, **attrs):
    """A new :class:`Span` when telemetry records, else the shared
    :data:`NULL_SPAN` no-op."""
    if not enabled(override):
        return NULL_SPAN
    return Span(name, attrs)


def begin_span(name: str, *, override: Optional[bool] = None, **attrs):
    """Manual-lifetime form of :func:`span` (close with ``.end()``) — for
    intervals that do not nest lexically, e.g. a serve request's
    admission-to-harvest latency."""
    return span(name, override=override, **attrs)


def event(name: str, *, override: Optional[bool] = None, **attrs) -> None:
    """A zero-duration instant (a pruned tune candidate, a degrade)."""
    if not enabled(override):
        return
    _record({
        "type": "event",
        "name": name,
        "ts": time.perf_counter() - _T0,
        "dur": 0.0,
        "tid": threading.get_ident(),
        "attrs": attrs,
    })


def _record(rec: dict) -> None:
    global _dropped
    with _lock:
        if len(_events) >= _MAX_EVENTS:
            _dropped += 1
            return
        _events.append(rec)


def events(name_prefix: Optional[str] = None) -> List[dict]:
    """Snapshot of finished spans/instants (optionally filtered by name
    prefix)."""
    with _lock:
        evs = list(_events)
    if name_prefix is None:
        return evs
    return [e for e in evs if e["name"].startswith(name_prefix)]


def reset() -> None:
    """Clear spans, instants and gauges (counters too — tests start
    clean)."""
    global _dropped
    with _lock:
        _events.clear()
        _dropped = 0
    _gauges.clear()
    _counters.clear()


# -- reporting / export --------------------------------------------------------

def report() -> Dict[str, Any]:
    """Aggregate snapshot: counters, per-gauge min/max/last, and per-name
    span statistics (count, total/mean/max seconds)."""
    evs = events()
    by_name: Dict[str, Dict[str, float]] = {}
    for e in evs:
        agg = by_name.setdefault(
            e["name"], {"count": 0, "total_s": 0.0, "max_s": 0.0})
        agg["count"] += 1
        agg["total_s"] += e["dur"]
        agg["max_s"] = max(agg["max_s"], e["dur"])
    for agg in by_name.values():
        agg["mean_s"] = agg["total_s"] / max(agg["count"], 1)
    gg = {
        name: {"samples": len(vals),
               "min": min(v for _, v in vals),
               "max": max(v for _, v in vals),
               "last": vals[-1][1]}
        for name, vals in _gauges.items() if vals
    }
    return {
        "enabled": _enabled,
        "counters": counters(),
        "gauges": gg,
        "spans": by_name,
        "events_recorded": len(evs),
        "events_dropped": _dropped,
    }


def format_report() -> str:
    """Human-readable :func:`report` (the ``--trace`` CLIs print this)."""
    r = report()
    lines = [f"telemetry report (enabled={r['enabled']}, "
             f"{r['events_recorded']} events)"]
    if r["counters"]:
        lines.append("  counters:")
        for k in sorted(r["counters"]):
            lines.append(f"    {k:<40s} {r['counters'][k]}")
    if r["gauges"]:
        lines.append("  gauges (min/max/last):")
        for k in sorted(r["gauges"]):
            g = r["gauges"][k]
            lines.append(f"    {k:<40s} {g['min']:g}/{g['max']:g}/"
                         f"{g['last']:g} ({g['samples']} samples)")
    if r["spans"]:
        lines.append("  spans (count, total, mean):")
        for k in sorted(r["spans"]):
            s = r["spans"][k]
            lines.append(f"    {k:<40s} {s['count']:>6d}  "
                         f"{s['total_s'] * 1e3:9.2f} ms  "
                         f"{s['mean_s'] * 1e6:9.1f} us")
    return "\n".join(lines)


def export_chrome_trace(path: str) -> str:
    """Write every recorded span/instant/gauge as a Chrome trace-event
    JSON file — load it at https://ui.perfetto.dev or chrome://tracing.
    Spans become complete ("X") events with their attributes under
    ``args``; instants become "i" events; gauge samples become counter
    ("C") tracks.  Returns ``path``."""
    pid = os.getpid()
    trace_events: List[dict] = [{
        "ph": "M", "pid": pid, "name": "process_name",
        "args": {"name": "targetdp-jax"},
    }]
    for e in events():
        rec = {
            "ph": "X" if e["type"] == "span" else "i",
            "name": e["name"],
            "cat": e["name"].split("/", 1)[0],
            "ts": e["ts"] * 1e6,
            "pid": pid,
            "tid": e["tid"],
            "args": {k: v if isinstance(v, (int, float, bool, str))
                     else str(v) for k, v in e["attrs"].items()},
        }
        if e["type"] == "span":
            rec["dur"] = e["dur"] * 1e6
        else:
            rec["s"] = "t"  # thread-scoped instant
        trace_events.append(rec)
    for name, vals in _gauges.items():
        for ts, v in vals:
            trace_events.append({
                "ph": "C", "name": name, "cat": name.split(".", 1)[0],
                "ts": ts * 1e6, "pid": pid, "args": {"value": v},
            })
    with open(path, "w") as f:
        json.dump({"traceEvents": trace_events, "displayTimeUnit": "ms"},
                  f, indent=1)
    return path


# -- logging -------------------------------------------------------------------

_LOG_HANDLER_FLAG = "_targetdp_telemetry_handler"


def configure_logging(level: int = logging.INFO,
                      stream=None) -> logging.Logger:
    """One entry point for the ``repro.*`` logger tree: attach a stderr
    (or ``stream``) handler with a uniform format to the ``repro`` root
    logger and set its level.  Every module logger in the stack is a
    ``logging.getLogger(__name__)`` child of it (``repro.core.fuse``,
    ``repro.core.overlap``, ``repro.core.tune``, ...), so the tuner's
    candidate-failure capture, the overlap thin-interior fallback and the
    tuned-misfit degrade messages all land here.  Idempotent: repeat
    calls re-level the existing handler instead of stacking new ones."""
    logger = logging.getLogger("repro")
    handler = next(
        (h for h in logger.handlers if getattr(h, _LOG_HANDLER_FLAG, False)),
        None)
    if handler is None:
        handler = logging.StreamHandler(stream)
        handler.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)-7s %(name)s: %(message)s"))
        setattr(handler, _LOG_HANDLER_FLAG, True)
        logger.addHandler(handler)
    handler.setLevel(level)
    logger.setLevel(level)
    return logger
