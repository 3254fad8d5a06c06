"""core.telemetry: the unified observability registry.  Disabled no-op
path, gated spans/gauges vs always-on counters, the fuse/tune stats()
back-compat shims, launch-span schema with cache transitions, per-launch
TargetConfig.telemetry override, Chrome trace export, report snapshots,
spans on the profiler's host plane, device-op scopes covering every op of
the Ludwig step and the MILC solve, the unified repro.* logging tree
(tuner candidate failures, overlap thin-interior fallback, tuned-misfit
degrade — all caplog-asserted), tune sweep spans and pipeline step spans.
"""

import collections
import functools
import glob
import json
import logging
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Field, LaunchGraph, LoweringPlan, SOA, StepPipeline, TargetConfig, fuse,
    telemetry, tune,
)

LAT = (4, 4, 8)  # 128 sites


@pytest.fixture(autouse=True)
def clean_telemetry():
    """Every test starts and ends with telemetry off and empty — span
    state must never leak between tests (or into other test files)."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _scale_body(v):
    return {"t": 2.0 * v["x"]}


def _graph(name="telemetry_probe"):
    return LaunchGraph(name).add(_scale_body, {"x": "x"}, {"t": 3})


def _field(rng):
    arr = rng.normal(size=(3, *LAT)).astype(np.float32)
    return Field.from_numpy("x", arr, LAT, SOA)


# -- gating --------------------------------------------------------------------

def test_env_parser():
    assert telemetry._env_enabled("1")
    assert telemetry._env_enabled(" TRUE ")
    assert telemetry._env_enabled("on") and telemetry._env_enabled("yes")
    assert not telemetry._env_enabled(None)
    assert not telemetry._env_enabled("")
    assert not telemetry._env_enabled("0")
    assert not telemetry._env_enabled("off")


def test_disabled_spans_are_noop_counters_still_count():
    s = telemetry.span("probe/x", a=1)
    assert s is telemetry.NULL_SPAN and not s
    with telemetry.span("probe/y") as s2:
        s2.set(k=2).end()
    telemetry.event("probe/ev", a=1)
    telemetry.sample("probe.g", 3.0)
    assert telemetry.events() == []
    assert telemetry.gauges() == {}
    # counters are the pre-telemetry stats() probes: never gated
    telemetry.inc("probe.count", 2)
    assert telemetry.counter_value("probe.count") == 2


def test_enabled_span_records_name_attrs_duration():
    telemetry.enable()
    with telemetry.span("probe/work", stage="a") as s:
        s.set(extra=1)
    (e,) = telemetry.events("probe/work")
    assert e["type"] == "span"
    assert e["attrs"] == {"stage": "a", "extra": 1}
    assert e["dur"] >= 0.0
    # an exception inside the span is recorded, not swallowed
    with pytest.raises(RuntimeError):
        with telemetry.span("probe/boom"):
            raise RuntimeError("kaboom")
    (b,) = telemetry.events("probe/boom")
    assert "RuntimeError" in b["attrs"]["error"]


def test_override_beats_process_switch():
    # off process-wide, on per call site
    s = telemetry.span("probe/forced", override=True)
    assert s is not telemetry.NULL_SPAN
    s.end()
    assert len(telemetry.events("probe/forced")) == 1
    # on process-wide, off per call site
    telemetry.enable()
    assert telemetry.span("probe/muted", override=False) is telemetry.NULL_SPAN


# -- counter shims -------------------------------------------------------------

def test_stats_shims_exact_keys_and_scoped_reset():
    fuse.reset_stats()
    tune.reset_stats()
    assert sorted(fuse.stats()) == [
        "cache_hits", "cache_misses", "pallas_calls", "traces"]
    assert sorted(tune.stats()) == [
        "hits", "lookups", "sweep_launches", "tunes"]
    telemetry.inc("fuse.traces")
    telemetry.inc("tune.lookups")
    assert fuse.stats()["traces"] == 1
    assert tune.stats()["lookups"] == 1
    fuse.reset_stats()  # prefix-scoped: must not touch tune.*
    assert fuse.stats()["traces"] == 0
    assert tune.stats()["lookups"] == 1


# -- launch spans --------------------------------------------------------------

LAUNCH_SPAN_SCHEMA = (
    "plan", "engine", "lattice", "batch", "halo", "from_tuned_table",
    "cache", "bytes_fused", "bytes_unfused",
)


def test_launch_span_schema_cache_transition_and_bitwise(rng):
    fx = _field(rng)
    cfg = TargetConfig("jnp")
    fuse.clear_cache()
    base = _graph().launch({"x": fx}, config=cfg)["t"].to_numpy()  # disabled

    telemetry.enable()
    fuse.clear_cache()
    got = _graph().launch({"x": fx}, config=cfg)["t"].to_numpy()
    again = _graph().launch({"x": fx}, config=cfg)["t"].to_numpy()
    # observability never perturbs the computation: bit-for-bit equal
    np.testing.assert_array_equal(got, base)
    np.testing.assert_array_equal(again, base)

    spans = telemetry.events("launch/telemetry_probe")
    assert len(spans) == 2
    for e in spans:
        for field in LAUNCH_SPAN_SCHEMA:
            assert field in e["attrs"], f"launch span missing {field}"
    assert [e["attrs"]["cache"] for e in spans] == ["miss", "hit"]
    a = spans[0]["attrs"]
    assert a["engine"] == "jnp"
    assert a["lattice"] == str(LAT)
    assert a["bytes_fused"] > 0 and a["bytes_unfused"] >= a["bytes_fused"]
    assert a["from_tuned_table"] is False


def test_config_telemetry_override_per_launch(rng):
    fx = _field(rng)
    # process switch off, per-launch on
    _graph("cfg_on").launch({"x": fx}, config=TargetConfig(
        "jnp", telemetry=True))
    assert len(telemetry.events("launch/cfg_on")) == 1
    # process switch on, per-launch off
    telemetry.enable()
    _graph("cfg_off").launch({"x": fx}, config=TargetConfig(
        "jnp", telemetry=False))
    assert telemetry.events("launch/cfg_off") == []


# -- export --------------------------------------------------------------------

def test_chrome_trace_export(tmp_path):
    telemetry.enable()
    with telemetry.span("probe/a", k="v"):
        pass
    telemetry.event("probe/inst", why="x")
    telemetry.sample("probe.gauge", 1.5)
    path = telemetry.export_chrome_trace(str(tmp_path / "trace.json"))
    data = json.loads(open(path).read())
    evs = data["traceEvents"]
    assert {"M", "X", "i", "C"} <= {e["ph"] for e in evs}
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "probe/a" and x["args"]["k"] == "v"
    assert x["dur"] >= 0 and x["cat"] == "probe"
    c = next(e for e in evs if e["ph"] == "C")
    assert c["name"] == "probe.gauge"


def test_report_and_format():
    telemetry.enable()
    telemetry.inc("probe.count", 3)
    telemetry.sample("probe.gauge", 2.0)
    telemetry.sample("probe.gauge", 4.0)
    for _ in range(2):
        with telemetry.span("probe/s"):
            pass
    r = telemetry.report()
    assert r["counters"]["probe.count"] == 3
    assert r["spans"]["probe/s"]["count"] == 2
    g = r["gauges"]["probe.gauge"]
    assert (g["min"], g["max"], g["last"]) == (2.0, 4.0, 4.0)
    txt = telemetry.format_report()
    assert "probe.count" in txt and "probe/s" in txt


# -- the profiler's clock ------------------------------------------------------

def _host_events(trace_dir):
    """{name: [(start_ns, end_ns)]} of the host planes of the one
    ``.xplane.pb`` a ``jax.profiler.trace`` wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out[e.name].append((e.start_ns, e.start_ns + e.duration_ns))
    return out


def test_spans_reach_the_profiler_host_plane(tmp_path):
    """A recording span is also a profiler annotation: a lexical span and
    two begin/end spans that close out of order appear by name on a host
    plane, on the trace's clock, each once."""
    import jax

    telemetry.enable()
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.span("probe/lexical"):
            jnp.ones(8).block_until_ready()
        first = telemetry.begin_span("probe/first")
        second = telemetry.begin_span("probe/second")
        first.end()  # closes while the span opened after it is open
        second.end()
    ev = _host_events(str(tmp_path))
    (lex,) = ev["probe/lexical"]
    (a,) = ev["probe/first"]
    (b,) = ev["probe/second"]
    assert lex[1] <= a[0]
    assert a[0] <= b[0] <= a[1] <= b[1]  # overlapping, not nested
    assert [e["name"] for e in telemetry.events("probe/")] == [
        "probe/lexical", "probe/first", "probe/second"]


class _CountingAnnotation:
    made = []

    def __init__(self, name):
        self.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def test_disabled_path_makes_no_annotation(rng, monkeypatch):
    """With telemetry off, ``span``/``begin_span`` hand back the shared
    NULL_SPAN and no profiler annotation is made — not even by a launch;
    switched on, each span makes exactly one."""
    import jax

    monkeypatch.setattr(_CountingAnnotation, "made", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    assert telemetry.span("probe/off") is telemetry.NULL_SPAN
    assert telemetry.begin_span("probe/off2") is telemetry.NULL_SPAN
    with telemetry.span("probe/off3") as s:
        s.set(k=1).end()
    _graph("silent").launch({"x": _field(rng)}, config=TargetConfig("jnp"))
    assert _CountingAnnotation.made == []
    assert telemetry.events() == []

    telemetry.enable()
    with telemetry.span("probe/on"):
        pass
    assert _CountingAnnotation.made == ["probe/on"]


# -- device-op scopes ------------------------------------------------------------

def test_scope_names_are_declared():
    assert telemetry.SCOPE_ROOTS == (
        "launch", "ludwig", "milc", "cg", "field", "halo")
    for name in ("launch/g", "cg/xpay", "stage_in", "stage_out"):
        with telemetry.scope(name):
            pass
    for name in ("bogus/x", "stage", "ludwigs/lb"):
        with pytest.raises(ValueError, match="SCOPE_ROOTS"):
            telemetry.scope(name)


_HLO_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
_HLO_INST = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][\w\-]*)\((.*)")
_HLO_CALLS = re.compile(r"\b(to_apply|body|condition)=%?([\w.\-]+)")
_HLO_OPNAME = re.compile(r'op_name="([^"]*)"')
# ops with no layer of their own: arguments, literals (and their
# broadcasts), tuple plumbing and the loop op, whose body is checked
_PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element", "while")


def _full_op_names(hlo_text):
    """(opcode, operands, op_name) of every op of a lowered module, each
    op_name with its callers' names in front, as XLA writes them when it
    inlines a ``call`` (a jitted function's computation holds names
    relative to it; loop bodies share the enclosing function's)."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _HLO_COMP.match(line)
            if m and not line.startswith(" "):
                cur = m.group(2)
                comps[cur] = []
                entry = cur if m.group(1) else entry
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _HLO_INST.match(line)
        if m:
            on = _HLO_OPNAME.search(line)
            comps[cur].append((m.group(1), m.group(2),
                               on.group(1) if on else "",
                               _HLO_CALLS.findall(line)))
    out, seen = [], set()

    def walk(comp, prefix):
        if (comp, prefix) in seen:
            return
        seen.add((comp, prefix))
        for opc, operands, name, called in comps[comp]:
            full = "/".join(p for p in (prefix, name) if p)
            out.append((opc, operands, full))
            for kind, callee in called:
                if opc == "call":
                    walk(callee, full)
                elif kind in ("body", "condition"):
                    walk(callee, prefix)
                # other to_apply computations are reducers, not device ops

    walk(entry, "")
    return out


def _lowered_for_chip(app, monkeypatch):
    """The tiny Ludwig step or MILC solve lowered as the chip gets it:
    compiled Pallas (Mosaic custom calls, DMA-staged stencil launches),
    lowered for a TPU from this CPU host."""
    from repro.apps.ludwig import driver as lud
    from repro.apps.milc import driver as milc
    from repro.core import plan as plan_mod

    monkeypatch.setattr(plan_mod, "_device_kind", lambda: "TPU v5 lite")
    tgt = TargetConfig("pallas", interpret=False)
    if app == "ludwig":
        cfg = lud.LudwigConfig(lattice=(8, 8, 128), target=tgt)
        fn, args = functools.partial(lud.step, cfg=cfg), (lud.init_state(cfg),)
    else:
        cfg = milc.MilcConfig(lattice=(4, 4, 8, 16), target=tgt, max_iter=5)
        fn, args = functools.partial(milc.solve, cfg), milc.init_problem(cfg)
    import jax

    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("app", ["ludwig", "milc"])
def test_every_device_op_is_scoped(app, monkeypatch):
    """Every op the chip would run carries a scope root in its name stack
    (so a device trace can name the layer that issued it), the launches'
    bodies are named after their graphs, and their staging is scoped: a
    refactor that drops a scope fails here."""
    lowered = _lowered_for_chip(app, monkeypatch)
    ops = _full_op_names(lowered.as_text(dialect="hlo", debug_info=True))
    roots = tuple(f"{r}/" for r in telemetry.SCOPE_ROOTS)
    unscoped = collections.Counter(
        (opc, name) for opc, operands, name in ops
        if opc not in _PLUMBING
        and not (opc == "broadcast" and operands.startswith("constant"))
        and not any(r in name for r in roots))
    assert not unscoped, f"ops under no scope root: {dict(unscoped)}"
    names = [name for _, _, name in ops]
    kernels = [n for opc, _, n in ops if opc == "custom-call"]
    graphs = (("ludwig_chem_stress", "ludwig_lb_step", "ludwig_lc_update")
              if app == "ludwig" else ("wilson_normal", "cg_update", "cg_xpay"))
    for g in graphs:
        assert any(f"launch/{g}/jit({g})/" in n for n in kernels), g
    stages = telemetry.STAGE_SCOPES
    if app == "ludwig":
        # the step's nd-stored fields leave every launch as they are stored:
        # it stages inputs (the LB launch's halo and tile pads), no outputs
        assert not any("/stage_out/" in n for n in names)
        stages = tuple(s for s in stages if s != "stage_out")
    for stage in stages:
        assert any(f"/{stage}/" in n for n in names), stage


# -- unified logging -----------------------------------------------------------

def test_configure_logging_idempotent():
    lg = telemetry.configure_logging(level=logging.DEBUG)
    assert lg.name == "repro"
    flagged = [h for h in lg.handlers
               if getattr(h, "_targetdp_telemetry_handler", False)]
    assert len(flagged) == 1
    try:
        lg2 = telemetry.configure_logging(level=logging.INFO)  # re-level only
        assert lg2 is lg
        assert [h for h in lg.handlers
                if getattr(h, "_targetdp_telemetry_handler", False)] == flagged
        assert lg.level == logging.INFO
    finally:
        lg.removeHandler(flagged[0])
        lg.setLevel(logging.NOTSET)


def test_tuned_misfit_degrade_logged_and_recovers(rng, monkeypatch, caplog):
    """A stale tuned-table plan that cannot validate degrades to the
    default plan through the repro.core.fuse logger — warned, not fatal,
    and numerically identical to the default policy."""
    fx = _field(rng)
    bad = LoweringPlan("jnp", rsplit=2)  # jnp has no reduction grid to split
    monkeypatch.setattr(tune, "lookup", lambda key, path=None: bad)
    want = _graph("degrade_probe").launch(
        {"x": fx}, config=TargetConfig("jnp"))["t"].to_numpy()
    with caplog.at_level(logging.WARNING, logger="repro.core.fuse"):
        got = _graph("degrade_probe").launch(
            {"x": fx}, config=TargetConfig("jnp", plan_policy="tuned"))[
                "t"].to_numpy()
    assert any("falling back to the default plan" in r.message
               for r in caplog.records)
    np.testing.assert_array_equal(got, want)


def test_overlap_thin_interior_fallback_logs_under_repro_root(rng, caplog):
    """The overlap thin-interior fallback reaches the unified ``repro``
    logger tree (configure_logging's single attachment point) as a
    ``repro.core.overlap`` child record."""
    from repro.core.stencil import halo_pad

    def body(v, gather):
        s = v["x"]
        for d in range(3):
            for sgn in (1, -1):
                disp = [0, 0, 0]
                disp[d] = sgn
                s = s + gather("x", tuple(disp))
        return {"z": s}

    g = LaunchGraph("tele_stencil").add_stencil(
        body, {"x": "x"}, {"z": 3}, width=1)
    thin = (2, 2, 2)
    arr = rng.normal(size=(3, *thin)).astype(np.float32)
    h = halo_pad(jnp.asarray(arr), 1, (1, 2, 3))
    fx = Field.from_canonical("x", h, tuple(h.shape[1:]), SOA)
    cfg = TargetConfig("jnp")
    want = g.launch({"x": fx}, config=cfg, halo="pre")["z"]
    with caplog.at_level(logging.WARNING, logger="repro"):
        got = g.launch({"x": fx}, config=cfg, halo="overlap")["z"]
    recs = [r for r in caplog.records if r.name == "repro.core.overlap"
            and "falling back to halo='pre'" in r.message]
    assert recs, "overlap fallback did not log through the repro.* tree"
    np.testing.assert_array_equal(want.to_numpy(), got.to_numpy())


# -- tune sweep spans ----------------------------------------------------------

def test_tune_sweep_spans_and_failure_capture(tmp_path, monkeypatch, rng,
                                              caplog):
    monkeypatch.setenv(tune.ENV_VAR, str(tmp_path / "t.json"))
    tune.clear_table_cache()
    telemetry.enable()
    fx = _field(rng)
    g = _graph("sweep_probe")
    cfg = TargetConfig("pallas", vvl=64)
    good = tune.plan_candidates_for(g, {"x": fx}, config=cfg)[0]
    bad = LoweringPlan("jnp", rsplit=2)  # raises at plan validation
    with caplog.at_level(logging.WARNING, logger="repro.core.tune"):
        times, failed = tune._sweep(
            g, {"x": fx}, {"config": cfg}, (good, bad), 1, 1)
    assert good in times and bad in failed
    assert any("failed" in r.message for r in caplog.records)
    (sweep,) = telemetry.events("tune/sweep")
    assert sweep["attrs"]["candidates"] == 2
    assert sweep["attrs"]["failed"] == 1 and sweep["attrs"]["timed"] == 1
    cands = telemetry.events("tune/candidate")
    assert any(e["attrs"]["phase"] == "timed" for e in cands)
    fails = telemetry.events("tune/failed")
    assert fails and "rsplit" in fails[0]["attrs"]["reason"]


# -- pipeline spans ------------------------------------------------------------

def test_pipeline_step_spans():
    telemetry.enable()

    def incstep(x):
        return x + 1

    pipe = StepPipeline(incstep, donate=False)
    (out,) = pipe.run((jnp.zeros(4),), steps=3)
    np.testing.assert_array_equal(np.asarray(out), 3.0 * np.ones(4))
    steps = [e for e in telemetry.events("pipeline/incstep")
             if e["name"] == "pipeline/incstep"]
    assert [e["attrs"]["step"] for e in steps] == [0, 1, 2]
    (blk,) = telemetry.events("pipeline/incstep.block")
    assert blk["attrs"]["steps"] == 3


# -- halo exchange counters ------------------------------------------------------

@pytest.mark.parametrize("width", [1, 2])
def test_halo_exchange_counts_bytes_sent_at_trace_time(width):
    """Each exchanged dim counts one exchange and both face slabs it
    sends, once per trace: the jitted calls that reuse the trace add
    nothing, and the counted volume is the one from the padded shape."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import compat, halo

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("x", "y"))
    dec = ((1, "x", 1), (2, "y", 1))
    shape = (3, 6 + 2 * width, 5 + 2 * width, 4)
    fn = jax.jit(compat.shard_map(
        lambda a: halo.exchange(a, dec, width=width), mesh=mesh,
        in_specs=P(None, "x", "y", None), out_specs=P(None, "x", "y", None)))
    x = jax.device_put(jnp.arange(np.prod(shape), dtype=jnp.float32)
                       .reshape(shape),
                       NamedSharding(mesh, P(None, "x", "y", None)))
    for _ in range(3):
        out = fn(x)
    # one rank per axis: the exchange is the periodic wrap of the interior
    want = np.asarray(x)
    for dim in (1, 2):
        n = want.shape[dim]
        inner = np.take(want, range(width, n - width), axis=dim)
        want = np.concatenate([np.take(inner, range(-width, 0), axis=dim),
                               inner, np.take(inner, range(width), axis=dim)],
                              axis=dim)
    np.testing.assert_array_equal(np.asarray(out), want)
    _, X, Y, Z = shape
    assert telemetry.counters("halo.") == {
        "halo.exchanges": 2,
        "halo.bytes": 2 * 3 * width * (Y * Z + X * Z) * 4}
