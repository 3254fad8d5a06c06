"""The scope reduction (``chipbench/scopes.py``): its protobuf decoder
against ``ProfileData``, the four buckets against busy time, on the trace
recorded before the program named its layers and on two recorded with the
scopes (TPU v5e, ``fixtures/``), and the readers of the shares."""

import json
import os
import shutil

import pytest

from chipbench import scopes, trace
from chipbench.tools import scoped_run

HERE = os.path.join(os.path.dirname(__file__), "fixtures")
OLD = os.path.join(HERE, "ludwig_32x32x128.xplane.pb")
# Ludwig at 32x32x128, 2 timesteps; MILC at 8^3x16, one solve of 20
# iterations; each under bench/window, bench/dispatch and bench/sync
NEW = [os.path.join(HERE, f) for f in ("ludwig_scoped_32x32x128.xplane.pb",
                                       "milc_scoped_8x8x8x16.xplane.pb")]
ROOTS = ("launch", "ludwig", "milc", "cg", "field", "halo")


def _window(data):
    """The bench/window of a trace; the old fixture has none, so its
    spans' extent, as ``test_trace.test_fixture_by_hand`` takes it."""
    spans = data["spans"]
    for n, a, b in spans:
        if n == "bench/window":
            return a, b
    return min(a for _, a, _ in spans), max(b for _, _, b in spans)


@pytest.mark.parametrize("path", [OLD] + NEW, ids=os.path.basename)
def test_decoder_agrees_with_profile_data(path):
    """The same device ops, in the same order, with the same starts and
    ends, as ``ProfileData`` gives through ``trace.load``."""
    ours = scopes.load(path)["devices"]
    theirs = trace.load(path)["devices"]
    assert sorted(ours) == sorted(theirs)
    for plane, ops in theirs.items():
        assert len(ops) > 100
        assert [o[:4] for o in ours[plane]] == [tuple(o) for o in ops]


def _as_run_dir(tmp_path, path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(path, d / "host.xplane.pb")
    return str(tmp_path)


def test_scoped_run_leaves_the_reduction_as_it_was(tmp_path, monkeypatch):
    """On the trace recorded before the scopes, the harness's
    ``trace.reduce_dir`` as ``scoped_run`` wraps it returns what it returned
    before (``ludwig_32x32x128.reduce.json``, written by the earlier code),
    and the scope reduction goes beside it."""
    load = trace.load

    def with_window(path):
        data = load(path)
        data["spans"].append(("bench/window", *_window(data)))
        return data

    monkeypatch.setattr(trace, "load", with_window)
    into = {}
    got = scoped_run.with_scopes(trace.reduce_dir, into)(
        _as_run_dir(tmp_path, OLD), 1)
    with open(os.path.join(HERE, "ludwig_32x32x128.reduce.json")) as f:
        before = json.load(f)
    assert json.loads(json.dumps(got)) == before
    sc = into["scopes"]
    # no scopes in that program: its ops are kernels or unscoped
    assert not sc["scoped"] and sc["staging_s"] == sc["app_glue_s"] == 0
    assert sc["kernel_s"] + sc["unscoped_s"] == pytest.approx(
        before["busy_s"], rel=1e-12)
    assert sc["reduce_s"] > 0


@pytest.mark.parametrize("path", NEW, ids=os.path.basename)
def test_scoped_run_shares_partition_busy_time(tmp_path, path):
    """The four shares ``scoped_run`` prints sum to 100% of busy time, and
    all but the kernels' to the XLA share of the harness's reduction."""
    into = {}
    scoped_run.with_scopes(trace.reduce_dir, into)(
        _as_run_dir(tmp_path, path), 1)
    shares = into["shares"]
    assert sum(shares.values()) == pytest.approx(100.0, abs=1e-9)
    assert shares["staging"] + shares["app_glue"] + shares["unscoped"] == (
        pytest.approx(into["xla_share"], abs=1e-9))
    assert shares["staging"] > 0 and shares["app_glue"] > 0


def _reduced(path):
    data = trace.load(path)
    window = _window(data)
    data["spans"].append(("bench/window", *window))
    tr = trace.reduce(data, 1)
    sc = scopes.reduce(scopes.load(path), window, 1, ROOTS, top=10**6)
    return tr, sc


@pytest.mark.parametrize("path", [OLD] + NEW, ids=os.path.basename)
def test_buckets_partition_busy_time(path):
    """Kernel + staging + app glue + unscoped is device busy time, and all
    but the kernels is the XLA time ``trace.reduce`` counts."""
    tr, sc = _reduced(path)
    parts = [sc[f"{b}_s"] for b in scopes.BUCKETS]
    assert sum(parts) == pytest.approx(tr["busy_s"], rel=1e-9)
    assert sum(parts[1:]) == pytest.approx(tr["xla_s"], rel=1e-9)
    leaf = sum(t for _, _, t in sc["leaf"])
    assert leaf + sc["unscoped_s"] == pytest.approx(tr["busy_s"], rel=1e-9)


@pytest.mark.parametrize("path", NEW, ids=os.path.basename)
def test_program_ops_are_scoped(path):
    """On the scoped fixtures no op whose source line lies in the program
    (``src/repro``) is unscoped; what is left unscoped is XLA's own, and
    every leaf path starts at a root."""
    tr, sc = _reduced(path)
    assert sc["scoped"] and sc["staging_s"] > 0 and sc["app_glue_s"] > 0
    program = [row for row in sc["unscoped_ops"] if "src/repro/" in row[1]]
    assert program == []
    assert all(p.split("/", 1)[0] in ROOTS for _, p, _ in sc["leaf"])


def test_scope_paths_and_buckets():
    path = scopes.scope_path
    stack = ("jit(solve)/while/body/cg/normal/launch/wilson_normal/"
             "jit(wilson_normal)/stage_in/jit(_pad)/pad:")
    assert path(stack, ROOTS) == "cg/normal/launch/wilson_normal/stage_in"
    # a program level that happens to be named like a loop level stays
    assert path("jit(solve)/milc/rhs/launch/body/jit(body)/pad", ROOTS) == (
        "milc/rhs/launch/body")
    # a relayout inside a launch's staging counts as staging
    relayout = ("jit(step)/ludwig/lb/launch/ludwig_lb_step/"
                "jit(ludwig_lb_step)/stage_out/field/relayout/reshape:")
    p = path(relayout, ROOTS)
    assert p == "ludwig/lb/launch/ludwig_lb_step/stage_out/field/relayout"
    assert scopes.bucket(p, False) == "staging"
    glue = path("jit(step)/ludwig/gradients/field/relayout/reshape", ROOTS)
    assert glue == "ludwig/gradients/field/relayout"
    assert scopes.bucket(glue, False) == "app_glue"
    assert path("jit(<unknown>)/jit(fn)/jit(_pad)/slice:", ROOTS) == ""
    assert path("", ROOTS) == ""
    assert path(stack, ()) == ""  # a program that declares no roots
    assert scopes.bucket("", False) == "unscoped"
    assert scopes.bucket("", True) == "kernel"
    assert scopes.bucket("cg/normal/launch/wilson_normal", True) == "kernel"
