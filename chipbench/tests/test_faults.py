"""A run with the timed path broken underneath comes out as not correct.

Each test drives the rest of a run of a tiny cell on the CPU (the look for
a chip skipped) with one fault planted in the program: a step that returns
its state, or only its distributions, unchanged; an answer altered where
it is produced; a solve that stops short of its tolerance.  The unbroken
run of the same cell is correct."""

import dataclasses
import time

import jax.numpy as jnp

from chipbench.run import run_cell
from chipbench.tests import tiny


def _run(workload, seconds=1.0, **over):
    tiny.allow_cpu_peaks()
    spec = tiny.spec(workload)
    spec["traffic"].update(over)
    return run_cell(workload, 2**31 + 17, seconds, False, spec=spec,
                    require_tpu=False, t0=time.perf_counter())


# -- Ludwig, one chip ---------------------------------------------------------

def test_ludwig_sound():
    assert _run("ludwig_lc_192.timesteps")["correct"]


def test_ludwig_state_unchanged(monkeypatch):
    from repro.apps.ludwig import driver as lud

    monkeypatch.setattr(lud, "step", lambda state, cfg: state)
    assert not _run("ludwig_lc_192.timesteps")["correct"]


def test_ludwig_answer_altered(monkeypatch):
    from repro.apps.ludwig import driver as lud

    real = lud.step

    def step(state, cfg):
        out = real(state, cfg)
        q = out.q.data.at[2, 5].add(1e-3)
        return lud.LudwigState(dist=out.dist, q=out.q.with_data(q))

    monkeypatch.setattr(lud, "step", step)
    assert not _run("ludwig_lc_192.timesteps")["correct"]


def test_ludwig_dist_unchanged(monkeypatch):
    """The LB update left out: q advances, dist stays where it was."""
    from repro.apps.ludwig import driver as lud

    real = lud.step

    def step(state, cfg):
        return lud.LudwigState(dist=state.dist, q=real(state, cfg).q)

    monkeypatch.setattr(lud, "step", step)
    r = _run("ludwig_lc_192.timesteps")
    assert not r["correct"]
    assert r["checks"]["dist_err"]["value"] > r["checks"]["dist_err"]["limit"]


def test_ludwig_dist_altered(monkeypatch):
    """One distribution at one site off by half of the step's change."""
    from repro.apps.ludwig import driver as lud

    real = lud.step

    def step(state, cfg):
        out = real(state, cfg)
        change = jnp.max(jnp.abs(out.dist.data - state.dist.data))
        d = out.dist.data.at[7, 5].add(0.5 * change)
        return lud.LudwigState(dist=out.dist.with_data(d), q=out.q)

    monkeypatch.setattr(lud, "step", step)
    r = _run("ludwig_lc_192.timesteps")
    assert not r["correct"]
    assert r["checks"]["dist_err"]["value"] > r["checks"]["dist_err"]["limit"]


# -- MILC solves ----------------------------------------------------------

def test_milc_sound():
    assert _run("milc_wilson_32x64.solves")["correct"]


def test_milc_state_unchanged(monkeypatch):
    from repro.apps.milc import driver as milc

    real = milc.solve

    def solve(cfg, u, b):
        res = real(cfg, u, b)
        return res._replace(x=res.x.with_data(jnp.zeros_like(res.x.data)))

    monkeypatch.setattr(milc, "solve", solve)
    assert not _run("milc_wilson_32x64.solves")["correct"]


def test_milc_answer_altered(monkeypatch):
    from repro.apps.milc import driver as milc

    real = milc.solve

    def solve(cfg, u, b):
        res = real(cfg, u, b)
        return res._replace(x=res.x.with_data(res.x.data * 1.01))

    monkeypatch.setattr(milc, "solve", solve)
    assert not _run("milc_wilson_32x64.solves")["correct"]


def test_milc_stops_short_of_tol(monkeypatch):
    """A solver that stops at a looser tolerance than it is given."""
    from repro.apps.milc import driver as milc

    real = milc.solve

    def solve(cfg, u, b):
        return real(dataclasses.replace(cfg, tol=cfg.tol * 1e3), u, b)

    monkeypatch.setattr(milc, "solve", solve)
    r = _run("milc_wilson_32x64.solves")
    assert not r["correct"]
    check = r["checks"]["reported_rr"]
    assert check["value"] > check["limit"]
