"""End-to-end behaviour: train a small LM until loss drops, then serve it;
run the two paper applications end-to-end."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import init_params
from repro.train.optimizer import OptConfig, init_opt
from repro.train.serve_step import generate
from repro.train.train_step import TrainConfig, build_train_step


@pytest.mark.slow
def test_train_then_serve_roundtrip():
    """Memorize a tiny corpus, then greedy-decode it back."""
    cfg = dataclasses.replace(get_arch("olmo-1b", smoke=True),
                              dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    # corpus: the repeating sequence 1 2 3 ... 16
    period = 16
    seq = (np.arange(64) % period + 1).astype(np.int32)
    tokens = jnp.asarray(seq[None, :-1])
    labels = jnp.asarray(seq[None, 1:])
    batch = {"tokens": tokens, "labels": labels}
    tcfg = TrainConfig(opt=OptConfig(lr=3e-3))
    step = jax.jit(build_train_step(cfg, tcfg))
    opt, ef = init_opt(params, tcfg.opt), None
    loss = None
    for _ in range(60):
        params, opt, ef, m = step(params, opt, ef, batch)
        loss = float(m["loss"])
    assert loss < 0.1, loss

    prompt = jnp.asarray(seq[None, :8].astype(np.int32))
    out = generate(params, cfg, prompt, steps=16, s_max=128)
    got = np.asarray(out)[0, 8:]
    want = (np.arange(8, 24) % period + 1)
    assert (got == want).mean() > 0.9, (got, want)


def test_ludwig_end_to_end():
    from repro.core import TargetConfig
    from repro.apps.ludwig import LudwigConfig, init_state, step
    from repro.apps.ludwig.driver import diagnostics

    cfg = LudwigConfig(lattice=(8, 8, 8), gamma=3.0,
                       target=TargetConfig("jnp"))
    s = init_state(cfg, seed=0)
    jstep = jax.jit(step, static_argnums=1)
    for _ in range(10):
        s = jstep(s, cfg)
    d = diagnostics(s, cfg)
    assert np.isfinite(float(d["free_energy"]))
    assert abs(float(d["mass"]) - 512.0) < 0.01


def test_milc_end_to_end():
    from repro.apps.milc import MilcConfig, init_problem, solve
    from repro.apps.milc.driver import residual_check

    cfg = MilcConfig(lattice=(4, 4, 4, 4), kappa=0.12, tol=1e-10,
                     max_iter=2000, hot=0.8)
    u, b = init_problem(cfg, seed=1)
    res = solve(cfg, u, b)
    assert residual_check(cfg, u, b, res.x) < 1e-3


def test_benchmark_runner_exits_nonzero_when_a_table_fails(monkeypatch,
                                                            capsys):
    """benchmarks/run.py runs every table, reports a failing one as an
    ERROR row, and then exits 1 instead of 0."""
    import sys
    import types
    from pathlib import Path

    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parents[1]))
    import benchmarks

    names = ("table1_ridge", "fig3_kernels", "fig4_bandwidth",
             "fig5_scaling", "lm_roofline")
    ran, failing = [], set()

    def table(name):
        def main():
            ran.append(name)
            if name in failing:
                raise RuntimeError("boom")
        return types.SimpleNamespace(__name__=f"benchmarks.{name}",
                                     main=main)

    for name in names:
        mod = table(name)
        monkeypatch.setitem(sys.modules, f"benchmarks.{name}", mod)
        monkeypatch.setattr(benchmarks, name, mod, raising=False)
    from benchmarks import run

    assert run.main() == 0
    failing.add("fig3_kernels")
    ran.clear()
    assert run.main() == 1
    assert ran == list(names)  # the tables after the failure still ran
    assert ("benchmarks.fig3_kernels,0.0,ERROR=RuntimeError:boom"
            in capsys.readouterr().out)
