"""Batched multi-simulation serving: batched CG bit-identity against
independent solves, convergence-mask invariance, the shape-bucketed
request scheduler draining mixed-shape streams, and the generate()
sampling-path regression (temperature > 0 with the default rng)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.milc import driver, fields
from repro.apps.milc.cg import make_wilson_op
from repro.core import Field, SOA, TargetConfig
from repro.launch.serve import SolveRequest, SolveServer

LAT = (4, 4, 4, 8)


def _cfg(engine, lattice=LAT, max_iter=40):
    return driver.MilcConfig(lattice=lattice, kappa=0.10, tol=1e-8,
                             max_iter=max_iter, layout=SOA,
                             target=TargetConfig(engine, vvl=128))


def _sources(cfg, n, seed0=10):
    return [Field.from_numpy(
        "b", fields.random_spinor(cfg.lattice, seed=seed0 + i),
        cfg.lattice, cfg.layout) for i in range(n)]


def _filtered(cfg, u, b, n=6):
    """Spectrally filter a source (repeated normal-operator applications)
    so its CG converges at a different iteration count — exercises the
    frozen-slot path while the rest of the batch keeps iterating."""
    _, _, apply_normal = make_wilson_op(u, cfg.kappa, cfg.target)
    for _ in range(n):
        b = apply_normal(b)
    return b.with_data(b.data / jnp.linalg.norm(b.data))


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_solve_batched_bitwise_vs_independent_solves(engine):
    """A batch of solves with *divergent* convergence points (one slot
    freezes early, one slot is empty) — every live request's x, iteration
    count and residual are bitwise the dedicated single solve's."""
    cfg = _cfg(engine)
    u, _ = driver.init_problem(cfg, seed=0)
    bs = _sources(cfg, 3)
    bs[1] = _filtered(cfg, u, bs[1])          # converges earlier
    bs[2] = bs[2].with_data(bs[2].data * 0.0)  # empty slot
    res = driver.solve_batched(cfg, u, bs)
    its = [int(i) for i in res.iterations]
    assert its[1] < its[0], its  # the freeze path actually ran
    assert its[2] == 0 and not np.any(np.asarray(res.x.element(2).data))
    for i in (0, 1):
        r1 = driver.solve(cfg, u, bs[i])
        np.testing.assert_array_equal(np.asarray(res.x.element(i).data),
                                      np.asarray(r1.x.data))
        assert its[i] == int(r1.iterations)
        np.testing.assert_array_equal(np.asarray(res.residual[i]),
                                      np.asarray(r1.residual))


def test_convergence_mask_invariance():
    """A request's trajectory must not depend on its batch neighbours:
    solve the same source next to a fast-converging neighbour and next to
    an empty slot — identical bits both times."""
    cfg = _cfg("jnp")
    u, _ = driver.init_problem(cfg, seed=0)
    b0, b1 = _sources(cfg, 2)
    fast = _filtered(cfg, u, b1)
    empty = b1.with_data(b1.data * 0.0)
    r_fast = driver.solve_batched(cfg, u, [b0, fast])
    r_empty = driver.solve_batched(cfg, u, [b0, empty])
    np.testing.assert_array_equal(np.asarray(r_fast.x.element(0).data),
                                  np.asarray(r_empty.x.element(0).data))
    assert int(r_fast.iterations[0]) == int(r_empty.iterations[0])
    np.testing.assert_array_equal(np.asarray(r_fast.residual[0]),
                                  np.asarray(r_empty.residual[0]))


def test_scheduler_drains_mixed_shapes_bitwise():
    """Mixed-shape request stream through the bucketed scheduler, more
    requests than slots (so slots drain and refill mid-flight): every
    completed solve is bitwise the dedicated driver.solve result."""
    shapes = [LAT, (4, 4, 8, 8)]
    cfgs, us, reqs, oracle = {}, {}, [], {}
    for i, lat in enumerate(shapes):
        cfg = _cfg("jnp", lattice=lat)
        u, _ = driver.init_problem(cfg, seed=i)
        cfgs[lat], us[lat] = cfg, u
        for j in range(3):
            rid = 10 * i + j
            b = _sources(cfg, 1, seed0=100 + rid)[0]
            reqs.append(SolveRequest(rid=rid, b=b))
            oracle[rid] = driver.solve(cfg, u, b)
    server = SolveServer(cfgs[LAT].target, slots=2, tol=cfgs[LAT].tol,
                         max_iter=cfgs[LAT].max_iter)
    for lat in shapes:
        server.register(us[lat], cfgs[lat].kappa)
    # interleave shapes in the submission order
    for req in sorted(reqs, key=lambda r: r.rid % 10):
        server.submit(req)
    results = server.run()
    assert sorted(results) == sorted(o.rid for o in reqs)
    for rid, out in results.items():
        want = oracle[rid]
        np.testing.assert_array_equal(np.asarray(out.x.data),
                                      np.asarray(want.x.data))
        assert out.iterations == int(want.iterations)
        assert out.residual == float(want.residual)


def _mixed_shape_server():
    """The mixed-shape 6-request workload of the drain test, rebuilt from
    scratch (fresh buckets, fresh queues) so back-to-back runs are
    independent."""
    shapes = [LAT, (4, 4, 8, 8)]
    cfgs, us, reqs = {}, {}, []
    for i, lat in enumerate(shapes):
        cfg = _cfg("jnp", lattice=lat)
        u, _ = driver.init_problem(cfg, seed=i)
        cfgs[lat], us[lat] = cfg, u
        for j in range(3):
            rid = 10 * i + j
            b = _sources(cfg, 1, seed0=100 + rid)[0]
            reqs.append(SolveRequest(rid=rid, b=b))
    server = SolveServer(cfgs[LAT].target, slots=2, tol=cfgs[LAT].tol,
                         max_iter=cfgs[LAT].max_iter)
    for lat in shapes:
        server.register(us[lat], cfgs[lat].kappa)
    for req in sorted(reqs, key=lambda r: r.rid % 10):
        server.submit(req)
    return server


def test_drain_telemetry_matches_oracle_trace_and_disabled_is_bitwise():
    """One drain with telemetry off, one with it on: the admission/harvest
    counters, per-bucket tick counters, queue-depth/occupancy gauges and
    per-request admission->harvest spans must replay the scheduler's
    oracle request trace exactly — and the enabled run's solves must be
    bitwise identical to the disabled run's (observability never touches
    the computation)."""
    from repro.core import telemetry

    telemetry.disable()
    telemetry.reset()
    res_off = _mixed_shape_server().run()
    assert telemetry.events() == []  # disabled: no spans recorded
    telemetry.reset_counters("serve.")

    telemetry.enable()
    try:
        server = _mixed_shape_server()
        res_on = server.run()
    finally:
        telemetry.disable()

    # disabled vs enabled: bitwise identical outcomes
    assert sorted(res_on) == sorted(res_off)
    for rid, off in res_off.items():
        on = res_on[rid]
        np.testing.assert_array_equal(np.asarray(off.x.data),
                                      np.asarray(on.x.data))
        assert off.iterations == on.iterations
        assert off.residual == on.residual

    n = len(res_on)
    assert telemetry.counter_value("serve.admitted") == n
    assert telemetry.counter_value("serve.harvested") == n
    total_ticks = sum(b.iterations_run for b in server.buckets.values())
    assert telemetry.counter_value("serve.ticks") == total_ticks
    for b in server.buckets.values():
        assert (telemetry.counter_value(f"serve.ticks.{b.label}")
                == b.iterations_run)
        # 3 requests through 2 slots: depth starts at 3, drains to 0;
        # occupancy peaks at the slot count
        depth = [v for _, v in
                 telemetry.gauges(f"serve.queue_depth.{b.label}")
                 [f"serve.queue_depth.{b.label}"]]
        assert depth[0] == 3 and max(depth) == 3 and depth[-1] == 0
        occ = [v for _, v in
               telemetry.gauges(f"serve.slot_occupancy.{b.label}")
               [f"serve.slot_occupancy.{b.label}"]]
        assert max(occ) == 2

    # per-request latency spans bracket exactly the active iterations
    spans = telemetry.events("serve/request")
    assert len(spans) == n
    for e in spans:
        a = e["attrs"]
        assert a["harvest_tick"] - a["admit_tick"] == a["iterations"]
        assert a["iterations"] == res_on[a["rid"]].iterations
    (drain,) = telemetry.events("serve/drain")
    assert drain["attrs"]["requests"] == n
    assert len(telemetry.events("serve/tick")) == total_ticks


def test_serve_sync_span_brackets_the_harvest():
    """Each tick's host wait for its device work (the harvest's liveness
    read) is a ``serve/sync`` span of its own, after the tick's dispatch
    span and before the next tick: one per tick, per bucket."""
    from repro.core import telemetry

    telemetry.disable()
    telemetry.reset()
    telemetry.enable()
    try:
        server = _mixed_shape_server()
        server.run()
    finally:
        telemetry.disable()
    ticks = telemetry.events("serve/tick")
    syncs = telemetry.events("serve/sync")
    assert len(syncs) == len(ticks) == sum(
        b.iterations_run for b in server.buckets.values())
    for t, s in zip(ticks, syncs):
        assert s["attrs"]["bucket"] == t["attrs"]["bucket"]
        assert s["attrs"]["tick"] == t["attrs"]["tick"]
        assert s["ts"] >= t["ts"] + t["dur"]
    for s, t_next in zip(syncs, ticks[1:]):
        assert t_next["ts"] >= s["ts"] + s["dur"]
    telemetry.reset()


def test_scheduler_rejects_unregistered_shape():
    cfg = _cfg("jnp")
    server = SolveServer(cfg.target)
    b = _sources(cfg, 1)[0]
    with pytest.raises(KeyError, match="no operator registered"):
        server.submit(SolveRequest(rid=0, b=b))


# -- generate() sampling-path regression --------------------------------------

def _lm():
    from repro.configs import get_arch
    from repro.models import init_params

    cfg = dataclasses.replace(get_arch("olmo-1b", smoke=True),
                              dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    return cfg, params, prompt


def test_generate_greedy_path():
    from repro.train.serve_step import generate

    cfg, params, prompt = _lm()
    out = generate(params, cfg, prompt, steps=4, s_max=32)
    assert out.shape == (1, 12) and out.dtype == jnp.int32


def test_generate_sampled_path_default_rng():
    """temperature > 0 with rng left at None used to crash in
    jax.random.split(None); it must sample with a fixed default key."""
    from repro.train.serve_step import generate

    cfg, params, prompt = _lm()
    out = generate(params, cfg, prompt, steps=4, s_max=32, temperature=0.7)
    out2 = generate(params, cfg, prompt, steps=4, s_max=32, temperature=0.7)
    assert out.shape == (1, 12)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # an explicit key still drives the sample stream
    out3 = generate(params, cfg, prompt, steps=4, s_max=32, temperature=0.7,
                    rng=jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out3))
