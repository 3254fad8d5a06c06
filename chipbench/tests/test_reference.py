"""The plain references agree with the program at a small size, and the
slab-wise check agrees with a whole-lattice one."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, inputs
from chipbench.reference import ludwig_lc, wilson

PARAMS = dict(a0=0.01, gamma=3.0, kappa=0.01, gamma_rot=0.3, xi=0.7,
              tau=0.8, dt=1.0)


def _program_steps(lat, n):
    from repro.apps.ludwig import driver as lud
    from repro.core import SOA, Field, TargetConfig

    d, q = inputs.ludwig_state(inputs.key(3, 0), lattice=lat)
    st = lud.LudwigState(Field.from_canonical("dist", d, lat, SOA),
                         Field.from_canonical("q", q, lat, SOA))
    cfg = lud.LudwigConfig(lattice=lat, target=TargetConfig("jnp"))
    step = jax.jit(functools.partial(lud.step, cfg=cfg))
    for _ in range(n):
        st = step(st)
    before = (st.dist.canonical_nd(), st.q.canonical_nd())
    st = step(st)
    return before, (st.dist.canonical_nd(), st.q.canonical_nd())


def test_ludwig_reference_matches_the_program():
    (d0, q0), (d1, q1) = _program_steps((8, 8, 16), 5)
    dr, qr = jax.jit(functools.partial(ludwig_lc.step, **PARAMS))(d0, q0)
    assert float(jnp.abs(dr - d1).max()) < 1e-7
    assert float(jnp.abs(qr - q1).max() / jnp.abs(qr).max()) < 1e-6


def test_slabwise_errors_equal_whole_lattice_errors():
    (d0, q0), (d1, q1) = _program_steps((12, 8, 16), 3)
    whole = compare.ludwig_step_errors(d0, q0, d1, q1, PARAMS, slab=12)
    slabs = compare.ludwig_step_errors(d0, q0, d1, q1, PARAMS, slab=4)
    assert whole == slabs
    # and the slab-wise reference step is the whole one
    dr, qr = compare.ludwig_reference_step(d0, q0, PARAMS, slab=4,
                                           dtype=jnp.float32)
    dw, qw = jax.jit(functools.partial(ludwig_lc.step, **PARAMS))(d0, q0)
    np.testing.assert_array_equal(np.asarray(dr), np.asarray(dw))
    np.testing.assert_array_equal(np.asarray(qr), np.asarray(qw))


def test_wilson_reference_matches_the_program():
    from repro.kernels.wilson_dslash.ref import dslash_ref

    lat = (4, 4, 4, 8)
    u = inputs.su3_links(inputs.key(3, 1), lattice=lat, hot=0.6)
    b = inputs.spinors(inputs.key(3, 2), lattice=lat, count=1)[0]
    u, b = u.reshape((72,) + lat), b.reshape((24,) + lat)
    got, want = wilson.dslash(b, u), dslash_ref(b, u)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-6


def test_wilson_mdag_is_the_adjoint():
    lat = (2, 2, 4, 4)
    u = inputs.su3_links(inputs.key(4, 1), lattice=lat, hot=0.6)
    a, b = inputs.spinors(inputs.key(4, 2), lattice=lat, count=2)
    u, a, b = (f.reshape(f.shape[:1] + lat) for f in (u, a, b))
    lhs = jnp.vdot(a, wilson.apply_m(b, u, 0.12))
    rhs = jnp.vdot(wilson.apply_mdag(a, u, 0.12), b)
    assert abs(float(lhs - rhs)) < 1e-4 * abs(float(lhs))


def test_reference_cg_solves():
    lat = (2, 2, 4, 4)
    u = inputs.su3_links(inputs.key(5, 1), lattice=lat, hot=0.6)
    b = inputs.spinors(inputs.key(5, 2), lattice=lat, count=1)[0]
    x, it = compare.wilson_control_solution(u, b, 0.12, lat, tol=1e-12,
                                            max_iter=500, dtype=jnp.float32)
    assert 0 < it < 500
    assert compare.wilson_residual(u, b, x, 0.12, lat) < 1e-5
