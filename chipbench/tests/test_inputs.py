"""Device-side inputs: seeded, unitary links, the Ludwig start state."""

import numpy as np

from chipbench import inputs


def test_links_unitary_by_the_programs_measure():
    from repro.apps.milc import fields

    u = inputs.su3_links(inputs.key(2**31 + 11, 1), lattice=(4, 4, 4, 8),
                         hot=0.6)
    assert u.shape == (72, 4 * 4 * 4 * 8)
    assert fields.unitarity_violation(np.asarray(u).reshape(72, 4, 4, 4, 8)) < 1e-5
    assert float(inputs.unitarity_violation(u)) < 1e-5
    # special unitary: det U = 1
    g = np.asarray(u).reshape(4, 3, 3, 2, -1)
    c = np.moveaxis(g[:, :, :, 0] + 1j * g[:, :, :, 1], -1, 1)
    assert np.abs(np.linalg.det(c) - 1).max() < 1e-5


def test_links_disorder_matches_the_host_generator():
    """The same recipe as ``fields.random_su3_gauge``: the spread of the
    link components agrees with the host generator's at the same ``hot``."""
    from repro.apps.milc import fields

    lat = (4, 4, 4, 8)
    dev = np.asarray(inputs.su3_links(inputs.key(5, 1), lattice=lat, hot=0.6))
    host = fields.random_su3_gauge(lat, seed=5, hot=0.6)
    assert abs(dev.std() - host.std()) < 0.02
    assert abs(np.abs(dev).mean() - np.abs(host).mean()) < 0.02


def test_same_seed_same_inputs_and_seeds_differ():
    a = inputs.spinors(inputs.key(2**31 + 3, 2), lattice=(2, 2, 2, 4),
                       count=2)
    b = inputs.spinors(inputs.key(2**31 + 3, 2), lattice=(2, 2, 2, 4),
                       count=2)
    c = inputs.spinors(inputs.key(2**31 + 4, 2), lattice=(2, 2, 2, 4),
                       count=2)
    d = inputs.spinors(inputs.key(2**31 + 3 + 2**32, 2),
                       lattice=(2, 2, 2, 4), count=2)
    assert (np.asarray(a) == np.asarray(b)).all()
    assert not (np.asarray(a) == np.asarray(c)).all()
    assert not (np.asarray(a) == np.asarray(d)).all()


def test_ludwig_state_is_fluid_at_rest():
    from repro.kernels.lb_collision import ref as lbref

    d, q = inputs.ludwig_state(inputs.key(1, 0), lattice=(4, 4, 8))
    rho, u = lbref.moments(np.asarray(d).reshape(19, -1))
    np.testing.assert_allclose(np.asarray(rho), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(u), 0.0, atol=1e-7)
    assert q.shape == (5, 4, 4, 8) and 0.005 < float(np.std(q)) < 0.02
