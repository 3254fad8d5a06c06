"""The comparisons that decide ``correct``: the program's answers against
the plain references in ``chipbench/reference``.

Each function returns the numbers compared, as plain floats; the limits
they are held to are in the configuration files.  Both references run on
one device, in blocks small enough to fit beside what the program left.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import ludwig_lc, wilson


def drawn(seed: int, k: int, n: int) -> list:
    """``k`` distinct indices out of ``range(n)``, drawn from the seed
    before the window opens, in increasing order."""
    rng = np.random.default_rng([seed, 0xC0FFEE])
    return sorted(int(i) for i in rng.choice(n, size=min(k, n),
                                             replace=False))


_take_planes = jax.jit(lambda a, idx: jnp.take(a, idx, axis=1))


def _take(device):
    """x-planes ``idx`` of a (possibly sharded) (ncomp, X, ...) array,
    gathered onto ``device``."""
    return lambda a, idx: jax.device_put(_take_planes(a, idx), device)


@functools.partial(jax.jit,
                   static_argnames=("params", "reach", "dtype", "stream"))
def _ludwig_ref_slab(d, q, *, params, reach, dtype, stream=True):
    """The reference step on one x-slab and its halo, cropped, in float32
    (computed in ``dtype``)."""
    dr, qr = ludwig_lc.step(d.astype(dtype), q.astype(dtype), **dict(params),
                            stream=stream)
    return (dr[:, reach:-reach].astype(jnp.float32),
            qr[:, reach:-reach].astype(jnp.float32))


@jax.jit
def _gaps(d_in, d_got, q_got, dr, qr):
    return jnp.stack([jnp.max(jnp.abs(d_got - dr)),
                      jnp.max(jnp.abs(dr - d_in)),
                      jnp.max(jnp.abs(q_got - qr)), jnp.max(jnp.abs(qr))])


def _slabs(X, slab):
    slab = min(slab, X)
    if X % slab:
        raise ValueError(f"slab {slab} does not divide the x extent {X}")
    R = ludwig_lc.REACH
    for x0 in range(0, X, slab):
        yield (jnp.asarray(np.arange(x0 - R, x0 + slab + R) % X),
               jnp.asarray(np.arange(x0, x0 + slab)))


def _ref_slabs(d_in, q_in, params, slab, dtype, device, stream=True):
    take = _take(device)
    key = tuple(sorted(params.items()))
    for halo, inner in _slabs(d_in.shape[1], slab):
        yield inner, _ludwig_ref_slab(
            take(d_in, halo), take(q_in, halo), params=key,
            reach=ludwig_lc.REACH, dtype=jnp.dtype(dtype), stream=stream)


def ludwig_step_errors(d_in, q_in, d_out, q_out, params, *, slab,
                       device=None):
    """Largest gap between the program's step (d_in, q_in) -> (d_out, q_out)
    and the float32 reference step, per field: ``{"dist_err": ...,
    "q_err": ...}``.

    ``dist_err`` is relative to the largest change the reference step
    makes to ``dist``, not to ``dist`` itself: a fluid near rest keeps
    every distribution within a hair of its weight (1/3, 1/18, 1/36), so a
    gap measured against those weights could not tell a step that moved
    ``dist`` from one that left it alone.  ``q_err`` is relative to the
    largest ``|q|``: the Q update is of the order of Q itself.

    The reference runs slab by slab along x, each slab with ``REACH``
    planes of periodic halo on either side, so it needs a fraction of the
    memory the whole lattice would.
    """
    device = device or jax.devices()[0]
    take = _take(device)
    acc = np.zeros(4)
    for inner, (dr, qr) in _ref_slabs(d_in, q_in, params, slab,
                                      jnp.float32, device):
        part = _gaps(take(d_in, inner), take(d_out, inner),
                     take(q_out, inner), dr, qr)
        acc = np.maximum(acc, np.asarray(part, np.float64))
    return {"dist_err": float(acc[0] / acc[1]),
            "q_err": float(acc[2] / acc[3])}


def ludwig_reference_step(d_in, q_in, params, *, slab, dtype, device=None,
                          stream=True):
    """The reference's whole step computed in ``dtype`` (bfloat16 for the
    control), assembled from slabs on ``device``, as float32; with
    ``stream=False``, the planted fault of a step that skips streaming."""
    device = device or jax.devices()[0]
    ds, qs = [], []
    for _, (dr, qr) in _ref_slabs(d_in, q_in, params, slab, dtype, device,
                                  stream):
        ds.append(dr)
        qs.append(qr)
    return jnp.concatenate(ds, axis=1), jnp.concatenate(qs, axis=1)


def _nd(a, lattice):
    return a.reshape(a.shape[:1] + lattice)


@functools.partial(jax.jit, static_argnames=("kappa", "lattice"))
def _residual(u, b, x, *, kappa, lattice):
    return wilson.relative_residual(_nd(u, lattice), _nd(b, lattice),
                                    _nd(x, lattice), kappa=kappa)


def wilson_residual(u, b, x, kappa, lattice):
    """|b - M x| / |b| under the reference operator, in float32; the
    fields are (ncomp, sites) on ``lattice``."""
    return float(_residual(u, b, x, kappa=float(kappa),
                           lattice=tuple(lattice)))


@functools.partial(jax.jit,
                   static_argnames=("kappa", "tol", "max_iter", "lattice",
                                    "dtype"))
def _control(u, b, *, kappa, tol, max_iter, lattice, dtype):
    x, it = wilson.cg_solve(_nd(u, lattice).astype(dtype),
                            _nd(b, lattice).astype(dtype), kappa=kappa,
                            tol=tol, max_iter=max_iter)
    return x.astype(jnp.float32).reshape(b.shape), it


def wilson_control_solution(u, b, kappa, lattice, *, tol, max_iter,
                            dtype=jnp.bfloat16):
    """The reference CG run in ``dtype`` in the program's place: its
    solution as float32 (ncomp, sites), to be judged like the program's,
    and its iteration count."""
    x, it = _control(u, b, kappa=float(kappa), tol=float(tol),
                     max_iter=int(max_iter), lattice=tuple(lattice),
                     dtype=jnp.dtype(dtype))
    return x, int(it)
