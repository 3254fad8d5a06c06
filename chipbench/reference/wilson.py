"""Plain reference for the Wilson-Dirac operator and its CG solve.

Written from the textbook definition (MILC's Wilson action, as in
arXiv:1609.01479 sec. 5), sharing no code with the program:

    M psi = psi - kappa D psi
    D psi(x) = sum_mu (1 - gamma_mu) U_mu(x) psi(x + mu)
                    + (1 + gamma_mu) U_mu(x - mu)^dag psi(x - mu)

with periodic boundaries and the DeGrand-Rossi gamma matrices that MILC
uses, gamma_5 = diag(1, 1, -1, -1), and M^dag = gamma_5 M gamma_5.

Fields are real canonical arrays: a spinor is (24, X, Y, Z, T) with index
(spin*3 + colour)*2 + re/im, a gauge field (72, X, Y, Z, T) with index
((mu*3 + a)*3 + b)*2 + re/im.  All arithmetic is elementwise, so float32
stays float32 on any backend; given bfloat16 arrays it computes in
bfloat16, which is the benchmark's lower-precision control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_I = 1j
GAMMA = [
    np.array([[0, 0, 0, _I], [0, 0, _I, 0], [0, -_I, 0, 0], [-_I, 0, 0, 0]]),
    np.array([[0, 0, 0, -1], [0, 0, 1, 0], [0, 1, 0, 0], [-1, 0, 0, 0]]),
    np.array([[0, 0, _I, 0], [0, 0, 0, -_I], [-_I, 0, 0, 0], [0, _I, 0, 0]]),
    np.array([[0, 0, 1, 0], [0, 0, 0, 1], [1, 0, 0, 0], [0, 1, 0, 0]]),
]


def _spin_apply(p, re, im):
    """(P psi) for a constant complex 4x4 P; re/im are (4, 3, ...)."""
    outr, outi = [], []
    for s in range(4):
        r, i = 0.0, 0.0
        for t in range(4):
            c = complex(p[s, t])
            if c.real:
                r, i = r + c.real * re[t], i + c.real * im[t]
            if c.imag:
                r, i = r - c.imag * im[t], i + c.imag * re[t]
        outr.append(r)
        outi.append(i)
    return jnp.stack(outr), jnp.stack(outi)


def _colour_apply(ur, ui, re, im, adjoint):
    """(U v) or (U^dag v) per spin; U (3, 3, ...), v (4, 3, ...)."""
    outr, outi = [], []
    for a in range(3):
        r, i = 0.0, 0.0
        for b in range(3):
            if adjoint:   # (U^dag)_ab = conj(U_ba)
                mr, mi = ur[b, a], -ui[b, a]
            else:
                mr, mi = ur[a, b], ui[a, b]
            r = r + mr * re[:, b] - mi * im[:, b]
            i = i + mr * im[:, b] + mi * re[:, b]
        outr.append(r)
        outi.append(i)
    return jnp.stack(outr, axis=1), jnp.stack(outi, axis=1)


def dslash(psi, u):
    """D psi for psi (24, *lat) and u (72, *lat), periodic."""
    lat = psi.shape[1:]
    s = psi.reshape((4, 3, 2) + lat)
    g = u.reshape((4, 3, 3, 2) + lat)
    one = np.eye(4)
    accr = acci = 0.0
    for mu in range(4):
        ax = 2 + mu   # lattice axis of (4, 3, *lat)
        fr, fi = (jnp.roll(s[:, :, k], -1, axis=ax) for k in (0, 1))
        br, bi = (jnp.roll(s[:, :, k], 1, axis=ax) for k in (0, 1))
        ur, ui = g[mu, :, :, 0], g[mu, :, :, 1]
        ubr, ubi = (jnp.roll(x, 1, axis=ax) for x in (ur, ui))
        hr, hi = _colour_apply(ur, ui, fr, fi, adjoint=False)
        hr, hi = _spin_apply(one - GAMMA[mu], hr, hi)
        kr, ki = _colour_apply(ubr, ubi, br, bi, adjoint=True)
        kr, ki = _spin_apply(one + GAMMA[mu], kr, ki)
        accr, acci = accr + hr + kr, acci + hi + ki
    return jnp.stack([accr, acci], axis=2).reshape(psi.shape)


def apply_m(psi, u, kappa):
    return psi - kappa * dslash(psi, u)


def gamma5(psi):
    lat = psi.shape[1:]
    s = psi.reshape((2, 12) + lat)
    return jnp.concatenate([s[0], -s[1]]).reshape(psi.shape)


def apply_mdag(psi, u, kappa):
    return gamma5(apply_m(gamma5(psi), u, kappa))


@functools.partial(jax.jit, static_argnames=("kappa",))
def relative_residual(u, b, x, *, kappa):
    """|b - M x| / |b| in float32."""
    r = b - apply_m(x, u, kappa)
    return jnp.sqrt(jnp.sum(r * r) / jnp.sum(b * b))


@functools.partial(jax.jit, static_argnames=("kappa", "tol", "max_iter"))
def cg_solve(u, b, *, kappa, tol, max_iter):
    """CG on the normal equations M^dag M x = M^dag b, in the dtype of the
    arguments, stopping when |r|^2 <= tol |M^dag b|^2 or after max_iter."""
    rhs = apply_mdag(b, u, kappa)
    b2 = jnp.sum(rhs * rhs)

    def body(c):
        x, r, p, rr, it = c
        ap = apply_mdag(apply_m(p, u, kappa), u, kappa)
        alpha = rr / jnp.sum(p * ap)
        x = x + alpha * p
        r = r - alpha * ap
        rr_new = jnp.sum(r * r)
        p = r + (rr_new / rr) * p
        return x, r, p, rr_new, it + 1

    def cond(c):
        return jnp.logical_and(c[3] > tol * b2, c[4] < max_iter)

    x0 = jnp.zeros_like(rhs)
    x, _, _, _, it = jax.lax.while_loop(
        cond, body, (x0, rhs, rhs, b2, jnp.int32(0)))
    return x, it
