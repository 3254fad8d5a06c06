r"""SU(3) x Dirac algebra on split re/im arrays (TPU has no complex dtype).

Conventions (MILC/DeGrand-Rossi basis):
  - A Wilson spinor at a site is psi[s, c] with s in 0..3 (spin), c in 0..2
    (color), complex.  Stored as two real arrays (re, im) of shape
    (4, 3, ...) where ... are site/vector dims.
  - A gauge link is U[a, b], 3x3 complex, stored as (3, 3, ...) pairs.
  - gamma matrices in the DeGrand-Rossi basis; the Wilson hopping term uses
    the spin projectors P^\mp_mu = (1 -+ gamma_mu)/2 to halve the work
    ("Extract" in MILC = apply the projector, "Mult" = SU(3) x half-spinor).

All routines are shape-polymorphic jnp code: they trace identically inside
a pallas kernel body (VVL trailing axis) and in whole-lattice jnp form —
the single-source property the paper demands.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

Pair = Tuple[jnp.ndarray, jnp.ndarray]  # (re, im)


# ---------------------------------------------------------------------------
# complex primitives on (re, im) pairs
# ---------------------------------------------------------------------------

def cmul(a: Pair, b: Pair) -> Pair:
    ar, ai = a
    br, bi = b
    return ar * br - ai * bi, ar * bi + ai * br


def cmul_conj(a: Pair, b: Pair) -> Pair:
    """conj(a) * b."""
    ar, ai = a
    br, bi = b
    return ar * br + ai * bi, ar * bi - ai * br


def cadd(a: Pair, b: Pair) -> Pair:
    return a[0] + b[0], a[1] + b[1]


def csub(a: Pair, b: Pair) -> Pair:
    return a[0] - b[0], a[1] - b[1]


def cscale(a: Pair, s) -> Pair:
    return a[0] * s, a[1] * s


def ci_mul(a: Pair) -> Pair:
    """i * a."""
    return -a[1], a[0]


def cneg_i_mul(a: Pair) -> Pair:
    """-i * a."""
    return a[1], -a[0]


# ---------------------------------------------------------------------------
# SU(3) action on color vectors
# ---------------------------------------------------------------------------

def _mat_vec(m: jnp.ndarray, v: jnp.ndarray, adj: bool = False) -> jnp.ndarray:
    """sum_b m[a, b] v[b] (m[b, a] when ``adj``) for a real 3x3 colour
    matrix m (3,3,...) and colour vector v (3,...).  Unrolled over
    Python-int colour indices, as lc.py unrolls its 3x3 algebra: a length-3
    contraction is too small for the MXU, and elementwise multiply-adds are
    what a Pallas TPU kernel can lower (Mosaic has no batched dot_general
    over trailing site axes)."""
    rows = []
    for a in range(3):
        acc = None
        for b in range(3):
            t = (m[b, a] if adj else m[a, b]) * v[b]
            acc = t if acc is None else acc + t
        rows.append(acc)
    return jnp.stack(rows)


def su3_mult_vec(u: Pair, v: Pair) -> Pair:
    """(U v): u = (3,3,...), v = (3,...) -> (3,...)."""
    ur, ui = u
    vr, vi = v
    return (_mat_vec(ur, vr) - _mat_vec(ui, vi),
            _mat_vec(ur, vi) + _mat_vec(ui, vr))


def su3_adj_mult_vec(u: Pair, v: Pair) -> Pair:
    """(U^dagger v)."""
    ur, ui = u
    vr, vi = v
    return (_mat_vec(ur, vr, True) + _mat_vec(ui, vi, True),
            _mat_vec(ur, vi, True) - _mat_vec(ui, vr, True))


def _per_spin(mult, u: Pair, h: Pair) -> Pair:
    parts = [mult(u, (h[0][s], h[1][s])) for s in range(h[0].shape[0])]
    return jnp.stack([p[0] for p in parts]), jnp.stack([p[1] for p in parts])


def su3_mult_halfspinor(u: Pair, h: Pair) -> Pair:
    """(U h) with an explicit leading spin axis: u (3,3,...), h (s,3,...)."""
    return _per_spin(su3_mult_vec, u, h)


def su3_adj_mult_halfspinor(u: Pair, h: Pair) -> Pair:
    """(U^dagger h) with an explicit leading spin axis."""
    return _per_spin(su3_adj_mult_vec, u, h)


# ---------------------------------------------------------------------------
# Wilson spin projection (DeGrand-Rossi gamma basis)
#
# gamma_x = [[0,0,0,i],[0,0,i,0],[0,-i,0,0],[-i,0,0,0]]
# gamma_y = [[0,0,0,-1],[0,0,1,0],[0,1,0,0],[-1,0,0,0]]
# gamma_z = [[0,0,i,0],[0,0,0,-i],[-i,0,0,0],[0,i,0,0]]
# gamma_t = [[0,0,1,0],[0,0,0,1],[1,0,0,0],[0,1,0,0]]
#
# P^-_mu = (1 - gamma_mu)/2 projects a 4-spinor to an effective 2-spinor
# (rows 2,3 are +-(i) linear combinations of rows 0,1); "project" returns
# the upper two spin components h[0:2], "reconstruct" rebuilds all four.
# ---------------------------------------------------------------------------

def _sp(psi: Pair, s: int) -> Pair:
    return psi[0][s], psi[1][s]


def project_minus(psi: Pair, mu: int) -> Pair:
    """h = upper two spin rows of (1 - gamma_mu) psi. psi: (4,3,...)."""
    p0, p1, p2, p3 = (_sp(psi, s) for s in range(4))
    if mu == 0:  # x: h0 = p0 - i p3, h1 = p1 - i p2
        h0 = csub(p0, ci_mul(p3))
        h1 = csub(p1, ci_mul(p2))
    elif mu == 1:  # y: h0 = p0 + p3, h1 = p1 - p2
        h0 = cadd(p0, p3)
        h1 = csub(p1, p2)
    elif mu == 2:  # z: h0 = p0 - i p2, h1 = p1 + i p3
        h0 = csub(p0, ci_mul(p2))
        h1 = cadd(p1, ci_mul(p3))
    else:  # t: h0 = p0 - p2, h1 = p1 - p3
        h0 = csub(p0, p2)
        h1 = csub(p1, p3)
    return (
        jnp.stack([h0[0], h1[0]]),
        jnp.stack([h0[1], h1[1]]),
    )


def project_plus(psi: Pair, mu: int) -> Pair:
    """h = upper two spin rows of (1 + gamma_mu) psi."""
    p0, p1, p2, p3 = (_sp(psi, s) for s in range(4))
    if mu == 0:
        h0 = cadd(p0, ci_mul(p3))
        h1 = cadd(p1, ci_mul(p2))
    elif mu == 1:
        h0 = csub(p0, p3)
        h1 = cadd(p1, p2)
    elif mu == 2:
        h0 = cadd(p0, ci_mul(p2))
        h1 = csub(p1, ci_mul(p3))
    else:
        h0 = cadd(p0, p2)
        h1 = cadd(p1, p3)
    return (
        jnp.stack([h0[0], h1[0]]),
        jnp.stack([h0[1], h1[1]]),
    )


def reconstruct_minus(h: Pair, mu: int) -> Pair:
    """Rebuild the 4-spinor (1 - gamma_mu) psi from its half-spinor h."""
    h0 = (h[0][0], h[1][0])
    h1 = (h[0][1], h[1][1])
    if mu == 0:  # p2 = i h1, p3 = i h0
        p2, p3 = ci_mul(h1), ci_mul(h0)
    elif mu == 1:  # p2 = -h1, p3 = h0
        p2, p3 = cscale(h1, -1.0), h0
    elif mu == 2:  # p2 = i h0, p3 = -i h1
        p2, p3 = ci_mul(h0), cneg_i_mul(h1)
    else:  # t: p2 = -h0, p3 = -h1
        p2, p3 = cscale(h0, -1.0), cscale(h1, -1.0)
    return (
        jnp.stack([h0[0], h1[0], p2[0], p3[0]]),
        jnp.stack([h0[1], h1[1], p2[1], p3[1]]),
    )


def reconstruct_plus(h: Pair, mu: int) -> Pair:
    """Rebuild the 4-spinor (1 + gamma_mu) psi from its half-spinor h."""
    h0 = (h[0][0], h[1][0])
    h1 = (h[0][1], h[1][1])
    if mu == 0:
        p2, p3 = cneg_i_mul(h1), cneg_i_mul(h0)
    elif mu == 1:
        p2, p3 = h1, cscale(h0, -1.0)
    elif mu == 2:
        p2, p3 = cneg_i_mul(h0), ci_mul(h1)
    else:
        p2, p3 = h0, h1
    return (
        jnp.stack([h0[0], h1[0], p2[0], p3[0]]),
        jnp.stack([h0[1], h1[1], p2[1], p3[1]]),
    )


# ---------------------------------------------------------------------------
# dense gamma matrices (oracle checks in tests)
# ---------------------------------------------------------------------------

def gamma_dense(mu: int) -> np.ndarray:
    i = 1j
    g = {
        0: np.array(
            [[0, 0, 0, i], [0, 0, i, 0], [0, -i, 0, 0], [-i, 0, 0, 0]]
        ),
        1: np.array(
            [[0, 0, 0, -1], [0, 0, 1, 0], [0, 1, 0, 0], [-1, 0, 0, 0]]
        ),
        2: np.array(
            [[0, 0, i, 0], [0, 0, 0, -i], [-i, 0, 0, 0], [0, i, 0, 0]]
        ),
        3: np.array(
            [[0, 0, 1, 0], [0, 0, 0, 1], [1, 0, 0, 0], [0, 1, 0, 0]]
        ),
    }[mu]
    return g.astype(np.complex128)
