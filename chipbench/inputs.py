"""Seeded inputs made on the device, each in one jitted call.

Every array the benchmark feeds the program comes from here: the Ludwig
start state, the SU(3) gauge links and the Wilson spinor sources.  All of
them are float32 and made from ``--seed`` by ``jax.random``, so that the
same seed gives the same inputs.  The Ludwig state is canonical
``(ncomp, *lattice)``; links and spinors are ``(ncomp, sites)``, the
program's SoA storage, which a TPU holds without padding the lattice's
last axis to 128 lanes.

The gauge generator follows the textbook recipe the program's host
generator also uses: a random traceless anti-hermitian 3x3 generator per
link, scaled by the disorder ``hot``, exponentiated by scaling and
squaring of a degree-12 Taylor series, then projected back onto SU(3)
against float32 rounding.  Complex numbers are kept as
(re, im) pairs and every 3x3 product is written out as elementwise
multiply-adds, so the arithmetic is exact float32 on any backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# D3Q19 weights in Ludwig's velocity order: rest, 6 faces, 12 edges
_D3Q19_W = np.array([1 / 3] + [1 / 18] * 6 + [1 / 36] * 12, np.float32)

GAUGE_NCOMP = 72    # 4 directions x 3 x 3 complex
SPINOR_NCOMP = 24   # 4 spins x 3 colours complex


def key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for one input stream of a run; seeds past 32 bits fold
    their high word in, so no two seeds share a key."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    if seed >> 32:
        k = jax.random.fold_in(k, seed >> 32)
    return jax.random.fold_in(k, stream)


# -- Ludwig --------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("lattice", "q_amp"))
def ludwig_state(k, *, lattice, q_amp=1e-2):
    """(dist (19, *lattice), q (5, *lattice)): a fluid at rest with unit
    density (its equilibrium distributions) and a small random Q tensor,
    the start state ``apps.ludwig.driver.init_state`` describes."""
    shape = (1,) + tuple(lattice)
    dist = jnp.asarray(_D3Q19_W).reshape((19,) + (1,) * len(lattice)) \
        * jnp.ones(shape, jnp.float32)
    q = q_amp * jax.random.normal(k, (5,) + tuple(lattice), jnp.float32)
    return dist, q


# -- Wilson spinors --------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("lattice", "count"))
def spinors(k, *, lattice, count):
    """(count, 24, sites) gaussian sources."""
    return jax.random.normal(
        k, (count, SPINOR_NCOMP, int(np.prod(lattice))), jnp.float32)


# -- SU(3) links ---------------------------------------------------------------

def _cmm(a, b):
    """Complex 3x3 products of (re, im) pairs of (3, 3, n) arrays."""
    ar, ai = a
    br, bi = b
    re = [[None] * 3 for _ in range(3)]
    im = [[None] * 3 for _ in range(3)]
    for i in range(3):
        for j in range(3):
            r = ar[i, 0] * br[0, j] - ai[i, 0] * bi[0, j]
            m = ar[i, 0] * bi[0, j] + ai[i, 0] * br[0, j]
            for kk in (1, 2):
                r = r + ar[i, kk] * br[kk, j] - ai[i, kk] * bi[kk, j]
                m = m + ar[i, kk] * bi[kk, j] + ai[i, kk] * br[kk, j]
            re[i][j], im[i][j] = r, m
    return (jnp.stack([jnp.stack(row) for row in re]),
            jnp.stack([jnp.stack(row) for row in im]))


def _expm(a, s):
    """exp of (re, im) 3x3 matrices: Taylor 12 on a / 2**s, squared s
    times (``s`` a traced int, as the host generator picks it)."""
    scale = 2.0 ** (-s.astype(jnp.float32))
    x = (a[0] * scale, a[1] * scale)
    eye = jnp.eye(3, dtype=jnp.float32)[:, :, None] * jnp.ones_like(a[0][0, 0])
    out = (eye, jnp.zeros_like(eye))
    term = out
    for n in range(1, 13):
        t = _cmm(term, x)
        term = (t[0] / n, t[1] / n)
        out = (out[0] + term[0], out[1] + term[1])
    return jax.lax.fori_loop(0, s, lambda _, m: _cmm(m, m), out)


def _reunitarize(m):
    """Project (re, im) 3x3 matrices back onto SU(3), as lattice codes do
    after float32 arithmetic: normalise row 0, orthonormalise row 1
    against it, and row 2 = conj(row 0 x row 1)."""
    r, i = [m[0][a] for a in range(3)], [m[1][a] for a in range(3)]

    def norm(vr, vi):
        n = jax.lax.rsqrt(sum(vr[c] ** 2 + vi[c] ** 2 for c in range(3)))
        return [vr[c] * n for c in range(3)], [vi[c] * n for c in range(3)]

    r0, i0 = norm(r[0], i[0])
    # <row0, row1> = sum conj(row0) row1
    dr = sum(r0[c] * r[1][c] + i0[c] * i[1][c] for c in range(3))
    di = sum(r0[c] * i[1][c] - i0[c] * r[1][c] for c in range(3))
    r1, i1 = norm([r[1][c] - (dr * r0[c] - di * i0[c]) for c in range(3)],
                  [i[1][c] - (dr * i0[c] + di * r0[c]) for c in range(3)])
    r2, i2 = [], []
    for c in range(3):
        a, b = (c + 1) % 3, (c + 2) % 3
        # (row0 x row1)_c, then conjugated
        xr = r0[a] * r1[b] - i0[a] * i1[b] - (r0[b] * r1[a] - i0[b] * i1[a])
        xi = r0[a] * i1[b] + i0[a] * r1[b] - (r0[b] * i1[a] + i0[b] * r1[a])
        r2.append(xr)
        i2.append(-xi)
    return (jnp.stack([jnp.stack(r0), jnp.stack(r1), jnp.stack(r2)]),
            jnp.stack([jnp.stack(i0), jnp.stack(i1), jnp.stack(i2)]))


@functools.partial(jax.jit, static_argnames=("lattice", "hot"))
def su3_links(k, *, lattice, hot):
    """(72, sites) float32 links, index ((mu*3 + a)*3 + b)*2 + reim, one
    direction at a time to bound the temporaries."""
    vol = int(np.prod(lattice))

    def direction(kmu):
        kr, ki = jax.random.split(kmu)
        gr = jax.random.normal(kr, (3, 3, vol), jnp.float32)
        gi = jax.random.normal(ki, (3, 3, vol), jnp.float32)
        # anti-hermitian part, trace removed, scaled by the disorder
        ahr = 0.5 * (gr - jnp.swapaxes(gr, 0, 1))
        ahi = 0.5 * (gi + jnp.swapaxes(gi, 0, 1))
        tr = (ahi[0, 0] + ahi[1, 1] + ahi[2, 2]) / 3.0
        ahi = ahi - jnp.eye(3, dtype=jnp.float32)[:, :, None] * tr
        ahr, ahi = hot * ahr, hot * ahi
        norm = jnp.max(jnp.sum(jnp.sqrt(ahr * ahr + ahi * ahi), axis=(0, 1)))
        s = jnp.maximum(
            0, jnp.ceil(jnp.log2(norm + 1e-30)).astype(jnp.int32) + 1)
        ur, ui = _reunitarize(_expm((ahr, ahi), s))
        return jnp.stack([ur, ui], axis=2)          # (3, 3, 2, sites)

    u = jax.lax.map(direction, jax.random.split(k, 4))
    return u.reshape(GAUGE_NCOMP, vol)


@jax.jit
def unitarity_violation(u72):
    """max |U U^dag - I| over every link of a (72, sites) field."""
    eye = jnp.eye(3, dtype=jnp.float32)[:, :, None]

    def direction(g):                                # (3, 3, 2, sites)
        ur, ui = g[:, :, 0], g[:, :, 1]
        pr, pi = _cmm((ur, ui),
                      (jnp.swapaxes(ur, 0, 1), -jnp.swapaxes(ui, 0, 1)))
        return jnp.max(jnp.sqrt((pr - eye) ** 2 + pi ** 2))

    return jnp.max(jax.lax.map(direction,
                               u72.reshape((4, 3, 3, 2, u72.shape[-1]))))
