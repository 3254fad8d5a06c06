"""Plain reference for one Ludwig liquid-crystal lattice-Boltzmann timestep.

Straightforward ``jax.numpy`` on canonical ``(ncomp, X, Y, Z)`` arrays with
periodic boundaries, written from the published model (arXiv:1609.01479
sec. 4; Ludwig's Landau-de Gennes free energy, Beris-Edwards dynamics and
D3Q19 BGK lattice Boltzmann with Guo forcing).  It shares no code with the
program.  Every product is elementwise, so float32 stays float32 on any
backend; called with bfloat16 arrays it computes in bfloat16, which makes
it the benchmark's lower-precision control.

One timestep, in the order the program's kernels run them:

1. ``Q`` from its five stored components (XX, XY, XZ, YY, YZ; ZZ = -XX-YY);
   central first derivatives and the 7-point Laplacian.
2. Molecular field ``H = -A0(1-g/3)Q + A0 g(Q^2 - I trQ^2/3) - A0 g Q trQ^2
   + kappa lap Q``, made symmetric and traceless.
3. Chemical stress ``sigma = -xi(H Qi + Qi H) + 2 xi Qi tr(QH) + QH - HQ
   - kappa d_a Q_gd d_b Q_gd`` with ``Qi = Q + I/3``; force
   ``F_a = d_b sigma_ab`` by central differences.
4. Lattice Boltzmann: ``u = (sum c f + F/2) / rho``, BGK relaxation to the
   second-order equilibrium with Guo's forcing term, then streaming
   ``f_i(r) = f*_i(r - c_i)``.
5. ``W_ab = d_b u_a``; first-order upwind advection flux divergence of Q
   with face velocities averaged from the two adjacent sites.
6. Beris-Edwards: ``Q += dt (Gamma H + S(W, Q) - div(u Q))`` with
   ``S = (xi D + O) Qi + Qi (xi D - O) - 2 xi Qi tr(QW)``, made symmetric
   and traceless.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# D3Q19 velocity set in Ludwig's order and its weights
C = np.array(
    [(0, 0, 0),
     (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1),
     (1, 1, 0), (1, -1, 0), (-1, 1, 0), (-1, -1, 0),
     (1, 0, 1), (1, 0, -1), (-1, 0, 1), (-1, 0, -1),
     (0, 1, 1), (0, 1, -1), (0, -1, 1), (0, -1, -1)], np.int64)
W = np.array([1 / 3] + [1 / 18] * 6 + [1 / 36] * 12)
_Q5 = [(0, 0), (0, 1), (0, 2), (1, 1), (1, 2)]

# how far (in sites) one step reads from a site: F at r needs sigma at
# r+-1, which needs grad Q at r+-2; collision and W at r+-1 need F there.
REACH = 3


def _shift(x, axis, s):
    """out(r) = x(r - s e_axis), periodic; ``axis`` counts lattice dims."""
    return jnp.roll(x, s, axis=axis + 1)


def _d(x, a):
    """Central difference along lattice dim ``a``."""
    return 0.5 * (_shift(x, a, -1) - _shift(x, a, 1))


def _mat(q5):
    """(5, ...) -> 3x3 nested list."""
    xx, xy, xz, yy, yz = (q5[i] for i in range(5))
    return [[xx, xy, xz], [xy, yy, yz], [xz, yz, -xx - yy]]


def _vec5(m):
    return jnp.stack([m[a][b] for a, b in _Q5])


def _mm(a, b):
    return [[a[i][0] * b[0][j] + a[i][1] * b[1][j] + a[i][2] * b[2][j]
             for j in range(3)] for i in range(3)]


def _tr(a):
    return a[0][0] + a[1][1] + a[2][2]


def _lin(*terms):
    """sum of coeff * matrix over (coeff, matrix) pairs."""
    return [[sum(c * m[i][j] for c, m in terms) for j in range(3)]
            for i in range(3)]


def _plus_diag(a, s):
    return [[a[i][j] + (s if i == j else 0.0) for j in range(3)]
            for i in range(3)]


def _sym_traceless(a):
    s = [[0.5 * (a[i][j] + a[j][i]) for j in range(3)] for i in range(3)]
    return _plus_diag(s, -_tr(s) / 3.0)


def step(dist, q, *, a0, gamma, kappa, gamma_rot, xi, tau, dt, stream=True):
    """One periodic LC-LB timestep: (dist (19, ...), q (5, ...)) -> same.

    ``stream=False`` leaves the post-collision distributions where they
    were: a planted fault for reading what the check sees, never a step."""
    Q = _mat(q)
    dQ = [_mat(_d(q, a)) for a in range(3)]
    lap = -6.0 * q
    for a in range(3):
        lap = lap + _shift(q, a, 1) + _shift(q, a, -1)

    # molecular field
    QQ = _mm(Q, Q)
    trQ2 = _tr(QQ)
    H = _lin((-a0 * (1.0 - gamma / 3.0), Q),
             (a0 * gamma, _plus_diag(QQ, -trQ2 / 3.0)),
             (-a0 * gamma * trQ2, Q),
             (kappa, _mat(lap)))
    H = _sym_traceless(H)

    # chemical stress and the force on the fluid
    Qi = _plus_diag(Q, 1.0 / 3.0)
    HQi, QiH, QH, HQ = _mm(H, Qi), _mm(Qi, H), _mm(Q, H), _mm(H, Q)
    trQH = _tr(QH)
    sigma = [[-xi * (HQi[a][b] + QiH[a][b]) + 2.0 * xi * Qi[a][b] * trQH
              + QH[a][b] - HQ[a][b]
              - kappa * sum(dQ[a][g][e] * dQ[b][g][e]
                            for g in range(3) for e in range(3))
              for b in range(3)] for a in range(3)]
    force = [sum(_d(sigma[a][b][None], b)[0] for b in range(3))
             for a in range(3)]

    # lattice Boltzmann: moments, BGK + Guo forcing, streaming
    rho = jnp.sum(dist, axis=0)
    mom = [sum(int(C[i, a]) * dist[i] for i in range(19) if C[i, a])
           for a in range(3)]
    u = [(mom[a] + 0.5 * force[a]) / rho for a in range(3)]
    usq = u[0] * u[0] + u[1] * u[1] + u[2] * u[2]
    uF = u[0] * force[0] + u[1] * force[1] + u[2] * force[2]
    post = []
    for i in range(19):
        cu = sum(int(C[i, a]) * u[a] for a in range(3) if C[i, a])
        cF = sum(int(C[i, a]) * force[a] for a in range(3) if C[i, a])
        w = float(W[i])
        feq = w * rho * (1.0 + 3.0 * cu + 4.5 * cu * cu - 1.5 * usq)
        guo = (1.0 - 0.5 / tau) * w * (3.0 * (cF - uF) + 9.0 * cu * cF)
        post.append(dist[i] - (dist[i] - feq) / tau + guo)
    dist_new = jnp.stack([
        _shift(_shift(_shift(post[i][None], 0, int(C[i, 0])), 1,
                      int(C[i, 1])), 2, int(C[i, 2]))[0] if stream
        else post[i] for i in range(19)])

    # velocity gradient and upwind advection of Q
    uu = jnp.stack(u)
    Wt = [[_d(uu[a][None], b)[0] for b in range(3)] for a in range(3)]
    adv = 0.0
    for a in range(3):
        ua = uu[a][None]
        face = 0.5 * (ua + _shift(ua, a, 1))          # face r - e_a/2
        flux = face * jnp.where(face > 0, _shift(q, a, 1), q)
        adv = adv + _shift(flux, a, -1) - flux

    # Beris-Edwards update
    D = [[0.5 * (Wt[a][b] + Wt[b][a]) for b in range(3)] for a in range(3)]
    O = [[0.5 * (Wt[a][b] - Wt[b][a]) for b in range(3)] for a in range(3)]
    trQW = _tr(_mm(Q, Wt))
    S = _lin((1.0, _mm(_lin((xi, D), (1.0, O)), Qi)),
             (1.0, _mm(Qi, _lin((xi, D), (-1.0, O)))),
             (-2.0 * xi * trQW, Qi))
    rhs = _sym_traceless(_lin((gamma_rot, H), (1.0, S)))
    q_new = q + dt * (_vec5(rhs) - adv)
    q_new = _vec5(_sym_traceless(_mat(q_new)))
    return dist_new, q_new
