"""Public wrapper for the Wilson-Dirac operator (engine dispatch), plus the
stencil-stage body that lets dslash join fused launch graphs (core.fuse):
the MILC "Shift" kernel becomes gather calls on a VMEM-resident halo'd
block, feeding the site-local project/mult/reconstruct math in the same
kernel — so D psi fuses with the CG axpy chain and the residual reduction
(see apps/milc/cg.py)."""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.core import Field, TargetConfig
from repro.core.plan import plan_for_launch
from . import kernel, ref


def dslash_stencil_body(v, gather):
    """Fused-graph stencil stage: v = {"psi": (24, *win), "u": (72, *win)}.

    Gathers the 8 neighbour spinors and the backward gauge links from the
    halo'd window (the "Shift" kernel, width 1), then runs the site-local
    hopping term — returns {"d": D psi (24, *win_out)}."""
    packs = []
    for mu in range(4):
        e = [0, 0, 0, 0]
        e[mu] = 1
        # psi(x + mu): out(r) = in(r - d) with d = -e
        packs.append(gather("psi", tuple(-x for x in e)))
        packs.append(gather("psi", tuple(e)))
    nbrs = jnp.concatenate(packs, axis=0)                       # (192, *win)
    u_fwd = v["u"]
    u_bwd = jnp.concatenate(
        [gather("u", (0,) * mu + (1,) + (0,) * (3 - mu))[mu * 18:(mu + 1) * 18]
         for mu in range(4)],
        axis=0,
    )                                                           # (72, *win)
    # the site chunk is elementwise over the trailing axes: it runs on the
    # nd window as-is (no in-kernel reshape across lattice dims)
    return {"d": ref.dslash_site_chunk(u_fwd, u_bwd, nbrs)}


def dslash(psi: Field, u: Field, *, config: TargetConfig) -> Field:
    """D psi on a single shard (periodic). psi: 24-comp, u: 72-comp fields
    over a 4-D lattice."""
    psi_nd, u_nd = psi.canonical_nd(), u.canonical_nd()
    if config.engine == "jnp":
        out = ref.dslash_ref(psi_nd, u_nd)
        return psi.with_canonical(out.reshape(psi.ncomp, psi.nsites))
    if config.engine == "pallas":
        nbrs = ref.gather_neighbours_periodic(psi_nd)
        u_bwd = ref.gather_gauge_bwd_periodic(u_nd)
        flat = lambda a: a.reshape(a.shape[0], -1)
        lay = psi.layout
        # vvl/interpret through the planning layer (auto-vvl: the seed
        # passed config.vvl raw and raised on non-dividing lattices)
        plan = plan_for_launch(config, psi.nsites, [lay])
        out_phys = kernel.dslash_site_pallas(
            lay.pack(flat(u_nd)),
            lay.pack(flat(u_bwd)),
            lay.pack(flat(nbrs)),
            layout=lay,
            vvl=plan.vvl,
            nsites=psi.nsites,
            interpret=plan.interpret,
        )
        return psi.with_data(out_phys)
    raise ValueError(f"unknown engine {config.engine!r}")


def dslash_halo(
    psi_h: jnp.ndarray, u_h: jnp.ndarray, *, config: TargetConfig, width: int = 1
) -> jnp.ndarray:
    """Halo'd-array form for shard_map: psi_h (24, X+2w, ...), u_h (72, ...)
    with halos exchanged; returns interior D psi (24, X, Y, Z, T).

    The periodic gathers on the halo'd local array read at most ``width``
    into the halo (neighbour data), so the cropped interior is exact.
    """

    def crop(x):
        sl = (slice(None),) + tuple(
            slice(width, s - width) for s in x.shape[1:]
        )
        return x[sl]

    nbrs = crop(ref.gather_neighbours_periodic(psi_h))
    u_bwd = crop(ref.gather_gauge_bwd_periodic(u_h))
    u_fwd = crop(u_h)
    lat = u_fwd.shape[1:]
    flat = lambda a: a.reshape(a.shape[0], -1)
    if config.engine == "jnp":
        out = ref.dslash_site_chunk(flat(u_fwd), flat(u_bwd), flat(nbrs))
    elif config.engine == "pallas":
        from repro.core.layout import SOA

        nsites = int(np.prod(lat))
        plan = plan_for_launch(config, nsites, [SOA])
        out_phys = kernel.dslash_site_pallas(
            flat(u_fwd), flat(u_bwd), flat(nbrs),
            layout=SOA, vvl=plan.vvl, nsites=nsites,
            interpret=plan.interpret,
        )
        out = out_phys
    else:
        raise ValueError(f"unknown engine {config.engine!r}")
    return out.reshape((ref.SPINOR_NCOMP,) + lat)


def wilson_matvec(psi: Field, u: Field, *, kappa: float, config: TargetConfig) -> Field:
    """M psi = psi - kappa D psi."""
    d = dslash(psi, u, config=config)
    return psi.with_canonical(psi.canonical() - kappa * d.canonical())
