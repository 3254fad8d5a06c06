"""Halo exchange over the device mesh — the paper's MPI layer, on ICI.

targetDP handles intra-node parallelism; the paper composes it with MPI halo
exchange on a domain-decomposed lattice (§2.1, §5).  Here the inter-"rank"
layer is ``jax.shard_map`` over a named mesh and the exchange is
``jax.lax.ppermute`` (XLA collective-permute, which lowers to neighbour ICI
transfers on TPU — the "CUDA-aware MPI" the paper wishes for is the default:
halos move HBM->ICI->HBM with no host staging).

All functions here run *inside* shard_map.  Arrays are local canonical
views ``(ncomp, *local_lattice)`` whose site dims already include ``width``
halo slots at both ends of every decomposed dimension.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
from jax import lax

from . import telemetry

__all__ = [
    "exchange_dim",
    "exchange",
    "exchange_field",
    "exchange_boundary",
    "start_exchange",
    "finish_exchange",
    "PendingExchange",
    "axis_perms",
]


def axis_perms(n: int):
    """Forward/backward neighbour permutations for a periodic 1-D rank line."""
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    return fwd, bwd


def _take(x, dim: int, lo: int, hi: int):
    idx = [slice(None)] * x.ndim
    idx[dim] = slice(lo, hi)
    return x[tuple(idx)]


def _put(x, dim: int, lo: int, hi: int, val):
    idx = [slice(None)] * x.ndim
    idx[dim] = slice(lo, hi)
    return x.at[tuple(idx)].set(val)


def exchange_dim(
    x: jax.Array, *, axis_name: str, axis_size: int, dim: int, width: int
) -> jax.Array:
    """Fill the two halo slabs of lattice dim ``dim`` from the neighbours.

    Periodic global topology (both applications use periodic boundaries at
    the decomposition level; physical walls are applied by the apps on top).
    With axis_size == 1 the self-permutation reproduces the periodic wrap.
    """
    n = axis_size
    fwd, bwd = axis_perms(n)
    L = x.shape[dim]
    if L < 3 * width:
        # the interior (L - 2*width) is thinner than the halo: the "interior"
        # slabs below would overlap the halo slots and silently exchange
        # corrupt data — refuse instead (thicken the local extent by using
        # fewer ranks along this dim, or shrink the stencil ring)
        raise ValueError(
            f"halo exchange of dim {dim}: local halo'd extent {L} is too "
            f"thin for width {width} (interior {L - 2 * width} < width; "
            f"need extent >= {3 * width})")
    with telemetry.scope("halo/exchange"):
        lo_interior = _take(x, dim, width, 2 * width)
        hi_interior = _take(x, dim, L - 2 * width, L - width)
        # counted at trace time: one traced step gives its exchanges and
        # the bytes each device sends per step
        telemetry.inc("halo.exchanges")
        telemetry.inc("halo.bytes", 2 * lo_interior.size * x.dtype.itemsize)
        # my high interior -> right neighbour's low halo
        recv_lo = lax.ppermute(hi_interior, axis_name, perm=fwd)
        # my low interior -> left neighbour's high halo
        recv_hi = lax.ppermute(lo_interior, axis_name, perm=bwd)
        x = _put(x, dim, 0, width, recv_lo)
        return _put(x, dim, L - width, L, recv_hi)


def exchange(
    x: jax.Array,
    decomposed: Sequence[Tuple[int, str, int]],
    *,
    width: int,
) -> jax.Array:
    """Exchange halos over every decomposed lattice dim.

    decomposed: sequence of (array_dim, mesh_axis_name, mesh_axis_size).
    Exchanges are ordered so that corner/edge halos become correct (each
    pass includes the previously-filled halos of the other dims, the
    standard dimension-by-dimension MPI trick the paper's applications use).
    """
    for dim, axis_name, axis_size in decomposed:
        x = exchange_dim(
            x, axis_name=axis_name, axis_size=axis_size, dim=dim, width=width
        )
    return x


def exchange_field(f, decomposed: Sequence[Tuple[int, str, int]], *, width: int):
    """Halo-exchange a :class:`~repro.core.field.Field` whose lattice is the
    halo'd local lattice, returning a Field in the SAME physical layout.

    The AoSoA-backed-shard form of :func:`exchange`: the ppermutes run on
    the canonical-nd view (collectives move whole halo planes — the
    physical layout of the wire format is irrelevant), and the result is
    re-packed into the input's layout, so a downstream native-AoSoA stencil
    launch (``LoweringPlan.view == "block"``) receives the physical tile
    stack it stages as-is.  With ``width`` 0 or no decomposed dims the
    Field is returned untouched."""
    if width < 1 or not decomposed:
        return f
    nd = exchange(f.canonical_nd(), decomposed, width=width)
    return f.with_canonical(nd.reshape(f.ncomp, -1))


def exchange_boundary(
    x: jax.Array,
    decomposed: Sequence[Tuple[int, str, int]],
    *,
    width: int,
    dims: Sequence[int] = None,
) -> jax.Array:
    """Slab-granular exchange: fill only the halos of the listed lattice
    dims (array dims), in decomposition order.  ``dims=None`` exchanges
    everything (== :func:`exchange`).  The overlap scheduler
    (core.overlap) uses this to exchange exactly the boundary slabs its
    thin sub-launches consume."""
    wanted = None if dims is None else set(dims)
    for dim, axis_name, axis_size in decomposed:
        if wanted is not None and dim not in wanted:
            continue
        x = exchange_dim(
            x, axis_name=axis_name, axis_size=axis_size, dim=dim, width=width
        )
    return x


@dataclasses.dataclass(frozen=True)
class PendingExchange:
    """Handle returned by :func:`start_exchange`.

    The ppermutes are already part of the traced program, but nothing
    forces them to complete before unrelated compute: an interior
    sub-launch built between ``start_exchange`` and ``finish_exchange``
    has no data dependence on the exchanged array, so XLA's scheduler (and
    the TPU's async collectives) may run the two concurrently — the
    comms/compute overlap of core.overlap.  ``finish_exchange`` (or
    ``.array``) yields the fully exchanged array for the boundary
    sub-launches."""

    array: jax.Array


def start_exchange(
    x: jax.Array,
    decomposed: Sequence[Tuple[int, str, int]],
    *,
    width: int,
) -> PendingExchange:
    """Begin the dimension-ordered halo exchange of ``x`` and return a
    :class:`PendingExchange`; consume it with :func:`finish_exchange` only
    where the exchanged halos are actually read (the boundary slabs), so
    interior compute issued in between stays dependence-free."""
    return PendingExchange(exchange(x, decomposed, width=width))


def finish_exchange(pending: PendingExchange) -> jax.Array:
    """The exchanged array of a :func:`start_exchange` handle."""
    return pending.array
