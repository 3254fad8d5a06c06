"""Lattice-wide reductions (paper §3.2.3, ``targetDoubleSum`` et al.).

The application produces a per-site array (a Field); the reduction API
combines it.  jnp engine: a plain sum.  pallas engine: a grid-sequential
accumulation kernel — each program adds its site-block into a (ncomp, VVL)
partial-sum buffer (TPU pallas grids execute sequentially per core, so
read-modify-write accumulation across grid steps is well defined), and the
final (ncomp, VVL) -> (ncomp,) fold happens outside.  Across shards, callers
compose with ``jax.lax.psum`` (see core.halo / apps drivers), mirroring the
paper's MPI_Allreduce-above-targetDP split.

Split reductions: a plan with ``rsplit > 1`` (an explicit
``TargetConfig.plan_policy`` plan — the standalone path has no graph key to
tune on) partitions the site-block grid into ``rsplit`` segments, each
accumulating its own ``(ncomp, VVL)`` stage-1 partial row; a tiny stage-2
combine folds the rows in segment order.  Same contract as the fused
lowering (core.fuse): deterministic for a fixed ``rsplit``, bitwise exact
for max and integer sums, tolerance-level reassociation for fp sums.

The reduction monoid itself (combine/init/fold) is the shared
:class:`~repro.core.fuse.ReduceSpec` definition.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import telemetry
from .field import Field  # noqa: F401  (re-exported reduction operand type)
from .fuse import ReduceSpec, kahan_fold
from .plan import plan_for_launch, resolve_accumulate
from .target import TargetConfig

__all__ = ["target_sum", "target_max"]


def _reduce(field, config: Optional[TargetConfig], op: str) -> jax.Array:
    # the reduction launch's device ops carry its scope (core.telemetry)
    with telemetry.scope(f"launch/target_{op}"):
        return _reduce_launch(field, config, op)


def _reduce_launch(field, config: Optional[TargetConfig],
                   op: str) -> jax.Array:
    config = config or TargetConfig()
    spec = ReduceSpec(op=op)
    batch = int(getattr(field, "batch", 0))
    if not batch:  # the site-block grid reads flat physical data
        field = field.as_flat()
    # lowering decisions (vvl conformance, interpret fallback, plan policy)
    # come from the planning layer, like every other launch
    plan = plan_for_launch(config, field.nsites, [field.layout])
    # Accumulate-dtype policy: applies only to floating-point sums (max and
    # integer reductions stay bitwise-unchanged by the dtype axis).  The
    # plan's own policy wins over the config-level one, like core.fuse.
    acc_dt, comp = None, False
    pol = plan.dtypes or getattr(config, "dtypes", None)
    if (pol and pol.accumulate and op == "sum"
            and jnp.issubdtype(jnp.dtype(field.dtype), jnp.floating)):
        acc_name, comp = resolve_accumulate(pol.accumulate)
        if acc_name:
            acc_dt = jnp.dtype(acc_name)
    if plan.engine == "jnp":
        # batched: (batch, ncomp, nsites) -> (batch, ncomp); the per-row
        # fold is the same whole-lattice fold as the single-Field path
        x = field.canonical()
        if acc_dt is not None:
            x = x.astype(acc_dt)
            return kahan_fold(x, axis=-1) if comp else spec.fold(x, axis=-1)
        return spec.fold(x, axis=-1)

    vvl, rsplit = plan.vvl, plan.rsplit
    nsites, ncomp = field.nsites, field.ncomp
    layout = field.layout
    blk = tuple(layout.block_shape(ncomp, vvl))
    bmap = layout.block_index_map()
    nblocks = nsites // vvl
    per = nblocks // rsplit
    # grid axes, outermost first: (batch?, rsplit?, blocks-per-segment);
    # each (batch row, split segment) accumulates its own (ncomp, vvl)
    # partial in the same site-block order as the unsplit kernel
    if rsplit > 1:
        in_map = lambda s, i, _m=bmap: tuple(_m(s * per + i))  # noqa: E731
        out_blk, out_map = (1, ncomp, vvl), lambda s, i: (s, 0, 0)
        acc_shape = (rsplit, ncomp, vvl)
    else:
        in_map = bmap
        out_blk, out_map = (ncomp, vvl), lambda i: (0, 0)
        acc_shape = (ncomp, vvl)
    out_dt = acc_dt if acc_dt is not None else field.dtype
    if comp:
        # compensated (Kahan) accumulation: widen with a trailing
        # (sum, compensation) axis carried across grid steps
        acc_shape = acc_shape + (2,)
        out_blk = out_blk + (2,)
        _m0 = out_map
        out_map = lambda *idx, _m=_m0: tuple(_m(*idx)) + (0,)  # noqa: E731
    if batch:
        grid = ((batch, rsplit, per) if rsplit > 1 else (batch, nblocks))
        in_spec = pl.BlockSpec(
            (1,) + blk, lambda b, *idx, _m=in_map: (b,) + tuple(_m(*idx)))
        out_spec = pl.BlockSpec(
            (1,) + out_blk, lambda b, *idx, _m=out_map: (b,) + tuple(_m(*idx)))
        out_shape = jax.ShapeDtypeStruct((batch,) + acc_shape, out_dt)
    else:
        grid = (rsplit, per) if rsplit > 1 else (nblocks,)
        in_spec = pl.BlockSpec(blk, in_map)
        out_spec = pl.BlockSpec(out_blk, out_map)
        out_shape = jax.ShapeDtypeStruct(acc_shape, out_dt)
    blk_axis = len(grid) - 1

    def kern(x_ref, acc_ref):
        @pl.when(pl.program_id(blk_axis) == 0)
        def _init():
            acc_ref[...] = spec.init(acc_ref.shape, acc_ref.dtype)

        x = x_ref[...][0] if batch else x_ref[...]
        chunk = layout.block_to_canonical(x, ncomp, vvl)
        if acc_dt is not None:
            chunk = chunk.astype(acc_dt)
        if comp:
            while chunk.ndim < len(acc_ref.shape) - 1:
                chunk = chunk[None]
            acc = acc_ref[...]
            s, c = acc[..., 0], acc[..., 1]
            y = chunk - c
            t = s + y
            acc_ref[...] = jnp.stack([t, (t - s) - y], axis=-1)
        else:
            while chunk.ndim < len(acc_ref.shape):
                chunk = chunk[None]
            acc_ref[...] = spec.combine(acc_ref[...], chunk)

    partial = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[in_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=plan.interpret,
        name=f"target_{op}",
    )(field.data)
    if comp:
        # drop the compensation column, then fold the vvl lanes with the
        # same compensated summation used across grid steps
        folded = kahan_fold(partial[..., 0], axis=-1)
    else:
        folded = spec.fold(partial, axis=-1)
    if rsplit > 1:  # stage-2 combine over the split-segment rows
        folded = spec.combine_partials(folded, axis=-2)
    return folded


def target_sum(field, config: Optional[TargetConfig] = None) -> jax.Array:
    """targetDoubleSum: per-component sum over all local lattice sites.
    A :class:`~repro.core.field.BatchedField` reduces per batch element to
    ``(batch, ncomp)`` — each row bitwise the single-Field reduction."""
    return _reduce(field, config, "sum")


def target_max(field, config: Optional[TargetConfig] = None) -> jax.Array:
    """Per-component max over all local lattice sites (per batch element
    for a BatchedField)."""
    return _reduce(field, config, "max")
