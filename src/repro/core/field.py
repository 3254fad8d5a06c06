"""Field: a multi-valued lattice quantity stored in a configurable Layout.

A Field is the targetDP-JAX unit of data: ``ncomp`` components at every site
of a (possibly multi-dimensional) lattice, physically stored per its Layout
(paper §3.1).  Kernels (core.target) consume and produce Fields; the kernel
body only ever sees canonical ``(ncomp, VVL)`` chunks.

An SoA Field is stored in one of two shapes with the same row-major order
(``comp*nsites + site``, ``Layout.flat_index``): flat ``(ncomp, nsites)``
(every constructor but :meth:`Field.from_nd`) or nd ``(ncomp, *lattice)``
(:meth:`Field.from_nd`; :attr:`Field.nd`).  On a TPU the two tile
differently — components on sublanes in one, the second lattice axis in the
other — so a reshape between them is a physical copy; each one traced is
counted under the ``field.relayout`` counter (core.telemetry).  Stencils and
nd-grid launches (core.fuse) take nd Fields with no copy.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry
from .layout import Layout, LayoutKind, SOA

# every layout view and relayout below issues its device ops under this
# scope (core.telemetry), so a device trace tells them from kernels
_RELAYOUT = "field/relayout"

__all__ = ["Field", "BatchedField"]


def _reshaped(arr, shape):
    """``arr`` in ``shape``; a reshape between the flat and the nd form of a
    field is a relayout on a TPU, counted under ``field.relayout``."""
    shape = tuple(shape)
    if tuple(arr.shape) == shape:
        return arr
    telemetry.inc("field.relayout")
    return arr.reshape(shape)


@dataclasses.dataclass
class Field:
    """ncomp values per site on a lattice, in a given physical layout.

    data      physical jax.Array, shape == layout.physical_shape(ncomp, nsites),
              or (ncomp, *lattice) for an nd-stored SoA Field (from_nd)
    lattice   site-space shape, e.g. (nx, ny, nz); nsites = prod(lattice)
    """

    name: str
    ncomp: int
    lattice: Tuple[int, ...]
    layout: Layout
    data: jax.Array

    # -- constructors ----------------------------------------------------------

    @classmethod
    def zeros(cls, name, ncomp, lattice, layout=SOA, dtype=jnp.float32):
        nsites = math.prod(lattice)
        data = jnp.zeros(layout.physical_shape(ncomp, nsites), dtype)
        return cls(name, ncomp, tuple(lattice), layout, data)

    @classmethod
    def from_canonical(cls, name, canonical, lattice, layout=SOA):
        """canonical: (ncomp, *lattice) or (ncomp, nsites)."""
        with telemetry.scope(_RELAYOUT):
            canonical = jnp.asarray(canonical)
            ncomp = canonical.shape[0]
            nsites = math.prod(lattice)
            flat = _reshaped(canonical, (ncomp, nsites))
            return cls(name, ncomp, tuple(lattice), layout, layout.pack(flat))

    @classmethod
    def from_nd(cls, name, arr_nd, layout=SOA):
        """arr_nd: (ncomp, *lattice).  SoA keeps the site axes (no copy);
        AoS and AoSoA pack flat, as :meth:`from_canonical`."""
        arr_nd = jnp.asarray(arr_nd)
        lattice = tuple(arr_nd.shape[1:])
        if layout.kind is not LayoutKind.SOA:
            return cls.from_canonical(name, arr_nd, lattice, layout)
        return cls(name, arr_nd.shape[0], lattice, layout, arr_nd)

    @classmethod
    def from_numpy(cls, name, array_cs, lattice, layout=SOA, dtype=jnp.float32):
        return cls.from_canonical(name, jnp.asarray(array_cs, dtype), lattice, layout)

    # -- views -----------------------------------------------------------------

    @property
    def nsites(self) -> int:
        return math.prod(self.lattice)

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def nd(self) -> bool:
        """Whether the data keeps its site axes: (ncomp, *lattice) SoA."""
        return self.layout.kind is LayoutKind.SOA and self.data.ndim > 2

    def canonical(self) -> jax.Array:
        """(ncomp, nsites) logical view (layout-independent)."""
        with telemetry.scope(_RELAYOUT):
            if self.nd:
                return _reshaped(self.data, (self.ncomp, self.nsites))
            return self.layout.unpack(self.data)

    def canonical_nd(self) -> jax.Array:
        """(ncomp, *lattice) logical view — stencil/geometry operations."""
        if self.nd:
            return self.data
        with telemetry.scope(_RELAYOUT):
            return _reshaped(self.layout.unpack(self.data),
                             (self.ncomp,) + self.lattice)

    def to_numpy(self) -> np.ndarray:
        return np.asarray(self.canonical_nd())

    # -- functional updates ----------------------------------------------------

    def with_data(self, data: jax.Array) -> "Field":
        return dataclasses.replace(self, data=data)

    def with_canonical(self, canonical: jax.Array) -> "Field":
        with telemetry.scope(_RELAYOUT):
            if self.nd:
                return dataclasses.replace(
                    self, data=_reshaped(canonical, self.data.shape))
            flat = _reshaped(canonical, (self.ncomp, self.nsites))
            return dataclasses.replace(self, data=self.layout.pack(flat))

    def as_nd(self) -> "Field":
        """This SoA Field stored nd (itself if it already is); AoS and
        AoSoA Fields are returned as they are."""
        if self.nd or self.layout.kind is not LayoutKind.SOA:
            return self
        return self.with_data(self.canonical_nd())

    def as_flat(self) -> "Field":
        """This Field in its layout's flat physical shape."""
        if not self.nd:
            return self
        return self.with_data(self.canonical())

    def as_layout(self, layout: Layout) -> "Field":
        """Relayout (the paper's per-architecture layout switch)."""
        if layout == self.layout:
            return self
        with telemetry.scope(_RELAYOUT):
            return dataclasses.replace(
                self, layout=layout, data=layout.pack(self.canonical())
            )

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (
            f"Field({self.name!r}, ncomp={self.ncomp}, lattice={self.lattice}, "
            f"layout={self.layout.name}, dtype={self.dtype})"
        )


@dataclasses.dataclass
class BatchedField:
    """A stack of ``batch`` independent same-shape Fields, one leading axis.

    data has shape ``(batch,) + layout.physical_shape(ncomp, nsites)`` —
    every batch element is an ordinary Field's physical array, so
    ``element(b)`` / ``unstack()`` round-trip bitwise.  The serving layer
    (launch.serve) packs many small simulations into one of these and the
    fused launch lowers the whole stack through a single kernel
    (core.fuse grows a leading grid axis).
    """

    name: str
    batch: int
    ncomp: int
    lattice: Tuple[int, ...]
    layout: Layout
    data: jax.Array

    # every batch element is stored flat (Field.nd)
    nd = False

    # -- constructors ----------------------------------------------------------

    @classmethod
    def stack(cls, fields, name=None):
        """Stack same-(ncomp, lattice, layout) Fields along a new batch axis."""
        fields = list(fields)
        if not fields:
            raise ValueError("BatchedField.stack needs at least one Field")
        f0 = fields[0]
        for f in fields[1:]:
            if (f.ncomp, f.lattice, f.layout) != (f0.ncomp, f0.lattice, f0.layout):
                raise ValueError(
                    f"cannot stack {f!r} with {f0!r}: batch elements must "
                    f"share ncomp, lattice and layout")
        data = jnp.stack([f.data for f in fields])
        return cls(name or f0.name, len(fields), f0.ncomp, f0.lattice,
                   f0.layout, data)

    @classmethod
    def zeros(cls, name, batch, ncomp, lattice, layout=SOA, dtype=jnp.float32):
        nsites = math.prod(lattice)
        shape = (batch,) + layout.physical_shape(ncomp, nsites)
        return cls(name, batch, ncomp, tuple(lattice), layout,
                   jnp.zeros(shape, dtype))

    @classmethod
    def from_canonical(cls, name, canonical, lattice, layout=SOA):
        """canonical: (batch, ncomp, *lattice) or (batch, ncomp, nsites)."""
        with telemetry.scope(_RELAYOUT):
            canonical = jnp.asarray(canonical)
            batch, ncomp = canonical.shape[:2]
            nsites = math.prod(lattice)
            flat = _reshaped(canonical, (batch, ncomp, nsites))
            return cls(name, batch, ncomp, tuple(lattice), layout,
                       jax.vmap(layout.pack)(flat))

    # -- views -----------------------------------------------------------------

    @property
    def nsites(self) -> int:
        return math.prod(self.lattice)

    @property
    def dtype(self):
        return self.data.dtype

    def element(self, b: int) -> Field:
        """Batch element ``b`` as an ordinary Field (bitwise the stacked data)."""
        return Field(f"{self.name}[{b}]", self.ncomp, self.lattice,
                     self.layout, self.data[b])

    def unstack(self):
        return [self.element(b) for b in range(self.batch)]

    def canonical(self) -> jax.Array:
        """(batch, ncomp, nsites) logical view."""
        with telemetry.scope(_RELAYOUT):
            return jax.vmap(self.layout.unpack)(self.data)

    def canonical_nd(self) -> jax.Array:
        """(batch, ncomp, *lattice) logical view."""
        with telemetry.scope(_RELAYOUT):
            return _reshaped(jax.vmap(self.layout.unpack)(self.data),
                             (self.batch, self.ncomp) + self.lattice)

    def to_numpy(self) -> np.ndarray:
        return np.asarray(self.canonical_nd())

    # -- functional updates ----------------------------------------------------

    def with_data(self, data: jax.Array) -> "BatchedField":
        return dataclasses.replace(self, data=data)

    def with_element(self, b, field: Field) -> "BatchedField":
        """Replace batch slot ``b`` with a Field's data (same shape/layout)."""
        f = field.as_layout(self.layout)
        return dataclasses.replace(self, data=self.data.at[b].set(f.data))

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (
            f"BatchedField({self.name!r}, batch={self.batch}, "
            f"ncomp={self.ncomp}, lattice={self.lattice}, "
            f"layout={self.layout.name}, dtype={self.dtype})"
        )


# Fields are pytrees: data is the leaf, everything else is static metadata.
def _field_flatten(f: Field):
    return (f.data,), (f.name, f.ncomp, f.lattice, f.layout)


def _field_unflatten(aux, children):
    name, ncomp, lattice, layout = aux
    return Field(name, ncomp, lattice, layout, children[0])


jax.tree_util.register_pytree_node(Field, _field_flatten, _field_unflatten)


def _bfield_flatten(f: BatchedField):
    return (f.data,), (f.name, f.batch, f.ncomp, f.lattice, f.layout)


def _bfield_unflatten(aux, children):
    name, batch, ncomp, lattice, layout = aux
    return BatchedField(name, batch, ncomp, lattice, layout, children[0])


jax.tree_util.register_pytree_node(
    BatchedField, _bfield_flatten, _bfield_unflatten)
