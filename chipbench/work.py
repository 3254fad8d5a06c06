"""The bytes that any implementation has to move, counted from the fields.

A bandwidth-bound lattice code has to read its state once and write it
once per step (or per solver iteration).  That count depends only on the
fields' component counts, the lattice and the precision, never on how the
program splits the work into launches, so a roofline share taken against
it cannot pass 100%.
"""

from __future__ import annotations

# components per site of each field
LUDWIG_DIST = 19      # D3Q19 distributions
LUDWIG_Q = 5          # symmetric traceless Q tensor
WILSON_SPINOR = 24    # 4 spins x 3 colours, complex
WILSON_GAUGE = 72     # 4 directions x 3 x 3, complex


def ludwig_bytes_per_site(itemsize: int = 4) -> int:
    """dist and q, each read and written once per timestep."""
    return 2 * (LUDWIG_DIST + LUDWIG_Q) * itemsize


def cg_bytes_per_site(itemsize: int = 4) -> int:
    """One iteration of CG on M^dag M: the links read once, and the
    solution, residual and search vectors each read and written once."""
    vectors = 3 * WILSON_SPINOR
    return (WILSON_GAUGE + 2 * vectors) * itemsize
