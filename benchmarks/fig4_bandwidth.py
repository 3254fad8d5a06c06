"""Paper Fig. 4: per-kernel operational intensity + achieved-bandwidth
fraction.

The wall-clock %STREAM measurement of the paper is replaced by its
structural equivalent on the compiled artifact: for each kernel we lower
the jnp engine on CPU and compare *useful* bytes (the minimal per-site
traffic of the algorithm, the counting the paper uses for OI) against the
HLO "bytes accessed" — useful/HLO = the fraction of achievable bandwidth
the compiled kernel can reach, assuming the memory system runs at STREAM
rate on the rest.  OIs land in the paper's 0.4-2.2 F/B band, far below
every Table-1 ridge point (C4).

``--json PATH`` writes the rows plus structured per-kernel metrics in the
same top-level schema as fig3 (``rows`` / ``metrics`` / ``gate``), so the
``BENCH_*.json`` trajectory tooling covers the bandwidth sweep too.

The ``fig4_tile/*`` rows extend the roofline to tiled lowerings
(``LoweringPlan.by``/``bz``): at the tile the planner itself picks under a
half-footprint VMEM budget, they record bytes moved per tile against the
whole-staging lowering — what tiling buys (per-program footprint bounded
by the tile, not the lattice) and what it costs (halo overfetch where
adjacent tile windows overlap).
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import setup_compile_cache
from repro.apps.ludwig import gradients as LG
from repro.core import plan as plan_mod
from repro.core.stencil import tile_boxes
from repro.kernels.lb_collision import ref as lbref
from repro.kernels.lb_propagation import ref as propref
from repro.kernels.wilson_dslash import ref as wdref

try:
    from .common import LUDWIG_KERNELS, MILC_KERNELS, csv_row, ridge_point
except ImportError:  # run as a script: python benchmarks/fig4_bandwidth.py
    from common import LUDWIG_KERNELS, MILC_KERNELS, csv_row, ridge_point


def _tile_roofline(name, lattice, in_views, out_views, rows, metrics,
                   dtypes=None):
    """Tiled-launch roofline row: bytes moved per tile vs whole-staging.

    Pure geometry — ``tile_boxes`` enumerates the cover and the planner's
    own VMEM model (``estimate_vmem_bytes``) prices the footprints, at the
    (by, bz) ``choose_tiles`` picks under a budget of half the untiled
    footprint.  No launch runs; these rows track the *traffic contract* of
    the tiled lowering across the perf trajectory.

    ``dtypes`` (a :class:`repro.core.DtypePolicy`) prices every view at
    the plan's *storage* dtype itemsize — the byte counts, footprints and
    tile picks below are exactly what the policy-aware planner would see,
    so the mixed-precision roofline rows stay honest."""
    if dtypes is not None and dtypes.storage:
        in_views = tuple((nc, r, dtypes.storage_itemsize(isz))
                         for nc, r, isz in in_views)
        out_views = tuple((nc, dtypes.storage_itemsize(isz))
                          for nc, isz in out_views)
        name = f"{name}@{dtypes.tag()}"
    bx = 1
    whole = plan_mod.LoweringPlan("pallas", bx=bx, dtypes=dtypes)
    fp_whole = plan_mod.estimate_vmem_bytes(
        whole, lattice=lattice, in_views=in_views, out_views=out_views)
    by, bz = plan_mod.choose_tiles(
        lattice, bx, in_views=in_views, out_views=out_views,
        vmem_bytes=fp_whole // 2, dtypes=dtypes)
    tiled = plan_mod.LoweringPlan("pallas", bx=bx, by=by, bz=bz,
                                  dtypes=dtypes)
    fp_tiled = plan_mod.estimate_vmem_bytes(
        tiled, lattice=lattice, in_views=in_views, out_views=out_views)
    boxes = tile_boxes(lattice, bx, by, bz)
    exts = [e for _, e in boxes[0]]
    # per-tile DMA payload: one halo'd window per input + the output tile
    tile_in = sum(ncomp * int(np.prod([e + 2 * r for e in exts])) * isz
                  for ncomp, r, isz in in_views)
    tile_out = sum(ncomp * int(np.prod(exts)) * isz
                   for ncomp, isz in out_views)
    whole_in = sum(ncomp * int(np.prod([s + 2 * r for s in lattice])) * isz
                   for ncomp, r, isz in in_views)
    useful_in = sum(ncomp * int(np.prod(lattice)) * isz
                    for ncomp, _, isz in in_views)
    # adjacent tile windows overlap by the halo ring, so total tile
    # traffic overfetches the minimal (whole-staged) input bytes
    overfetch = len(boxes) * tile_in / max(useful_in, 1)
    metrics[f"tile_{name}"] = {
        "tile": [bx, by, bz],
        "tiles": len(boxes),
        "bytes_per_tile": tile_in + tile_out,
        "bytes_whole_staged": whole_in,
        "vmem_tiled": fp_tiled,
        "vmem_whole": fp_whole,
        "overfetch_vs_useful": overfetch,
    }
    rows.append(csv_row(
        f"fig4_tile/{name}", 0.0,
        f"tile={bx}x{by or lattice[1]}x{bz or lattice[2]};"
        f"tiles={len(boxes)};bytes_per_tile={tile_in + tile_out};"
        f"bytes_whole_staged={whole_in};vmem_tiled={fp_tiled};"
        f"vmem_whole={fp_whole};overfetch_vs_useful={overfetch:.2f}"))


def _cost(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(c, (list, tuple)):  # older jax: one dict per computation
        c = c[0] if c else {}
    c = c or {}
    return float(c.get("flops", 0)), float(c.get("bytes accessed", 0))


def main(argv=None):
    setup_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows + per-kernel metrics to PATH "
                         "(fig3-compatible schema)")
    args = ap.parse_args(argv)
    rows = []
    metrics = {}
    lat = (16, 16, 16)
    nsites = int(np.prod(lat))
    f19 = jax.ShapeDtypeStruct((19, *lat), jnp.float32)
    f5 = jax.ShapeDtypeStruct((5, *lat), jnp.float32)
    f3 = jax.ShapeDtypeStruct((3, *lat), jnp.float32)
    flat = lambda s: jax.ShapeDtypeStruct((s.shape[0], nsites), jnp.float32)

    cases = {
        "collision": (lambda f, g: lbref.collide_ref(f, g, 0.8),
                      (flat(f19), flat(f3)), LUDWIG_KERNELS["collision"]),
        "propagation": (propref.propagate_ref, (f19,),
                        LUDWIG_KERNELS["propagation"]),
        "order_parameter_gradients": (
            lambda q: (LG.grad_central(q), LG.laplacian(q)), (f5,),
            LUDWIG_KERNELS["order_parameter_gradients"]),
        "advection": (LG.advective_divergence, (f5, f3),
                      LUDWIG_KERNELS["advection"]),
    }
    lat4 = (8, 8, 8, 8)
    nsites4 = int(np.prod(lat4))
    psi = jax.ShapeDtypeStruct((24, *lat4), jnp.float32)
    u = jax.ShapeDtypeStruct((72, *lat4), jnp.float32)
    cases["wilson_dslash"] = (wdref.dslash_ref, (psi, u),
                              MILC_KERNELS["extract_and_mult"])

    for name, (fn, fargs, (bps, fps)) in cases.items():
        n = nsites4 if name == "wilson_dslash" else nsites
        flops, hbytes = _cost(fn, *fargs)
        useful = n * bps
        oi = fps / bps if bps else 0.0
        frac = useful / max(hbytes, 1.0)
        metrics[name] = {
            "oi_fpb": oi,
            "useful_bytes": useful,
            "hlo_bytes": hbytes,
            "hlo_flops": flops,
            "achievable_bw_frac": frac,
            "memory_bound_on_v5e": bool(oi < ridge_point("tpu-v5e")),
        }
        rows.append(csv_row(
            f"fig4/{name}", 0.0,
            f"oi_fpb={oi:.2f};useful_bytes={useful};hlo_bytes={hbytes:.0f};"
            f"achievable_bw_frac={frac:.2f};"
            f"memory_bound_on_v5e={oi < ridge_point('tpu-v5e')}"))
    # tiled-launch roofline: views mirror what launch() feeds the planner
    # (dist width-1 + width-0 force for the LB stencil; width-2 spinor +
    # gauge for the fused M^dag M)
    tile_cases = {
        "lb_stencil": (lat, ((19, 1, 4), (3, 0, 4)), ((19, 4),)),
        "wilson_normal": (lat4, ((24, 2, 4), (72, 2, 4)), ((24, 4),)),
    }
    # every tile case also gets a mixed-precision twin row: identical
    # geometry, views priced at the policy's storage itemsize
    policies = (None,
                plan_mod.DtypePolicy(storage="float32", compute="float32",
                                     accumulate="float64"),
                plan_mod.DtypePolicy(storage="bfloat16", compute="float32",
                                     accumulate="float64"))
    for name, (tlat, iv, ov) in tile_cases.items():
        for pol in policies:
            _tile_roofline(name, tlat, iv, ov, rows, metrics, dtypes=pol)
    for r in rows:
        print(r)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "metrics": metrics, "mode": "fig4",
                       "gate": {"tolerance": None, "failures": []}},
                      f, indent=2)
    return rows


if __name__ == "__main__":
    main()
