"""Quickstart: the paper's §3 'scale' example, written once, run on both
engines and three layouts — targetDP-JAX in 40 lines.

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --trace quickstart.json
    # then load quickstart.json at https://ui.perfetto.dev — every fused
    # launch is a span tagged with its plan, cache hit/miss, modeled HBM
    # bytes and live roofline placement
"""

import argparse

import numpy as np

from repro.launch.compile_cache import setup_compile_cache
from repro.core import AOS, SOA, Field, LaunchGraph, TargetConfig, aosoa, kernel, launch, target_sum, telemetry


# __targetEntry__ void scale(double* field): the kernel body is written
# once over canonical (ncomp, VVL) chunks — TLP/ILP/layout are config.
@kernel
def scale(v, a):
    return {"field": a * v["field"]}


@kernel
def shift(v, c):
    return {"field": v["field"] + c}


def fused_chain_demo(field, layout):
    """Fused launch graphs: a chain of kernels whose outputs feed later
    inputs lowers to ONE device kernel per engine — the intermediate
    (2*field) never round-trips through HBM — and the jit-backed launch
    cache means the second launch does not re-trace."""
    g = (LaunchGraph("scale_then_shift")
         .add(scale, {"field": "field"}, {"field": 3},
              params={"a": 2.0}, rename={"field": "scaled"})
         .add(shift, {"field": "scaled"}, {"field": 3},
              params={"c": 1.0}, rename={"field": "out"}))
    for engine in ("jnp", "pallas"):
        out = g.launch({"field": field}, config=TargetConfig(engine, vvl=256),
                       outputs=("out",))["out"]
        want = 2.0 * field.to_numpy() + 1.0
        assert np.allclose(out.to_numpy(), want, rtol=1e-6)
        print(f"fused  layout={layout.name:9s} engine={engine:6s} OK "
              f"(2 kernels, 1 launch)")


def _poisson_body(v, gather, *, c):
    """A p = (6 + c) p - sum of the 6 face neighbours: a width-1 stencil
    stage body — ``gather(name, disp)`` reads the displaced window straight
    from the VMEM-resident halo'd block."""
    ap = (6.0 + c) * v["p"]
    for d in range(3):
        for s in (1, -1):
            disp = [0, 0, 0]
            disp[d] = s
            ap = ap - gather("p", tuple(disp))
    return {"ap": ap}


def fused_stencil_reduction_demo(lattice=(8, 8, 8), engine="pallas"):
    """The CG residual loop on fused stencil + reduction graphs.

    Two launches per iteration, exactly like the MILC solver (apps/milc/cg):

      op grph   stencil A p  ->  site-local p * Ap  ->  terminal sum <p, Ap>
      upd graph x+alpha p, r-alpha Ap (site-local)  ->  terminal sum |r'|^2

    The stencil gathers neighbours from the halo'd block in VMEM, and both
    inner products accumulate on-chip — neither p*Ap nor r'*r' ever exists
    in HBM.  A = (6 + c) I - 6-point laplacian stencil is SPD, so CG
    converges; the loop below drives it from the two fused launches alone.
    """
    cfg = TargetConfig(engine, vvl=256)
    c = 0.5
    op = (LaunchGraph("poisson_op")
          .add_stencil(_poisson_body, {"p": "p"}, {"ap": 1}, width=1,
                       params={"c": c})
          .add(lambda v: {"prod": v["p"] * v["ap"]},
               {"p": "p", "ap": "ap"}, {"prod": 1})
          .add_reduce("prod", op="sum", name="pap"))
    upd = (LaunchGraph("cg_update")
           .add(lambda v: {"x": v["x"] + v["alpha"] * v["p"]},
                {"x": "x", "p": "p", "alpha": "alpha"}, {"x": 1},
                rename={"x": "x_new"})
           .add(lambda v: {"r": v["r"] - v["alpha"] * v["ap"]},
                {"r": "r", "ap": "ap", "alpha": "alpha"}, {"r": 1},
                rename={"r": "r_new"})
           .add(lambda v: {"sq": v["r"] * v["r"]}, {"r": "r_new"}, {"sq": 1})
           .add_reduce("sq", op="sum", name="rr"))

    rng = np.random.default_rng(1)
    lat = tuple(lattice)
    b = Field.from_numpy("b", rng.normal(size=(1, *lat)), lat, SOA)
    x = Field.from_numpy("x", np.zeros((1, *lat)), lat, SOA)
    r, p = b, b
    rr = float(np.square(b.to_numpy()).sum())
    b2 = rr
    for it in range(50):
        o = op.launch({"p": p}, config=cfg, outputs=("ap", "pap"))
        alpha = rr / float(np.asarray(o["pap"]).sum())
        u = upd.launch({"x": x, "r": r, "p": p, "ap": o["ap"]},
                       scalars={"alpha": alpha}, config=cfg,
                       outputs=("x_new", "r_new", "rr"))
        x, r = u["x_new"], u["r_new"]
        rr_new = float(np.asarray(u["rr"]).sum())
        if rr_new / b2 < 1e-10:
            break
        beta = rr_new / rr
        p = p.with_canonical(r.canonical() + beta * p.canonical())
        rr = rr_new
    assert rr_new / b2 < 1e-8, (it, rr_new / b2)
    print(f"fused stencil+reduction CG: engine={engine:6s} "
          f"converged in {it + 1} iters, |r|^2/|b|^2 = {rr_new / b2:.2e}")


def main(argv=None):
    setup_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable telemetry and write a Chrome trace "
                         "(Perfetto-loadable) of every launch to PATH")
    args = ap.parse_args(argv)
    if args.trace:
        telemetry.enable()
        telemetry.configure_logging()
    lattice = (16, 16, 16)
    rng = np.random.default_rng(0)
    host_field = rng.normal(size=(3, *lattice)).astype(np.float32)

    for layout in (SOA, AOS, aosoa(128)):
        # targetMalloc + copyToTarget
        field = Field.from_numpy("field", host_field, lattice, layout)

        for engine in ("jnp", "pallas"):
            cfg = TargetConfig(engine, vvl=256)
            out = launch(scale, {"field": field}, {"field": 3},
                         config=cfg, params={"a": 2.0})["field"]
            # copyFromTarget
            host_out = out.to_numpy()
            assert np.allclose(host_out, 2.0 * host_field, rtol=1e-6)
            total = np.asarray(target_sum(out, cfg))
            print(f"layout={layout.name:9s} engine={engine:6s} "
                  f"sum={total.sum():+.3f}  OK")

        fused_chain_demo(field, layout)

    for engine in ("jnp", "pallas"):
        fused_stencil_reduction_demo(engine=engine)

    print("same source, every layout x engine: portable (paper C1/C2)")
    if args.trace:
        print(telemetry.format_report())
        print(f"chrome trace: {telemetry.export_chrome_trace(args.trace)}")


if __name__ == "__main__":
    main()
