"""Multi-device correctness on 8 fake host devices (subprocess: jax locks
the device count at first init, so these run isolated)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_script(body: str, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"  # a child never competes for the chip
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


COMMON = """
import numpy as np, jax, jax.numpy as jnp
from repro.core.compat import make_mesh, shard_map
mesh = make_mesh((4, 2), ("data", "model"))
"""


@pytest.mark.slow
def test_halo_exchange_matches_periodic_roll():
    run_script(COMMON + """
from repro.core import halo
from repro.lattice import Domain
dom = Domain(global_shape=(8, 8, 8), mesh=mesh,
             dim_axes=("data", "model", None), halo=1)
x = np.arange(3*8*8*8, dtype=np.float32).reshape(3, 8, 8, 8)

def local(xl):
    pads = [(0, 0)] + [(1, 1)]*3
    xh = jnp.pad(xl, pads, mode="wrap")
    xh = halo.exchange(xh, dom.decomposed, width=1)
    # after exchange: halo'd shifted window == periodic roll of global
    out = xh[:, :-2, 1:-1, 1:-1]      # shift +1 in x => value at (r-1)
    return out

f = jax.jit(shard_map(local, mesh=mesh, in_specs=dom.spec(),
            out_specs=dom.spec()))
got = np.asarray(f(jax.device_put(jnp.asarray(x), dom.sharding())))
want = np.roll(x, 1, axis=1)
np.testing.assert_array_equal(got, want)
print("halo OK")
""")


@pytest.mark.slow
def test_ludwig_sharded_equals_single():
    run_script(COMMON + """
from repro.core import TargetConfig
from repro.apps.ludwig import LudwigConfig, init_state, step
from repro.apps.ludwig.driver import make_sharded_step
from repro.lattice import Domain
cfg = LudwigConfig(lattice=(8, 8, 8), target=TargetConfig("jnp"))
st0 = init_state(cfg, seed=0)
jstep = jax.jit(step, static_argnums=1)
s = st0
for _ in range(3): s = jstep(s, cfg)
dom = Domain(global_shape=cfg.lattice, mesh=mesh,
             dim_axes=("data", "model", None), halo=2)
sstep = make_sharded_step(cfg, dom)
sh = dom.sharding()
dist_nd = jax.device_put(jnp.asarray(st0.dist.to_numpy()), sh)
q_nd = jax.device_put(jnp.asarray(st0.q.to_numpy()), sh)
for _ in range(3): dist_nd, q_nd = sstep(dist_nd, q_nd)
np.testing.assert_allclose(np.asarray(dist_nd), s.dist.to_numpy(),
                           rtol=5e-5, atol=1e-7)
np.testing.assert_allclose(np.asarray(q_nd), s.q.to_numpy(),
                           rtol=5e-5, atol=1e-7)
print("ludwig sharded OK")
""")


@pytest.mark.slow
def test_ludwig_overlap_step_bit_identical_to_pre():
    """The comms/compute overlap schedule (interior/boundary split
    launches, core.overlap) must be bit-identical to the pre-exchange
    schedule on the sharded LB step — and the run_steps StepPipeline must
    reproduce the step-by-step loop exactly."""
    run_script(COMMON + """
from repro.core import TargetConfig
from repro.apps.ludwig import LudwigConfig, init_state
from repro.apps.ludwig.driver import make_sharded_step, run_steps
from repro.lattice import Domain
cfg = LudwigConfig(lattice=(16, 8, 8), target=TargetConfig("jnp"))
st0 = init_state(cfg, seed=0)
dom = Domain(global_shape=cfg.lattice, mesh=mesh,
             dim_axes=("data", "model", None), halo=2)
sh = dom.sharding()
d0 = jax.device_put(jnp.asarray(st0.dist.to_numpy()), sh)
q0 = jax.device_put(jnp.asarray(st0.q.to_numpy()), sh)
pre = make_sharded_step(cfg, dom, halo="pre")
ov = make_sharded_step(cfg, dom, halo="overlap")
dp, qp, do, qo = d0, q0, d0, q0
for _ in range(3):
    dp, qp = pre(dp, qp)
    do, qo = ov(do, qo)
np.testing.assert_array_equal(np.asarray(dp), np.asarray(do))
np.testing.assert_array_equal(np.asarray(qp), np.asarray(qo))
# the multi-step pipeline (donated double-buffers) is the same trajectory
dr, qr = run_steps(cfg, dom, d0, q0, 3, halo="overlap")
np.testing.assert_array_equal(np.asarray(dr), np.asarray(do))
np.testing.assert_array_equal(np.asarray(qr), np.asarray(qo))
print("ludwig overlap OK")
""")


@pytest.mark.slow
def test_milc_cg_overlap_bit_identical_to_pre():
    """Fused sharded CG under halo='overlap' must follow the exact same
    trajectory as halo='pre': same iterates bit-for-bit, same iteration
    count (the inner products are computed producer-independently from the
    assembled Fields).  Physics check: both agree with the single-shard
    fused solve within fp tolerance."""
    run_script(COMMON + """
from repro.apps.milc import MilcConfig, init_problem, solve
from repro.apps.milc.driver import solve_sharded
from repro.lattice import Domain
# local dim0 extent 5 >= 2*ring+1 with ring 2: a real interior/boundary
# split (not the thin-interior fallback) on the 4-rank axis
mesh1 = make_mesh((4,), ("mx",))
cfg = MilcConfig(lattice=(20, 4, 4, 4), kappa=0.10, tol=1e-10, max_iter=2000)
u, b = init_problem(cfg, seed=0)
dom = Domain(global_shape=cfg.lattice, mesh=mesh1,
             dim_axes=("mx", None, None, None), halo=1)
un, bn = jnp.asarray(u.to_numpy()), jnp.asarray(b.to_numpy())
xp, ip, rp = solve_sharded(cfg, dom, un, bn, halo="pre")
xo, io, ro = solve_sharded(cfg, dom, un, bn, halo="overlap")
assert int(ip) == int(io), (int(ip), int(io))
np.testing.assert_array_equal(np.asarray(xp), np.asarray(xo))
np.testing.assert_array_equal(np.asarray(rp), np.asarray(ro))
res = solve(cfg, u, b)
assert float(ro) <= cfg.tol
np.testing.assert_allclose(np.asarray(xo), res.x.to_numpy(),
                           rtol=5e-4, atol=5e-6)
print("milc overlap OK")
""")


@pytest.mark.slow
def test_milc_sharded_equals_single():
    run_script(COMMON + """
from repro.apps.milc import MilcConfig, init_problem, solve
from repro.apps.milc.driver import solve_sharded, make_domain
cfg = MilcConfig(lattice=(8, 4, 4, 4), kappa=0.10, tol=1e-10, max_iter=2000)
u, b = init_problem(cfg, seed=0)
res = solve(cfg, u, b)
dom = make_domain(cfg, mesh, ("data", "model", None, None))
x_nd, iters, resid = solve_sharded(cfg, dom, jnp.asarray(u.to_numpy()),
                                   jnp.asarray(b.to_numpy()))
assert int(iters) == int(res.iterations)
np.testing.assert_allclose(np.asarray(x_nd), res.x.to_numpy(),
                           rtol=5e-4, atol=5e-6)
print("milc sharded OK")
""")


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """FSDP+TP GSPMD train step == single-device step (same batch/params)."""
    run_script(COMMON + """
import dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_arch
from repro.models import init_params
from repro.train.optimizer import OptConfig, init_opt
from repro.train.train_step import TrainConfig, build_train_step
from repro.train.sharding import param_specs, set_rules
from repro.launch.specs import resolve_tree

cfg = dataclasses.replace(get_arch("granite-3-2b", smoke=True),
                          dtype=jnp.float32)
params = init_params(cfg, jax.random.PRNGKey(0))
tcfg = TrainConfig(opt=OptConfig(lr=1e-2))
step = build_train_step(cfg, tcfg)
opt = init_opt(params, tcfg.opt)
rngb = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rngb.integers(0, cfg.vocab, (4, 16)), jnp.int32),
         "labels": jnp.asarray(rngb.integers(0, cfg.vocab, (4, 16)), jnp.int32)}

# single device reference
p1, o1, _, m1 = jax.jit(step)(params, opt, None, batch)

# sharded
pspecs = resolve_tree(param_specs(params), params, mesh)
pshard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)
params_s = jax.tree_util.tree_map(lambda x, s: jax.device_put(x, s), params, pshard)
set_rules({"batch": ("data",), "seq": None, "seq_attn": None, "embed": None,
           "heads": None, "kv_heads": None, "head_dim": None, "mlp": "model",
           "vocab": "model", "expert": "model", "state": None})
with mesh:
    p2, o2, _, m2 = jax.jit(step)(params_s, opt, None, batch)
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
# fp32 collective-reduction order differs across shards (and across GSPMD
# partitioner generations: old jax needs the atol headroom)
for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)
print("sharded train step OK")
""")


@pytest.mark.slow
def test_compressed_ring_allreduce():
    """Beyond-paper distributed trick: int8 ring all-reduce over ppermute
    with error feedback matches the exact mean within quantization error."""
    run_script(COMMON + """
from repro.train.optimizer import _q8, _dq8

def compressed_allreduce(x):
    n = 8
    acc = x
    val = x
    for _ in range(n - 1):
        codes, scale = _q8(val)      # int8 on the wire
        val = _dq8(codes, scale)
        val = jax.lax.ppermute(val, "flat",
                               perm=[(i, (i + 1) % n) for i in range(n)])
        acc = acc + val
    return acc / n

mesh1 = make_mesh((8,), ("flat",))
xs = np.random.default_rng(0).normal(size=(8, 64)).astype(np.float32)
f = jax.jit(shard_map(compressed_allreduce, mesh=mesh1,
            in_specs=jax.sharding.PartitionSpec("flat"),
            out_specs=jax.sharding.PartitionSpec("flat")))
got = np.asarray(f(jnp.asarray(xs.reshape(8*1, 64))))
want = xs.mean(0, keepdims=True)
# every shard holds an approximation of the mean
err = np.abs(got - want).max()
rel = err / (np.abs(want).max() + 1e-9)
assert rel < 0.15, rel
print("compressed ring allreduce OK, rel err", rel)
""")


@pytest.mark.slow
def test_native_block_view_sharded_bit_identical():
    """Acceptance: a sharded halo'd stencil launch (the fused LB step) with
    AoSoA inputs under the native view='block' lowering is bit-identical to
    view='staged-nd' — both for the halo='pre' single launch and the
    halo='overlap' split schedule — on 8 fake devices, and matches the
    single-shard jnp oracle."""
    run_script(COMMON + """
from repro.core import Field, TargetConfig, aosoa
from repro.core import halo as halo_mod
from repro.core.overlap import overlap_launch
from repro.core.plan import LoweringPlan
from repro.kernels.lb_propagation.ops import collide_propagate_graph
from repro.lattice import Domain

LAT = (16, 8, 8)
dom = Domain(global_shape=LAT, mesh=mesh,
             dim_axes=("data", "model", None), halo=1)
rng = np.random.default_rng(0)
dist = (1.0 + 0.1 * rng.normal(size=(19, *LAT))).astype(np.float32)
force = (0.01 * rng.normal(size=(3, *LAT))).astype(np.float32)
lay = aosoa(4)  # local padded lattice (6, 6, 10): inner planes 60, 4 | 60
g = collide_propagate_graph(0.8)
tgt = TargetConfig("pallas", vvl=64)

def pad(x):
    return jnp.pad(x, [(0, 0)] + [(1, 1)] * 3, mode="wrap")

def local(d_nd, f_nd, view, halo):
    dF = Field.from_canonical("dist", pad(d_nd), pad(d_nd).shape[1:], lay)
    fF = Field.from_canonical("force", pad(f_nd), pad(f_nd).shape[1:], lay)
    plan = LoweringPlan("pallas", bx=1, halo=halo, interpret=True, view=view)
    if halo == "pre":
        # layout-preserving exchange: AoSoA shards in, AoSoA shards out,
        # so the native-block launch stages the physical tiles as-is
        dF = halo_mod.exchange_field(dF, dom.decomposed, width=1)
        fF = halo_mod.exchange_field(fF, dom.decomposed, width=1)
        out = g.launch({"dist": dF, "force": fF}, config=tgt,
                       outputs=("dist2",), halo="pre", plan=plan)
    else:
        out = overlap_launch(g, {"dist": dF, "force": fF},
                             decomposed=dom.decomposed, config=tgt,
                             outputs=("dist2",), halo="overlap", plan=plan)
    assert out["dist2"].layout == lay
    return out["dist2"].canonical_nd()

sh = dom.sharding()
spec = dom.spec()
d = jax.device_put(jnp.asarray(dist), sh)
f = jax.device_put(jnp.asarray(force), sh)
results = {}
for view in ("staged-nd", "block"):
    for halo in ("pre", "overlap"):
        fn = jax.jit(shard_map(
            lambda a, b, _v=view, _h=halo: local(a, b, _v, _h),
            mesh=mesh, in_specs=(spec, spec), out_specs=spec))
        results[(view, halo)] = np.asarray(fn(d, f))
base = results[("staged-nd", "pre")]
for k, v in results.items():
    np.testing.assert_array_equal(v, base, err_msg=str(k))
# single-shard jnp oracle (periodic == the wrap+exchange decomposition)
distF = Field.from_canonical("dist", jnp.asarray(dist), LAT, aosoa(4))
forceF = Field.from_canonical("force", jnp.asarray(force), LAT, aosoa(4))
want = g.launch({"dist": distF, "force": forceF},
                config=TargetConfig("jnp"), outputs=("dist2",))
np.testing.assert_allclose(base, want["dist2"].canonical_nd(),
                           rtol=1e-5, atol=1e-6)
print("native block sharded OK")
""")


@pytest.mark.slow
def test_tiled_lowering_sharded_bit_identical():
    """Acceptance (tiled y/z lowering): the sharded fused LB step under a
    tiled plan (LoweringPlan.by/bz — per-shard VMEM bounded by the tile,
    not the lattice) is bit-identical to the untiled whole-staging plan on
    8 fake devices, for both the halo='pre' single launch and the
    halo='overlap' split schedule (sub-launches inherit the tiles through
    sub_lattice_plan), and matches the single-shard jnp oracle."""
    run_script(COMMON + """
import dataclasses
from repro.core import Field, SOA, TargetConfig
from repro.core import halo as halo_mod
from repro.core.overlap import overlap_launch
from repro.core.plan import LoweringPlan
from repro.kernels.lb_propagation.ops import collide_propagate_graph
from repro.lattice import Domain

LAT = (16, 8, 8)  # mesh (4, 2): local interior (4, 4, 8)
dom = Domain(global_shape=LAT, mesh=mesh,
             dim_axes=("data", "model", None), halo=1)
rng = np.random.default_rng(0)
dist = (1.0 + 0.1 * rng.normal(size=(19, *LAT))).astype(np.float32)
force = (0.01 * rng.normal(size=(3, *LAT))).astype(np.float32)
g = collide_propagate_graph(0.8)
tgt = TargetConfig("pallas", vvl=64)
untiled = LoweringPlan("pallas", bx=1, interpret=True)
tiles = [(2, 0), (0, 4), (2, 4)]  # divide the (4, 4, 8) local interior

def pad(x):
    return jnp.pad(x, [(0, 0)] + [(1, 1)] * 3, mode="wrap")

def local(d_nd, f_nd, plan, halo):
    dF = Field.from_canonical("dist", pad(d_nd), pad(d_nd).shape[1:], SOA)
    fF = Field.from_canonical("force", pad(f_nd), pad(f_nd).shape[1:], SOA)
    plan = dataclasses.replace(plan, halo=halo)
    if halo == "pre":
        dF = halo_mod.exchange_field(dF, dom.decomposed, width=1)
        fF = halo_mod.exchange_field(fF, dom.decomposed, width=1)
        out = g.launch({"dist": dF, "force": fF}, config=tgt,
                       outputs=("dist2",), halo="pre", plan=plan)
    else:
        out = overlap_launch(g, {"dist": dF, "force": fF},
                             decomposed=dom.decomposed, config=tgt,
                             outputs=("dist2",), halo="overlap", plan=plan)
    return out["dist2"].canonical_nd()

sh = dom.sharding()
spec = dom.spec()
d = jax.device_put(jnp.asarray(dist), sh)
f = jax.device_put(jnp.asarray(force), sh)
results = {}
for by, bz in [(0, 0)] + tiles:
    plan = dataclasses.replace(untiled, by=by, bz=bz)
    for halo in ("pre", "overlap"):
        fn = jax.jit(shard_map(
            lambda a, b, _p=plan, _h=halo: local(a, b, _p, _h),
            mesh=mesh, in_specs=(spec, spec), out_specs=spec))
        results[(by, bz, halo)] = np.asarray(fn(d, f))
base = results[(0, 0, "pre")]
for k, v in results.items():
    np.testing.assert_array_equal(v, base, err_msg=str(k))
# single-shard jnp oracle (periodic == the wrap+exchange decomposition)
distF = Field.from_canonical("dist", jnp.asarray(dist), LAT, SOA)
forceF = Field.from_canonical("force", jnp.asarray(force), LAT, SOA)
want = g.launch({"dist": distF, "force": forceF},
                config=TargetConfig("jnp"), outputs=("dist2",))
np.testing.assert_allclose(base, want["dist2"].canonical_nd(),
                           rtol=1e-5, atol=1e-6)
print("tiled sharded OK")
""")
