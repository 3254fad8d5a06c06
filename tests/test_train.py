"""Training substrate: optimizers, microbatching, gradient compression."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import init_params
from repro.train.data import DataConfig, TokenStream, write_token_file
from repro.train.optimizer import OptConfig, _dq8, _dq8v, _q8, _q8v, init_opt
from repro.train.train_step import TrainConfig, build_train_step, init_ef_state


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_arch("granite-3-2b", smoke=True),
                              dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    stream = TokenStream(DataConfig(vocab=cfg.vocab, seq_len=16,
                                    global_batch=4))
    t, l = stream.batch(0)
    fixed = {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)}
    return cfg, params, fixed


# adamw stays in tier-1; the 8-bit/adafactor variants compile a second and
# third full train graph apiece, so they ride the -m slow sweep
@pytest.mark.parametrize(
    "kind",
    ["adamw",
     pytest.param("adamw8bit", marks=pytest.mark.slow),
     pytest.param("adafactor", marks=pytest.mark.slow)],
)
def test_optimizer_memorizes_fixed_batch(setup, kind):
    cfg, params, fixed = setup
    tcfg = TrainConfig(opt=OptConfig(kind=kind, lr=1e-2))
    step = jax.jit(build_train_step(cfg, tcfg))
    p, o, e = params, init_opt(params, tcfg.opt), None
    losses = []
    for _ in range(15):
        p, o, e, m = step(p, o, e, fixed)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 2.0, (kind, losses)


@pytest.mark.slow
def test_grad_compression_converges(setup):
    cfg, params, fixed = setup
    tcfg = TrainConfig(opt=OptConfig(lr=1e-2), grad_compression=True)
    step = jax.jit(build_train_step(cfg, tcfg))
    p, o, e = params, init_opt(params, tcfg.opt), init_ef_state(params)
    losses = []
    for _ in range(15):
        p, o, e, m = step(p, o, e, fixed)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 2.0


@pytest.mark.slow
def test_microbatch_equals_full_batch(setup):
    """Gradient accumulation is loss-equivalent to the full batch."""
    cfg, params, fixed = setup
    t1 = TrainConfig(opt=OptConfig(lr=1e-3), microbatches=1)
    t2 = TrainConfig(opt=OptConfig(lr=1e-3), microbatches=2)
    s1 = jax.jit(build_train_step(cfg, t1))
    s2 = jax.jit(build_train_step(cfg, t2))
    p1, o1, _, m1 = s1(params, init_opt(params, t1.opt), None, fixed)
    p2, o2, _, m2 = s2(params, init_opt(params, t2.opt), None, fixed)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


def test_q8_roundtrip_error_bound(rng):
    x = jnp.asarray(rng.normal(size=(16, 256)).astype(np.float32)) * 3.0
    codes, scale = _q8(x)
    err = np.abs(np.asarray(_dq8(codes, scale) - x))
    assert (err <= np.asarray(scale) * 0.5 + 1e-7).all()


def test_q8v_preserves_order_of_magnitude(rng):
    v = jnp.asarray(10.0 ** rng.uniform(-12, 2, size=(8, 64)))
    codes, lo, scale = _q8v(v)
    back = np.asarray(_dq8v(codes, lo, scale))
    ratio = back / np.asarray(v)
    assert (ratio > 0.5).all() and (ratio < 2.0).all()


def test_data_stream_determinism(tmp_path):
    cfg = DataConfig(vocab=1000, seq_len=8, global_batch=4, seed=3)
    s1, s2 = TokenStream(cfg), TokenStream(cfg)
    for step in [0, 5, 117]:
        a, al = s1.batch(step)
        b, bl = s2.batch(step)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(al, bl)
    # labels are next-token shifted
    assert a.shape == (4, 8) and al.shape == (4, 8)

    # file-backed
    toks = np.arange(10000) % 1000
    path = str(tmp_path / "tokens.bin")
    write_token_file(path, toks, 1000)
    fs = TokenStream(DataConfig(vocab=1000, seq_len=8, global_batch=2,
                                seed=1, path=path))
    t, l = fs.batch(0)
    np.testing.assert_array_equal(t[:, 1:], l[:, :-1])  # shift property


def test_append_libtpu_flags_keeps_what_the_machine_set():
    from repro.launch.train import OVERLAP_FLAGS, append_libtpu_flags

    env = {"LIBTPU_INIT_ARGS": "--xla_machine_fix=true "
                               "--xla_tpu_enable_latency_hiding_scheduler=false"}
    out = append_libtpu_flags(OVERLAP_FLAGS, env).split()
    # the machine's flags stay first and win over ours
    assert out[:2] == ["--xla_machine_fix=true",
                       "--xla_tpu_enable_latency_hiding_scheduler=false"]
    assert out[2:] == ["--xla_tpu_enable_async_collective_fusion=true"]
    assert env["LIBTPU_INIT_ARGS"] == " ".join(out)
    assert append_libtpu_flags(OVERLAP_FLAGS, {}) == " ".join(OVERLAP_FLAGS)

