"""Production training launcher: mesh + sharding rules + fault-tolerant
loop for any --arch.

On real TPU pods this process runs per host under the usual multi-host
bootstrap (jax.distributed.initialize); on this container it degrades to
the single local device with identical code paths.  XLA flags for
compute/collective overlap (latency-hiding scheduler) are set here.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --steps 100 --smoke-arch
"""

import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import ARCH_IDS, get_arch
from repro.models import init_params
from repro.train.data import DataConfig, TokenStream
from repro.train.fault import LoopConfig, run_loop
from repro.train.optimizer import OptConfig, init_opt, opt_kind_for
from repro.train.sharding import param_specs, set_rules
from repro.train.train_step import TrainConfig, build_train_step

# collective/compute overlap: XLA's latency-hiding scheduler and async
# collectives (the TPU defaults; stated explicitly because they are part of
# the §Perf story)
OVERLAP_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_latency_hiding_scheduler=true",
)


def append_libtpu_flags(flags, environ=os.environ) -> str:
    """Add ``flags`` to ``$LIBTPU_INIT_ARGS`` before the TPU backend starts.
    Whatever the variable already holds is kept — a machine may set flags
    it needs there — and a flag it already names is not added again."""
    have = environ.get("LIBTPU_INIT_ARGS", "").split()
    named = {f.split("=", 1)[0] for f in have}
    extra = [f for f in flags if f.split("=", 1)[0] not in named]
    environ["LIBTPU_INIT_ARGS"] = " ".join(have + extra)
    return environ["LIBTPU_INIT_ARGS"]


def main():
    append_libtpu_flags(OVERLAP_FLAGS)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_ckpt")
    ap.add_argument("--smoke-arch", action="store_true")
    ap.add_argument("--data-path", default=None,
                    help="raw token file (synthetic stream if omitted)")
    args = ap.parse_args()

    cfg = get_arch(args.arch, smoke=args.smoke_arch)
    if args.smoke_arch:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)

    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1:
        # square-ish (data, model) mesh from whatever devices exist
        import numpy as np
        d = int(np.sqrt(n_dev))
        while n_dev % d:
            d -= 1
        from repro.core import compat
        mesh = compat.make_mesh((n_dev // d, d), ("data", "model"))
        set_rules({"batch": ("data",), "seq": None, "seq_attn": None,
                   "embed": None, "heads": None, "kv_heads": None,
                   "head_dim": None, "mlp": "model", "vocab": "model",
                   "expert": "model", "state": None})

    okind = opt_kind_for(cfg.name, cfg.param_count())
    tcfg = TrainConfig(opt=OptConfig(kind=okind, lr=args.lr))
    params = init_params(cfg, jax.random.PRNGKey(0))
    if mesh is not None:
        from repro.launch.specs import resolve_tree
        pspecs = resolve_tree(param_specs(params), params, mesh)
        params = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, pspecs)
    state = {"params": params, "opt": init_opt(params, tcfg.opt), "ef": None}

    step = jax.jit(build_train_step(cfg, tcfg))
    stream = TokenStream(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                    global_batch=args.batch, seed=0,
                                    path=args.data_path))

    def make_batch(tokens, labels):
        b = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
        if cfg.family == "vlm":
            b["image_embeds"] = jnp.zeros((args.batch, 4, cfg.d_model),
                                          cfg.dtype)
            b["positions"] = jnp.broadcast_to(
                jnp.arange(args.seq)[None, None],
                (3, args.batch, args.seq)).astype(jnp.int32)
        if cfg.enc_dec:
            b["frames"] = jnp.zeros((args.batch, args.seq, cfg.d_model),
                                    cfg.dtype)
        return b

    def on_step(i, m):
        if i % 10 == 0:
            print(f"step {i:5d} loss {m['loss']:.4f} "
                  f"({m['step_time_s']*1e3:.0f} ms)", flush=True)

    ctx = mesh if mesh is not None else _nullctx()
    with ctx:
        run_loop(step, state, stream,
                 LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                            ckpt_every=25, async_save=True),
                 make_batch=make_batch, on_step=on_step)


class _nullctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
