"""End-to-end LM training driver: any --arch, fault-tolerant loop with
checkpoints (reduced config on CPU by default; the full configs are for
the TPU meshes via the dry-run/launcher).

    PYTHONPATH=src python examples/train_lm.py --arch granite-3-2b \
        --steps 50 --ckpt-dir /tmp/ckpt
Kill it mid-run and re-run the same command: it resumes from the last
valid checkpoint and reproduces the uninterrupted loss trajectory.
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import setup_compile_cache
from repro.configs import ARCH_IDS, get_arch
from repro.models import init_params
from repro.train.data import DataConfig, TokenStream
from repro.train.fault import LoopConfig, run_loop
from repro.train.optimizer import OptConfig, init_opt
from repro.train.train_step import TrainConfig, build_train_step, init_ef_state


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--opt", default="adamw",
                    choices=["adamw", "adamw8bit", "adafactor"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--full-config", action="store_true",
                    help="use the FULL architecture (TPU-scale; not for CPU)")
    args = ap.parse_args()

    cfg = get_arch(args.arch, smoke=not args.full_config)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    print(f"arch={cfg.name} params~{cfg.param_count():,}")

    tcfg = TrainConfig(
        opt=OptConfig(kind=args.opt, lr=args.lr),
        microbatches=args.microbatches,
        grad_compression=args.grad_compression,
    )
    step = jax.jit(build_train_step(cfg, tcfg))
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = {
        "params": params,
        "opt": init_opt(params, tcfg.opt),
        "ef": init_ef_state(params) if args.grad_compression else None,
    }
    stream = TokenStream(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                    global_batch=args.batch, seed=0))

    def make_batch(tokens, labels):
        b = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
        if cfg.family == "vlm":
            b["image_embeds"] = jnp.zeros((args.batch, 4, cfg.d_model),
                                          jnp.float32)
            b["positions"] = jnp.broadcast_to(
                jnp.arange(args.seq)[None, None],
                (3, args.batch, args.seq)).astype(jnp.int32)
        if cfg.enc_dec:
            b["frames"] = jnp.zeros((args.batch, args.seq, cfg.d_model),
                                    jnp.float32)
        return b

    def on_step(i, m):
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {m['loss']:.4f}  "
                  f"gnorm {m['grad_norm']:.3f}  {m['step_time_s']*1e3:.0f} ms")

    run_loop(step, state, stream,
             LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                        ckpt_every=args.ckpt_every),
             make_batch=make_batch, on_step=on_step)
    print("done (checkpoints in", args.ckpt_dir + ")")


if __name__ == "__main__":
    main()
