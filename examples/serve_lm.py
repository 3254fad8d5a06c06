"""Batched LM serving: prefill a batch of prompts, then decode with the
KV/state cache — the serve_step the decode_32k / long_500k dry-run cells
lower, on a CPU-sized config.

    PYTHONPATH=src python examples/serve_lm.py --arch rwkv6-7b --steps 24
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import setup_compile_cache
from repro.configs import ARCH_IDS, get_arch
from repro.models import init_params
from repro.train.serve_step import build_serve_step, generate


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_arch(args.arch, smoke=True)
    if cfg.enc_dec:
        raise SystemExit("use train_lm for enc-dec; serving demo targets "
                         "decoder-only archs")
    params = init_params(cfg, jax.random.PRNGKey(0))
    print(f"arch={cfg.name} (reduced), batch={args.batch}")

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(1, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)

    jit_step = jax.jit(build_serve_step(cfg))
    t0 = time.perf_counter()
    out = generate(params, cfg, prompts, steps=args.steps,
                   s_max=args.prompt_len + args.steps + 8,
                   temperature=args.temperature,
                   rng=jax.random.PRNGKey(1), jit_step=jit_step)
    dt = time.perf_counter() - t0
    toks = np.asarray(out)
    total_new = args.batch * args.steps
    print(f"generated {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.0f} tok/s on CPU, includes compile)")
    for b in range(min(args.batch, 2)):
        print(f"  seq[{b}]: {toks[b].tolist()}")
    assert toks.shape == (args.batch, args.prompt_len + args.steps)
    print("OK")


if __name__ == "__main__":
    main()
