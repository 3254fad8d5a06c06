"""Serving steps: prefill and batched autoregressive decode.

``serve_step`` is the dry-run decode unit: one new token per sequence
against a KV/state cache of seq_len — exactly the decode_32k / long_500k
shapes.  ``generate`` drives it for the runnable serving example (greedy
or temperature sampling over a batch of requests).

``build_cg_serve_step`` is the lattice-solver analogue: the jitted unit
of work the request scheduler (launch/serve.py) replays between admission
and drain — one convergence-masked batched CG iteration over a fixed
(lattice, batch-slots) bucket.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import decode_step, forward, init_cache


def build_serve_step(cfg: ArchConfig):
    """(params, cache, tokens (B,)) -> (logits (B, vocab), cache)."""

    def step(params, cache, tokens):
        return decode_step(params, cfg, cache, tokens)

    return step


def build_prefill(cfg: ArchConfig):
    """(params, batch) -> logits — the prefill_32k dry-run unit."""

    def prefill(params, batch):
        logits, _ = forward(params, cfg, batch)
        return logits

    return prefill


def build_cg_serve_step(u, kappa: float, config, *, tol: float,
                        max_iter: int, refine_every: int = 0,
                        config_hi=None):
    """Jitted masked-iteration step for batched CG serving: (BatchedCGState)
    -> BatchedCGState, one fused operator launch + one fused masked-update
    launch for the whole slot batch.  Converged/empty slots ride along
    bitwise frozen, so the scheduler can drain and refill them between
    calls without perturbing in-flight solves (apps.milc.cg semantics).

    ``refine_every > 0`` switches the returned step to the reliable-update
    signature ``step(state, rhs)``: every that many active iterations a
    slot's residual is recomputed exactly as ``b - A x`` through the
    ``config_hi`` operator (default: ``config`` stripped of any dtype
    policy) and its search direction restarted — the serving analogue of
    :func:`repro.apps.milc.cg.cg_batched`'s mixed-precision restarts."""
    import dataclasses

    from repro.apps.milc.cg import (
        batched_cg_iteration, batched_cg_refresh, wilson_normal_graph,
    )

    # the serving unit is a bound launch: graph + config + outputs fixed
    # at build time, only the solve vector (and its layout) vary per call
    bound = wilson_normal_graph(float(kappa)).bind(
        config=config, outputs=("ap", "pap"))

    # the gauge field is an argument of the jitted step, bound here: a
    # closed-over field would be embedded in the executable as a constant
    # (its halo'd copy constant-folded beside it), a program too large for
    # the persistent compile cache to keep
    def apply_a_dot(p, u):
        out = bound({"p": p, "u": u}, out_layouts={"ap": p.layout})
        return p.with_data(out["ap"].data), out["pap"].sum(axis=-1)

    if refine_every <= 0:
        def step(state, u):
            return batched_cg_iteration(
                state, functools.partial(apply_a_dot, u=u), config=config,
                tol=tol, max_iter=max_iter)

        return functools.partial(jax.jit(step), u=u)

    hi_cfg = config_hi or (
        dataclasses.replace(config, dtypes=None)
        if getattr(config, "dtypes", None) else config)
    bound_hi = wilson_normal_graph(float(kappa)).bind(
        config=hi_cfg, outputs=("ap", "pap"))

    def apply_a_dot_hi(p, u):
        out = bound_hi({"p": p, "u": u}, out_layouts={"ap": p.layout})
        return p.with_data(out["ap"].data), out["pap"].sum(axis=-1)

    def step_refined(state, rhs, u):
        state = batched_cg_iteration(
            state, functools.partial(apply_a_dot, u=u), config=config,
            tol=tol, max_iter=max_iter)
        return jax.lax.cond(
            jnp.any(jnp.logical_and(
                state.rr / state.b2 > tol,
                jnp.logical_and(state.it < max_iter,
                                state.it % refine_every == 0))),
            lambda s: batched_cg_refresh(
                s, rhs, functools.partial(apply_a_dot_hi, u=u), tol=tol,
                max_iter=max_iter, refine_every=refine_every),
            lambda s: s, state)

    return functools.partial(jax.jit(step_refined), u=u)


def generate(params, cfg: ArchConfig, prompt_tokens, *, steps: int,
             s_max: int, temperature: float = 0.0, rng=None,
             jit_step=None):
    """Greedy/sampled generation for the examples (CPU, smoke configs).
    prompt_tokens: (B, P) int32.  Returns (B, P+steps) tokens.

    ``rng`` is only consulted when ``temperature > 0``; it defaults to a
    fixed PRNGKey(0) so sampled generation is usable (and reproducible)
    out of the box — passing rng=None used to crash in jax.random.split."""
    B, P = prompt_tokens.shape
    if temperature > 0.0 and rng is None:
        rng = jax.random.PRNGKey(0)
    cache = init_cache(cfg, B, s_max)
    step = jit_step or jax.jit(build_serve_step(cfg))
    toks = [prompt_tokens[:, i] for i in range(P)]
    logits = None
    for i in range(P):
        logits, cache = step(params, cache, toks[i])
    out = list(toks)
    for t in range(steps):
        if temperature > 0.0:
            rng, sub = jax.random.split(rng)
            nxt = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        nxt = nxt.astype(jnp.int32)
        out.append(nxt)
        logits, cache = step(params, cache, nxt)
    return jnp.stack(out, axis=1)
