"""Per-event cost profiler: where does dispatcher time go?

Measures the steady-state cost of one dispatcher iteration (every lane
dispatches one event) isolated from init and convoy effects: K iterations
of the vmapped step inside one jit, timed after warmup.  Also reports the
compiled module's op/byte footprint via XLA cost analysis.

Usage:
    python tools/profile_step.py [--model mm1] [--r 256 8192] [--iters 200]

Run with JAX_PLATFORMS=cpu on a host without a chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from cimba_tpu.core import loop as cl


def build_model(name: str):
    if name == "mm1":
        from cimba_tpu.models import mm1

        spec, _ = mm1.build(record=False)
        return spec, mm1.params(10**9)  # effectively endless: steady state
    if name == "mmc":
        from cimba_tpu.models import mmc

        spec, _ = mmc.build(record=False) if "record" in mmc.build.__code__.co_varnames else (mmc.build()[0], None)
        return spec, mmc.params(10**9) if hasattr(mmc, "params") else None
    raise SystemExit(f"unknown model {name}")


def profile(spec, params, r: int, iters: int):
    step = jax.vmap(cl.make_step(spec, batched=True))

    def init(rep):
        return cl.init_sim(spec, 2026, rep, params)

    sims = jax.jit(jax.vmap(init))(jnp.arange(r))

    def k_steps(s):
        return jax.lax.fori_loop(0, iters, lambda i, x: step(x), s)

    fn = jax.jit(k_steps)
    lowered = fn.lower(sims)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}

    warm = jax.block_until_ready(fn(sims))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(warm))
    wall = time.perf_counter() - t0

    n_events = int(jnp.sum(out.n_events - warm.n_events))
    return {
        "r": r,
        "iters": iters,
        "wall_s": wall,
        "events": n_events,
        "events_per_sec": n_events / wall,
        "us_per_iter": wall / iters * 1e6,
        "flops_per_iter": cost.get("flops", -1) / iters if cost else None,
        "bytes_per_iter": (
            cost.get("bytes accessed", -1) / iters if cost else None
        ),
        "backend": jax.default_backend(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mm1")
    ap.add_argument("--r", type=int, nargs="+", default=[256])
    ap.add_argument("--iters", type=int, default=200)
    args = ap.parse_args()

    spec, params = build_model(args.model)
    for r in args.r:
        print(json.dumps(profile(spec, params, r, args.iters)), flush=True)


if __name__ == "__main__":
    main()
