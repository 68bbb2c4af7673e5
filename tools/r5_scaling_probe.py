"""Round-5 follow-up probe: find each path's best operating point on
the real chip, now that first contact established the baselines
(XLA-while 39.6M events/s at R=4096; kernel 17.4M at R=8192/chunk=512
with a measured ~139 us/step fixed cost and ~75 ms/launch overhead).

Phases (cautious-first, one JSON line each so a wedge leaves evidence):
  1. XLA path lane scaling: R = 8192..32768 (the headline upside).
  2. Kernel big-chunk cells: amortize the per-launch overhead and test
     whether per-step cost stays flat in L (run only cells that passed
     the offline Mosaic AOT compile first — tests/test_mosaic_aot.py
     discipline).
  3. AWACS XLA lane scaling (R=16 left ~19x on the table).

Usage: python tools/r5_scaling_probe.py [phase...]   (default: 1 2 3)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from cimba_tpu import config
from cimba_tpu.core import loop as cl
from cimba_tpu.core import pallas_run as pr
from cimba_tpu.models import mm1


def log(**kw):
    print(json.dumps(kw), flush=True)


def xla_scaling(N=500):
    log(phase="xla_scaling_start", backend=jax.default_backend(), N=N)
    spec, _ = mm1.build(record=False)
    run = cl.make_lanes_run(spec)

    for R in (4096, 8192, 16384, 32768):
        def experiment(n):
            sims = run(jax.vmap(
                lambda rep: cl.init_sim(spec, 2026, rep, mm1.params(n))
            )(jnp.arange(R)))[1]
            return (
                jnp.sum(sims.n_events),
                jnp.sum((sims.err != 0).astype(jnp.int32)),
            )

        fn = jax.jit(experiment)
        jax.block_until_ready(fn(jnp.int32(1)))
        t0 = time.perf_counter()
        ev, failed = jax.block_until_ready(fn(jnp.int32(N)))
        dt = time.perf_counter() - t0
        log(phase="xla_cell", R=R, events=int(ev), wall_s=dt,
            rate=int(ev) / dt, failed=int(failed))


def kernel_big(N=500):
    log(phase="kernel_big_start", backend=jax.default_backend(), N=N)
    with config.profile("f32"):
        spec, _ = mm1.build(record=False)
        for R, chunk in (
            (8192, 1024), (8192, 2048), (16384, 512), (16384, 1024),
        ):
            try:
                sims = jax.jit(jax.vmap(
                    lambda r: cl.init_sim(spec, 2026, r, mm1.params(N))
                ))(jnp.arange(R))
                jax.block_until_ready(jax.tree.leaves(sims))
                krun = pr.make_kernel_run(spec, chunk_steps=chunk)
                kout = krun(sims)  # compile + first run
                jax.block_until_ready(jax.tree.leaves(kout))
                t0 = time.perf_counter()
                kout = krun(sims)
                jax.block_until_ready(jax.tree.leaves(kout))
                dt = time.perf_counter() - t0
                ev_n = int(kout.n_events.sum())
                log(phase="kernel_cell", R=R, chunk=chunk, events=ev_n,
                    wall_s=dt, rate=ev_n / dt,
                    failed=int((kout.err != 0).sum()))
            except Exception as e:  # keep probing the other cells
                log(phase="kernel_cell", R=R, chunk=chunk,
                    error=f"{type(e).__name__}: {e}"[:300])


def awacs_scaling(t_end=40.0):
    from cimba_tpu.models import awacs

    log(phase="awacs_scaling_start", backend=jax.default_backend(),
        t_end=t_end)
    spec, _ = awacs.build(1000)
    run = cl.make_lanes_run(spec)
    for R in (64, 256):
        def experiment(t):
            sims = run(jax.vmap(
                lambda rep: cl.init_sim(spec, 2026, rep, (t,))
            )(jnp.arange(R)))[1]
            return (
                jnp.sum(sims.n_events),
                jnp.sum((sims.err != 0).astype(jnp.int32)),
            )

        fn = jax.jit(experiment)
        jax.block_until_ready(fn(jnp.asarray(0.5)))
        t0 = time.perf_counter()
        ev, failed = jax.block_until_ready(fn(jnp.asarray(t_end)))
        dt = time.perf_counter() - t0
        log(phase="awacs_cell", R=R, events=int(ev), wall_s=dt,
            rate=int(ev) / dt, failed=int(failed))


if __name__ == "__main__":
    phases = sys.argv[1:] or ["1", "2", "3"]
    if "1" in phases:
        xla_scaling()
    if "2" in phases:
        kernel_big()
    if "3" in phases:
        awacs_scaling()
    log(phase="done")
