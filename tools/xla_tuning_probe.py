"""Fine-tune the XLA-path mm1 operating point around the measured peak
(R=131072, N=16000, f32 -> 386M events/s, BENCH_NOTES round 5): ring
cap, longer workloads, non-power-of-two lane counts.  One JSON line per
cell; safe to cut anywhere (each cell is independent).

Usage: python tools/xla_tuning_probe.py
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
from cimba_tpu.models import mm1


def log(**kw):
    print(json.dumps(kw), flush=True)


def cell(tag, R, N, cap=128, prof="f32"):
    with config.profile(prof):
        spec, _ = mm1.build(queue_cap=cap, record=False)
        run = cl.make_lanes_run(spec)

        def experiment(n):
            sims = run(jax.vmap(
                lambda rep: cl.init_sim(spec, 2026, rep, mm1.params(n))
            )(jnp.arange(R)))[1]
            return (
                jnp.sum(sims.n_events.astype(jnp.int64)),
                jnp.sum((sims.err != 0).astype(jnp.int32)),
            )

        fn = jax.jit(experiment)
        jax.block_until_ready(fn(jnp.int32(1)))
        t0 = time.perf_counter()
        ev, failed = jax.block_until_ready(fn(jnp.int32(N)))
        dt = time.perf_counter() - t0
        log(phase="cell", tag=tag, R=R, N=N, cap=cap, profile=prof,
            events=int(ev), wall_s=dt, rate=int(ev) / dt, failed=int(failed))


def main():
    log(phase="xla_tuning_start", backend=jax.default_backend())
    cell("peak_repro", 131072, 16000)        # reproduce the 386M point
    cell("longer", 131072, 32000)            # wall ~23 s, tail amortization
    cell("cap96", 131072, 16000, cap=96)     # ring bytes -25% (failures counted)
    cell("cap64_diag", 131072, 16000, cap=64)  # diagnosis only: bias risk
    cell("r3q", 98304, 16000)                # 0.75x lanes (HBM pressure)
    cell("r196k", 196608, 16000)             # 1.5x lanes


if __name__ == "__main__":
    main()
