"""Dispatch-cost probes for the round-6 arms (docs/11_dispatch_cost.md).

Three measurements, each isolating one term of the step-cost model:

1. ``--probe arms``: full-run mm1 events/s at the CPU default operating
   point, packed+hierarchical vs flat (what ``bench.py --config mm1``
   records under ``detail.dispatch_arms`` — this is the standalone
   repro).
2. ``--probe pop``: vmapped make_step() us/step on a POP-dominated
   big-table workload (~1.9k live timers at cap=2048, one re-arm + one
   pop per step), hier vs flat — the shape the two-level min helps.
3. ``--probe sched``: the same at 16 masked schedules per resume — the
   mutation-heavy adversarial shape, where the per-mutation block
   refresh costs more than the saved scan (kept honest here; the flat
   oracle flag is the escape hatch).

Run with JAX_PLATFORMS=cpu on a host without a chip.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from cimba_tpu import config
from cimba_tpu.core import api, cmd
from cimba_tpu.core import loop as cl
from cimba_tpu.core.model import Model


def _timer_spec(cap, per_resume, rearm_spread):
    m = Model("probe", n_ilocals=1, event_cap=cap)

    @m.block
    def tick(sim, p, sig):
        k = api.local_i(sim, p, 0)
        sim = api.add_local_i(sim, p, 0, 1)
        for i in range(per_resume):
            sim, _ = api.timer_add(
                sim, p,
                5.0 + ((k + i) % rearm_spread).astype(jnp.float32) * 0.003,
                0,
            )
        return sim, cmd.hold(0.002, next_pc=tick.pc)

    m.process("ticker", entry=tick)
    return m.build()


def step_probe(hier, per_resume, R, cap, fill, iters):
    config.EVENTSET_HIER = hier
    try:
        spec = _timer_spec(cap, per_resume, rearm_spread=1793)
        step = jax.vmap(cl.make_step(spec, batched=True))

        def warmed(sims, k):
            return jax.lax.fori_loop(0, k, lambda i, s: step(s), sims)

        sims = jax.jit(
            jax.vmap(lambda r: cl.init_sim(spec, 2026, r, None))
        )(jnp.arange(R))
        sims = jax.block_until_ready(
            jax.jit(lambda s: warmed(s, fill))(sims)
        )
        occ = float(
            jnp.mean(jnp.sum(jnp.isfinite(sims.events.time), axis=1))
        )
        fn = jax.jit(lambda s: warmed(s, iters))
        jax.block_until_ready(fn(sims))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(sims))
        dt = time.perf_counter() - t0
        return dt / iters * 1e6, occ
    finally:
        config.EVENTSET_HIER = None


def arms_probe(R, N):
    from cimba_tpu.models import mm1

    out = {}
    for arm, (pack, hier) in (
        ("packed_hier", (True, True)), ("flat", (False, False))
    ):
        config.XLA_PACK, config.EVENTSET_HIER = pack, hier
        try:
            spec, _ = mm1.build(record=False)
            run = cl.make_run(spec, batched=True)

            def experiment(n):
                sims = jax.vmap(
                    lambda r: run(cl.init_sim(spec, 2026, r, mm1.params(n)))
                )(jnp.arange(R))
                return jnp.sum(sims.n_events.astype(jnp.int64))

            fn = jax.jit(experiment)
            jax.block_until_ready(fn(jnp.int32(1)))
            t0 = time.perf_counter()
            events = int(jax.block_until_ready(fn(jnp.int32(N))))
            out[arm] = events / (time.perf_counter() - t0)
        finally:
            config.XLA_PACK = config.EVENTSET_HIER = None
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--probe", default="all", choices=["all", "arms", "pop", "sched"]
    )
    which = ap.parse_args().probe
    if which in ("all", "arms"):
        rates = arms_probe(R=256, N=500)
        ratio = rates["packed_hier"] / rates["flat"]
        print(
            f"arms (mm1 R=256 N=500): packed_hier "
            f"{rates['packed_hier']:.0f} ev/s, flat "
            f"{rates['flat']:.0f} ev/s ({ratio:.2f}x)"
        )
    for name, per_resume, fill, iters in (
        ("pop", 1, 2200, 300), ("sched", 16, 80, 50),
    ):
        if which not in ("all", name):
            continue
        for hier in (False, True):
            us, occ = step_probe(
                hier, per_resume, R=64, cap=2048, fill=fill, iters=iters
            )
            print(
                f"{name} (per_resume={per_resume}, ~{occ:.0f} live): "
                f"hier={hier} {us:.0f} us/step"
            )


if __name__ == "__main__":
    main()
