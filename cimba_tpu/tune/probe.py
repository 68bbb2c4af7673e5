"""The step-probe workload: a timer-heavy model for the schedule search.

A big-table ticker: each resume re-arms ``per_resume`` timers spread
over a large event table, so every step pays the full event-set scan
and a burst of schedules.  The tune tests search, pin and persist
schedules on it (docs/21_autotune.md).

Unlike the raw ``make_step`` microprobe in tools/, this spec runs
through the ordinary chunked stream path (``t_end`` bounds the run),
so search arms are measured and bitwise-pinned by exactly the
machinery that serves production traffic.
"""

from __future__ import annotations

# module-level imports (the models/ convention): the block below must
# reference these as GLOBALS, not closure cells — a module object in a
# closure cell has no stable value digest, and the probe's whole point
# is exercising the persistent tuned-entry path (UnstableStoreKey
# would demote every search on it to unsaveable)
import jax.numpy as jnp

from cimba_tpu.core import api, cmd
from cimba_tpu.core.model import Model
from cimba_tpu.stats import summary as sm

__all__ = ["build", "params", "DEFAULT_T_END"]

#: default horizon: ~`t_end / hold` resumes per lane — enough steps for
#: the per-step cost difference to dominate dispatch overhead on the
#: CPU window while the schedule burst stays inside the event table
DEFAULT_T_END = 0.2


def build(event_cap: int = 2048, per_resume: int = 16,
          rearm_spread: int = 1793, hold: float = 0.002):
    """The mutation-bursty ticker spec: one process holding ``hold``
    per resume and re-arming ``per_resume`` far-future timers (spread
    over ``rearm_spread`` distinct times so pattern-cancel never
    collapses them) into an ``event_cap``-slot table.
    ``event_cap`` must hold the burst: with ``t_end`` T, a lane
    schedules ``~T/hold * per_resume`` timers (all far-future), so size
    the horizon accordingly.  Returns ``(spec, ())`` in the model
    builders' convention.  The probe records per-resume waits so the
    default ``summary_path`` works unchanged."""
    m = Model("tune_step_probe", n_ilocals=1, event_cap=event_cap)

    @m.user_state
    def user_init(params):
        return {"wait": sm.empty()}

    @m.block
    def tick(sim, p, sig):
        k = api.local_i(sim, p, 0)
        sim = api.add_local_i(sim, p, 0, 1)
        for i in range(per_resume):
            sim, _ = api.timer_add(
                sim, p,
                5.0 + ((k + i) % rearm_spread).astype(jnp.float32)
                * 0.003,
                0,
            )
        wait = sm.add(sim.user["wait"], api.clock(sim))
        sim = api.set_user(sim, {**sim.user, "wait": wait})
        return sim, cmd.hold(hold, next_pc=tick.pc)

    m.process("ticker", entry=tick)
    return m.build(), ()


def params(_n=None):
    """The probe takes no per-lane parameters (the model-builder
    convention's params hook; the workload knob is ``t_end``)."""
    return None
