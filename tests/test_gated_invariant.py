"""Self-enforcing _vswitch invariant (VERDICT r4 weak #3).

The kernel's zero-merge handler chain is correct only if every
``_gated`` handler is a bitwise no-op under ``gate=False`` — one
ungated write corrupts OTHER lanes' state, only under vmap, far from
the cause.  ``loop.validate_gated_handlers`` enforces it structurally:
eager, concrete, once per kernel build (wired behind the dbc debug tier
in pallas_run).  Every vmapped XLA step rests on it too
(``make_step(batched=True)``, the chunk programs of every benchmark
cell), so it is also checked on a batched Sim, as vmap runs the
handlers with the gate off on every other lane.  These tests prove the
check passes for the real handler tables and FAILS for a deliberately
broken handler.
"""

import jax
import jax.numpy as jnp
import pytest

from cimba_tpu import config
from cimba_tpu.core import dyn
from cimba_tpu.core import loop as cl
from cimba_tpu.models import awacs, mg1, mm1


def _sim():
    spec, _ = mm1.build(record=False)
    return spec, cl.init_sim(spec, 2026, 0, (1.0 / 0.9, 1.0, 50))


def test_real_handler_table_passes():
    with config.profile("f32"):
        spec, sim = _sim()
        cl.validate_gated_handlers(spec, sim)  # raises on violation


def _batched(name, lanes=4):
    if name == "mm1":
        spec, params = mm1.build(record=False)[0], (1.0 / 0.9, 1.0, 50)
    elif name == "mg1":
        spec, params = mg1.build(queue_cap=64)[0], (1.25, 1.0, 2.0, 50)
    else:
        spec, params = awacs.build(16, dwell=0.04)[0], awacs.params(1.0)
    sims = jax.vmap(lambda r: cl.init_sim(spec, 2026, r, params))(
        jnp.arange(lanes)
    )
    # run a few events first, so the tables hold more than their
    # initial values
    return spec, jax.vmap(cl.make_run(spec, max_steps=9))(sims)


@pytest.mark.parametrize("name", ["mm1", "mg1", "awacs"])
def test_batched_handler_table_passes(name):
    with config.profile("f32"):
        spec, sims = _batched(name)
        cl.validate_gated_handlers(spec, sims)  # raises on violation


def test_broken_handler_fails_by_name():
    """A handler with ONE ungated write (the exact bug class the
    invariant exists to catch) is rejected, named, with the leaf path."""

    def bad_handler(sim, p, cmd, is_retry, gate=True):
        # pc write forgot its gate: a no-op only when gate is true
        procs = sim.procs._replace(
            pc=dyn.dset(sim.procs.pc, p, cmd.next_pc)  # MISSING pred=gate
        )
        return sim._replace(procs=procs), jnp.asarray(True)

    with config.profile("f32"):
        spec, sim = _sim()
        # make the ungated write visible: target pc differs from current
        sim = sim._replace(
            procs=sim.procs._replace(pc=sim.procs.pc + 7)
        )
        with pytest.raises(AssertionError, match="bad_handler"):
            cl._check_gated_noop("bad_handler", bad_handler, sim, tag=0)


def test_broken_handler_fails_batched():
    """The batched check finds the ungated write on the gated-off lanes
    while the gated-on lanes write as they should."""

    def bad_handler(sim, p, cmd, is_retry, gate=True):
        procs = sim.procs._replace(
            pc=dyn.dset(sim.procs.pc, p, cmd.next_pc + 3)  # MISSING pred
        )
        return sim._replace(procs=procs), jnp.asarray(True)

    with config.profile("f32"):
        spec, sims = _batched("mm1")
        with pytest.raises(AssertionError, match="bad_handler"):
            cl._check_gated_noop("bad_handler", bad_handler, sims, tag=0)


def test_equal_but_new_leaf_is_accepted():
    """The invariant is VALUE identity, not object identity: a handler
    that rebuilds a leaf with identical contents is still a no-op."""

    def rebuilder(sim, p, cmd, is_retry, gate=True):
        procs = sim.procs._replace(pc=sim.procs.pc + 0)  # new, equal
        return sim._replace(procs=procs), jnp.asarray(True)

    with config.profile("f32"):
        spec, sim = _sim()
        cl._check_gated_noop("rebuilder", rebuilder, sim, tag=0)
