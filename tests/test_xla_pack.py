"""Packed XLA while-loop carry (core/carry.py via loop.make_run).

Packing is a carry-LAYOUT change, never a semantic one: the packed run
(the accelerator backends' layout) must be bitwise the per-leaf run
(the CPU's) on every Sim leaf, in both dtype profiles, batched and
unbatched.  The layout follows the backend through
``loop._packed_carry``; these tests drive both arms through it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cimba_tpu import config
from cimba_tpu.core import api, carry, cmd
from cimba_tpu.core import loop as cl
from cimba_tpu.core.model import Model
from cimba_tpu.models import mm1


def _assert_trees_equal(a, b):
    al, bl = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(al) == len(bl)
    for x, y in zip(al, bl):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_pack_unpack_roundtrip_is_identity():
    """pack -> unpack is bitwise identity on a real Sim's leaves (both
    layouts), including u32 rows riding the int buffer via bitcast."""
    spec, _ = mm1.build(record=True)
    sim = cl.init_sim(spec, 1, 0, mm1.params(10))
    leaves = jax.tree.leaves(sim)
    avals = [
        jax.ShapeDtypeStruct(jnp.shape(l), jnp.result_type(l))
        for l in leaves
    ]
    plan = carry.pack_plan(avals, lane_last=False)
    assert carry.n_buffers(plan) < len(leaves) // 4, (
        "packing should collapse the ~50-leaf carry to a handful of "
        f"buffers, got {carry.n_buffers(plan)} of {len(leaves)}"
    )
    back = carry.unpack(carry.pack(leaves, plan), plan)
    for x, y in zip(leaves, back):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize(
    "profile",
    [
        # heavyweight twins: over the timed tier-1 budget; tools/ci.sh cells
        # run both (the cheaper unbatched/timer-model packed-vs-flat pins
        # keep the packed bitwise contract tier-1)
        pytest.param("f64", marks=pytest.mark.slow),
        pytest.param("f32", marks=pytest.mark.slow),
    ],
)
def test_mm1_packed_matches_flat_bitwise(profile, carry_layout):
    with config.profile(profile):
        spec, _ = mm1.build(record=True)
        sims = jax.vmap(
            lambda r: cl.init_sim(spec, 7, r, mm1.params(50))
        )(jnp.arange(4))
        carry_layout(False)
        flat = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
        carry_layout(True)
        packed = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
        assert int(jnp.sum(flat.n_events)) > 300
        _assert_trees_equal(flat, packed)


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_unbatched_packed_matches_flat(carry_layout):
    spec, _ = mm1.build(record=False)
    sim = cl.init_sim(spec, 3, 0, mm1.params(40))
    carry_layout(False)
    flat = jax.jit(cl.make_run(spec))(sim)
    carry_layout(True)
    _assert_trees_equal(flat, jax.jit(cl.make_run(spec))(sim))


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_default_is_flat_jaxpr_on_cpu(carry_layout):
    """SENTINEL: on the CPU backend make_run traces the per-leaf jaxpr
    character-identically, and the packed layout traces another."""
    if jax.default_backend() != "cpu":
        pytest.skip("default-layout pin is for the CPU backend")
    spec, _ = mm1.build(record=False)
    sim = cl.init_sim(spec, 1, 0, mm1.params(10))
    j_default = str(jax.make_jaxpr(cl.make_run(spec))(sim))
    carry_layout(False)
    j_flat = str(jax.make_jaxpr(cl.make_run(spec))(sim))
    carry_layout(True)
    j_packed = str(jax.make_jaxpr(cl.make_run(spec))(sim))
    assert j_default == j_flat != j_packed


def _timer_model(event_cap, per_resume, n_sched, n_exit):
    """One process schedules ``per_resume`` far-future timers on each of
    its first ``n_sched`` resumes (holding 0.1 between them, so the
    table fills to per_resume * n_sched live timers before any fires),
    then exits after ``n_exit`` total resumes — a general-table-heavy
    workload (the shipped models keep the general table nearly empty).
    Timer fires abort in-progress holds, so the pop interleavings cross
    both tables."""
    m = Model("tmr", n_ilocals=1, event_cap=event_cap)

    @m.block
    def tick(sim, p, sig):
        k = api.local_i(sim, p, 0)
        sim = api.add_local_i(sim, p, 0, 1)
        arming = k < n_sched
        for i in range(per_resume):
            sim2, _ = api.timer_add(
                sim, p, 3.0 + (i % 7) * 0.61 + (i % 3) * 1.7, 0
            )
            sim = cl._tree_select(arming, sim2, sim)
        fin = k >= n_exit
        return sim, cmd.select(
            fin, cmd.exit_(), cmd.hold(0.1, next_pc=tick.pc)
        )

    m.process("ticker", entry=tick)
    return m.build()


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells (the ci.sh packed timer-model smoke keeps a quick twin)
def test_timer_model_packed_matches_flat(carry_layout):
    """Packed carry against per-leaf carry on a general-table-heavy
    model (256-slot table, timer fires aborting holds): every Sim leaf
    bitwise equal, matched by path."""

    def arm(packed):
        carry_layout(packed)
        spec = _timer_model(256, per_resume=10, n_sched=6, n_exit=16)
        sims = jax.vmap(lambda r: cl.init_sim(spec, 13, r, None))(
            jnp.arange(3)
        )
        return jax.jit(jax.vmap(cl.make_run(spec)))(sims)

    flat, packed = arm(False), arm(True)
    assert int(jnp.sum(flat.n_events)) > 40
    assert not bool(jnp.any(flat.err != 0))
    packed_by_path = dict(
        (jax.tree_util.keystr(p), l)
        for p, l in jax.tree_util.tree_flatten_with_path(packed)[0]
    )
    for path, a in jax.tree_util.tree_flatten_with_path(flat)[0]:
        b = packed_by_path[jax.tree_util.keystr(path)]
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=str(path)
        )


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
@pytest.mark.parametrize("profile", ["f64", "f32"])
def test_mg1_sweep_packed_matches_flat_pooled(profile, carry_layout):
    """M/G/1 sweep pooled statistics, packed vs flat, both profiles
    (the acceptance battery's second model)."""
    from cimba_tpu.models import mg1
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.stats import summary as sm

    with config.profile(profile):
        spec, _ = mg1.build()
        params, cells = mg1.sweep_params(120, reps_per_cell=2)
        R = len(cells)
        outs = []
        for packed in (False, True):
            carry_layout(packed)
            res = ex.run_experiment(spec, params, R, seed=9)
            assert int(res.n_failed) == 0
            outs.append(res)
        _assert_trees_equal(outs[0].sims, outs[1].sims)
        pooled = [
            jax.jit(sm.merge_tree)(r.sims.user["wait"]) for r in outs
        ]
        _assert_trees_equal(pooled[0], pooled[1])
