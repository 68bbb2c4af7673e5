"""Job-shop and AWACS model tests: conservation laws, condition firing,
many-process scaling, physics-hook behavior."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cimba_tpu.core import loop as cl
from cimba_tpu.models import awacs, jobshop
from cimba_tpu.stats import summary as sm


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_jobshop_conserves_jobs_and_runs_maintenance():
    spec, refs = jobshop.build(backlog=4.0)
    run = cl.make_run(spec)

    def one(rep):
        return run(cl.init_sim(spec, 5, rep, jobshop.params(300)))

    sims = jax.jit(jax.vmap(one))(jnp.arange(4))
    assert int(jnp.sum(sims.err)) == 0
    done = np.asarray(sims.user["done"].n)
    np.testing.assert_array_equal(done, 300)  # every job completes
    # all crew returned, WIP drained to whatever stage B hasn't pulled
    np.testing.assert_allclose(np.asarray(sims.pools.level[:, 0]), 3.0)
    # the backlog condition fired at least once per replication at this
    # arrival pressure
    assert (np.asarray(sims.user["maintenance_runs"]) >= 1).all()


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_jobshop_sojourn_increases_with_load():
    spec, _ = jobshop.build()
    run = cl.make_run(spec)

    def one(rep, arr_mean):
        return run(
            cl.init_sim(spec, 6, rep, (arr_mean, 0.4, 200))
        )

    light = jax.jit(jax.vmap(lambda r: one(r, 2.0)))(jnp.arange(4))
    heavy = jax.jit(jax.vmap(lambda r: one(r, 0.9)))(jnp.arange(4))
    # completion time of the 200th job shrinks when arrivals speed up
    assert float(heavy.clock.mean()) < float(light.clock.mean())


def test_awacs_detects_and_scales_with_targets():
    outs = {}
    for n in (8, 32):
        spec, _ = awacs.build(n)
        run = cl.make_run(spec)
        sim = jax.jit(run)(cl.init_sim(spec, 9, 0, awacs.params(20.0)))
        assert int(sim.err) == 0
        assert int(sim.user["dwells"]) > 10
        outs[n] = float(sm.mean(sim.user["detections"]))
    # detections per dwell scale with target count (targets start at the
    # center, inside detection range)
    assert outs[32] > 2.0 * outs[8]
    assert outs[8] > 0.0


def test_awacs_positions_stay_in_arena_neighborhood():
    spec, _ = awacs.build(16)
    run = cl.make_run(spec)
    sim = jax.jit(run)(cl.init_sim(spec, 4, 0, awacs.params(50.0)))
    pos = np.stack(
        [np.asarray(sim.user["pos_x"]), np.asarray(sim.user["pos_y"])],
        axis=1,
    )
    # soft-bounce keeps targets within arena + one leg's travel
    assert np.linalg.norm(pos, axis=1).max() < awacs.ARENA + awacs.SPEED * 30

def test_awacs_nn_scores_pallas_matches_jnp():
    """The NN physics hook: the Pallas kernel (interpret mode here — the
    Mosaic-compiled path runs on real TPU in benchmark/run.py's awacs.radar
    cell) and
    the plain-jnp trace are the same matmul stack; results must agree to
    f32 roundoff."""
    rng = np.random.default_rng(7)
    n = 137  # deliberately not a lane multiple: exercises row padding
    pos = jnp.asarray(rng.uniform(-80, 80, (n, 2)))
    vel = jnp.asarray(rng.normal(0, awacs.SPEED, (n, 2)))
    ref = np.asarray(awacs.nn_scores(pos, vel, use_pallas=False))
    ker = np.asarray(
        awacs.nn_scores(pos, vel, use_pallas=True, interpret=True)
    )
    assert ref.shape == ker.shape == (n,)
    np.testing.assert_allclose(ker, ref, rtol=1e-6, atol=1e-6)
    # physically sensible without training: a target at the center must
    # outscore one far outside detection range
    center = float(awacs.nn_scores(jnp.zeros((1, 2)), jnp.zeros((1, 2)),
                                   use_pallas=False)[0])
    far = float(awacs.nn_scores(jnp.full((1, 2), 90.0), jnp.zeros((1, 2)),
                                use_pallas=False)[0])
    assert center > 0.9 and far < 0.3 and center > 2 * far


def test_awacs_nn_and_threshold_scoring_both_run():
    """Same model, both physics hooks; NN is the default (BASELINE
    configs[4])."""
    means = {}
    for scoring in ("nn", "threshold"):
        spec, _ = awacs.build(24, scoring=scoring)
        run = cl.make_run(spec)
        sim = jax.jit(run)(cl.init_sim(spec, 11, 0, awacs.params(15.0)))
        assert int(sim.err) == 0
        means[scoring] = float(sm.mean(sim.user["detections"]))
    # both detect a sensible fraction of the 24 targets per dwell
    assert 1.0 < means["nn"] <= 24.0
    assert 1.0 < means["threshold"] <= 24.0


def test_awacs_reference_scale_1000_targets():
    """The reference scenario runs 1000 target coroutines
    (`tutorial/tut_5_1.c`); this exercises the dense wake table at that
    scale — 1001 process rows, O(P) lexicographic pop per event — the
    widest per-event scan any shipped model performs.  (Large GENERAL
    event tables are covered by test_eventset's big-capacity battery:
    models only fill that table with timers/user events now.)"""
    spec, _ = awacs.build(1000)
    run = cl.make_run(spec)
    sim = jax.jit(run)(cl.init_sim(spec, 3, 0, awacs.params(2.0)))
    assert int(sim.err) == 0
    assert int(sim.n_events) > 1000  # every target launched + legs + dwells
    assert int(sim.user["dwells"]) >= 2
    # most of 1000 center-started targets are detected each dwell
    assert float(sm.mean(sim.user["detections"])) > 500.0
