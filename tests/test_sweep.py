"""The many-scenario sweep engine (docs/16_sweeps.md).

Contracts pinned here:

* **grid migration**: ``mg1.sweep_params`` rebuilt on ``SweepGrid``
  reproduces the historical hand-rolled 4x5 experiment array BITWISE
  (rows, dtypes, cell list), and the monolithic runner pools the grid
  layout to the same summary;
* **fixed-R bitwise**: every engine cell equals the direct per-cell
  ``run_experiment_stream`` call (same ``wave_size``, the
  ``round_seed(seed, c, 0)`` schedule) bitwise — summaries, failure
  counts, event totals — under both dtype profiles, whether cells get
  their own waves or share packed ones;
* **adaptive stopping**: an easy cell stops rounds before a hard one,
  freed lanes keep the hard cell converging, and the deterministic
  (cell, round) seed schedule makes adaptive runs reproduce
  bit-for-bit;
* **pad-and-mask**: quantized waves with ``t_stop=-inf`` pad lanes
  fold bitwise-identically to unpadded dispatch;
* **serve-backed**: the same schedule through a ``serve.Service``
  returns per-cell results bitwise the direct engine's;
* **export**: rows()/CSV carry cell coordinates + statistics.

The tier-1 battery rides a tiny one-block model (fractions of mm1's
compile); mg1-at-size twins are slow (tools/ci.sh cells).
"""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cimba_tpu.random as cr
from cimba_tpu import config, serve, sweep
from cimba_tpu.core import api, cmd
from cimba_tpu.core.model import Model
from cimba_tpu.models import mg1
from cimba_tpu.runner import experiment as ex
from cimba_tpu.serve import cache as pc
from cimba_tpu.stats import summary as sm


def _sweep_spec():
    """Tiny parametrized model: one process drawing exp(step_mean)
    holds, recording each draw into ``wait`` until ``n_steps`` samples
    — compiles in a fraction of mm1's time, and the cell mean/variance
    scale with ``step_mean`` (so absolute halfwidth targets separate
    easy from hard cells provably)."""
    m = Model("tinysweep", event_cap=1, guard_cap=2)

    @m.user_state
    def ui(params):
        step_mean, n_steps = params
        return {
            "step_mean": jnp.asarray(step_mean, config.REAL),
            "n_steps": jnp.asarray(n_steps, jnp.int32),
            "wait": sm.empty(),
        }

    @m.block
    def work(sim, p, sig):
        sim, t = api.draw(sim, cr.exponential, sim.user["step_mean"])
        wait = sm.add(sim.user["wait"], t)
        sim = api.set_user(sim, {**sim.user, "wait": wait})
        sim = api.stop(
            sim, wait.n >= sim.user["n_steps"].astype(wait.n.dtype)
        )
        return sim, cmd.hold(t, next_pc=work.pc)

    m.process("w", entry=work)
    return m.build()


def _grid(means=(0.1, 1.0, 2.5), n_steps=12):
    return sweep.SweepGrid(
        {"step_mean": means},
        lambda step_mean: (np.float64(step_mean), np.int32(n_steps)),
        name="tiny",
    )


@pytest.fixture(scope="module")
def tiny():
    """ONE spec object for the module (program-cache keys pin function
    identities; sharing the object pays each compile once)."""
    return _sweep_spec()


@pytest.fixture(scope="module")
def shared_cache():
    return pc.ProgramCache(capacity=256)


def _assert_trees_equal(a, b):
    al, bl = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(al) == len(bl)
    for x, y in zip(al, bl):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --- grid / mg1 migration ---------------------------------------------------


def test_mg1_grid_rows_bitwise_hand_rolled():
    """The migration pin: the SweepGrid-backed ``mg1.sweep_params``
    reproduces the pre-migration hand-rolled construction bitwise —
    row values, leaf dtypes, and the per-replication cell list."""

    def legacy(n_objects, cvs, utilizations, reps_per_cell, srv_mean):
        # the historical models/mg1.py::sweep_params body, verbatim
        cells = [
            (cv, rho)
            for cv in cvs
            for rho in utilizations
            for _ in range(reps_per_cell)
        ]
        cv_arr = np.asarray([c for c, _ in cells])
        rho_arr = np.asarray([r for _, r in cells])
        arr_mean = srv_mean / rho_arr
        return (
            (
                jnp.asarray(arr_mean),
                jnp.full(len(cells), srv_mean),
                jnp.asarray(cv_arr),
                jnp.full(len(cells), n_objects, jnp.int32),
            ),
            cells,
        )

    for kw in (
        dict(n_objects=4000, cvs=(0.25, 0.5, 1.0, 2.0),
             utilizations=(0.5, 0.6, 0.7, 0.8, 0.9), reps_per_cell=10,
             srv_mean=1.0),
        dict(n_objects=77, cvs=(0.25, 1.0), utilizations=(0.5, 0.9),
             reps_per_cell=3, srv_mean=2.0),
    ):
        got_p, got_c = mg1.sweep_params(**kw)
        want_p, want_c = legacy(**kw)
        assert got_c == want_c
        for a, b in zip(got_p, want_p):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    grid = mg1.sweep_grid(100)
    assert grid.n_cells == 20
    assert grid.cell_label(0) == "cv=0.25,rho=0.5"
    _, cell_ids = grid.rows(3)
    np.testing.assert_array_equal(cell_ids, np.repeat(np.arange(20), 3))


def test_grid_validates_axes_and_structure():
    with pytest.raises(ValueError, match="at least one axis"):
        sweep.SweepGrid({}, lambda: ())
    with pytest.raises(ValueError, match="no values"):
        sweep.SweepGrid({"a": ()}, lambda a: (a,))
    # ragged tree structure across cells fails loudly
    bad = sweep.SweepGrid(
        {"a": (0, 1)}, lambda a: (1.0,) if a == 0 else (1.0, 2.0)
    )
    with pytest.raises(ValueError, match="structure"):
        bad.rows(2)
    with pytest.raises(ValueError, match="structure"):
        sweep.run_sweep(None, bad, reps_per_cell=2)


# --- fixed-R: bitwise vs per-cell direct stream calls -----------------------


@pytest.mark.slow  # ci.sh "sweep smoke" pins fixed-R engine cells bitwise vs direct every pass
def test_fixed_r_cells_bitwise_direct_stream(tiny, shared_cache):
    """Every engine cell — whole waves, ragged tails, multiple cells
    packed into one physical wave — bitwise the direct
    ``run_experiment_stream`` call at the same wave partition and the
    ``round_seed`` schedule."""
    grid = _grid()
    res = sweep.run_sweep(
        tiny, grid, reps_per_cell=6, seed=5, cell_wave=4, max_wave=16,
        chunk_steps=8, program_cache=shared_cache,
    )
    assert res.met is None
    assert (res.stop_round == -1).all()
    assert res.n_rounds == 1
    # 3 cells x (4+2) slots into 16-lane waves: packing really happened
    assert res.occupancy["waves"] < 6
    for i in range(grid.n_cells):
        direct = ex.run_experiment_stream(
            tiny, grid.cell_row(i), 6, wave_size=4, chunk_steps=8,
            seed=sweep.round_seed(5, i, 0), program_cache=shared_cache,
        )
        _assert_trees_equal(res.cell_summary(i), direct.summary)
        assert int(res.n_failed[i]) == int(direct.n_failed)
        assert int(res.total_events[i]) == int(direct.total_events)


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
# (the f64 twin stays tier-1)
def test_fixed_r_cells_bitwise_direct_stream_f32(tiny, shared_cache):
    """The accelerator profile arm of the acceptance pin (both dtype
    profiles).  A fresh spec: dtypes bind at trace time."""
    with config.profile("f32"):
        spec = _sweep_spec()
        grid = _grid(means=(0.2, 1.5), n_steps=10)
        res = sweep.run_sweep(
            spec, grid, reps_per_cell=6, seed=3, cell_wave=4,
            chunk_steps=8, program_cache=shared_cache,
        )
        for i in range(grid.n_cells):
            direct = ex.run_experiment_stream(
                spec, grid.cell_row(i), 6, wave_size=4, chunk_steps=8,
                seed=sweep.round_seed(3, i, 0),
                program_cache=shared_cache,
            )
            _assert_trees_equal(res.cell_summary(i), direct.summary)
            assert int(res.total_events[i]) == int(direct.total_events)


def test_pad_and_mask_waves_bitwise_inert(tiny, shared_cache):
    """pad_waves=True quantizes wave shapes with dead ``t_stop=-inf``
    lanes; every per-cell statistic equals the unpadded run bitwise
    (pads sit past the live segment and never join a fold)."""
    grid = _grid()
    kw = dict(
        reps_per_cell=6, seed=7, cell_wave=4, max_wave=32,
        chunk_steps=8, program_cache=shared_cache,
    )
    padded = sweep.run_sweep(tiny, grid, pad_waves=True, **kw)
    plain = sweep.run_sweep(tiny, grid, pad_waves=False, **kw)
    assert padded.occupancy["lanes_padded"] > 0
    assert plain.occupancy["lanes_padded"] == 0
    assert 0.0 < padded.occupancy["padding_waste_frac"] < 1.0
    _assert_trees_equal(padded.summaries, plain.summaries)
    np.testing.assert_array_equal(padded.n_failed, plain.n_failed)
    np.testing.assert_array_equal(
        padded.total_events, plain.total_events
    )


# --- adaptive ---------------------------------------------------------------


def test_adaptive_easy_stops_before_hard_and_reproduces(tiny, shared_cache):
    """Sequential stopping: under an ABSOLUTE halfwidth target the
    low-mean cell converges rounds before the high-mean cell (exp
    stddev == mean), freed lanes grow the hard cell's rounds
    (redistribute), and the deterministic (cell, round) seed schedule
    reproduces the whole run bitwise."""
    grid = _grid(means=(0.1, 0.6), n_steps=16)
    rule = sweep.HalfwidthTarget(target=0.05, min_reps=4)
    kw = dict(
        reps_per_cell=8, stop=rule, max_rounds=20, seed=7, cell_wave=8,
        max_wave=32, chunk_steps=16, program_cache=shared_cache,
    )
    res = sweep.run_sweep(tiny, grid, **kw)
    assert res.met is not None and res.met.all(), (
        res.halfwidth, res.n_reps,
    )
    assert 0 <= res.stop_round[0] < res.stop_round[1]
    assert res.n_reps[0] < res.n_reps[1]
    # redistribute: once cell 0 stopped, cell 1's rounds doubled
    assert res.n_reps[1] > rule.min_reps
    hw = np.asarray(res.halfwidth)
    assert (hw <= 0.05).all()
    # stopped cells really stopped receiving lanes: total lanes < the
    # fixed-R run sized for the worst cell would have spent
    worst_rounds = res.stop_round.max() + 1
    assert res.n_reps.sum() < grid.n_cells * res.n_reps.max() or (
        worst_rounds == 1
    )

    twin = sweep.run_sweep(tiny, grid, **kw)
    _assert_trees_equal(res.summaries, twin.summaries)
    np.testing.assert_array_equal(res.stop_round, twin.stop_round)
    np.testing.assert_array_equal(res.n_reps, twin.n_reps)


def test_replication_means_batch_ci(tiny, shared_cache):
    """``sweep.replication_means()``: the pooled cell summary's samples
    are REPLICATION means (n == reps, the batch-means CI), repeated
    calls return the same function object (fold/compat caches key on
    summary_path identity), and the per-cell mean equals the mean of
    the lanes' means from the default path's run."""
    assert sweep.replication_means() is sweep.replication_means()
    grid = _grid(means=(0.5, 2.0), n_steps=8)
    res = sweep.run_sweep(
        tiny, grid, reps_per_cell=6, seed=4, cell_wave=6,
        chunk_steps=8, program_cache=shared_cache,
        summary_path=sweep.replication_means(),
    )
    # n = replications, not pooled within-replication samples
    np.testing.assert_array_equal(
        np.asarray(res.summaries.n), [6.0, 6.0]
    )
    # the batch-means mean == mean of per-replication means from a
    # direct run over the same (seed, rep) lanes
    for i in range(grid.n_cells):
        direct = ex.run_experiment_stream(
            tiny, grid.cell_row(i), 6, wave_size=6, chunk_steps=8,
            seed=sweep.round_seed(4, i, 0), program_cache=shared_cache,
            summary_path=sweep.replication_means(),
        )
        _assert_trees_equal(res.cell_summary(i), direct.summary)
    # replication-level CI is wider than the pooled-sample CI on the
    # same data (fewer, independent observations)
    pooled = sweep.run_sweep(
        tiny, grid, reps_per_cell=6, seed=4, cell_wave=6,
        chunk_steps=8, program_cache=shared_cache,
    )
    assert (res.halfwidth > pooled.halfwidth).all(), (
        res.halfwidth, pooled.halfwidth,
    )


def test_adaptive_max_rounds_reports_unmet(tiny, shared_cache):
    """A target no cell can reach inside max_rounds surfaces as
    met=False / stop_round=-1 — never an infinite loop, never a lie."""
    grid = _grid(means=(2.0,), n_steps=8)
    res = sweep.run_sweep(
        tiny, grid, reps_per_cell=4,
        stop=sweep.HalfwidthTarget(target=1e-6, min_reps=4),
        max_rounds=2, seed=1, cell_wave=4, chunk_steps=8,
        program_cache=shared_cache,
    )
    assert res.n_rounds == 2
    assert not res.met.any()
    assert (res.stop_round == -1).all()
    assert (res.halfwidth > 1e-6).all()


# --- serve-backed -----------------------------------------------------------


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
# (the ci.sh sweep smoke re-proves serve-backed cells bitwise on every pass)
def test_serve_backed_sweep_bitwise_direct_engine(tiny, shared_cache):
    """The grid submitted as per-lane-seed/horizon serve requests
    (shared heterogeneous waves, PR 5 classes) returns per-cell
    results bitwise the direct engine's fixed-R results."""
    grid = _grid()
    direct = sweep.run_sweep(
        tiny, grid, reps_per_cell=6, seed=7, cell_wave=4,
        chunk_steps=16, program_cache=shared_cache,
    )
    with serve.Service(max_wave=32, cache=shared_cache) as svc:
        served = sweep.run_sweep(
            tiny, grid, reps_per_cell=6, seed=7, cell_wave=4,
            chunk_steps=16, service=svc,
        )
        stats = svc.stats()
    assert stats["completed"] == grid.n_cells
    _assert_trees_equal(served.summaries, direct.summaries)
    np.testing.assert_array_equal(served.n_failed, direct.n_failed)
    np.testing.assert_array_equal(
        served.total_events, direct.total_events
    )
    assert served.occupancy["serve"]["lanes_dispatched"] >= 18
    with pytest.raises(ValueError, match="serve-backed"):
        sweep.run_sweep(
            tiny, grid, reps_per_cell=2, service=svc,
            program_cache=shared_cache,
        )


# --- result export ----------------------------------------------------------


def test_sweep_result_rows_and_csv(tiny, shared_cache):
    grid = _grid(means=(0.5, 1.5), n_steps=8)
    res = sweep.run_sweep(
        tiny, grid, reps_per_cell=4, seed=2, cell_wave=4,
        chunk_steps=8, program_cache=shared_cache,
    )
    rows = res.rows()
    assert len(rows) == 2
    assert rows[0]["step_mean"] == 0.5 and rows[1]["step_mean"] == 1.5
    for row in rows:
        assert row["reps"] == 4
        assert row["n"] == 4 * 8
        assert row["halfwidth"] > 0.0
        assert row["total_events"] > 0
    # sample means track the cell parameter (wrong-cell pooling tripwire)
    assert rows[1]["mean"] > 2.0 * rows[0]["mean"]

    buf = io.StringIO()
    res.to_csv(buf)
    lines = buf.getvalue().strip().splitlines()
    assert len(lines) == 3
    assert lines[0].startswith("step_mean,")

    # an axis named like a statistic keeps its coordinate column; the
    # statistic moves to stat_<name> instead of silently overwriting
    g2 = sweep.SweepGrid(
        {"mean": (0.5,)},
        lambda mean: (np.float64(mean), np.int32(4)),
    )
    r2 = sweep.run_sweep(
        tiny, g2, reps_per_cell=2, seed=2, cell_wave=2,
        chunk_steps=8, program_cache=shared_cache,
    )
    row = r2.rows()[0]
    assert row["mean"] == 0.5 and "stat_mean" in row


def test_run_sweep_validates_arguments(tiny):
    grid = _grid(means=(1.0,))
    with pytest.raises(ValueError, match="reps_per_cell"):
        sweep.run_sweep(tiny, grid, reps_per_cell=0)
    with pytest.raises(ValueError, match="cell_wave"):
        sweep.run_sweep(
            tiny, grid, reps_per_cell=4, cell_wave=64, max_wave=32
        )
    with pytest.raises(ValueError, match="target"):
        sweep.HalfwidthTarget(target=0.0)
    with pytest.raises(ValueError, match="confidence"):
        sweep.HalfwidthTarget(target=1.0, confidence=1.5)


# --- mg1 at size (slow twins) -----------------------------------------------


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_mg1_fixed_sweep_engine_bitwise_direct():
    """The acceptance pin at model scale: the 4x5 M/G/1 grid through
    the fixed-R engine, every cell bitwise its direct stream call."""
    spec, _ = mg1.build()
    grid = mg1.sweep_grid(300)
    cache = pc.ProgramCache()
    res = sweep.run_sweep(
        spec, grid, reps_per_cell=6, seed=11, cell_wave=4,
        max_wave=64, chunk_steps=512, program_cache=cache,
    )
    assert int(res.n_failed.sum()) == 0
    for i in range(grid.n_cells):
        direct = ex.run_experiment_stream(
            spec, grid.cell_row(i), 6, wave_size=4, chunk_steps=512,
            seed=sweep.round_seed(11, i, 0), program_cache=cache,
        )
        _assert_trees_equal(res.cell_summary(i), direct.summary)
        assert int(res.total_events[i]) == int(direct.total_events)


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_mg1_grid_pools_like_monolithic():
    """The migrated grid layout through the MONOLITHIC runner: pooled
    per-cell summaries equal slicing the batched run by cell id."""
    spec, _ = mg1.build()
    grid = mg1.sweep_grid(200, cvs=(0.5, 1.0), utilizations=(0.5, 0.8))
    params, cell_ids = grid.rows(4)
    R = len(cell_ids)
    res = ex.run_experiment(spec, params, R, seed=9)
    assert int(res.n_failed) == 0
    means = np.asarray(res.sims.user["wait"].m1)
    for i in range(grid.n_cells):
        cell = grid.cell(i)
        w = mg1.pk_sojourn(cell["rho"], cell["cv"])
        got = means[cell_ids == i].mean()
        assert abs(got - w) < 0.45 * w, (cell, got, w)


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_mg1_adaptive_spends_fewer_reps_than_fixed():
    """The statistical-efficiency claim at model scale: adaptive-R
    meets a relative halfwidth target in every cell of a CV-spread
    M/G/1 grid with >= 30% fewer total replications than fixed-R sized
    for the worst cell."""
    spec, _ = mg1.build()
    grid = mg1.sweep_grid(400, cvs=(0.25, 2.0), utilizations=(0.5, 0.9))
    cache = pc.ProgramCache()
    # round size 4 with min_reps=4: the easy low-CV cells can stop at
    # one round while the heavy-tail cells accumulate (savings are
    # granularity-limited: every cell pays at least min_reps and whole
    # rounds)
    rule = sweep.HalfwidthTarget(target=0.05, relative=True, min_reps=4)
    # redistribute=False: the worst cell's total is then its demand at
    # round granularity, not inflated by a final oversized freed-lanes
    # round — the honest fixed-R comparator
    res = sweep.run_sweep(
        spec, grid, reps_per_cell=4, stop=rule, max_rounds=24, seed=5,
        cell_wave=4, max_wave=128, chunk_steps=1024,
        redistribute=False, program_cache=cache,
    )
    assert res.met.all(), (res.halfwidth, res.n_reps)
    worst = int(res.n_reps.max())
    fixed_total = worst * grid.n_cells
    savings = 1.0 - res.n_reps.sum() / fixed_total
    assert savings >= 0.30, (res.n_reps, savings)
