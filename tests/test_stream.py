"""Chunked dispatch + wave streaming (docs/12_streaming.md).

The contracts pinned here:

* chunked runs (``make_run(max_steps=)`` re-dispatched by the host,
  donated carry) are TRAJECTORY-IDENTICAL to the monolithic while-loop:
  every Sim leaf bitwise equal, on mm1 and the M/G/1 sweep, both dtype
  profiles, under both carry layouts (packed and per leaf);
* the streamed experiment's wave fold is exactly the associative Pébay
  merge of the monolithic run's per-wave pools (bitwise vs the by-hand
  fold; counts/event totals exact vs the monolithic pool);
* wave parameter slicing delivers swept leaves bitwise as the
  monolithic broadcast would (the M/G/1 4x5 sweep regression);
* the chunk program's donation actually aliases buffers (flat
  steady-state memory: no per-chunk Sim copy);
* chunk-boundary checkpoints resume bit-identically;
* regrow composes at wave granularity;
* command-tag inference survives spec twins sharing block functions
  (the jax.eval_shape memo must not swallow the collector's side
  effects — found by the regrow battery);
* R beyond the single-dispatch lane budget streams to correct pooled
  statistics without materializing all R sims (slow twin: R=2**20).

The full profile x pack batteries and the end-to-end heavyweights are
marked slow (tier-1 budget); tools/ci.sh runs them in every cell.
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cimba_tpu import config
from cimba_tpu.core import api, cmd
from cimba_tpu.core import loop as cl
from cimba_tpu.core.model import Model
from cimba_tpu.models import mg1, mm1
from cimba_tpu.obs import metrics as om
from cimba_tpu.runner import experiment as ex
from cimba_tpu.stats import summary as sm


def _assert_trees_equal(a, b):
    al, bl = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(al) == len(bl)
    for x, y in zip(al, bl):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _tiny_spec(t_stop=4.0):
    """Smallest possible chunkable model (hold/exit only — compiles in
    a fraction of mm1's time): one process holding unit steps until
    ``t_stop``."""
    m = Model("tiny", event_cap=1, guard_cap=2)

    @m.block
    def work(sim, p, sig):
        done = api.clock(sim) > t_stop
        return sim, cmd.select(
            done, cmd.exit_(), cmd.hold(1.0, next_pc=work.pc)
        )

    m.process("w", entry=work)
    return m.build()


#: the canonical tier-1 mm1 configuration, shared by the monolithic
#: fixture and both core pins below
_R, _WAVE, _N, _SEED = 32, 8, 40, 11


@pytest.fixture(scope="module")
def mm1_mono():
    """ONE monolithic mm1 run (f64, record=False) both core tier-1 pins
    compare against — module-scoped so its compile is paid once."""
    spec, _ = mm1.build(record=False)
    res = ex.run_experiment(spec, mm1.params(_N), _R, seed=_SEED)
    assert int(res.n_failed) == 0
    return spec, res


def test_chunked_matches_monolithic_mm1(mm1_mono):
    """Chunked dispatch reproduces every Sim leaf bitwise (chunk_steps
    chosen to NOT divide the run length: partial last chunks and
    mid-event-cycle boundaries are the interesting case)."""
    spec, res = mm1_mono
    chunked = ex.run_experiment_chunked(
        spec, mm1.params(_N), _R, seed=_SEED, chunk_steps=37, poll_every=3
    )
    assert int(jnp.sum(chunked.sims.n_events)) > 300
    _assert_trees_equal(res.sims, chunked.sims)


def test_stream_matches_monolithic_and_fold_oracle(mm1_mono):
    """The streamed experiment reproduces counts/event totals exactly,
    and its summary is BITWISE the by-hand sequential fold of the
    monolithic run's per-wave pools — the stream machinery adds nothing
    beyond the associative merge."""
    spec, res = mm1_mono
    st = ex.run_experiment_stream(
        spec, mm1.params(_N), _R, wave_size=_WAVE, chunk_steps=37,
        seed=_SEED,
    )
    assert st.n_waves == _R // _WAVE
    assert int(st.n_failed) == 0
    assert int(st.total_events) == int(res.total_events)

    mono = jax.jit(sm.merge_tree)(res.sims.user["wait"])
    assert float(st.summary.n) == float(mono.n)
    assert float(st.summary.w) == float(mono.w)
    np.testing.assert_allclose(
        float(sm.mean(st.summary)), float(sm.mean(mono)), rtol=1e-12
    )

    # the fold oracle: pool each wave of the MONOLITHIC sims, then merge
    # sequentially — bitwise what the stream accumulated
    merge_j = jax.jit(sm.merge)
    merge_tree_j = jax.jit(sm.merge_tree)
    oracle = sm.empty()
    for w in range(_R // _WAVE):
        sl = jax.tree.map(
            lambda x: x[w * _WAVE : (w + 1) * _WAVE],
            res.sims.user["wait"],
        )
        oracle = merge_j(oracle, merge_tree_j(sl))
    _assert_trees_equal(st.summary, oracle)


def test_chunked_matches_monolithic_f32_packed(carry_layout):
    """The accelerator headline arm's trace shape (f32 profile + packed
    carry through the BOUNDED while-loop) stays tier-1 on the cheap
    model; the full mm1/mg1 profile x pack batteries are the slow twins
    below (run by tools/ci.sh)."""
    carry_layout(True)
    with config.profile("f32"):
        spec = _tiny_spec(t_stop=30.0)
        init = jax.jit(jax.vmap(lambda r: cl.init_sim(spec, 7, r, None)))
        mono = jax.jit(jax.vmap(cl.make_run(spec)))(
            init(jnp.arange(4))
        )
        chunked = cl.make_chunked_run(
            spec, chunk_steps=7, poll_every=3
        )(init(jnp.arange(4)))
        assert int(jnp.sum(mono.n_events)) > 100
        _assert_trees_equal(mono, chunked)


@pytest.mark.slow  # heavyweight twin: over the timed tier-1 budget; runs in tools/ci.sh cells
@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("profile", ["f64", "f32"])
def test_chunked_matches_monolithic_mm1_battery(profile, pack, carry_layout):
    """Every Sim leaf bitwise equal between the monolithic while-loop
    and chunked re-dispatch, across dtype profiles and carry layouts
    (chunk_steps chosen to NOT divide the run length: partial last
    chunks and mid-event-cycle boundaries are the interesting case)."""
    carry_layout(pack)
    with config.profile(profile):
        spec, _ = mm1.build(record=True)
        init = jax.jit(
            jax.vmap(lambda r: cl.init_sim(spec, 7, r, mm1.params(50)))
        )
        mono = jax.jit(jax.vmap(cl.make_run(spec)))(
            init(jnp.arange(4))
        )
        chunked = cl.make_chunked_run(
            spec, chunk_steps=13, poll_every=3
        )(init(jnp.arange(4)))
        assert int(jnp.sum(mono.n_events)) > 300
        _assert_trees_equal(mono, chunked)


def test_wave_param_slicing_bitwise_mg1_sweep():
    """The M/G/1 4x5 sweep regression: per-wave slices of swept
    leading-axis param leaves must reach lanes bitwise as the monolithic
    broadcast delivers them — pinned at the init level (every Sim leaf
    of a wave init == the matching rows of the full init) and at the
    _slice_params level (composition == broadcast-then-slice)."""
    spec, _ = mg1.build()
    params, cells = mg1.sweep_params(30, reps_per_cell=1)
    R = len(cells)
    assert R == 20  # 4 CVs x 5 utilizations

    full = ex._broadcast_params(params, R)
    for lo, n in [(0, 8), (8, 8), (16, 4), (0, R)]:
        sliced = ex._slice_params(params, R, lo, n)
        _assert_trees_equal(
            sliced, jax.tree.map(lambda x: x[lo : lo + n], full)
        )
    # a shared leaf whose length happens to equal the wave size must
    # still broadcast per-lane, not be misread as per-lane data
    shared = (jnp.arange(4.0),)
    sliced = ex._slice_params(shared, R, 8, 4)
    _assert_trees_equal(
        sliced, jax.tree.map(lambda x: x[8:12], ex._broadcast_params(shared, R))
    )

    init_full = jax.jit(
        jax.vmap(lambda r, p: cl.init_sim(spec, 9, r, p))
    )(jnp.arange(R), full)
    for lo, n in [(0, 8), (8, 8), (16, 4)]:
        wave = jax.jit(
            jax.vmap(lambda r, p: cl.init_sim(spec, 9, r, p))
        )(jnp.arange(lo, lo + n), ex._slice_params(params, R, lo, n))
        _assert_trees_equal(
            wave, jax.tree.map(lambda x: x[lo : lo + n], init_full)
        )


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
@pytest.mark.parametrize("pack", [False, True])
def test_stream_and_chunked_mg1_sweep_match_monolithic(pack, carry_layout):
    """The sweep end to end (ragged final wave included): chunked sims
    bitwise the monolithic ones; streamed totals exact and pooled
    moments at merge-order rounding."""
    carry_layout(pack)
    spec, _ = mg1.build()
    params, cells = mg1.sweep_params(30, reps_per_cell=1)
    R = len(cells)
    res = ex.run_experiment(spec, params, R, seed=9)
    chunked = ex.run_experiment_chunked(
        spec, params, R, seed=9, chunk_steps=41
    )
    _assert_trees_equal(res.sims, chunked.sims)

    st = ex.run_experiment_stream(
        spec, params, R, wave_size=8, chunk_steps=41, seed=9
    )
    assert st.n_waves == 3  # 8 + 8 + 4: the ragged last wave
    assert int(st.total_events) == int(res.total_events)
    mono = jax.jit(sm.merge_tree)(res.sims.user["wait"])
    assert float(st.summary.n) == float(mono.n)
    np.testing.assert_allclose(
        float(sm.mean(st.summary)), float(sm.mean(mono)), rtol=1e-9
    )


@pytest.mark.slow  # heavyweight twin: over the timed tier-1 budget; runs in tools/ci.sh cells
@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("profile", ["f64", "f32"])
def test_chunked_matches_monolithic_mg1_sweep_bitwise(
    profile, pack, carry_layout,
):
    """The full acceptance battery on the second model class: every Sim
    leaf of the chunked M/G/1 sweep bitwise the monolithic run's, both
    profiles, both carry layouts."""
    carry_layout(pack)
    with config.profile(profile):
        spec, _ = mg1.build()
        params, cells = mg1.sweep_params(60, reps_per_cell=2)
        R = len(cells)
        res = ex.run_experiment(spec, params, R, seed=5)
        chunked = ex.run_experiment_chunked(
            spec, params, R, seed=5, chunk_steps=97
        )
        assert int(res.n_failed) == 0
        _assert_trees_equal(res.sims, chunked.sims)


def test_chunk_donation_aliases_buffers():
    """The donation contract: the chunk program carries the
    input/output alias annotation, and calling it consumes (deletes)
    the input buffers — chunk n+1 reuses chunk n's memory, so
    steady-state device memory is flat across chunks (no per-chunk Sim
    copy)."""
    spec = _tiny_spec(t_stop=20.0)
    run = cl.make_chunked_run(spec, chunk_steps=4)
    init = jax.jit(jax.vmap(lambda r: cl.init_sim(spec, 3, r, None)))
    sims = init(jnp.arange(8))

    lowered = jax.jit(
        cl.make_chunk(spec, max_steps=4), donate_argnums=(0,)
    ).lower(sims)
    txt = lowered.as_text()
    assert "tf.aliasing_output" in txt or "jax.buffer_donor" in txt, (
        "chunk lowering carries no donation annotation"
    )

    handles = jax.tree.leaves(sims)
    out, any_live = run.chunk(sims)
    assert all(h.is_deleted() for h in handles), (
        "donated chunk left input buffers alive — a per-chunk Sim copy"
    )
    # re-dispatch keeps working on the donated output (the host loop's
    # steady state), and a finished batch is a stable no-op
    for _ in range(3):
        out, any_live = run.chunk(out)
    out = cl.drive_chunks(run.chunk, out, poll_every=2)
    assert int(jnp.sum(out.err)) == 0
    assert bool(jnp.all(out.n_events == 22))  # 21 holds + exit, per lane

    # and the drive-level wrapper equals the monolithic run bitwise
    mono = jax.jit(jax.vmap(cl.make_run(spec)))(init(jnp.arange(8)))
    _assert_trees_equal(mono, run(init(jnp.arange(8))))


def test_used_tags_inference_survives_shared_block_functions():
    """Regression for the jax.eval_shape memo: a spec twin sharing
    block FUNCTIONS with an already-inferred spec at identical Sim
    avals must infer the same non-empty tag set — a cache hit that
    swallows the tag collector's side effects would route every
    command to h_invalid/ERR_USER (surfaced by the wave-regrow
    battery: the re-built chunk program ran a dataclasses.replace twin
    of a spec the stream had already traced)."""
    import dataclasses

    spec = _tiny_spec()
    sim = cl.init_sim(spec, 1, 0, None)
    tags = cl._used_tags_for(spec, sim)
    assert tags and cl.pr.C_HOLD in tags

    twin = dataclasses.replace(spec)  # same avals, same block functions
    assert not hasattr(twin, "_used_tags_memo")
    assert cl._used_tags_for(twin, cl.init_sim(twin, 1, 0, None)) == tags

    # end to end: the twin's run must behave, not ERR_USER out
    out = jax.jit(cl.make_run(twin))(cl.init_sim(twin, 1, 0, None))
    assert int(out.err) == 0 and int(out.n_events) > 0


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_chunked_checkpoint_resume_bit_identical():
    """Chunk boundaries as checkpoints: a run checkpointed mid-flight
    and resumed from disk equals the uninterrupted (and the monolithic)
    run bitwise."""
    spec, _ = mm1.build(record=False)
    R = 8
    path = os.path.join(tempfile.mkdtemp(), "stream_ck.npz")
    mono = ex.run_experiment(spec, mm1.params(40), R, seed=5)
    full = ex.run_experiment_chunked(
        spec, mm1.params(40), R, seed=5, chunk_steps=23,
        checkpoint_path=path, checkpoint_every=2,
    )
    assert os.path.exists(path)
    _assert_trees_equal(mono.sims, full.sims)
    resumed = ex.run_experiment_chunked(
        spec, mm1.params(40), R, seed=5, chunk_steps=23,
        checkpoint_path=path, resume=True,
    )
    _assert_trees_equal(mono.sims, resumed.sims)

    # a different spec must refuse the checkpoint (fingerprint tag)
    import dataclasses

    other = dataclasses.replace(spec, event_cap=2 * spec.event_cap)
    with pytest.raises(ValueError, match="fingerprint"):
        ex.run_experiment_chunked(
            other, mm1.params(40), R, seed=5, chunk_steps=23,
            checkpoint_path=path, resume=True,
        )

    # so must a different seed or different params: shapes all match,
    # so without the run tag the resume would silently continue the OLD
    # run's trajectories
    with pytest.raises(ValueError, match="fingerprint"):
        ex.run_experiment_chunked(
            spec, mm1.params(40), R, seed=6, chunk_steps=23,
            checkpoint_path=path, resume=True,
        )
    with pytest.raises(ValueError, match="fingerprint"):
        ex.run_experiment_chunked(
            spec, mm1.params(41), R, seed=5, chunk_steps=23,
            checkpoint_path=path, resume=True,
        )
    with pytest.raises(ValueError, match="fingerprint"):
        ex.run_experiment_chunked(
            spec, mm1.params(40), R, seed=5, chunk_steps=23,
            t_end=50.0, checkpoint_path=path, resume=True,
        )


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_stream_metrics_fold_equals_monolithic_pool():
    """The wave fold of the metrics registry (obs.metrics.merge) equals
    pooling all lanes at once: counters/histograms sum, gauges max."""
    om.enable()
    try:
        spec, _ = mm1.build(record=False)
        R = 16
        res = ex.run_experiment(spec, mm1.params(25), R, seed=2)
        st = ex.run_experiment_stream(
            spec, mm1.params(25), R, wave_size=4, chunk_steps=19, seed=2
        )
    finally:
        om.disable()
    assert st.metrics is not None
    pooled = jax.jit(om.pool)(res.sims.metrics)
    _assert_trees_equal(st.metrics, pooled)
    assert int(om.events_dispatched(st.metrics)) == int(res.total_events)


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_stream_regrow_at_wave_granularity():
    """A wave that dies of event overflow is re-run under a doubled cap
    (later waves keep the grown spec); the pooled result matches a
    monolithic run at the final capacity."""
    import dataclasses

    from test_regrow import _burst_spec

    spec = _burst_spec(12, event_cap=4)
    # the burst model carries no Summary; pool each lane's final clock
    path = lambda sims: jax.vmap(lambda c: sm.add(sm.empty(), c))(
        sims.clock
    )
    st = ex.run_experiment_stream(
        spec, (), 8, wave_size=4, chunk_steps=16, seed=3,
        summary_path=path, max_regrows=4,
    )
    assert st.n_regrows >= 1
    assert int(st.n_failed) == 0

    grown = dataclasses.replace(
        spec, event_cap=spec.event_cap * 2**st.n_regrows
    )
    direct = ex.run_experiment(grown, (), 8, seed=3)
    assert int(direct.n_failed) == 0
    assert int(st.total_events) == int(direct.total_events)
    np.testing.assert_allclose(
        float(sm.mean(st.summary)),
        float(np.asarray(direct.sims.clock).mean()),
        rtol=1e-12,
    )

    # max_regrows=0 keeps the historical behavior: failures are counted,
    # never retried
    st0 = ex.run_experiment_stream(
        spec, (), 8, wave_size=4, chunk_steps=16, seed=3,
        summary_path=path,
    )
    assert st0.n_regrows == 0
    assert int(st0.n_failed) == 8


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_large_r_stream_beyond_lane_budget():
    """R=2**20 on CPU at tiny N: far past any single-dispatch budget,
    streamed in 16384-lane waves — pooled statistics come back correct
    (exact sample count, zero failures, mean in the short-run transient
    envelope) while device/host memory only ever holds one wave."""
    spec, _ = mm1.build(record=False)
    R, wave, n_objects = 2**20, 16384, 3
    st = ex.run_experiment_stream(
        spec, mm1.params(n_objects), R, wave_size=wave,
        chunk_steps=256, seed=2026,
    )
    assert st.n_waves == R // wave
    assert int(st.n_failed) == 0
    assert float(st.summary.n) == float(n_objects * R)
    assert int(st.total_events) > 6 * R  # ~10 events per 3-object lane
    # 3-object transient of the rho=0.9 M/M/1: far below the stationary
    # mean of 10; a generous envelope still catches wrong-lane pooling
    assert 1.0 < float(sm.mean(st.summary)) < 2.0
    assert 0.5 < float(sm.stddev(st.summary)) < 3.0
