"""The AWACS radar study at small sizes on the CPU: the program against
the plain reference (``benchmark/refs/awacs.py``), the boundary phase of
the XLA chunk against the per-event path, the batched scorer, the
boundary counters, and the programs of the models without boundary
blocks, pinned by digest."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare
from benchmark.refs import awacs as ref
from cimba_tpu import config, serve
from cimba_tpu.core import loop as cl
from cimba_tpu.models import awacs, mg1, mm1
from cimba_tpu.obs import telemetry
from cimba_tpu.runner import experiment as ex
from cimba_tpu.serve import cache as pc

N_TARGETS, LANES, HORIZON, DWELL = 32, 16, 5.0, 0.04


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 4000000007])
def test_program_matches_the_reference(seed):
    """Dwells and events equal; pooled mean and sd of the detections per
    dwell within 1e-6 relative.  Both sides run the same float32 events
    and the same scores; what is left is how each pools its moments (the
    program's Pebay merge in float32 on the device, the reference's
    float32 lanes in float64 on the host): about 1e-8 here.  One
    detection counted otherwise in these ~2000 dwells of ~32 would move
    the mean by ~1.5e-5."""
    with config.profile("f32"):
        spec, _ = awacs.build(N_TARGETS, dwell=DWELL)
        res = ex.run_experiment_stream(
            spec, awacs.params(HORIZON), LANES, seed=seed, chunk_steps=256,
            summary_path=awacs.detections_path,
        )
    lanes = ref.Lanes(np.full(LANES, seed), np.arange(LANES),
                      np.full(LANES, HORIZON), N_TARGETS, DWELL)
    prog = compare.pool_program(res, LANES)
    want = compare.pool_lanes(ref.run(lanes))
    # a dwell every 0.04 s to the first at or past the horizon, on a
    # float32 clock
    assert prog.n == want.n >= LANES * (round(HORIZON / DWELL) + 1)
    assert prog.events == want.events
    assert prog.failed == want.failed == 0
    np.testing.assert_allclose(prog.mean, want.mean, rtol=1e-6)
    np.testing.assert_allclose(prog.sd, want.sd, rtol=1e-6)


def _per_event_and_chunked(n_targets, lanes=8, horizon=2.0, max_steps=64):
    spec, _ = awacs.build(n_targets, dwell=DWELL)
    sims = jax.jit(jax.vmap(
        lambda r: cl.init_sim(spec, 5, r, awacs.params(horizon))
    ))(jnp.arange(lanes))
    per_event = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
    chunk = jax.jit(cl.make_chunk(spec, max_steps=max_steps))
    total = np.zeros(len(cl.BOUNDARY_COUNTERS), np.int64)
    while True:
        sims, live, counters = chunk(sims)
        total += np.asarray(counters)
        if not bool(live):
            return per_event, sims, dict(zip(cl.BOUNDARY_COUNTERS, total))


@pytest.mark.parametrize("pack", [False, True])
def test_boundary_phase_is_bitwise_the_per_event_path(pack, carry_layout):
    carry_layout(pack)  # the per-event path's layout
    per_event, chunked, _ = _per_event_and_chunked(16)
    assert int(jnp.sum(chunked.user["dwells"])) > 8 * 40
    a, b = jax.tree.leaves(per_event), jax.tree.leaves(chunked)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_boundary_counters_count_the_dwells():
    per_event, _, c = _per_event_and_chunked(16)
    assert c["boundary_dispatches"] == int(jnp.sum(per_event.user["dwells"]))
    assert c["lane_steps_dispatched"] == int(jnp.sum(per_event.n_events))
    assert c["lane_steps_run"] >= c["lane_steps_dispatched"]
    # one round per dwell instant (51), split where a chunk's budget ends
    assert 51 <= c["boundary_rounds"] < 2 * 51


def test_boundary_counters_are_the_vmapped_loops():
    """The counters as the lanes' loop ran under ``vmap`` of a while
    loop: the rounds, dwells and lane-steps of these lanes, to the
    count."""
    _, _, c = _per_event_and_chunked(16)
    assert c == {"boundary_rounds": 52, "boundary_dispatches": 408,
                 "lane_steps_run": 1256, "lane_steps_dispatched": 591}


def test_stream_result_and_span_carry_the_counters():
    with config.profile("f32"):
        spec, _ = awacs.build(8, dwell=DWELL)
        res = ex.run_experiment_stream(
            spec, awacs.params(1.0), 4, seed=3, chunk_steps=64,
            summary_path=awacs.detections_path,
        )
    b = {k: int(v) for k, v in res.boundary.items()}
    assert b["boundary_dispatches"] == int(res.summary.n) == 4 * 26
    assert b["lane_steps_dispatched"] == int(res.total_events)
    stream = [r for r in telemetry.program_spans()
              if r["name"] == "runner.stream"][-1]
    assert {k: stream["attrs"][k] for k in b} == b
    # a model without boundary blocks keeps none
    with config.profile("f32"):
        plain = ex.run_experiment_stream(
            mm1.build(record=False)[0], mm1.params(10), 4, seed=3)
    assert plain.boundary is None


def test_sharded_stream_matches_one_device():
    """The boundary chunk under a 4-device replication mesh: each shard
    runs its own rounds; pooled results equal the one-device run's, and
    the counters add up over the shards."""
    with config.profile("f32"):
        spec, _ = awacs.build(8, dwell=DWELL)
        kw = dict(seed=21, chunk_steps=64, summary_path=awacs.detections_path)
        one = ex.run_experiment_stream(spec, awacs.params(1.0), 8, **kw)
        four = ex.run_experiment_stream(spec, awacs.params(1.0), 8,
                                        mesh=ex.make_mesh(4), **kw)
    assert int(four.total_events) == int(one.total_events)
    assert float(four.summary.n) == float(one.summary.n)
    np.testing.assert_allclose(float(four.summary.m1), float(one.summary.m1),
                               rtol=1e-6)
    for k in ("boundary_dispatches", "lane_steps_dispatched"):
        assert int(four.boundary[k]) == int(one.boundary[k])


def test_scorer_runs_once_per_round_in_the_boundary_phase(monkeypatch):
    """With the Pallas scorer (interpret mode) in the model, the chunk
    program holds one scorer call, batched over the lanes: the per-event
    step carries the boundary block's stub."""
    monkeypatch.setattr(awacs, "nn_scores", functools.partial(
        awacs.nn_scores, use_pallas=True, interpret=True))
    with config.profile("f32"):
        spec, _ = awacs.build(16, dwell=DWELL)
        sims = jax.vmap(
            lambda r: cl.init_sim(spec, 1, r, awacs.params(0.5))
        )(jnp.arange(4))
        text = str(jax.make_jaxpr(cl.make_chunk(spec, max_steps=64))(sims))
        assert text.count("pallas_call") == 1
        assert text.count(f"name={awacs.KERNEL_NAME}") == 1
        out = jax.jit(cl.make_chunk(spec, max_steps=256))(sims)
        plain = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
    np.testing.assert_array_equal(np.asarray(out[0].user["dwells"]),
                                  np.asarray(plain.user["dwells"]))
    np.testing.assert_array_equal(np.asarray(out[0].n_events),
                                  np.asarray(plain.n_events))


def test_batched_scorer_matches_the_forward_pass():
    """vmap of the Pallas scorer (one call over every lane's targets) in
    interpret mode against the plain matmul stack, lane by lane."""
    rng = np.random.default_rng(3)
    pos = jnp.asarray(rng.uniform(-120, 120, (3, 50, 2)), jnp.float32)
    vel = jnp.asarray(rng.normal(0, awacs.SPEED, (3, 50, 2)), jnp.float32)
    got = jax.vmap(functools.partial(
        awacs.nn_scores, use_pallas=True, interpret=True))(pos, vel)
    assert got.shape == (3, 50)
    for lane in range(3):
        feats, g = awacs._nn_features(pos[lane], vel[lane])
        want = awacs._nn_forward(feats, g, *awacs._NN_WEIGHTS)
        np.testing.assert_allclose(np.asarray(got[lane]), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_boundary_chunk_runs_the_per_leaf_loop(carry_layout):
    """A boundary model's chunk carries its Sim per leaf whatever the
    backend's carry rule says (an accelerator packs)."""

    def chunk_text(n_targets, packed):
        carry_layout(packed)
        spec, _ = awacs.build(n_targets, dwell=DWELL)
        sims = jax.eval_shape(jax.vmap(
            lambda r: cl.init_sim(spec, 1, r, awacs.params(1.0))
        ), jnp.arange(2))
        return str(jax.make_jaxpr(cl.make_chunk(spec, max_steps=8))(sims))

    with config.profile("f32"):
        for n in (8, 1000):
            assert chunk_text(n, True) == chunk_text(n, False)


def test_dwell_is_a_build_parameter():
    for dwell, dwells in ((awacs.DWELL, 3), (DWELL, 51)):
        spec, _ = awacs.build(4, dwell=dwell)
        sim = jax.jit(cl.make_run(spec))(
            cl.init_sim(spec, 9, 0, awacs.params(2.0)))
        assert int(sim.user["dwells"]) == dwells


# sha256 of each program's jaxpr text as the current programs trace (mm1
# with a FIFO of 128, mg1 of 512; 8 lanes, chunk_steps 16, horizon column
# 50; fold over the default summary path): init and fold as before the
# boundary phase existed; the chunk programs as the lanes' loop
# (loop.make_lanes_run) traces them, on one any-lane-live condition with
# the self-gated, merge-free step (their lanes pinned bitwise the
# lax.switch form's by tests/test_batched_dispatch.py).  The lanes' loop
# carries per leaf on every backend, so 'chunk' (the backend's carry rule
# reading per leaf, the CPU's) and 'chunk_packed' (reading packed, an
# accelerator's) are one program.  AWACS (8 targets, the same lanes, chunk
# steps and horizon): its boundary chunk.
PROGRAM_SHA256 = {
    ('awacs', 'f32', 'chunk'): (
        "e30f81f26d43d9e42af0956a354035685da096eb3e4a761b4279aea95a870fc9"
    ),
    ('awacs', 'f64', 'chunk'): (
        "d48e44e40f7d807b8a0e94b6f7952dc0a8cc6b10734b4878f18c0e282471dfe5"
    ),
    ('mg1', 'f32', 'chunk'): (
        "de1c9b42610667eb952b7d563c83fe7de5fcf05a30229d6c6e899553f6c2ac4e"
    ),
    ('mg1', 'f32', 'chunk_packed'): (
        "de1c9b42610667eb952b7d563c83fe7de5fcf05a30229d6c6e899553f6c2ac4e"
    ),
    ('mg1', 'f32', 'fold'): (
        "3fb9aa6f030ef6666c746cc15cc0e69e93b7a2b2b9265406e9b533ffd6a0f68b"
    ),
    ('mg1', 'f32', 'init'): (
        "ab5b51ef2941702aeb459d7e2066820fabd3705dc7a7610b23b3fac718cbacac"
    ),
    ('mg1', 'f64', 'chunk'): (
        "916124d8b204447df088e37c700f7c5f649edeff8d850a9749f68952d53444db"
    ),
    ('mg1', 'f64', 'chunk_packed'): (
        "916124d8b204447df088e37c700f7c5f649edeff8d850a9749f68952d53444db"
    ),
    ('mg1', 'f64', 'fold'): (
        "f05b06ed07c5fe26b8f4c734186230eac5f293b78b92a2fe9604a6bb3ddc1157"
    ),
    ('mg1', 'f64', 'init'): (
        "7fbc8bcd7c4f84dd1fd3ac7e9f6989c8fcbbf92a6ffb4881b37118be3fa702e8"
    ),
    ('mm1', 'f32', 'chunk'): (
        "9a0925afbce746fd266f95cd2c77710f5da63ad613aea8a4fcd3292a6d3192ff"
    ),
    ('mm1', 'f32', 'chunk_packed'): (
        "9a0925afbce746fd266f95cd2c77710f5da63ad613aea8a4fcd3292a6d3192ff"
    ),
    ('mm1', 'f32', 'fold'): (
        "18571c07247feaf6aef88328af2110e19e2ee1b9612becd2fb858544b581e4fe"
    ),
    ('mm1', 'f32', 'init'): (
        "1effc0a98b36a1cf043c8f47f7e4b44b7d5a7c10444e121cfb9f9f4fcf74037f"
    ),
    ('mm1', 'f64', 'chunk'): (
        "a4df909b217b960f77abb55eb093b5ef11ee9850bc2437d2e0b84b6612f67ddb"
    ),
    ('mm1', 'f64', 'chunk_packed'): (
        "a4df909b217b960f77abb55eb093b5ef11ee9850bc2437d2e0b84b6612f67ddb"
    ),
    ('mm1', 'f64', 'fold'): (
        "2badfe19d0059a738d9932ea5bd3bd574c7fbc4a30187c5e0c36a38a1f580ec7"
    ),
    ('mm1', 'f64', 'init'): (
        "bc73f373c66db4d5c38f3bb19886d26175c41b0cacba7ffefcc961dcd1d86fb2"
    ),
}


def _programs(name, profile, pack):
    """The programs' jaxpr texts; ``pack`` names the chunk's layout,
    which the caller has set."""
    with config.profile(profile):
        if name == "mm1":
            spec, params = mm1.build(queue_cap=128, record=False)[0], mm1.params(10)
        elif name == "mg1":
            spec, params = mg1.build(queue_cap=512)[0], (1.25, 1.0, 0.5, 10)
        else:
            spec, params = awacs.build(8, dwell=DWELL)[0], awacs.params(50.0)
        cache = serve.ProgramCache(store=False)
        init_j, chunk_j = pc.get_programs(
            cache, spec, mesh=None, chunk_steps=16, with_metrics=False)
        lanes = 8
        args = (jnp.arange(lanes), ex._seed_column(1, lanes),
                ex._horizon_column(50.0, lanes),
                ex._slice_params(params, lanes, 0, lanes))
        sims = jax.eval_shape(init_j, *args)
        out = {
            "init": str(jax.make_jaxpr(init_j)(*args)),
            "chunk_packed" if pack else "chunk":
                str(jax.make_jaxpr(chunk_j)(sims)),
        }
        if name != "awacs":  # AWACS folds another statistic
            fold_j = pc.get_fold(cache, False, ex.default_summary_path)
            out["fold"] = str(
                jax.make_jaxpr(fold_j)(pc.stream_acc(spec, False), sims))
        return out


@pytest.mark.parametrize("pack", [True, False])
@pytest.mark.parametrize("profile", ["f32", "f64"])
@pytest.mark.parametrize("name", ["mm1", "mg1"])
def test_programs_without_boundary_blocks_are_unchanged(
    name, profile, pack, carry_layout,
):
    carry_layout(pack)
    for kind, text in _programs(name, profile, pack).items():
        digest = hashlib.sha256(text.encode()).hexdigest()
        assert digest == PROGRAM_SHA256[(name, profile, kind)], kind


def _digest(text):
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("profile", ["f32", "f64"])
def test_awacs_chunk_program_is_unchanged(profile):
    text = _programs("awacs", profile, False)["chunk"]
    assert _digest(text) == PROGRAM_SHA256[("awacs", profile, "chunk")]


@pytest.mark.parametrize("backend,kind", [("cpu", "chunk"),
                                          ("tpu", "chunk_packed")])
def test_backend_picks_the_carry(backend, kind, monkeypatch):
    """The carry rule reads the backend (``loop._packed_carry``, for the
    one-replication ``make_run``); the mm1 chunk program is the same
    per-leaf lanes' loop whichever way it reads."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with config.profile("f32"):
        spec = mm1.build(queue_cap=128, record=False)[0]
        init_j, chunk_j = pc.get_programs(
            serve.ProgramCache(store=False), spec, mesh=None,
            chunk_steps=16, with_metrics=False)
        lanes = 8
        sims = jax.eval_shape(
            init_j, jnp.arange(lanes), ex._seed_column(1, lanes),
            ex._horizon_column(50.0, lanes),
            ex._slice_params(mm1.params(10), lanes, 0, lanes))
        text = str(jax.make_jaxpr(chunk_j)(sims))
    assert _digest(text) == PROGRAM_SHA256[("mm1", "f32", kind)]
