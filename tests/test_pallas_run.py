"""The Pallas mega-kernel event loop (core/pallas_run.py).

Semantics are validated here in interpret mode (backend-independent): the
kernel path must be *bit-identical* to the plain-XLA f32 interpreter path —
it runs the same make_step dispatcher, so any divergence is a bug in the
kernel plumbing (lane layout, const hoisting, masking), never a tolerance.

The Mosaic-compiled TPU path is compiled offline by tests/test_mosaic_aot.py
and run on the chip by chip_smoke.py.
"""

import jax
import jax.numpy as jnp
import pytest

from cimba_tpu import config
from cimba_tpu.core import loop as cl
from cimba_tpu.core import pallas_run as pr
from cimba_tpu.models import mm1
from cimba_tpu.stats import summary as sm


@pytest.fixture
def f32_profile():
    with config.profile("f32"):
        yield


def _init_batch(spec, n_lanes, n_objects):
    def one(rep):
        return cl.init_sim(spec, 2026, rep, (1.0 / 0.9, 1.0, n_objects))

    return jax.jit(jax.vmap(one))(jnp.arange(n_lanes))


def test_kernel_matches_xla_f32_bitwise(f32_profile):
    spec, _ = mm1.build(record=False)
    sims = _init_batch(spec, 128, 200)
    xla = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
    ker = pr.make_kernel_run(spec, chunk_steps=64, interpret=True)(sims)
    assert bool((xla.n_events == ker.n_events).all())
    assert bool((xla.clock == ker.clock).all())
    assert bool((xla.err == ker.err).all()) and int(xla.err.sum()) == 0
    mx = sm.merge_tree(xla.user["wait"])
    mk = sm.merge_tree(ker.user["wait"])
    assert float(sm.mean(mx)) == float(sm.mean(mk))


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_kernel_chunk_boundary_invariance(f32_profile):
    """Splitting the run into different chunk sizes cannot change results
    (state round-trips through the kernel boundary losslessly)."""
    spec, _ = mm1.build(record=False)
    sims = _init_batch(spec, 64, 100)
    a = pr.make_kernel_run(spec, chunk_steps=16, interpret=True)(sims)
    b = pr.make_kernel_run(spec, chunk_steps=1024, interpret=True)(sims)
    assert bool((a.n_events == b.n_events).all())
    assert bool((a.clock == b.clock).all())


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_f32_profile_statistics_close_to_f64():
    spec64_out = None
    with config.profile("f64"):
        spec, _ = mm1.build(record=False)
        sims = _init_batch(spec, 128, 500)
        out = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
        m = sm.merge_tree(out.user["wait"])
        mean64, ev64 = float(sm.mean(m)), int(out.n_events.sum())
    with config.profile("f32"):
        spec, _ = mm1.build(record=False)
        sims = _init_batch(spec, 128, 500)
        out = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
        m = sm.merge_tree(out.user["wait"])
        mean32, ev32 = float(sm.mean(m)), int(out.n_events.sum())
    # identical draw-count contract: one counter tick per draw in both
    # profiles keeps the streams aligned — but event COUNTS may differ
    # by a handful of near-tie order flips (two wakes whose f64 times
    # differ inside one f32 ulp pop in seq order instead of time order,
    # turning a direct success into a pend retry or back; ~1e-4 of
    # events at this size).  The statistics contract is the guarantee.
    assert abs(ev32 - ev64) <= max(5, ev64 // 5_000)
    assert mean32 == pytest.approx(mean64, rel=5e-3)


def test_kernel_requires_f32_profile():
    spec, _ = mm1.build(record=False)
    with pytest.raises(ValueError, match="f32"):
        pr.make_kernel_run(spec)


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_kernel_sharded_over_mesh_matches_single(f32_profile):
    """Kernel x mesh composition: the chunked kernel driver under
    shard_map over the lane axis (per-device kernels, global-liveness
    host loop) must reproduce the single-device kernel run bitwise —
    lanes are independent, so device placement cannot leak into
    results.  Runs on the 8-virtual-device CPU mesh (conftest)."""
    from jax.sharding import Mesh

    spec, _ = mm1.build(record=False)
    sims = _init_batch(spec, 64, 100)
    mesh = Mesh(jax.devices(), ("rep",))
    one = pr.make_kernel_run(spec, chunk_steps=64, interpret=True)(sims)
    many = pr.make_kernel_run(
        spec, chunk_steps=64, interpret=True, mesh=mesh
    )(sims)
    assert bool((one.n_events == many.n_events).all())
    assert bool((one.clock == many.clock).all())
    assert int(many.err.sum()) == 0


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_kernel_matches_xla_f32_awacs(f32_profile):
    """configs[4] through the kernel: exercises the BOUNDARY-block
    machinery end to end — sensor_dwell dispatches are deferred by the
    chunk, applied host-side as plain XLA steps between chunks, and the
    result must still match the pure-XLA run bitwise (event counts,
    clocks, statistics)."""
    from cimba_tpu.models import awacs

    spec, _ = awacs.build(16)  # default scoring='nn'

    def one(rep):
        return cl.init_sim(spec, 2026, rep, awacs.params(2.0))

    sims = jax.jit(jax.vmap(one))(jnp.arange(8))
    xla = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
    ker = pr.make_kernel_run(spec, chunk_steps=64, interpret=True)(sims)
    assert bool((xla.n_events == ker.n_events).all())
    assert bool((xla.clock == ker.clock).all())
    assert int(ker.err.sum()) == 0
    mx = sm.merge_tree(xla.user["detections"])
    mk = sm.merge_tree(ker.user["detections"])
    assert float(sm.mean(mx)) == float(sm.mean(mk))


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_kernel_matches_xla_f32_mmc(f32_profile):
    """Kernel path on a model with pool + bool pqueue-style state (mmc):
    exercises lane_sel's bool-leaf handling (i1 selects are rewritten as
    logic ops — Mosaic cannot lower select_n on i1 payloads)."""
    from cimba_tpu.models import mmc

    spec, _ = mmc.build(3)

    def one(rep):
        return cl.init_sim(spec, 7, rep, mmc.params(120, 2.5, 1.0))

    sims = jax.jit(jax.vmap(one))(jnp.arange(32))
    xla = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
    ker = pr.make_kernel_run(spec, chunk_steps=64, interpret=True)(sims)
    assert bool((xla.n_events == ker.n_events).all())
    assert bool((xla.clock == ker.clock).all())
    assert int(ker.err.sum()) == 0


def test_lanelast_dot_general_rule(f32_profile):
    """Direct coverage for lanelast's per-lane dot_general rule ([m,K] @
    unbatched [K,n] under the lane-last layout) — awacs no longer
    exercises it in-kernel since its scorer became a boundary block, but
    the rule stays for models that keep small matmuls in the hot loop."""
    import numpy as np

    from cimba_tpu.core import lanelast

    W = jnp.asarray(np.random.default_rng(0).normal(size=(3, 4)), jnp.float32)

    def f(x):  # per-lane [2,3] @ [3,4]
        return (x @ W).sum(axis=1)

    L = 8
    xs = jnp.asarray(
        np.random.default_rng(1).normal(size=(2, 3, L)), jnp.float32
    )
    j = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((2, 3), jnp.float32))
    (out,) = lanelast.eval_lanelast(
        j.jaxpr, j.consts, L, [lanelast._Val(xs, True)]
    )
    want = jax.vmap(f, in_axes=-1, out_axes=-1)(xs)
    np.testing.assert_allclose(
        np.asarray(out.x), np.asarray(want), rtol=1e-6
    )


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_kernel_awacs_sharded_over_mesh_matches_single(f32_profile):
    """Flagship x mesh: the AWACS kernel run — boundary-block NN physics
    applied between chunks — sharded over the 8-virtual-device mesh must
    reproduce the single-device kernel run bitwise (the full multi-chip
    shape of BASELINE configs[4])."""
    from jax.sharding import Mesh

    from cimba_tpu.models import awacs

    spec, _ = awacs.build(8)

    def one(rep):
        return cl.init_sim(spec, 2026, rep, awacs.params(1.5))

    sims = jax.jit(jax.vmap(one))(jnp.arange(16))
    mesh = Mesh(jax.devices(), ("rep",))
    single = pr.make_kernel_run(spec, chunk_steps=32, interpret=True)(sims)
    many = pr.make_kernel_run(
        spec, chunk_steps=32, interpret=True, mesh=mesh
    )(sims)
    assert bool((single.n_events == many.n_events).all())
    assert bool((single.clock == many.clock).all())
    assert int(many.err.sum()) == 0
    mx = sm.merge_tree(single.user["detections"])
    mk = sm.merge_tree(many.user["detections"])
    assert float(sm.mean(mx)) == float(sm.mean(mk))


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_boundary_block_mid_chain_entry_fails_loudly(f32_profile):
    """A boundary block reached mid-chain (via a completed command's
    next_pc instead of a resume) violates the boundary contract; the
    kernel must fail that lane with ERR_BOUNDARY rather than silently
    running the stub.  The XLA path runs the same model fine (the
    marker is kernel-only semantics)."""
    from cimba_tpu.core import api, cmd
    from cimba_tpu.core.model import Model

    m = Model("bad_boundary", event_cap=4)

    @m.user_state
    def init(params):
        return {"acc": jnp.zeros((), jnp.float32)}

    @m.block
    def go(sim, p, sig):
        # jump straight into the boundary block: mid-chain entry
        return sim, cmd.jump(heavy.pc)

    @m.boundary_block
    def heavy(sim, p, sig):
        sim = api.set_user(sim, {"acc": sim.user["acc"] + 1.0})
        sim = api.stop(sim, sim.user["acc"] > 2.0)
        return sim, cmd.hold(1.0, next_pc=go.pc)

    m.process("w", entry=heavy)
    spec = m.build()

    def one(rep):
        return cl.init_sim(spec, 3, rep)

    sims = jax.jit(jax.vmap(one))(jnp.arange(4))
    # XLA path: marker ignored, model completes
    xla = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
    assert int(xla.err.sum()) == 0
    # kernel path: every lane flags the illegal mid-chain entry
    ker = pr.make_kernel_run(spec, chunk_steps=16, interpret=True)(sims)
    assert bool((ker.err == cl.ERR_BOUNDARY).all()), [int(e) for e in ker.err]


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_kernel_matches_xla_f32_mg1(f32_profile):
    """Kernel path on mg1: the lognormal sampler (exp/log chains) and
    the 512-slot ring in-kernel."""
    from cimba_tpu.models import mg1

    spec, _ = mg1.build()

    def one(rep):
        return cl.init_sim(spec, 13, rep, (1.25, 1.0, 1.5, 100))

    sims = jax.jit(jax.vmap(one))(jnp.arange(16))
    xla = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
    ker = pr.make_kernel_run(spec, chunk_steps=64, interpret=True)(sims)
    assert bool((xla.n_events == ker.n_events).all())
    assert bool((xla.clock == ker.clock).all())
    assert int(ker.err.sum()) == 0


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_kernel_matches_xla_f32_jobshop(f32_profile):
    """Kernel path on jobshop: pools (greedy acquire + rollback),
    buffers (partial fulfillment), pq and recording accumulators all
    live in one kernel trace — the widest handler table shipped."""
    from cimba_tpu.models import jobshop

    spec, _ = jobshop.build()

    def one(rep):
        return cl.init_sim(spec, 13, rep, jobshop.params(40))

    sims = jax.jit(jax.vmap(one))(jnp.arange(16))
    xla = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
    ker = pr.make_kernel_run(spec, chunk_steps=64, interpret=True)(sims)
    assert bool((xla.n_events == ker.n_events).all())
    assert bool((xla.clock == ker.clock).all())
    assert int(ker.err.sum()) == 0


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_kernel_matches_xla_f32_condition(f32_profile):
    """Kernel path on a condition-variable model: the registered traced
    predicate, cond_wait's retry gating and cond_signal's per-pid
    wake-all loop all execute in-kernel (the one component the model
    battery didn't previously trace through the kernel)."""
    from cimba_tpu.core import api, cmd
    from cimba_tpu.core.model import Model

    m = Model("kcond", n_flocals=1, event_cap=16)

    @m.user_state
    def user_init(params):
        return {"count": jnp.zeros((), jnp.float32)}

    cv = m.condition("enough", lambda sim, p: sim.user["count"] >= 2.0)

    @m.block
    def waiter(sim, p, sig):
        return sim, cmd.cond_wait(cv.id, next_pc=granted.pc)

    @m.block
    def granted(sim, p, sig):
        sim = api.set_local_f(sim, p, 0, api.clock(sim))
        return sim, cmd.exit_()

    @m.block
    def tick(sim, p, sig):
        return sim, cmd.hold(1.0, next_pc=bump.pc)

    @m.block
    def bump(sim, p, sig):
        sim = api.set_user(sim, {"count": sim.user["count"] + 1.0})
        sim = api.cond_signal(sim, spec_holder[0], cv)
        return sim, cmd.select(
            sim.user["count"] >= 2.0, cmd.exit_(), cmd.jump(tick.pc)
        )

    m.process("waiter", entry=waiter, count=2)
    m.process("incrementer", entry=tick)
    spec_holder = [None]
    spec_holder[0] = m.build()
    spec = spec_holder[0]

    sims = jax.jit(jax.vmap(lambda r: cl.init_sim(spec, 3, r)))(
        jnp.arange(8)
    )
    xla = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
    ker = pr.make_kernel_run(spec, chunk_steps=32, interpret=True)(sims)
    assert bool((xla.n_events == ker.n_events).all())
    assert bool((xla.clock == ker.clock).all())
    assert bool((xla.procs.locals_f == ker.procs.locals_f).all())
    assert int(ker.err.sum()) == 0
    # both waiters woke exactly when the predicate turned true
    assert bool((ker.procs.locals_f[:, 0, 0] == 2.0).all())


def test_pack_unpack_roundtrip():
    """pallas_run._pack/_unpack are exact inverses over the leaf-shape
    zoo the engine produces: scalars, [k], [k,1], [1,cap] per-lane
    shapes; f32/i32/u32 (bitcast rows) and bool (passthrough)."""
    import numpy as np

    L = 4
    rng = np.random.default_rng(0)
    specs = [
        ((), jnp.float32), ((), jnp.int32), ((), jnp.uint32),
        ((2,), jnp.float32), ((2,), jnp.int32), ((2, 1), jnp.int32),
        ((1, 128), jnp.float32), ((), jnp.bool_), ((3,), jnp.uint32),
    ]
    leaves = []
    for s, dt in specs:
        full = s + (L,)
        if dt == jnp.bool_:
            leaves.append(jnp.asarray(rng.integers(0, 2, full), dt))
        elif dt == jnp.float32:
            leaves.append(jnp.asarray(rng.normal(size=full), dt))
        else:
            leaves.append(
                jnp.asarray(rng.integers(0, 2**31 - 1, full), dt)
            )
    avals = [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in leaves]
    plan = pr._pack_plan(avals)
    # grouping: 3 f32 + 5 int/uint rows packed, 1 bool passthrough
    assert len(plan["groups"]["f32"]) == 3
    assert len(plan["groups"]["i32"]) == 5
    assert plan["passthrough"] == [7]
    bufs = pr._pack(leaves, plan)
    assert len(bufs) == 3  # f32 buffer, i32 buffer, bool leaf
    assert bufs[0].shape == (1 + 2 + 128, L)
    assert bufs[1].shape == (1 + 1 + 2 + 2 + 3, L)
    out = pr._unpack(bufs, plan, L)
    for a, b in zip(leaves, out):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_kernel_lane_block_grid_matches_xla(f32_profile):
    """The lane-block grid (pallas grid over lane blocks; VMEM holds one
    block) is trajectory-identical to the monolithic kernel and the XLA
    path — lanes are independent, so per-block while-loops change
    nothing.  Composed with the packed carry in the second arm."""
    import numpy as np

    spec, _ = mm1.build(record=False)
    sims = jax.jit(
        jax.vmap(lambda r: cl.init_sim(spec, 5, r, (1.0 / 0.9, 1.0, 120)))
    )(jnp.arange(8))
    xla = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
    for kw in (dict(lane_block=4), dict(lane_block=2, packed=True)):
        ker = pr.make_kernel_run(spec, interpret=True, **kw)(sims)
        for a, b in zip(jax.tree.leaves(xla), jax.tree.leaves(ker)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_lane_block_must_divide(f32_profile):
    spec, _ = mm1.build(record=False)
    sims = jax.jit(
        jax.vmap(lambda r: cl.init_sim(spec, 5, r, (1.0 / 0.9, 1.0, 10)))
    )(jnp.arange(6))
    with pytest.raises(ValueError, match="divide"):
        pr.make_kernel_run(spec, interpret=True, lane_block=4)(sims)
