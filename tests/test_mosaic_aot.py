"""Offline Mosaic compiles of the Pallas kernels for a v5e.

The interpret-mode equivalence tests (test_pallas_run.py,
test_pallas_kernels.py, test_kernel_fuzz.py) validate kernel *semantics*
but say nothing about Mosaic *lowering*: tiling alignment, the
scoped-VMEM budget, the lane-last layouts and i32-carried bools the
lanelast/bool32 transforms exist to guarantee.  These tests run the full
Mosaic pipeline here with no TPU attached: a described
``v5e:2x2`` topology gives a compile-only client, and
``jit(kernel).lower(avals on one described device).compile()`` raises
what the chip's compiler would raise.

The topology is described inside a module fixture, in the test's own
process, never at import: only one process may load the TPU library,
and it keeps it until it exits (see the on-chip-measurement guide).
Everything that must compile before a chip run lives in this one file.
Tier-1 keeps the kernels of the main path at small widths (a second or
two each); the bench-width mm1 chunk (L=4096, ~25-30 s each) and the
other models' chunks are ``slow``.

Reference-parity note: this is the TPU answer to the reference CI building
every tier (debug/NDEBUG/NASSERT) to prove each still *builds*
(`/root/reference/test/meson.build:8-38`).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cimba_tpu import config
from cimba_tpu.core import loop as cl
from cimba_tpu.core import pallas_run as pr


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


# in-kernel matmul fixture: a block computing a per-lane [2,3]@[3,4]
# against a captured weight const — keeps the lanelast dot_general rule
# and whole-ref VMEM const routing under REAL Mosaic coverage now that
# awacs's scorer moved to a boundary block (stubbed out of its chunk)
def _build_matmul():
    import numpy as np
    import cimba_tpu.random as cr
    from cimba_tpu.core import api, cmd
    from cimba_tpu.core.model import Model

    m = Model("aot_matmul", event_cap=4)
    W = jnp.asarray(np.linspace(-1.0, 1.0, 12).reshape(3, 4), jnp.float32)

    @m.user_state
    def init(params):
        return {"h": jnp.zeros((2, 3), jnp.float32),
                 "acc": jnp.zeros((), jnp.float32)}

    @m.block
    def work(sim, p, sig):
        y = sim.user["h"] @ W
        sim, u = api.draw(sim, cr.uniform01)
        sim = api.set_user(sim, {
            "h": sim.user["h"] + u.astype(jnp.float32),
            "acc": sim.user["acc"] + jnp.sum(y),
        })
        sim = api.stop(sim, sim.user["acc"] > 50.0)
        sim, t = api.draw(sim, cr.exponential, 1.0)
        return sim, cmd.hold(t, next_pc=work.pc)

    m.process("w", entry=work)
    return m.build(), None

# wait_event fixture: keeps the vectorized waiter scan (ev._valid_vec's
# [P, CAP] one-hot) and a LIVE general event table under real Mosaic
# coverage — every shipped kernel model runs that table empty
def _build_wev():
    import cimba_tpu.random as cr
    from cimba_tpu.core import api, cmd
    from cimba_tpu.core.model import Model

    m = Model("aot_wev", n_flocals=1, n_ilocals=1, event_cap=16)

    @m.user_state
    def init(params):
        return {"fires": jnp.zeros((), jnp.int32)}

    @m.handler
    def on_fire(sim, subj, arg):
        return api.set_user(sim, {"fires": sim.user["fires"] + 1})

    @m.block
    def s_go(sim, p, sig):
        sim, dt = api.draw(sim, cr.exponential, 1.0)
        sim, h = api.schedule(sim, api.clock(sim) + dt, 0, on_fire)
        return sim, cmd.wait_event(h, next_pc=s_woke.pc)

    @m.block
    def s_woke(sim, p, sig):
        sim = api.set_local_i(sim, p, 0, sig)
        done = api.clock(sim) > 4.0
        return sim, cmd.select(
            done, cmd.exit_(), cmd.hold(0.1, next_pc=s_go.pc)
        )

    m.process("sched", entry=s_go, count=3)
    return m.build(), None

# spawn-pool fixture: keeps spawn_process's in-kernel free-row scan
# (the (status==CREATED)|(status==FINISHED) & in-pool bool chain and
# the row resets) under real Mosaic coverage
def _build_spawn():
    import cimba_tpu.random as cr
    from cimba_tpu.core import api, cmd
    from cimba_tpu.core.model import Model

    m = Model("aot_spawn", n_flocals=1, event_cap=8)

    @m.user_state
    def init(params):
        return {"n": jnp.zeros((), jnp.int32)}

    @m.block
    def src(sim, p, sig):
        sim, pid = api.spawn(sim, pool)
        sim = api.set_user(sim, {"n": sim.user["n"] + (pid >= 0)})
        sim = api.stop(sim, sim.user["n"] >= 10)
        sim, t = api.draw(sim, cr.exponential, 1.0)
        return sim, cmd.hold(t, next_pc=src.pc)

    @m.block
    def worker(sim, p, sig):
        sim = api.set_local_f(sim, p, 0, api.clock(sim))
        sim, t = api.draw(sim, cr.exponential, 0.5)
        return sim, cmd.hold(t, next_pc=w_done.pc)

    @m.block
    def w_done(sim, p, sig):
        return sim, cmd.exit_()

    m.process("src", entry=src)
    pool = m.process("worker", entry=worker, count=3, start=False)
    return m.build(), None

# condition fixture: registered traced predicate + cond_signal's
# per-pid wake-all loop (kfori) under real Mosaic coverage
def _build_cond():
    from cimba_tpu.core import api, cmd
    from cimba_tpu.core.model import Model

    m = Model("aot_cond", n_flocals=1, event_cap=16)

    @m.user_state
    def init(params):
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
    return spec_holder[0], None


def _mm1():
    from cimba_tpu.models import mm1

    return mm1.build(record=False)[0], (1.0 / 0.9, 1.0, 20)


def _awacs_chunk():
    from cimba_tpu.models import awacs

    return awacs.build(16)[0], (1.0,)


def _mg1():
    from cimba_tpu.models import mg1

    return mg1.build()[0], (1.25, 1.0, 1.5, 20)


def _jobshop():
    from cimba_tpu.models import jobshop

    return jobshop.build()[0], jobshop.params(10)


def _compile_chunk(sharding, build, L, packed=False, chunk_steps=16):
    """Mosaic-compile one chunk kernel of ``build()``'s model at ``L``
    lanes, exactly as ``make_kernel_run`` builds it for the chip."""
    with config.profile("f32"):
        spec, args = build()
        sims = jax.eval_shape(
            jax.vmap(lambda r: cl.init_sim(spec, 2026, r, args)),
            jnp.arange(L),
        )
        krun = pr.make_kernel_run(
            spec, chunk_steps=chunk_steps, packed=packed
        )
        with jax.enable_x64(False):
            leaves, treedef = jax.tree.flatten(sims)
            # the kernel boundary moves the lane axis last
            leaves = [
                jax.ShapeDtypeStruct(l.shape[1:] + (L,), l.dtype)
                for l in leaves
            ]
            chunk_fn, _ = krun.build_chunk_call(leaves, treedef)
            compiled = jax.jit(chunk_fn).lower(
                *_on(sharding, leaves)
            ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# -- tier-1: the main path's kernels -----------------------------------------


@pytest.mark.parametrize("packed", [False, True], ids=["per_leaf", "packed"])
def test_mm1_chunk_compiles(one_chip, packed):
    """The mm1 chunk kernel in both carry layouts (the packed one adds
    concat/slice/bitcast inside the loop body, a risk class the
    per-leaf carry never exercises)."""
    _compile_chunk(one_chip, _mm1, 8, packed=packed)


def test_awacs_scorer_compiles_at_1000_targets(one_chip):
    """The AWACS detection scorer as the boundary phase runs it: vmapped
    over a 4096-lane wave of 1000 targets, ONE ``awacs_nn_score`` call
    over the 4,096,000 rows, tiled in lane-dense row blocks."""
    from cimba_tpu.models import awacs

    pos = jax.ShapeDtypeStruct((4096, 1000, 2), jnp.float32,
                               sharding=one_chip)
    with jax.enable_x64(False):
        compiled = jax.jit(jax.vmap(
            lambda p, v: awacs.nn_scores(p, v, use_pallas=True)
        )).lower(pos, pos).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert awacs.KERNEL_NAME in text


def test_random_blocks_compile(one_chip):
    """The bulk-variate kernels (random/pallas_kernels.py) under the f32
    profile, the only one Mosaic can represent."""
    import cimba_tpu.random as cr
    from cimba_tpu.random import pallas_kernels as pk

    states = _on(one_chip, jax.eval_shape(
        jax.vmap(lambda r: cr.initialize(5, r)), jnp.arange(8)
    ))
    with config.profile("f32"), jax.enable_x64(False):
        for block in (
            pk.exponential_block, pk.normal_block, pk.exponential_block_zig
        ):
            compiled = jax.jit(lambda s: block(s, 128)).lower(
                states
            ).compile()
            assert "tpu_custom_call" in compiled.as_text()


def test_xla_chunk_ring_tiles_eight_lanes(one_chip, carry_layout):
    """The XLA chunk program (the stream runner's, packed carry) keeps
    mm1's FIFO ring rank-1 per lane: vmapped, it is ``[lanes, 128]`` in
    dense ``(8,128)`` tiles.  A ``[lanes, 1, 128]`` ring tiles ``(1,128)``
    (one lane's row per vreg, 7 of 8 sublanes empty) and made the ring's
    read and write passes the chip's slowest ops."""
    import re

    from cimba_tpu import serve
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.serve import cache as pc

    L = 1024
    carry_layout(True)  # the chip's layout
    with config.profile("f32"):
        spec, args = _mm1()
        init_j, chunk_j = pc.get_programs(
            serve.ProgramCache(store=False), spec, mesh=None,
            chunk_steps=1024, with_metrics=False)
        sims = jax.eval_shape(
            init_j, jnp.arange(L), ex._seed_column(1, L), None,
            ex._slice_params(args, L, 0, L))
        hlo = chunk_j.lower(_on(one_chip, sims)).compile().as_text()
    assert f"f32[{L},1,128]" not in hlo
    assert re.search(rf"f32\[{L},128\]\{{1,0:T\(8,128\)", hlo)


# -- slow: the bench width and the other models ------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("packed", [False, True], ids=["per_leaf", "packed"])
def test_mm1_chunk_compiles_at_bench_width(one_chip, packed):
    """L=4096, chunk 512: the bench's and chip_smoke's kernel width,
    under the 96 MiB scoped-VMEM budget (v5e has 128 MiB)."""
    _compile_chunk(one_chip, _mm1, 4096, packed=packed, chunk_steps=512)


@pytest.mark.slow
@pytest.mark.parametrize(
    "build",
    [_awacs_chunk, _build_matmul, _build_wev, _build_spawn, _mg1, _jobshop,
     _build_cond],
    ids=["awacs", "matmul", "wait_event", "spawn", "mg1", "jobshop",
         "condition"],
)
def test_model_chunk_compiles(one_chip, build):
    """awacs: dense wake table, boundary-block stubbing (the NN scorer
    is OUTSIDE this chunk), target physics.  matmul: the lanelast
    dot_general rule + whole-ref VMEM const routing.  wait_event: the
    vectorized waiter scan + a live general event table.  spawn: the
    free-row scan and row resets.  mg1: the lognormal sampler chain +
    the 512-slot ring.  jobshop: the widest handler table shipped.
    condition: a registered predicate + cond_signal's wake loop."""
    _compile_chunk(one_chip, build, 8)
