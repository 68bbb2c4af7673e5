"""The batched step's merge-free dispatch (``loop._vswitch`` with
``batched=True``): every function that vmaps the step (``make_chunk`` and
the boundary phase among them) dispatches its three levels — event kind,
block, command handler — without ``lax.switch``'s per-leaf ``select_n``
merges.  Its lanes are bitwise those of ``jax.vmap`` over the scalar
``make_run``, which keeps ``lax.switch``; its program holds one chain
loop per dispatch level and a fraction of the selects.

The lanes' loop (``loop.make_lanes_run``) runs on one unbatched
any-lane-live condition and freezes a finished lane through the step's
own gate: a gated-off step returns its lane bitwise, so the loop holds
none of the per-leaf freeze selects that ``vmap`` of a while loop
adds."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from cimba_tpu import config
from cimba_tpu.core import loop as cl
from cimba_tpu.models import awacs, mg1, mm1

LANES = 6  # a lane count no per-lane table has, so a leading dim names it


def _model(name):
    if name == "mm1":
        return mm1.build(record=False)[0], mm1.params(40)
    if name == "mg1":
        # rho 0.8 at CV 2: long queues, both put paths, the lognormal draw
        return mg1.build(queue_cap=64)[0], (1.25, 1.0, 2.0, 40)
    return awacs.build(16, dwell=0.04)[0], awacs.params(1.0)


def _sims(spec, params, lanes, seed=7):
    return jax.vmap(lambda r: cl.init_sim(spec, seed, r, params))(
        jnp.arange(lanes)
    )


def _drive(chunk, sims):
    while True:
        out = chunk(sims)
        sims = out[0]
        if not bool(out[1]):
            return sims


@pytest.mark.parametrize("profile", ["f32", "f64"])
@pytest.mark.parametrize(
    "name,pack",
    [("mm1", False), ("mm1", True), ("mg1", False), ("mg1", True),
     ("awacs", None)],
)
def test_chunk_is_bitwise_the_switch_form(name, pack, profile,
                                          carry_layout):
    """``make_chunk`` driven to the end against ``jax.vmap`` of the
    unbatched ``make_run``, every Sim leaf equal, under each carry
    layout (``None``: the backend's)."""
    if pack is not None:
        carry_layout(pack)
    with config.profile(profile):
        spec, params = _model(name)
        sims = jax.jit(lambda: _sims(spec, params, 8))()
        want = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
        got = _drive(jax.jit(cl.make_chunk(spec, max_steps=32)), sims)
    assert int(jnp.sum(got.n_events)) > 8 * 40
    assert int(jnp.sum(got.err != 0)) == 0
    a, b = jax.tree.leaves(want), jax.tree.leaves(got)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _sub_jaxprs(v):
    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _sub_jaxprs(x)


def _eqns(jx):
    for eq in jx.eqns:
        yield eq
        for v in eq.params.values():
            for sub in _sub_jaxprs(v):
                yield from _eqns(sub)


def _while_tree(jx):
    """The nesting of ``while`` loops: each loop as the sorted tuple of
    the loops nearest inside it (a chain loop is ``()``)."""
    out = []
    for eq in jx.eqns:
        if eq.primitive.name == "while":
            out.append(_while_tree(eq.params["body_jaxpr"].jaxpr))
            continue
        for v in eq.params.values():
            for sub in _sub_jaxprs(v):
                out.extend(_while_tree(sub))
    return tuple(sorted(out))


def _lane_selects(closed):
    return sum(
        1
        for eq in _eqns(closed.jaxpr)
        if eq.primitive.name == "select_n"
        and eq.outvars[0].aval.shape[:1] == (LANES,)
    )


CHAIN = ()  # a chain loop: no loop inside it
STEP_LOOP = (CHAIN,)  # one chain loop a step


@pytest.mark.parametrize(
    "name,chunk_tree",
    [
        ("mm1", (STEP_LOOP,)),
        # the round loop: the lanes' loop and the boundary phase's step
        ("awacs", ((CHAIN, STEP_LOOP),)),
    ],
)
def test_batched_step_traces_one_chain_per_dispatch(name, chunk_tree):
    with config.profile("f32"):
        spec, params = _model(name)
        sims = jax.eval_shape(lambda: _sims(spec, params, LANES))
        chunk = jax.make_jaxpr(cl.make_chunk(spec, max_steps=8))(sims)
        switch = jax.make_jaxpr(
            jax.vmap(cl.make_run(spec, max_steps=8))
        )(sims)
        one = jax.eval_shape(lambda: cl.init_sim(spec, 7, 0, params))
        scalar = jax.make_jaxpr(cl.make_run(spec))(one)
    assert _while_tree(chunk.jaxpr) == chunk_tree
    # the switch form traces the resume once per aliased kind (K_PROC,
    # K_TIMER): two chain loops in its step
    assert _while_tree(switch.jaxpr) == ((CHAIN, CHAIN),)
    assert 0 < 2 * _lane_selects(chunk) <= _lane_selects(switch)
    # the scalar run keeps lax.switch at its three levels: the event
    # kinds, the block table, the command handlers
    branches = {
        len(eq.params["branches"])
        for eq in _eqns(scalar.jaxpr)
        if eq.primitive.name == "cond"
    }
    n_handlers = len(cl._used_tags_for(spec, one)) + 1  # + h_invalid
    assert {2, len(spec.blocks), n_handlers} <= branches


def _lanes_loop(jx):
    """The lanes' loop: the one ``while`` whose body holds one loop, the
    chain loop, and nothing deeper."""
    loops = [
        eq for eq in _eqns(jx)
        if eq.primitive.name == "while"
        and _while_tree(eq.params["body_jaxpr"].jaxpr) == STEP_LOOP
    ]
    assert len(loops) == 1
    return loops[0]


def _while_bodies(text):
    """Each ``stablehlo.while`` body of a lowered module, as the span of
    its ``do { ... }`` region."""
    out = []
    for m in re.finditer(r"stablehlo\.while", text):
        i = text.index(" do {", m.end()) + len(" do {")
        j, depth = i, 1
        while depth:
            depth += (text[j] == "{") - (text[j] == "}")
            j += 1
        out.append((i, j))
    return out


def _own_lane_selects(text):
    """Per while body, ``(loops nested in it, lane-batched selects it
    holds outside them)``.  jax lowers ``vmap`` of a while loop with a
    select of every carried leaf in the body; those are the selects
    counted here."""
    bodies = _while_bodies(text)
    out = []
    for a, b in bodies:
        inner = [(c, d) for c, d in bodies if a < c and d <= b]
        own = text[a:b]
        for c, d in sorted(inner, reverse=True):
            own = own[:c - a] + own[d - a:]
        n = sum(
            1 for line in own.splitlines()
            if "stablehlo.select" in line and f"tensor<{LANES}x" in line
        )
        out.append((len(inner), n))
    return out


#: the lane-batched selects each lanes' loop body held outside its chain
#: loop when the lanes' loop was ``vmap`` of a while loop, per leaf or
#: packed as the carry rule read (one freeze select a carried leaf, or a
#: carried packed buffer)
VMAPPED_WHILE_SELECTS = {
    ("mm1", False, "f32"): 38, ("mm1", False, "f64"): 38,
    ("mm1", True, "f32"): 4, ("mm1", True, "f64"): 5,
    ("mg1", False, "f32"): 49, ("mg1", False, "f64"): 49,
    ("mg1", True, "f32"): 5, ("mg1", True, "f64"): 6,
    ("awacs", None, "f32"): 22, ("awacs", None, "f64"): 22,
}


@pytest.mark.parametrize("profile", ["f32", "f64"])
@pytest.mark.parametrize(
    "name,pack",
    [("mm1", False), ("mm1", True), ("mg1", False), ("mg1", True),
     ("awacs", None)],
)
def test_lanes_loop_runs_on_one_condition(name, pack, profile,
                                          carry_layout):
    """In every chunk program, whichever carry the backend's rule reads
    (``None``: the boundary chunk's, per leaf), the lanes' loop is a
    ``while`` whose cond returns one scalar read off the carried
    liveness (no per-lane cond evaluated there), and its body holds no
    lane-batched select outside the chain loop: no leaf is
    freeze-selected at any iteration."""
    if pack is not None:
        carry_layout(pack)
    with config.profile(profile):
        spec, params = _model(name)
        sims = jax.eval_shape(lambda: _sims(spec, params, LANES))
        chunk = cl.make_chunk(spec, max_steps=8)
        loop = _lanes_loop(jax.make_jaxpr(chunk)(sims).jaxpr)
        text = jax.jit(chunk).lower(sims).as_text()
    cond = loop.params["cond_jaxpr"].jaxpr
    assert cond.outvars[0].aval.shape == ()
    assert {eq.primitive.name for eq in _eqns(cond)} <= {
        "reduce_or", "lt", "and"
    }
    # the lanes' loop holds one loop, the chain loop
    own = [n for inner, n in _own_lane_selects(text) if inner == 1]
    assert own == [0]
    assert own[0] < VMAPPED_WHILE_SELECTS[(name, pack, profile)]


def _wlazy():
    """A lazy event cancel: the waiter's handle dies with the set, so a
    step with no event to pop still wakes it (the stale-handle arm)."""
    from cimba_tpu.core import api, cmd
    from cimba_tpu.core.model import Model

    m = Model("wlazy", n_flocals=1, n_ilocals=1, event_cap=16)

    @m.user_state
    def init(params):
        return {"h": jnp.asarray(-1, jnp.int32)}

    @m.handler
    def never(sim, subj, arg):
        return api.fail(sim)

    @m.block
    def c_sched(sim, p, sig):
        sim, h = api.schedule(sim, 50.0, 0, never)
        sim = api.set_user(sim, {**sim.user, "h": h})
        return sim, cmd.hold(2.0, next_pc=c_cancel.pc)

    @m.block
    def c_cancel(sim, p, sig):
        sim, _ = api.event_cancel(sim, sim.user["h"])
        return sim, cmd.exit_()

    @m.block
    def w_wait(sim, p, sig):
        return sim, cmd.wait_event(sim.user["h"], next_pc=w_done.pc)

    @m.block
    def w_done(sim, p, sig):
        return api.set_local_i(sim, p, 0, sig), cmd.exit_()

    m.process("canceller", entry=c_sched, prio=1)
    m.process("waiter", entry=w_wait, prio=0)
    return m.build(), None


def _states(name, spec, params):
    """One lane per state the lanes' loop can hold: the run's first
    steps one by one, then a finished lane, a failed one, one past its
    horizon and a dead-on-arrival pad lane (``t_stop = -inf``); each
    lane twice, for a gate on and a gate off."""
    one = cl.init_sim(spec, 11, 0, params, t_stop=jnp.inf)
    step = jax.jit(cl.make_step(spec))
    runs = [one]
    for _ in range(6):
        runs.append(step(runs[-1]))
    done = jax.jit(cl.make_run(spec))(one)
    failed = runs[3]._replace(err=jnp.asarray(1, runs[3].err.dtype))
    past = runs[4]._replace(t_stop=jnp.zeros_like(runs[4].t_stop))
    pad = one._replace(t_stop=jnp.full_like(one.t_stop, -jnp.inf))
    lanes = runs + [done, failed, past, pad]
    if name == "awacs":
        # lanes that hold a boundary dispatch (the deferring step raises
        # boundary_pending and stops there)
        dstep = jax.jit(cl.make_step(spec, defer_boundary=True))
        held = runs[6]
        for _ in range(40):
            held = dstep(held)
            if bool(held.boundary_pending):
                break
        assert bool(held.boundary_pending)
        lanes.append(held)
    return jax.tree.map(
        lambda *xs: jnp.repeat(jnp.stack(xs), 2, axis=0), *lanes
    )


def _assert_lanes_equal(a, b, lanes):
    for (path, x), y in zip(
        jax.tree_util.tree_flatten_with_path(a)[0], jax.tree.leaves(b)
    ):
        np.testing.assert_array_equal(
            np.asarray(x)[lanes], np.asarray(y)[lanes],
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("defer", [False, True])
@pytest.mark.parametrize("name", ["mm1", "mg1", "awacs", "wlazy"])
def test_gated_off_step_returns_its_lane(name, defer):
    """``make_step(batched=True)`` under ``vmap``, its gate false on the
    odd lanes: those come out bitwise as they went in, whatever state
    they hold, and the even lanes are the ungated step's."""
    with config.profile("f32"):
        spec, params = _wlazy() if name == "wlazy" else _model(name)
        sims = _states(name, spec, params)
        gate = jnp.arange(sims.clock.shape[0]) % 2 == 0
        step = jax.vmap(
            cl.make_step(spec, defer_boundary=defer, batched=True)
        )
        got = jax.jit(step)(sims, gate)
        want = jax.jit(step)(sims)
    _assert_lanes_equal(got, sims, slice(1, None, 2))
    _assert_lanes_equal(got, want, slice(0, None, 2))
    # the states differ from step to step, so the check is not vacuous
    assert not np.array_equal(np.asarray(want.n_events)[0::2],
                              np.asarray(sims.n_events)[0::2])


def test_boundary_apply_leaves_lanes_not_pending():
    """``make_boundary_apply`` gates its step by ``boundary_pending``:
    with no lane pending it returns the Sim bitwise; a pending lane is
    the full step of that lane with the flag cleared."""
    with config.profile("f32"):
        spec, params = _model("awacs")
        sims = _states("awacs", spec, params)
        apply = jax.jit(cl.make_boundary_apply(spec))
        assert not bool(jnp.any(sims.boundary_pending[:-2]))
        idle = jax.tree.map(lambda x: x[:-2], sims)
        _assert_lanes_equal(apply(idle), idle, slice(None))
        got = apply(sims)
        cleared = sims._replace(
            boundary_pending=jnp.zeros_like(sims.boundary_pending)
        )
        want = jax.jit(jax.vmap(cl.make_step(spec, defer_boundary=False)))(
            cleared
        )
    _assert_lanes_equal(got, sims, slice(None, -2))
    _assert_lanes_equal(got, want, slice(-2, None))
    assert not bool(jnp.any(got.boundary_pending))
