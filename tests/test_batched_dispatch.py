"""The batched step's merge-free dispatch (``loop._vswitch`` with
``batched=True``): every function that vmaps the step (``make_chunk`` and
the boundary phase among them) dispatches its three levels — event kind,
block, command handler — without ``lax.switch``'s per-leaf ``select_n``
merges.  Its lanes are bitwise those of ``jax.vmap`` over the scalar
``make_run``, which keeps ``lax.switch``; its program holds one chain
loop per dispatch level and a fraction of the selects."""

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
