"""Scan-over-rows process-table dispatch (docs/25_compile_wall.md).

Contracts pinned here:

* **bitwise parity**: the scan arm's AWACS chunk equals the dense
  arm's bitwise — every carry leaf plus the liveness flag — under both
  dtype profiles, at a height (P=17, block=8) where the blocked
  dispatch provably engages;
* **default off, character-identical**: with both tri-states at their
  ambient defaults the traced chunk jaxpr is the same STRING as the
  explicit-dense one — the knob can't perturb today's programs;
* **small-P structural inertness**: scan ON at a height at or below
  the block traces the identical jaxpr string too (engagement is
  strictly height > block, so every small model rides the baseline
  program even with the env knob set fleet-wide);
* **knob liveness**: at a height above the block the scan arm's jaxpr
  DIFFERS and carries ``dynamic_slice`` — the gate registry's
  ``on_differs=False`` claim is about sweep-model height, not a dead
  knob;
* **O(1)-in-P program size**: scan-on equation counts are FLAT across
  engaged heights (trace-only probe), and the at-scale P=1001 count
  stays within 1.2x of the P=32 one;
* **primitive-level parity**: blocked ``dget/dset/dget2/dset2/dadd``
  match their dense answers under ``vmap`` for float/int/bool leaves
  (the lanelast + bool32 dynamic-slice rules), and ``dexchange`` (the
  queue ring's read-or-write) matches plain indexing in both arms;
* **registration**: both env knobs live in ``config.ENV_KNOBS`` and
  the ``table_scan`` gate rides the check/gates.py identity sweep.

The at-scale compile arm (P=1001, both arms compiled and run) is
``slow`` — tools/ci.sh territory; tier-1 keeps to tiny heights.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cimba_tpu import config
from cimba_tpu.check import gates as cg
from cimba_tpu.core import dyn
from cimba_tpu.core import loop as cl
from cimba_tpu.models import awacs
from cimba_tpu.obs import program_size as ps


@contextlib.contextmanager
def _scan(scan, block=None):
    prev = config.TABLE_SCAN, config.TABLE_SCAN_BLOCK
    try:
        config.TABLE_SCAN, config.TABLE_SCAN_BLOCK = scan, block
        yield
    finally:
        config.TABLE_SCAN, config.TABLE_SCAN_BLOCK = prev


def _chunk_leaves(spec, *, lanes=4, max_steps=64, seed=2026):
    sims = jax.vmap(
        lambda r: cl.init_sim(spec, seed, r, (2.0,))
    )(jnp.arange(lanes))
    # every output: the Sim, the liveness flag, and a boundary model's
    # counters
    return jax.tree.leaves(
        jax.jit(cl.make_chunk(spec, max_steps=max_steps))(sims)
    )


def _chunk_jaxpr_text(spec, *, lanes=2, max_steps=32, seed=2026):
    sims = jax.eval_shape(
        jax.vmap(lambda r: cl.init_sim(spec, seed, r, (2.0,))),
        jnp.arange(lanes),
    )
    text = str(
        jax.make_jaxpr(cl.make_chunk(spec, max_steps=max_steps))(sims)
    )
    # custom_jvp thunk reprs carry per-trace function addresses; the
    # structural claim is about everything else
    return re.sub(r"0x[0-9a-f]+", "0x", text)


@pytest.mark.parametrize(
    "profile",
    [
        "f64",
        # displaced for the qos suite: the f64 twin stays tier-1 and
        # ci.sh "compile wall smoke" runs scan-vs-dense bitwise every pass
        pytest.param("f32", marks=pytest.mark.slow),
    ],
)
def test_awacs_bitwise_parity(profile):
    spec, _ = awacs.build(8)
    with config.profile(profile):
        with _scan(False):
            dense = _chunk_leaves(spec, lanes=2, max_steps=32)
        with _scan(True, 4):
            scan = _chunk_leaves(spec, lanes=2, max_steps=32)
    assert len(dense) == len(scan)
    for a, b in zip(dense, scan):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow  # displaced for the qos suite: ci.sh static analysis sweeps the table_scan gate (off==baseline + block-1024 inert arm) every pass
def test_jaxpr_structure():
    spec, _ = awacs.build(16)
    ambient = _chunk_jaxpr_text(spec)  # tri-states at None
    with _scan(False):
        dense = _chunk_jaxpr_text(spec)
    with _scan(True):
        inert = _chunk_jaxpr_text(spec)  # P=17 <= default block 128
    with _scan(True, 8):
        live = _chunk_jaxpr_text(spec)  # P=17 > block 8: engaged
    # default off: character-identical to explicit dense
    assert ambient == dense
    # small-P structural inertness: engagement is strictly
    # height > block, so scan ON at small P traces the same program
    assert inert == dense
    # knob liveness above the block: the program must actually change
    # (the gate registry's on_differs=False is a height claim, not a
    # dead knob)
    assert live != dense


@pytest.mark.slow  # ci.sh "compile wall smoke" pins flat engaged eqn counts + JXL004 firing every pass
def test_eqn_count_flat_and_sublinear_in_p():
    # scan-on equation counts are FLAT across engaged heights...
    sizes = {}
    with _scan(True, 8):
        for n_t in (16, 48):
            spec, _ = awacs.build(n_t)
            sizes[n_t] = ps.chunk_program_size(
                spec, (2.0,), lanes=2, lower=False
            ).eqns
    assert sizes[16] == sizes[48], sizes
    # ...and the at-scale P=1001 count (default block, engaged) stays
    # within 1.2x of the P=32 one (inert) — the headline sublinearity
    # pin, trace-only so it costs fractions of a second per arm
    with _scan(True):
        small, _ = awacs.build(31)
        big, _ = awacs.build(1000)
        e_small = ps.chunk_program_size(
            small, (2.0,), lanes=2, lower=False).eqns
        e_big = ps.chunk_program_size(
            big, (2.0,), lanes=2, lower=False).eqns
    assert e_big <= 1.2 * e_small, (e_small, e_big)


def _dense_scan_pair(fn):
    with _scan(False):
        dense = fn()
    with _scan(True, 8):
        blocked = fn()
    return dense, blocked


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bool_])
def test_primitive_parity_vmap(dtype):
    # blocked dget/dset under vmap (the lanelast dynamic_slice batching
    # rules + the bool32 structural allowlist) vs the dense answers
    n, lanes = 33, 4
    key = jax.random.PRNGKey(0)
    base = jax.random.normal(key, (n, 3))
    arr = (base > 0) if dtype == jnp.bool_ else base.astype(dtype)
    idx = jnp.array([0, 7, 31, 32], jnp.int32)
    val = jnp.ones((3,), arr.dtype)
    pred = jnp.array([True, False, True, True])

    def run():
        get = jax.jit(jax.vmap(lambda i: dyn.dget(arr, i)))(idx)
        setr = jax.jit(
            jax.vmap(lambda i, p: dyn.dset(arr, i, val, p))
        )(idx, pred)
        return get, setr

    (g0, s0), (g1, s1) = _dense_scan_pair(run)
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_primitive_parity_2d_and_add():
    n0, n1 = 5, 40
    arr = jax.random.normal(jax.random.PRNGKey(1), (n0, n1))
    i0 = jnp.array([0, 4, 2], jnp.int32)
    i1 = jnp.array([0, 39, 17], jnp.int32)
    pred = jnp.array([True, True, False])

    def run():
        get2 = jax.jit(jax.vmap(lambda a, b: dyn.dget2(arr, a, b)))(i0, i1)
        set2 = jax.jit(
            jax.vmap(lambda a, b, p: dyn.dset2(arr, a, b, 7.5, p))
        )(i0, i1, pred)
        add1 = jax.jit(
            jax.vmap(lambda b, p: dyn.dadd(arr[0], b, 2.0, p))
        )(i1, pred)
        return get2, set2, add1

    d, s = _dense_scan_pair(run)
    for a, b in zip(d, s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_registration():
    for name in ("CIMBA_TABLE_SCAN", "CIMBA_TABLE_SCAN_BLOCK"):
        assert name in config.ENV_KNOBS, name
    gate = next(g for g in cg.GATES if g.name == "table_scan")
    assert set(gate.env) == {"CIMBA_TABLE_SCAN", "CIMBA_TABLE_SCAN_BLOCK"}
    assert gate.on_differs is False
    # the arm context binds and restores the tri-states
    before = config.TABLE_SCAN, config.TABLE_SCAN_BLOCK
    with cg._table_scan_state(True, 1024):
        assert config.TABLE_SCAN is True
        assert config.TABLE_SCAN_BLOCK == 1024
    assert (config.TABLE_SCAN, config.TABLE_SCAN_BLOCK) == before
    # the tri-state override beats the env default
    with _scan(True, 64):
        assert config.table_scan_enabled() is True
        assert config.table_scan_block() == 64
    with _scan(None):
        assert config.table_scan_enabled() is False


def test_schedule_knob_roundtrip_and_pruning():
    from cimba_tpu.tune.space import Schedule

    s = Schedule(table_scan=True, table_block=64)
    assert Schedule.from_json(s.to_json()) == s
    # block is dead weight when the scan resolves off
    c = Schedule(table_scan=False, table_block=64).canonical()
    assert c.table_block is None
    # explicit-equals-ambient collapses to the default arm
    assert Schedule(table_scan=False).canonical() == Schedule()


@pytest.mark.slow
def test_at_scale_compile_and_parity():
    # the P=1001 compile arm: both arms compile on CPU XLA and agree
    # bitwise (minutes-scale territory rides tools/ci.sh, not tier-1)
    spec, _ = awacs.build(1000)
    with _scan(False):
        dense = _chunk_leaves(spec, lanes=2, max_steps=32)
    with _scan(True):
        scan = _chunk_leaves(spec, lanes=2, max_steps=32)
    for a, b in zip(dense, scan):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n", [24, 40], ids=["narrow", "wide"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bool_])
def test_dexchange_matches_indexing(dtype, n):
    """``dyn.dexchange`` is ``(arr[j], arr.at[j].set(where(do_write, v,
    arr[j])))`` gated by ``pred``, in the dense arm and the blocked one:
    a gated-off lane or an out-of-range index reads zero and writes
    nothing.  ``n`` 24 keeps the gate in the mask, 40 folds it into the
    index."""
    base = jax.random.normal(jax.random.PRNGKey(2), (n,))
    arr = (base > 0) if dtype == jnp.bool_ else (base * 100).astype(dtype)
    v = jnp.asarray(True if dtype == jnp.bool_ else 7, dtype)
    j = jnp.array([0, 5, n - 1, 9, n, -1, 3, 17], jnp.int32)
    write = jnp.array([True, False, True, True, True, True, False, True])
    pred = jnp.array([True, True, True, False, True, True, True, True])

    def run():
        return jax.jit(jax.vmap(
            lambda i, w, p: dyn.dexchange(arr, i, v, w, p)))(j, write, pred)

    a = np.asarray(arr)
    for item, out in _dense_scan_pair(run):
        for k in range(len(j)):
            jk = int(j[k])
            hit = bool(pred[k]) and 0 <= jk < n
            want = a.copy()
            if hit and bool(write[k]):
                want[jk] = np.asarray(v)
            assert item[k] == (a[jk] if hit else np.zeros((), a.dtype)), k
            np.testing.assert_array_equal(np.asarray(out[k]), want)
