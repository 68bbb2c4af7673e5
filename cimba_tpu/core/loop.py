"""The event loop: one replication's dispatcher, jit-compiled and vmapped.

Reference parity: ``cmb_event_queue_execute`` (`src/cmb_event.c:296-335`)
— pop next event, advance the clock, run the action, repeat — where the
action context-switches into a coroutine until it yields
(`src/cmb_process.c:329-375`).

TPU rendition (the "fiber scheduler lowered to an XLA while-loop" of the
north star): ``make_run`` builds ``lax.while_loop(cond, step, sim)`` where
``step`` pops from the flat event set, advances the batched clock, and
dispatches through ``lax.switch``:

* kind K_PROC / K_TIMER: resume the subject process — an inner bounded
  while_loop runs its current block (``lax.switch`` over the model's block
  table) and applies the returned command, chaining while commands complete
  without yielding.  This is exactly a coroutine running until it waits,
  with (pc, locals) rows instead of a C stack.
* kinds >= 2 = user handlers (parity: arbitrary (action, subject, object)
  events).

Signal delivery contract: a yielding command's continuation block receives
the wakeup signal as its ``sig`` argument — SUCCESS when the operation
completed, PREEMPTED/INTERRUPTED/STOPPED/TIMEOUT/app-defined when it was
aborted.  This is the array-world image of the reference's
``sig = cmb_resource_acquire(...)`` return value.  Blocked commands pend
on guards and are *re-attempted* on a SUCCESS wakeup (the reference's
loop-around-guard-wait fairness protocol, `src/cmb_resource.c:202-233`);
a non-SUCCESS wakeup aborts the pending operation instead (guard entry
removed), like ``cmi_process_cancel_awaiteds``.

Failure containment (parity: §3.5 error recovery, `src/cimba.c:185-209`):
any structural failure — event/guard overflow, non-finite time, a block
chain that never yields, releasing an unheld resource — sets ``sim.err``
and freezes the replication; the experiment runner counts and masks it,
and the other replications in the batch are unaffected.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from cimba_tpu import config
from cimba_tpu.config import INDEX_DTYPE
from cimba_tpu.config import argmax32 as _argmax32
from cimba_tpu.core import dyn
from cimba_tpu.core import eventset as ev
from cimba_tpu.core import guard as gd
from cimba_tpu.core import process as pr
from cimba_tpu.core.model import ModelSpec
from cimba_tpu.obs import metrics as obs_metrics
from cimba_tpu.obs import trace as obs_trace
from cimba_tpu.random import bits as rb
from cimba_tpu.stats import timeseries as ts

_I = INDEX_DTYPE
_R = config.REAL
_T = config.TIME

K_PROC = 0   # resume process `subj` with signal `arg`
K_TIMER = 1  # same dispatch; separate kind so timers_clear can pattern-cancel
N_KINDS = 2  # user handler kinds start here

# chain-safety bound: a process may not execute more than this many blocks
# without yielding (a JUMP cycle would otherwise hang the whole batch)
MAX_CHAIN = 1024

# error codes (sim.err)
ERR_NONE = 0
ERR_EVENT_OVERFLOW = 1
ERR_GUARD_OVERFLOW = 2
ERR_CHAIN_RUNAWAY = 3
ERR_USER = 4
ERR_BAD_RELEASE = 5
ERR_BOUNDARY = 6   # boundary block entered mid-chain inside the kernel


class Queues(NamedTuple):
    #: [NQ * QCAP] f64 ring buffers, queue q's slot k at q * QCAP + k:
    #: rank-1 so that, vmapped, the lanes fill the TPU tile's 8 sublanes
    #: ([lanes, NQ, QCAP] with small NQ tiles (NQ, 128): docs/11)
    items: jnp.ndarray
    head: jnp.ndarray   # [NQ] i32
    size: jnp.ndarray   # [NQ] i32
    acc: ts.StepAccum   # leaves [NQ]: queue-length recording


class Resources(NamedTuple):
    holder: jnp.ndarray  # [NR] i32, -1 = free
    acc: ts.StepAccum    # leaves [NR]: utilization recording


class Pools(NamedTuple):
    level: jnp.ndarray   # [NP] f64 available units
    held: jnp.ndarray    # [NP, P] f64 per-process held amounts
    held_seq: jnp.ndarray  # [NP, P] i32 grab order (LIFO victim selection)
    next_seq: jnp.ndarray  # [NP] i32
    acc: ts.StepAccum    # leaves [NP]: in-use recording


class Buffers(NamedTuple):
    level: jnp.ndarray   # [NB] f64 stored amount
    acc: ts.StepAccum    # leaves [NB]: level recording


class PQueues(NamedTuple):
    items: jnp.ndarray   # [NPQ, CAP] f64 payloads
    prio: jnp.ndarray    # [NPQ, CAP] f64 item priorities (higher first)
    seq: jnp.ndarray     # [NPQ, CAP] i32 insertion order (FIFO tiebreak)
    live: jnp.ndarray    # [NPQ, CAP] bool slot occupancy
    next_seq: jnp.ndarray  # [NPQ] i32
    acc: ts.StepAccum    # leaves [NPQ]: length recording


class Sim(NamedTuple):
    """One replication's full state."""

    clock: jnp.ndarray
    rep: jnp.ndarray       # i32 replication index (logger trial context)
    rng: rb.RandomState
    events: ev.EventSet
    wakes: ev.Wakes        # dense per-process resumes (see eventset.Wakes)
    procs: pr.Procs
    guards: gd.Guards
    queues: Queues
    resources: Resources
    pools: Pools
    buffers: Buffers
    pqueues: PQueues
    user: Any
    done: jnp.ndarray      # bool, set by model code (api.stop)
    err: jnp.ndarray       # i32, ERR_* (0 = healthy)
    n_events: jnp.ndarray  # i64, dispatched events (bench metric)
    #: this lane's next dispatch targets a boundary block: a deferring
    #: step (the kernel's, the XLA boundary chunk's) holds the lane for
    #: the boundary phase (make_boundary_apply)
    boundary_pending: jnp.ndarray
    #: flight recorder ring (obs.trace.TraceRing) or None — None prunes
    #: the leaves from the pytree, so a disabled recorder costs zero ops
    #: (the logger's NLOGINFO story, as state instead of lines)
    trace: Any = None
    #: metrics registry (obs.metrics.Metrics) or None, same contract
    metrics: Any = None
    #: per-lane horizon (TIME scalar) or None — None prunes the leaf so
    #: the historical pytree (and static-``t_end`` programs) are
    #: untouched.  When carried, :func:`make_cond` reads it INSTEAD of
    #: its static ``t_end``: the lane stops dispatching once its next
    #: event would pass ``t_stop``, exactly as a program compiled with
    #: that static horizon would.  This is what lets heterogeneous
    #: horizons share ONE compiled chunk program (a short lane goes
    #: dead early; ``-inf`` makes a lane dead-on-arrival — the wave
    #: padding mask, docs/14_wave_packing.md)
    t_stop: Any = None


def _tree_select(pred, a, b):
    # leaves untouched by either branch are the *same object* (branches are
    # built with _replace from a shared base) — pass them through instead of
    # emitting a select, so an event that modifies three arrays doesn't
    # rewrite every leaf of the Sim (full-state HBM traffic per event was
    # the dominant dispatch cost before this)
    return jax.tree.map(
        lambda x, y: x if x is y else dyn.bwhere(pred, x, y), a, b
    )


def _batched(tree, n):
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + jnp.shape(x)), tree
    )


def init_sim(spec: ModelSpec, seed, replication, params=None, t0=0.0,
             t_stop=None) -> Sim:
    """Build one replication's initial state and schedule process starts
    (parity: the trial-init sequence `benchmark/MM1_multi.c:91-124`).

    ``seed`` may be a python int OR a traced u64 scalar: the stream key
    is ``fmix64(seed + c*replication)`` — pure integer arithmetic, so a
    per-lane seed column produces bit-identical streams to the
    historical static-seed trace (the Tier-A packing contract,
    docs/14_wave_packing.md).  ``t_stop`` (optional, TIME scalar) gives
    the lane a per-lane horizon — see :class:`Sim`."""
    nq = max(len(spec.queues), 1)
    nr = max(len(spec.resources), 1)
    np_ = max(len(spec.pools), 1)
    nb = max(len(spec.buffers), 1)
    npq = max(len(spec.pqueues), 1)
    events = ev.create(spec.event_cap)
    procs = pr.create(
        spec.proc_entry, spec.proc_prio, spec.n_flocals, spec.n_ilocals
    )
    # process starts are dense wakes at t0, consuming seqs 0..n_started-1
    # in pid order exactly as the former per-start ev.schedule calls did
    # (golden-stable: all-started models get seq=arange(P)).  Spawn-pool
    # rows (proc_start False) stay CREATED with no wake until api.spawn.
    import numpy as _np

    started_np = _np.asarray(spec.proc_start, bool)
    started = jnp.asarray(started_np)
    seq0 = _np.cumsum(started_np) - started_np  # rank among started
    wakes = ev.wakes_create(spec.n_procs)._replace(
        time=jnp.where(started, jnp.asarray(t0, config.TIME), ev.NEVER),
        sig=jnp.full((spec.n_procs,), pr.SUCCESS, _I),
        seq=jnp.asarray(seq0, _I),
    )
    events = events._replace(
        next_seq=jnp.asarray(int(started_np.sum()), _I)
    )
    procs = procs._replace(
        status=jnp.where(
            started, jnp.asarray(pr.RUNNING, _I), jnp.asarray(pr.CREATED, _I)
        ),
    )
    user = spec.user_init(params) if spec.user_init else jnp.zeros(())
    t0 = jnp.asarray(t0, _T)
    pool_caps = jnp.asarray([p.capacity for p in spec.pools] or [0.0], _R)
    buf_init = jnp.asarray([b.initial for b in spec.buffers] or [0.0], _R)
    return Sim(
        clock=t0,
        rep=jnp.asarray(replication, _I),
        rng=rb.initialize(seed, replication),
        events=events,
        wakes=wakes,
        procs=procs,
        guards=gd.create(spec.n_guards),
        # absent components carry no state at all (None prunes the
        # pytree — the while_loop body then never touches those leaves),
        # and recording accumulators exist only if some member records
        queues=Queues(
            items=jnp.zeros((nq * spec.queue_cap_max,), _R),
            head=jnp.zeros((nq,), _I),
            size=jnp.zeros((nq,), _I),
            acc=_batched(ts.step_create(t0, 0.0), nq)
            if any(q.record for q in spec.queues)
            else None,
        )
        if spec.queues
        else None,
        resources=Resources(
            holder=jnp.full((nr,), -1, _I),
            acc=_batched(ts.step_create(t0, 0.0), nr)
            if any(r.record for r in spec.resources)
            else None,
        )
        if spec.resources
        else None,
        pools=Pools(
            level=pool_caps,
            held=jnp.zeros((np_, spec.n_procs), _R),
            held_seq=jnp.zeros((np_, spec.n_procs), _I),
            next_seq=jnp.zeros((np_,), _I),
            acc=_batched(ts.step_create(t0, 0.0), np_)
            if any(pl.record for pl in spec.pools)
            else None,
        )
        if spec.pools
        else None,
        buffers=Buffers(
            level=buf_init,
            # the recorded signal starts at each buffer's *initial* level,
            # not 0 — otherwise time-average levels are biased low
            acc=_batched(ts.step_create(t0, 0.0), nb)._replace(
                last_v=buf_init
            )
            if any(b.record for b in spec.buffers)
            else None,
        )
        if spec.buffers
        else None,
        pqueues=PQueues(
            items=jnp.zeros((npq, spec.pqueue_cap_max), _R),
            prio=jnp.zeros((npq, spec.pqueue_cap_max), _R),
            seq=jnp.zeros((npq, spec.pqueue_cap_max), _I),
            live=jnp.zeros((npq, spec.pqueue_cap_max), jnp.bool_),
            next_seq=jnp.zeros((npq,), _I),
            acc=_batched(ts.step_create(t0, 0.0), npq)
            if any(q.record for q in spec.pqueues)
            else None,
        )
        if spec.pqueues
        else None,
        user=user,
        done=jnp.asarray(False),
        err=jnp.where(
            events.overflow,
            jnp.asarray(ERR_EVENT_OVERFLOW, _I),
            jnp.zeros((), _I),
        ),
        n_events=jnp.zeros((), config.COUNT),
        boundary_pending=jnp.asarray(False),
        # observability state is trace-time gated like the logger mask:
        # disabled (the default) carries no arrays at all
        trace=obs_trace.create() if obs_trace.enabled() else None,
        metrics=obs_metrics.create(
            N_KINDS + len(spec.user_handlers), len(spec.queues)
        )
        if obs_metrics.enabled()
        else None,
        t_stop=None if t_stop is None else jnp.asarray(t_stop, _T),
    )


# --- micro-ops on Sim --------------------------------------------------------


class _ConstTable:
    """Tiny static per-component table indexed by a traced id.

    Emits a select chain over scalar literals instead of materializing an
    array constant: Pallas kernels cannot capture array constants, and for
    the 1–8 entries these tables have, a literal select chain is also
    cheaper than a dynamic-slice gather on the VPU.  Behaves like the
    1-D array it replaces for ``tab[idx]`` with an int or traced index.
    """

    def __init__(self, values, dtype):
        self._values = list(values)
        self._dtype = dtype

    def __len__(self):
        return len(self._values)

    def __getitem__(self, idx):
        vals = self._values
        if isinstance(idx, int):
            return jnp.asarray(vals[idx], self._dtype)
        out = jnp.asarray(vals[0], self._dtype)
        for j in range(1, len(vals)):
            out = jnp.where(
                idx == j, jnp.asarray(vals[j], self._dtype), out
            )
        return out


_kfori = dyn.kfori  # scan-free counted loop (see dyn.kfori docstring)


def _bounded_while(cond, body, init, bound: int):
    """``lax.while_loop`` with a trip-count backstop in kernel mode.

    In kernel mode the emitted while keeps the REAL data-dependent
    condition (per-lane at trace time); lanelast's batched-cond rule
    lowers it as a scalar any-lane-live condition with per-lane freeze
    masking — so the loop exits after max-over-lanes iterations instead
    of always running ``bound`` masked steps (the chain loop's bound is
    ``spec.max_chain``=16 by default while real chains are 2-3 blocks:
    measured ~5x of pure hot-loop waste before this).  ``bound`` remains
    as the runaway backstop the callers' error codes check."""
    if not config.KERNEL_MODE:
        return lax.while_loop(cond, body, init)

    def wcond(kc):
        k, c = kc
        return cond(c) & (k < bound)

    def wbody(kc):
        k, c = kc
        return k + jnp.int32(1), body(c)

    return lax.while_loop(wcond, wbody, (jnp.int32(0), init))[1]


def _and(pred, gate):
    """AND a handler's write predicate with its branch gate; ``True``
    short-circuits so ungated paths trace zero extra ops."""
    if gate is True:
        return pred
    if pred is True:
        return gate
    return pred & gate


def _gated(fn):
    """Mark a branch as SELF-GATED for :func:`_vswitch`: it accepts a
    ``gate`` keyword and guarantees its output Sim is identical to its
    input Sim wherever ``gate`` is false (every write pred-gated).
    _vswitch then composes gated branches sequentially under their
    exclusive selection predicates instead of select-merging their
    outputs — zero merge ops for the Sim."""
    fn.self_gated = True
    return fn


def _vswitch(idx, branches, *args, batched: bool = False):
    """``lax.switch`` for the vmapped interpreter.  Under vmap a
    ``lax.switch`` with a batched index executes every traced branch
    anyway and then ``select_n``-merges EVERY output leaf — the whole
    per-lane Sim, once per dispatch level (event kind, block, command
    handler) — and Mosaic rejects the N-ary select outright (only
    2-way selects).  Two strategies replace the merge:

    * branches marked with :func:`_gated` (the internal command
      handlers) are composed SEQUENTIALLY, each fully pred-gated by its
      exclusive selection predicate — the inactive handlers' writes are
      runtime no-ops, so the chain needs no Sim merge at all.  A later
      branch's reads can see an earlier branch's traced writes, but
      whenever the later branch is the selected one those writes were
      gated off — the composition is exact.
    * unmarked branches (user blocks) are evaluated against the base
      args and select-merged per leaf, folding only over the branches
      that actually changed each leaf (identity test).  A table is
      either all-gated or all-ungated; mixing raises.

    Aliased branches (the same callable at several indices) are traced
    once.  The branch predicates are exclusive and exhaustive (every
    caller clips or LUT-maps ``idx`` into range).

    This merge-free form is taken wherever the trace is batched: in
    the kernel trace (``config.KERNEL_MODE``) and when the function
    that builds and vmaps the step says so (``batched=True``, threaded
    from :func:`make_step`).  A batched index cannot be detected here —
    jax traces a while body with per-lane avals before batching it — so
    the caller has to say it.  An unbatched trace keeps the real
    ``lax.switch``: a scalar run then executes only the selected branch
    (side effects like debug callbacks fire once, and scalar oracle runs
    stay cheap; gated handlers see gate=True there).  Both forms give
    bitwise the same lanes (tests/test_batched_dispatch.py).
    """
    if not (batched or config.KERNEL_MODE):
        return lax.switch(idx, branches, *args)
    # dedupe identical branch callables: the dispatch table aliases the
    # same handler at several indices (K_PROC and K_TIMER both run
    # on_proc), and tracing it per alias would duplicate the entire chain
    # loop in the hot kernel (measured: 2x the step body for any model
    # with no user handlers)
    uniq: list = []
    index_sets: list = []
    for j, b in enumerate(branches):
        for u, (ub, idxs) in enumerate(zip(uniq, index_sets)):
            if ub is b:
                idxs.append(j)
                break
        else:
            uniq.append(b)
            index_sets.append([j])
    idx = jnp.asarray(idx, _I)
    if len(uniq) == 1:
        # exhaustive single branch: always selected (gate stays True)
        return uniq[0](*args)
    sels = []
    for idxs in index_sets:
        s = idx == idxs[0]
        for j in idxs[1:]:
            s = s | (idx == j)
        sels.append(s)

    is_gated = [getattr(b, "self_gated", False) for b in uniq]
    base_sim = args[0]
    cur = base_sim
    outs = []
    for u, b in enumerate(uniq):
        if is_gated[u]:
            o = b(cur, *args[1:], gate=sels[u])
            cur = o[0] if isinstance(o, tuple) else o
            outs.append(o)
        else:
            outs.append(b(*args))

    flat0, treedef = jax.tree.flatten(outs[0])
    flats = [flat0]
    for u, o in enumerate(outs[1:], 1):
        fl, td = jax.tree.flatten(o)
        if td != treedef:
            raise TypeError(
                f"_vswitch branch {u} returned a different pytree "
                f"structure than branch 0:\n{td}\nvs\n{treedef}"
            )
        flats.append(fl)

    if any(is_gated):
        # gated tables must be all-gated: the Sim result is the chain's
        # output and needs NO merge; non-Sim positions (yielded flags)
        # still select over every branch below.  (A mixed gated/ungated
        # table has no call site — fail loudly rather than run an
        # unexercised merge semantics.)
        if not all(is_gated):
            raise TypeError(
                "_vswitch: mixed gated/ungated branch table is not "
                "supported — gate all branches or none"
            )
        flat_cur = jax.tree.flatten(cur)[0]
        n_sim = len(flat_cur)
    else:
        flat_cur = []
        n_sim = 0

    merged = []
    for pos, leaf_vals in enumerate(zip(*flats)):
        if pos < n_sim:
            merged.append(flat_cur[pos])
            continue
        groups: list = []  # (value, [branch indices]) by identity
        for u, v in enumerate(leaf_vals):
            for gv, gus in groups:
                if gv is v:
                    gus.append(u)
                    break
            else:
                groups.append((v, [u]))
        if len(groups) == 1:
            merged.append(leaf_vals[0])
            continue
        # the value shared by the most branches is the select default
        # (fewest selects); ties break on first occurrence for a stable
        # trace
        groups.sort(key=lambda g: -len(g[1]))
        res = groups[0][0]
        for gv, gus in groups[1:]:
            s = sels[gus[0]]
            for u in gus[1:]:
                s = s | sels[u]
            res = dyn.bwhere(s, gv, res)
        merged.append(res)
    return jax.tree.unflatten(treedef, merged)


def _check_gated_noop(name: str, h, sim: Sim, tag: int) -> None:
    """Eagerly run one self-gated handler with ``gate=False`` on a
    CONCRETE Sim and assert the output is bitwise identical — the
    invariant :func:`_vswitch`'s zero-merge sequential composition rests
    on.  A handler with one ungated write corrupts *other lanes'* state
    only under vmap, far from the cause; this fails loudly at the
    handler, by name and leaf path.

    A Sim with a leading lane axis is checked as the batched step runs
    the handler: under ``jax.vmap``, its gate true on the even lanes and
    false on the odd ones, which must come out bitwise untouched."""
    import numpy as np

    cmd = pr.Command(
        jnp.asarray(tag, _I),
        jnp.asarray(0.5, _R),
        jnp.asarray(0.25, _R),
        jnp.asarray(0.125, _R),
        jnp.zeros((), _I),
        jnp.zeros((), _I),
    )

    def run(sim, gate):
        out = h(sim, jnp.zeros((), _I), cmd, jnp.asarray(False), gate=gate)
        return out[0] if isinstance(out, tuple) else out

    lanes = jnp.shape(sim.clock)[:1]
    if lanes:
        sim2 = jax.vmap(run)(sim, jnp.arange(lanes[0]) % 2 == 0)

        def off(x):
            return np.asarray(x)[1::2]
    else:
        sim2 = run(sim, jnp.zeros((), jnp.bool_))
        off = np.asarray
    flat, _ = jax.tree_util.tree_flatten_with_path(sim)
    flat2 = jax.tree.leaves(sim2)
    for (path, a), b in zip(flat, flat2):
        a, b = off(a), off(b)
        same = (
            np.array_equal(a, b, equal_nan=True)
            if np.issubdtype(a.dtype, np.inexact)
            else np.array_equal(a, b)
        )
        if not same:
            raise AssertionError(
                f"gated handler {name!r} (tag {tag}) is not a no-op "
                f"under gate=False: Sim leaf "
                f"{jax.tree_util.keystr(path)} changed — every write in "
                "a _gated handler must be pred-gated by its gate"
            )


def validate_gated_handlers(spec: ModelSpec, sim: Sim) -> None:
    """Debug-tier structural check over the full handler table: every
    self-gated command handler must leave a concrete Sim bitwise
    untouched under ``gate=False``.  Traced nowhere — runs eagerly,
    so the invariant the fuzz battery only samples is enforced
    structurally.  Every batched step rests on it: the kernel's and
    the vmapped XLA step's (``make_step(batched=True)``, which every
    chunk program runs).  Given one per-lane Sim it checks the handlers
    as traced per lane (pallas_run wires that behind the dbc debug
    tier, once per kernel build); given a batched Sim (two lanes or
    more), as vmap batches them, gate false on every other lane
    (tests/test_gated_invariant.py runs it for the benchmark's models).

    Checked once per DISPATCH SLOT, not per unique handler: an aliased
    handler (h_queue at put/get/put_hold/get_hold, h_buffer at both
    verbs) branches internally on cmd.tag, and an ungated write on the
    get side would be invisible under the put tag.  Eager and
    once-per-build, so the aliased repeats cost nothing that matters."""
    apply = _make_apply(spec, None)
    for tag, h in apply.handler_items:
        if not getattr(h, "self_gated", False):
            continue
        _check_gated_noop(
            getattr(h, "__name__", repr(h)), h, sim, tag
        )


def _set_err(sim: Sim, pred, code) -> Sim:
    return sim._replace(
        err=jnp.where((sim.err == 0) & pred, jnp.asarray(code, _I), sim.err)
    )


def _schedule_if(sim: Sim, pred, t, prio, kind, subj, arg) -> Sim:
    es2, _ = ev.schedule(sim.events, t, prio, kind, subj, arg)
    es2 = _tree_select(pred, es2, sim.events)
    sim = sim._replace(events=es2)
    return _set_err(sim, es2.overflow, ERR_EVENT_OVERFLOW)


def _schedule_wake(sim: Sim, pred, p, sig, t=None) -> Sim:
    """Arm a resume for process p at ``t`` (default: now).  Dense wake
    slot — at most one resume per process exists, every caller follows
    the cancel-before-rearm discipline, so the overwrite is safe.  A
    non-finite target time fails the replication (the general table's
    overflow-as-failure parity)."""
    t = sim.clock if t is None else t
    wk2, ok = ev.wake_set(sim.wakes, p, t, sig, sim.events.next_seq, pred)
    sim = sim._replace(
        wakes=wk2,
        events=sim.events._replace(
            next_seq=sim.events.next_seq + ok.astype(_I)
        ),
    )
    armed = pred if pred is not True else jnp.asarray(True)
    return _set_err(sim, armed & ~ok, ERR_EVENT_OVERFLOW)


def _guard_signal(sim: Sim, gid, pred=True, spec=None) -> Sim:
    """Wake the best waiter (if any): schedule its retry at the current
    time with its process priority (parity: cmb_resourceguard_signal
    scheduling wakeup events rather than switching directly).  ``pred``
    gates the whole signal (lets handlers run straight-line with masked
    writes instead of a whole-Sim branch select).

    Observer forwarding (parity: cmb_resourceguard_register,
    `src/cmb_resourceguard.c:313-330`): when ``spec`` is supplied and a
    condition declares ``observes`` covering this guard, the signal also
    re-evaluates that condition's waiters — so a release/put/rollback
    satisfying a predicate wakes its cond_wait-ers without the model
    signalling manually.  Observer-free models trace zero extra ops."""
    pid, found = gd.best_waiter(
        sim.procs.pend_guard, sim.procs.pend_seq, sim.procs.prio, gid
    )
    woke = found if pred is True else (found & pred)
    p = jnp.maximum(pid, 0)
    # dequeue = clearing membership; the pend_* command fields stay for
    # the woken process's retry (retry-keeps-seq reads pend_seq)
    sim = sim._replace(
        procs=sim.procs._replace(
            pend_guard=dyn.dset(sim.procs.pend_guard, p, -1, woke)
        )
    )
    sim = _schedule_wake(sim, woke, p, pr.SUCCESS)
    if spec is not None:
        for c in spec.conditions:
            if not c.observes:
                continue
            # membership of THIS (possibly traced) gid in the observed
            # set, as a const table lookup; forwarding is gated by the
            # same pred as the signal itself
            obs = _ConstTable(
                [1 if g in c.observes else 0 for g in range(spec.n_guards)],
                jnp.int32,
            )
            fire = obs[jnp.asarray(gid, _I)] != 0
            if pred is not True:
                fire = fire & pred
            sim = cond_signal(spec, sim, c.id, pred=fire)
    return sim


def _guard_wait(sim: Sim, p, gid, cmd: pr.Command, is_retry=False,
                pred=True) -> Sim:
    """Pend the blocked command, enqueue on the guard, and advance pc to
    the continuation (signals deliver there if the wait is aborted).
    ``pred`` gates every write (see _guard_signal).

    A retry re-enqueues with the process's original FIFO sequence so a
    woken-but-unsatisfied waiter keeps its place (no starvation; parity
    with the reference's evaluate-the-front-without-dequeuing signals)."""
    seq_override = jnp.where(
        jnp.asarray(is_retry), dyn.dget(sim.procs.pend_seq, p), jnp.asarray(-1, _I)
    )
    g2, seq = gd.alloc_seq(sim.guards, gid, seq_override, pred)
    # membership IS pend_guard (dense guards): the pend bookkeeping below
    # is the whole enqueue; nothing can overflow (capacity = P)
    # one grouped write: every pend field lands at the same pid under the
    # same gate, so the scan-over-rows arm serves all nine from a single
    # block loop (dense mode is the per-field dset sequence, unchanged)
    (pend_tag, pend_f, pend_f2, pend_f3, pend_i, pend_pc, pend_guard,
     pend_seq, pc) = dyn.dset_tree(
        (sim.procs.pend_tag, sim.procs.pend_f, sim.procs.pend_f2,
         sim.procs.pend_f3, sim.procs.pend_i, sim.procs.pend_pc,
         sim.procs.pend_guard, sim.procs.pend_seq, sim.procs.pc),
        p,
        (cmd.tag, cmd.f, cmd.f2, cmd.f3, cmd.i, cmd.next_pc,
         jnp.asarray(gid, _I), seq, cmd.next_pc),
        pred,
    )
    procs = sim.procs._replace(
        pend_tag=pend_tag, pend_f=pend_f, pend_f2=pend_f2, pend_f3=pend_f3,
        pend_i=pend_i, pend_pc=pend_pc, pend_guard=pend_guard,
        pend_seq=pend_seq, pc=pc,
    )
    return sim._replace(procs=procs, guards=g2)


def _clear_pend(sim: Sim, p, pred=True) -> Sim:
    pend_tag, pend_guard = dyn.dset_tree(
        (sim.procs.pend_tag, sim.procs.pend_guard), p,
        (pr.NO_PEND, -1), pred,
    )
    return sim._replace(
        procs=sim.procs._replace(pend_tag=pend_tag, pend_guard=pend_guard)
    )


def _record_row(acc: ts.StepAccum, row, t, v, pred=True) -> ts.StepAccum:
    """step_record on one row of a batched StepAccum, gated by ``pred``."""
    one = dyn.dget_tree(acc, row)
    upd = ts.step_record(one, t, v)
    return dyn.dset_tree(acc, row, upd, pred)


def _record_row_if(flags, acc, row, t, v, pred=True):
    """Recording gated by per-component static flags: traces to nothing
    when no component records (parity: the reference's optional recording
    — a documented hot-loop cost), and to a masked update when only some
    do."""
    if acc is None or not any(flags):
        return acc
    rec = _record_row(acc, row, t, v, pred)
    if all(flags):
        return rec
    # int table compared != 0: a bool _ConstTable would emit i1 select
    # chains, which Mosaic cannot lower in kernel mode
    mask = _ConstTable([int(bool(f)) for f in flags], jnp.int32)[row] != 0
    return _tree_select(mask, rec, acc)


def _cancel_wake(sim: Sim, p, pred=True) -> Sim:
    """Cancel p's outstanding resume (a no-op if none is armed).  The
    analog of cancelling a stale hold timer (`src/cmb_process.c:344-349`)."""
    return sim._replace(wakes=ev.wake_clear(sim.wakes, p, pred))


def _unwait(spec: ModelSpec, sim: Sim, p, pred=True) -> Sim:
    """Detach p from whatever it waits on: guard membership, pending
    command, wake event (parity: cmi_process_cancel_awaiteds,
    `src/cmb_process.c:694-748`).  Dense guards: clearing ``pend_guard``
    (done by _clear_pend) IS the guard removal.  Statics: bookkeeping a
    model's command set cannot populate stays out of the trace."""
    if _may_pend(spec, sim):
        sim = _clear_pend(sim, p, pred)
    sim = _cancel_wake(sim, p, pred)
    procs = sim.procs
    if _may_wait_procs(spec, sim):
        procs = procs._replace(
            await_pid=dyn.dset(procs.await_pid, p, -1, pred)
        )
    if _may_wait_events(spec, sim):
        procs = procs._replace(
            await_evt=dyn.dset(procs.await_evt, p, -1, pred)
        )
    return sim._replace(procs=procs)


def _scan_evt_waiters(sim: Sim, decide) -> Sim:
    """Shared waiter scan, fully vectorized: ``decide(sim, h_vec[P]) ->
    (wake_vec, sig_vec)`` elementwise over every process's awaited
    handle; woken waiters get their dense wake slot armed (FIFO seqs in
    pid order, like the mass-wake in _wake_waiters) and their await
    cleared.  (The per-pid counted loop this replaces ran P masked
    [P]-wide iterations per step for wait_event models — O(P^2).)"""
    h = sim.procs.await_evt
    awaiting = (h >= 0) & (sim.procs.status == pr.RUNNING)
    wake, sig = decide(sim, h)
    wake = wake & awaiting
    sim = _mass_wake(sim, wake, sig)
    return sim._replace(
        procs=sim.procs._replace(
            await_evt=jnp.where(wake, jnp.asarray(-1, _I), h)
        ),
    )


def _dispatch_evt_wakes(sim: Sim, handle, found, pred=True) -> Sim:
    """Wake processes waiting on the just-popped event with SUCCESS —
    before its action runs, like the reference (`src/cmb_event.c:312-314`)
    — and, as the lazy arm of the cancel protocol, any waiter whose awaited
    handle has died (pattern-cancelled timers etc.) with CANCELLED.

    ``pred`` suppresses the WHOLE scan (both arms) for a step that defers
    a boundary dispatch: even the stale arm must wait, because its wake
    would be armed at the un-advanced clock and dispatch AHEAD of the
    deferred event — the host-side XLA step re-runs the scan in order."""

    def decide(sim, h):
        fired = found & (h == handle)
        stale = ~fired & ~ev._valid_vec(sim.events, h)
        wake = fired | stale
        if pred is not True:
            wake = wake & pred
        return wake, jnp.where(fired, pr.SUCCESS, pr.CANCELLED).astype(_I)

    return _scan_evt_waiters(sim, decide)


def _cancel_evt_wakes(sim: Sim, handle, pred) -> Sim:
    """Wake waiters of a just-cancelled event with CANCELLED immediately
    (the eager arm; parity: the reference wakes waiter lists at cancel)."""

    def decide(sim, h):
        return (
            jnp.asarray(pred) & (h == handle),
            jnp.asarray(pr.CANCELLED, _I),
        )

    return _scan_evt_waiters(sim, decide)


def _exclusive_rank(mask):
    """[P] bool -> [P] i32: for each true element, how many true elements
    precede it (pid-ascending).  Log-doubling prefix sum built from
    concatenate+slice (both have lanelast/Mosaic rules; lax.cumsum's
    lowering does not)."""
    x = mask.astype(_I)
    n = x.shape[0]
    inc = x
    shift = 1
    while shift < n:
        inc = inc + lax.concatenate(
            [jnp.zeros((shift,), _I), lax.slice(inc, (0,), (n - shift,))],
            dimension=0,
        )
        shift *= 2
    return inc - x


def _mass_wake(sim: Sim, mask, sig) -> Sim:
    """Arm the dense wake slot of every process in ``mask`` at the
    current clock, assigning FIFO seqs in pid order — the contract both
    waiter-wake paths (WAIT_PROC and wait_event) must share.  The count
    dtype is pinned: under x64, jnp.sum would promote i32 -> i64."""
    base = sim.events.next_seq
    n_woken = jnp.sum(mask.astype(_I), dtype=_I)
    wk = sim.wakes
    wk2 = ev.Wakes(
        time=jnp.where(mask, sim.clock, wk.time),
        sig=jnp.where(mask, jnp.asarray(sig, _I), wk.sig),
        seq=jnp.where(mask, base + _exclusive_rank(mask), wk.seq),
    )
    return sim._replace(
        wakes=wk2,
        events=sim.events._replace(next_seq=base + n_woken),
    )


def _wake_waiters(spec: ModelSpec, sim: Sim, target, sig, pred=True) -> Sim:
    """Wake every process waiting on `target` finishing (WAIT_PROC) — one
    vectorized mass-arm of the dense wake table.  (The per-pid loop this
    replaces cost O(P^2) per event at AWACS scale: its [P]-wide body ran
    P masked iterations inside every chain step.)  Seqs are assigned in
    pid order among the woken, exactly as the loop did.

    Statically absent from models that never issue C_WAIT_PROC:
    ``await_pid`` is then always -1, so the scan plus its prefix-rank
    seq assignment (~45 [P]-wide ops per exit) can wake no one."""
    if not _may_wait_procs(spec, sim):
        return sim
    waiting = (sim.procs.await_pid == jnp.asarray(target, _I)) & (
        sim.procs.status == pr.RUNNING
    )
    if pred is not True:
        waiting = waiting & pred
    sim = _mass_wake(sim, waiting, sig)
    return sim._replace(
        procs=sim.procs._replace(
            await_pid=jnp.where(
                waiting, jnp.asarray(-1, _I), sim.procs.await_pid
            )
        ),
    )


def _abort_cleanup(spec: ModelSpec, sim: Sim, p, pend: pr.Command, sig,
                   pred=True) -> Sim:
    """Command-specific cleanup when a pended wait is aborted:

    * pool acquire: roll the holding back to its pre-call amount and
      return the difference (parity: the INTERRUPTED unwind in
      cmi_pool_acquire_inner) — except on PREEMPTED, where a mugger
      already took everything;
    * buffer get/put: keep the partial amount and report the obtained/
      deposited quantity in the result register (partial-fulfillment
      contract, `src/cmb_buffer.c:194-346`)."""
    sig = jnp.asarray(sig, _I)
    if spec.pools:
        p_guard_c = _ConstTable([pl.guard for pl in spec.pools], _I)
        p_rec_c = [pl.record for pl in spec.pools]
        p_cap_c = _ConstTable([pl.capacity for pl in spec.pools], _R)
        k = jnp.clip(pend.i, 0, len(spec.pools) - 1)
        is_pool = (pend.tag == pr.C_POOL_ACQ) | (pend.tag == pr.C_POOL_PRE)
        do_rb = is_pool & (sig != pr.PREEMPTED)
        if pred is not True:
            do_rb = do_rb & pred
        excess = jnp.maximum(dyn.dget2(sim.pools.held, k, p) - pend.f2, 0.0)
        rb = sim._replace(
            pools=sim.pools._replace(
                level=dyn.dadd(sim.pools.level, k, excess),
                held=dyn.dadd2(sim.pools.held, k, p, -excess),
                acc=_record_row_if(
                    p_rec_c, sim.pools.acc, k, sim.clock,
                    p_cap_c[k] - (dyn.dget(sim.pools.level, k) + excess),
                ),
            )
        )
        rb = _guard_signal(rb, p_guard_c[k], spec=spec)
        sim = _tree_select(do_rb, rb, sim)
    if spec.buffers:
        is_buf = (pend.tag == pr.C_BUF_GET) | (pend.tag == pr.C_BUF_PUT)
        if pred is not True:
            is_buf = is_buf & pred
        obtained = pend.f2 - pend.f
        sim = sim._replace(
            procs=sim.procs._replace(
                got=dyn.dset(sim.procs.got, p, obtained, is_buf)
            )
        )
    return sim


def _abort_wait(spec: ModelSpec, sim: Sim, p, sig, pred=True) -> Sim:
    """Abort whatever p is waiting on AND run the command-specific abort
    cleanup (pool rollback, buffer partial-fulfillment report).  Every
    wait-aborting path — timer/interrupt delivery, preemption, mugging,
    stop — must come through here; clearing the pend without the cleanup
    silently breaks the rollback/partial-fulfillment contracts."""
    if not _may_pend(spec, sim):
        # nothing can ever pend: no snapshot, no command-specific
        # cleanup — unwait is the whole abort
        return _unwait(spec, sim, p, pred)
    pend = pr.Command(*dyn.dget_tree(
        (sim.procs.pend_tag, sim.procs.pend_f, sim.procs.pend_f2,
         sim.procs.pend_f3, sim.procs.pend_i, sim.procs.pend_pc), p,
    ))
    # _abort_cleanup self-gates on pend.tag, so NO_PEND is a clean no-op
    return _abort_cleanup(
        spec, _unwait(spec, sim, p, pred), p, pend, sig, pred=pred
    )


def finish_process(spec: ModelSpec, sim: Sim, p, exit_sig, pred=True) -> Sim:
    """Terminate process p: status, waiter wakeup, resource cleanup
    (parity: kill semantics — drop resources, cancel awaits, wake waiters,
    `src/cmb_process.c:776-828`).  Every write is gated by ``pred`` so
    h_exit can run straight-line under its branch gate."""
    r_guard = _ConstTable([r.guard for r in spec.resources] or [0], _I)
    p_guard = _ConstTable([pl.guard for pl in spec.pools] or [0], _I)
    p_cap = _ConstTable([pl.capacity for pl in spec.pools] or [0.0], _R)

    r_rec = [r.record for r in spec.resources]
    p_rec = [pl.record for pl in spec.pools]

    sim = _abort_wait(spec, sim, p, exit_sig, pred=pred)
    # cancel any outstanding timers aimed at p
    es2, _ = ev.pattern_cancel(sim.events, kind=K_TIMER, subj=p, pred=pred)
    sim = sim._replace(events=es2)
    sim = sim._replace(
        procs=sim.procs._replace(
            status=dyn.dset(sim.procs.status, p, pr.FINISHED, pred),
            exit_sig=dyn.dset(
                sim.procs.exit_sig, p, jnp.asarray(exit_sig, _I), pred
            ),
        )
    )
    sim = _wake_waiters(spec, sim, p, exit_sig, pred=pred)

    # drop binary resources held by p (holdable drop protocol)
    def drop_res(rid, sim):
        held = dyn.dget(sim.resources.holder, rid) == p
        if pred is not True:
            held = held & pred
        r2 = Resources(
            holder=dyn.dset(sim.resources.holder, rid, -1, held),
            acc=_record_row_if(
                r_rec, sim.resources.acc, rid, sim.clock, 0.0, held
            ),
        )
        sim = sim._replace(resources=r2)
        return _guard_signal(sim, r_guard[rid], pred=held, spec=spec)

    # pool units held by p return to the pool
    def drop_pool(k, sim):
        amt = dyn.dget2(sim.pools.held, k, p)
        has = amt > 0.0
        if pred is not True:
            has = has & pred
        p2 = sim.pools._replace(
            level=dyn.dadd(sim.pools.level, k, amt, has),
            held=dyn.dset2(sim.pools.held, k, p, 0.0, has),
            acc=_record_row_if(
                p_rec, sim.pools.acc, k, sim.clock,
                p_cap[k] - (dyn.dget(sim.pools.level, k) + amt), has,
            ),
        )
        sim = sim._replace(pools=p2)
        return _guard_signal(sim, p_guard[k], pred=has, spec=spec)

    if spec.resources:
        sim = _kfori(0, sim.resources.holder.shape[0], drop_res, sim)
    if spec.pools:
        sim = _kfori(0, sim.pools.level.shape[0], drop_pool, sim)
    return sim


# --- inter-process verbs (callable from blocks via core.api) -----------------


def interrupt(spec: ModelSpec, sim: Sim, target, sig) -> Sim:
    """Deliver ``sig`` to a waiting process NOW, aborting whatever it waits
    on (parity: cmb_process_interrupt, `include/cmb_process.h:406`)."""
    target = jnp.asarray(target, _I)
    alive = dyn.dget(sim.procs.status, target) == pr.RUNNING
    sim = _abort_wait(spec, sim, target, sig, pred=alive)
    return _schedule_wake(sim, alive, target, jnp.asarray(sig, _I))


def stop_process(spec: ModelSpec, sim: Sim, target) -> Sim:
    """Kill a process (parity: cmb_process_stop, `src/cmb_process.c:803-828`):
    drops its resources, cancels its waits/timers, wakes its waiters with
    STOPPED."""
    target = jnp.asarray(target, _I)
    alive = dyn.dget(sim.procs.status, target) == pr.RUNNING
    return finish_process(spec, sim, target, pr.STOPPED, pred=alive)


def release_resource(spec: ModelSpec, sim: Sim, p, rid, pred=True) -> Sim:
    """Release binary resource ``rid`` held by ``p`` inline — the body of
    the C_RELEASE handler, callable from a block (via api.release) so a
    release costs ZERO chain iterations: it never blocks and never
    yields, so making it a command spent a full masked kernel body pass
    per call just to run these few writes (the reference's plain
    function call, `src/cmb_resource.c:249-273`, had the same
    insight — only waits go through the scheduler)."""
    rid = jnp.asarray(rid, _I)
    r_guard = _ConstTable([r.guard for r in spec.resources] or [0], _I)
    r_rec = [r.record for r in spec.resources]
    owner_ok = dyn.dget(sim.resources.holder, rid) == p
    r2 = Resources(
        holder=dyn.dset(sim.resources.holder, rid, -1, pred),
        acc=_record_row_if(
            r_rec, sim.resources.acc, rid, sim.clock, 0.0, pred
        ),
    )
    sim = sim._replace(resources=r2)
    sim = _guard_signal(sim, r_guard[rid], pred=pred, spec=spec)
    return _set_err(sim, _and(~owner_ok, pred), ERR_BAD_RELEASE)


def release_pool(spec: ModelSpec, sim: Sim, p, k, amount, pred=True) -> Sim:
    """Release ``amount`` units of pool ``k`` inline (parity:
    cmb_resourcepool_release; partial release allowed) — the body of the
    C_POOL_REL handler, callable from a block via api.pool_release (see
    :func:`release_resource` for why inline releases are free)."""
    k = jnp.asarray(k, _I)
    p_guard = _ConstTable([pl.guard for pl in spec.pools] or [0], _I)
    p_cap = _ConstTable([pl.capacity for pl in spec.pools] or [0.0], _R)
    p_rec = [pl.record for pl in spec.pools]
    amount = jnp.asarray(amount, _R)
    amt = jnp.minimum(amount, dyn.dget2(sim.pools.held, k, p))  # partial ok
    # profile-scaled ownership tolerance: held amounts accumulate in
    # REAL, so the release check must forgive rounding at REAL's
    # resolution (a fixed 1e-12 is below f32 eps and would degenerate
    # to exact compare under the kernel profile); floored at the
    # historical 1e-12 — held carries absolute error from its past
    # magnitude, not amount's, so the relative term alone would be
    # tighter than the old constant on f64
    tol = jnp.maximum(
        64.0 * float(jnp.finfo(config.REAL_DTYPE).eps) * jnp.maximum(
            jnp.asarray(1.0, config.REAL_DTYPE), jnp.abs(amount)
        ),
        jnp.asarray(1e-12, config.REAL_DTYPE),
    )
    owner_ok = dyn.dget2(sim.pools.held, k, p) >= amount - tol
    in_use = p_cap[k] - (dyn.dget(sim.pools.level, k) + amt)
    p2 = sim.pools._replace(
        level=dyn.dadd(sim.pools.level, k, amt, pred),
        held=dyn.dadd2(sim.pools.held, k, p, -amt, pred),
        acc=_record_row_if(
            p_rec, sim.pools.acc, k, sim.clock, in_use, pred
        ),
    )
    sim = sim._replace(pools=p2)
    sim = _guard_signal(sim, p_guard[k], pred=pred, spec=spec)
    return _set_err(sim, _and(~owner_ok, pred), ERR_BAD_RELEASE)


def spawn_process(sim: Sim, pt, at=None, prio=None):
    """Activate one row of a spawn pool (a process type declared with
    ``start=False``); returns ``(sim, pid)`` with pid == -1 when every
    row of the pool is currently RUNNING.

    The jit answer to runtime process creation
    (`cmb_process_create`/`cmb_process_start`,
    `include/cmb_process.h:119-180`): the pool's rows are declared
    statically, activation picks the lowest-pid CREATED-or-FINISHED row,
    resets its per-process state, and arms its entry wake at ``at``
    (default: now).  FINISHED rows are recycled — their timers were
    pattern-cancelled and waiters woken at exit, so reuse is clean."""
    lo, n = pt.first_pid, pt.count
    if lo < 0:
        raise ValueError("spawn_process needs a built model's ProcessType")
    pididx = jnp.arange(sim.procs.pc.shape[0], dtype=_I)
    in_pool = (pididx >= lo) & (pididx < lo + n)
    free = in_pool & (
        (sim.procs.status == pr.CREATED)
        | (sim.procs.status == pr.FINISHED)
    )
    found = jnp.any(free)
    # lowest free pid — iota-min, NOT argmax: several rows tie at True
    # and Mosaic's argmax tie-break differs from XLA's lowest-index rule
    # (the first on-device fuzz divergence — dyn.first_true32)
    slot = dyn.first_true32(free).astype(_I)
    p = jnp.where(found, slot, 0)
    new_prio = jnp.asarray(pt.prio if prio is None else prio, _I)
    (status, pc, prio, got, exit_sig, await_pid, await_evt, pend_tag,
     pend_guard, locals_f, locals_i) = dyn.dset_tree(
        (sim.procs.status, sim.procs.pc, sim.procs.prio, sim.procs.got,
         sim.procs.exit_sig, sim.procs.await_pid, sim.procs.await_evt,
         sim.procs.pend_tag, sim.procs.pend_guard, sim.procs.locals_f,
         sim.procs.locals_i),
        p,
        (pr.RUNNING, pt.entry_pc, new_prio, 0.0, 0, -1, -1, pr.NO_PEND,
         -1, 0.0, 0),
        found,
    )
    procs = sim.procs._replace(
        status=status, pc=pc, prio=prio, got=got, exit_sig=exit_sig,
        await_pid=await_pid, await_evt=await_evt, pend_tag=pend_tag,
        pend_guard=pend_guard, locals_f=locals_f, locals_i=locals_i,
    )
    sim = sim._replace(procs=procs)
    t = sim.clock if at is None else jnp.asarray(at, _T)
    sim = _schedule_wake(sim, found, p, pr.SUCCESS, t=t)
    return sim, jnp.where(found, slot, jnp.asarray(-1, _I))


def timer_add(sim: Sim, p, dur, sig):
    """Schedule a timer delivering ``sig`` to p after ``dur`` (parity:
    cmb_process_timer_add); returns (sim, handle)."""
    es2, handle = ev.schedule(
        sim.events, sim.clock + jnp.maximum(jnp.asarray(dur, _T), 0.0),
        dyn.dget(sim.procs.prio, p), K_TIMER, p, sig,
    )
    sim = sim._replace(events=es2)
    return _set_err(sim, es2.overflow, ERR_EVENT_OVERFLOW), handle


def timer_cancel(sim: Sim, handle, spec: Optional[ModelSpec] = None):
    """Cancel a timer (or any event) by handle (parity:
    cmb_process_timer_cancel / cmb_event_cancel); returns (sim, existed).

    When ``spec`` is passed and the model can wait on events, processes
    waiting on this handle wake with CANCELLED immediately (without it
    they still wake, lazily, at the next dispatch — see
    _dispatch_evt_wakes)."""
    es2, ok = ev.cancel(sim.events, handle)
    sim = sim._replace(events=es2)
    if spec is not None and _may_wait_events(spec, sim):
        sim = _cancel_evt_wakes(sim, handle, ok)
    return sim, ok


def timers_clear(sim: Sim, p) -> Sim:
    """Cancel all timers aimed at p (parity: cmb_process_timers_clear)."""
    es2, _ = ev.pattern_cancel(sim.events, kind=K_TIMER, subj=p)
    return sim._replace(events=es2)


def priority_set(sim: Sim, p, new_prio) -> Sim:
    """Change a process's priority, reshuffling its wake event and guard
    entry (parity: cmb_process_priority_set, `src/cmb_process.c:170-220`)."""
    new_prio = jnp.asarray(new_prio, _I)
    # no wake or guard touch-up: both pop_merged and gd.best_waiter read
    # procs.prio LIVE, which IS the reshuffle the reference performs here
    return sim._replace(
        procs=sim.procs._replace(prio=dyn.dset(sim.procs.prio, p, new_prio)),
    )


def _cond_satisfied(spec: ModelSpec, sim: Sim, cid, pid):
    """Evaluate condition ``cid``'s registered predicate for ``pid``.
    A static (python int) ``cid`` — the observer-forwarding path —
    traces only that condition's predicate."""
    if not spec.conditions:
        return jnp.asarray(False)
    if isinstance(cid, int):
        return jnp.asarray(spec.conditions[cid].predicate(sim, pid))
    pred_fns = [
        (lambda c: (lambda s, q: jnp.asarray(c.predicate(s, q))))(c)
        for c in spec.conditions
    ]
    return _vswitch(
        jnp.clip(jnp.asarray(cid, _I), 0, len(pred_fns) - 1), pred_fns, sim,
        pid,
    )


def cond_signal(spec: ModelSpec, sim: Sim, cid, pred=True) -> Sim:
    """Signal a condition: evaluate the predicate for every waiter and wake
    all satisfied ones (parity: cmb_condition_signal's two-pass wake-all,
    `src/cmb_condition.c:106-167`; the woken retry re-checks, so spurious
    wakeups re-wait inside the framework).  ``pred`` gates the whole
    signal (the observer-forwarding path runs straight-line, masked)."""
    if not spec.conditions:
        return sim
    if isinstance(cid, int):
        gid = spec.conditions[cid].guard
    else:
        c_guard = _ConstTable([c.guard for c in spec.conditions], _I)
        cid = jnp.asarray(cid, _I)
        gid = c_guard[cid]

    def visit(q, sim):
        # dense guards: candidate waiters are the processes themselves
        live = dyn.dget(sim.procs.pend_guard, q) == gid
        satisfied = _cond_satisfied(spec, sim, cid, q)
        wake = live & satisfied
        if pred is not True:
            wake = wake & pred
        sim = sim._replace(
            procs=sim.procs._replace(
                pend_guard=dyn.dset(sim.procs.pend_guard, q, -1, wake)
            )
        )
        return _schedule_wake(sim, wake, q, pr.SUCCESS)

    # O(P) iterations of a per-pid traced predicate (the user predicate
    # takes one pid, so it cannot be vectorized here); bodies touch [P]
    # rows, so this is the O(P^2) shape class for cond-heavy big-P
    # models — acceptable because conditions wake rarely, flagged in
    # tools/kernel_cost's audit notes
    return _kfori(0, sim.procs.pc.shape[0], visit, sim)


# --- command handlers ---------------------------------------------------------


def _infer_used_tags(spec: ModelSpec, sim: Sim):
    """The set of command tags this model's blocks can emit, collected by
    abstractly tracing every block once (constructors register their tag —
    see process._tag_collector).  Pended retries re-apply a tag a block
    emitted, so the set is closed under the dispatch protocol.  Returns
    None (= trace the full table) if any block resists abstract evaluation.
    """
    if pr._tag_collector is not None:
        return None  # nested inference (a block queried it): be conservative
    tags: set = set()
    pr._tag_collector = tags
    try:
        p0 = jnp.zeros((), _I)
        for blk in spec.blocks:
            # fresh wrapper per trace: jax.eval_shape memoizes on
            # (function, avals), and a cache hit would skip the block
            # body — the collector's side effect — entirely.  Two specs
            # sharing block functions at identical Sim avals (e.g. a
            # dataclasses.replace twin of a spec whose tags were already
            # inferred) would then "infer" an EMPTY tag set and route
            # every command to h_invalid/ERR_USER (found by the stream
            # regrow battery, pinned in tests/test_stream.py).
            jax.eval_shape(lambda *a: blk(*a), sim, p0, p0)
    except Exception:
        return None
    finally:
        pr._tag_collector = None
    return frozenset(tags)


def _used_tags_for(spec: ModelSpec, sim: Sim):
    """Memoized on the spec object itself (an id()-keyed dict would hand a
    recycled id a stale tag set after the old spec is collected)."""
    if not hasattr(spec, "_used_tags_memo"):
        spec._used_tags_memo = _infer_used_tags(spec, sim)
    return spec._used_tags_memo


def _may_wait_events(spec: ModelSpec, sim: Sim) -> bool:
    """Static: can this model issue C_WAIT_EVT?  Gates the per-dispatch
    waiter scan (an O(P) fori) out of models that never wait on events."""
    used = _used_tags_for(spec, sim)
    return used is None or pr.C_WAIT_EVT in used


def _may_wait_procs(spec: ModelSpec, sim: Sim) -> bool:
    """Static: can this model issue C_WAIT_PROC?  Gates the exit-time
    waiter mass-wake out of models that never wait on processes."""
    used = _used_tags_for(spec, sim)
    return used is None or pr.C_WAIT_PROC in used


#: command tags whose handlers can pend (block through _guard_wait) —
#: the only writers of procs.pend_tag
_PENDING_TAGS = frozenset({
    pr.C_PUT, pr.C_GET, pr.C_ACQUIRE, pr.C_PREEMPT, pr.C_POOL_ACQ,
    pr.C_POOL_PRE, pr.C_BUF_GET, pr.C_BUF_PUT, pr.C_PQ_PUT, pr.C_PQ_GET,
    pr.C_COND_WAIT, pr.C_PUT_HOLD, pr.C_GET_HOLD, pr.C_ACQ_HOLD,
    pr.C_PRE_HOLD, pr.C_POOL_ACQ_HOLD, pr.C_POOL_PRE_HOLD,
    pr.C_BUF_GET_HOLD, pr.C_BUF_PUT_HOLD, pr.C_PQ_PUT_HOLD,
    pr.C_PQ_GET_HOLD,
})


def _may_pend(spec: ModelSpec, sim: Sim) -> bool:
    """Static: can ANY command this model emits block through a guard?
    If not, ``pend_tag`` stays NO_PEND forever and resume's whole
    retry/abort arm — the pend reads, the per-chain-iteration use_pend
    merge, the clears — gates out of the trace (hold/exit-only models
    like AWACS keep only the wake bookkeeping)."""
    used = _used_tags_for(spec, sim)
    return used is None or bool(_PENDING_TAGS & set(used))


def _make_apply(spec: ModelSpec, used_tags=None, batched: bool = False):
    q_cap = _ConstTable([q.capacity for q in spec.queues] or [1], _I)
    q_front = _ConstTable([q.front_guard for q in spec.queues] or [0], _I)
    q_rear = _ConstTable([q.rear_guard for q in spec.queues] or [0], _I)
    r_guard = _ConstTable([r.guard for r in spec.resources] or [0], _I)
    p_guard = _ConstTable([p.guard for p in spec.pools] or [0], _I)
    p_cap = _ConstTable([p.capacity for p in spec.pools] or [0.0], _R)
    b_cap = _ConstTable([b.capacity for b in spec.buffers] or [0.0], _R)
    b_front = _ConstTable([b.front_guard for b in spec.buffers] or [0], _I)
    b_rear = _ConstTable([b.rear_guard for b in spec.buffers] or [0], _I)
    pq_cap = _ConstTable([q.capacity for q in spec.pqueues] or [1], _I)
    pq_front = _ConstTable([q.front_guard for q in spec.pqueues] or [0], _I)
    pq_rear = _ConstTable([q.rear_guard for q in spec.pqueues] or [0], _I)
    c_guard = _ConstTable([c.guard for c in spec.conditions] or [0], _I)
    q_rec = [q.record for q in spec.queues]
    r_rec = [r.record for r in spec.resources]
    p_rec = [pl.record for pl in spec.pools]
    b_rec = [b.record for b in spec.buffers]
    pq_rec = [q.record for q in spec.pqueues]

    def set_pc(sim, p, pc, pred=True):
        return sim._replace(
            procs=sim.procs._replace(pc=dyn.dset(sim.procs.pc, p, pc, pred))
        )

    @_gated
    def h_hold(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        dur = jnp.maximum(cmd.f, 0.0)
        sim = _schedule_wake(
            sim, gate, p, pr.SUCCESS, t=sim.clock + dur
        )
        return set_pc(sim, p, cmd.next_pc, gate), jnp.asarray(True)

    @_gated
    def h_exit(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        return (
            finish_process(spec, sim, p, pr.SUCCESS, pred=gate),
            jnp.asarray(True),
        )

    @_gated
    def h_jump(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        return set_pc(sim, p, cmd.next_pc, gate), jnp.asarray(False)

    @_gated
    def h_queue(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        """PUT and GET as ONE traced handler, aliased at both dispatch
        slots (so _vswitch traces it once).  The verbs differ only in a
        few scalar selects; sharing lets the ring's full-width ops —
        the largest line in the kernel's per-event budget — serve both:
        one one-hot, one read pass, one write pass (dyn.dexchange: a
        get "writes back" the value it read, a bitwise no-op).  All
        writes pred-gated straight-line, as before (no whole-Sim branch
        select — each saved select is a full pass over the ring).
        """
        qid = cmd.i
        is_put = (cmd.tag == pr.C_PUT) | (cmd.tag == pr.C_PUT_HOLD)
        # fused verbs: on success the process holds cmd.f3 instead of
        # continuing inline — the whole queue cycle in ONE chain
        # iteration (process.put_hold/get_hold)
        fused = (cmd.tag == pr.C_PUT_HOLD) | (cmd.tag == pr.C_GET_HOLD)
        size = dyn.dget(sim.queues.size, qid)
        head = dyn.dget(sim.queues.head, qid)
        cap = q_cap[qid]
        # no-jump-ahead fairness (parity: src/cmb_resource.c:202-233): a
        # fresh caller must queue behind existing waiters (putters watch
        # the rear guard, getters the front); a woken caller IS the
        # dequeued front and may proceed despite others behind it
        own_gid = jnp.where(is_put, q_rear[qid], q_front[qid])
        may = is_retry | gd.is_empty(sim.procs.pend_guard, own_gid)
        blocked = jnp.where(is_put, size >= cap, size <= 0) | ~may
        ok = _and(~blocked, gate)
        ok_get = ok & ~is_put

        idx = jnp.where(is_put, (head + size) % cap, head)
        if len(spec.queues) > 1:
            idx = qid * spec.queue_cap_max + idx
        item, items2 = dyn.dexchange(sim.queues.items, idx, cmd.f, is_put, ok)
        dsz = jnp.where(is_put, 1, -1).astype(size.dtype)
        sim = sim._replace(
            queues=Queues(
                items=items2,
                head=dyn.dset(sim.queues.head, qid, (head + 1) % cap,
                              ok_get),
                size=dyn.dadd(sim.queues.size, qid, dsz, ok),
                acc=_record_row_if(
                    q_rec, sim.queues.acc, qid, sim.clock,
                    (size + dsz).astype(_R), ok,
                ),
            ),
            procs=sim.procs._replace(
                got=dyn.dset(sim.procs.got, p, item, ok_get)
            ),
        )
        if sim.metrics is not None:
            # queue-length high-water ratchet, gated by the same ok as
            # the size write (gate=False lanes write nothing — the
            # _gated no-op contract holds bitwise)
            sim = obs_metrics.on_queue_len(sim, qid, size + dsz, ok)
        # signal order preserved from the split handlers (wake seqs are
        # order-assigned): a get signals rear (space) then front
        # (leftover items); a put frees no space, so only the getter
        # side can newly be satisfiable
        sim = _guard_signal(sim, q_rear[qid], pred=ok_get, spec=spec)
        sim = _guard_signal(sim, q_front[qid], pred=ok, spec=spec)
        # fused success: hold cmd.f3 (h_hold semantics), waking at
        # next_pc — the signal seqs above come first, as they would if
        # the hold were issued by a continuation block
        sim = _schedule_wake(
            sim, _and(fused, ok), p, pr.SUCCESS,
            t=sim.clock + jnp.maximum(cmd.f3, 0.0),
        )
        # both outcomes continue at next_pc (the blocked path's signals
        # deliver there), so the pc write is gated only by the branch
        sim = set_pc(sim, p, cmd.next_pc, gate)
        sim = _guard_wait(
            sim, p, own_gid, cmd, is_retry, pred=_and(blocked, gate)
        )
        return sim, blocked | fused

    def _grab_resource(sim, p, rid, pred=True):
        r2 = Resources(
            holder=dyn.dset(sim.resources.holder, rid, p, pred),
            acc=_record_row_if(
                r_rec, sim.resources.acc, rid, sim.clock, 1.0, pred
            ),
        )
        return sim._replace(resources=r2)

    @_gated
    def h_acquire(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        rid = cmd.i
        fused = cmd.tag == pr.C_ACQ_HOLD
        free = dyn.dget(sim.resources.holder, rid) < 0
        may_grab = is_retry | gd.is_empty(sim.procs.pend_guard, r_guard[rid])
        ok = free & may_grab

        sim = _grab_resource(sim, p, rid, _and(ok, gate))
        sim = _schedule_wake(
            sim, _and(fused & ok, gate), p, pr.SUCCESS,
            t=sim.clock + jnp.maximum(cmd.f3, 0.0),
        )
        sim = set_pc(sim, p, cmd.next_pc, gate)
        sim = _guard_wait(
            sim, p, r_guard[rid], cmd, is_retry, pred=_and(~ok, gate)
        )
        return sim, ~ok | fused

    @_gated
    def h_preempt(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        """Parity: cmb_resource_preempt (`src/cmb_resource.c:275-325`) —
        grab if free; kick a holder of <= priority (it resumes with
        PREEMPTED, its pending waits cancelled); else wait like acquire.
        Straight-line: the three outcomes write disjoint state under
        exclusive predicates."""
        rid = cmd.i
        fused = cmd.tag == pr.C_PRE_HOLD
        holder = dyn.dget(sim.resources.holder, rid)
        free = holder < 0
        victim = jnp.maximum(holder, 0)
        can_kick = ~free & (dyn.dget(sim.procs.prio, p) >= dyn.dget(sim.procs.prio, victim))
        g_free = _and(free, gate)
        g_kick = _and(can_kick, gate)
        blocked = ~free & ~can_kick

        # kick path: cancel victim's awaits (incl. pool rollback /
        # buffer partial report if it was waiting on one), deliver
        # PREEMPTED
        sim = _abort_wait(spec, sim, victim, pr.PREEMPTED, pred=g_kick)
        sim = _schedule_wake(sim, g_kick, victim, pr.PREEMPTED)
        # holder switch on kick: no utilization record (still in use);
        # fresh grab on free records
        sim = sim._replace(
            resources=sim.resources._replace(
                holder=dyn.dset(sim.resources.holder, rid, p, g_kick)
            )
        )
        sim = _grab_resource(sim, p, rid, g_free)
        sim = _schedule_wake(
            sim, _and(fused & ~blocked, gate), p, pr.SUCCESS,
            t=sim.clock + jnp.maximum(cmd.f3, 0.0),
        )
        sim = set_pc(sim, p, cmd.next_pc, _and(free | can_kick, gate))
        sim = _guard_wait(
            sim, p, r_guard[rid], cmd, is_retry, pred=_and(blocked, gate)
        )
        return sim, blocked | fused

    @_gated
    def h_release(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        sim2 = release_resource(spec, sim, p, cmd.i, pred=gate)
        sim2 = set_pc(sim2, p, cmd.next_pc, gate)
        return sim2, jnp.asarray(False)

    def _pool_stamp(sim, k, q, pred=True):
        """Stamp q's grab order on its first units (LIFO victim order)."""
        fresh = dyn.dget2(sim.pools.held, k, q) <= 0.0
        if pred is not True:
            fresh = fresh & pred
        pools = sim.pools._replace(
            held_seq=dyn.dset2(sim.pools.held_seq, k, q,
                dyn.dget(sim.pools.next_seq, k), fresh
            ),
            next_seq=dyn.dadd(sim.pools.next_seq, k, 1, fresh),
        )
        return sim._replace(pools=pools)

    def _pool_acquire_impl(sim: Sim, p, cmd: pr.Command, is_retry, mug,
                           gate=True):
        """Greedy acquire (parity: cmi_pool_acquire_inner,
        `src/cmb_resourcepool.c:362-533`): take available units NOW, then
        (preempt variant) mug strictly-lower-priority holders lowest-prio-
        first / LIFO, then pend for the remainder.  pend_f carries the
        remaining claim; pend_f2 the pre-call holding for abort rollback."""
        k = cmd.i
        rem = cmd.f
        init_held = jnp.where(
            is_retry, dyn.dget(sim.procs.pend_f2, p), dyn.dget2(sim.pools.held, k, p)
        )

        # greedy grab (the reference pool has no no-jump-ahead gate: new
        # callers race for available units; FIFO applies to the wait line)
        take = jnp.clip(rem, 0.0, dyn.dget(sim.pools.level, k))
        sim = _pool_stamp(sim, k, p, pred=gate)
        sim = sim._replace(
            pools=sim.pools._replace(
                level=dyn.dadd(sim.pools.level, k, -take, gate),
                held=dyn.dadd2(sim.pools.held, k, p, take, gate),
            )
        )
        rem = rem - take

        if mug:
            n_procs = sim.procs.prio.shape[0]
            pididx = jnp.arange(n_procs)

            def can_mug(carry):
                sim, rem = carry
                vmask = (
                    (dyn.dget(sim.pools.held, k) > 0.0)
                    & (sim.procs.prio < dyn.dget(sim.procs.prio, p))
                    & (pididx != p)
                )
                return _and((rem > 0.0) & jnp.any(vmask), gate)

            def mug_one(carry):
                sim, rem = carry
                vmask = (
                    (dyn.dget(sim.pools.held, k) > 0.0)
                    & (sim.procs.prio < dyn.dget(sim.procs.prio, p))
                    & (pididx != p)
                )
                # lowest priority first, then LIFO (latest grab first)
                vprio = jnp.min(
                    jnp.where(vmask, sim.procs.prio, jnp.iinfo(jnp.int32).max)
                )
                m2 = vmask & (sim.procs.prio == vprio)
                vseq = jnp.max(jnp.where(m2, dyn.dget(sim.pools.held_seq, k), -1))
                v = _argmax32(m2 & (dyn.dget(sim.pools.held_seq, k) == vseq)).astype(_I)
                loot = dyn.dget2(sim.pools.held, k, v)
                used = jnp.minimum(loot, rem)
                surplus = loot - used
                sim = sim._replace(
                    pools=sim.pools._replace(
                        held=dyn.dadd2(
                            dyn.dset2(sim.pools.held, k, v, 0.0), k, p, used
                        ),
                        level=dyn.dadd(sim.pools.level, k, surplus),
                    )
                )
                # victim loses everything and resumes with PREEMPTED
                sim = _abort_wait(spec, sim, v, pr.PREEMPTED)
                sim = _schedule_wake(sim, True, v, pr.PREEMPTED)
                return sim, rem - used

            sim, rem = _bounded_while(
                can_mug, mug_one, (sim, rem), spec.n_procs
            )

        done = rem <= 0.0
        fused = (cmd.tag == pr.C_POOL_ACQ_HOLD) | (
            cmd.tag == pr.C_POOL_PRE_HOLD
        )
        in_use = p_cap[k] - dyn.dget(sim.pools.level, k)
        sim = sim._replace(
            pools=sim.pools._replace(
                acc=_record_row_if(
                    p_rec, sim.pools.acc, k, sim.clock, in_use, gate
                )
            )
        )
        # leftovers may satisfy the next waiter — signaled ONLY on success
        # (parity: cmi_pool_acquire_inner signals after completing a grab;
        # signaling from a still-blocked partial grab would ping-pong
        # wakes between starved waiters forever)
        sim = _guard_signal(sim, p_guard[k], pred=_and(done, gate), spec=spec)
        # fused success: the pre-drawn hold (f3 rides the pend through a
        # blocked wait), armed after the signal like h_queue
        sim = _schedule_wake(
            sim, _and(fused & done, gate), p, pr.SUCCESS,
            t=sim.clock + jnp.maximum(cmd.f3, 0.0),
        )
        sim = set_pc(sim, p, cmd.next_pc, _and(done, gate))
        sim = _guard_wait(
            sim,
            p,
            p_guard[k],
            cmd._replace(f=rem, f2=init_held),
            is_retry,
            pred=_and(~done, gate),
        )
        return sim, ~done | fused

    @_gated
    def h_pool_acquire(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        return _pool_acquire_impl(sim, p, cmd, is_retry, mug=False, gate=gate)

    @_gated
    def h_pool_preempt(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        return _pool_acquire_impl(sim, p, cmd, is_retry, mug=True, gate=gate)

    @_gated
    def h_pool_release(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        sim2 = release_pool(spec, sim, p, cmd.i, cmd.f, pred=gate)
        sim2 = set_pc(sim2, p, cmd.next_pc, gate)
        return sim2, jnp.asarray(False)

    def _buffer_xfer_impl(sim: Sim, p, cmd: pr.Command, is_retry, getting,
                          gate=True):
        """Greedy partial-fulfillment transfer shared by get/put (parity:
        cmb_buffer_get/_put, `src/cmb_buffer.c:194-346`): move what fits
        now, wait for the remainder; an aborted wait keeps the partial
        amount and the continuation reads it via api.got.

        ``getting`` is a TRACED scalar (cmd.tag == C_BUF_GET): both
        dispatch slots alias one handler, so the impl traces once and
        the verbs differ in a few scalar selects (signal order is
        other-then-my for both, so no wake-seq hazard).

        Signals: opposite guard on any progress (the transfer freed space /
        added content for the other side); SAME-side guard only on
        completion — a partial grab leaves this side drained/full, so a
        same-side wake could only spin (and a zero-progress re-signal
        would ping-pong wakes between starved waiters forever)."""
        b = cmd.i
        rem = cmd.f
        total = jnp.where(is_retry, dyn.dget(sim.procs.pend_f2, p), cmd.f)
        level = dyn.dget(sim.buffers.level, b)
        room = jnp.where(getting, level, b_cap[b] - level)
        moved = jnp.clip(rem, 0.0, room)
        level2 = level + jnp.where(getting, -moved, moved)
        rem2 = rem - moved
        done = rem2 <= 0.0
        my_guard = jnp.where(getting, b_front[b], b_rear[b])
        other_guard = jnp.where(getting, b_rear[b], b_front[b])
        sim = sim._replace(
            buffers=Buffers(
                level=dyn.dset(sim.buffers.level, b, level2, gate),
                acc=_record_row_if(
                    b_rec, sim.buffers.acc, b, sim.clock, level2, gate
                ),
            )
        )
        sim = _guard_signal(sim, other_guard, pred=_and(moved > 0.0, gate), spec=spec)
        # pass leftover wake along on completion only
        sim = _guard_signal(sim, my_guard, pred=_and(done, gate), spec=spec)
        sim = sim._replace(
            procs=sim.procs._replace(
                got=dyn.dset(sim.procs.got, p, total, _and(done, gate))
            )
        )
        fused = (cmd.tag == pr.C_BUF_GET_HOLD) | (
            cmd.tag == pr.C_BUF_PUT_HOLD
        )
        sim = _schedule_wake(
            sim, _and(fused & done, gate), p, pr.SUCCESS,
            t=sim.clock + jnp.maximum(cmd.f3, 0.0),
        )
        sim = set_pc(sim, p, cmd.next_pc, gate)
        sim = _guard_wait(
            sim, p, my_guard, cmd._replace(f=rem2, f2=total), is_retry,
            pred=_and(~done, gate),
        )
        return sim, ~done | fused

    @_gated
    def h_buffer(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        getting = (cmd.tag == pr.C_BUF_GET) | (
            cmd.tag == pr.C_BUF_GET_HOLD
        )
        return _buffer_xfer_impl(
            sim, p, cmd, is_retry, getting=getting, gate=gate,
        )

    @_gated
    def h_pq_put(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        qid = cmd.i
        n_live = jnp.sum(dyn.dget(sim.pqueues.live, qid).astype(_I))
        may = is_retry | gd.is_empty(sim.procs.pend_guard, pq_rear[qid])
        full = (n_live >= pq_cap[qid]) | ~may
        ok = _and(~full, gate)
        # lowest free column — several columns tie at True; argmax
        # tie-breaks are backend-dependent under Mosaic (first_true32)
        free_col = dyn.first_true32(
            ~dyn.dget(sim.pqueues.live, qid)
        ).astype(_I)
        pq2 = PQueues(
            items=dyn.dset2(sim.pqueues.items, qid, free_col, cmd.f, ok),
            prio=dyn.dset2(sim.pqueues.prio, qid, free_col, cmd.f2, ok),
            seq=dyn.dset2(
                sim.pqueues.seq, qid, free_col,
                dyn.dget(sim.pqueues.next_seq, qid), ok,
            ),
            live=dyn.dset2(sim.pqueues.live, qid, free_col, True, ok),
            next_seq=dyn.dadd(sim.pqueues.next_seq, qid, 1, ok),
            acc=_record_row_if(
                pq_rec, sim.pqueues.acc, qid, sim.clock,
                (n_live + 1).astype(_R), ok,
            ),
        )
        sim = sim._replace(pqueues=pq2)
        # put frees no slots: only the getter side can newly proceed
        sim = _guard_signal(sim, pq_front[qid], pred=ok, spec=spec)
        fused = cmd.tag == pr.C_PQ_PUT_HOLD
        sim = _schedule_wake(
            sim, fused & ok, p, pr.SUCCESS,
            t=sim.clock + jnp.maximum(cmd.f3, 0.0),
        )
        sim = set_pc(sim, p, cmd.next_pc, gate)
        sim = _guard_wait(
            sim, p, pq_rear[qid], cmd, is_retry, pred=_and(full, gate)
        )
        return sim, full | fused

    @_gated
    def h_pq_get(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        qid = cmd.i
        live = dyn.dget(sim.pqueues.live, qid)
        may = is_retry | gd.is_empty(sim.procs.pend_guard, pq_front[qid])
        empty = ~jnp.any(live) | ~may
        n_live = jnp.sum(live.astype(_I))
        # highest priority, then FIFO
        neg_inf = jnp.asarray(-jnp.inf, _R)
        p_best = jnp.max(jnp.where(live, dyn.dget(sim.pqueues.prio, qid), neg_inf))
        m = live & (dyn.dget(sim.pqueues.prio, qid) == p_best)
        s_min = jnp.min(
            jnp.where(m, dyn.dget(sim.pqueues.seq, qid), jnp.iinfo(jnp.int32).max)
        )
        col = _argmax32(m & (dyn.dget(sim.pqueues.seq, qid) == s_min)).astype(_I)
        item = dyn.dget2(sim.pqueues.items, qid, col)
        ok = _and(~empty, gate)
        pq2 = sim.pqueues._replace(
            live=dyn.dset2(sim.pqueues.live, qid, col, False, ok),
            acc=_record_row_if(
                pq_rec, sim.pqueues.acc, qid, sim.clock,
                (n_live - 1).astype(_R), ok,
            ),
        )
        sim = sim._replace(
            pqueues=pq2,
            procs=sim.procs._replace(
                got=dyn.dset(sim.procs.got, p, item, ok)
            ),
        )
        sim = _guard_signal(sim, pq_rear[qid], pred=ok, spec=spec)
        sim = _guard_signal(sim, pq_front[qid], pred=ok, spec=spec)
        fused = cmd.tag == pr.C_PQ_GET_HOLD
        sim = _schedule_wake(
            sim, fused & ok, p, pr.SUCCESS,
            t=sim.clock + jnp.maximum(cmd.f3, 0.0),
        )
        sim = set_pc(sim, p, cmd.next_pc, gate)
        sim = _guard_wait(
            sim, p, pq_front[qid], cmd, is_retry, pred=_and(empty, gate)
        )
        return sim, empty | fused

    @_gated
    def h_cond_wait(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        """First issue always blocks until a signal (parity: the reference's
        guard wait enqueues + yields unconditionally); a signal-driven retry
        re-checks the predicate and re-waits if it no longer holds (the
        documented spurious-wakeup contract, handled inside the framework)."""
        cid = cmd.i
        satisfied = _cond_satisfied(spec, sim, cid, p)
        proceed = is_retry & satisfied
        sim = set_pc(sim, p, cmd.next_pc, gate)
        sim = _guard_wait(
            sim, p, c_guard[cid], cmd, is_retry, pred=_and(~proceed, gate)
        )
        return sim, ~proceed

    @_gated
    def h_wait_proc(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        tgt = cmd.i
        finished = dyn.dget(sim.procs.status, tgt) == pr.FINISHED
        # already finished: yield anyway and deliver the target's exit
        # signal (SUCCESS or STOPPED) through an immediate wakeup, so the
        # continuation sees the same signal either way
        sim = _schedule_wake(
            sim, _and(finished, gate), p, dyn.dget(sim.procs.exit_sig, tgt)
        )
        sim = sim._replace(
            procs=sim.procs._replace(
                await_pid=dyn.dset(
                    sim.procs.await_pid, p, tgt, _and(~finished, gate)
                )
            )
        )
        return set_pc(sim, p, cmd.next_pc, gate), jnp.asarray(True)

    @_gated
    def h_wait_evt(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        """Wait for event handle cmd.i to be dispatched (parity:
        cmb_process_wait_event, `include/cmb_process.h:374`).  A dead
        handle (already fired or cancelled) delivers CANCELLED through an
        immediate wakeup, mirroring wait_process's already-finished path."""
        h = cmd.i
        valid = ev._valid(sim.events, h)
        sim = _schedule_wake(
            sim, _and(~valid, gate), p, jnp.asarray(pr.CANCELLED, _I)
        )
        sim = sim._replace(
            procs=sim.procs._replace(
                await_evt=dyn.dset(
                    sim.procs.await_evt, p, h, _and(valid, gate)
                )
            )
        )
        return set_pc(sim, p, cmd.next_pc, gate), jnp.asarray(True)

    @_gated
    def h_invalid(sim: Sim, p, cmd: pr.Command, is_retry, gate=True):
        """Stub for commands whose component type the model never declared
        — keeps the traced handler table small (compile time scales with
        it) while turning stray commands into a contained failure."""
        return _set_err(sim, gate, ERR_USER), jnp.asarray(True)

    def component_gate(pred, h):
        return h if pred else h_invalid

    has_q = bool(spec.queues)
    has_r = bool(spec.resources)
    handlers = [
        h_hold,                                  # C_HOLD
        h_exit,                                  # C_EXIT
        h_jump,                                  # C_JUMP
        component_gate(has_q, h_queue),                    # C_PUT
        component_gate(has_q, h_queue),                    # C_GET
        component_gate(has_r, h_acquire),                  # C_ACQUIRE
        component_gate(has_r, h_release),                  # C_RELEASE
        component_gate(has_r, h_preempt),                  # C_PREEMPT
        component_gate(bool(spec.pools), h_pool_acquire),  # C_POOL_ACQ
        component_gate(bool(spec.pools), h_pool_release),  # C_POOL_REL
        component_gate(bool(spec.buffers), h_buffer),      # C_BUF_GET
        component_gate(bool(spec.buffers), h_buffer),      # C_BUF_PUT
        component_gate(bool(spec.pqueues), h_pq_put),      # C_PQ_PUT
        component_gate(bool(spec.pqueues), h_pq_get),      # C_PQ_GET
        component_gate(bool(spec.conditions), h_cond_wait),  # C_COND_WAIT
        h_wait_proc,                             # C_WAIT_PROC
        component_gate(bool(spec.pools), h_pool_preempt),  # C_POOL_PRE
        h_wait_evt,                              # C_WAIT_EVT
        component_gate(has_q, h_queue),                    # C_PUT_HOLD
        component_gate(has_q, h_queue),                    # C_GET_HOLD
        component_gate(has_r, h_acquire),                  # C_ACQ_HOLD
        component_gate(has_r, h_preempt),                  # C_PRE_HOLD
        component_gate(bool(spec.pools), h_pool_acquire),  # C_POOL_ACQ_HOLD
        component_gate(bool(spec.pools), h_pool_preempt),  # C_POOL_PRE_HOLD
        component_gate(bool(spec.buffers), h_buffer),      # C_BUF_GET_HOLD
        component_gate(bool(spec.buffers), h_buffer),      # C_BUF_PUT_HOLD
        component_gate(bool(spec.pqueues), h_pq_put),      # C_PQ_PUT_HOLD
        component_gate(bool(spec.pqueues), h_pq_get),      # C_PQ_GET_HOLD
    ]

    if used_tags is None:
        def apply_command(sim: Sim, p, cmd: pr.Command, is_retry=False):
            return _vswitch(
                jnp.clip(cmd.tag, 0, pr.N_COMMANDS - 1), handlers, sim, p,
                cmd, jnp.asarray(is_retry), batched=batched,
            )
        apply_command.handler_items = list(enumerate(handlers))
        return apply_command

    # Specialized table: trace only the handlers this model's blocks can
    # emit (every traced lax.switch branch *executes* for every lane under
    # vmap — dead handlers are pure hot-loop cost).  Unknown tags land on
    # h_invalid -> ERR_USER, a contained failure, never corruption.
    used = sorted(t for t in used_tags if 0 <= t < pr.N_COMMANDS)
    table = [handlers[t] for t in used] + [h_invalid]
    lut_vals = [len(used)] * pr.N_COMMANDS
    for j, t in enumerate(used):
        lut_vals[t] = j
    lut = _ConstTable(lut_vals, jnp.int32)

    def apply_command(sim: Sim, p, cmd: pr.Command, is_retry=False):
        idx = lut[jnp.clip(cmd.tag, 0, pr.N_COMMANDS - 1)]
        return _vswitch(
            idx, table, sim, p, cmd, jnp.asarray(is_retry), batched=batched,
        )

    apply_command.handler_items = list(enumerate(handlers))
    return apply_command


# --- the dispatcher -----------------------------------------------------------


def _defers(spec: ModelSpec, defer_boundary: Optional[bool]) -> bool:
    """Whether a step or cond built with ``defer_boundary`` defers
    boundary dispatches, decided at trace time (see :func:`make_step`)."""
    if not spec.boundary_pcs:
        return False
    if defer_boundary is None:
        return config.KERNEL_MODE
    return defer_boundary


def make_step(
    spec: ModelSpec,
    defer_boundary: Optional[bool] = None,
    batched: bool = False,
):
    """Build ``step(sim) -> sim`` dispatching exactly one event.

    ``defer_boundary`` (models with boundary blocks only): a resume whose
    target block is a boundary block is not dispatched; the lane raises
    ``boundary_pending`` and the block's body is a stub in this trace,
    for :func:`make_boundary_apply` to run the dispatch batched.  ``None``
    defers exactly in the kernel trace (``config.KERNEL_MODE``).

    ``batched``: the caller vmaps this step (directly or as the body of
    a vmapped while loop).  Its three dispatch levels — event kind,
    block, command handler — then take :func:`_vswitch`'s merge-free
    form instead of a ``lax.switch`` that select-merges every Sim leaf
    per level; the lanes are bitwise the same either way.  Leave it
    False for a scalar (unbatched) run, which then executes one branch
    per level.

    The step is ``step(sim, gate=None)``.  A false ``gate`` makes it a
    no-op whose output is its input bitwise: the lanes' loop
    (:func:`make_lanes_run`) passes each lane's liveness there, and
    :func:`make_boundary_apply` each lane's ``boundary_pending``.
    Without a gate the step traces as it did before the argument
    existed."""
    blocks = list(spec.blocks)

    def _defer():
        return _defers(spec, defer_boundary)

    # The handler table is specialized to the tags the model's blocks can
    # emit, which requires a Sim to trace them against — so it is built
    # lazily at the first (tracing) call and cached.  Static per spec:
    # retraces at other batch shapes reuse it.
    _cache: dict = {}

    def apply_command(sim: Sim, p, cmd: pr.Command, is_retry=False):
        if "apply" not in _cache:
            _cache["apply"] = _make_apply(
                spec, _used_tags_for(spec, sim), batched
            )
        return _cache["apply"](sim, p, cmd, is_retry)

    def _boundary_stub(sim, p, sig):
        # placeholder for a boundary block in a deferring trace: the
        # block is unreachable there (dispatch defers it to the boundary
        # phase; mid-chain entry is flagged ERR_BOUNDARY before the
        # switch), so its body — the whole point of the marker — stays
        # out of the per-event jaxpr
        return sim, pr.exit_()

    def run_block(sim: Sim, p, sig):
        table = blocks
        if _defer():
            table = [
                _boundary_stub if pc in spec.boundary_pcs else b
                for pc, b in enumerate(blocks)
            ]
        return _vswitch(
            jnp.clip(dyn.dget(sim.procs.pc, p), 0, len(blocks) - 1),
            table,
            sim,
            p,
            sig,
            batched=batched,
        )

    def resume(sim: Sim, p, sig, gate=True):
        """Resume process p with a signal: retry or abort a pending
        command, then chain blocks until something yields.

        ``gate`` (scalar bool) disables the resume entirely: every
        preamble write is pred-gated by it and the chain loop starts
        pre-yielded, so a gated-off lane's output IS the input — no
        caller-side merge needed.  (The while loop's own freeze
        semantics — lanelast's per-lane freeze in the kernel, jax's
        batched-while carry selects under vmap, a plain false condition
        unbatched — already guarantee the loop body writes nothing when
        the condition is false from iteration 0.)"""
        # statics: machinery a model cannot exercise stays out of the
        # trace entirely (the flags derive from the inferred command-tag
        # set, memoized per spec)
        may_pend = _may_pend(spec, sim)

        # any remaining wake event is stale once we are resumed
        sim = _cancel_wake(sim, p, pred=gate)
        # ANY delivery ends a wait-on-process / wait-on-event: a direct
        # user-timer wake bypasses the abort arm, and a surviving
        # await_pid/await_evt would spuriously re-resume this process when
        # the target later finishes/fires (parity:
        # cmi_process_cancel_awaiteds runs on every signal delivery,
        # `src/cmb_process.c:694-748`); statically absent when the model
        # cannot wait on processes/events
        procs2 = sim.procs
        if _may_wait_procs(spec, sim):
            procs2 = procs2._replace(
                await_pid=dyn.dset(procs2.await_pid, p, -1, gate)
            )
        if _may_wait_events(spec, sim):
            procs2 = procs2._replace(
                await_evt=dyn.dset(procs2.await_evt, p, -1, gate)
            )
        sim = sim._replace(procs=procs2)

        if may_pend:
            pend = pr.Command(
                dyn.dget(sim.procs.pend_tag, p),
                dyn.dget(sim.procs.pend_f, p),
                dyn.dget(sim.procs.pend_f2, p),
                dyn.dget(sim.procs.pend_f3, p),
                dyn.dget(sim.procs.pend_i, p),
                dyn.dget(sim.procs.pend_pc, p),
            )
            has_pend = pend.tag != pr.NO_PEND
            ok_wake = jnp.asarray(sig, _I) == pr.SUCCESS
            gated = has_pend if gate is True else (has_pend & gate)

            # Unwait-BEFORE-cleanup, as _abort_wait orders it: _clear_pend
            # must clear p's guard membership before _abort_cleanup's pool
            # rollback signals the pool guard, or p steals its own rollback
            # wake (best_waiter would still see p enrolled) and the waiter
            # the signal was meant for starves.  _abort_cleanup reads the
            # pend from the snapshot above, so clearing first is safe.
            # (_clear_pend also covers the SUCCESS-wake path: a user timer
            # with sig=SUCCESS can wake a pended process directly, and the
            # cleared pend_guard IS the dense-guard removal — no zombie
            # membership can survive.)
            sim = _clear_pend(sim, p, pred=gate)
            # non-SUCCESS wake of a pended process: abort the wait — the
            # signal flows to the continuation block below.  Sequential
            # predication instead of branch-and-merge: the preamble above
            # already did the unwait bookkeeping (wake cancel, await
            # clears) for EVERY path, so the abort arm is just the
            # command-specific cleanup, pred-gated; for pool/buffer-free
            # models it traces to nothing.  A SUCCESS wake re-attempts the
            # pended command as the chain's first iteration (use_pend) —
            # handlers are traced only there.
            sim = _abort_cleanup(
                spec, sim, p, pend, sig, pred=gated & ~ok_wake
            )
            use_pend0 = has_pend & ok_wake
        else:
            # nothing can ever pend: no retry arm, no use_pend merge in
            # the chain body, no pend bookkeeping
            pend = None
            use_pend0 = jnp.asarray(False)
        yielded0 = (
            jnp.asarray(False) if gate is True else ~jnp.asarray(gate)
        )

        def cond(carry):
            sim, sig, yielded, n, use_pend = carry
            alive = (dyn.dget(sim.procs.status, p) == pr.RUNNING) & (sim.err == 0)
            return ~yielded & alive & (n < MAX_CHAIN)

        def body(carry):
            sim, sig, _, n, use_pend = carry
            # Draw-word hoist (bits.stash_arm): every block branch's first
            # counter tick shares one traced Threefry keyed on the
            # pre-dispatch rng tracers; branches are exclusive per lane,
            # so one block of ~120 scalar ops serves every draw site in
            # the switch (values bit-identical, lazily traced — see
            # random/bits.py).  The XLA cond arm below traces blocks in a
            # sub-trace where the key cannot match; it simply misses.
            rb.stash_arm(sim.rng)
            try:
                if not may_pend:
                    # no retry arm exists: the block always runs and its
                    # command applies directly (no use_pend merge at all)
                    if _defer():
                        in_b = boundary_table[dyn.dget(sim.procs.pc, p)] != 0
                        sim = _set_err(sim, in_b, ERR_BOUNDARY)
                    sim2, cmd = run_block(sim, p, sig)
                elif config.KERNEL_MODE:
                    if _defer():
                        # boundary blocks may only be entered by dispatch
                        # (which the kernel defers to the chunk driver) —
                        # reaching one mid-chain would run its stub, so it
                        # fails the lane loudly instead
                        in_b = boundary_table[dyn.dget(sim.procs.pc, p)] != 0
                        sim = _set_err(sim, in_b & ~use_pend, ERR_BOUNDARY)
                    # both arms run under vmap regardless; the explicit
                    # bwhere-fold keeps bool leaves off Mosaic's unsupported
                    # i1 select_n path
                    s_blk, c_blk = run_block(sim, p, sig)
                    sim2 = _tree_select(use_pend, sim, s_blk)
                    cmd = jax.tree.map(
                        lambda a, b: dyn.bwhere(use_pend, a, b), pend, c_blk
                    )
                else:
                    if _defer():
                        in_b = boundary_table[dyn.dget(sim.procs.pc, p)] != 0
                        sim = _set_err(sim, in_b & ~use_pend, ERR_BOUNDARY)
                    # scalar/XLA path keeps lax.cond: an unbatched pend-retry
                    # must not execute the block (user side effects fire once)
                    sim2, cmd = lax.cond(
                        use_pend,
                        lambda s: (s, pend),
                        lambda s: run_block(s, p, sig),
                        sim,
                    )
            finally:
                rb.stash_clear()
            sim2, yielded = apply_command(
                sim2, p, cmd,
                is_retry=use_pend if may_pend else False,
            )
            return (
                sim2,
                jnp.asarray(pr.SUCCESS, _I),
                yielded,
                n + 1,
                jnp.asarray(False),
            )

        chain_bound = spec.max_chain if config.KERNEL_MODE else MAX_CHAIN
        sim, _, yielded, n = _bounded_while(
            cond,
            body,
            (
                sim,
                jnp.asarray(sig, _I),
                yielded0,
                jnp.zeros((), _I),
                use_pend0,
            ),
            chain_bound,
        )[:4]
        # runaway containment: in kernel mode a process still live and
        # unyielded after spec.max_chain chained commands is flagged the
        # same way a MAX_CHAIN overrun is on the XLA path
        alive_end = (dyn.dget(sim.procs.status, p) == pr.RUNNING) & (
            sim.err == 0
        )
        runaway = (
            (~yielded & alive_end)
            if config.KERNEL_MODE
            else (n >= MAX_CHAIN)
        )
        sim = _set_err(sim, runaway, ERR_CHAIN_RUNAWAY)
        if sim.metrics is not None:
            # n == 0 exactly when the resume was gated off, so the hook's
            # own ran-gate preserves the "gated-off resume output IS the
            # input" contract on_proc rests on (per-lane values; the
            # masked adds contribute zero there)
            sim = obs_metrics.on_resume(sim, n, use_pend0)
        return sim

    def on_proc(sim: Sim, subj, arg, gate):
        # NO merge at all: resume pred-gates every preamble write by
        # (event-found & target-alive) and starts the chain pre-yielded
        # when gated off, so a gated-off lane's resume output IS the
        # input.  (Each merge layer here used to cost a select per Sim
        # leaf, because the chain while returns every carried leaf as a
        # fresh value.)
        alive = dyn.dget(sim.procs.status, subj) == pr.RUNNING
        return resume(sim, subj, arg, gate=alive & gate)

    user_handlers = [
        (lambda fn: (
            lambda sim, subj, arg, gate:
            _tree_select(gate, fn(sim, subj, arg), sim)
        ))(fn)
        for fn in spec.user_handlers
    ]
    dispatch_fns = [on_proc, on_proc] + user_handlers  # K_PROC, K_TIMER

    boundary_table = (
        _ConstTable(
            [
                1 if pc in spec.boundary_pcs else 0
                for pc in range(len(spec.blocks))
            ],
            _I,
        )
        if spec.boundary_pcs
        else None
    )

    def step(sim: Sim, gate=None) -> Sim:
        event, take_e, take_w = ev.peek_merged(
            sim.events, sim.wakes, sim.procs.prio, K_PROC
        )
        # every write below is gated by ``found`` (the dispatch's
        # handlers included) or folds the gate in itself
        found = event.found if gate is None else event.found & gate
        if _defer():
            # a resume whose target block is a boundary block is NOT
            # dispatched here: the event stays in its table, the lane
            # raises boundary_pending, and the boundary phase applies one
            # full engine step to it (make_boundary_apply: MXU physics
            # batched over lanes — parity with the reference's
            # in-coroutine CUDA launches)
            pc_t = dyn.dget(
                sim.procs.pc, jnp.maximum(event.subj, 0)
            )
            is_b = boundary_table[pc_t] != 0
            boundary = found & (event.kind <= K_TIMER) & is_b
            proceed = found & ~boundary
            not_deferred = ~boundary
            if gate is not None:
                boundary = jnp.where(gate, boundary, sim.boundary_pending)
            sim = sim._replace(boundary_pending=boundary)
        else:
            proceed = found
            not_deferred = True
        out_of_events = ~event.found  # BEFORE the boundary defer masks it
        if gate is not None:
            out_of_events = out_of_events & gate
            not_deferred = _and(not_deferred, gate)
        event = event._replace(found=proceed)
        # event-set occupancy BEFORE the consume (the popped event still
        # pends): the high-water gauge of how close this replication came
        # to ERR_EVENT_OVERFLOW.  Computed only when a registry is carried
        # — the [CAP]+[P] reductions stay out of the metrics-off trace.
        if sim.metrics is not None:
            occupancy = ev.length(sim.events) + jnp.sum(
                jnp.isfinite(sim.wakes.time).astype(_I), dtype=_I
            )
        es2, wk2 = ev.consume_merged(
            sim.events, sim.wakes, take_e, take_w, proceed
        )
        sim = sim._replace(
            events=es2,
            wakes=wk2,
            clock=jnp.where(proceed, event.time, sim.clock),
            n_events=sim.n_events
            + jnp.where(proceed, 1, 0).astype(config.COUNT),
        )
        # the flight-recorder/metrics hooks return sim UNCHANGED (the
        # same object — zero traced ops) when the Sim carries no
        # ring/registry; with one, this is THE dispatch-site write
        sim = obs_trace.emit(
            sim, event.time, event.subj, event.kind, event.arg, proceed
        )
        if sim.metrics is not None:
            sim = obs_metrics.on_dispatch(sim, event.kind, occupancy, proceed)
        if _may_wait_events(spec, sim):
            # wake event-waiters before the action runs (reference order,
            # `src/cmb_event.c:312-314`); statically absent from models
            # that never issue wait_event.  The stale-handle arm can
            # schedule wakes even on an empty pop, so "out of events" is
            # judged AFTER the scan (else a cancel that drains the set
            # would strand its waiter forever).
            sim = _dispatch_evt_wakes(
                sim, event.handle, event.found, not_deferred
            )
            sim = sim._replace(
                done=sim.done
                | (
                    out_of_events
                    & ev.is_empty(sim.events)
                    & ev.wakes_empty(sim.wakes)
                )
            )
        else:
            sim = sim._replace(done=sim.done | out_of_events)
        return _vswitch(
            jnp.clip(event.kind, 0, len(dispatch_fns) - 1),
            dispatch_fns,
            sim,
            event.subj,
            event.arg,
            event.found,
            batched=batched,
        )

    return step


def make_cond(spec: ModelSpec, t_end: Optional[float] = None,
              defer_boundary: Optional[bool] = None):
    """Build the per-replication liveness predicate ``cond(sim) -> bool``
    used by :func:`make_run` (and by the Pallas kernel runner, which hoists
    the while-loop out of vmap and needs the same predicate).
    ``defer_boundary`` as in :func:`make_step`: a deferring cond holds a
    lane whose next dispatch is a boundary block."""

    def cond(sim: Sim):
        empty = ev.is_empty(sim.events) & ev.wakes_empty(sim.wakes)
        if _may_wait_events(spec, sim):
            # an event-waiter whose handle died with the set (a cancel was
            # the run's last activity) still needs one more step: the
            # stale-handle scan there schedules its CANCELLED wake
            stranded = jnp.any(
                (sim.procs.await_evt >= 0)
                & (sim.procs.status == pr.RUNNING)
            )
            out_of_work = empty & ~stranded
        else:
            out_of_work = empty
        live = ~sim.done & (sim.err == 0) & ~out_of_work
        if _defers(spec, defer_boundary):
            # a lane whose next dispatch is a boundary block holds until
            # the boundary phase steps it (make_boundary_apply)
            live = live & ~sim.boundary_pending
        # horizon: a Sim carrying a per-lane ``t_stop`` leaf (the
        # heterogeneous-wave path) reads it INSTEAD of the static
        # ``t_end`` — ``t_stop = t_end`` reproduces the static check's
        # decisions bit-for-bit (same compare on the same values), and
        # ``t_stop = +inf`` reproduces ``t_end=None`` (the conjunct is
        # identically true); ``-inf`` is the dead-on-arrival pad lane
        lim = sim.t_stop if sim.t_stop is not None else t_end
        if lim is not None:
            nxt = jnp.minimum(
                ev.min_time(sim.events), jnp.min(sim.wakes.time)
            )
            live = live & ((nxt <= lim) | (empty & ~out_of_work))
        return live

    return cond


def _packed_carry() -> bool:
    """The one-replication loop's carry layout (:func:`make_run`):
    packed on accelerator backends, where the per-leaf carry's cost was
    measured (BENCH_NOTES round 5 floor probes), per leaf on the CPU.
    Both layouts run the same step and cond, so trajectories are
    bitwise identical either way.  The batched lanes' loop
    (:func:`make_lanes_run`) carries per leaf on every backend."""
    return jax.default_backend() != "cpu"


def make_run(
    spec: ModelSpec,
    t_end: Optional[float] = None,
    max_steps: Optional[int] = None,
):
    """Build ``run(sim) -> sim``: dispatch events until the model stops
    (api.stop), fails, runs out of events, or passes ``t_end``
    (parity: cmb_event_queue_execute; t_end plays the role of the
    user-scheduled end event).

    The while-loop carry layout follows the backend
    (:func:`_packed_carry`): on an accelerator the loop runs the SAME
    step/cond on a carry of a few wide per-dtype buffers instead of the
    Sim's ~50 narrow leaves (core/carry.py, the same packing the Pallas
    chunk loop uses under ``CIMBA_KERNEL_PACK``).  Pack/unpack are
    bitwise-lossless structural ops, so trajectories are identical.
    See docs/11_dispatch_cost.md.

    ``max_steps`` bounds one invocation to at most that many dispatches
    (the bounded-chunk variant, docs/12_streaming.md): the loop carries
    a per-replication step counter and exits when either the liveness
    cond fails or the counter hits the bound, so a host loop can
    re-dispatch the returned Sim until :func:`make_cond` reports it
    done.  Truncation is exact: each lane executes the identical step
    sequence the unbounded loop would, merely split across invocations
    — chunked trajectories are bitwise the monolithic ones (pinned by
    tests/test_stream.py).  ``None`` (the default) keeps today's
    unbounded loop, jaxpr-identical to before this knob existed.

    ``run`` is the one-replication loop and dispatches by ``lax.switch``;
    ``jax.vmap(run)`` gives the same lanes, but a batched caller builds
    :func:`make_lanes_run` instead, whose step dispatches merge-free and
    whose frozen lanes cost no per-iteration select."""
    step = make_step(spec)
    cond = make_cond(spec, t_end)
    if max_steps is not None and max_steps <= 0:
        raise ValueError(f"max_steps must be positive, got {max_steps}")
    if not _packed_carry():
        if max_steps is None:
            def run(sim: Sim) -> Sim:
                return lax.while_loop(cond, step, sim)

            return run

        def run(sim: Sim) -> Sim:
            def kcond(kc):
                return cond(kc[1]) & (kc[0] < max_steps)

            def kbody(kc):
                return kc[0] + jnp.asarray(1, _I), step(kc[1])

            return lax.while_loop(
                kcond, kbody, (jnp.zeros((), _I), sim)
            )[1]

        return run

    from cimba_tpu.core import carry as _carry

    def run(sim: Sim) -> Sim:
        leaves, treedef = jax.tree.flatten(sim)
        plan = _carry.pack_plan(
            [
                jax.ShapeDtypeStruct(jnp.shape(l), jnp.result_type(l))
                for l in leaves
            ],
            lane_last=False,
        )

        def unflatten(bufs):
            return jax.tree.unflatten(
                treedef, _carry.unpack(list(bufs), plan)
            )

        def pstep(bufs):
            return tuple(
                _carry.pack(jax.tree.leaves(step(unflatten(bufs))), plan)
            )

        if max_steps is None:
            def pcond(bufs):
                return cond(unflatten(bufs))

            out = lax.while_loop(
                pcond, pstep, tuple(_carry.pack(leaves, plan))
            )
            return unflatten(out)

        def kcond(kb):
            return cond(unflatten(kb[1])) & (kb[0] < max_steps)

        def kbody(kb):
            return kb[0] + jnp.asarray(1, _I), pstep(kb[1])

        out = lax.while_loop(
            kcond, kbody,
            (jnp.zeros((), _I), tuple(_carry.pack(leaves, plan))),
        )
        return unflatten(out[1])

    return run


def make_lanes_run(
    spec: ModelSpec,
    t_end: Optional[float] = None,
    defer_boundary: Optional[bool] = None,
):
    """Build ``run(sims, kmax=None) -> (steps, sims, live)`` over a
    BATCHED Sim (leading lane axis): the lanes' loop of every batched
    XLA program (:func:`make_chunk`, the boundary chunk's rounds,
    ``runner.experiment``).  It runs outside ``vmap``, on one unbatched
    condition::

        while (steps < kmax) & any(live):
            sims = vmap(step)(sims, gate=live)
            live = vmap(cond)(sims)

    A lane whose :func:`make_cond` is false steps with its gate off,
    and a gated-off step returns its input bitwise (the self-gated
    step, :func:`make_step`), so each lane dispatches exactly the
    events of ``jax.vmap`` over :func:`make_run`.  ``vmap`` of a while
    loop would instead freeze such lanes by a select of every carried
    leaf at every iteration (jax's batched-while rule), and evaluate
    the cond twice.  ``kmax`` (an int or a traced scalar; ``None``: no
    bound) bounds the iterations, so no lane dispatches more; ``steps``
    is the count run, ``live`` each lane's cond on the returned Sim.
    ``defer_boundary`` as in :func:`make_step`.

    The loop carries the Sim per leaf on every backend: on a v5e the
    packed carry's (core/carry.py) per-lane columns cost more to read
    back each step than the selects this loop saves (PERF.md, section
    6)."""
    step = make_step(spec, defer_boundary=defer_boundary, batched=True)
    cond = make_cond(spec, t_end, defer_boundary=defer_boundary)

    def lstep(sim, gate):
        sim = step(sim, gate)
        return sim, cond(sim)

    def run(sims: Sim, kmax=None):
        def lcond(c):
            i, _, live = c
            any_live = jnp.any(live)
            return any_live if kmax is None else (i < kmax) & any_live

        def lbody(c):
            i, sims, live = c
            sims, live = jax.vmap(lstep)(sims, live)
            return i + jnp.asarray(1, _I), sims, live

        return lax.while_loop(
            lcond, lbody, (jnp.zeros((), _I), sims, jax.vmap(cond)(sims))
        )

    return run


# --- chunked dispatch: watchdog-proof runs of any length ---------------------


def make_boundary_apply(spec: ModelSpec):
    """Build ``apply(sims) -> sims`` over a batched Sim (leading lane
    axis): one full engine step — boundary blocks' bodies included — on
    exactly the lanes that hold ``boundary_pending``, which it clears;
    the other lanes pass through unchanged.  The one home of the
    boundary phase: the XLA chunk (:func:`make_chunk`) runs it inside
    its program, the kernel driver (``core.pallas_run``) between Pallas
    chunks.  Since the step is vmapped, a boundary block's bulk work
    (AWACS's scorer) sees every lane at once."""
    xstep = jax.vmap(make_step(spec, defer_boundary=False, batched=True))

    def apply(sims: Sim) -> Sim:
        with jax.named_scope("runner.boundary"):
            pending = sims.boundary_pending
            # the step gated by ``pending``: the other lanes come out
            # bitwise as they went in, with no per-leaf merge
            return xstep(
                sims._replace(boundary_pending=jnp.zeros_like(pending)),
                pending,
            )

    return apply


#: the boundary chunk's counters, in the order of its third output
BOUNDARY_COUNTERS = (
    "boundary_rounds",      # boundary phases run
    "boundary_dispatches",  # boundary-block dispatches (AWACS: dwells)
    "lane_steps_run",       # lanes x steps of the batched loops
    "lane_steps_dispatched",  # of those, steps that dispatched an event
)


def _make_boundary_chunk(spec: ModelSpec, t_end, max_steps: int):
    """:func:`make_chunk` for a model with boundary blocks: rounds of
    the per-event loop, whose step defers boundary dispatches (the lane
    holds with ``boundary_pending``), each followed by the boundary
    phase (:func:`make_boundary_apply`) where some lane is pending.  A
    round's loop ends when every lane is pending or done, or after
    ``spec.boundary_round_steps`` steps: in AWACS every lane's dwells
    fall at the same instants, so a round is one dwell of the wave.
    Each lane dispatches exactly the events, in exactly the order, of
    the per-event path.  ``max_steps`` bounds the chunk's steps: the
    loops' iterations and boundary phases over all its rounds, so no
    lane dispatches more.  Returns ``chunk(sims) -> (sims, any_live,
    counters)``, ``counters`` the :data:`BOUNDARY_COUNTERS` of this
    chunk (config.COUNT)."""
    lanes_run = make_lanes_run(spec, t_end, defer_boundary=True)
    vcond = jax.vmap(make_cond(spec, t_end, defer_boundary=True))
    apply = make_boundary_apply(spec)
    round_steps = spec.boundary_round_steps or max_steps

    def chunk(sims: Sim):
        n = sims.boundary_pending.shape[0]
        _C = config.COUNT.dtype  # resolved in this trace's profile

        def rcond(c):
            used, sims, _ = c
            return (used < max_steps) & jnp.any(vcond(sims))

        def rbody(c):
            used, sims, ctr = c
            limit = jnp.minimum(round_steps, max_steps - used)
            k, sims, _ = lanes_run(sims, limit)
            pending = sims.boundary_pending
            some = jnp.any(pending)
            sims = lax.cond(some, apply, lambda s: s, sims)
            steps = k + some.astype(_I)
            ctr = ctr + jnp.stack([
                some.astype(_C),
                jnp.sum(pending, dtype=_C),
                steps.astype(_C) * n,
                jnp.zeros((), _C),
            ])
            return used + steps, sims, ctr

        ev0 = jnp.sum(sims.n_events, dtype=_C)
        _, sims, ctr = lax.while_loop(
            rcond, rbody,
            (jnp.zeros((), _I), sims, jnp.zeros((4,), _C)),
        )
        ctr = ctr.at[3].set(jnp.sum(sims.n_events, dtype=_C) - ev0)
        return sims, jnp.any(vcond(sims)), ctr

    return chunk


def make_chunk(
    spec: ModelSpec,
    t_end: Optional[float] = None,
    max_steps: int = 1024,
    audit: bool = False,
):
    """Build ``chunk(sims) -> (sims, any_live)`` over a BATCHED Sim
    (leading lane axis): one bounded dispatch chunk (each lane advances
    at most ``max_steps`` events) plus the cheap liveness scalar the
    host loop polls.  Not jitted — callers jit it with donation
    (:func:`make_chunked_run`) or wrap it in ``shard_map`` first
    (``runner.experiment`` composes it with the replication mesh).

    ``audit=True`` (the determinism-audit plane, docs/18_audit.md)
    appends a THIRD output: the per-wave carry-class digest vector
    (:func:`cimba_tpu.obs.audit.sim_digest` over the post-chunk Sim),
    which :func:`drive_chunks` hands to its ``on_digest`` hook at every
    chunk boundary.  Trace-time gated like the flight recorder:
    ``audit=False`` (the default) takes the historical code path —
    the chunk jaxpr is character-identical to one built before the
    knob existed (pinned in tests/test_audit.py).

    The lanes' loop is :func:`make_lanes_run`'s, which carries the Sim
    per leaf on every backend.  A model with boundary blocks gets
    :func:`_make_boundary_chunk`'s program, whose outputs carry its
    counters third, before an audit digest."""
    if max_steps <= 0:
        raise ValueError(f"max_steps must be positive, got {max_steps}")
    if spec.boundary_pcs:
        bchunk = _make_boundary_chunk(spec, t_end, max_steps)
        if not audit:
            return bchunk

        from cimba_tpu.obs import audit as obs_audit

        def audited(sims: Sim):
            out = bchunk(sims)
            return out + (obs_audit.sim_digest(out[0]),)

        return audited

    lanes_run = make_lanes_run(spec, t_end)

    if not audit:
        def chunk(sims: Sim):
            _, sims, live = lanes_run(sims, max_steps)
            return sims, jnp.any(live)

        return chunk

    from cimba_tpu.obs import audit as obs_audit

    def chunk(sims: Sim):
        _, sims, live = lanes_run(sims, max_steps)
        return sims, jnp.any(live), obs_audit.sim_digest(sims)

    return chunk


def make_refill(spec: ModelSpec):
    """Build ``refill(sims, mask, reps, seeds, t_stops, params) ->
    sims``: re-initialize EXACTLY the masked lanes of a batched Sim
    through the same per-lane init path the wave was born from
    (:func:`init_sim` with per-lane seed/horizon columns,
    docs/14_wave_packing.md) and splice the fresh rows into the live
    carry — the lane-recycling primitive behind continuous wave refill
    (docs/22_refill.md).

    Unmasked lanes pass through BIT-IDENTICALLY (a per-leaf masked
    select; leaves are never re-laid-out), so a mid-wave splice cannot
    perturb its wave-mates — and a refilled lane starts from exactly
    the state its solo run would start from, which is what makes a
    refilled request's result bitwise its solo run's (trajectories are
    lane-local under vmap; chunk phase is trajectory-invariant).  The
    batched Sim between chunks is the plain per-leaf pytree, so one
    refill program serves every chunk program of its class, under both
    dtype profiles.

    The wave must carry the per-lane ``t_stop`` leaf (refill waves
    always do — lane death and reclamation are horizon-driven); a
    ``t_stop=-inf`` row retires a lane into reclaimable dead capacity
    (the pad-lane encoding), which is also how cancellation and
    deadline expiry free lanes mid-wave.  Not jitted here — callers
    jit with the Sim DONATED (``runner.experiment._refill_program``),
    so a boundary splice allocates nothing beyond the fresh rows."""

    def refill(sims: Sim, mask, reps, seeds, t_stops, params):
        if sims.t_stop is None:
            raise ValueError(
                "make_refill: the wave carries no per-lane t_stop "
                "leaf — refill needs horizon-carrying waves (the "
                "serving layer always materializes the column on the "
                "refill path; see docs/22_refill.md)"
            )
        fresh = jax.vmap(
            lambda r, s, t, p: init_sim(spec, s, r, p, t_stop=t)
        )(reps, seeds, t_stops, params)

        def sel(a, b):
            m = mask.reshape(mask.shape + (1,) * (a.ndim - 1))
            return jnp.where(m, a, b)

        return jax.tree.map(sel, fresh, sims)

    return refill


def make_lanes_live(spec: ModelSpec, t_end: Optional[float] = None):
    """Build ``live(sims) -> bool[L]`` over a batched Sim: each lane's
    :func:`make_cond` liveness — the per-lane readback the refill
    driver (and the live lane-occupancy gauge, docs/22_refill.md)
    polls at chunk boundaries to learn which lanes died this chunk.
    Read-only and tiny; callers jit WITHOUT donation so the readback
    never races the next chunk's buffer donation."""
    cond = make_cond(spec, t_end)

    def live(sims: Sim):
        return jax.vmap(cond)(sims)

    return live


def drive_chunks(
    chunk,
    sims: Sim,
    *,
    poll_every: int = 4,
    on_chunk=None,
    on_state=None,
    on_state_every: int = 0,
    max_chunks: Optional[int] = None,
    n0: int = 0,
    on_digest=None,
    on_boundary=None,
    on_counters=None,
) -> Sim:
    """Host loop over a jitted, donated ``chunk(sims) -> (sims,
    any_live)``: re-dispatch until every lane is done.

    The ``any_live`` scalar is polled ASYNCHRONOUSLY: up to
    ``poll_every`` chunks are queued before the oldest flag is read, so
    jax's async dispatch keeps the device pipeline full instead of
    round-tripping a host sync per chunk.  Over-dispatched chunks after
    global completion are exact no-ops (every lane's cond is false, the
    while loop exits at iteration 0, and donation aliases the buffers
    straight through), so late polling never perturbs the result.

    ``on_chunk(n)`` fires after each dispatch (a caller's progress or
    watchdog heartbeat).  ``on_state(sims, n)`` fires every
    ``on_state_every`` chunks with the CURRENT batched Sim, *before* it
    is donated into the next chunk — the checkpoint hook (chunk
    boundaries are the natural checkpoints; ``runner.checkpoint``
    serializes from here).  ``n0`` offsets the chunk counter (a resumed
    run keeps counting where the checkpoint left off).  ``max_chunks``
    is an optional hard stop (the returned Sim may then be unfinished;
    :func:`make_cond` tells).

    ``on_digest(n, vec)`` fires per chunk when the chunk program was
    built with ``audit=True`` (a third output — the carry-class digest
    vector, docs/18_audit.md); the vector is handed over as a device
    array so the drive loop stays asynchronous.  Over-dispatched no-op
    chunks after completion still append (their digests repeat the
    settled state — deterministic, so trails stay comparable).

    ``on_counters(n, vec)`` fires per chunk with a boundary chunk's
    counters (:data:`BOUNDARY_COUNTERS`, a device array; the chunk
    programs of models with boundary blocks, :func:`make_chunk`).

    ``on_boundary(n, sims)`` fires after each chunk with the CURRENT
    batched Sim, before it is donated into the next dispatch — the
    refill hook (docs/22_refill.md): the hook may inspect per-lane
    liveness (:func:`make_lanes_live`) and return a REPLACEMENT Sim
    (typically the jitted, donated refill program's output with dead
    lanes re-seeded); returning ``None`` leaves the wave untouched.
    When the hook splices (returns non-None), the queued liveness
    flags are discarded: they describe the pre-splice wave, and a
    stale ``any_live=False`` from before a refill revived lanes must
    not retire the wave under the fresh work.
    """
    from collections import deque

    poll_every = max(int(poll_every), 1)
    pending = deque()
    n = n0
    while max_chunks is None or n - n0 < max_chunks:
        out = chunk(sims)
        sims, any_live = out[0], out[1]
        n += 1
        if on_digest is not None and len(out) > 2:
            on_digest(n, out[-1])
        if on_counters is not None:
            on_counters(n, out[2])
        if on_chunk is not None:
            on_chunk(n)
        if on_boundary is not None:
            respliced = on_boundary(n, sims)
            if respliced is not None:
                sims = respliced
                pending.clear()
                continue
        if (
            on_state is not None
            and on_state_every > 0
            and n % on_state_every == 0
        ):
            on_state(sims, n)
        pending.append(any_live)
        if len(pending) >= poll_every and not bool(pending.popleft()):
            break
    return sims


def make_chunked_run(
    spec: ModelSpec,
    t_end: Optional[float] = None,
    chunk_steps: int = 1024,
    poll_every: int = 4,
    donate: bool = True,
    on_chunk=None,
    max_chunks: Optional[int] = None,
):
    """Build ``run(sims) -> sims`` over a batched Sim: the chunked,
    device-resident twin of ``jit(vmap(make_run(spec)))``.

    One jitted chunk program advances every lane at most ``chunk_steps``
    dispatches; the host re-dispatches it with ``donate_argnums`` so the
    batched Sim stays on device with ZERO inter-chunk copies (XLA
    aliases each chunk's input buffers to its outputs), polling the
    ``any_live`` scalar every ``poll_every`` chunks (see
    :func:`drive_chunks`).  Trajectories are bitwise the monolithic
    run's — chunking only splits the while loop across dispatches — but
    no single device program runs longer than one chunk, so runs of any
    length clear the TPU runtime's ~3-minute program watchdog
    (docs/12_streaming.md).

    The jitted chunk is exposed as ``run.chunk`` (tests verify its
    donation) and compiles ONCE per batch shape — warm re-runs reuse it.
    """
    chunk = jax.jit(
        make_chunk(spec, t_end=t_end, max_steps=chunk_steps),
        donate_argnums=(0,) if donate else (),
    )

    def run(sims: Sim) -> Sim:
        return drive_chunks(
            chunk, sims, poll_every=poll_every, on_chunk=on_chunk,
            max_chunks=max_chunks,
        )

    run.chunk = chunk
    return run
