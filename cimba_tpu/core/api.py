"""Block-author helpers: the model-facing surface inside blocks.

These are the TPU equivalents of the calls a reference process body makes
between yields — ``cmb_time()``, ``cmb_random_*``, reading/writing its own
state — expressed functionally over the :class:`~cimba_tpu.core.loop.Sim`
pytree.  Commands (the yield points) live in :mod:`cimba_tpu.core.process`.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from cimba_tpu import config
from cimba_tpu.core import dyn
from cimba_tpu.config import INDEX_DTYPE
from cimba_tpu.core.loop import ERR_USER, Sim

_I = INDEX_DTYPE
_R = config.REAL


def clock(sim: Sim):
    """Current simulation time (parity: ``cmb_time``)."""
    return sim.clock


def draw(sim: Sim, dist, *params):
    """Draw from a distribution, threading the replication's RNG stream:
    ``sim, x = api.draw(sim, random.exponential, mean)``."""
    rng, x = dist(sim.rng, *params)
    return sim._replace(rng=rng), x


def got(sim: Sim, p):
    """Result register: the item produced by this process's last GET."""
    return dyn.dget(sim.procs.got, p)


def local_f(sim: Sim, p, k: int):
    return dyn.dget(sim.procs.locals_f[:, k], p)


def set_local_f(sim: Sim, p, k: int, v) -> Sim:
    return sim._replace(
        procs=sim.procs._replace(
            locals_f=dyn.set_col(
                sim.procs.locals_f, k,
                dyn.dset(sim.procs.locals_f[:, k], p, jnp.asarray(v, _R)),
            )
        )
    )


def local_i(sim: Sim, p, k: int):
    return dyn.dget(sim.procs.locals_i[:, k], p)


def set_local_i(sim: Sim, p, k: int, v) -> Sim:
    return sim._replace(
        procs=sim.procs._replace(
            locals_i=dyn.set_col(
                sim.procs.locals_i, k,
                dyn.dset(sim.procs.locals_i[:, k], p, jnp.asarray(v, _I)),
            )
        )
    )


def add_local_i(sim: Sim, p, k: int, dv=1) -> Sim:
    return sim._replace(
        procs=sim.procs._replace(
            locals_i=dyn.set_col(
                sim.procs.locals_i, k,
                dyn.dadd(sim.procs.locals_i[:, k], p, jnp.asarray(dv, _I)),
            )
        )
    )


def user(sim: Sim):
    return sim.user


def set_user(sim: Sim, new_user) -> Sim:
    return sim._replace(user=new_user)


def stop(sim: Sim, pred=True) -> Sim:
    """End the replication after the current event (the analog of the
    reference's user-scheduled end event)."""
    return sim._replace(done=sim.done | jnp.asarray(pred))


def fail(sim: Sim, pred=True) -> Sim:
    """Mark the replication failed (parity: cmb_logger_error recovery —
    the replication is abandoned and counted, §3.5)."""
    return sim._replace(
        err=jnp.where(
            (sim.err == 0) & jnp.asarray(pred), jnp.asarray(ERR_USER, _I), sim.err
        )
    )


def queue_length(sim: Sim, q):
    """Current number of items in an object queue (parity:
    ``cmb_objectqueue_length``)."""
    return sim.queues.size[q.id if hasattr(q, "id") else q]


def queue_space(sim: Sim, q):
    """Free slots in an object queue (parity: ``cmb_objectqueue_space``,
    `include/cmb_objectqueue.h`).  Requires the QueueRef: the declared
    capacity lives there, and the shared ring width a bare id could
    read can be wider than this queue's real capacity."""
    if not hasattr(q, "capacity"):
        raise TypeError("queue_space needs the QueueRef, not a bare id")
    return (jnp.asarray(q.capacity, _I) - sim.queues.size[q.id]).astype(_I)


def queue_position(sim: Sim, q, item):
    """1-based position of the first item equal to ``item`` (nearest the
    front), 0 if absent (parity: cmb_objectqueue_position,
    `include/cmb_objectqueue.h:199`; the reference matches object pointers,
    this matches the f64 payload)."""
    qid = q.id if hasattr(q, "id") else q
    # queue qid's places in the flat ring; the QueueRef's capacity is the
    # modulus its ring wraps at (a bare id reads the shared ring width)
    width = sim.queues.items.shape[0] // sim.queues.head.shape[0]
    cap = getattr(q, "capacity", width)
    items = lax.dynamic_slice_in_dim(sim.queues.items, qid * width, cap)
    # gather-free: for each physical slot c, its queue position is
    # (c - head) mod cap; a slot is occupied if that position < size.
    # (A permutation gather over the ring would not lower in Mosaic.)
    c = jnp.arange(cap)
    pos = (c - sim.queues.head[qid]) % cap
    hit = (pos < sim.queues.size[qid]) & (items == jnp.asarray(item, _R))
    best = jnp.min(jnp.where(hit, pos, cap))
    return jnp.where(jnp.any(hit), best + 1, 0).astype(_I)


def _pq_match(sim: Sim, qid, item):
    """Earliest-dequeuing live item equal to ``item``: returns
    ``(one_hot, match, p_best, s_best)`` — the single source of the
    payload-keyed tie-break rule (max priority, then min seq) shared by
    position/cancel/reprioritize."""
    live = sim.pqueues.live[qid]
    prio = sim.pqueues.prio[qid]
    seq = sim.pqueues.seq[qid]
    match = live & (sim.pqueues.items[qid] == jnp.asarray(item, _R))
    p_best = jnp.max(jnp.where(match, prio, jnp.asarray(-jnp.inf, _R)))
    m2 = match & (prio == p_best)
    s_best = jnp.min(jnp.where(m2, seq, jnp.iinfo(jnp.int32).max))
    return m2 & (seq == s_best), match, p_best, s_best


def pqueue_position(sim: Sim, q, item):
    """1-based position in dequeue order (priority desc, FIFO within equal
    priority) of the first item equal to ``item``, 0 if absent (parity:
    cmb_priorityqueue_position, `include/cmb_priorityqueue.h:140`; the
    reference locates by put-handle — here puts return no handle, so the
    payload is the lookup key and the earliest-dequeuing match wins)."""
    qid = q.id if hasattr(q, "id") else q
    _, match, p_best, s_best = _pq_match(sim, qid, item)
    live = sim.pqueues.live[qid]
    prio = sim.pqueues.prio[qid]
    seq = sim.pqueues.seq[qid]
    ahead = live & (
        (prio > p_best) | ((prio == p_best) & (seq < s_best))
    )
    pos = jnp.sum(ahead.astype(_I)) + 1
    return jnp.where(jnp.any(match), pos, 0).astype(_I)


def resource_holder(sim: Sim, r):
    """Holding pid of a resource, -1 if free."""
    return sim.resources.holder[r.id if hasattr(r, "id") else r]


def pool_level(sim: Sim, pool):
    """Available units in a resource pool (parity: cmb_resourcepool_level)."""
    return sim.pools.level[pool.id if hasattr(pool, "id") else pool]


def buffer_level(sim: Sim, b):
    """Stored amount in a buffer (parity: cmb_buffer_level)."""
    return sim.buffers.level[b.id if hasattr(b, "id") else b]


def buffer_space(sim: Sim, b):
    """Room left in a buffer (parity: ``cmb_buffer_space``,
    `include/cmb_buffer.h`).  Requires the BufferRef (capacity is
    declared there, not stored in the Sim)."""
    if not hasattr(b, "capacity"):
        raise TypeError("buffer_space needs the BufferRef, not a bare id")
    return jnp.asarray(b.capacity, _R) - sim.buffers.level[b.id]


def pool_in_use(sim: Sim, pool):
    """Units currently held out of a pool (parity:
    ``cmb_resourcepool_in_use``).  Requires the PoolRef (capacity is
    declared there, not stored in the Sim)."""
    if not hasattr(pool, "capacity"):
        raise TypeError("pool_in_use needs the PoolRef, not a bare id")
    return jnp.asarray(pool.capacity, _R) - sim.pools.level[pool.id]


def pool_held(sim: Sim, pool, p):
    """Units process ``p`` holds from a pool (parity:
    ``cmb_resourcepool_held_by_process``,
    `include/cmb_resourcepool.h:118`)."""
    k = pool.id if hasattr(pool, "id") else pool
    return dyn.dget2(sim.pools.held, k, p)


def proc_priority(sim: Sim, p):
    """Current process priority (parity: ``cmb_process_priority``;
    the setter is :func:`priority_set`)."""
    return dyn.dget(sim.procs.prio, p)


def pqueue_length(sim: Sim, q):
    """Items in a priority queue (parity: cmb_priorityqueue_length)."""
    qid = q.id if hasattr(q, "id") else q
    return jnp.sum(sim.pqueues.live[qid].astype(_I))


def pqueue_cancel(sim: Sim, q, item):
    """(sim, existed): remove the earliest-dequeuing item equal to
    ``item`` from a priority queue (parity: ``cmb_priorityqueue_cancel``,
    `include/cmb_priorityqueue.h` — the reference cancels by put-handle;
    payload-keyed here, matching pqueue_position's documented lookup).
    Requires the PQueueRef: the freed slot signals the rear guard so a
    blocked putter wakes (as the reference does), and the length
    recording appends a step when the queue records."""
    from cimba_tpu.core import loop as _loop

    if not hasattr(q, "rear_guard"):
        raise TypeError("pqueue_cancel needs the PQueueRef, not a bare id")
    qid = q.id
    m, _, _, _ = _pq_match(sim, qid, item)
    existed = jnp.any(m)
    live2 = dyn.dset(sim.pqueues.live, qid, sim.pqueues.live[qid] & ~m)
    pq2 = sim.pqueues._replace(live=live2)
    if q.record and sim.pqueues.acc is not None:
        pq2 = pq2._replace(
            acc=_loop._record_row(
                sim.pqueues.acc, qid, sim.clock,
                jnp.sum(live2[qid].astype(_I)).astype(_R), existed,
            )
        )
    sim = sim._replace(pqueues=pq2)
    # the freed slot can satisfy a pending putter
    sim = _loop._guard_signal(sim, q.rear_guard, pred=existed)
    return sim, existed


def pqueue_reprioritize(sim: Sim, q, item, new_prio):
    """(sim, existed): change the priority of the earliest-dequeuing
    item equal to ``item`` (parity: ``cmb_priorityqueue_reprioritize``;
    payload-keyed, see pqueue_cancel).  FIFO seq is preserved, so equal
    priorities keep insertion order — the same contract as
    event_reprioritize."""
    qid = q.id if hasattr(q, "id") else q
    m, _, _, _ = _pq_match(sim, qid, item)
    existed = jnp.any(m)
    prio2 = dyn.dset(
        sim.pqueues.prio, qid,
        jnp.where(m, jnp.asarray(new_prio, _R), sim.pqueues.prio[qid]),
    )
    return sim._replace(pqueues=sim.pqueues._replace(prio=prio2)), existed


# --- inter-process verbs (thin wrappers over core.loop; blocks close over
#     their model's built spec, e.g. via a late-bound `spec()` accessor) ----


def interrupt(sim: Sim, spec, target, sig) -> Sim:
    """Deliver ``sig`` to a waiting process now, aborting its wait
    (parity: cmb_process_interrupt)."""
    from cimba_tpu.core import loop as _loop

    return _loop.interrupt(spec, sim, target, jnp.asarray(sig, _I))


def stop_process(sim: Sim, spec, target) -> Sim:
    """Kill a process: drop resources, cancel waits, wake waiters with
    STOPPED (parity: cmb_process_stop)."""
    from cimba_tpu.core import loop as _loop

    return _loop.stop_process(spec, sim, target)


def spawn(sim: Sim, ptype, at=None, prio=None):
    """(sim, pid): activate one row of a spawn pool — a process type
    declared ``m.process(name, entry, count=N, start=False)``.  Picks
    the lowest-pid CREATED/FINISHED row, resets its state, and arms its
    entry wake at ``at`` (default now); pid == -1 when all N rows are
    RUNNING (parity: runtime ``cmb_process_create``/``start``,
    `include/cmb_process.h:119-180` — the pool is declared, activation
    is dynamic)."""
    from cimba_tpu.core import loop as _loop

    return _loop.spawn_process(sim, ptype, at=at, prio=prio)


def timer_add(sim: Sim, p, dur, sig):
    """(sim, handle): deliver ``sig`` to p after ``dur`` unless cancelled
    (parity: cmb_process_timer_add)."""
    from cimba_tpu.core import loop as _loop

    return _loop.timer_add(sim, p, dur, jnp.asarray(sig, _I))


def timer_cancel(sim: Sim, handle, spec=None):
    """(sim, existed) — parity: cmb_process_timer_cancel.  Pass the model
    ``spec`` so processes waiting on this handle (cmd.wait_event) wake with
    CANCELLED immediately rather than at the next dispatch."""
    from cimba_tpu.core import loop as _loop

    return _loop.timer_cancel(sim, handle, spec)


def event_cancel(sim: Sim, handle, spec=None):
    """(sim, existed): cancel any scheduled event by handle (parity:
    cmb_event_cancel); wait_event waiters wake with CANCELLED (immediately
    when ``spec`` is passed, else at the next dispatch)."""
    from cimba_tpu.core import loop as _loop

    return _loop.timer_cancel(sim, handle, spec)


def timers_clear(sim: Sim, p) -> Sim:
    """Cancel all timers aimed at p (parity: cmb_process_timers_clear)."""
    from cimba_tpu.core import loop as _loop

    return _loop.timers_clear(sim, p)


def priority_set(sim: Sim, p, new_prio) -> Sim:
    """Change process priority, reshuffling event and guard queues
    (parity: cmb_process_priority_set)."""
    from cimba_tpu.core import loop as _loop

    return _loop.priority_set(sim, p, new_prio)


def cond_signal(sim: Sim, spec, condition) -> Sim:
    """Signal a condition variable: wake every waiter whose predicate
    holds (parity: cmb_condition_signal)."""
    from cimba_tpu.core import loop as _loop

    cid = condition.id if hasattr(condition, "id") else condition
    return _loop.cond_signal(spec, sim, cid)


def release(sim: Sim, spec, resource, p) -> Sim:
    """Release a binary resource INLINE from a block — zero chain
    iterations (release never blocks or yields, so spending a command —
    a full masked kernel body pass — on it was pure cost; parity:
    cmb_resource_release as the reference's plain function call).
    ``cmd.release`` remains for block-boundary control flow."""
    from cimba_tpu.core import loop as _loop

    rid = resource.id if hasattr(resource, "id") else resource
    return _loop.release_resource(spec, sim, p, rid)


def pool_release(sim: Sim, spec, pool, p, amount) -> Sim:
    """Release pool units INLINE from a block (partial release allowed;
    parity: cmb_resourcepool_release) — see :func:`release` for why
    this costs zero chain iterations.  ``cmd.pool_release`` remains."""
    from cimba_tpu.core import loop as _loop

    k = pool.id if hasattr(pool, "id") else pool
    return _loop.release_pool(spec, sim, p, k, amount)


def proc_status(sim: Sim, p):
    """CREATED/RUNNING/FINISHED (parity: cmb_process_status)."""
    return dyn.dget(sim.procs.status, p)


def event_is_scheduled(sim: Sim, handle):
    """True while ``handle`` names a live scheduled event (parity:
    ``cmb_event_is_scheduled``, `include/cmb_event.h:196` — generation
    tags make a fired/cancelled/reused slot report False)."""
    from cimba_tpu.core import eventset as _ev

    return _ev._valid(sim.events, jnp.asarray(handle, _I))


def event_time(sim: Sim, handle):
    """Scheduled activation time of a live event, ``+inf`` for a dead
    handle (parity: ``cmb_event_time``, `include/cmb_event.h:205` — the
    reference errors on a dead handle; here the sentinel composes with
    jit, and :func:`event_is_scheduled` is the validity check)."""
    from cimba_tpu.core import eventset as _ev

    h = jnp.asarray(handle, _I)
    slot = _ev._slot_of(h)
    return jnp.where(
        _ev._valid(sim.events, h),
        dyn.dget(sim.events.time, slot),
        jnp.asarray(jnp.inf, sim.events.time.dtype),
    )


def event_priority(sim: Sim, handle):
    """Dispatch priority of a live event, 0 for a dead handle (parity:
    ``cmb_event_priority``, `include/cmb_event.h:214`)."""
    from cimba_tpu.core import eventset as _ev

    h = jnp.asarray(handle, _I)
    slot = _ev._slot_of(h)
    return jnp.where(
        _ev._valid(sim.events, h),
        dyn.dget(sim.events.prio, slot),
        jnp.zeros((), _I),
    )


def event_reschedule(sim: Sim, handle, new_t):
    """(sim, existed): move a scheduled event to ``new_t`` keeping its
    FIFO sequence — unlike cancel+schedule, which would send it to the
    back of its (time, prio) tie class (parity: ``cmb_event_reschedule``,
    `include/cmb_event.h:193-210`).  A non-finite ``new_t`` fails the
    move and returns existed=False."""
    from cimba_tpu.core import eventset as _ev

    es2, ok = _ev.reschedule(sim.events, handle, new_t)
    return sim._replace(events=es2), ok


def event_reprioritize(sim: Sim, handle, new_prio):
    """(sim, existed): change a scheduled event's dispatch priority in
    place, keeping time and FIFO sequence (parity:
    ``cmb_event_reprioritize``, `include/cmb_event.h:212-228`)."""
    from cimba_tpu.core import eventset as _ev

    es2, ok = _ev.reprioritize(sim.events, handle, new_prio)
    return sim._replace(events=es2), ok


def _pattern_kind(kind):
    from cimba_tpu.core import eventset as _ev

    if kind is None:
        return _ev.WILDCARD
    return kind.kind if hasattr(kind, "kind") else kind


def event_pattern_count(sim: Sim, kind=None, subj=None):
    """Number of scheduled events matching (kind, subj); ``None`` is a
    wildcard, ``kind`` may be a Model.handler function (parity:
    ``cmb_event_pattern_count``, `src/cmb_event.c:459-470`)."""
    from cimba_tpu.core import eventset as _ev

    return _ev.pattern_count(
        sim.events, _pattern_kind(kind),
        _ev.WILDCARD if subj is None else subj,
    )


def event_pattern_find(sim: Sim, kind=None, subj=None):
    """Handle of the soonest scheduled event matching (kind, subj), else
    NULL_HANDLE=-1 (parity: ``cmb_event_pattern_find``,
    `src/cmb_event.c:472-481`).  The handle feeds event_cancel /
    event_reschedule / cmd.wait_event."""
    from cimba_tpu.core import eventset as _ev

    return _ev.pattern_find(
        sim.events, _pattern_kind(kind),
        _ev.WILDCARD if subj is None else subj,
    )


def event_pattern_cancel(sim: Sim, kind=None, subj=None):
    """(sim, n_cancelled): cancel every scheduled event matching
    (kind, subj) (parity: ``cmb_event_pattern_cancel``,
    `src/cmb_event.c:483-493`)."""
    from cimba_tpu.core import eventset as _ev

    es2, n = _ev.pattern_cancel(
        sim.events, _pattern_kind(kind),
        _ev.WILDCARD if subj is None else subj,
    )
    return sim._replace(events=es2), n


def schedule(sim: Sim, t, prio, handler, subj=0, arg=0):
    """(sim, handle): schedule a user event (parity: cmb_event_schedule
    with an arbitrary action); ``handler`` is a function registered with
    Model.handler.  The handle supports event_cancel / cmd.wait_event
    (NULL_HANDLE = -1 if the event table was full; the replication is then
    already marked failed)."""
    from cimba_tpu.core import eventset as _ev
    from cimba_tpu.core import loop as _loop

    kind = handler.kind if hasattr(handler, "kind") else handler
    es2, handle = _ev.schedule(sim.events, t, prio, kind, subj, arg)
    sim = sim._replace(events=es2)
    sim = _loop._set_err(sim, es2.overflow, _loop.ERR_EVENT_OVERFLOW)
    return sim, handle