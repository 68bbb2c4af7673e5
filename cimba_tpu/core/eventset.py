"""The future-event set: fixed-capacity, branch-free, batched by vmap.

Reference parity: the event queue is the reference's performance heart — a
binary heap fused with a hash map (`src/cmi_hashheap.c`, 937 lines of
open-addressing, tombstones and Fibonacci hashing) giving O(log n) pops and
O(1) handle-based cancel/reschedule (`src/cmb_event.c:190-335`).

TPU redesign: none of that survives contact with the VPU.  A heap's
sift-up/down is a chain of data-dependent scalar gathers — poison under
vmap.  Instead the event set is a **flat slot table**: CAP parallel arrays,
`time == +inf` marks a free slot, and "pop min" is a lexicographic argmin
over (time, -priority, seq) computed with three masked reductions — O(CAP)
work but a handful of fully-vectorized VPU ops, which for the CAP <= a few
hundred of process-interaction models beats the heap's serial pointer
chasing by a wide margin.  Handles are (slot | generation<<16), making
cancel/reschedule O(1) scatters and ABA-safe, replacing the hash map
entirely.  The hashheap's amortized-doubling growth
(`src/cmi_hashheap.c:384-426`) becomes a static capacity with an overflow
flag — the replication is failure-masked, the experiment continues
(SURVEY.md §7 hard part (b)).

Event ordering contract (parity with `src/cmb_event.c:75-100`): earlier
time first, then HIGHER priority, then FIFO by sequence number.

All functions are scalar-style (one replication); the framework vmaps.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from cimba_tpu import config
from cimba_tpu.core import dyn
from cimba_tpu.config import INDEX_DTYPE
from cimba_tpu.config import argmax32 as _argmax32

_T = config.TIME
_I = INDEX_DTYPE

#: slot value meaning "no event here"
NEVER = jnp.inf
#: handle returned when scheduling fails (capacity exhausted)
NULL_HANDLE = jnp.int32(-1)

_GEN_SHIFT = 16
_SLOT_MASK = (1 << _GEN_SHIFT) - 1


class EventSet(NamedTuple):
    """One replication's future events (CAP slots, struct-of-arrays)."""

    time: jnp.ndarray   # [CAP] f64, +inf = free
    prio: jnp.ndarray   # [CAP] i32, higher fires first at equal time
    seq: jnp.ndarray    # [CAP] i32, schedule order, FIFO tiebreak
    kind: jnp.ndarray   # [CAP] i32, dispatch index (framework/user handler)
    subj: jnp.ndarray   # [CAP] i32, subject (process id, resource id, ...)
    arg: jnp.ndarray    # [CAP] i32, payload (signal code, ...)
    gen: jnp.ndarray    # [CAP] i32, slot generation (ABA-safe handles)
    next_seq: jnp.ndarray  # i32, next sequence number
    overflow: jnp.ndarray  # bool, a schedule was dropped


class Event(NamedTuple):
    """A popped event."""

    time: jnp.ndarray
    prio: jnp.ndarray
    kind: jnp.ndarray
    subj: jnp.ndarray
    arg: jnp.ndarray
    found: jnp.ndarray   # bool: False if the set was empty
    handle: jnp.ndarray  # the event's (pre-pop) handle; NULL_HANDLE if none


def create(capacity: int) -> EventSet:
    if capacity > _SLOT_MASK + 1:
        raise ValueError(f"event capacity {capacity} exceeds {_SLOT_MASK + 1}")
    return EventSet(
        time=jnp.full((capacity,), NEVER, _T),
        prio=jnp.zeros((capacity,), _I),
        seq=jnp.zeros((capacity,), _I),
        kind=jnp.zeros((capacity,), _I),
        subj=jnp.zeros((capacity,), _I),
        arg=jnp.zeros((capacity,), _I),
        gen=jnp.zeros((capacity,), _I),
        next_seq=jnp.zeros((), _I),
        overflow=jnp.asarray(False),
    )


def _handle(slot, gen):
    return (gen << _GEN_SHIFT) | slot


def schedule(es: EventSet, t, prio, kind, subj, arg):
    """Insert an event; returns (es, handle).

    A non-finite time or a full table sets the overflow/error flag and
    returns NULL_HANDLE — the caller (event loop) masks the replication
    as failed rather than corrupting state.
    """
    t = jnp.asarray(t, _T)
    free = jnp.isinf(es.time)
    # first free slot — iota-min, NOT argmax: several slots are free, and
    # Mosaic's argmax tie-break differs from XLA's lowest-index rule
    # (dyn.first_true32); out-of-range when full is gated by ok
    slot = dyn.first_true32(free).astype(_I)
    ok = jnp.any(free) & jnp.isfinite(t)
    # ONE shared write mask for all six field scatters (a per-field
    # dyn.dset would re-derive the iota==slot one-hot six times over —
    # measured as the dominant per-schedule cost at large CAP, back when
    # holds still lived here; timer-heavy models still hit this path)
    m = dyn._oh1(es.time.shape[0], slot) & ok

    def put(a, v):
        return jnp.where(m, jnp.asarray(v, a.dtype), a)

    es2 = es._replace(
        time=put(es.time, t),
        prio=put(es.prio, jnp.asarray(prio, _I)),
        seq=put(es.seq, es.next_seq),
        kind=put(es.kind, jnp.asarray(kind, _I)),
        subj=put(es.subj, jnp.asarray(subj, _I)),
        arg=put(es.arg, jnp.asarray(arg, _I)),
        next_seq=es.next_seq + jnp.where(ok, 1, 0).astype(_I),
        overflow=es.overflow | ~ok,
    )
    handle = jnp.where(
        ok, _handle(slot, dyn._reduce_pick(m, es.gen)), NULL_HANDLE
    )
    return es2, handle.astype(_I)


def _slot_of(handle):
    return handle & _SLOT_MASK


def _gen_of(handle):
    return handle >> _GEN_SHIFT


def _valid(es: EventSet, handle):
    slot = _slot_of(handle)
    return (
        (handle >= 0)
        & jnp.isfinite(dyn.dget(es.time, slot))
        & (dyn.dget(es.gen, slot) == _gen_of(handle))
    )


def _valid_vec(es: EventSet, handles):
    """Vectorized :func:`_valid` for a [k] vector of handles (the
    wait_event waiter scan checks every process's awaited handle per
    step — a per-handle dget would make that scan O(k*CAP) serial).
    One [k, CAP] one-hot serves both the liveness and generation reads;
    out-of-range slots behave exactly as the scalar dget (all-false
    mask -> zero picks)."""
    slot = (jnp.maximum(handles, 0) & _SLOT_MASK)[:, None]
    oh = slot == lax.broadcasted_iota(
        jnp.int32, (1, es.time.shape[0]), 1
    )
    t_at = jnp.sum(
        jnp.where(oh, es.time[None, :], jnp.zeros((), _T)),
        axis=1, dtype=_T,
    )
    g_at = jnp.sum(
        jnp.where(oh, es.gen[None, :], jnp.zeros((), _I)),
        axis=1, dtype=_I,
    )
    return (
        (handles >= 0)
        & jnp.isfinite(t_at)
        & (g_at == _gen_of(handles))
    )


def _handle_mask(es: EventSet, handle):
    """Shared (one-hot mask, ok) for handle-addressed ops: the slot
    one-hot is derived once and reused for the liveness/generation reads
    AND the writes, instead of one one-hot per dget/dset."""
    slot = _slot_of(jnp.maximum(handle, 0))
    ohs = dyn._oh1(es.time.shape[0], slot)
    t_at = dyn._reduce_pick(ohs, es.time)
    g_at = dyn._reduce_pick(ohs, es.gen)
    ok = (handle >= 0) & jnp.isfinite(t_at) & (g_at == _gen_of(handle))
    return ohs & ok, ok


def cancel(es: EventSet, handle):
    """Remove by handle; returns (es, existed).  O(1) scatter — the
    capability the reference needed the whole hash map for."""
    m, ok = _handle_mask(es, handle)
    return es._replace(
        time=jnp.where(m, _T(NEVER), es.time),
        gen=es.gen + m.astype(_I),
    ), ok


def reschedule(es: EventSet, handle, new_t):
    """Move an event in time, keeping FIFO seq (parity:
    ``cmb_event_reschedule``).  Returns (es, existed)."""
    new_t = jnp.asarray(new_t, _T)
    m, ok = _handle_mask(es, handle)
    fin = jnp.isfinite(new_t)
    return es._replace(time=jnp.where(m & fin, new_t, es.time)), ok & fin


def reprioritize(es: EventSet, handle, new_prio):
    """Parity: ``cmb_event_reprioritize``.  Returns (es, existed)."""
    m, ok = _handle_mask(es, handle)
    return es._replace(
        prio=jnp.where(m, jnp.asarray(new_prio, _I), es.prio)
    ), ok


def _lexmin(time, prio, seq):
    """Shared (time asc, prio desc, seq asc) argnext over parallel arrays:
    returns (mask, found, t_min, p_max, s_min).  ``found`` is folded into
    the first mask, which makes the result EXACTLY one-hot with no
    uniquification pass: live slots carry distinct seq values (strictly
    increasing at schedule, preserved by reschedule), and when the set is
    empty the mask is all-false rather than matching every +inf free
    slot."""
    t_min = jnp.min(time)
    found = jnp.isfinite(t_min)
    m1 = (time == t_min) & found
    p_max = jnp.max(jnp.where(m1, prio, jnp.iinfo(jnp.int32).min))
    m2 = m1 & (prio == p_max)
    s_min = jnp.min(jnp.where(m2, seq, jnp.iinfo(jnp.int32).max))
    m3 = m2 & (seq == s_min)  # one-hot (or empty): seq unique when live
    return m3, found, t_min, p_max, s_min


def _argnext(es: EventSet):
    """Index of the next event: min time, then max prio, then min seq —
    three masked reductions, no data-dependent control flow."""
    m3, found, _, _, _ = _lexmin(es.time, es.prio, es.seq)
    slot = _argmax32(m3).astype(_I)
    return slot, m3, found


def _next_parts(es: EventSet):
    """(found, slot, time, prio, kind, subj, arg, gen, take[CAP]) of the
    next event.  Not-found fields are the all-false-mask picks (zeros)."""
    slot, m, found = _argnext(es)
    return (
        found,
        slot,
        dyn._reduce_pick(m, es.time),
        dyn._reduce_pick(m, es.prio),
        dyn._reduce_pick(m, es.kind),
        dyn._reduce_pick(m, es.subj),
        dyn._reduce_pick(m, es.arg),
        dyn._reduce_pick(m, es.gen),
        m,
    )


def peek(es: EventSet) -> Event:
    found, slot, t, prio, kind, subj, arg, gen, _ = _next_parts(es)
    return Event(
        time=t,
        prio=prio,
        kind=kind,
        subj=subj,
        arg=arg,
        found=found,
        handle=jnp.where(
            found, _handle(slot, gen), NULL_HANDLE
        ).astype(_I),
    )


def pop(es: EventSet):
    """Remove and return the next event; (es, Event)."""
    found, slot, t, prio, kind, subj, arg, gen, m = _next_parts(es)
    ev = Event(
        time=t,
        prio=prio,
        kind=kind,
        subj=subj,
        arg=arg,
        found=found,
        handle=jnp.where(
            found, _handle(slot, gen), NULL_HANDLE
        ).astype(_I),
    )
    # m already folds `found` (all-false on an empty set), so the consume
    # writes need no extra gating
    return es._replace(
        time=jnp.where(m, _T(NEVER), es.time),
        gen=es.gen + m.astype(_I),
    ), ev


def is_empty(es: EventSet):
    return ~jnp.any(jnp.isfinite(es.time))


def min_time(es: EventSet):
    """Soonest live time (+inf when empty)."""
    return jnp.min(es.time)


def length(es: EventSet):
    return jnp.sum(jnp.isfinite(es.time).astype(_I))


# --- pattern operations (parity: cmb_event_pattern_* wildcards,
#     `src/cmb_event.c:459-493`) — vectorized full scans -------------------

WILDCARD = jnp.int32(-1)


def _match(es: EventSet, kind, subj):
    live = jnp.isfinite(es.time)
    k = jnp.asarray(kind, _I)
    s = jnp.asarray(subj, _I)
    mk = (k == WILDCARD) | (es.kind == k)
    ms = (s == WILDCARD) | (es.subj == s)
    return live & mk & ms


def pattern_count(es: EventSet, kind=WILDCARD, subj=WILDCARD):
    return jnp.sum(_match(es, kind, subj).astype(_I))


def pattern_cancel(es: EventSet, kind=WILDCARD, subj=WILDCARD, pred=True):
    """Cancel all matching events; returns (es, n_cancelled).  ``pred``
    gates the cancellation (n_cancelled still reports the match count)."""
    m = _match(es, kind, subj)
    mw = m if pred is True else (m & pred)
    return es._replace(
        time=jnp.where(mw, NEVER, es.time),
        gen=es.gen + mw.astype(_I),
    ), jnp.sum(m.astype(_I))


def pattern_find(es: EventSet, kind=WILDCARD, subj=WILDCARD):
    """Handle of the soonest matching event, else NULL_HANDLE."""
    m = _match(es, kind, subj)
    t = jnp.where(m, es.time, NEVER)
    t_min = jnp.min(t)
    found = jnp.isfinite(t_min)
    # lowest slot among equal-time matches — argmin time ties are
    # backend-dependent under Mosaic (dyn.first_true32)
    slot = dyn.first_true32(m & (t == t_min)).astype(_I)
    return jnp.where(
        found, _handle(slot, dyn.dget(es.gen, slot)), NULL_HANDLE
    ).astype(_I)

# --- dense per-process resume events ------------------------------------
#
# The overwhelming majority of events in any model are process resumes —
# holds, guard wakes, interrupt/timer deliveries (kind K_PROC) — and the
# dispatcher maintains at most ONE pending resume per process (every
# K_PROC schedule either follows a cancel of the previous wake or targets
# a process that provably has none; loop.py's _schedule_wake/_cancel_wake
# discipline).  Storing them densely with slot = pid removes the general
# table's free-slot search, generation tags and scatter masks for the hot
# case, and shrinks the general table to timers + user events only.
# Priority is read LIVE from procs.prio at pop time — exactly the
# semantics priority_set's reshuffle used to restore — and seq draws from
# the same next_seq counter as the general table, so the (time, prio
# DESC, seq) dispatch contract is preserved verbatim across both tables.
# (Reference parity note: this splits `cmi_hashheap` by event class; the
# reference's heap does not need the split because its per-op cost is
# O(log n) serial, ours is O(table width) vectorized.)


class Wakes(NamedTuple):
    """Pending per-process resumes ([P] slots, +inf time = none)."""

    time: jnp.ndarray  # [P] _T
    sig: jnp.ndarray   # [P] i32 signal delivered on resume
    seq: jnp.ndarray   # [P] i32 FIFO tiebreak (shared next_seq counter)


def wakes_create(n: int) -> Wakes:
    return Wakes(
        time=jnp.full((n,), NEVER, _T),
        sig=jnp.zeros((n,), _I),
        seq=jnp.zeros((n,), _I),
    )


def wake_set(wk: Wakes, p, t, sig, seq, pred=True):
    """Arm (or overwrite) process p's resume; returns (wk, ok).  ``ok``
    is false — and nothing is written — for a non-finite time (the
    general table's overflow-as-failure parity; a dense slot can never
    be 'full')."""
    t = jnp.asarray(t, _T)
    ok = jnp.isfinite(t)
    if pred is not True:
        ok = ok & pred
    m = dyn._oh1(wk.time.shape[0], p) & ok
    return (
        Wakes(
            time=jnp.where(m, t, wk.time),
            sig=jnp.where(m, jnp.asarray(sig, _I), wk.sig),
            seq=jnp.where(m, jnp.asarray(seq, _I), wk.seq),
        ),
        ok,
    )


def wake_clear(wk: Wakes, p, pred=True) -> Wakes:
    m = dyn._oh1(wk.time.shape[0], p)
    if pred is not True:
        m = m & pred
    return wk._replace(time=jnp.where(m, _T(NEVER), wk.time))


def wakes_empty(wk: Wakes):
    return ~jnp.any(jnp.isfinite(wk.time))


def peek_merged(es: EventSet, wk: Wakes, prio, wake_kind):
    """Next event across the general table and the dense wakes WITHOUT
    consuming it (lexicographic (time, prio DESC, seq) over the union;
    ``prio`` is the live procs.prio array, ``wake_kind`` the dispatch
    kind a wake pop reports — the caller's K_PROC).  Returns
    (Event, take_e, take_w): the one-hot consume masks for the two
    tables, for :func:`consume_merged`.  A wake pop carries
    ``handle=NULL_HANDLE`` — wake events are unaddressable, so the
    wait_event machinery (which only ever holds general-table handles)
    never matches them."""
    m_e, found_e, t_e, p_e, s_e = _lexmin(es.time, es.prio, es.seq)
    slot_e = _argmax32(m_e).astype(_I)
    kind_e = dyn._reduce_pick(m_e, es.kind)
    subj_e = dyn._reduce_pick(m_e, es.subj)
    arg_e = dyn._reduce_pick(m_e, es.arg)
    gen_e = dyn._reduce_pick(m_e, es.gen)
    m_w, found_w, t_w, p_w, s_w = _lexmin(wk.time, prio, wk.seq)

    wake_first = found_w & (
        ~found_e
        | (t_w < t_e)
        | ((t_w == t_e) & ((p_w > p_e) | ((p_w == p_e) & (s_w < s_e))))
    )
    found = found_e | found_w

    pid_w = _argmax32(m_w).astype(_I)
    event = Event(
        time=jnp.where(wake_first, t_w, t_e),
        prio=jnp.where(wake_first, p_w, p_e),
        kind=jnp.where(
            wake_first, jnp.asarray(wake_kind, _I), kind_e
        ),
        subj=jnp.where(wake_first, pid_w, subj_e),
        arg=jnp.where(
            wake_first, dyn._reduce_pick(m_w, wk.sig), arg_e
        ),
        found=found,
        handle=jnp.where(
            found & ~wake_first,
            _handle(slot_e, gen_e),
            NULL_HANDLE,
        ).astype(_I),
    )
    return event, m_e & ~wake_first, m_w & wake_first


def consume_merged(es: EventSet, wk: Wakes, take_e, take_w, pred=True):
    """Remove the peeked event (``pred`` gates the removal — the kernel
    driver defers boundary-block dispatches by peeking without
    consuming)."""
    if pred is not True:
        take_e = take_e & pred
        take_w = take_w & pred
    es2 = es._replace(
        time=jnp.where(take_e, _T(NEVER), es.time),
        gen=es.gen + take_e.astype(_I),
    )
    wk2 = wk._replace(time=jnp.where(take_w, _T(NEVER), wk.time))
    return es2, wk2


def pop_merged(es: EventSet, wk: Wakes, prio, wake_kind):
    """peek_merged + consume_merged in one step; returns (es, wk, Event)."""
    event, take_e, take_w = peek_merged(es, wk, prio, wake_kind)
    es2, wk2 = consume_merged(es, wk, take_e, take_w)
    return es2, wk2, event
