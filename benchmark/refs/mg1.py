"""A plain M/G/1 queue, one replication per lane, in ``jax.numpy``.

The semantics of upstream cimba's ``benchmark/MM1_multi.c`` and of the M/G/1
sweep (``README.md:283-294``) as cimba-tpu's models run them:

- an arrival process holds an exponential inter-arrival time, then puts its
  arrival time into a FIFO of ``cap`` places and holds the next one, drawn
  at the put; after ``n_objects`` puts it ends;
- a server draws its service time, takes the head of the FIFO (waiting for
  a put when it is empty, keeping the time it drew), holds it, records the
  sojourn (now minus the arrival time) and draws again; the lane stops at
  the ``n_objects``-th record, or before the first event past ``t_end``;
- events pop in (time, schedule order); a put that wakes the waiting
  server schedules the wake at the put's time, before the next arrival;
- one stream per replication (:mod:`benchmark.refs.rng`), one draw per
  duration, in the order the events draw them;
- a put into a full FIFO fails the lane, which keeps what it recorded.

Service is exponential (M/M/1) or lognormal of a given mean and
coefficient of variation.  Every time and statistic is computed in
``dtype``; the control runs the same code in the next precision below.
Lanes run side by side, one event per lane per step of a ``while_loop``.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.refs import rng

#: lanes a step holds at most; more are run block after block
BLOCK = 32768

_A_START, _A_NEXT, _A_NONE = 0, 1, 2
_S_START, _S_DONE, _S_WAKE, _S_NONE = 0, 1, 2, 3


class Lanes(NamedTuple):
    """Per-lane inputs (host arrays of one length)."""

    seed: np.ndarray       # experiment seed of each lane
    rep: np.ndarray        # replication index of each lane
    arr_mean: np.ndarray   # mean inter-arrival time
    srv_mean: np.ndarray   # mean service time
    srv_cv: np.ndarray     # service coefficient of variation (lognormal)
    n_objects: np.ndarray  # records after which a lane stops
    t_end: np.ndarray      # horizon (inf: none)


class Out(NamedTuple):
    """Per-lane results (host arrays)."""

    n: np.ndarray       # sojourns recorded
    mean: np.ndarray
    m2: np.ndarray      # sum of squared deviations
    mn: np.ndarray
    mx: np.ndarray
    events: np.ndarray  # events popped
    clock: np.ndarray   # time of the last event
    failed: np.ndarray  # FIFO overflow or step limit


def run(lanes: Lanes, *, service: str, cap: int, dtype=jnp.float32,
        max_steps: int = 1 << 16) -> Out:
    """Run every lane; ``service`` is ``"exponential"`` or
    ``"lognormal"``."""
    n = len(lanes.seed)
    k0, k1 = rng.stream_keys(lanes.seed, lanes.rep)
    cols = [np.asarray(c) for c in lanes[2:]]
    outs = []
    for lo in range(0, n, BLOCK):
        hi = min(lo + BLOCK, n)
        width = BLOCK if n > BLOCK else n
        pad = width - (hi - lo)

        def take(a, fill):
            a = a[lo:hi]
            return np.concatenate([a, np.full(pad, fill, a.dtype)]) if pad else a

        args = (
            take(k0, 0), take(k1, 0),
            take(cols[0].astype(np.float64), 1.0),
            take(cols[1].astype(np.float64), 1.0),
            take(cols[2].astype(np.float64), 1.0),
            take(cols[3].astype(np.int64), 0),
            take(cols[4].astype(np.float64), -np.inf),
        )
        res = _block(*args, service=service, cap=cap,
                     dtype=jnp.dtype(dtype), max_steps=max_steps)
        outs.append([np.asarray(x)[: hi - lo] for x in res])
    return Out(*(np.concatenate(c) for c in zip(*outs)))


@partial(jax.jit, static_argnames=("service", "cap", "dtype", "max_steps"))
def _block(k0, k1, arr_mean, srv_mean, srv_cv, n_objects, t_end, *,
           service, cap, dtype, max_steps):
    f = lambda x: jnp.asarray(x).astype(dtype)
    arr_mean, srv_mean, srv_cv = f(arr_mean), f(srv_mean), f(srv_cv)
    t_end = f(t_end)
    n_obj = jnp.asarray(n_objects).astype(jnp.int32)
    L = k0.shape[0]
    zero = jnp.zeros((L,), dtype)
    izero = jnp.zeros((L,), jnp.int32)
    # lognormal parameters of mean m and coefficient of variation c:
    # sigma^2 = log(1 + c^2), mu = log(m) - sigma^2 / 2
    ln_s2 = jnp.log1p(srv_cv * srv_cv)
    ln_mu = jnp.log(srv_mean) - jnp.asarray(0.5, dtype) * ln_s2
    ln_sigma = jnp.sqrt(ln_s2)
    tiny = jnp.asarray(float(jnp.finfo(dtype).eps) / 2.0, dtype)

    def draw(ctr, mean, lognormal):
        u = rng.uniform(k0, k1, ctr, dtype)
        if lognormal:
            x = jnp.clip(2.0 * u - 1.0, -1.0 + tiny, 1.0 - tiny).astype(dtype)
            z = jnp.sqrt(jnp.asarray(2.0, dtype)) * jax.lax.erf_inv(x)
            return jnp.exp(ln_mu + ln_sigma * z)
        return mean * -jnp.log1p(-u)

    ring0 = jnp.zeros((L, cap), dtype)
    slot = jnp.arange(cap, dtype=jnp.int32)[None, :]
    state = dict(
        step=jnp.int32(0),
        clock=zero, ctr=jnp.zeros((L,), jnp.uint32), seq=izero + 2,
        a_kind=izero + _A_START, a_t=zero, a_seq=izero,
        s_kind=izero + _S_START, s_t=zero, s_seq=izero + 1,
        s_keep=zero, s_item=zero,
        ring=ring0, head=izero, count=izero, produced=izero,
        n=izero, mean=zero, m2=zero,
        mn=jnp.full((L,), jnp.inf, dtype), mx=jnp.full((L,), -jnp.inf, dtype),
        events=izero, done=jnp.zeros((L,), bool), failed=jnp.zeros((L,), bool),
    )

    def live(s):
        a_t = jnp.where(s["a_kind"] == _A_NONE, jnp.inf, s["a_t"])
        s_t = jnp.where(s["s_kind"] == _S_NONE, jnp.inf, s["s_t"])
        nxt = jnp.minimum(a_t, s_t)
        return ~s["done"] & ~s["failed"] & jnp.isfinite(nxt) & (nxt <= t_end)

    def cond(s):
        return jnp.any(live(s)) & (s["step"] < max_steps)

    def body(s):
        go = live(s)
        a_t = jnp.where(s["a_kind"] == _A_NONE, jnp.inf, s["a_t"])
        s_t = jnp.where(s["s_kind"] == _S_NONE, jnp.inf, s["s_t"])
        is_a = (a_t < s_t) | ((a_t == s_t) & (s["a_seq"] < s["s_seq"]))
        do_a, do_s = go & is_a, go & ~is_a
        clock = jnp.where(do_a, a_t, jnp.where(do_s, s_t, s["clock"]))
        ctr, seq = s["ctr"], s["seq"]
        ring, head, count = s["ring"], s["head"], s["count"]

        # -- the arrival process ------------------------------------------
        dur_a = draw(ctr, arr_mean, False)
        ctr = ctr + do_a.astype(jnp.uint32)
        put = do_a & (s["a_kind"] == _A_NEXT)
        produced = s["produced"] + put.astype(jnp.int32)
        overflow = put & (count >= cap)
        put = put & ~overflow
        tail = (head + count) % cap
        ring = jnp.where(put[:, None] & (slot == tail[:, None]),
                         clock[:, None], ring)
        count = count + put.astype(jnp.int32)
        wake = put & (s["s_kind"] == _S_NONE)
        s_kind = jnp.where(wake, _S_WAKE, s["s_kind"])
        s_t = jnp.where(wake, clock, s["s_t"])
        s_seq = jnp.where(wake, seq, s["s_seq"])
        seq = seq + wake.astype(jnp.int32)
        more = do_a & ((s["a_kind"] == _A_START) | (produced < n_obj))
        a_kind = jnp.where(do_a, jnp.where(more, _A_NEXT, _A_NONE),
                           s["a_kind"])
        a_t2 = jnp.where(more, clock + dur_a, s["a_t"])
        a_seq = jnp.where(more, seq, s["a_seq"])
        seq = seq + more.astype(jnp.int32)

        # -- the server ----------------------------------------------------
        done_now = do_s & (s["s_kind"] == _S_DONE)
        x = clock - s["s_item"]
        n = s["n"] + done_now.astype(jnp.int32)
        d = x - s["mean"]
        mean = jnp.where(
            done_now, s["mean"] + d / jnp.maximum(n, 1).astype(dtype),
            s["mean"])
        m2 = jnp.where(done_now, s["m2"] + d * (x - mean), s["m2"])
        mn = jnp.where(done_now, jnp.minimum(s["mn"], x), s["mn"])
        mx = jnp.where(done_now, jnp.maximum(s["mx"], x), s["mx"])
        stop = done_now & (n >= n_obj)
        fresh = do_s & ((s["s_kind"] == _S_START) | done_now)
        dur_s = draw(ctr, srv_mean, service == "lognormal")
        ctr = ctr + fresh.astype(jnp.uint32)
        keep = jnp.where(fresh, dur_s, s["s_keep"])
        take = do_s & (count > 0)
        item = jnp.sum(jnp.where(slot == head[:, None], ring, 0), axis=1,
                       dtype=dtype)
        head = jnp.where(take, (head + 1) % cap, head)
        count = count - take.astype(jnp.int32)
        s_kind = jnp.where(do_s, jnp.where(take, _S_DONE, _S_NONE), s_kind)
        s_t = jnp.where(take, clock + keep, s_t)
        s_seq = jnp.where(take, seq, s_seq)
        seq = seq + take.astype(jnp.int32)
        return dict(
            step=s["step"] + 1, clock=clock, ctr=ctr, seq=seq,
            a_kind=a_kind, a_t=a_t2, a_seq=a_seq,
            s_kind=s_kind, s_t=s_t, s_seq=s_seq, s_keep=keep,
            s_item=jnp.where(take, item, s["s_item"]),
            ring=ring, head=head, count=count, produced=produced,
            n=n, mean=mean, m2=m2, mn=mn, mx=mx,
            events=s["events"] + go.astype(jnp.int32),
            done=s["done"] | stop, failed=s["failed"] | overflow,
        )

    s = jax.lax.while_loop(cond, body, state)
    failed = s["failed"] | live(s)
    return (s["n"], s["mean"], s["m2"], s["mn"], s["mx"], s["events"],
            s["clock"], failed)
