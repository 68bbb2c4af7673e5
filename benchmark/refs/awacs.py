"""A plain AWACS radar study, one replication per lane, in ``jax.numpy``.

The semantics of upstream cimba's tutorial 5 (``tutorial/tut_5_1.c``;
``README.md:299-303``) as cimba-tpu's ``models/awacs.py`` runs them:

- ``n_targets`` targets start at the centre of the arena at t = 0, each
  with a resume due at once; a target's resume folds its position
  forward along its velocity to now, draws a uniform heading on
  [0, 2 pi), turns back toward the centre instead where it is outside
  the arena (a soft bounce), draws an exponential leg of mean
  ``LEG_MEAN`` and resumes at its end; at or past ``t_end`` it ends;
- one sensor resumes at t = 0 and every ``dwell`` after: it draws one
  uniform noise, scores every target's position and velocity with a
  fixed scorer (two hidden layers of 32 and a skip on the range
  gaussian, weights from seed 20260729) and records the count of
  targets whose score exceeds the noise; at or past ``t_end`` it stops
  the replication;
- the next event is the earliest resume; ties go by priority (the
  sensor's 1 over the targets' 0), then by schedule order (starts in
  target order, then the sensor; each resume takes the next number);
- one stream per replication (:mod:`benchmark.refs.rng`), one draw per
  variate, in the order the events draw them.

Departures from tut_5, which the program shares: the repo's scorer
stands in for upstream's terrain-masked detection; targets start at the
centre instead of spread over the arena; the sensor's score is the count
over all targets, not a per-target track.

Lanes run side by side, at most one event per lane per step of a
``while_loop``.  A lane whose next event is a dwell waits until every
live lane's is, and then the whole block dwells in one step: every
lane's dwells fall at the same instants, so waiting changes no lane's
events or their order, and the scorer runs once per dwell, not at every
step.  Every time and statistic is computed in ``dtype``; the scorer's
matmuls in float32 at the highest precision (``dtype`` below float32
runs them in ``dtype``).  The control runs the same code in bfloat16.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.refs import rng
from benchmark.refs.mg1 import Out

#: lanes a step holds at most; more are run block after block
BLOCK = 4096

ARENA = 100.0
SPEED = 5.0
LEG_MEAN = 4.0
DETECT_RANGE = 40.0
WEIGHT_SEED = 20260729
HIDDEN = 32


class Lanes(NamedTuple):
    """Per-lane inputs (host arrays of one length) and the study's
    sizes."""

    seed: np.ndarray   # experiment seed of each lane
    rep: np.ndarray    # replication index of each lane
    t_end: np.ndarray  # horizon of each lane
    n_targets: int
    dwell: float


def weights():
    """The scorer's fixed weights: Glorot-uniform layers of 8 -> 32 ->
    32, an output layer over [hidden, range gaussian] whose gaussian
    weight is 8, bias -2."""
    g = np.random.default_rng(WEIGHT_SEED)

    def glorot(shape):
        lim = np.sqrt(6.0 / (shape[0] + shape[1]))
        return g.uniform(-lim, lim, shape).astype(np.float32)

    w1 = glorot((8, HIDDEN))
    w2 = glorot((HIDDEN, HIDDEN))
    w3 = np.concatenate([0.3 * glorot((HIDDEN, 1)),
                         np.full((1, 1), 8.0, np.float32)])
    return (w1, np.zeros(HIDDEN, np.float32), w2,
            np.zeros(HIDDEN, np.float32), w3, np.full(1, -2.0, np.float32))


def score(px, py, vx, vy, dtype=jnp.float32):
    """Detection probability of each target (any leading shape)."""
    wdt = jnp.float32 if jnp.finfo(dtype).bits >= 32 else dtype
    px, py, vx, vy = (a.astype(wdt) for a in (px, py, vx, vy))
    r2 = px * px + py * py
    gauss = jnp.exp(-r2 / jnp.asarray(DETECT_RANGE ** 2, wdt))
    feats = jnp.stack([
        px / ARENA, py / ARENA, r2 / jnp.asarray(ARENA ** 2, wdt), gauss,
        vx / SPEED, vy / SPEED,
        (px * vx + py * vy) / jnp.asarray(SPEED * DETECT_RANGE, wdt),
        jnp.ones_like(gauss)], axis=-1)
    w1, b1, w2, b2, w3, b3 = (jnp.asarray(w, wdt) for w in weights())
    h1 = jax.nn.relu(feats @ w1 + b1)
    h2 = jax.nn.relu(h1 @ w2 + b2)
    logit = jnp.concatenate([h2, gauss[..., None]], axis=-1) @ w3 + b3
    return jax.nn.sigmoid(logit[..., 0]).astype(dtype)


def run(lanes: Lanes, *, service=None, cap=None, dtype=jnp.float32,
        max_steps: int = 1 << 17) -> Out:
    """Run every lane.  ``service`` and ``cap`` are accepted and unused:
    the harness hands every reference the queueing models' keywords.
    ``n`` is the dwells of a lane, ``mean`` its detections per dwell.  A
    lane still live after ``max_steps`` steps failed: 60 s of the study
    takes about 40,000 (a bfloat16 clock stops advancing past t = 16)."""
    del service, cap
    n = len(lanes.seed)
    k0, k1 = rng.stream_keys(lanes.seed, lanes.rep)
    t_end = np.asarray(lanes.t_end, np.float64)
    outs = []
    for lo in range(0, n, BLOCK):
        hi = min(lo + BLOCK, n)
        width = BLOCK if n > BLOCK else n
        pad = width - (hi - lo)

        def take(a, fill):
            a = a[lo:hi]
            return np.concatenate([a, np.full(pad, fill, a.dtype)]) if pad else a

        with jax.default_matmul_precision("highest"):
            res = _block(take(k0, 0), take(k1, 0), take(t_end, -np.inf),
                         n_targets=int(lanes.n_targets),
                         dwell=float(lanes.dwell), dtype=jnp.dtype(dtype),
                         max_steps=max_steps)
        outs.append([np.asarray(x)[: hi - lo] for x in res])
    return Out(*(np.concatenate(c) for c in zip(*outs)))


@partial(jax.jit, static_argnames=("n_targets", "dwell", "dtype", "max_steps"))
def _block(k0, k1, t_end, *, n_targets, dwell, dtype, max_steps):
    N, P = n_targets, n_targets + 1
    L = k0.shape[0]
    f = lambda x: jnp.asarray(x).astype(dtype)
    t_end = f(t_end)
    zero = jnp.zeros((L,), dtype)
    izero = jnp.zeros((L,), jnp.int32)
    cols = jnp.zeros((L, N), dtype)
    prio = jnp.concatenate([jnp.zeros(N, jnp.int32), jnp.ones(1, jnp.int32)])
    pid = jnp.arange(P, dtype=jnp.int32)[None, :]
    two_pi = f(2.0 * np.pi)
    never = jnp.asarray(jnp.inf, dtype)
    state = dict(
        step=jnp.int32(0), clock=zero, ctr=jnp.zeros((L,), jnp.uint32),
        seq=izero + P,
        wake_t=jnp.zeros((L, P), dtype),
        wake_seq=jnp.broadcast_to(pid, (L, P)),
        px=cols, py=cols, vx=cols, vy=cols, t_mark=cols,
        n=izero, mean=zero, m2=zero,
        mn=jnp.full((L,), jnp.inf, dtype), mx=jnp.full((L,), -jnp.inf, dtype),
        events=izero, done=jnp.zeros((L,), bool),
    )

    def live(s):
        return ~s["done"] & jnp.any(s["wake_t"] < never, axis=1)

    def cond(s):
        return jnp.any(live(s)) & (s["step"] < max_steps)

    def body(s):
        go = live(s)
        wt, ws = s["wake_t"], s["wake_seq"]
        tmin = jnp.min(wt, axis=1)
        at = wt == tmin[:, None]
        pmax = jnp.max(jnp.where(at, prio[None, :], -1), axis=1)
        cand = at & (prio[None, :] == pmax[:, None])
        smin = jnp.min(jnp.where(cand, ws, jnp.iinfo(jnp.int32).max), axis=1)
        pick = cand & (ws == smin[:, None])
        to_sensor = pick[:, N]
        # the block dwells together: once every live lane's next event
        # is a dwell
        dwell_now = jnp.all(to_sensor | ~go)
        go_t = go & ~to_sensor
        go_s = go & to_sensor & dwell_now
        step_go = go_t | go_s
        clock = jnp.where(step_go, tmin, s["clock"])
        ctr, seq = s["ctr"], s["seq"]

        # -- a target's leg end --------------------------------------------
        oh = pick[:, :N] & go_t[:, None]

        def at_j(a):
            return jnp.sum(jnp.where(oh, a, 0), axis=1, dtype=dtype)

        dt = clock - at_j(s["t_mark"])
        px = at_j(s["px"]) + at_j(s["vx"]) * dt
        py = at_j(s["py"]) + at_j(s["vy"]) * dt
        heading = two_pi * rng.uniform(k0, k1, ctr, dtype)
        r = jnp.sqrt(px * px + py * py)
        outside = r > ARENA
        inv_r = 1.0 / jnp.maximum(r, f(1e-6))
        vx = SPEED * jnp.where(outside, -px * inv_r, jnp.cos(heading))
        vy = SPEED * jnp.where(outside, -py * inv_r, jnp.sin(heading))
        leg = LEG_MEAN * -jnp.log1p(-rng.uniform(k0, k1, ctr + 1, dtype))
        put = lambda a, v: jnp.where(oh, v[:, None], a)
        tgt = dict(px=put(s["px"], px), py=put(s["py"], py),
                   vx=put(s["vx"], vx), vy=put(s["vy"], vy),
                   t_mark=put(s["t_mark"], clock))
        t_ends = go_t & (clock >= t_end)
        t_next = jnp.where(t_ends, never, clock + leg)

        # -- the sensor's dwell ----------------------------------------------
        def dwell_scores(_):
            since = clock[:, None] - s["t_mark"]
            return score(s["px"] + s["vx"] * since, s["py"] + s["vy"] * since,
                         s["vx"], s["vy"], dtype)

        p_det = jax.lax.cond(dwell_now & jnp.any(go_s), dwell_scores,
                             lambda _: jnp.zeros((L, N), dtype), None)
        noise = rng.uniform(k0, k1, ctr, dtype)
        x = jnp.sum((p_det > noise[:, None]).astype(dtype), axis=1, dtype=dtype)
        n = s["n"] + go_s.astype(jnp.int32)
        d = x - s["mean"]
        mean = jnp.where(go_s, s["mean"] + d / jnp.maximum(n, 1).astype(dtype),
                         s["mean"])
        m2 = jnp.where(go_s, s["m2"] + d * (x - mean), s["m2"])
        mn = jnp.where(go_s, jnp.minimum(s["mn"], x), s["mn"])
        mx = jnp.where(go_s, jnp.maximum(s["mx"], x), s["mx"])
        stop = go_s & (clock >= t_end)
        s_next = clock + f(dwell)

        # -- the resume tables -------------------------------------------------
        resched = (go_t & ~t_ends) | (go_s & ~stop)
        new_t = jnp.where(go_t, t_next, jnp.where(stop, never, s_next))
        hit = pick & step_go[:, None]
        wake_t = jnp.where(hit, new_t[:, None], wt)
        wake_seq = jnp.where(hit & resched[:, None], seq[:, None], ws)
        return dict(
            step=s["step"] + 1, clock=clock,
            ctr=ctr + jnp.where(go_t, 2, jnp.where(go_s, 1, 0)).astype(jnp.uint32),
            seq=seq + resched.astype(jnp.int32),
            wake_t=wake_t, wake_seq=wake_seq, **tgt,
            n=n, mean=mean, m2=m2, mn=mn, mx=mx,
            events=s["events"] + step_go.astype(jnp.int32),
            done=s["done"] | stop,
        )

    s = jax.lax.while_loop(cond, body, state)
    failed = live(s)
    return (s["n"], s["mean"], s["m2"], s["mn"], s["mx"], s["events"],
            s["clock"], failed)
