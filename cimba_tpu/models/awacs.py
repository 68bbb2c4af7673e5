"""AWACS radar scenario: many target agents + a scanning sensor with an
in-step vectorized physics computation.

Reference parity: the tutorial-5 AWACS scenario (`tutorial/tut_5_1.c` CPU,
`tut_5_3.c` multi-GPU): 1000 target coroutines fly straight-line legs with
random turn points; one sensor coroutine wakes every dwell interval and
scores all targets (terrain-masked detection) — on the GPU via CUDA kernels
launched from inside the coroutine.

TPU rendition of "level-3 parallelism": the physics IS jax — the sensor's
block computes detection over the whole [N, 2] position array in one
vectorized expression (later: a Pallas kernel via the same hook — a block
is arbitrary traced compute).  Per-target processes stay as framework
processes (count=N instances of one type), exercising the engine at the
reference's process counts.

Model state: user["pos_x/y"], user["vel_x/y"] [N] columns updated lazily — each
target process re-draws its leg at leg-end events; the sensor extrapolates
positions analytically between updates (pos + vel * (t - t_mark)), so
movement costs nothing between events, exactly like the reference storing
(position, velocity, t_mark) per target.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

import cimba_tpu.random as cr
from cimba_tpu import config
from cimba_tpu.config import INDEX_DTYPE
from cimba_tpu.core import api, cmd, dyn
from cimba_tpu.core.model import Model
from cimba_tpu.stats import summary as sm

_R = config.REAL
_I = INDEX_DTYPE

ARENA = 100.0          # square arena half-size
SPEED = 5.0            # target speed
LEG_MEAN = 4.0         # mean straight-leg duration
DETECT_RANGE = 40.0    # sensor detection radius
DWELL = 0.04 * 25      # default dwell interval (25x tut_5's 0.04 s)

# --- NN detection scorer (BASELINE configs[4]: "on-device NN scoring",
# the reference's CUDA physics hook `tutorial/tut_5_3.cu` re-imagined as a
# Pallas matmul stack).  Weights are fixed at import (a deterministic
# stand-in for a trained radar-SNR model): two hidden layers + a strong
# skip connection on the range-gaussian feature so near targets dominate
# detections, as in the threshold model.

_NN_F = 8    # features per target
_NN_H = 32   # hidden width


def _make_nn_weights():
    rng = np.random.default_rng(20260729)

    def glorot(shape):
        lim = np.sqrt(6.0 / (shape[0] + shape[1]))
        return rng.uniform(-lim, lim, shape).astype(np.float32)

    w1 = glorot((_NN_F, _NN_H))
    b1 = np.zeros(_NN_H, np.float32)
    w2 = glorot((_NN_H, _NN_H))
    b2 = np.zeros(_NN_H, np.float32)
    # final layer sees [h2, range_gaussian]; the fixed skip weight keeps
    # the scorer physically sensible without training
    w3 = np.concatenate(
        [0.3 * glorot((_NN_H, 1)), np.full((1, 1), 8.0, np.float32)]
    )
    b3 = np.full(1, -2.0, np.float32)
    return tuple(jnp.asarray(a) for a in (w1, b1, w2, b2, w3, b3))


_NN_WEIGHTS = _make_nn_weights()


def _feature_rows(px, py, vx, vy):
    """The scorer's 8 features and the range gaussian, elementwise over
    any shape: one home for the formula, shared by the plain path
    (columns) and the kernel (rows of a lane-dense tile)."""
    r2 = px * px + py * py
    g = jnp.exp(-r2 / jnp.float32(DETECT_RANGE**2))
    radial = (px * vx + py * vy) / jnp.float32(SPEED * DETECT_RANGE)
    feats = [
        px / ARENA,
        py / ARENA,
        r2 / jnp.float32(ARENA**2),
        g,
        vx / SPEED,
        vy / SPEED,
        radial,
        jnp.ones_like(g),
    ]
    return feats, g


def _nn_features(pos, vel):
    """[N,2],[N,2] -> ([N,F] f32 features, [N] f32 range gaussian)."""
    pos = pos.astype(jnp.float32)
    vel = vel.astype(jnp.float32)
    feats, g = _feature_rows(pos[:, 0], pos[:, 1], vel[:, 0], vel[:, 1])
    return jnp.stack(feats, axis=1), g


def _nn_forward(feats, g, w1, b1, w2, b2, w3, b3):
    """The matmul stack: [N,F] -> detection probability [N] (f32)."""
    h1 = jax.nn.relu(
        jnp.dot(feats, w1, preferred_element_type=jnp.float32) + b1
    )
    h2 = jax.nn.relu(
        jnp.dot(h1, w2, preferred_element_type=jnp.float32) + b2
    )
    h2g = jnp.concatenate([h2, g[:, None]], axis=1)
    logit = jnp.dot(h2g, w3, preferred_element_type=jnp.float32) + b3
    return jax.nn.sigmoid(logit[:, 0])


#: the kernel's name in a device trace (benchmark/metrics reads it)
KERNEL_NAME = "awacs_nn_score"
#: rows of one kernel block: targets laid along the 128-wide lane axis
ROW_BLOCK = 4096
#: the scorer's matmul precision on the MXU: f32 operands as written,
#: every pass of the f32 product (PERF.md, section 4)
PRECISION = jax.lax.Precision.HIGHEST


def _nn_kernel(x_ref, w1t, b1, w2t, b2, w3t, b3, out_ref):
    """One block of rows: x_ref [4, TB] (pos x, pos y, vel x, vel y)
    -> out_ref [1, TB] detection probabilities.  Rows ride the lane
    axis, so every operand and the output are lane-dense; the stack is
    the transposed form of :func:`_nn_forward`."""
    x = x_ref[...]
    feats, g = _feature_rows(x[0:1], x[1:2], x[2:3], x[3:4])
    f = jnp.concatenate(feats, axis=0)  # [F, TB]

    def dot(w, h):
        return jnp.dot(w, h, precision=PRECISION,
                       preferred_element_type=jnp.float32)

    h1 = jax.nn.relu(dot(w1t[...], f) + b1[...])
    h2 = jax.nn.relu(dot(w2t[...], h1) + b2[...])
    h2g = jnp.concatenate([h2, g], axis=0)  # [H+1, TB]
    out_ref[...] = jax.nn.sigmoid(dot(w3t[...], h2g) + b3[...])


def _score_rows(x, ws, interpret):
    """[4, R] rows of (pos x, pos y, vel x, vel y) -> [R] probabilities:
    ONE pallas_call over every row, tiled in blocks of ROW_BLOCK (fewer
    rows take one block of the next multiple of 128).  ``ws`` are the
    kernel's weights (:func:`_kernel_weights`)."""
    r = x.shape[1]
    tb = min(ROW_BLOCK, -(-r // 128) * 128)
    rpad = -(-r // tb) * tb
    x = jnp.pad(x, ((0, 0), (0, rpad - r)))
    whole = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))
    # traced with x64 off: index maps that return i64 do not lower
    with config.x64_scope(False):
        out = pl.pallas_call(
            _nn_kernel,
            out_shape=jax.ShapeDtypeStruct((1, rpad), jnp.float32),
            grid=(rpad // tb,),
            in_specs=[pl.BlockSpec((4, tb), lambda i: (0, i))]
            + [whole(a) for a in ws],
            out_specs=pl.BlockSpec((1, tb), lambda i: (0, i)),
            interpret=interpret,
            name=KERNEL_NAME,
        )(x, *ws)
    return out[0, :r]


def _kernel_weights():
    """The weights as the kernel takes them: transposed, biases as
    columns ([out, in] @ [in, TB])."""
    w1, b1, w2, b2, w3, b3 = _NN_WEIGHTS
    return (w1.T, b1[:, None], w2.T, b2[:, None], w3.T, b3[:, None])


def _rows(pos, vel):
    """[..., N, 2] positions and velocities -> [4, prod(...) * N]: the
    four coordinates stacked whole, so no minor-axis transpose."""
    x = jnp.stack([pos[..., 0], pos[..., 1], vel[..., 0], vel[..., 1]])
    return x.astype(jnp.float32).reshape(4, -1)


def _pallas_scores(interpret):
    """The Pallas scorer, batched by hand: under ``vmap`` (the boundary
    phase steps every lane at once) the lanes' targets become the rows of
    one call, instead of a grid of one-lane programs.  The weights ride
    as arguments, not as closed-over constants: a vmapped switch (the
    block table) broadcasts every operand of its branches over the lanes,
    and the rule takes lane 0's copy of these fixed weights back."""

    @jax.custom_batching.custom_vmap
    def scores(pos, vel, *ws):
        return _score_rows(_rows(pos, vel), ws, interpret)

    @scores.def_vmap
    def _batched(axis_size, in_batched, pos, vel, *ws):
        pos, vel = (
            a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, b in zip((pos, vel), in_batched)
        )
        ws = [w[0] if b else w for w, b in zip(ws, in_batched[2:])]
        out = _score_rows(_rows(pos, vel), ws, interpret)
        return out.reshape(pos.shape[:-1]), True

    return lambda pos, vel: scores(pos, vel, *_kernel_weights())


def nn_scores(pos, vel, *, use_pallas=None, interpret=False):
    """Detection probabilities [N] for all targets — the physics hook.

    ``use_pallas=True`` executes the stack as the Pallas kernel
    ``awacs_nn_score`` (matmuls on the MXU); under ``vmap`` every lane's
    targets are the rows of one call.  ``False`` is the plain-jnp trace
    (the oracle for the equivalence test).  ``None`` auto-selects Pallas
    on TPU.  The kernel is always pure f32 — detection scores need no f64
    regardless of the active profile.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and not config.KERNEL_MODE
    if use_pallas:
        return _pallas_scores(interpret)(pos, vel)
    feats, g = _nn_features(pos, vel)
    return _nn_forward(feats, g, *_NN_WEIGHTS)


def detections_path(sims):
    """The pooled statistic of an AWACS experiment: detections per dwell
    (``run_experiment_stream(..., summary_path=awacs.detections_path)``).
    Module level, so its identity keys the fold program."""
    return sims.user["detections"]


def build(n_targets: int, scoring: str = "nn", dwell: float = DWELL):
    """``scoring="nn"`` (default) runs the Pallas/MLP detection scorer —
    the reference's GPU physics hook (`tut_5_3.cu`) as a TPU matmul stack;
    ``"threshold"`` keeps the closed-form linear-falloff score (the
    tut_5_1 CPU model and the legacy behavior).  ``dwell`` is the
    sensor's dwell interval: the published 0.04 s, or the default
    ``DWELL`` (25x it, the scaled pattern the examples run)."""
    if scoring not in ("nn", "threshold"):
        raise ValueError(f"scoring must be 'nn' or 'threshold': {scoring}")
    # a lane dispatches about n_targets * dwell / LEG_MEAN leg ends
    # between two dwells (10 at the published sizes); a boundary round
    # lets it run 30% more, and the step that defers its dwell
    # (PERF.md, section 4: 14 measured against waiting for every lane)
    legs = n_targets * dwell / LEG_MEAN
    m = Model(
        "awacs",
        boundary_round_steps=math.ceil(1.3 * legs) + 1,
        # the general event table holds only timers/user events (process
        # holds and resumes live in the dense per-pid wake table) and
        # this model schedules neither — a token capacity suffices where
        # 2*n_targets+8 slots were needed before the wake-table split,
        # and the per-event table scan cost scales with it
        event_cap=8,
        guard_cap=2,
    )

    @m.user_state
    def user_init(params):
        (t_end,) = params
        return {
            "t_end": jnp.asarray(t_end, _R),
            # positions/velocities as split x/y [N] columns, not [N,2]:
            # per-event one-hot row access on [N,2] pays a rank-expanded
            # mask (2N elements per op); split columns share the cached
            # [N] one-hot at exactly matching shape, halving the footprint of the
            # kernel path's hottest model-side ops
            "pos_x": jnp.zeros((n_targets,), _R),
            "pos_y": jnp.zeros((n_targets,), _R),
            "vel_x": jnp.zeros((n_targets,), _R),
            "vel_y": jnp.zeros((n_targets,), _R),
            "t_mark": jnp.zeros((n_targets,), _R),
            "detections": sm.empty(),  # per-dwell detection counts
            "dwells": jnp.zeros((), _I),
        }

    def _current_positions(sim):
        dt = sim.clock - sim.user["t_mark"]
        return jnp.stack(
            [
                sim.user["pos_x"] + sim.user["vel_x"] * dt,
                sim.user["pos_y"] + sim.user["vel_y"] * dt,
            ],
            axis=1,
        )

    @m.block
    def tgt_leg(sim, p, sig):
        """Start a new straight leg: random heading, exponential duration."""
        # target index within the type (targets are pids 0..N-1)
        idx = p
        # fold the position forward to now, then draw a new velocity
        # one-hot dynamic reads (dyn.dget): a raw traced-index gather has
        # no Mosaic lowering for the kernel path
        # grouped read: all five [N] columns at one pid, so the
        # scan-over-rows arm serves them from a single block loop
        t_mark, vel_x, vel_y, pos_x, pos_y = dyn.dget_tree(
            (sim.user["t_mark"], sim.user["vel_x"], sim.user["vel_y"],
             sim.user["pos_x"], sim.user["pos_y"]), idx,
        )
        dt = sim.clock - t_mark
        px = pos_x + vel_x * dt
        py = pos_y + vel_y * dt
        # soft-bounce: if outside the arena, head back toward the center.
        # Directions are selected as unit VECTORS, not heading angles:
        # cos/sin(arctan2(-y,-x)) in closed form is just -pos/|pos|, and
        # atan2 has no Pallas TPU lowering (the kernel path compiles this
        # block through Mosaic).
        sim, heading = api.draw(sim, cr.uniform, 0.0, 2.0 * jnp.pi)
        r = jnp.sqrt(px * px + py * py)
        outside = r > ARENA
        inv_r = 1.0 / jnp.maximum(r, 1e-6)
        vx = SPEED * jnp.where(outside, -px * inv_r, jnp.cos(heading))
        vy = SPEED * jnp.where(outside, -py * inv_r, jnp.sin(heading))
        u = sim.user
        w_pos_x, w_pos_y, w_vel_x, w_vel_y, w_t_mark = dyn.dset_tree(
            (u["pos_x"], u["pos_y"], u["vel_x"], u["vel_y"], u["t_mark"]),
            idx, (px, py, vx, vy, sim.clock),
        )
        sim = api.set_user(
            sim,
            {
                **u,
                "pos_x": w_pos_x,
                "pos_y": w_pos_y,
                "vel_x": w_vel_x,
                "vel_y": w_vel_y,
                "t_mark": w_t_mark,
            },
        )
        sim, leg = api.draw(sim, cr.exponential, LEG_MEAN)
        done = sim.clock >= sim.user["t_end"]
        return sim, cmd.select(
            done, cmd.exit_(), cmd.hold(leg, next_pc=tgt_leg.pc)
        )

    @m.boundary_block
    def sensor_dwell(sim, p, sig):
        """One radar dwell: vectorized detection over ALL targets — the
        physics hook (CUDA kernel in the reference, jax/Pallas here).

        A BOUNDARY block: its dispatch runs in the boundary phase
        (``loop.make_boundary_apply``) — inside the XLA chunk program,
        or between Pallas chunks on the kernel path — so the scorer runs
        once per dwell over every lane's targets instead of executing
        masked at every event (it is needed at about 1 event in 11 at
        the published dwell).  Entered only via hold resumes and process
        entry, as the boundary contract requires."""
        pos = _current_positions(sim)
        # detection scores for every target, plus one uniform draw for the
        # whole dwell (scan noise)
        sim, noise = api.draw(sim, cr.uniform01)
        if scoring == "nn":
            vel = jnp.stack(
                [sim.user["vel_x"], sim.user["vel_y"]], axis=1
            )
            p_det = nn_scores(pos, vel).astype(_R)
        else:
            r2 = jnp.sum(pos * pos, axis=1)
            p_det = jnp.clip(1.2 - jnp.sqrt(r2) / DETECT_RANGE, 0.0, 1.0)
        detected = jnp.sum((p_det > noise).astype(_R))
        u = sim.user
        sim = api.set_user(
            sim,
            {
                **u,
                "detections": sm.add(u["detections"], detected),
                "dwells": u["dwells"] + 1,
            },
        )
        done = sim.clock >= sim.user["t_end"]
        sim = api.stop(sim, done)
        return sim, cmd.select(
            done, cmd.exit_(), cmd.hold(dwell, next_pc=sensor_dwell.pc)
        )

    m.process("target", entry=tgt_leg, count=n_targets)  # pids 0..N-1
    m.process("sensor", entry=sensor_dwell, prio=1)      # pid N
    return m.build(), {}


def params(t_end: float):
    return (t_end,)