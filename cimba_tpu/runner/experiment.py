"""The experiment runner: replications fanned over lanes and chips.

Reference parity: ``cimba_run`` (`src/cimba.c:232-276`) — a pthread worker
pool pulling trials off an atomic counter, with per-thread init/exit hooks
and longjmp failure recovery, returning the failed-trial count.

TPU redesign: replications are the leading axis of every state array.

* The atomic work-stealing dispenser disappears: partitioning is static —
  replication r is lane r of the batch (`vmap`), shard r // per_device of
  the mesh (`shard_map`).  Balanced because every replication runs the
  same model; divergence in *length* is absorbed by the batched
  while-loop's masking.
* Thread hooks (the reference's per-thread CUDA stream setup,
  `tutorial/tut_5_3.c:854-880`) have no analog: SPMD code is identical on
  every chip, and device-local setup is XLA's job.
* Failure recovery: a failed replication freezes with ``sim.err`` set and
  is counted (`result.n_failed`) — the §3.5 longjmp story without a
  longjmp, and unlike the reference the failed replication's partial state
  remains inspectable.
* Cross-replication statistics: ``pooled_summary`` tree-merges the
  per-replication Pébay summaries; under a mesh the per-shard partials go
  through ``all_gather`` over ICI and merge identically on every device.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from cimba_tpu.core.loop import (
    Sim,
    drive_chunks,
    init_sim,
    make_chunk,
    make_lanes_run,
)
from cimba_tpu.core.model import ModelSpec
from cimba_tpu.stats import summary as sm

REP_AXIS = "rep"


def default_summary_path(sims):
    """The default pooled statistic: the per-replication ``wait``
    summary every shipped queueing model records.  A NAMED module-level
    function (not a fresh lambda) so every caller that leaves
    ``summary_path`` unset shares one identity — the fold-program cache
    and the serving layer's request-compatibility key both key on it."""
    return sims.user["wait"]


class ExperimentResult(NamedTuple):
    sims: Sim                 # batched: every leaf has leading axis [R]
    n_failed: jnp.ndarray     # replications with err != 0
    total_events: jnp.ndarray # dispatched events across all replications


class StreamResult(NamedTuple):
    """What :func:`run_experiment_stream` returns: pooled statistics for
    all R replications WITHOUT the batched sims (they were streamed
    through the device in waves and folded into these accumulators)."""

    summary: sm.Summary        # pooled over every replication
    n_failed: jnp.ndarray      # replications with err != 0, all waves
    total_events: jnp.ndarray  # i64 dispatched events, all waves
    n_waves: int
    n_regrows: int             # wave-granular capacity regrows performed
    metrics: Any = None        # pooled obs.metrics registry when enabled
    audit: Any = None          # run card (docs/18_audit.md) when audited
    #: models with boundary blocks: {name: i64 total} over every chunk,
    #: names ``core.loop.BOUNDARY_COUNTERS``; None for the others
    boundary: Any = None


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D replication mesh over the available devices."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(devs, (REP_AXIS,))


def _broadcast_params(params: Any, n: int):
    """Scalar params broadcast to [n]; leaves already [n, ...] pass through."""
    def bc(x):
        x = jnp.asarray(x)
        if x.ndim > 0 and x.shape[0] == n:
            return x
        return jnp.broadcast_to(x, (n,) + x.shape)

    return jax.tree.map(bc, params)


def _slice_params(params: Any, n_total: int, lo: int, n: int):
    """The wave view of an experiment array: swept leaves (leading axis
    ``n_total``) are sliced to rows ``[lo, lo+n)``; every other leaf is
    broadcast to the wave exactly as ``_broadcast_params`` would have
    broadcast it to the full batch.

    ``_slice_params(p, R, lo, n)`` is bitwise
    ``_broadcast_params(p, R)[lo:lo+n]`` on every leaf — the wave's
    lanes see exactly the parameter rows the monolithic run's lanes
    ``lo..lo+n-1`` see, WITHOUT materializing any [R]-sized array (the
    M/G/1 sweep regression, pinned in tests/test_stream.py).  Shared
    leaves are broadcast here (not left to a later ``_broadcast_params``
    pass) so a shared leaf whose leading axis happens to equal the wave
    size cannot be misread as per-lane data.

    This is also the delivery contract ``sweep.SweepGrid`` rows ride:
    a grid cell's scalar row broadcast to its wave slot here equals
    the monolithic ``grid.rows()`` broadcast row-for-row, which is
    what makes the sweep engine's cells bitwise the monolithic sweep
    (docs/16_sweeps.md)."""
    def sl(x):
        x = jnp.asarray(x)
        if x.ndim > 0 and x.shape[0] == n_total:
            return x[lo : lo + n]
        return jnp.broadcast_to(x, (n,) + x.shape)

    return jax.tree.map(sl, params)


def run_experiment(
    spec: ModelSpec,
    params: Any,
    n_replications: int,
    *,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    t_end: Optional[float] = None,
    with_report: bool = False,
    profile_dir: Optional[str] = None,
):
    """Run ``n_replications`` independent replications of ``spec``.

    ``params`` is the experiment array (reference: the user's trial struct
    array): a pytree whose leaves are either scalars (shared by all
    replications) or arrays with leading axis ``n_replications`` (a
    parameter sweep — the M/G/1 4x5x10 sweep pattern).

    ``with_report=True`` returns ``(ExperimentResult, obs.prof.RunReport)``
    instead: the run goes through the AOT path so the report carries the
    trace/compile/execute wall-time split, plus device memory stats and —
    when the metrics registry is enabled — the pooled metrics snapshot.
    ``profile_dir`` additionally wraps the execute leg in a
    ``jax.profiler.trace`` context writing there.
    """
    run = make_lanes_run(spec, t_end=t_end)
    pb = _broadcast_params(params, n_replications)
    reps = jnp.arange(n_replications)

    def vm(reps, p):
        sims = jax.vmap(lambda r, q: init_sim(spec, seed, r, q))(reps, p)
        return run(sims)[1]

    timings = None
    if mesh is None:
        fn = vm
    else:
        n_dev = mesh.devices.size
        if n_replications % n_dev:
            raise ValueError(
                f"n_replications={n_replications} must divide evenly over "
                f"{n_dev} devices"
            )

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(REP_AXIS), P(REP_AXIS)),
            out_specs=P(REP_AXIS),
            check_vma=False,  # cond/switch branches mix replicated constants
            # with varying data; semantics are plain SPMD over 'rep'
        )
        def sharded(reps_local, p_local):
            return vm(reps_local, p_local)

        fn = sharded

    if with_report:
        from cimba_tpu.obs import prof as _prof

        sims, timings = _prof.profiled_call(
            jax.jit(fn), reps, pb, profile_dir=profile_dir
        )
    else:
        sims = jax.jit(fn)(reps, pb)

    result = ExperimentResult(
        sims=sims,
        n_failed=jnp.sum((sims.err != 0).astype(jnp.int32)),
        total_events=jnp.sum(sims.n_events),
    )
    if not with_report:
        return result
    from cimba_tpu.obs import metrics as _metrics

    snap = None
    if sims.metrics is not None:
        snap = _metrics.snapshot(jax.jit(_metrics.pool)(sims.metrics), spec)
    report = _prof.build_report(
        timings,
        n_replications=n_replications,
        n_failed=int(result.n_failed),
        total_events=int(result.total_events),
        metrics=snap,
        profile_dir=profile_dir,
    )
    return result, report


def run_experiment_regrow(
    spec: ModelSpec,
    params: Any,
    n_replications: int,
    *,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    t_end: Optional[float] = None,
    max_regrows: int = 4,
):
    """``run_experiment`` with the capacity escape hatch: if any
    replication died with ``ERR_EVENT_OVERFLOW``/``ERR_GUARD_OVERFLOW``,
    double the event cap and re-run the WHOLE batch under the grown
    spec (a re-jit at the larger shapes).

    Reference parity: the reference's hashheap grows amortized-doubling
    under the hood (`src/cmi_hashheap.c:384-426`); under jit capacities
    are static shapes, so growth happens between jit calls instead.
    Re-running every lane (not only the overflowed ones) keeps the
    batched Sim shape-consistent, and costs nothing in correctness:
    replication streams are counter-derived from (seed, rep), so healthy
    lanes reproduce bit-identically under any capacity.

    Returns ``(result, final_spec, n_regrows)`` — ``final_spec`` is what
    actually ran last (callers reuse it to skip re-discovery).
    """
    import dataclasses

    import numpy as np

    from cimba_tpu.core import loop as _cl

    # dense guards cannot overflow; the event table is the one growable cap
    grow_errs = (_cl.ERR_EVENT_OVERFLOW,)
    for n_regrows in range(max_regrows + 1):
        result = run_experiment(
            spec, params, n_replications, seed=seed, mesh=mesh,
            t_end=t_end,
        )
        err = np.asarray(result.sims.err)
        if not np.isin(err, grow_errs).any():
            return result, spec, n_regrows
        if n_regrows < max_regrows:
            spec = dataclasses.replace(
                spec, event_cap=2 * spec.event_cap,
            )
    raise RuntimeError(
        f"run_experiment_regrow: capacity overflow persists after "
        f"{max_regrows} doublings (last run at event_cap={spec.event_cap}) "
        "— the model schedules unboundedly or the cap estimate is "
        "pathologically low"
    )


def _chunk_program(
    spec: ModelSpec,
    t_end,
    chunk_steps: int,
    mesh: Optional[Mesh],
    donate: bool = True,
    audit: bool = False,
):
    """One compiled chunk program: ``chunk(sims) -> (sims, any_live)``,
    jitted with the batched Sim DONATED so chunk n+1 aliases chunk n's
    output buffers — zero inter-chunk copies, flat steady-state device
    memory (the donation contract, docs/12_streaming.md).  Under a mesh
    the chunk runs per-shard with the liveness flag psum-reduced over
    ICI, so the host polls one replicated scalar.

    A model with boundary blocks adds the chunk's boundary counters
    (``core.loop.BOUNDARY_COUNTERS``) as the third output, summed over
    the shards under a mesh.

    ``audit=True`` (docs/18_audit.md) appends the per-wave carry-class
    digest vector as the last output.  Under a mesh the digest is
    computed per shard with GLOBAL lane offsets (``axis_index x local
    lanes``) and psum-combined — integer sums mod 2^64 are exact and
    commutative, so the combined digest equals the single-device digest
    of the same wave.  ``audit=False`` is the historical program,
    jaxpr-identical (pinned in tests/test_audit.py)."""
    if mesh is None:
        chunk = make_chunk(
            spec, t_end=t_end, max_steps=chunk_steps, audit=audit,
        )
    else:
        chunk_local = make_chunk(spec, t_end=t_end, max_steps=chunk_steps)
        counted = bool(spec.boundary_pcs)
        out_specs = (
            (P(REP_AXIS), P())
            + ((P(),) if counted else ())
            + ((P(),) if audit else ())
        )

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(REP_AXIS),),
            out_specs=out_specs,
            check_vma=False,
        )
        def chunk(sims):
            local = chunk_local(sims)
            sims, live_local = local[0], local[1]
            n_live = jax.lax.psum(
                live_local.astype(jnp.int32), REP_AXIS
            )
            out = (sims, n_live > 0)
            if counted:
                # each shard ran its own rounds: the counters add up
                out = out + (jax.lax.psum(local[2], REP_AXIS),)
            if audit:
                from cimba_tpu.obs import audit as _obs_audit

                n_local = jax.tree.leaves(sims)[0].shape[0]
                off = jax.lax.axis_index(REP_AXIS).astype(
                    jnp.uint64
                ) * jnp.uint64(n_local)
                dig = _obs_audit.sim_digest(sims, lane_offset=off)
                out = out + (jax.lax.psum(dig, REP_AXIS),)
            return out

    return jax.jit(chunk, donate_argnums=(0,) if donate else ())


def _seed_column(seed, n: int):
    """A per-lane ``[n]`` u64 seed column (one request seed broadcast).

    Seed is per-lane DATA on the chunked/streamed/served paths, not a
    constant baked into the compiled init program: ``init_sim`` derives
    each lane's stream as ``fmix64(seed + c*rep)`` — pure integer
    arithmetic, bit-identical whether ``seed`` arrives traced or
    static — so requests differing only in seed share one compiled
    program (docs/14_wave_packing.md)."""
    return jnp.full((n,), jnp.asarray(seed, jnp.uint64))


def _horizon_column(t_end, n: int):
    """A per-lane ``[n]`` horizon column: ``t_end`` broadcast, with
    ``None`` (run to completion) encoded as ``+inf`` — the lane-data
    image of the static ``t_end`` knob (see ``Sim.t_stop``)."""
    from cimba_tpu import config as _config

    return jnp.full(
        (n,), jnp.inf if t_end is None else t_end, _config.TIME
    )


def _init_program(spec: ModelSpec, mesh: Optional[Mesh]):
    """``init(reps, seeds, t_stops, params) -> batched Sim`` (sharded
    over the mesh when one is given, so the chunk program never
    reshards).

    ``seeds`` is the per-lane u64 seed column (:func:`_seed_column`)
    and ``t_stops`` the per-lane horizon column
    (:func:`_horizon_column`) — both lane DATA, so one compiled init
    program serves every (seed, horizon) mix; ``t_stops=None`` omits
    the horizon leaf entirely (the Sim then matches the historical
    pytree — static-``t_end`` programs and old checkpoints)."""
    def init(reps, seeds, t_stops, p):
        return jax.vmap(
            lambda r, s, t, q: init_sim(spec, s, r, q, t_stop=t)
        )(reps, seeds, t_stops, p)

    if mesh is not None:
        init = partial(
            shard_map,
            mesh=mesh,
            in_specs=(
                P(REP_AXIS), P(REP_AXIS), P(REP_AXIS), P(REP_AXIS),
            ),
            out_specs=P(REP_AXIS),
            check_vma=False,
        )(init)
    return jax.jit(init)


def _refill_program(spec: ModelSpec, mesh: Optional[Mesh]):
    """One compiled refill program: ``refill(sims, mask, reps, seeds,
    t_stops, params) -> sims`` (:func:`cimba_tpu.core.loop.
    make_refill`), jitted with the batched Sim DONATED so a boundary
    splice aliases the wave's buffers instead of copying them — the
    same zero-copy contract the chunk program rides
    (docs/12_streaming.md).  Under a mesh every operand is lane-data
    sharded over ``REP_AXIS``, so the splice never reshards the wave
    the chunk program runs on.  Compiled once per wave shape alongside
    ``(init, chunk)`` — after warmup a refill is a cached dispatch,
    never a compile (docs/22_refill.md)."""
    from cimba_tpu.core.loop import make_refill

    refill = make_refill(spec)
    if mesh is not None:
        refill = partial(
            shard_map,
            mesh=mesh,
            in_specs=(
                P(REP_AXIS), P(REP_AXIS), P(REP_AXIS), P(REP_AXIS),
                P(REP_AXIS), P(REP_AXIS),
            ),
            out_specs=P(REP_AXIS),
            check_vma=False,
        )(refill)
    return jax.jit(refill, donate_argnums=(0,))


def _fused_init_program(fused, mesh: Optional[Mesh]):
    """The fused twin of :func:`_init_program`: ``init(reps, seeds,
    t_stops, sids, params) -> batched Sim`` with a per-lane ``sids``
    (spec-id) column switching each lane's ``init_sim`` through its own
    member spec (:func:`cimba_tpu.core.fuse.make_fused_init`,
    docs/26_wave_fusion.md).  Fused waves ALWAYS materialize the
    horizon column — the refill splice and lane reclamation need it —
    which is bitwise-safe (``t_stop=t_end`` reproduces the static
    cond's decisions and no result reads the leaf)."""
    from cimba_tpu.core.fuse import make_fused_init

    init = make_fused_init(fused)
    if mesh is not None:
        init = partial(
            shard_map,
            mesh=mesh,
            in_specs=(
                P(REP_AXIS), P(REP_AXIS), P(REP_AXIS), P(REP_AXIS),
                P(REP_AXIS),
            ),
            out_specs=P(REP_AXIS),
            check_vma=False,
        )(init)
    return jax.jit(init)


def _fused_refill_program(fused, mesh: Optional[Mesh]):
    """The fused twin of :func:`_refill_program`: ``refill(sims, mask,
    reps, seeds, t_stops, sids, params) -> sims``
    (:func:`cimba_tpu.core.fuse.make_fused_refill`), jitted with the
    batched Sim DONATED like its solo twin.  One program serves every
    member of the fusion class, so a boundary splice admitting ANY
    member is a cached dispatch, never a compile
    (docs/26_wave_fusion.md)."""
    from cimba_tpu.core.fuse import make_fused_refill

    refill = make_fused_refill(fused)
    if mesh is not None:
        refill = partial(
            shard_map,
            mesh=mesh,
            in_specs=(
                P(REP_AXIS), P(REP_AXIS), P(REP_AXIS), P(REP_AXIS),
                P(REP_AXIS), P(REP_AXIS), P(REP_AXIS),
            ),
            out_specs=P(REP_AXIS),
            check_vma=False,
        )(refill)
    return jax.jit(refill, donate_argnums=(0,))


def _live_program(spec: ModelSpec, mesh: Optional[Mesh]):
    """One compiled per-lane liveness readback: ``live(sims) ->
    bool[L]`` (:func:`cimba_tpu.core.loop.make_lanes_live`) — NOT
    donated (it reads the wave the next chunk will consume).  The
    refill driver polls it at chunk boundaries to learn which lanes
    died this chunk; the serving layer's live lane-occupancy gauge
    rides the same program (docs/22_refill.md)."""
    from cimba_tpu.core.loop import make_lanes_live

    live = make_lanes_live(spec)
    if mesh is not None:
        live = partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(REP_AXIS),),
            out_specs=P(REP_AXIS),
            check_vma=False,
        )(live)
    return jax.jit(live)


def _tel_hooks(telemetry, kind: str, on_wave, on_chunk):
    """Generalize the ``on_wave``/``on_chunk`` progress hooks into
    telemetry ticks (docs/17_telemetry.md): with a
    :class:`cimba_tpu.obs.telemetry.Telemetry` plane attached, each
    wave/chunk boundary ticks its counter and refreshes the liveness
    heartbeat (the watchdog primitive an operator's harness reads), THEN calls
    the user hook.  ``telemetry=None`` returns the hooks untouched —
    the zero-overhead default (no wrapper closures, no allocations on
    the drive loop)."""
    if telemetry is None:
        return on_wave, on_chunk

    def wave_hook(n_waves, lanes_done, _u=on_wave):
        telemetry.tick(f"{kind}.wave")
        if _u is not None:
            _u(n_waves, lanes_done)

    def chunk_hook(n, _u=on_chunk):
        telemetry.tick(f"{kind}.chunk")
        if _u is not None:
            _u(n)

    return wave_hook, chunk_hook


def run_experiment_chunked(
    spec: ModelSpec,
    params: Any,
    n_replications: int,
    *,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    t_end: Optional[float] = None,
    chunk_steps: int = 1024,
    poll_every: int = 4,
    donate: bool = True,
    on_chunk=None,
    telemetry=None,
    checkpoint_path: Optional[str] = None,
    checkpoint_every: int = 0,
    resume: bool = False,
):
    """:func:`run_experiment` with chunked, donated dispatch: the batched
    Sim stays device-resident while the host re-dispatches one compiled
    chunk program (every lane advances at most ``chunk_steps`` events
    per dispatch) until all lanes finish.

    Trajectories are bitwise the monolithic run's — chunking only splits
    the while loop across dispatches — but no single device program
    outlives one chunk, so arbitrarily long runs clear the TPU runtime's
    ~3-minute program watchdog, and the ``any_live`` poll (every
    ``poll_every`` chunks, asynchronous) keeps the dispatch pipeline
    full.  See docs/12_streaming.md.

    ``checkpoint_path`` + ``checkpoint_every`` save the batched Sim at
    chunk boundaries (``runner.checkpoint.save_resumable``, tagged with
    spec identity, ``seed``, and a params digest); ``resume=True``
    restores from an existing checkpoint first — the resumed run is
    bit-identical to an uninterrupted one (the Sim is the complete
    state, RNG position included), and a resume under a different spec,
    seed, or params fails loudly on the fingerprint instead of silently
    continuing the old run.
    """
    import os as _os

    pb = _broadcast_params(params, n_replications)
    reps = jnp.arange(n_replications)
    if mesh is not None and n_replications % mesh.devices.size:
        raise ValueError(
            f"n_replications={n_replications} must divide evenly over "
            f"{mesh.devices.size} devices"
        )
    init_j = _init_program(spec, mesh)
    # static horizon, no per-lane t_stop leaf (t_stops=None): the
    # checkpointed pytree stays the historical one, and the chunk
    # program below keeps its static t_end cond
    seeds = _seed_column(seed, n_replications)

    n0 = 0
    sims = None
    ckpt_tag = None
    if checkpoint_path:
        from cimba_tpu.runner import checkpoint as _ckpt

        # the tag carries seed + horizon + params digest beyond spec
        # identity: a resume under different seed/t_end/params has
        # matching shapes and would otherwise silently continue the OLD
        # run's trajectories
        ckpt_tag = _ckpt.run_tag(spec, seed=seed, params=pb, t_end=t_end)
    if checkpoint_path and resume:
        if _os.path.exists(checkpoint_path):
            # restore validates against an ABSTRACT init (eval_shape):
            # materializing a full fresh batch just to serve as the
            # shape/dtype template would waste the init compute and
            # transiently hold TWO full batched Sims on exactly the
            # memory-bound runs checkpointing targets
            sims, n0 = _ckpt.restore_resumable(
                checkpoint_path,
                jax.eval_shape(init_j, reps, seeds, None, pb),
                tag=ckpt_tag,
            )
    if sims is None:
        sims = init_j(reps, seeds, None, pb)

    on_state = None
    if checkpoint_path and checkpoint_every:
        def on_state(s, n):
            _ckpt.save_resumable(
                checkpoint_path, s, tag=ckpt_tag, progress=n
            )

    _, on_chunk = _tel_hooks(telemetry, "chunked", None, on_chunk)
    chunk = _chunk_program(spec, t_end, chunk_steps, mesh, donate)
    sims = drive_chunks(
        chunk, sims, poll_every=poll_every, on_chunk=on_chunk,
        on_state=on_state, on_state_every=checkpoint_every, n0=n0,
    )
    return ExperimentResult(
        sims=sims,
        n_failed=jnp.sum((sims.err != 0).astype(jnp.int32)),
        total_events=jnp.sum(sims.n_events),
    )


def run_experiment_stream(
    spec: ModelSpec,
    params: Any,
    n_replications: int,
    *,
    wave_size: Optional[int] = None,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    t_end: Optional[float] = None,
    chunk_steps: Optional[int] = None,
    poll_every: int = 4,
    summary_path=default_summary_path,
    max_regrows: int = 0,
    on_wave=None,
    on_chunk=None,
    telemetry=None,
    program_cache: Optional[dict] = None,
    audit=None,
    schedule=None,
) -> StreamResult:
    """Pooled statistics for R replications with R beyond the
    per-dispatch lane budget: stream waves of ``wave_size`` lanes
    through ONE compiled chunk program (chunked, donated dispatch — see
    :func:`run_experiment_chunked`), folding each finished wave's pooled
    Pébay summary, metrics registry (when ``obs.metrics`` is enabled),
    failure count, and event total into on-device accumulators.  The
    batched sims of a wave are freed before the next wave initializes,
    so peak device memory is one wave regardless of R — pooled
    statistics for R in the millions without ever materializing all
    sims.

    Lane r of wave w is replication ``w*wave_size + r``: identical
    (seed, rep)-derived streams and bitwise-identical per-wave parameter
    rows (:func:`_slice_params`) make every replication's trajectory
    bitwise the monolithic run's; the summary fold is the associative
    Pébay merge, so the pooled moments match the monolithic pool up to
    float merge-order rounding (counts and event totals exactly).

    Composition: ``mesh`` shards each wave over devices (wave = local
    lanes x devices, the ``make_sharded_experiment`` topology);
    ``max_regrows > 0`` retries a wave under a doubled event cap when it
    hit ``ERR_EVENT_OVERFLOW`` (regrow at wave granularity — later waves
    keep the grown spec; healthy lanes reproduce bit-identically under
    any capacity).  A final partial wave re-specializes the same
    programs at the remainder shape (one extra compile).

    ``on_wave(n_waves, lanes_done)`` and ``on_chunk(n)`` are progress
    hooks (a caller's progress or watchdog heartbeat).
    ``telemetry`` generalizes them: a
    :class:`cimba_tpu.obs.telemetry.Telemetry` plane gets a tick
    (counter + liveness heartbeat) per wave and per chunk, and — with
    spans enabled — one "stream" span covering the call with a
    per-wave event trail (docs/17_telemetry.md).  All host-side: the
    compiled programs and the streamed results are bitwise identical
    with or without it.

    ``program_cache``: pass the SAME mapping to repeated calls to reuse
    the compiled init/chunk/fold programs across calls (a benchmark's
    warm-then-time protocol; a service shares one cache across every
    request).  Every setting a program bakes in — the spec's structural
    fingerprint, the dtype profile, the ``obs.metrics`` and
    ``obs.trace`` states, the backend (which picks the loop's carry
    layout), ``chunk_steps``, ``mesh``, and ``summary_path`` identity —
    is part of the cache key, so a mismatched call recompiles rather
    than replaying stale programs.  ``seed`` and
    ``t_end`` are NOT program constants on this path: they ride as
    per-lane data columns (bit-identical trajectories), so calls
    differing only in them — and structurally identical spec twins from
    ``dataclasses.replace`` — share compiled programs
    (docs/14_wave_packing.md); jitted programs additionally
    re-specialize per wave shape internally, so full waves always
    share one compile.  The default is
    a fresh :class:`cimba_tpu.serve.cache.ProgramCache` — a bounded LRU
    with hit/miss/eviction counters (``CIMBA_PROGRAM_CACHE_CAP``);
    plain dicts keep working for legacy callers but never evict.

    Cold starts: with ``CIMBA_PROGRAM_STORE`` set (or a cache whose
    ``store=`` names a :class:`~cimba_tpu.serve.store.ProgramStore`),
    a cache miss hydrates serialized executables from disk before
    compiling, and every jit on this path additionally rides jax's
    persistent compilation cache — a fresh process reaches its first
    result without re-paying XLA compile (docs/15_program_store.md).

    Sweeping many scenarios?  :func:`cimba_tpu.sweep.run_sweep` drives
    this same chunked machinery per grid cell — per-cell pooled
    summaries (bitwise these calls'), adaptive replication counts, and
    shared waves across cells (docs/16_sweeps.md).

    ``audit`` (docs/18_audit.md): ``None`` defers to the
    ``CIMBA_AUDIT`` env knob (unset = off — the chunk program is then
    jaxpr-identical to the unaudited one, pinned); ``True`` / a
    directory path / an :class:`cimba_tpu.obs.audit.Audit` enable the
    determinism audit — the chunk program additionally folds each
    packed carry class into a per-wave digest vector at every chunk
    boundary (the digest trail), and the returned ``StreamResult``
    carries a content-addressed **run card** in ``.audit`` (spec
    fingerprint, seed schedule, program key, env, geometry, trail,
    result digest), written to the Audit's ``out_dir`` when set.  Two
    clean same-seed runs produce identical trails and the same card
    digest; ``tools/audit_diff.py`` localizes any divergence to its
    first (wave, chunk, carry-class).

    ``schedule`` / tuned resolution (docs/21_autotune.md): the
    dispatch knobs left unset here — ``chunk_steps`` (default 1024),
    ``wave_size``, and the trace-time table-scan layout — resolve
    through :func:`cimba_tpu.tune.registry.resolve_entry` at
    program-build time: an explicit
    ``schedule=``:class:`~cimba_tpu.tune.space.Schedule` binds exactly
    that schedule (the search harness's arm dispatch); otherwise, with
    ``CIMBA_TUNE`` on (the default) and a program store in reach
    (``program_cache.store`` / ``CIMBA_PROGRAM_STORE``), a searched
    winner for this (spec, backend, workload bucket) fills the unset
    knobs.  Explicit kwargs ALWAYS win, ``CIMBA_TUNE=0`` restores the
    hand-frozen defaults bitwise, and the resolution source
    (tuned/default/override) is recorded in the run card's
    ``schedule`` block when auditing.
    """
    from cimba_tpu.obs.telemetry import span
    from cimba_tpu.serve import cache as _pcache_r
    from cimba_tpu.tune import registry as _tune_reg

    store = None
    if isinstance(program_cache, _pcache_r.ProgramCache):
        # respect an explicitly opted-out cache (store=False)
        store = program_cache._store
    with span("runner.stream", spec=spec.name,
              R=int(n_replications)) as sp:
        rs = _tune_reg.resolve_entry(
            spec, n_replications, schedule=schedule,
            chunk_steps=chunk_steps, wave_size=wave_size, store=store,
        )
        with rs.scope():
            result = _stream_impl(
                spec, params, n_replications,
                wave_size=rs.wave_size, seed=seed, mesh=mesh, t_end=t_end,
                chunk_steps=rs.chunk_steps,
                poll_every=poll_every, summary_path=summary_path,
                max_regrows=max_regrows, on_wave=on_wave,
                on_chunk=on_chunk, telemetry=telemetry,
                program_cache=program_cache, audit=audit,
                sched_block=rs.block(),
            )
        if result.boundary is not None:
            # read back inside the span, which then ends with the device
            sp.attrs.update(
                {k: int(v) for k, v in result.boundary.items()}
            )
        return result


def _stream_impl(
    spec: ModelSpec,
    params: Any,
    n_replications: int,
    *,
    wave_size: Optional[int] = None,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    t_end: Optional[float] = None,
    chunk_steps: int = 1024,
    poll_every: int = 4,
    summary_path=default_summary_path,
    max_regrows: int = 0,
    on_wave=None,
    on_chunk=None,
    telemetry=None,
    program_cache: Optional[dict] = None,
    audit=None,
    sched_block: Optional[dict] = None,
) -> StreamResult:
    """The stream runner's body (see :func:`run_experiment_stream`,
    which resolves the schedule and enters its trace-time scope before
    delegating here — program keys and traces below must see the
    bound knobs)."""
    import dataclasses

    import numpy as np

    from cimba_tpu.core import loop as _cl
    from cimba_tpu.obs import audit as _obs_audit
    from cimba_tpu.obs import metrics as _metrics
    from cimba_tpu.obs.telemetry import span
    from cimba_tpu.serve import cache as _pcache

    R = int(n_replications)
    if R <= 0:
        raise ValueError(f"n_replications must be positive, got {R}")
    if wave_size is None or wave_size >= R:
        wave_size = R
    if wave_size <= 0:
        raise ValueError(f"wave_size must be positive, got {wave_size}")
    if mesh is not None:
        n_dev = mesh.devices.size
        if wave_size % n_dev or R % n_dev:
            raise ValueError(
                f"wave_size={wave_size} and n_replications={R} must "
                f"divide evenly over {n_dev} devices"
            )

    aud = _obs_audit.resolve(audit)
    use_audit = aud is not None
    spec0 = spec  # regrow replaces spec; the card cites the original

    with_metrics = _metrics.enabled()
    acc = _pcache.stream_acc(spec, with_metrics)

    # the program cache, its keys, the fold program, and the preflight
    # all live in serve/cache.py now — the serving layer's request
    # compatibility key IS the program key, so both stay one definition
    programs = (
        program_cache if program_cache is not None else _pcache.ProgramCache()
    )
    fold_j = _pcache.get_fold(programs, with_metrics, summary_path)

    def get_programs(spec):
        # one (init, chunk) program pair per (spec STRUCTURE, settings)
        # point — seed and t_end are per-lane columns now, NOT program
        # constants, so calls differing only in them share compiled
        # programs (the Tier-A packing contract, docs/14_wave_packing);
        # jit re-specializes per wave shape internally (full waves
        # share one compile).  Regrow's dataclasses.replace doubles
        # event_cap, which changes the structural fingerprint, so
        # grown capacities get their own programs as before.
        return _pcache.get_programs(
            programs, spec, mesh=mesh,
            chunk_steps=chunk_steps, with_metrics=with_metrics,
            audit=use_audit,
        )

    init_probe, _ = get_programs(spec)
    _pcache.preflight_summary_path(
        programs, spec, init_probe, summary_path, params,
        R, min(wave_size, R), with_metrics,
    )

    on_wave, on_chunk = _tel_hooks(telemetry, "stream", on_wave, on_chunk)
    rec = telemetry.spans if telemetry is not None else None
    # a boundary chunk's counters, added up on the device chunk by chunk
    bctr = None
    on_counters = None
    if spec.boundary_pcs:
        bctr = [jnp.zeros((len(_cl.BOUNDARY_COUNTERS),), jnp.int64)]

        def on_counters(c, vec):
            bctr[0] = bctr[0] + vec

    trace = None
    if rec is not None:
        trace = rec.new_trace()
        rec.start(
            trace, "stream", spec=spec.name, R=R, wave_size=wave_size,
        )

    grow_errs = (_cl.ERR_EVENT_OVERFLOW,)
    n_waves = 0
    n_regrows = 0
    lo = 0
    try:
        while lo < R:
            n = min(wave_size, R - lo)
            reps = jnp.arange(lo, lo + n)
            pw = _slice_params(params, R, lo, n)
            seeds = _seed_column(seed, n)
            # no horizon -> NO t_stop leaf: the chunk cond then skips
            # the per-event next-event-min + compare entirely (the
            # historical t_end=None jaxpr — per-event cost matters on
            # the headline path).  jit re-specializes per pytree
            # structure under the same program key, so both variants
            # share the cache entry.
            t_stops = None if t_end is None else _horizon_column(t_end, n)
            on_digest = None
            if use_audit:
                def on_digest(c, d, _w=n_waves, _aud=aud):
                    _aud.on_chunk(_w, c, d)
            while True:
                with span("runner.init", wave=n_waves, lanes=n):
                    init_j, chunk_j = get_programs(spec)
                    sims = init_j(reps, seeds, t_stops, pw)
                with span("runner.run", wave=n_waves):
                    sims = drive_chunks(
                        chunk_j, sims, poll_every=poll_every,
                        on_chunk=on_chunk, on_digest=on_digest,
                        on_counters=on_counters,
                    )
                if n_regrows >= max_regrows:
                    break
                with span("runner.check", wave=n_waves):
                    err = np.asarray(sims.err)
                if not np.isin(err, grow_errs).any():
                    break
                # wave-granular regrow: double the event cap and re-run
                # THIS wave (healthy lanes reproduce bit-identically —
                # streams are counter-derived); later waves keep the
                # grown spec.  Drop the failed wave's sims before the
                # re-init allocates — holding the name across init_j
                # would peak at TWO waves of HBM
                spec = dataclasses.replace(
                    spec, event_cap=2 * spec.event_cap
                )
                n_regrows += 1
                sims = None
            with span("runner.fold", wave=n_waves):
                acc = fold_j(acc, sims)
            # release the wave's batched sims before the next wave's
            # init allocates: the one-wave peak-memory contract (fold_j
            # has the buffers; the host must not keep a second live
            # reference)
            sims = None
            n_waves += 1
            lo += n
            if rec is not None:
                rec.event(trace, "wave", n=n_waves, lanes_done=lo)
            if on_wave is not None:
                on_wave(n_waves, lo)
    except BaseException:
        if rec is not None:
            rec.end_trace(trace, "error")
        raise
    if rec is not None:
        rec.end_trace(trace, "completed", n_waves=n_waves)

    result = StreamResult(
        summary=acc[0],
        n_failed=acc[1],
        total_events=acc[2],
        n_waves=n_waves,
        n_regrows=n_regrows,
        metrics=acc[3] if with_metrics else None,
        boundary=(
            None if bctr is None
            else dict(zip(_cl.BOUNDARY_COUNTERS, bctr[0]))
        ),
    )
    if use_audit:
        from cimba_tpu import config as _config
        from cimba_tpu.serve import store as _pstore

        try:
            pkey = _pstore.store_key(
                spec0, with_metrics, mesh=mesh, chunk_steps=chunk_steps,
            )
        except Exception:
            pkey = None  # unstable spec: the card's spec block says why
        card = aud.finalize(
            "stream",
            spec=spec0,
            seed_schedule={"seed": int(seed)},
            geometry={
                "R": R,
                "wave_size": wave_size,
                "chunk_steps": chunk_steps,
                "poll_every": poll_every,
                "t_end": t_end,
                "profile": _config.active_profile(),
                "with_metrics": with_metrics,
                "mesh": _pstore._mesh_descriptor(mesh),
                "n_waves": n_waves,
                "n_regrows": n_regrows,
            },
            program_key=pkey,
            result_digest=_obs_audit.stream_result_digest(result),
            schedule=sched_block,
            telemetry=(
                telemetry.snapshot() if telemetry is not None else None
            ),
        )
        result = result._replace(audit=card)
    return result


def pooled_summary(batched: sm.Summary) -> sm.Summary:
    """Merge per-replication summaries into one (host-side / jit-able)."""
    return jax.jit(sm.merge_tree)(batched)


def make_sharded_experiment(
    spec: ModelSpec, n_replications: int, mesh: Mesh, *,
    summary_path=default_summary_path,
    t_end: Optional[float] = None,
):
    """Build the fully-fused multi-chip experiment step: run all local
    replications AND reduce statistics over the mesh inside one jitted
    program (per-shard Pébay partials ride an all_gather over ICI, the
    scalar counters a psum).  Returns ``fn(params, seed=0) ->
    (pooled Summary, n_failed, total_events)`` — everything replicated.

    When the metrics registry is enabled (``obs.metrics.enable()``) at
    build time, the return gains a fourth element: the registry pooled
    over lanes AND the mesh (psum for counters/histograms, pmax for
    high-water gauges — the same ICI layer the summaries ride).  The
    flag binds here, like logger flags bind at trace time: don't flip it
    between build and run.
    """
    from cimba_tpu.obs import metrics as _metrics

    run = make_lanes_run(spec, t_end=t_end)
    reps = jnp.arange(n_replications)
    with_metrics = _metrics.enabled()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(REP_AXIS), P(REP_AXIS), P()),
        out_specs=(P(), P(), P()) + ((P(),) if with_metrics else ()),
        check_vma=False,
    )
    def sharded(reps_local, p_local, seed):
        sims = run(jax.vmap(
            lambda r, q: init_sim(spec, seed, r, q)
        )(reps_local, p_local))[1]
        if (sims.metrics is None) == with_metrics:
            # the flag bound at build time; init_sim re-reads it at trace
            # time — fail with the subsystem's loud, named error instead
            # of an opaque NoneType crash deep in the shard_map trace
            raise RuntimeError(
                "make_sharded_experiment: obs.metrics was "
                f"{'enabled' if with_metrics else 'disabled'} when this "
                "experiment was built but flipped before the first call "
                "— the flag binds at build time (like logger flags at "
                "trace time); rebuild the experiment after changing it"
            )
        local = sm.merge_tree(summary_path(sims))
        # gather per-shard partial summaries over ICI, merge identically
        # everywhere (merge is not a plain sum, so psum cannot do it)
        gathered = jax.tree.map(
            lambda x: jax.lax.all_gather(x, REP_AXIS), local
        )
        pooled = sm.merge_tree(gathered)
        n_failed = jax.lax.psum(
            jnp.sum((sims.err != 0).astype(jnp.int32)), REP_AXIS
        )
        events = jax.lax.psum(jnp.sum(sims.n_events), REP_AXIS)
        if with_metrics:
            pooled_metrics = _metrics.pool_across(
                _metrics.pool(sims.metrics), REP_AXIS
            )
            return pooled, n_failed, events, pooled_metrics
        return pooled, n_failed, events

    def experiment(params, seed=0):
        pb = _broadcast_params(params, n_replications)
        return sharded(reps, pb, jnp.asarray(seed, jnp.uint64))

    return jax.jit(experiment)