"""The many-scenario sweep engine: cells x replications fanned across
waves of the chunked stream program, folded per cell.

``run_experiment_stream`` (PR 3) pools ONE statistic for one scenario;
a sweep wants one statistic PER CELL of a scenario grid.  This engine
drives the same compiled machinery — the shared ``(init, chunk)``
program pair from :mod:`cimba_tpu.serve.cache`, per-lane seed/horizon
columns (PR 5), donated chunked dispatch — but lays each wave out as a
sequence of per-cell SLOTS and folds it **slot-keyed**: each slot's
contiguous lanes slice off the wave (data movement only) and fold
through the ONE shared fold program into that cell's accumulator, so
the grid converges as per-cell pooled summaries (stacked to a batched
``Summary[C]`` for the stopping rule and the result) instead of the
stream runner's single grid-pooled scalar.

Why per-slot applications of the shared program rather than one fused
all-cells fold: the fixed-R contract below is BITWISE, and XLA only
preserves float semantics within one compiled program — a fused
segment-reduction program computing the same merges measurably drifts
from the direct path by 1 ulp in the high moments at model scale
(fusion/FMA contraction differ across program boundaries).  Program
identity with the direct call's fold is the whole proof.

Three dispatch modes, one schedule:

* **fixed-R** (``stop=None``): every cell runs ``reps_per_cell``
  replications.  Cell ``c``'s lanes are
  ``(seed=round_seed(seed, c, 0), rep=0..R)`` partitioned into
  ``cell_wave``-sized slots — exactly the wave partition of a direct
  ``run_experiment_stream(spec, row_c, R, wave_size=cell_wave,
  seed=round_seed(seed, c, 0))`` call, and the per-slot fold performs
  the same merge sequence from the same empty accumulator, so the
  engine's per-cell results are BITWISE the direct calls' (the tier-1
  pin, tests/test_sweep.py) while many cells share each physical wave.
* **adaptive-R** (``stop=HalfwidthTarget(...)``): rounds of
  ``reps_per_cell`` per live cell; after each round, cells whose CI
  halfwidth beats the target stop receiving lanes and the freed lanes
  go to the cells still running (``redistribute``).  The
  (cell, round) -> seed schedule is deterministic and
  packing-independent, so adaptive runs are reproducible bit-for-bit
  (docs/16_sweeps.md).
* **serve-backed** (``service=``): each (cell, round) submits as a
  :class:`~cimba_tpu.serve.service.Request` carrying its own per-lane
  seed and horizon, so sweep traffic packs into shared heterogeneous
  waves alongside live requests (PR 5 compatibility classes — same
  spec + scalar param rows means ONE class, no new program keys) and
  the per-cell results are bitwise the direct mode's fixed-R results.

Waves that cannot fill (``pad_waves=True``, or a mesh's device
quantum) pad with the bitwise-inert ``t_stop=-inf`` lanes of
docs/14_wave_packing.md; pad lanes sit past the live segment and never
join a fold.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import numpy as np

from cimba_tpu.sweep.adaptive import HalfwidthTarget, round_seed
from cimba_tpu.sweep.grid import SweepGrid

__all__ = ["SweepResult", "run_sweep", "run_fused_sweeps"]


@dataclass
class SweepResult:
    """Per-cell pooled statistics of one sweep run.

    ``summaries`` is a batched :class:`~cimba_tpu.stats.summary.Summary`
    with leading axis ``n_cells`` (device); the count arrays are host
    numpy.  ``stop_round[c]`` is the 0-based round after which cell
    ``c`` met the stopping target (-1: never — fixed-R runs, or cells
    still unconverged at ``max_rounds``); ``met`` is None for fixed-R
    runs.  ``occupancy`` carries the wave/lane accounting (live vs
    padded lanes — the obs-style efficiency counters; serve-backed runs
    report the service's counter deltas instead)."""

    grid: SweepGrid
    summaries: Any
    n_reps: np.ndarray
    n_failed: np.ndarray
    total_events: np.ndarray
    stop_round: np.ndarray
    halfwidth: np.ndarray
    met: Optional[np.ndarray]
    n_rounds: int
    seed: int
    confidence: float
    wall_s: float
    occupancy: dict = field(default_factory=dict)
    metrics: Any = None
    #: run card (docs/18_audit.md) when the sweep ran with ``audit=``:
    #: per-cell result digests + the deterministic seed schedule
    audit: Any = None

    @property
    def n_cells(self) -> int:
        return self.grid.n_cells

    def cell_summary(self, i: int):
        """Cell ``i``'s pooled Summary (scalar leaves, device)."""
        import jax

        return jax.tree.map(lambda x: x[i], self.summaries)

    def rows(self) -> list:
        """One dict per cell: axis values + pooled statistics — the
        dataset export (feed to csv/pandas/plotting)."""
        from cimba_tpu.stats import summary as sm

        s = self.summaries
        cols = {
            "n": s.n, "mean": sm.mean(s), "stddev": sm.stddev(s),
            "min": s.mn, "max": s.mx,
        }
        cols = {k: np.asarray(v, np.float64) for k, v in cols.items()}
        axes = set(self.grid.axes)

        def key(k):
            # an axis named like a statistic keeps its name; the
            # statistic column gets a stat_ prefix instead of silently
            # overwriting the cell coordinate
            return f"stat_{k}" if k in axes else k

        out = []
        for i, cell in enumerate(self.grid.cells()):
            row = dict(cell)
            row[key("reps")] = int(self.n_reps[i])
            row[key("n")] = float(cols["n"][i])
            row[key("mean")] = float(cols["mean"][i])
            row[key("stddev")] = float(cols["stddev"][i])
            row[key("halfwidth")] = float(self.halfwidth[i])
            row[key("min")] = float(cols["min"][i])
            row[key("max")] = float(cols["max"][i])
            row[key("n_failed")] = int(self.n_failed[i])
            row[key("total_events")] = int(self.total_events[i])
            row[key("stop_round")] = int(self.stop_round[i])
            if self.met is not None:
                row[key("met")] = bool(self.met[i])
            out.append(row)
        return out

    def to_csv(self, path) -> None:
        """Write :meth:`rows` as CSV (``path``: filename, Path, or
        file-like)."""
        import csv
        import os

        rows = self.rows()
        own = isinstance(path, (str, os.PathLike))
        f = open(path, "w", newline="") if own else path
        try:
            w = csv.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
        finally:
            if own:
                f.close()


def _stack_summaries(accs):
    """The batched per-cell ``Summary[C]`` view of the per-cell
    accumulators — what the stopping rule vectorizes over and what
    :class:`SweepResult` carries.  Pure data movement (stack), so the
    per-cell scalars' bits pass through untouched."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda *xs: jnp.stack(xs), *[a[0] for a in accs])


def _serve_merge(acc, summary, n_failed, total_events, metrics=None):
    """Merge one served (cell, round) StreamResult into that cell's
    accumulator — ``merge(empty, s)`` is exact, so a fixed-R serve run
    delivers each cell BITWISE as the service returned it (which is
    itself bitwise the direct stream call, the PR 4 contract)."""
    from cimba_tpu.obs import metrics as _metrics
    from cimba_tpu.stats import summary as sm

    out = (
        sm.merge(acc[0], summary),
        acc[1] + n_failed,
        acc[2] + total_events,
    )
    if metrics is not None:
        out = out + (_metrics.merge(acc[3], metrics),)
    return out


def _wave_shape(total: int, unit: int, pad_waves: bool, max_wave: int):
    """Lanes one physical wave dispatches at: always a multiple of the
    mesh device count; with ``pad_waves`` additionally quantized to the
    next power-of-two multiple (capped at ``max_wave``) so mixed rounds
    cycle a handful of compiled wave shapes — serve's pad-and-mask
    policy (docs/14_wave_packing.md)."""
    if total <= 0:
        return total
    up = total if total % unit == 0 else total + (unit - total % unit)
    if not pad_waves:
        return up
    q = unit
    while q < total:
        q *= 2
    q = min(q, max_wave)
    if q < up or q % unit:
        return up
    return q


def run_sweep(
    spec,
    grid: SweepGrid,
    *,
    reps_per_cell: int,
    stop: Optional[HalfwidthTarget] = None,
    max_rounds: int = 32,
    seed: int = 0,
    cell_wave: Optional[int] = None,
    max_wave: int = 4096,
    t_end: Optional[float] = None,
    chunk_steps: Optional[int] = None,
    poll_every: int = 4,
    mesh=None,
    summary_path=None,
    pad_waves: bool = False,
    redistribute: bool = True,
    program_cache=None,
    service=None,
    serve_timeout: float = 600.0,
    on_round: Optional[Callable] = None,
    on_chunk: Optional[Callable] = None,
    telemetry=None,
    audit=None,
) -> SweepResult:
    """Run a scenario grid: ``reps_per_cell`` replications per cell
    (per ROUND when ``stop`` is given), folded into per-cell pooled
    summaries.

    Fixed-R mode (``stop=None``): one round; cell ``c``'s result is
    bitwise the direct ``run_experiment_stream`` call at
    ``seed=round_seed(seed, c, 0)``, ``wave_size=cell_wave`` (the
    engine merely packs many cells' slots into shared physical waves
    of up to ``max_wave`` lanes).

    Adaptive mode (``stop=HalfwidthTarget(...)``): up to ``max_rounds``
    rounds; after each round, cells whose CI halfwidth beats the
    target stop receiving lanes.  ``redistribute=True`` (default)
    grows the per-round replication count as cells drop out —
    ``reps_per_cell * n_cells / n_live``, capped at
    ``max(reps_per_cell, max_wave)`` lanes per cell per round — so the
    hardware stays busy while the hard cells converge.  The
    (cell, round) seed schedule is deterministic and independent of
    the stopping pattern: adaptive runs reproduce bit-for-bit.

    ``service=`` dispatches every (cell, round) as a serve Request
    instead (per-lane seeds/horizons — sweeps pack into shared
    heterogeneous waves with live traffic; ``mesh``/``program_cache``
    then belong to the service).  ``pad_waves`` quantizes direct-mode
    wave shapes with dead ``t_stop=-inf`` lanes (bitwise-inert; a mesh
    always pads to its device-count multiple).  ``on_round(round,
    n_live, reps_total)`` is the progress hook (a caller's watchdog
    heartbeat ticks there).  ``telemetry`` attaches the host-side
    telemetry plane (docs/17_telemetry.md): per-round/per-chunk ticks
    (counter + liveness heartbeat) and — with spans enabled — one
    "sweep" trace whose per-round spans carry live-cell/replication
    counts; serve-backed sweeps additionally get the service's own
    request spans per (cell, round).  Host-side only: results are
    bitwise identical with or without it.

    ``chunk_steps=None`` (the default) resolves
    through the tuned-schedule registry for the per-cell workload
    bucket at program-build time (docs/21_autotune.md) — explicit
    kwargs always win, ``CIMBA_TUNE=0`` restores the hand-frozen
    defaults bitwise, and the resolved block lands in the sweep run
    card's ``schedule`` section; serve-backed sweeps defer resolution
    to the service's own submit path.

    ``audit`` (docs/18_audit.md): ``None`` defers to ``CIMBA_AUDIT``;
    when enabled, the result carries a content-addressed run card in
    ``.audit`` with the full per-cell seed schedule (every
    ``round_seed(seed, cell, round)`` actually dispatched) and a
    bitwise result digest per cell — the citable form of the fixed-R
    "bitwise the direct calls" contract.  Host-side only (the
    dispatched programs are unchanged); per-chunk digest TRAILS are
    the stream runner's — sweep waves interleave many cells, so the
    sweep card pins cell results, not chunk boundaries."""
    import jax
    import jax.numpy as jnp

    from cimba_tpu.obs import audit as _obs_audit
    from cimba_tpu.obs import metrics as _metrics
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.serve import cache as _pcache

    C = grid.n_cells
    R0 = int(reps_per_cell)
    if R0 <= 0:
        raise ValueError(f"reps_per_cell must be positive, got {R0}")
    # tuned-schedule resolution at program-build time
    # (docs/21_autotune.md): the ARGUMENT knobs left unset resolve
    # against the program store for the per-cell workload bucket (a
    # cell runs as an R0-sized stream).  Explicit kwargs always win;
    # CIMBA_TUNE=0 restores the hand-frozen defaults bitwise.  Serve-
    # backed sweeps leave resolution to the service's own submit path.
    from cimba_tpu.tune import registry as _tune_reg

    if service is None:
        _store = (
            program_cache._store
            if hasattr(program_cache, "_store") else None
        )
        rs = _tune_reg.resolve_entry(
            spec, R0, chunk_steps=chunk_steps, store=_store,
        )
        chunk_steps = rs.chunk_steps
        sched_block = rs.block()
    else:
        # serve-backed: chunk_steps=None flows into each Request and
        # the service resolves it at submit (one resolution authority)
        sched_block = None
    if stop is not None and max_rounds <= 0:
        raise ValueError(f"max_rounds must be positive, got {max_rounds}")
    cell_wave = R0 if cell_wave is None else int(cell_wave)
    if cell_wave <= 0:
        raise ValueError(f"cell_wave must be positive, got {cell_wave}")
    if cell_wave > max_wave:
        raise ValueError(
            f"cell_wave={cell_wave} exceeds max_wave={max_wave} — a "
            "slot could never fit one physical wave"
        )
    if service is not None and (mesh is not None or program_cache is not None):
        raise ValueError(
            "serve-backed sweeps dispatch through the service's own "
            "mesh and program cache — don't pass mesh=/program_cache="
        )
    unit = 1 if mesh is None else int(mesh.devices.size)
    if unit > 1 and (cell_wave % unit or max_wave % unit):
        raise ValueError(
            f"cell_wave={cell_wave} and max_wave={max_wave} must "
            f"divide evenly over {unit} devices"
        )

    rows = grid.cell_rows()
    if summary_path is None:
        summary_path = ex.default_summary_path
    with_metrics = _metrics.enabled()

    _, on_chunk = ex._tel_hooks(telemetry, "sweep", None, on_chunk)
    rec = telemetry.spans if telemetry is not None else None
    trace = root = None
    if rec is not None:
        trace = rec.new_trace()
        root = rec.start(
            trace, "sweep", grid=grid.name, n_cells=C,
            adaptive=stop is not None, serve_backed=service is not None,
        )

    t0 = time.perf_counter()
    occ = {
        "waves": 0, "lanes_live": 0, "lanes_padded": 0,
        "slots_by_cell": np.zeros(C, np.int64),
    }
    serve_stats0 = service.stats() if service is not None else None

    if service is None:
        programs = (
            program_cache if program_cache is not None
            else _pcache.ProgramCache()
        )
        init_j, chunk_j = _pcache.get_programs(
            programs, spec, mesh=mesh,
            chunk_steps=chunk_steps, with_metrics=with_metrics,
        )
        _pcache.preflight_summary_path(
            programs, spec, init_j, summary_path, rows[0],
            R0, min(cell_wave, R0), with_metrics,
        )
        # THE shared fold program — the same compiled object a direct
        # run_experiment_stream call folds through.  Program identity
        # is what makes per-cell results bitwise the direct calls':
        # XLA preserves float semantics within one compiled program,
        # not across two structurally different ones (a fused
        # all-cells-in-one-program fold measurably drifts by 1 ulp in
        # the high moments at model scale)
        fold_j = _pcache.get_fold(programs, with_metrics, summary_path)
    else:
        programs = service.cache

    # per-cell accumulators, every one starting from the same zeros a
    # direct stream call starts from (immutable — sharing is safe)
    acc0 = _pcache.stream_acc(spec, with_metrics)
    accs = [acc0] * C

    def dispatch_direct(jobs):
        # whole-slot partition per cell (the direct call's wave
        # partition), then greedy physical packing up to max_wave
        slots = []
        for ci, sd, reps in jobs:
            lo = 0
            while lo < reps:
                n = min(cell_wave, reps - lo)
                slots.append((ci, sd, lo, n))
                lo += n
        waves, cur, lanes = [], [], 0
        for s in slots:
            if cur and lanes + s[3] > max_wave:
                waves.append(cur)
                cur, lanes = [], 0
            cur.append(s)
            lanes += s[3]
        if cur:
            waves.append(cur)
        from cimba_tpu.core.loop import drive_chunks

        for wslots in waves:
            sizes = tuple(n for _, _, _, n in wslots)
            live = sum(sizes)
            pad = _wave_shape(live, unit, pad_waves, max_wave) - live
            reps_c = [
                jnp.arange(lo, lo + n) for _, _, lo, n in wslots
            ]
            seeds_c = [
                ex._seed_column(sd, n) for _, sd, _, n in wslots
            ]
            if t_end is None and pad == 0:
                # no horizon and no pads: omit the t_stop leaf like the
                # direct stream path (the cheap chunk cond)
                ts_c = None
            else:
                ts_c = [
                    ex._horizon_column(t_end, n)
                    for _, _, _, n in wslots
                ]
            pws_c = [
                jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        jnp.asarray(x), (n,) + jnp.shape(x)
                    ),
                    rows[ci],
                )
                for ci, _, _, n in wslots
            ]
            if pad:
                # dead masked lanes (t_stop=-inf): never dispatch an
                # event, sliced off before every fold; params are the
                # lead cell's row so user_init sees valid values
                reps_c.append(jnp.zeros((pad,), reps_c[0].dtype))
                seeds_c.append(ex._seed_column(0, pad))
                ts_c.append(jnp.full((pad,), -jnp.inf, ts_c[0].dtype))
                pws_c.append(jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        jnp.asarray(x), (pad,) + jnp.shape(x)
                    ),
                    rows[wslots[0][0]],
                ))
            if len(reps_c) == 1:
                reps_cat, seed_cat, pw_cat = (
                    reps_c[0], seeds_c[0], pws_c[0]
                )
                ts_cat = None if ts_c is None else ts_c[0]
            else:
                reps_cat = jnp.concatenate(reps_c)
                seed_cat = jnp.concatenate(seeds_c)
                ts_cat = None if ts_c is None else jnp.concatenate(ts_c)
                pw_cat = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *pws_c
                )
            sims = init_j(reps_cat, seed_cat, ts_cat, pw_cat)
            sims = drive_chunks(
                chunk_j, sims, poll_every=poll_every, on_chunk=on_chunk
            )
            # slot-keyed fold: slice each cell's contiguous slot off
            # the wave (data movement only) and fold it through the ONE
            # shared fold program, in (cell, lo) order — the exact
            # merge sequence of that cell's direct stream call.  Pad
            # lanes sit past the last slot's offset and never fold.
            off = 0
            for ci, _, _, n in wslots:
                sl = jax.tree.map(
                    lambda x, off=off, n=n: x[off : off + n], sims
                )
                accs[ci] = fold_j(accs[ci], sl)
                off += n
            sims = None  # one-wave peak memory, like the stream runner
            occ["waves"] += 1
            occ["lanes_live"] += live
            occ["lanes_padded"] += pad
            for ci, _, _, _ in wslots:
                occ["slots_by_cell"][ci] += 1

    def dispatch_serve(jobs, round_):
        from cimba_tpu.serve.service import Request

        handles = []
        for ci, sd, reps in jobs:
            handles.append((ci, service.submit(Request(
                spec, rows[ci], reps, seed=sd, t_end=t_end,
                chunk_steps=chunk_steps,
                wave_size=min(cell_wave, reps),
                summary_path=summary_path,
                label=f"{grid.name}:{grid.cell_label(ci)}:r{round_}",
            ))))
        merge_j = _pcache.cached(
            programs, ("sweep_serve_merge",),
            lambda: jax.jit(_serve_merge),
        )
        for ci, h in handles:
            res = h.result(serve_timeout)
            accs[ci] = merge_j(
                accs[ci], res.summary, res.n_failed, res.total_events,
                res.metrics if with_metrics else None,
            )

    aud = _obs_audit.resolve(audit)
    seed_log: list = [[] for _ in range(C)] if aud is not None else []

    live = np.ones(C, bool)
    n_reps = np.zeros(C, np.int64)
    stop_round = np.full(C, -1, np.int32)
    n_rounds = 0
    total_rounds = 1 if stop is None else int(max_rounds)
    rep_cap = max(R0, max_wave)
    try:
        while n_rounds < total_rounds and live.any():
            live_cells = np.flatnonzero(live)
            if stop is not None and redistribute:
                reps_r = min(max(R0, R0 * C // len(live_cells)), rep_cap)
            else:
                reps_r = R0
            jobs = [
                (int(c), round_seed(seed, int(c), n_rounds), reps_r)
                for c in live_cells
            ]
            if aud is not None:
                for c, sd, _ in jobs:
                    seed_log[c].append(int(sd))
            span_round = None
            if rec is not None:
                span_round = rec.start(
                    trace, "round", parent=root, round=n_rounds,
                    n_live=len(live_cells), reps_per_cell=reps_r,
                )
            if service is None:
                dispatch_direct(jobs)
            else:
                dispatch_serve(jobs, n_rounds)
            for c, _, n in jobs:
                n_reps[c] += n
            n_rounds += 1
            if stop is not None:
                met_now = stop.met(_stack_summaries(accs), n_reps)
                newly = live & met_now
                stop_round[np.flatnonzero(newly)] = n_rounds - 1
                live &= ~met_now
            else:
                live[:] = False
            if span_round is not None:
                rec.end(span_round, outcome="ok",
                        still_live=int(live.sum()))
            if telemetry is not None:
                telemetry.tick("sweep.round")
            if on_round is not None:
                on_round(n_rounds, int(live.sum()), int(n_reps.sum()))
    except BaseException:
        if rec is not None:
            rec.end_trace(trace, "error")
        raise
    if rec is not None:
        rec.end_trace(trace, "completed", rounds=n_rounds)

    confidence = 0.95 if stop is None else stop.confidence
    from cimba_tpu.sweep.adaptive import _halfwidths_jit

    summaries = _stack_summaries(accs)
    hw = np.asarray(_halfwidths_jit(confidence)(summaries), np.float64)
    met = None if stop is None else stop.met(summaries, n_reps)
    metrics = None
    if with_metrics:
        mmerge_j = _pcache.cached(
            programs, ("sweep_metrics_merge",),
            lambda: jax.jit(_metrics.merge),
        )
        metrics = accs[0][3]
        for a in accs[1:]:
            metrics = mmerge_j(metrics, a[3])
    occ["slots_by_cell"] = occ["slots_by_cell"].tolist()
    lanes = occ["lanes_live"] + occ["lanes_padded"]
    occ["padding_waste_frac"] = (
        occ["lanes_padded"] / lanes if lanes else 0.0
    )
    if serve_stats0 is not None:
        s1 = service.stats()
        occ["serve"] = {
            k: s1[k] - serve_stats0[k]
            for k in ("batches", "waves", "lanes_dispatched",
                      "lanes_padded")
        }
    audit_card = None
    if aud is not None:
        from cimba_tpu import config as _config

        cells_blk = [
            {
                "cell": grid.cell_label(c),
                "seeds": seed_log[c],
                "reps": int(n_reps[c]),
                "stop_round": int(stop_round[c]),
                "result_digest": _obs_audit.result_digest(accs[c]),
            }
            for c in range(C)
        ]
        audit_card = aud.finalize(
            "sweep",
            spec=spec,
            seed_schedule={
                "seed": int(seed),
                "rule": "round_seed(seed, cell, round)",
            },
            geometry={
                "grid": grid.name,
                "n_cells": C,
                "reps_per_cell": R0,
                "cell_wave": cell_wave,
                "max_wave": max_wave,
                "chunk_steps": chunk_steps,
                "t_end": t_end,
                "profile": _config.active_profile(),
                "with_metrics": with_metrics,
                "adaptive": stop is not None,
                "redistribute": bool(redistribute),
                "n_rounds": n_rounds,
                "serve_backed": service is not None,
            },
            cells=cells_blk,
            schedule=sched_block,
            telemetry=(
                telemetry.snapshot() if telemetry is not None else None
            ),
        )
    return SweepResult(
        grid=grid,
        summaries=summaries,
        n_reps=n_reps,
        n_failed=np.asarray(
            [int(a[1]) for a in accs], np.int64
        ),
        total_events=np.asarray(
            [int(a[2]) for a in accs], np.int64
        ),
        stop_round=stop_round,
        halfwidth=hw,
        met=met,
        n_rounds=n_rounds,
        seed=seed,
        confidence=confidence,
        wall_s=time.perf_counter() - t0,
        occupancy=occ,
        metrics=metrics,
        audit=audit_card,
    )


def run_fused_sweeps(
    points,
    *,
    reps_per_cell: int,
    seed: int = 0,
    service=None,
    fuse_max_specs: Optional[int] = None,
    max_wave: int = 4096,
    serve_timeout: float = 600.0,
    **kw,
) -> list:
    """Run several DISTINCT-model sweeps through one shared
    fuse-enabled service, so their cells pack into cross-spec fused
    waves (docs/26_wave_fusion.md) instead of each model degenerating
    to its own mostly-padded waves.

    ``points`` is a sequence of ``(spec, grid)`` pairs; each runs as a
    serve-backed :func:`run_sweep` with the SAME ``reps_per_cell`` /
    ``seed`` / forwarded ``**kw``, concurrently, against one
    :class:`~cimba_tpu.serve.service.Service` with ``fuse=True`` —
    compatible-shape specs land in one fusion class and their
    (cell, round) requests splice into shared superprogram waves;
    shape-incompatible specs simply serve unfused (fusion never
    changes results, only packing).  Returns the per-point
    :class:`SweepResult` list in ``points`` order — every per-cell
    result stays bitwise the direct fixed-R call's, exactly as the
    serve-backed single-sweep contract pins.

    Pass ``service=`` to reuse a caller-owned service (its ``fuse``
    setting then governs; the per-call knobs are ignored) — e.g. to
    fuse sweep traffic with live serving traffic."""
    import threading

    points = list(points)
    if not points:
        return []
    owned = service is None
    if owned:
        from cimba_tpu.serve.service import Service

        service = Service(
            max_wave=max_wave, fuse=True,
            fuse_max_specs=fuse_max_specs,
        )
    results: list = [None] * len(points)
    errors: list = [None] * len(points)

    def one(i, spec, grid):
        try:
            results[i] = run_sweep(
                spec, grid, reps_per_cell=reps_per_cell, seed=seed,
                service=service, serve_timeout=serve_timeout,
                max_wave=max_wave, **kw,
            )
        except BaseException as e:  # re-raised on the caller thread
            errors[i] = e

    try:
        threads = [
            threading.Thread(
                target=one, args=(i, s, g), daemon=True,
                name=f"fused-sweep-{i}",
            )
            for i, (s, g) in enumerate(points)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        if owned:
            service.shutdown(wait=True)
    for e in errors:
        if e is not None:
            raise e
    return results
