"""Budgeted search over a schedule space, emitting a ``TuneReport``.

The search races every structurally-distinct candidate of a
:class:`~cimba_tpu.tune.space.ScheduleSpace` against the default
schedule at ONE operating point (the caller's (spec, params, R) —
schedules are per-workload, which is the whole reason they are
searched, docs/21_autotune.md), through the real
``run_experiment_stream`` entry point:

* **exhaustive** when the grid fits the wall budget (the common case —
  canonicalization already collapsed inert knobs);
* **successive halving** otherwise: one interleaved pilot round over
  the live set, drop the slowest half (the incumbent default is never
  dropped), repeat until the survivors x ``repeats`` fit, then a full
  :func:`~cimba_tpu.tune.measure.measure_arms` pass with the
  self-vs-self noise twin.  Every eliminated/skipped arm stays in the
  report with its measured walls — nothing is silently dropped.

**Bitwise pinning**: a candidate is eligible to win only if its
result digest equals the default schedule's at the candidate's own
wave geometry — dispatch knobs (table-scan layout) and
``chunk_steps`` are bitwise-invariant, so same-``wave_size`` arms must
reproduce the baseline digest exactly; a candidate that changes
``wave_size`` is pinned against an untimed default-knob twin at that
``wave_size`` (the pooled summary's merge order legitimately follows
the wave partition, docs/12_streaming.md).  A pin failure is a
determinism bug somewhere and raises by default (``strict_pin``).

**Decision**: the best pinned challenger must beat the default by more
than the measured noise floor (plus ``min_gain``) or the report HOLDs
the default — a tuned entry is only ever written for a win the machine
could actually distinguish from its own jitter.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
import warnings
from typing import Callable, List, Optional

from cimba_tpu.tune import measure as _measure
from cimba_tpu.tune.space import Schedule, ScheduleSpace, default_space

__all__ = ["TuneReport", "search_schedule", "write_report", "load_report"]

#: TuneReport schema version
REPORT_FORMAT = 1


class SchedulePinError(RuntimeError):
    """A candidate schedule's result diverged bitwise from the default
    schedule's — schedules must never change results; this is a
    determinism bug, not a slow arm."""


@dataclasses.dataclass
class TuneReport:
    """One search's full record: every arm (times, status, digest,
    pinned), the noise floor, the winner, and provenance — the JSON
    artifact ``tools/bench_history.py --tune`` collates."""

    spec_name: str
    spec_fingerprint: Optional[str]   # sha256 of the stable fingerprint
    backend: str
    device_kind: str
    bucket: int                       # workload bucket (pow2 of R)
    workload: dict
    space: dict
    arms: List[dict]
    baseline: str
    noise_floor_frac: Optional[float]
    winner: Schedule
    winner_name: str
    decision: str                     # "tuned" | "hold"
    speedup_frac: float               # winner rate gain over default
    env: dict
    created_unix: float
    wall_s: float

    def to_json(self) -> dict:
        doc = dataclasses.asdict(self)
        doc["format"] = REPORT_FORMAT
        doc["winner"] = self.winner.to_json()
        doc["report_digest"] = self.digest()
        return doc

    # cimba-check: content-path
    def digest(self) -> str:
        """Content digest (sha256) excluding the creation timestamp —
        two identical searches on one machine digest identically (the
        run-card discipline, docs/18_audit.md)."""
        doc = dataclasses.asdict(self)
        doc["winner"] = self.winner.to_json()
        doc.pop("created_unix", None)
        doc.pop("wall_s", None)
        return hashlib.sha256(
            json.dumps(doc, sort_keys=True, default=str).encode("utf-8")
        ).hexdigest()


def write_report(report: TuneReport, out_dir) -> str:
    """Write a report content-addressed (``tunereport_<digest16>.json``),
    crash-atomic (tmp + rename — the run-card discipline)."""
    out_dir = str(out_dir)
    os.makedirs(out_dir, exist_ok=True)
    doc = report.to_json()
    path = os.path.join(
        out_dir, f"tunereport_{doc['report_digest'][:16]}.json"
    )
    tmp = f"{path}.tmp{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True, default=str)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def load_report(path) -> dict:
    """Load one TuneReport JSON with a loud error naming the file."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or doc.get("format") != REPORT_FORMAT:
        raise ValueError(
            f"{path}: not a TuneReport (format "
            f"{doc.get('format') if isinstance(doc, dict) else '?'} != "
            f"{REPORT_FORMAT})"
        )
    return doc


def _block_result(st):
    """Block on every result leaf — the timing anchor (async dispatch
    must not leak out of a timed region)."""
    import jax

    jax.block_until_ready(
        jax.tree.leaves((st.summary, st.n_failed, st.total_events))
    )
    return st


def search_schedule(
    spec,
    params,
    n_replications: int,
    *,
    space: Optional[ScheduleSpace] = None,
    candidates: Optional[list] = None,
    wave_size: Optional[int] = None,
    seed: int = 2026,
    t_end: Optional[float] = None,
    mesh=None,
    summary_path=None,
    warm_params=None,
    repeats: int = 2,
    budget_s: Optional[float] = None,
    compile_budget_s: Optional[float] = None,
    min_gain: float = 0.0,
    strict_pin: bool = True,
    probe_program_size: bool = True,
    program_cache=None,
    out_dir=None,
    on_round: Optional[Callable[[int], None]] = None,
    workload_label: Optional[str] = None,
    runner: Optional[Callable] = None,
) -> TuneReport:
    """Search the schedule space for ``(spec, params, R)`` and return a
    :class:`TuneReport` (written to ``out_dir`` when given).  The
    default arm is always measured (it is the incumbent and the noise
    twin); ``warm_params`` (e.g. the model's tiny-workload params)
    warms each arm's compiled shapes outside the timed rounds.  The
    report's winner is only persisted by the caller
    (:func:`cimba_tpu.tune.registry.save_tuned`) — searching and
    adopting are separate decisions.

    ``runner(schedule, warm=...)`` replaces the direct stream call as
    the measured workload — the hook serve-backed searches use for
    knobs the direct path never exercises (``waves_per_device`` /
    ``preempt_quantum`` / ``mem_fraction`` / ``fuse``, which live in
    the Service dispatcher, not the chunk program).  It must return a
    StreamResult-shaped payload (``summary``/``n_failed``/
    ``total_events``/``metrics`` — tuples of per-request results are
    fine; the digest walks leaves), deterministic for a given
    schedule so the bitwise pin holds across arms."""
    import jax

    from cimba_tpu.obs import audit as _audit
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.serve import cache as _pcache
    from cimba_tpu.serve import store as _pstore
    from cimba_tpu.tune import registry as _registry

    R = int(n_replications)
    if candidates is None:
        if space is None:
            space = default_space(spec)
        candidates = space.candidates(spec)
    else:
        # canonicalize + dedup explicit candidates too: structurally
        # inert knob settings collapse (prune, don't measure)
        from cimba_tpu.tune.space import _FIELDS as _SCHED_FIELDS

        canon, seen = [], set()
        for c in candidates:
            cc = c.canonical(spec)
            key = tuple(getattr(cc, f) for f in _SCHED_FIELDS)
            if key not in seen:
                seen.add(key)
                canon.append(cc)
        candidates = [Schedule()] + [
            c for c in canon if not c.is_default()
        ]
    space_doc = space.axes() if space is not None else {
        "explicit": [c.label() for c in candidates]
    }
    cache = (
        program_cache if program_cache is not None
        else _pcache.ProgramCache(capacity=max(64, 4 * len(candidates)))
    )
    base_wave = R if wave_size is None else int(wave_size)

    def eff_wave(sched: Schedule) -> int:
        return int(sched.wave_size) if sched.wave_size is not None \
            else base_wave

    def run_point(sched: Schedule, warm: bool):
        if runner is not None:
            return _block_result(runner(sched, warm=warm))
        p = warm_params if (warm and warm_params is not None) else params
        st = ex.run_experiment_stream(
            spec, p, R,
            wave_size=eff_wave(sched),
            seed=seed, t_end=t_end, mesh=mesh,
            summary_path=(
                summary_path if summary_path is not None
                else ex.default_summary_path
            ),
            program_cache=cache,
            schedule=sched,   # explicit: the registry is never consulted
        )
        return _block_result(st)

    def probe_size(sched: Schedule) -> Optional[dict]:
        """Trace-only program-size probe under the arm's trace-time
        scope (docs/25_compile_wall.md) — eqn count / jaxpr bytes next
        to each arm's wall numbers, so a compile-budget skip is
        measured data, not a silent cut.  Never compiles; a model the
        probe can't trace (exotic spec) degrades to None, not a
        failed search."""
        if not probe_program_size:
            return None
        from cimba_tpu.obs import program_size as _ps

        try:
            with sched.scope():
                return _ps.chunk_program_size(
                    spec, params, lanes=4, lower=False,
                ).to_dict()
        except Exception:
            return None

    def make_arm(sched: Schedule) -> _measure.Arm:
        name = sched.label()

        def prepare(sched=sched):
            run_point(sched, warm=True)
            if warm_params is None:
                return
            # warm at the REAL workload too when a cheap warm ran
            # first: jit specializes per shape, and params shapes are
            # identical either way, so this is usually a cache hit
            run_point(sched, warm=False)

        def run(sched=sched):
            return run_point(sched, warm=False)

        return _measure.Arm(name=name, run=run, prepare=prepare,
                            meta=sched, program_size=probe_size(sched))

    arms = [make_arm(c) for c in candidates]
    psizes = {a.name: a.program_size for a in arms}
    by_name = {c.label(): c for c in candidates}
    t0 = time.perf_counter()

    # -- successive halving when the grid x budget doesn't fit ---------------
    stage_rows: dict = {}   # name -> {"stage_walls": [...], "status": ...}
    for a in arms:
        stage_rows[a.name] = {"stage_walls": [], "stages": 0}
    live = arms
    stage = 0
    final_rep = None
    while True:
        remaining = (
            None if budget_s is None
            else budget_s - (time.perf_counter() - t0)
        )
        last_round = None
        if stage:
            walls = [
                stage_rows[a.name]["stage_walls"][-1]
                for a in live if stage_rows[a.name]["stage_walls"]
            ]
            last_round = sum(walls) if walls else None
        fits = (
            budget_s is None
            or len(live) <= 2
            or (
                stage > 0 and last_round is not None
                and last_round * (repeats + 1) <= (remaining or 0.0)
            )
        )
        if fits:
            final_rep = _measure.measure_arms(
                live, repeats=repeats, baseline=0,
                budget_s=remaining, noise_twin=True,
                compile_budget_s=compile_budget_s if stage == 0 else None,
                on_round=on_round,
            )
            break
        pilot = _measure.measure_arms(
            live, repeats=1, baseline=0, budget_s=remaining,
            noise_twin=False,
            compile_budget_s=compile_budget_s if stage == 0 else None,
            on_round=on_round,
        )
        ranked = []
        for res in pilot.arms:
            row = stage_rows[res.name]
            row["stage_walls"].extend(res.walls)
            row["stages"] += 1
            if res.status == _measure.SKIPPED:
                row["status"] = "skipped"
                row["skip_reason"] = res.skip_reason
            elif res.best_wall is not None:
                ranked.append((res.best_wall, res.name))
        ranked.sort()
        remaining = (
            None if budget_s is None
            else budget_s - (time.perf_counter() - t0)
        )
        full_round = sum(w for w, _ in ranked)
        if (
            remaining is None
            or full_round * (repeats + 1) <= remaining
            or len(ranked) <= 2
        ):
            # the pilot proved the whole grid fits the budget after
            # all (compiles dominated the estimate): keep every arm
            survivors = {name for _, name in ranked}
        else:
            keep = max(2, (len(ranked) + 1) // 2)
            survivors = {name for _, name in ranked[:keep]}
        survivors.add(arms[0].name)   # the incumbent is never dropped
        for res in pilot.arms:
            if res.name not in survivors and res.status == _measure.OK:
                stage_rows[res.name]["status"] = "eliminated"
        # prepares already ran in stage 0 — don't re-pay them per stage
        live = [
            dataclasses.replace(a, prepare=None)
            for a in live if a.name in survivors
        ]
        live.sort(key=lambda a: 0 if a.name == arms[0].name else 1)
        stage += 1

    # -- bitwise pinning -----------------------------------------------------
    base_res = final_rep.arm(arms[0].name)
    base_payload = base_res.payload
    if base_payload is None:
        raise RuntimeError(
            "tune.search: the default schedule never completed a "
            "measured round — raise the budget"
        )
    pin_digests = {
        base_wave: _audit.stream_result_digest(base_payload)
    }

    def pin_digest_for(w: int) -> str:
        if w not in pin_digests:
            # untimed default-knob twin at this wave geometry: the
            # merge order follows the wave partition, so the bitwise
            # reference must share it
            if runner is not None:
                pin_digests[w] = _audit.stream_result_digest(
                    _block_result(
                        runner(Schedule(wave_size=w), warm=False)
                    )
                )
                return pin_digests[w]
            st = ex.run_experiment_stream(
                spec, params, R, wave_size=w, seed=seed, t_end=t_end,
                mesh=mesh,
                summary_path=(
                    summary_path if summary_path is not None
                    else ex.default_summary_path
                ),
                program_cache=cache,
                schedule=Schedule(wave_size=w),
            )
            pin_digests[w] = _audit.stream_result_digest(
                _block_result(st)
            )
        return pin_digests[w]

    rows: List[dict] = []
    rates: dict = {}
    for cand in candidates:
        name = cand.label()
        srow = stage_rows[name]
        row = {
            "name": name,
            "schedule": cand.to_json(),
            "stage_walls_s": [round(w, 6) for w in srow["stage_walls"]],
            "status": srow.get("status", "ok"),
            "skip_reason": srow.get("skip_reason"),
            "walls_s": [],
            "best_wall_s": None,
            "compile_s": None,
            "program_size": psizes.get(name),
            "events": None,
            "rate": None,
            "digest": None,
            "pinned": None,
        }
        try:
            res = final_rep.arm(name)
        except KeyError:
            res = None
        if res is not None:
            row["walls_s"] = [round(w, 6) for w in res.walls]
            row["best_wall_s"] = res.best_wall
            row["compile_s"] = res.compile_s
            if res.status == _measure.SKIPPED:
                row["status"] = "skipped"
                row["skip_reason"] = res.skip_reason
            elif res.payload is not None:
                events = int(res.payload.total_events)
                dig = _audit.stream_result_digest(res.payload)
                row["events"] = events
                row["digest"] = dig
                pinned = dig == pin_digest_for(eff_wave(cand))
                row["pinned"] = pinned
                if not pinned:
                    row["status"] = "mismatch"
                    msg = (
                        f"tune.search: arm {name!r} diverged bitwise "
                        f"from the default schedule at wave_size="
                        f"{eff_wave(cand)} — schedules must never "
                        "change results"
                    )
                    if strict_pin:
                        raise SchedulePinError(msg)
                    warnings.warn(msg, RuntimeWarning)
                elif res.best_wall:
                    row["rate"] = events / res.best_wall
                    rates[name] = row["rate"]
        rows.append(row)

    # -- decision ------------------------------------------------------------
    base_name = arms[0].name
    base_rate = rates.get(base_name)
    floor = final_rep.noise_floor_frac
    winner_name, decision, speedup = base_name, "hold", 0.0
    if base_rate:
        best_name = max(rates, key=lambda n: rates[n])
        gain = rates[best_name] / base_rate - 1.0
        if (
            best_name != base_name
            and gain > (floor or 0.0) + float(min_gain)
        ):
            winner_name, decision, speedup = best_name, "tuned", gain
        else:
            speedup = max(gain, 0.0) if best_name != base_name else 0.0
    winner = by_name[winner_name]

    try:
        fp = hashlib.sha256(
            repr(_pstore.stable_spec_fingerprint(spec)).encode("utf-8")
        ).hexdigest()
    except _pstore.UnstableStoreKey:
        fp = None
    dev = jax.devices()[0]
    workload = {
        "R": R,
        "wave_size": base_wave,
        "t_end": t_end,
        "seed": int(seed),
        "label": workload_label,
    }
    report = TuneReport(
        spec_name=getattr(spec, "name", "?"),
        spec_fingerprint=fp,
        backend=jax.default_backend(),
        device_kind=getattr(dev, "device_kind", "?"),
        bucket=_registry.workload_bucket(R),
        workload=workload,
        space={k: list(v) for k, v in space_doc.items()},
        arms=rows,
        baseline=base_name,
        noise_floor_frac=floor,
        winner=winner,
        winner_name=winner_name,
        decision=decision,
        speedup_frac=speedup,
        env=_audit.environment(),
        created_unix=time.time(),
        wall_s=time.perf_counter() - t0,
    )
    if out_dir:
        write_report(report, out_dir)
    return report
