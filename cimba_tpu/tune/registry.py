"""Tuned-schedule persistence and resolution.

The winner of a :func:`~cimba_tpu.tune.search.search_schedule` run
persists in the PR 6 program-store manifest (a ``"tuned"`` section
beside ``"entries"`` — same file, same crash-atomic + cross-process
lock discipline, same strict environment invalidation ladder: a
jax/jaxlib/backend/device drift invalidates a tuned entry exactly like
a serialized executable, docs/15_program_store.md) keyed by

    ``tune_key = sha256(stable_spec_fingerprint, backend, device kind,
    workload bucket)``

— value-based, so a fresh process resolves the same entry a tuner
process saved.  The workload bucket is the pow2 ceiling of R: a tuned
schedule is a per-workload-SCALE decision (the round-6 lesson — the
winner flips between the 256-lane CPU window and the 131072-lane TPU
point), and bucketing at pow2 granularity keeps nearby R sharing one
entry without letting a 64-lane probe's winner govern a million-lane
fleet.

Resolution (:func:`resolve_schedule`) is what every entry point calls
at program-build time — ``run_experiment_stream``,
``serve.Service.submit``, ``sweep.run_sweep``, fleet slices via the
service.  The ladder, loudest first:

1. explicit kwargs / an explicit ``schedule=`` always win
   (``source="override"``);
2. ``CIMBA_TUNE=0`` opts out entirely (``source="off"``);
3. a valid tuned entry in the store (env-checked) resolves
   (``source="tuned"``);
4. otherwise the hand-frozen defaults run, as they always have
   (``source="default"``).

The source surfaces in ``Service.stats()["schedule"]`` / ``/varz`` and
in every run card's ``schedule`` block, so "which schedule did this
number run under?" is always answerable (docs/21_autotune.md).
Lookups are memoized per (store root, key) against the manifest's
mtime, so the serve submit path never re-parses the manifest per
request.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import threading
import time
from typing import Optional, Tuple

from cimba_tpu.tune.space import Schedule

__all__ = [
    "TUNE_ENV", "tune_enabled", "workload_bucket", "tune_key",
    "save_tuned", "lookup_tuned", "resolve_schedule",
    "ResolvedEntry", "resolve_entry",
]

#: environment knob: "0" opts every entry point out of tuned-schedule
#: resolution (registered in ``config.ENV_KNOBS``; the ``tune`` gate in
#: check/gates.py pins that the off state is jaxpr-identical to the
#: default)
TUNE_ENV = "CIMBA_TUNE"

_lock = threading.Lock()
#: (store root, tune key) -> (manifest mtime, entry-or-None, verdict
#: counter name); every access holds ``_lock``
_memo: dict = {}


def tune_enabled() -> bool:
    from cimba_tpu import config

    return config.env_raw(TUNE_ENV) != "0"


def workload_bucket(n_replications: int) -> int:
    """The pow2 ceiling of R — the workload-scale bucket a tuned entry
    is keyed by (64 and 100 lanes share a schedule; 256 and 131072 do
    not)."""
    R = int(n_replications)
    if R <= 1:
        return 1
    return 1 << (R - 1).bit_length()


# cimba-check: content-path
def tune_key(spec, *, n_replications: int, backend: Optional[str] = None,
             device_kind: Optional[str] = None) -> str:
    """The persistent tuned-entry key: sha256 over the VALUE-based spec
    fingerprint, backend, device kind, and the workload bucket.
    Raises :class:`~cimba_tpu.serve.store.UnstableStoreKey` when the
    spec has no value identity (same contract as the artifact store)."""
    from cimba_tpu.serve import store as _pstore

    if backend is None or device_kind is None:
        import jax

        dev = jax.devices()[0]
        if backend is None:
            backend = jax.default_backend()
        if device_kind is None:
            device_kind = getattr(dev, "device_kind", "?")
    key = (
        "tune", 1,
        _pstore.stable_spec_fingerprint(spec),
        str(backend), str(device_kind),
        workload_bucket(n_replications),
    )
    return hashlib.sha256(repr(key).encode("utf-8")).hexdigest()


def _invalidate_memo(root: str) -> None:
    with _lock:
        for k in [k for k in _memo if k[0] == root]:
            del _memo[k]


def save_tuned(store, spec, n_replications: int, report) -> Optional[dict]:
    """Persist a search's winner into ``store``'s manifest (merged
    under the cross-process manifest lock).  ``report`` is a
    :class:`~cimba_tpu.tune.search.TuneReport`; a HOLD decision saves
    nothing and returns None — the default needs no entry.  Returns
    the written record."""
    from cimba_tpu.serve import store as _pstore

    if getattr(report, "decision", None) != "tuned":
        return None
    try:
        key = tune_key(
            spec, n_replications=n_replications,
            backend=report.backend, device_kind=report.device_kind,
        )
    except _pstore.UnstableStoreKey as e:
        # no value identity -> no persistent slot to save under: record
        # a downgrade like the artifact path (the in-process winner can
        # still be applied via an explicit schedule= kwarg)
        import warnings

        warnings.warn(
            f"tuned winner for {report.spec_name!r} cannot persist "
            f"({e}); pass schedule= explicitly instead",
            _pstore.StoreInvalidationWarning,
        )
        store._count("downgrades")
        return None
    # the winner arm's compile/program-size numbers ride into the
    # manifest (docs/25_compile_wall.md): a tuned entry that traded
    # run-time for compile-time shows its price next to the speedup
    win_row = next(
        (r for r in report.arms if r.get("name") == report.winner_name),
        None,
    ) or {}
    rec = {
        "schedule": report.winner.to_json(),
        "schedule_digest": report.winner.digest(),
        "env": _pstore._environment(),
        "created": time.time(),
        "report_digest": report.digest(),
        "meta": {
            "model": report.spec_name,
            "bucket": report.bucket,
            "workload": report.workload,
            "speedup_frac": report.speedup_frac,
            "noise_floor_frac": report.noise_floor_frac,
            "compile_s": win_row.get("compile_s"),
            "program_size": win_row.get("program_size"),
        },
    }

    def put(manifest):
        manifest.setdefault("tuned", {})[key] = rec

    store._update_manifest(put)
    store._count("tuned_saves")
    _invalidate_memo(store.root)
    return rec


def lookup_tuned(store, key: str) -> Optional[dict]:
    """One tuned entry by key, under the artifact store's invalidation
    ladder: absent -> counted miss; environment drift (jax/jaxlib/
    backend/device/x64) -> counted ``tuned_invalidated`` with a loud
    :class:`~cimba_tpu.serve.store.StoreInvalidationWarning` — a tuned
    schedule measured on different software/hardware is a guess, and
    this registry exists to end guessing.  Memoized against the
    manifest mtime (the serve submit path resolves per request)."""
    import warnings

    from cimba_tpu.serve import store as _pstore

    mpath = store._manifest_path()
    try:
        mtime = os.stat(mpath).st_mtime_ns
    except OSError:
        mtime = None
    memo_key = (store.root, key)
    with _lock:
        hit = _memo.get(memo_key)
        if hit is not None and hit[0] == mtime:
            # re-count the memoized VERDICT, not a guess from the
            # payload: an env-invalidated entry must keep reading as
            # invalidated in the counters (the re-run-the-search
            # signal), never degrade into "misses" after the first
            # lookup; the warning stays once-per-manifest-generation
            store._count(hit[2])
            return hit[1]
    with store._lock:
        manifest = store._read_manifest()
    entry = (manifest.get("tuned") or {}).get(key)
    out = None
    if entry is None:
        verdict = "tuned_misses"
    elif entry.get("env") != _pstore._environment():
        env = _pstore._environment()
        drift = {
            k: (entry.get("env", {}).get(k), env[k])
            for k in env if entry.get("env", {}).get(k) != env[k]
        }
        warnings.warn(
            f"tuned schedule entry {key[:16]} was measured in a "
            f"different environment ({drift}); falling back to the "
            "default schedule — re-run the search",
            _pstore.StoreInvalidationWarning,
        )
        verdict = "tuned_invalidated"
    else:
        verdict = "tuned_hits"
        out = entry
    store._count(verdict)
    with _lock:
        _memo[memo_key] = (mtime, out, verdict)
    return out


def resolve_schedule(
    spec, n_replications: int, *, store=None,
) -> Tuple[Optional[Schedule], str, Optional[str]]:
    """The resolution ladder every entry point rides at program-build
    time: ``(schedule | None, source, tune_entry_digest | None)`` with
    ``source`` one of ``"off"`` (``CIMBA_TUNE=0``), ``"default"`` (no
    store / no entry / invalidated / unstable spec), or ``"tuned"``.
    ``store=None`` resolves ``CIMBA_PROGRAM_STORE`` (the fleet-slice
    path — a slice with the env knob set resolves tuned schedules with
    zero configuration); ``store=False`` opts out like a missing
    store.  Never raises: an unstable spec or a corrupt record is a
    counted degrade to the default schedule, exactly like the artifact
    ladder."""
    import warnings

    from cimba_tpu.serve import store as _pstore

    if not tune_enabled():
        return None, "off", None
    if store is False:
        return None, "default", None
    st = store if store is not None else _pstore.default_store()
    if st is None:
        return None, "default", None
    try:
        key = tune_key(spec, n_replications=n_replications)
    except _pstore.UnstableStoreKey:
        return None, "default", None
    entry = lookup_tuned(st, key)
    if entry is None:
        return None, "default", None
    try:
        sched = Schedule.from_json(entry["schedule"])
    except (KeyError, TypeError, ValueError) as e:
        warnings.warn(
            f"tuned schedule entry {key[:16]} is malformed "
            f"({type(e).__name__}: {e}); using the default schedule",
            _pstore.StoreInvalidationWarning,
        )
        st._count("tuned_invalidated")
        return None, "default", None
    return sched, "tuned", entry.get("schedule_digest")


@dataclasses.dataclass
class ResolvedEntry:
    """One entry point's resolved schedule: the effective argument
    knobs (explicit kwargs already folded in — they always win), the
    trace-time knob subset to bind via :meth:`scope`, the resolution
    ``source`` (``override``/``tuned``/``default``/``off``), and the
    ``schedule`` block run cards and ``Service.stats()`` surface."""

    schedule: Optional[Schedule]
    source: str
    tune_digest: Optional[str]
    chunk_steps: int
    wave_size: Optional[int]
    applied: dict

    def scope(self):
        """Context manager binding the resolved TRACE-time knob (the
        kernel lane block) for a dispatch region.  The argument knobs
        (chunk/wave) ride kwargs instead."""
        if self.schedule is None:
            return contextlib.nullcontext()
        sub = Schedule(lane_block=self.schedule.lane_block)
        if sub.is_default():
            return contextlib.nullcontext()
        return sub.scope()

    def block(self) -> dict:
        """The ``schedule`` block (docs/18_audit.md): resolved knobs +
        resolution source + tuned-entry digest — what run cards carry
        so every bitwise claim names the schedule it ran under."""
        knobs = {
            "chunk_steps": self.chunk_steps,
            "wave_size": self.wave_size,
        }
        if self.schedule is not None:
            for f in ("lane_block", "waves_per_device", "preempt_quantum",
                      "mem_fraction", "fuse", "fuse_max_specs"):
                v = getattr(self.schedule, f)
                if v is not None:
                    knobs[f] = v
        return {
            "source": self.source,
            "tune_entry": self.tune_digest,
            "knobs": knobs,
        }


def resolve_entry(
    spec,
    n_replications: int,
    *,
    schedule: Optional[Schedule] = None,
    chunk_steps: Optional[int] = None,
    wave_size: Optional[int] = None,
    store=None,
    default_chunk_steps: int = 1024,
) -> ResolvedEntry:
    """Fold one entry point's explicit kwargs over the resolution
    ladder and return the effective knob set.  ``schedule=`` (an
    explicit :class:`Schedule`) pre-empts the registry entirely
    (``source="override"`` — the search harness and power users);
    otherwise a registry-resolved schedule fills ONLY the knobs the
    caller left unset, and ``source`` reports ``"tuned"`` only when at
    least one tuned knob actually took effect."""
    if schedule is not None:
        sched, source, dig = schedule, "override", None
    else:
        sched, source, dig = resolve_schedule(
            spec, n_replications, store=store,
        )
    applied: dict = {}
    eff_chunk = chunk_steps
    if eff_chunk is None:
        if sched is not None and sched.chunk_steps is not None:
            eff_chunk = int(sched.chunk_steps)
            applied["chunk_steps"] = eff_chunk
        else:
            eff_chunk = int(default_chunk_steps)
    eff_wave = wave_size
    if eff_wave is None and sched is not None \
            and sched.wave_size is not None:
        eff_wave = int(sched.wave_size)
        applied["wave_size"] = eff_wave
    if sched is not None:
        if sched.lane_block is not None:
            applied["lane_block"] = int(sched.lane_block)
        # device-scheduler policy knobs (docs/24_device_scheduler.md):
        # service-level, not per-request — serve.Service adopts them
        # at submit time when its own constructor knobs were left
        # None (Service._adopt_sched_knobs); they count as applied so
        # the resolution source stays truthful
        if sched.waves_per_device is not None:
            applied["waves_per_device"] = int(sched.waves_per_device)
        if sched.preempt_quantum is not None:
            applied["preempt_quantum"] = int(sched.preempt_quantum)
        if sched.mem_fraction is not None:
            applied["mem_fraction"] = float(sched.mem_fraction)
        # wave-fusion policy knobs (docs/26_wave_fusion.md): same
        # service-level adoption path (Service._adopt_fuse_knobs)
        if sched.fuse is not None:
            applied["fuse"] = bool(sched.fuse)
        if sched.fuse_max_specs is not None:
            applied["fuse_max_specs"] = int(sched.fuse_max_specs)
    if source == "tuned" and not applied:
        # a tuned entry existed but every one of its knobs lost to an
        # explicit kwarg/ambient override — the run is the caller's
        source = "override"
    return ResolvedEntry(
        schedule=sched,
        source=source,
        tune_digest=dig,
        chunk_steps=int(eff_chunk),
        wave_size=eff_wave,
        applied=applied,
    )
