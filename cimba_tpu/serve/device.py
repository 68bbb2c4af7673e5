"""The preemptive device scheduler (docs/24_device_scheduler.md).

PR 15's boundary controller drove exactly ONE refill wave per device to
retirement: a request whose class matched no live wave waited for
whole-wave retirement even with device memory to spare.  This module
grows that controller into a device scheduler:

* **concurrent waves** — the dispatcher interleaves chunk dispatch
  across up to ``waves_per_device`` live waves, one preemption quantum
  (``preempt_quantum`` chunks) per wave per turn, round-robin.  Each
  wave is the PR 15 :class:`~cimba_tpu.serve.service._RefillWave`
  driven by the same boundary controller — retirement, reclamation,
  and boundary admission are unchanged, so every bitwise contract the
  refill plane pinned carries over verbatim.
* **memory-aware admission** — a new wave starts only when its
  estimated footprint (:func:`cimba_tpu.serve.cache
  .wave_footprint_bytes`: store-measured ``footprint_bytes`` →
  ``memory_analysis()`` → conservative estimate) fits the device
  budget (``mem_budget_bytes``, default ``mem_fraction`` x the
  device's reported memory).  A request whose wave could NEVER fit
  fails fast with structured
  :class:`~cimba_tpu.serve.sched.MemoryBudgetExceeded` backpressure;
  one that merely doesn't fit right now waits (or preempts).
* **wave preemption** — at a quantum boundary a lower-priority wave is
  checkpointed through the PR 3 resumable path
  (``runner.checkpoint.save_resumable``), its device buffers evicted,
  the urgent class runs, and the victim restores bit-identically: the
  Sim pytree is the COMPLETE per-lane state (counter-mode RNG
  position included), so a save/evict/restore round-trip is invisible
  to results — the determinism contract extended to scheduling.  The
  wave's host-side ownership table (``_RefillWave`` slots/free pool)
  is untouched by evict/restore, so retirements and mid-wave
  deliveries resume exactly where they left off.

Everything here is HOST-side dispatch policy: compiled programs are
byte-identical with the scheduler on or off (the ``device_sched``
gate in check/gates.py pins ambient inertness), and the scheduler
itself runs on the service's single dispatcher thread — no new
concurrency, the same lock discipline.
"""

from __future__ import annotations

import os
import time
import warnings
from typing import Optional

from cimba_tpu.serve import cache as _pcache
from cimba_tpu.serve.sched import Cancelled, MemoryBudgetExceeded
from cimba_tpu.tune.space import (
    DEFAULT_MEM_FRACTION,
    DEFAULT_PREEMPT_QUANTUM,
    DEFAULT_WAVES_PER_DEVICE,
)

__all__ = ["DeviceScheduler", "WaveTask", "device_memory_budget"]

#: fallback device memory when the backend reports none (CPU PjRt has
#: no ``bytes_limit``) — deliberately roomy: on such backends the
#: budget is a policy knob for tests/ops, not a hard physical wall
_DEFAULT_DEVICE_BYTES = 8 << 30

#: delay for a claimed request that fits nothing right now (budget or
#: wave slots held by equal/higher-priority waves): parked in the
#: queue's DELAYED heap — invisible to the boundary-admission fairness
#: valve while it waits, re-offered when capacity can have changed
_WAIT_REQUEUE_S = 0.05


def device_memory_budget(
    mem_fraction: Optional[float] = None,
    mem_budget_bytes: Optional[int] = None,
) -> int:
    """The admission budget in bytes: an explicit ``mem_budget_bytes``
    wins; otherwise ``mem_fraction`` (default
    ``tune.space.DEFAULT_MEM_FRACTION``) of the device's reported
    memory (``jax.devices()[0].memory_stats()`` where implemented,
    ``_DEFAULT_DEVICE_BYTES`` where not — CPU backends report
    nothing)."""
    if mem_budget_bytes is not None:
        return int(mem_budget_bytes)
    frac = float(
        DEFAULT_MEM_FRACTION if mem_fraction is None else mem_fraction
    )
    limit = None
    try:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        limit = (
            stats.get("bytes_limit")
            or stats.get("bytes_reservable_limit")
        )
    except Exception:
        limit = None
    if not limit:
        limit = _DEFAULT_DEVICE_BYTES
    return int(int(limit) * frac)


class WaveTask:
    """One live wave under the scheduler: the PR 15 ownership table
    (``wave``), its device state (``sims`` — None while PREEMPTED),
    the absolute chunk counter ``n`` (``drive_chunks`` resumes at
    ``n0=n``, so the boundary cadence ``n % refill_every`` is
    continuous across quanta AND across preempt/restore), the admitted
    footprint, and — while preempted — the checkpoint path plus the
    ``jax.eval_shape``-style aval template ``restore_resumable``
    rebuilds the pytree against."""

    RUNNING = "running"
    PREEMPTED = "preempted"

    __slots__ = (
        "wave", "sims", "n", "state", "footprint", "ckpt_path",
        "template",
    )

    def __init__(self, wave, sims, footprint: int):
        self.wave = wave
        self.sims = sims
        self.n = 0
        self.state = WaveTask.RUNNING
        self.footprint = int(footprint)
        self.ckpt_path = None
        self.template = None

    def priority(self) -> int:
        """The wave's CURRENT priority: the max over its live (unfolded,
        undelivered) members — a wave is as urgent as its most urgent
        member, so admitting an urgent request into a background wave
        also shields that wave from preemption."""
        best = None
        for s in self.wave.slots:
            if s.folded or s.entry.done.is_set():
                continue
            p = s.entry.priority
            if best is None or p > best:
                best = p
        return 0 if best is None else best

    def earliest_deadline(self) -> float:
        """The earliest ``deadline_at`` over the wave's live members
        (``+inf`` when none carries one) — the EDF refinement of the
        restore order (docs/27_qos.md): among equal-priority preempted
        waves, the one whose tightest live deadline expires first
        restores first, so a deadline-carrying wave does not burn its
        remaining budget parked behind a deadline-free peer."""
        best = float("inf")
        for s in self.wave.slots:
            if s.folded or s.entry.done.is_set():
                continue
            dl = s.entry.deadline_at
            if dl is not None and dl < best:
                best = dl
        return best


class DeviceScheduler:
    """The device-owner scheduling loop ``Service._loop`` delegates to
    when ``device_sched`` is on.  Runs ON the service's dispatcher
    thread and drives up to ``waves_per_device`` concurrent
    :class:`WaveTask`\\ s, one ``preempt_quantum`` of chunks each per
    round-robin turn; every quantum boundary is a control point for
    admission, preemption, and restore.  All wave mechanics (pack,
    init, boundary retire/reclaim/admit, failure containment) are the
    service's own refill methods — this class only decides WHICH wave
    runs next and WHETHER a new one may start."""

    def __init__(self, service):
        self.svc = service
        self.tasks: list = []     # WaveTasks, RUNNING + PREEMPTED
        self._rr = 0              # round-robin cursor over running waves
        self._ckpt_root = None    # lazily-created checkpoint spill dir
        self._budget_cache = None
        self._budget_frac = object()  # sentinel != any fraction

    # -- effective knobs (read lazily: submit-time schedule adoption
    # -- may fill them after this scheduler started) -------------------------

    def waves_per_device(self) -> int:
        with self.svc._lock:
            v = self.svc._waves_per_device
        return int(DEFAULT_WAVES_PER_DEVICE if v is None else v)

    def preempt_quantum(self) -> int:
        with self.svc._lock:
            v = self.svc._preempt_quantum
        return max(int(DEFAULT_PREEMPT_QUANTUM if v is None else v), 1)

    def budget_bytes(self) -> int:
        svc = self.svc
        with svc._lock:
            mb = svc._mem_budget_bytes
            mf = svc._mem_fraction
        if mb is not None:
            return int(mb)
        if mf != self._budget_frac:
            self._budget_cache = device_memory_budget(mf)
            self._budget_frac = mf
        return self._budget_cache

    # -- the loop ------------------------------------------------------------

    def run(self) -> None:
        """The scheduler's main loop — the device-sched twin of
        ``Service._loop``: poll the queue (non-blocking while waves
        are live), offer any claimed entry to admission, restore a
        preempted wave when capacity allows, then advance ONE running
        wave by one quantum.  Exits when stopping/drained with no live
        waves, cancelling stragglers exactly like the plain loop."""
        svc = self.svc
        try:
            while True:
                if svc._tel is not None:
                    svc._tel.heartbeat(
                        f"serve.{svc._tel_name}.dispatch"
                    )
                entry = svc._queue.pop_ready(
                    timeout=0.0 if self.tasks else 0.25
                )
                with svc._lock:
                    stopping = svc._stop
                    drained = svc._closed and svc._outstanding == 0
                if entry is None:
                    if not self.tasks and (stopping or drained):
                        for e in svc._queue.drain_now():
                            if not e.done.is_set():
                                svc._finish(
                                    e, exc=Cancelled(e.label),
                                    outcome="cancelled",
                                )
                        return
                elif stopping:
                    if not entry.done.is_set():
                        svc._finish(entry, exc=Cancelled(entry.label),
                                    outcome="cancelled")
                else:
                    self._offer_claimed(entry)
                self._maybe_restore()
                self._step_one()
        finally:
            self._cleanup_ckpt_root()

    # -- admission -----------------------------------------------------------

    def _offer_claimed(self, entry) -> None:
        """Claim ``entry`` (the plain loop's claim discipline) and
        route it: tombstone/cancel/deadline handling first, then the
        admission decision."""
        svc = self.svc
        with svc._lock:
            if entry.done.is_set():   # cancelled tombstone
                return
            cancelled_flag = entry.cancelled
            if not cancelled_flag:
                entry.in_flight = True
        if cancelled_flag:
            svc._finish(entry, exc=Cancelled(entry.label),
                        outcome="cancelled")
            return
        now = time.monotonic()
        if entry.deadline_at is not None and now > entry.deadline_at:
            from cimba_tpu.serve.sched import DeadlineExceeded

            svc._finish(
                entry,
                exc=DeadlineExceeded(
                    entry.request.deadline, now - entry.submit_t,
                    entry.label,
                ),
                outcome="deadline_exceeded",
            )
            return
        try:
            self._admit(entry)
        except Exception as e:
            # footprint estimation traces user code (eval_shape over
            # the init program): a bad request must fail ITSELF, never
            # kill the scheduler thread
            svc._batch_failed([entry], e)

    def _admit(self, entry) -> None:
        svc = self.svc
        # same-class live wave with slot headroom: unclaim and let the
        # boundary controller splice it — the bitwise-pinned PR 15
        # admission path, and no second wave of the same class
        slot = svc._refill_slot_size(entry)
        if not entry.solo:
            for t in self.tasks:
                if (t.state == WaveTask.RUNNING
                        and not t.wave.no_admit
                        and t.wave.cls == entry.cls
                        and len(t.wave.free) >= slot):
                    with svc._lock:
                        entry.in_flight = False
                    svc._queue.requeue(entry)
                    return
        fp = self._entry_footprint(entry)
        budget = self.budget_bytes()
        if fp > budget:
            # structured backpressure: this wave can NEVER fit —
            # resize or route elsewhere, never a wrong program
            with svc._lock:
                svc._counters["mem_rejects"] += 1
            svc._finish(
                entry,
                exc=MemoryBudgetExceeded(fp, budget, entry.label),
                outcome="failed",
            )
            return
        running = self._running()
        used = sum(t.footprint for t in running)
        if len(running) < self.waves_per_device() \
                and used + fp <= budget:
            self._start_wave(entry, fp)
            return
        # preemption: the lowest-priority running wave STRICTLY below
        # this entry yields its slot+memory at this quantum boundary —
        # and only when evicting it actually makes the entry fit
        victim = None
        victim_p = None
        for t in running:
            p = t.priority()
            if p >= entry.priority:
                continue
            if victim is None or p < victim_p:
                victim, victim_p = t, p
        if victim is not None \
                and used - victim.footprint + fp <= budget:
            self._preempt(victim)
            self._start_wave(entry, fp)
            return
        # no capacity right now (and nobody to preempt): wait in the
        # delayed heap — invisible to the boundary fairness valve, so
        # live waves keep admitting their own class meanwhile
        with svc._lock:
            entry.in_flight = False
        svc._queue.requeue(entry, delay=_WAIT_REQUEUE_S)

    def _entry_footprint(self, entry) -> int:
        """The entry's wave footprint at the shape its wave would
        actually be born at: full quantized capacity for an admitting
        wave (the _pack_refill birth policy), the quantized/solo slot
        otherwise."""
        svc = self.svc
        n = svc._refill_slot_size(entry)
        if svc.pad_waves and not entry.solo:
            cap = svc.max_wave
            if svc.mesh is not None:
                unit = int(svc.mesh.devices.size)
                cap -= cap % unit
            lanes = max(cap, n)
        elif svc.pad_waves:
            lanes = svc._wave_shape(n)
        else:
            lanes = n
        req = entry.request
        return _pcache.wave_footprint_bytes(
            svc.cache, req.spec, mesh=svc.mesh,
            chunk_steps=req.chunk_steps,
            with_metrics=entry.with_metrics, lanes=lanes,
            params=req.params, n_replications=req.n_replications,
        )

    def _start_wave(self, lead, footprint: int) -> None:
        """Pack + init a new wave for ``lead`` (the service's refill
        pack path — mates of the same class join immediately) and
        enroll it as a RUNNING task.  Failure containment mirrors
        ``_serve_refill_wave``: members not yet delivered fail through
        ``_batch_failed``; a wave whose members were all delivered
        before a late error only warns."""
        from cimba_tpu.obs import metrics as _metrics

        svc = self.svc
        req = lead.request
        wave = None
        try:
            cls_now = _pcache.program_class_key(
                req.spec, _metrics.enabled(), mesh=svc.mesh,
            )
            if cls_now != lead.cls[0]:
                raise ValueError(
                    "serve: a trace-time global (dtype profile or "
                    "obs.metrics/obs.trace state) changed between this "
                    "request's submit and its dispatch — the "
                    "compatibility key binds at submit time; resubmit "
                    "after settling the globals"
                )
            wave = svc._pack_refill(lead)
            sims = svc._init_refill_wave(wave)
        except Exception as e:
            members, seen = [], set()
            if wave is not None:
                for s in wave.slots:
                    e2 = s.entry
                    if s.folded or e2.done.is_set() or id(e2) in seen:
                        continue
                    seen.add(id(e2))
                    members.append(e2)
            else:
                members = [lead]
            if not members:
                warnings.warn(
                    "serve device-sched: late wave error after every "
                    f"member delivered ({type(e).__name__}: {e})",
                    RuntimeWarning,
                )
            else:
                svc._batch_failed(members, e)
            self._update_gauges()
            return
        self.tasks.append(WaveTask(wave, sims, footprint))
        with svc._lock:
            svc._counters["sched_waves_started"] += 1
        self._update_gauges()

    # -- stepping ------------------------------------------------------------

    def _running(self) -> list:
        return [t for t in self.tasks if t.state == WaveTask.RUNNING]

    def _step_one(self) -> None:
        """Advance ONE running wave by one preemption quantum, round-
        robin — between any two quanta the loop returns to the queue,
        so admission/preemption latency is bounded by one quantum."""
        running = self._running()
        if not running:
            return
        task = running[self._rr % len(running)]
        self._rr += 1
        self._step(task)

    def _step(self, task: WaveTask) -> None:
        from cimba_tpu.core.loop import drive_chunks

        import numpy as np

        svc = self.svc
        wave = task.wave
        lead = wave.slots[0].entry
        state = {"n": task.n}
        user_hook = svc._on_chunk
        tel = svc._tel
        rec = tel.spans if tel is not None else None
        src = f"serve.{svc._tel_name}.chunk" if tel is not None else None

        def on_chunk(n):
            state["n"] = n
            if tel is not None:
                tel.tick(src)
                if rec is not None and lead.span_wave is not None:
                    rec.event(lead.trace, "chunk",
                              parent=lead.span_wave, n=n)
            if user_hook is not None:
                user_hook(n)

        every = svc.refill_every

        def on_boundary(n, s):
            if n % every:
                return None
            return svc._refill_boundary(wave, n, s)

        try:
            task.sims = drive_chunks(
                wave.chunk_j, task.sims, poll_every=svc.poll_every,
                on_chunk=on_chunk, on_boundary=on_boundary,
                max_chunks=self.preempt_quantum(), n0=task.n,
            )
            task.n = state["n"]
            # quantum boundary: retire the wave if every lane is dead
            # (the final boundary folds and delivers whatever the last
            # unpolled chunks finished)
            live = np.asarray(wave.live_j(task.sims))
            if not bool(live.any()):
                svc._refill_boundary(wave, -1, task.sims, final=True)
                self._retire(task)
        except Exception as e:
            self._fail_task(task, e)

    def _retire(self, task: WaveTask) -> None:
        self.tasks.remove(task)
        self._drop_ckpt(task)
        self._update_gauges()

    def _fail_task(self, task: WaveTask, exc: Exception) -> None:
        """A wave died mid-quantum: remove it and fail its undelivered
        members (the ``_serve_refill_wave`` containment, per-task)."""
        self.tasks.remove(task)
        self._drop_ckpt(task)
        members, seen = [], set()
        for s in task.wave.slots:
            e2 = s.entry
            if s.folded or e2.done.is_set() or id(e2) in seen:
                continue
            seen.add(id(e2))
            members.append(e2)
        if not members:
            warnings.warn(
                "serve device-sched: late wave error after every "
                f"member delivered ({type(exc).__name__}: {exc})",
                RuntimeWarning,
            )
        else:
            self.svc._batch_failed(members, exc)
        self._update_gauges()

    # -- preemption ----------------------------------------------------------

    def _preempt(self, task: WaveTask) -> None:
        """Checkpoint-evict ``task`` at the current quantum boundary:
        ``save_resumable`` the wave's Sim pytree (+ its absolute chunk
        counter as ``progress``), capture the aval template restore
        rebuilds against, then delete the device buffers.  The wave's
        HOST state — ownership slots, free-lane pool, accumulated
        per-request folds — rides the ``_RefillWave``/entries
        untouched, which is exactly why retirements and mid-wave
        deliveries resume unperturbed after restore."""
        import jax
        import numpy as np

        from cimba_tpu.runner import checkpoint as _ck

        svc = self.svc
        wave = task.wave
        path = os.path.join(
            self._ckpt_dir(), f"wave-{wave.batch_no}.ckpt"
        )
        task.template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype),
            task.sims,
        )
        _ck.save_resumable(
            path, task.sims, progress=task.n,
            tag=f"devsched:{wave.batch_no}",
        )
        for leaf in jax.tree.leaves(task.sims):
            try:
                leaf.delete()
            except (RuntimeError, AttributeError):
                pass  # already-donated / non-Array leaf: GC takes it
        task.sims = None
        task.ckpt_path = path
        task.state = WaveTask.PREEMPTED
        with svc._lock:
            svc._counters["preemptions"] += 1
            svc._counters["evictions"] += 1
        rec = svc._tel.spans if svc._tel is not None else None
        if rec is not None:
            for s in wave.slots:
                e = s.entry
                if s.folded or e.done.is_set() or e.trace is None:
                    continue
                rec.event(e.trace, "preempt", parent=e.span_wave,
                          boundary=task.n, batch=wave.batch_no)
        self._update_gauges()

    def _maybe_restore(self) -> None:
        """Restore the most-urgent preempted wave when a slot AND
        budget free up — priority order (max live-member priority),
        NOT eviction order: an urgent wave preempted under earlier
        pressure must come back before a background wave that merely
        got evicted first.  Equal priority breaks by EDF — the wave
        whose earliest live-member ``deadline_at`` expires first
        restores first (deadline-aware restore, docs/27_qos.md; waves
        with no deadlines sort last) — then deterministically by
        ``fmix64(batch_no)`` (the obs/audit.py host mixer — arbitrary
        but stable, so equal-priority restore order is reproducible
        and owes nothing to list position).  With NO running wave the
        pick restores unconditionally (it fit when admitted; holding
        it back could deadlock the device idle)."""
        from cimba_tpu.obs.audit import _fmix64_host

        running = self._running()
        if len(running) >= self.waves_per_device():
            return
        preempted = [
            t for t in self.tasks if t.state == WaveTask.PREEMPTED
        ]
        if not preempted:
            return
        task = max(
            preempted,
            key=lambda t: (
                t.priority(), -t.earliest_deadline(),
                _fmix64_host(t.wave.batch_no),
            ),
        )
        if running:
            used = sum(t.footprint for t in running)
            if used + task.footprint > self.budget_bytes():
                return
        self._restore(task)

    def _restore(self, task: WaveTask) -> None:
        from cimba_tpu.runner import checkpoint as _ck

        svc = self.svc
        wave = task.wave
        sims, progress = _ck.restore_resumable(
            task.ckpt_path, task.template,
            tag=f"devsched:{wave.batch_no}",
        )
        task.sims = sims
        task.n = int(progress)
        task.state = WaveTask.RUNNING
        task.template = None
        self._drop_ckpt(task)
        with svc._lock:
            svc._counters["restores"] += 1
        rec = svc._tel.spans if svc._tel is not None else None
        if rec is not None:
            for s in wave.slots:
                e = s.entry
                if s.folded or e.done.is_set() or e.trace is None:
                    continue
                rec.event(e.trace, "restore", parent=e.span_wave,
                          boundary=task.n, batch=wave.batch_no)
        self._update_gauges()

    # -- bookkeeping ---------------------------------------------------------

    def _update_gauges(self) -> None:
        """Refresh the scrapeable aggregates after any wave-set change
        (the boundary controller writes per-wave ``_free_lanes``; with
        several live waves the scheduler owns the AGGREGATE)."""
        svc = self.svc
        running = self._running()
        used = sum(t.footprint for t in running)
        with svc._lock:
            svc._free_lanes = sum(
                len(t.wave.free) for t in running
            )
            svc._waves_live = len(running)
            svc._est_free_mem = max(self.budget_bytes() - used, 0)

    def _ckpt_dir(self) -> str:
        if self._ckpt_root is None:
            import tempfile

            self._ckpt_root = tempfile.mkdtemp(
                prefix="cimba-devsched-"
            )
        return self._ckpt_root

    def _drop_ckpt(self, task: WaveTask) -> None:
        if task.ckpt_path is not None:
            try:
                os.unlink(task.ckpt_path)
            except OSError:
                pass
            task.ckpt_path = None

    def _cleanup_ckpt_root(self) -> None:
        if self._ckpt_root is not None:
            import shutil

            shutil.rmtree(self._ckpt_root, ignore_errors=True)
            self._ckpt_root = None
