"""The experiment service: many clients multiplexed onto shared waves.

PR 3 built the primitive a service needs — one compiled chunk program
that waves of lanes stream through (`runner.run_experiment_stream`) —
but every entry point was a blocking, single-caller function.  This
module multiplexes many concurrent experiment requests onto those
already-warm programs:

* **One device-owner dispatcher thread** (cimba's one-event-loop-per-
  worker discipline, transposed: the DEVICE is the scarce resource, so
  exactly one thread builds batches and dispatches programs; client
  threads only enqueue and wait on futures).
* **Compatibility-class packing** (docs/14_wave_packing.md): queued
  requests of the same *compatibility class* — the spec's structural
  fingerprint, dtype profile, metrics/trace flags, mesh
  (`serve.cache.program_class_key`), the params tree
  signature, and the horizon bucket — are packed into ONE wave of the
  shared compiled chunk program, and the pooled results are sliced
  back per request.  Seed, parameter VALUES, R, priority, horizon
  value, and chunk budget are per-lane data (or trajectory-invariant),
  so requests differing only in them pack unconditionally: each lane
  carries its own seed and `t_stop` column, a short-horizon lane goes
  dead early inside a longer wave (exact truncation via the chunked
  driver's `any_live` early-exit), and partially-filled waves are
  padded to a quantized shape with dead masked lanes (`t_stop=-inf`)
  that are bitwise-inert for the live lanes.  The class is
  definitionally a prefix of the compiled-program key, so packing can
  never mix trajectories that belong to different programs.
* **Bitwise request isolation**: lanes are independent under `vmap`
  (the masking/donation contract of docs/12), so a request packed with
  strangers produces results bitwise equal to the direct
  `run_experiment_stream` call with the same `wave_size` — the slot
  partition `n = min(wave_size, R - lo)` reproduces the direct call's
  wave partition, each slot's slice folds through the SAME jitted fold
  program, and the accumulator starts from the same zeros
  (`tests/test_serve.py` pins this with concurrent mixed clients).

Around the dispatcher: admission control with a bounded queue and
blocking backpressure, per-request deadlines and cancellation, and
retry-with-exponential-backoff on dispatch failure that never stalls
the queue (failed requests back off in a delay heap while the
dispatcher keeps serving; see `serve.sched`).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Optional

from cimba_tpu.obs.telemetry import span as _span
from cimba_tpu.serve import cache as _pcache
from cimba_tpu.serve.sched import (
    AdmissionQueue,
    Backoff,
    Cancelled,
    DeadlineExceeded,
    QueueFull,
    RetriesExhausted,
    RetryAfter,
    ServeError,
    ServiceClosed,
)

__all__ = [
    "Request", "ResultHandle", "Service",
    "request_class_key", "horizon_bucket_of",
    "ServeError", "QueueFull", "ServiceClosed", "Cancelled",
    "DeadlineExceeded", "RetriesExhausted", "Backoff",
]


def _default_summary_path():
    from cimba_tpu.runner import experiment as ex

    return ex.default_summary_path


def horizon_bucket_of(t_end, horizon_bucket) -> object:
    """Which horizon bucket a ``t_end`` falls into — the Tier-B packing
    ladder (docs/14_wave_packing.md).  Module-level: the fleet router
    (docs/20_fleet.md) co-locates requests by the SAME class definition
    the dispatcher packs by, so the two can never drift.  Truncation is
    per-lane-exact regardless of who shares the wave; bucketing is
    purely the LATENCY policy bounding how much longer than its own
    horizon a request's wave may run."""
    if t_end is None:
        return "inf"
    t = float(t_end)
    if not t > 0.0:
        return "nonpos"
    if horizon_bucket is None:
        return "finite"
    import math

    return math.floor(math.log(t) / math.log(horizon_bucket))


def request_class_key(request, with_metrics: bool, *, mesh,
                      horizon_bucket) -> tuple:
    """What may share a wave — the compatibility CLASS of one
    :class:`Request`: the compiled-program class (spec structural
    fingerprint, profile, flags, mesh —
    ``serve.cache.program_class_key``), the params tree signature
    (slices of both requests' params must concatenate), and the horizon
    bucket.  Seed, param VALUES, R, priority, the exact ``t_end``, and
    ``chunk_steps`` are per-lane data (or trajectory-invariant) and do
    not join the key; ``summary_path`` doesn't either, because each
    request folds its own slice through its own fold program.  ONE
    definition serves both the in-process :class:`Service` packer and
    the fleet router's co-location policy (docs/20_fleet.md)."""
    import jax

    from cimba_tpu.runner import experiment as ex

    pck = _pcache.program_class_key(request.spec, with_metrics, mesh=mesh)
    shapes = jax.eval_shape(
        lambda: ex._slice_params(
            request.params, request.n_replications, 0, 1
        )
    )
    sig = (
        jax.tree.structure(shapes),
        tuple(
            (tuple(l.shape[1:]), str(l.dtype))
            for l in jax.tree.leaves(shapes)
        ),
    )
    return (pck, sig, horizon_bucket_of(request.t_end, horizon_bucket))


def fusion_class_key(request, with_metrics: bool, *, cache, mesh,
                     horizon_bucket) -> tuple:
    """The SECOND rung of the class ladder — what may share a **fused**
    wave (docs/26_wave_fusion.md): the spec's structural-geometry key
    with the model identity erased (``core.fuse.fusion_shape_key``),
    the full one-lane Sim STRUCTURE signature (user state, metrics and
    trace leaves, dtypes — so ``lax.switch`` branch structures can
    never mismatch), the params-row signature, the resolved trace-time
    globals, the mesh, and the horizon bucket.  Everything the exact
    class treats as per-lane data stays per-lane data here; what the
    exact class keys by *model identity* this key keys by model
    *shape*.  Raises :class:`cimba_tpu.core.fuse.FusionError` for
    structurally unfusable specs (spawn pools, boundary blocks) —
    callers treat that as "exact class only"."""
    from cimba_tpu import config as _config
    from cimba_tpu.core import fuse as _fuse
    from cimba_tpu.obs import trace as _trace

    shape = _fuse.fusion_shape_key(request.spec)
    sim_sig = _pcache.sim_structure_sig(
        cache, request.spec, request.params, request.n_replications,
        with_metrics, mesh=mesh,
    )
    psig = _pcache._params_sig(request.params, request.n_replications)
    return (
        shape, sim_sig, psig,
        _config.active_profile(), bool(with_metrics),
        _trace.enabled(),
        mesh, horizon_bucket_of(request.t_end, horizon_bucket),
    )


@dataclass
class Request:
    """One experiment request — the arguments of a direct
    :func:`cimba_tpu.runner.experiment.run_experiment_stream` call,
    plus serving policy (priority, deadline, label).

    ``wave_size=None`` uses the service's ``max_wave``; either way the
    effective wave size defines the request's slot partition, and the
    result is bitwise the direct call's at that same ``wave_size``.
    ``deadline`` is seconds from submission, checked at every dispatch
    boundary: a request whose deadline has expired when the dispatcher
    reaches it (initially or between its waves) fails with
    :class:`DeadlineExceeded`; work already running on the device is
    never interrupted — a deadline expiring mid-wave delivers that
    wave, then fails before the next.

    ``expect_digest`` (docs/18_audit.md): the result digest this
    request is EXPECTED to reproduce (e.g. from a stored run card,
    :func:`cimba_tpu.obs.audit.stream_result_digest`).  The result is
    delivered either way, but a mismatch bumps the service's
    ``digest_mismatches`` counter, marks the request's span tree, and
    flips ``/healthz`` to degraded — determinism regressions surface
    in the fleet's monitoring, not just in pytest.

    ``chunk_steps=None`` (the default) resolves
    through the tuned-schedule registry at submit time
    (docs/21_autotune.md): with ``CIMBA_TUNE`` on and the service's
    program store carrying a searched winner for this (spec, backend,
    workload bucket), the winner's argument knobs fill in; otherwise
    the historical default (``chunk_steps=1024``) runs unchanged.
    Explicit values always win, and the resolution source
    (tuned/default/override) surfaces per class in
    ``Service.stats()["schedule"]`` and ``/varz``."""

    spec: Any
    params: Any
    n_replications: int
    seed: int = 0
    t_end: Optional[float] = None
    chunk_steps: Optional[int] = None
    wave_size: Optional[int] = None
    summary_path: Optional[Callable] = None
    priority: int = 0
    deadline: Optional[float] = None
    label: Optional[str] = None
    expect_digest: Optional[str] = None
    # cross-process trace grafting (docs/23_fleet_observability.md):
    # ``{"id": <remote trace id>, "parent": <remote span id>}`` — the
    # fleet slice fills this from the wire header so the request's span
    # tree grows under the router's, instead of starting a new trace.
    # None (the default) means a locally-rooted trace; ignored when the
    # service has no telemetry plane.  Never part of the class key.
    trace_context: Optional[dict] = None
    # multi-tenant QoS (docs/27_qos.md): who this request belongs to.
    # None = the registry's default tenant — exactly today's behavior.
    # Admission POLICY only (fair lane shares, quotas, rate limits,
    # deadline-class defaults): the tenant id is NEVER part of the
    # program/compatibility class key — two tenants' identical requests
    # share one compiled program, one wave, one bitwise digest.
    tenant: Optional[str] = None

    def __post_init__(self):
        if self.summary_path is None:
            self.summary_path = _default_summary_path()


class _Entry:
    """Dispatcher-internal per-request state (the queue stores these)."""

    __slots__ = (
        "request", "seq", "priority", "label", "cls", "eff_wave",
        "with_metrics", "next_lo", "acc", "n_waves", "retries", "solo",
        "cancelled", "in_flight", "submit_t", "first_dispatch_t",
        "deadline_at", "done", "result", "exc", "result_digest",
        "finish_t",
        "trace", "span_root", "span_queue", "span_wave",
        "fuse_cls", "spec_fp", "tenant",
    )

    def __init__(self, request, seq, cls, eff_wave, with_metrics):
        self.request = request
        self.seq = seq
        self.priority = request.priority
        self.label = request.label
        self.cls = cls
        self.eff_wave = eff_wave
        self.with_metrics = with_metrics
        self.next_lo = 0
        self.acc = None
        self.n_waves = 0
        self.retries = 0
        self.solo = False          # excluded from packing (retry isolation)
        self.cancelled = False
        self.in_flight = False
        self.submit_t = time.monotonic()
        self.first_dispatch_t = None
        self.deadline_at = (
            None if request.deadline is None
            else self.submit_t + request.deadline
        )
        self.done = threading.Event()
        self.result = None
        self.exc = None
        self.result_digest = None
        self.finish_t = None       # monotonic stamp set by _finish
        # telemetry span state — all None when the service has no
        # telemetry plane (the zero-allocation hot-submit contract)
        self.trace = None
        self.span_root = None
        self.span_queue = None
        self.span_wave = None
        # wave fusion (docs/26_wave_fusion.md): the fusion-class key
        # and the spec's in-memory fingerprint — both None unless the
        # service has fusion on AND the spec is fusable AND it joined
        # the class's member roster at submit
        self.fuse_cls = None
        self.spec_fp = None
        # resolved tenant id (docs/27_qos.md) — stamped at submit from
        # the service's registry (None request.tenant -> "default")
        self.tenant = None


class ResultHandle:
    """The future a :meth:`Service.submit` returns."""

    def __init__(self, service: "Service", entry: _Entry):
        self._service = service
        self._entry = entry

    @property
    def label(self) -> Optional[str]:
        return self._entry.label

    def done(self) -> bool:
        return self._entry.done.is_set()

    @property
    def finish_t(self) -> Optional[float]:
        """``time.monotonic()`` stamp of the moment the dispatcher
        retired this request (None while still in flight).  Load
        drivers pair it with their own monotonic submit stamp to get
        DELIVERY latency — a future collected long after it resolved
        must not read as slow (docs/27_qos.md measures per-tenant
        tails this way)."""
        return self._entry.finish_t

    def cancel(self) -> bool:
        """Cancel if still undispatched; returns False once any slot is
        in flight or the request already completed."""
        return self._service._cancel(self._entry)

    def exception(self, timeout: Optional[float] = None):
        if not self._entry.done.wait(timeout):
            raise TimeoutError(
                f"request {self._entry.label or self._entry.seq} not "
                f"done within {timeout}s"
            )
        return self._entry.exc

    def result(self, timeout: Optional[float] = None):
        """Block for the request's ``StreamResult`` (raises the
        structured serving error on failure)."""
        exc = self.exception(timeout)
        if exc is not None:
            raise exc
        return self._entry.result

    def digest(self, timeout: Optional[float] = None) -> str:
        """The completed result's bitwise digest
        (:func:`cimba_tpu.obs.audit.stream_result_digest`) — equal to
        the digest of the direct ``run_experiment_stream`` call at the
        same (spec, params, R, seed, wave_size), whoever shared the
        wave (the bitwise-isolation contract, docs/18_audit.md).
        Blocks like :meth:`result`; computed once and cached (the
        dispatcher already computed it when spans or ``expect_digest``
        were active)."""
        res = self.result(timeout)
        if self._entry.result_digest is None:
            from cimba_tpu.obs import audit as _audit

            self._entry.result_digest = _audit.stream_result_digest(res)
        return self._entry.result_digest


#: outcomes recorded in stats and trace spans
_OUTCOMES = ("completed", "failed", "cancelled", "deadline_exceeded")

#: refill-plane counters (docs/22_refill.md) — grouped in
#: ``stats()["refill"]`` and mirrored as ``cimba_serve_refill_*``
#: telemetry families
_REFILL_COUNTERS = (
    "refill_boundaries", "refill_admissions", "refill_retirements",
    "lanes_refilled", "lanes_reclaimed", "mid_wave_deliveries",
)

_DEVSCHED_COUNTERS = (
    "preemptions", "evictions", "restores", "sched_waves_started",
    "mem_rejects",
)

#: wave-fusion counters (docs/26_wave_fusion.md) — grouped in
#: ``stats()["fusion"]``: batches/waves actually dispatched through a
#: fused superprogram, the lanes they carried, and submits whose spec
#: could not join a fusion class (unfusable structure or a full roster)
_FUSION_COUNTERS = (
    "fused_batches", "fused_waves", "fused_lanes", "fusion_rejects",
)

#: per-tenant QoS counters (docs/27_qos.md) — grouped per tenant in
#: ``stats()["qos"]["tenants"]`` and mirrored as tenant-labeled
#: ``cimba_serve_qos_*`` telemetry families.  ``throttled`` splits by
#: reason (``throttled_rate`` + ``throttled_quota``); outcome counters
#: mirror the service-level ``_OUTCOMES`` names so per-tenant goodput
#: is ``completed / submitted`` with no new vocabulary.
_QOS_TENANT_COUNTERS = (
    "submitted", "admitted", "throttled", "throttled_rate",
    "throttled_quota", "completed", "failed", "cancelled",
    "deadline_exceeded", "claims", "lanes_claimed",
)


class _RefillSlot:
    """One request slot's lane ownership inside a refill-driven wave:
    the request entry, its replication window ``[lo, lo+n)``, and the
    wave lane indices it owns (ascending — lane order IS replication
    order, so the retirement fold gathers rows in exactly the order
    the direct call's contiguous wave slice has them)."""

    __slots__ = ("entry", "lo", "n", "lanes", "folded")

    def __init__(self, entry, lo, n):
        self.entry = entry
        self.lo = lo
        self.n = n
        self.lanes = []
        self.folded = False


class _RefillWave:
    """One refill-driven wave's bookkeeping: the ownership table
    (slots), the reclaimable free-lane pool (pad lanes at birth, plus
    every retired/killed slot's lanes), and the compiled programs the
    boundary controller dispatches (docs/22_refill.md)."""

    __slots__ = (
        "cls", "slots", "free", "L", "batch_no", "no_admit",
        "init_j", "chunk_j", "refill_j", "live_j", "pad_row",
        "fused", "sid_of",
    )

    def __init__(self, cls, no_admit):
        self.cls = cls
        self.slots = []
        self.free = []
        self.L = 0
        self.batch_no = 0
        self.no_admit = no_admit
        self.init_j = None
        self.chunk_j = None
        self.refill_j = None
        self.live_j = None
        self.pad_row = None
        # wave fusion (docs/26_wave_fusion.md): the FusedSpec bundle
        # this wave was born with (None = an ordinary single-spec
        # wave) and the member-fingerprint -> spec_id map the boundary
        # controller admits against.  The member set is FIXED at
        # birth: only specs in ``sid_of`` may splice in later (one
        # compiled superprogram per wave — a splice is never a
        # compile), so a roster that grew after birth reaches lanes
        # only through the next wave.
        self.fused = None
        self.sid_of = None


class Service:
    """A thread-based experiment service over one device (or mesh).

    ``max_wave`` bounds the lanes of one packed wave (one dispatch);
    ``max_pending`` bounds the admission queue (backpressure past it);
    ``cache`` is the shared :class:`~cimba_tpu.serve.cache.ProgramCache`
    (one is created if not given — pass your own to share warm programs
    with direct `run_experiment_stream` calls or across services);
    ``max_retries``/``backoff`` govern dispatch-failure retries;
    ``on_chunk`` is a per-chunk progress hook (a caller's watchdog
    heartbeat).  Use as a context manager for a graceful shutdown.

    Packing policy knobs (docs/14_wave_packing.md):

    * ``pad_waves`` (default True): pad each packed wave's lane count
      up to a quantized shape — the next power-of-two multiple of the
      mesh device count, capped at ``max_wave`` — with dead masked
      lanes (``t_stop=-inf``; bitwise-inert for live lanes), so mixed
      traffic cycles a handful of compiled wave shapes instead of one
      compile per distinct fill level.  Padding waste is observable in
      ``stats()["lane_occupancy"]``.
    * ``horizon_bucket`` (default 16.0): requests pack only within a
      horizon bucket — finite ``t_end`` values bucket by
      ``floor(log(t_end)/log(horizon_bucket))`` and ``t_end=None``
      (run-to-completion) is its own bucket — bounding how long a
      short request can be held hostage by a long wave-mate to one
      bucket ratio.  ``None`` packs ALL finite horizons together
      (truncation stays exact either way; this is purely a latency
      policy).

    * ``refill`` (default None → the ``CIMBA_REFILL`` env knob, unset
      = off): continuous wave refill (docs/22_refill.md) — at every
      chunk boundary the dispatcher retires lanes whose owning request
      completed (folding and delivering THAT request immediately, not
      at whole-wave retirement), reclaims the lanes of cancelled /
      deadline-expired requests, and splices queued compatible
      requests into the freed lanes through a jitted, donated refill
      program — steady-state lane occupancy stays near-flat under
      mixed-horizon open-loop traffic instead of decaying over each
      wave's life, with zero recompiles after warmup.  Results stay
      bitwise the direct call's (lanes are independent; the splice is
      a masked per-lane re-init through the same init path).  Off,
      requests dispatch exactly as before — compiled chunk programs,
      packing, and results are identical; the only addition is the
      per-boundary liveness readback feeding the live occupancy gauge
      (service-local, never the shared program cache).

    * ``device_sched`` (default None → the ``CIMBA_DEVICE_SCHED`` env
      knob, unset = off): the preemptive device scheduler
      (docs/24_device_scheduler.md) — the dispatcher interleaves up to
      ``waves_per_device`` concurrent refill waves round-robin, one
      ``preempt_quantum`` of chunks each per turn; a new wave admits
      only when its estimated footprint fits the memory budget
      (``mem_budget_bytes``, default ``mem_fraction`` x device memory;
      a request that could NEVER fit fails fast with structured
      :class:`~cimba_tpu.serve.sched.MemoryBudgetExceeded`); and an
      urgent request may checkpoint-evict a strictly lower-priority
      wave at a quantum boundary and restore it bit-identically later
      (the PR 3 resumable-checkpoint path).  The three policy knobs
      left None resolve from a tuned schedule at submit time, else the
      ``tune.space`` defaults.  Off, dispatch is byte-identical to the
      refill/plain paths (the 'device_sched' trace gate pins this).

    * ``qos`` (default None → the ``CIMBA_QOS`` env knob, unset =
      off): the multi-tenant QoS plane (docs/27_qos.md) — freed refill
      lanes apportion across tenants by deficit-weighted round robin
      (``tenants``: a :class:`cimba_tpu.qos.TenantRegistry` of per-
      tenant weight / lane quota / rate limit / deadline class),
      equal-priority requests within a class order by earliest
      deadline (EDF), and a tenant past its quota or rate gets
      structured :class:`~cimba_tpu.serve.sched.RetryAfter` at submit
      instead of queueing.  Host-side admission POLICY only: the
      tenant never joins the class key, compiled programs are
      byte-identical either way (the 'qos' trace gate pins this), and
      every delivered result stays bitwise its direct solo call
      regardless of the admission order QoS chooses.  Off, admission
      is the historical priority-order prefix, byte for byte.

    ``telemetry`` (default None) attaches a
    :class:`cimba_tpu.obs.telemetry.Telemetry` plane: the background
    sampler scrapes :meth:`stats` into the time-series registry, the
    dispatcher loop heartbeats for ``/healthz`` liveness, request
    latencies feed the log2 histograms, and — with spans enabled — a
    ``trace_id`` minted at :meth:`submit` threads through
    admit → queue → pack → wave → chunk → fold → deliver as a JSONL
    span log (docs/17_telemetry.md).  None is strictly zero-cost: no
    threads, no span allocations, compiled programs untouched."""

    # cimba-check: must-hold(_lock) _counters, _outstanding, _seq, _closed, _stop, _occupancy, _class_ids, _spans, _depth_samples, _ttfw_sum, _ttfw_max, _ttfw_n, _sched_sources, _schedules, _occ_samples, _waves_live, _est_free_mem, _waves_per_device, _preempt_quantum, _mem_fraction, _mem_budget_bytes, _fuse_roster, _fuse_max_specs, _qos_lanes_held, _qos_tenant_counters, _qos_log, _qos_lat

    def __init__(
        self,
        *,
        max_wave: int = 4096,
        max_pending: int = 64,
        mesh=None,
        cache=None,
        max_retries: int = 2,
        backoff: Backoff = Backoff(),
        poll_every: int = 4,
        on_chunk: Optional[Callable] = None,
        trace_cap: int = 4096,
        pad_waves: bool = True,
        horizon_bucket: Optional[float] = 16.0,
        telemetry=None,
        refill: Optional[bool] = None,
        refill_every: Optional[int] = None,
        fuse: Optional[bool] = None,
        fuse_max_specs: Optional[int] = None,
        device_sched: Optional[bool] = None,
        waves_per_device: Optional[int] = None,
        preempt_quantum: Optional[int] = None,
        mem_fraction: Optional[float] = None,
        mem_budget_bytes: Optional[int] = None,
        qos: Optional[bool] = None,
        tenants=None,
        qos_clock: Optional[Callable[[], float]] = None,
        name: str = "cimba-serve",
    ):
        from cimba_tpu import config as _config

        if max_wave <= 0:
            raise ValueError(f"max_wave must be positive: {max_wave}")
        self.max_wave = int(max_wave)
        self.name = name
        self.mesh = mesh
        self.poll_every = poll_every
        # continuous wave refill (docs/22_refill.md): None defers to
        # the CIMBA_REFILL env knob (unset = off — the historical
        # dispatch path plus only the occupancy readback).  A
        # host-side dispatch policy only: compiled
        # chunk programs are identical either way (the 'refill' trace
        # gate pins this), and the refill/liveness programs live at
        # their own cache keys.
        self.refill = (
            _config.env_raw("CIMBA_REFILL") == "1" if refill is None
            else bool(refill)
        )
        # boundary-controller cadence: the controller's per-lane
        # liveness readback is a HOST SYNC (it must act on concrete
        # lane deaths), so running it every chunk would serialize the
        # async dispatch pipeline drive_chunks builds.  Every
        # ``refill_every`` chunks (default: poll_every — the same
        # depth the liveness poll already pipelines at) keeps the
        # pipeline full between control points; retirement/admission
        # latency is bounded by refill_every chunks.
        self.refill_every = max(
            int(poll_every if refill_every is None else refill_every), 1
        )
        # cross-spec wave fusion (docs/26_wave_fusion.md): None defers
        # to the CIMBA_WAVE_FUSE env knob (unset = off — the historical
        # one-spec-per-wave packer, byte for byte; the 'wave_fuse'
        # trace gate pins this).  A host-side dispatch policy like
        # refill/device_sched: ON, cross-spec requests of one fusion
        # class share a compiled superprogram whose per-lane spec-id
        # column switches each lane through its own model's blocks.
        # ``fuse_max_specs`` left None adopts a tuned schedule's value
        # at submit time, else tune.space.DEFAULT_FUSE_MAX_SPECS.
        self._fuse_unset = (
            fuse is None and _config.env_raw("CIMBA_WAVE_FUSE") == ""
        )
        self.fuse = (
            _config.env_raw("CIMBA_WAVE_FUSE") == "1" if fuse is None
            else bool(fuse)
        )
        self._fuse_max_specs = (
            None if fuse_max_specs is None else int(fuse_max_specs)
        )
        if self._fuse_max_specs is not None and self._fuse_max_specs < 2:
            raise ValueError(
                f"fuse_max_specs must be >= 2 (a fusion needs two "
                f"members to exist): {fuse_max_specs}"
            )
        # the fusion rosters: fusion-class key -> {spec fingerprint:
        # spec}, insertion-ordered, capped at the effective
        # fuse_max_specs.  The roster BINDS AT FIRST SIGHT: the first
        # fuse_max_specs distinct specs of a class are its members for
        # the service's life, so every fused wave of the class runs the
        # SAME superprogram (stable bundle -> zero steady-state
        # compiles); later distinct specs serve unfused.  Guarded by
        # the service lock.
        self._fuse_roster: dict = {}
        # the preemptive device scheduler (docs/24_device_scheduler.md):
        # None defers to the CIMBA_DEVICE_SCHED env knob (unset = off).
        # On, the dispatcher thread delegates to
        # serve.device.DeviceScheduler — concurrent refill waves per
        # device with memory-aware admission and checkpoint-evict-
        # restore preemption.  Host-side dispatch policy only, like
        # refill: compiled programs are byte-identical either way (the
        # 'device_sched' trace gate pins this).  The three policy knobs
        # stay None here when unset so a tuned schedule can adopt them
        # at submit time (_adopt_sched_knobs); effective defaults live
        # in tune.space (DEFAULT_WAVES_PER_DEVICE & co).
        self.device_sched = (
            _config.env_raw("CIMBA_DEVICE_SCHED") == "1"
            if device_sched is None else bool(device_sched)
        )
        self._waves_per_device = (
            None if waves_per_device is None else int(waves_per_device)
        )
        self._preempt_quantum = (
            None if preempt_quantum is None else int(preempt_quantum)
        )
        self._mem_fraction = (
            None if mem_fraction is None else float(mem_fraction)
        )
        self._mem_budget_bytes = (
            None if mem_budget_bytes is None else int(mem_budget_bytes)
        )
        if self._waves_per_device is not None \
                and self._waves_per_device <= 0:
            raise ValueError(
                f"waves_per_device must be positive: {waves_per_device}"
            )
        if self._mem_fraction is not None \
                and not 0.0 < self._mem_fraction <= 1.0:
            raise ValueError(
                f"mem_fraction must be in (0, 1]: {mem_fraction}"
            )
        # the multi-tenant QoS plane (docs/27_qos.md): None defers to
        # the CIMBA_QOS env knob (unset = off — admission is the PR 15
        # priority-order prefix, byte for byte; the 'qos' trace gate
        # pins ambient inertness).  On, freed refill lanes apportion
        # across tenants by deficit-weighted round robin, equal-
        # priority requests order by earliest deadline, and per-tenant
        # quotas/rate limits throttle at submit with structured
        # RetryAfter.  HOST-side admission policy only: the tenant id
        # never joins the class key, and delivered results stay bitwise
        # their direct solo calls regardless of admission order.
        # ``tenants`` is a qos.TenantRegistry (one is created if not
        # given — every tenant then runs the unlimited default policy,
        # fairly weighted); ``qos_clock`` injects the rate-limiter
        # clock (replay-determinism tests pin throttle logs under a
        # logical clock; production uses time.monotonic).
        from cimba_tpu.qos import (
            AdmissionLimiter as _QosLimiter,
            FairScheduler as _QosSched,
            TenantRegistry as _TenantRegistry,
        )

        self.qos = (
            _config.env_raw("CIMBA_QOS") == "1" if qos is None
            else bool(qos)
        )
        self._tenants = (
            tenants if tenants is not None else _TenantRegistry()
        )
        # DRR deficits: dispatcher-thread only (inside the queue's
        # take_selected lock) — needs no service lock
        self._qos_sched = _QosSched(self._tenants)
        self._qos_limiter = _QosLimiter(
            self._tenants,
            clock=time.monotonic if qos_clock is None else qos_clock,
        )
        self._qos_lanes_held: dict = {}      # tenant -> lanes in flight
        self._qos_tenant_counters: dict = {}  # tenant -> counter dict
        # the admission log the replay-determinism contract pins
        # (docs/27_qos.md): ("claim", tenant, seq, lanes) per fair-claim
        # admission and ("throttle", tenant, seq, lanes, reason) per
        # submit-time RetryAfter, in decision order
        self._qos_log = deque(maxlen=4096)
        # per-tenant completed-request latency window: what feeds the
        # stats()/telemetry p99 gauge — the victim-tail signal a QoS
        # dashboard watches under a flooding tenant
        self._qos_lat: dict = {}             # tenant -> deque[float]
        self.max_retries = int(max_retries)
        self.backoff = backoff
        self.cache = cache if cache is not None else _pcache.ProgramCache()
        self.pad_waves = bool(pad_waves)
        if horizon_bucket is not None and not horizon_bucket > 1.0:
            raise ValueError(
                f"horizon_bucket must be > 1 (a ratio), got "
                f"{horizon_bucket}"
            )
        self.horizon_bucket = horizon_bucket
        self._on_chunk = on_chunk
        self._queue = AdmissionQueue(max_pending)
        self._lock = threading.RLock()
        self._drained = threading.Condition(self._lock)
        self._outstanding = 0
        self._seq = 0
        self._closed = False
        self._stop = False
        self._t0 = time.monotonic()
        self._spans = deque(maxlen=trace_cap)
        self._depth_samples = deque(maxlen=trace_cap)
        self._counters = {
            "submitted": 0, "admitted": 0, "rejected": 0,
            "throttled": 0,
            "retries": 0, "batches": 0, "waves": 0,
            "lanes_dispatched": 0, "lanes_padded": 0,
            "digest_mismatches": 0,
        }
        for o in _OUTCOMES:
            self._counters[o] = 0
        for o in _REFILL_COUNTERS:
            self._counters[o] = 0
        for o in _DEVSCHED_COUNTERS:
            self._counters[o] = 0
        for o in _FUSION_COUNTERS:
            self._counters[o] = 0
        # per-chunk live-lane occupancy samples: (live, lanes_in_wave)
        # pairs appended at every chunk boundary — ``live`` is a host
        # int on the refill path (the boundary controller already
        # synced it) and a DEVICE [L] bool vector on the plain path
        # (the readback dispatch stays asynchronous; stats() converts
        # at scrape time).  This is what keeps
        # ``stats()["lane_occupancy"]`` live over a wave's life instead
        # of frozen at pack time (docs/22_refill.md).
        self._occ_samples = deque(maxlen=256)
        # free lanes in the in-flight refill wave RIGHT NOW — the
        # admission-headroom signal capacity-aware fleet placement
        # scrapes (docs/23_fleet_observability.md); 0 whenever no
        # refill wave is in flight (plain waves have no free pool)
        self._free_lanes = 0
        # device-scheduler aggregates (docs/24_device_scheduler.md):
        # live RUNNING waves and the estimated free device memory under
        # the admission budget — written by DeviceScheduler after every
        # wave-set change, scraped by stats()/fleet health
        self._waves_live = 0
        self._est_free_mem: Optional[int] = None
        # plain-path liveness-readback programs, per compatibility
        # class (dispatcher-thread only — see _run_batch)
        self._live_cache: dict = {}
        self._occupancy: dict = {}       # requests-per-batch -> count
        self._class_ids: dict = {}       # class key -> short label
        # tuned-schedule resolution accounting (docs/21_autotune.md)
        self._sched_sources = {
            "tuned": 0, "default": 0, "override": 0, "off": 0,
        }
        self._schedules: dict = {}       # class label -> resolved block
        self._ttfw_sum = 0.0
        self._ttfw_max = 0.0
        self._ttfw_n = 0
        # the host-side telemetry plane (docs/17_telemetry.md) — None
        # (the default) means zero overhead: no sampler thread, no span
        # objects on the submit path, nothing new on the dispatch path
        self._tel = telemetry
        self._tel_name = (
            telemetry.attach_service(self, name)
            if telemetry is not None else None
        )
        self._thread = threading.Thread(
            target=self._loop, name=name, daemon=True
        )
        self._thread.start()

    # -- client surface ------------------------------------------------------

    def submit(
        self, request: Request, *, block: bool = True,
        timeout: Optional[float] = None,
    ) -> ResultHandle:
        """Admit a request; returns its future.  ``block=True`` (the
        default) waits for queue space — the backpressure arm;
        ``block=False`` (or a ``timeout`` expiry) raises
        :class:`QueueFull` instead and counts an admission reject."""
        with _span("serve.submit") as sp:
            return self._admit(request, block, timeout, sp)

    def _admit(self, request: Request, block: bool,
               timeout: Optional[float], sp) -> ResultHandle:
        """:meth:`submit`'s body, inside its ``serve.submit`` span
        ``sp``, which it gives the request's seq, its lanes and the
        nanoseconds spent waiting for the service lock."""
        R = int(request.n_replications)
        if R <= 0:
            raise ValueError(f"n_replications must be positive, got {R}")
        eff_wave = min(
            R, self.max_wave if request.wave_size is None
            else int(request.wave_size),
        )
        # tuned-schedule resolution (docs/21_autotune.md): the ARGUMENT
        # knobs left unset resolve against the service's program store
        # at submit time, BEFORE the compatibility class binds — the
        # class must describe the program that will actually dispatch.
        # ``wave_size`` is passed as the already-effective value (a
        # Request's None has always meant "the service's max_wave", an
        # explicit policy, not an unset knob — a tuned wave_size never
        # applies here and never claims the 'tuned' source).  Trace-
        # time knobs — the table-scan layout — are process-level on the
        # serve path: set the CIMBA_TABLE_SCAN* env/config state the
        # tuner recommends; the dispatcher never flips globals under
        # concurrent traffic.  Explicit values always win.
        import dataclasses as _dc

        from cimba_tpu.tune import registry as _tune_reg

        _store = (
            self.cache._store
            if isinstance(self.cache, _pcache.ProgramCache) else None
        )
        rs = _tune_reg.resolve_entry(
            request.spec, R,
            chunk_steps=request.chunk_steps, wave_size=eff_wave,
            store=_store,
        )
        if request.chunk_steps != rs.chunk_steps:
            # normalize a COPY — the caller's Request is never mutated
            request = _dc.replace(
                request, chunk_steps=rs.chunk_steps,
            )
        if eff_wave <= 0:
            raise ValueError(
                f"wave_size must be positive, got {request.wave_size}"
            )
        if eff_wave > self.max_wave:
            raise ValueError(
                f"request wave_size={eff_wave} exceeds the service's "
                f"max_wave={self.max_wave} — it could never be scheduled"
            )
        if self.mesh is not None:
            n_dev = self.mesh.devices.size
            if R % n_dev or eff_wave % n_dev:
                raise ValueError(
                    f"n_replications={R} and wave_size={eff_wave} must "
                    f"divide evenly over {n_dev} devices"
                )
        from cimba_tpu.obs import metrics as _metrics

        with_metrics = _metrics.enabled()
        cls = self._class_key(request, with_metrics)
        # tuned fuse knobs adopt BEFORE the fusion class binds (a
        # schedule flipping fusion on must affect this very request);
        # device-sched knobs keep their historical adoption gate
        if rs.schedule is not None:
            with self._lock:
                if self.device_sched:
                    self._adopt_sched_knobs(rs.schedule)
                self._adopt_fuse_knobs(rs.schedule)
        # the fusion-class key (docs/26_wave_fusion.md) computes OUTSIDE
        # the lock — its Sim-structure signature eval_shapes on a cold
        # cache — and the roster binds under the lock below
        fuse_cls = None
        if self.fuse:
            from cimba_tpu.core import fuse as _fuse_mod

            try:
                fuse_cls = fusion_class_key(
                    request, with_metrics, cache=self.cache,
                    mesh=self.mesh, horizon_bucket=self.horizon_bucket,
                )
            except _fuse_mod.FusionError:
                fuse_cls = None
        asked = time.monotonic_ns()
        with self._lock:
            sp.attrs["lock_wait_ns"] = time.monotonic_ns() - asked
            if self._closed:
                raise ServiceClosed(
                    "service is draining/shut down — no new requests"
                )
            self._counters["submitted"] += 1
            self._seq += 1
            label = self._class_ids.setdefault(
                cls, f"class{len(self._class_ids)}"
            )
            self._sched_sources[rs.source] = (
                self._sched_sources.get(rs.source, 0) + 1
            )
            self._schedules[label] = rs.block()
            entry = _Entry(request, self._seq, cls, eff_wave,
                           with_metrics)
            sp.attrs.update(seq=entry.seq, lanes=R)
            entry.tenant = self._tenants.resolve(request.tenant)
            if self.qos:
                self._qos_tenant(entry.tenant)["submitted"] += 1
                if entry.deadline_at is None:
                    # the tenant's deadline_class stamps a default
                    # deadline on requests that carry none — what the
                    # EDF ordering within a class keys on (docs/27)
                    dc = self._qos_limiter.deadline_for(request.tenant)
                    if dc is not None:
                        entry.deadline_at = entry.submit_t + dc
            if self.fuse:
                self._bind_fusion(entry, fuse_cls)
            self._outstanding += 1
        rec = self._tel.spans if self._tel is not None else None
        if rec is not None:
            # the trace_id minted at submit — threaded through
            # admit → queue → pack → wave → chunk → fold → deliver.
            # The whole tree skeleton (root AND queue span) exists
            # BEFORE the entry is published to the queue: the moment
            # put() returns, the dispatcher may pack, run, and even
            # finish the request, and a span started after that would
            # resurrect the already-ended trace as a permanent leak.
            # cross-process grafting (docs/23_fleet_observability.md):
            # a request arriving over the fleet wire carries the
            # router's trace id + parent span — adopt them so this
            # process's tree hangs under the router's, instead of
            # minting a disconnected local trace
            ctx = request.trace_context
            remote_parent = None
            if ctx is not None and ctx.get("id"):
                remote_parent = (
                    str(ctx["parent"]) if ctx.get("parent") else None
                )
                entry.trace = rec.adopt_trace(
                    str(ctx["id"]), remote_parent
                )
            else:
                entry.trace = rec.new_trace()
            entry.span_root = rec.start(
                entry.trace, "request", parent=remote_parent,
                seq=entry.seq, label=entry.label,
                service=self._tel_name, lanes=R,
            )
            entry.span_queue = rec.start(
                entry.trace, "queue", parent=entry.span_root
            )
        if self.qos:
            # quota/rate admission control (docs/27_qos.md): a tenant
            # past its policy gets structured RetryAfter — never bare
            # QueueFull — naming the tenant, the reason, and a concrete
            # delay; nothing was admitted and the span tree closes
            # exactly once with the 'throttled' outcome, mirroring the
            # reject path below.  Checked under the service lock: the
            # lanes-held read and the token-bucket take must be atomic
            # against concurrent submits.
            try:
                with self._lock:
                    self._qos_limiter.check(
                        request.tenant, R,
                        self._qos_lanes_held.get(entry.tenant, 0),
                        label=entry.label,
                    )
            except RetryAfter as e:
                with self._lock:
                    self._outstanding -= 1
                    self._counters["throttled"] += 1
                    tc = self._qos_tenant(entry.tenant)
                    tc["throttled"] += 1
                    tc["throttled_" + e.reason] += 1
                    self._qos_log.append((
                        "throttle", entry.tenant, int(entry.seq),
                        int(R), e.reason,
                    ))
                    self._drained.notify_all()
                if self._tel is not None:
                    self._tel.observe_request(
                        self._tel_name, "throttled",
                        time.monotonic() - entry.submit_t, None,
                    )
                if rec is not None:
                    rec.end_trace(entry.trace, "throttled")
                raise
        try:
            self._queue.put(entry, block=block, timeout=timeout)
        except (QueueFull, ServiceClosed):
            with self._lock:
                self._outstanding -= 1
                self._counters["rejected"] += 1
                self._drained.notify_all()
            if rec is not None:
                rec.end_trace(entry.trace, "rejected")
            raise
        asked = time.monotonic_ns()
        with self._lock:
            sp.attrs["lock_wait_ns"] += time.monotonic_ns() - asked
            self._counters["admitted"] += 1
            if self.qos:
                self._qos_tenant(entry.tenant)["admitted"] += 1
                self._qos_lanes_held[entry.tenant] = (
                    self._qos_lanes_held.get(entry.tenant, 0) + R
                )
        if rec is not None:
            # instant marker only — safe after put even if the request
            # already completed (events never re-open a trace)
            rec.event(entry.trace, "admit", parent=entry.span_root)
        return ResultHandle(self, entry)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every admitted request has completed (a quiesce
        point; admission stays open).  Returns False on timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while self._outstanding > 0:
                remaining = (
                    None if deadline is None
                    else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    return False
                self._drained.wait(remaining)
            return True

    def shutdown(
        self, wait: bool = True, timeout: Optional[float] = None,
    ) -> None:
        """Stop admitting.  ``wait=True`` drains queued requests first
        (graceful); ``wait=False`` cancels everything still queued.
        Idempotent."""
        with self._lock:
            self._closed = True
        self._queue.close()
        if wait:
            self.drain(timeout)
        else:
            for entry in self._queue.drain_now():
                self._finish(entry, exc=Cancelled(entry.label),
                             outcome="cancelled")
        with self._lock:
            # CHK002: _stop is read by the dispatcher under the lock —
            # an unlocked write here could be reordered past the
            # dispatcher's claim
            self._stop = True
        self._queue.kick()
        self._thread.join(timeout)
        if self._tel is not None:
            # stop being observed: the plane takes a final stats
            # sample, then drops its collector and reference — a
            # long-lived Telemetry over a churn of services must not
            # pin or keep scraping shut-down ones (idempotent)
            self._tel.detach_service(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown(wait=True)

    # -- observability -------------------------------------------------------

    def stats(self) -> dict:
        """Service-level metrics: counters, queue depth (+ high-water,
        and per compatibility class), batch-occupancy histogram
        (requests per packed wave), lane-level occupancy (live vs
        padded lanes — padding waste is observable, not just
        request-count occupancy), time-to-first-wave aggregate, and
        the shared program cache's hit/miss/eviction counters.

        Every value is read under either the service lock or the
        queue's one-acquisition :meth:`AdmissionQueue.snapshot`, so a
        scrape landing mid-dispatch is an atomic snapshot: the
        queue-depth total always equals the sum of its per-class
        breakdown, and the lane/occupancy counters always describe
        waves that were actually recorded together (the torn-read
        audit; tests/test_telemetry.py hammers this under live load).
        The dict IS the telemetry snapshot the background sampler
        scrapes into the ``/metrics`` registry (docs/17_telemetry.md)."""
        with self._lock:
            qs = self._queue.snapshot()
            out = dict(self._counters)
            out["queue_depth"] = qs["depth"]
            out["queue_depth_hwm"] = qs["depth_hwm"]
            out["queue_capacity"] = qs["capacity"]
            # every class ever seen reports, zeros included — a gauge
            # mirrored from this dict must drop to 0 when a class
            # drains, not stick at its last nonzero depth (the same
            # rule _class_sample applies to the chrome counter tracks)
            out["queue_depth_by_class"] = {
                label: qs["by_class"].get(c, 0)
                for c, label in sorted(
                    self._class_ids.items(), key=lambda cl: cl[1],
                )
            }
            out["classes_seen"] = len(self._class_ids)
            out["outstanding"] = self._outstanding
            out["batch_occupancy"] = dict(
                sorted(self._occupancy.items())
            )
            live = self._counters["lanes_dispatched"]
            padded = self._counters["lanes_padded"]
            out["lane_occupancy"] = {
                "lanes_live": live,
                "lanes_padded": padded,
                "padding_waste_frac": (
                    padded / (live + padded) if live + padded else 0.0
                ),
            }
            out["refill"] = {"enabled": self.refill}
            for k in _REFILL_COUNTERS:
                out["refill"][k] = self._counters[k]
            out["refill"]["free_lanes"] = self._free_lanes
            out["device_sched"] = {
                "enabled": self.device_sched,
                "waves_per_device": self._waves_per_device,
                "preempt_quantum": self._preempt_quantum,
                "mem_fraction": self._mem_fraction,
                "mem_budget_bytes": self._mem_budget_bytes,
                "waves_live": self._waves_live,
                "est_free_mem_bytes": self._est_free_mem,
            }
            for k in _DEVSCHED_COUNTERS:
                out["device_sched"][k] = self._counters[k]
            # the fusion rung (docs/26_wave_fusion.md): which fusion
            # classes formed, how full each roster is, and how much
            # traffic actually dispatched fused vs was rejected
            out["fusion"] = {
                "enabled": self.fuse,
                "fuse_max_specs": self._eff_fuse_max(),
                "classes": len(self._fuse_roster),
                "roster_sizes": sorted(
                    len(r) for r in self._fuse_roster.values()
                ),
            }
            for k in _FUSION_COUNTERS:
                out["fusion"][k] = self._counters[k]
            # the QoS plane (docs/27_qos.md): per-tenant counters
            # (goodput = completed/submitted), lanes currently held
            # against quota, the live DRR deficits, and the admission
            # log the replay-determinism contract compares
            qos_tenants = {}
            for t, c in sorted(self._qos_tenant_counters.items()):
                xs = sorted(self._qos_lat.get(t, ()))
                p99 = (
                    xs[min(len(xs) - 1,
                           int(round(0.99 * (len(xs) - 1))))]
                    if xs else 0.0
                )
                qos_tenants[t] = dict(c, latency_p99_s=p99)
            out["qos"] = {
                "enabled": self.qos,
                "tenants": qos_tenants,
                "lanes_held": dict(self._qos_lanes_held),
                "deficits": self._qos_sched.deficits(),
                "admission_log": [list(ev) for ev in self._qos_log],
            }
            occ_samples = list(self._occ_samples)
            out["time_to_first_wave"] = {
                "count": self._ttfw_n,
                "mean_s": (
                    self._ttfw_sum / self._ttfw_n if self._ttfw_n else 0.0
                ),
                "max_s": self._ttfw_max,
            }
            # which dispatch schedule each class runs, and where it
            # came from (docs/21_autotune.md) — ``/varz`` carries this
            # dict verbatim, so "is the fleet on the searched
            # schedule?" is one scrape away
            out["schedule"] = {
                "sources": dict(self._sched_sources),
                "by_class": dict(self._schedules),
            }
        # the live-occupancy view is computed OUTSIDE the lock: the
        # plain dispatch path stores device vectors (the readback stays
        # asynchronous), and forcing them to host must never stall a
        # concurrent submit/dispatch on the service lock
        import numpy as _np

        vals = []
        for v, tot in occ_samples:
            if not isinstance(v, int):
                v = int(_np.asarray(v).sum())
            vals.append((v, tot))
        fracs = [lv / t for lv, t in vals if t]
        last_live, last_tot = vals[-1] if vals else (0, 0)
        out["lane_occupancy"].update({
            "lanes_live_now": last_live,
            "lanes_in_wave": last_tot,
            "occupancy_now": last_live / last_tot if last_tot else 0.0,
            "occupancy_mean": (
                sum(fracs) / len(fracs) if fracs else 0.0
            ),
            "occupancy_samples": len(vals),
        })
        if hasattr(self.cache, "stats"):
            out["program_cache"] = self.cache.stats()
            # the persistent AOT store's hit/miss/downgrade counters,
            # surfaced top-level too (docs/15_program_store.md): a
            # fleet health check reads ONE dict to see whether rollouts
            # are serving from artifacts or silently recompiling
            store_stats = out["program_cache"].get("store")
            if store_stats is not None:
                out["program_store"] = store_stats
        return out

    def chrome_trace(self) -> dict:
        """Request lifecycle spans + queue-depth counter tracks (total
        and per compatibility class) + per-wave live/padded lane
        counters as a
        Chrome-trace / Perfetto dict (the same Trace Event Format schema
        ``obs.export`` emits, and it passes
        ``obs.export.validate_chrome_trace``): each request is one
        complete 'X' span on its own pid track, service stats ride in
        ``otherData.service``.  With a telemetry plane recording spans
        (docs/17_telemetry.md), each request's pid track additionally
        carries its queue/wave child spans and chunk/fold/deliver
        instants — the same span tree the JSONL log streams."""
        with self._lock:
            spans = list(self._spans)
            depths = list(self._depth_samples)
        children: dict = {}
        if self._tel is not None and self._tel.spans is not None:
            # the telemetry span trees (queue/wave spans, chunk/fold/
            # deliver instants) ride their request's pid track, one tid
            # per phase; the root "request" span is skipped — the
            # service's own lifecycle span below already draws it
            trace_pid = {
                s["trace"]: s["seq"] for s in spans
                if s.get("trace") is not None
            }
            tids = {"queue": 1, "wave": 2, "chunk": 3, "fold": 3,
                    "admit": 3, "deliver": 3}
            for e in self._tel.spans.chrome_events(
                self._t0,
                pid_of=trace_pid.get,
                tid_of=lambda n: tids.get(n, 4),
            ):
                if e["name"] != "request":
                    children.setdefault(e["pid"], []).append(e)
        events = []
        meta = []
        for s in spans:
            events.append({
                "name": s["label"] or f"request {s['seq']}",
                "ph": "X",
                "ts": (s["submit"] - self._t0) * 1e6,
                "dur": max((s["end"] - s["submit"]) * 1e6, 0.0),
                "pid": s["seq"],
                "tid": 0,
                "args": {
                    "outcome": s["outcome"],
                    "lanes": s["lanes"],
                    "time_to_first_wave_s": s["ttfw"],
                    "retries": s["retries"],
                },
            })
            # child spans spliced right after their root, sorted by ts:
            # every child starts at or after submit, so the pid track
            # stays timestamp-monotone (the validator's contract)
            events.extend(sorted(
                children.pop(s["seq"], ()), key=lambda e: e["ts"]
            ))
            meta.append({
                "name": "process_name", "ph": "M", "pid": s["seq"],
                "args": {"name": s["label"] or f"request {s['seq']}"},
            })
        # a live depth sample closes the counter tracks — and
        # guarantees at least one event, so an IDLE service still
        # exports a validator-clean trace; every seen class emits its
        # current (usually 0) depth so no track sticks at a stale value
        with self._lock:
            closing = self._class_sample()
        depths.append(
            (time.monotonic(), self._queue.depth(), closing, 0, 0)
        )
        for t, d, by_class, live, padded in depths:
            ts = (t - self._t0) * 1e6
            events.append({
                "name": "queue_depth", "ph": "C",
                "ts": ts, "pid": 0, "tid": 0,
                "args": {"depth": d},
            })
            # per-class queue-depth counter tracks (one track per
            # compatibility class) + the live/padded lane split of the
            # wave dispatched at this sample — padding waste as a
            # timeline, not just an aggregate
            for label, depth in by_class:
                events.append({
                    "name": f"queue_depth/{label}", "ph": "C",
                    "ts": ts, "pid": 0, "tid": 0,
                    "args": {"depth": depth},
                })
            if live or padded:
                events.append({
                    "name": "wave_lanes", "ph": "C",
                    "ts": ts, "pid": 0, "tid": 0,
                    "args": {"live": live, "padded": padded},
                })
        return {
            "traceEvents": events + meta,
            "displayTimeUnit": "ms",
            "otherData": {"service": self.stats()},
        }

    # -- internals -----------------------------------------------------------

    def _horizon_bucket(self, t_end):
        """This service's horizon bucket for ``t_end`` (the shared
        module-level :func:`horizon_bucket_of` at this service's
        ratio)."""
        return horizon_bucket_of(t_end, self.horizon_bucket)

    def _wave_shape(self, total: int) -> int:
        """The quantized lane count one wave of ``total`` live lanes
        dispatches at: the next power-of-two multiple of the mesh
        device count, capped at ``max_wave`` (pad-and-mask — the
        excess lanes are dead on arrival and bitwise-inert).  Disabled
        padding, or a cap that would under-shoot, returns ``total``
        unchanged."""
        if not self.pad_waves or total <= 0:
            return total
        unit = 1 if self.mesh is None else int(self.mesh.devices.size)
        q = unit
        while q < total:
            q *= 2
        q = min(q, self.max_wave)
        if q < total or (self.mesh is not None and q % unit):
            return total
        return q

    def _plan_pad(self, slots) -> tuple:
        """``(total live lanes, pad lanes)`` of one packed wave — the
        ONE definition both the stats recording (:meth:`_pack`) and the
        actual dispatch (:meth:`_run_batch`) use, so the counters can
        never describe a wave shape that wasn't dispatched."""
        total = sum(n for _, _, n in slots)
        return total, self._wave_shape(total) - total

    # cimba-check: assume-held
    def _class_sample(self) -> tuple:
        """Per-class queue depths over EVERY class ever seen (zeros
        included — a Chrome counter track holds its last value, so a
        drained class must emit 0 or it renders as stuck at its last
        nonzero depth forever).  Caller holds the service lock."""
        depths = self._queue.class_depths()
        return tuple(
            (label, depths.get(c, 0))
            for c, label in self._class_ids.items()
        )

    def _class_key(self, request: Request, with_metrics: bool) -> tuple:
        """This service's compatibility class for ``request`` — the
        shared module-level :func:`request_class_key` at this service's
        mesh and horizon-bucket ratio."""
        return request_class_key(
            request, with_metrics, mesh=self.mesh,
            horizon_bucket=self.horizon_bucket,
        )

    def _cancel(self, entry: _Entry) -> bool:
        with self._lock:
            if entry.done.is_set():
                return False
            if entry.in_flight:
                if not (self.refill or self.device_sched):
                    return False
                # refill/device-sched mode: an in-flight request's
                # lanes are freed
                # at the NEXT chunk boundary (flipped to t_stop=-inf —
                # reclaimable capacity), where the boundary controller
                # finishes it with Cancelled exactly once.  Best
                # effort: if every lane happens to die at that same
                # boundary, completion wins and the result is
                # delivered (docs/22_refill.md).
                entry.cancelled = True
                return True
            entry.cancelled = True
        # finish now (snappy futures); the dispatcher drops the
        # tombstone when it reaches it in the queue
        self._finish(entry, exc=Cancelled(entry.label),
                     outcome="cancelled")
        self._queue.kick()
        return True

    def _finish(self, entry: _Entry, *, result=None, exc=None,
                outcome: str) -> None:
        with self._lock:
            if entry.done.is_set():
                return
            entry.result = result
            entry.exc = exc
            now = time.monotonic()
            entry.finish_t = now
            self._counters[outcome] += 1
            ttfw = (
                None if entry.first_dispatch_t is None
                else entry.first_dispatch_t - entry.submit_t
            )
            self._spans.append({
                "seq": entry.seq,
                "label": entry.label,
                "submit": entry.submit_t,
                "end": now,
                "outcome": outcome,
                "lanes": entry.request.n_replications,
                "ttfw": ttfw,
                "retries": entry.retries,
                "trace": entry.trace,
            })
            if ttfw is not None:
                self._ttfw_sum += ttfw
                self._ttfw_max = max(self._ttfw_max, ttfw)
                self._ttfw_n += 1
            if self.qos and entry.tenant is not None:
                # quota release: the tenant's lanes free the moment the
                # request retires, whatever the outcome — and the
                # per-tenant outcome counter feeds the goodput gauges
                held = self._qos_lanes_held.get(entry.tenant, 0) \
                    - entry.request.n_replications
                if held > 0:
                    self._qos_lanes_held[entry.tenant] = held
                else:
                    self._qos_lanes_held.pop(entry.tenant, None)
                tc = self._qos_tenant(entry.tenant)
                if outcome in tc:
                    tc[outcome] += 1
                if outcome == "completed":
                    lat = self._qos_lat.get(entry.tenant)
                    if lat is None:
                        lat = deque(maxlen=512)
                        self._qos_lat[entry.tenant] = lat
                    lat.append(now - entry.submit_t)
            self._outstanding -= 1
            entry.done.set()
            self._drained.notify_all()
        tel = self._tel
        if tel is not None:
            tel.observe_request(
                self._tel_name, outcome, now - entry.submit_t, ttfw
            )
            if entry.trace is not None:
                rec = tel.spans
                rec.event(entry.trace, "deliver",
                          parent=entry.span_root, outcome=outcome)
                # closes any still-open queue/wave spans first — a
                # cancelled, deadline-expired, or retries-exhausted
                # request still yields one COMPLETE span tree
                rec.end_trace(entry.trace, outcome,
                              retries=entry.retries)

    # cimba-check: assume-held
    def _qos_tenant(self, name: str) -> dict:
        """The per-tenant QoS counter dict (created zeroed on first
        touch, so stats always reports full rows).  Caller holds the
        service lock."""
        tc = self._qos_tenant_counters.get(name)
        if tc is None:
            tc = {k: 0 for k in _QOS_TENANT_COUNTERS}
            self._qos_tenant_counters[name] = tc
        return tc

    # cimba-check: assume-held
    def _adopt_sched_knobs(self, sched) -> None:
        """Adopt a tuned schedule's device-scheduler policy knobs
        (docs/24_device_scheduler.md) for every knob the constructor
        left None — explicit constructor values always win, and the
        first adopted value sticks (one service, one policy; a later
        class resolving a different tuned schedule does not flap the
        scheduler mid-flight).  Caller holds the service lock."""
        if self._waves_per_device is None \
                and sched.waves_per_device is not None:
            self._waves_per_device = int(sched.waves_per_device)
        if self._preempt_quantum is None \
                and sched.preempt_quantum is not None:
            self._preempt_quantum = int(sched.preempt_quantum)
        if self._mem_fraction is None \
                and sched.mem_fraction is not None:
            self._mem_fraction = float(sched.mem_fraction)

    # cimba-check: assume-held
    def _adopt_fuse_knobs(self, sched) -> None:
        """Adopt a tuned schedule's wave-fusion knobs
        (docs/26_wave_fusion.md): ``fuse`` fills in only when BOTH the
        constructor and the ``CIMBA_WAVE_FUSE`` env left it unset
        (explicit policy always wins), ``fuse_max_specs`` when the
        constructor left it None — and as with the device-scheduler
        knobs, the first adopted value sticks.  Caller holds the
        service lock."""
        if self._fuse_unset and getattr(sched, "fuse", None) is not None:
            self.fuse = bool(sched.fuse)
            self._fuse_unset = False
        if self._fuse_max_specs is None \
                and getattr(sched, "fuse_max_specs", None) is not None \
                and int(sched.fuse_max_specs) >= 2:
            self._fuse_max_specs = int(sched.fuse_max_specs)

    # cimba-check: assume-held
    def _eff_fuse_max(self) -> int:
        """The effective roster cap — the constructor/adopted value,
        else the ``tune.space`` default."""
        if self._fuse_max_specs is not None:
            return self._fuse_max_specs
        from cimba_tpu.tune import space as _tspace

        return _tspace.DEFAULT_FUSE_MAX_SPECS

    # cimba-check: assume-held
    def _bind_fusion(self, entry: _Entry, fuse_cls) -> None:
        """Bind one admitted entry to its fusion class: join (or match)
        the class roster — first ``fuse_max_specs`` distinct specs win,
        for the service's life — and stamp the entry's fusion identity.
        A spec that cannot fuse (``fuse_cls=None``) or arrives at a
        full roster counts a ``fusion_rejects`` and serves through its
        exact class unchanged.  Caller holds the service lock."""
        if fuse_cls is None:
            self._counters["fusion_rejects"] += 1
            return
        fp = _pcache.spec_fingerprint(entry.request.spec)
        roster = self._fuse_roster.setdefault(fuse_cls, {})
        if fp not in roster:
            if len(roster) >= self._eff_fuse_max():
                self._counters["fusion_rejects"] += 1
                return
            roster[fp] = entry.request.spec
        entry.fuse_cls = fuse_cls
        entry.spec_fp = fp

    def _fused_bundle(self, fuse_cls):
        """The cached FusedSpec bundle for a class's CURRENT roster —
        members in canonical (stable-fingerprint) order, so any arrival
        order of the same member set shares one superprogram.  Requires
        >= 2 roster members (a single-member class serves exact —
        fusing it would shadow the historical program for no gain);
        returns None otherwise.  Dispatcher thread only."""
        with self._lock:
            roster = self._fuse_roster.get(fuse_cls)
            specs = () if roster is None else tuple(roster.values())
        if len(specs) < 2:
            return None
        specs = tuple(sorted(specs, key=_pcache.fusion_order_key))
        return _pcache.get_fused(self.cache, specs)

    def _loop(self) -> None:
        if self.device_sched:
            # the preemptive device scheduler
            # (docs/24_device_scheduler.md) owns this thread: concurrent
            # refill waves, memory-aware admission, checkpoint-evict-
            # restore preemption.  Off, everything below is the
            # historical loop, byte for byte.
            from cimba_tpu.serve.device import DeviceScheduler

            DeviceScheduler(self).run()
            return
        while True:
            if self._tel is not None:
                # liveness: the dispatcher beats at least once per
                # queue poll (and per chunk, via the _run_batch hook),
                # which is what /healthz judges "stalled" against
                self._tel.heartbeat(f"serve.{self._tel_name}.dispatch")
            entry = self._queue.pop_ready(timeout=0.25)
            # one atomic read of the shutdown state per poll (CHK002):
            # _stop/_closed/_outstanding together decide the exit, and
            # a torn read could exit with a request still outstanding
            with self._lock:
                stopping = self._stop
                drained = self._closed and self._outstanding == 0
            if entry is None:
                if stopping or drained:
                    # a backoff-delayed retry may still sit in the
                    # delay heap (it failed after shutdown's
                    # drain_now): cancel it rather than strand its
                    # future forever
                    for e in self._queue.drain_now():
                        if not e.done.is_set():
                            self._finish(e, exc=Cancelled(e.label),
                                         outcome="cancelled")
                    return
                continue
            if stopping:
                # non-graceful shutdown: whatever is still being popped
                # (including a requeued multi-wave remainder) is
                # cancelled, not run to completion
                if not entry.done.is_set():
                    self._finish(entry, exc=Cancelled(entry.label),
                                 outcome="cancelled")
                continue
            with self._lock:
                if entry.done.is_set():  # cancelled tombstone
                    continue
                cancelled_flag = entry.cancelled
                if not cancelled_flag:
                    # CLAIM under the service lock: from here cancel()
                    # returns False — an entry is either cancelled
                    # while truly undispatched, or it runs; never both
                    entry.in_flight = True
            if cancelled_flag:
                # a mid-wave cancel whose entry was requeued before the
                # flag was honored (refill remainder race): finish it
                # instead of running a whole slot for a dead request
                self._finish(entry, exc=Cancelled(entry.label),
                             outcome="cancelled")
                continue
            now = time.monotonic()
            if entry.deadline_at is not None and now > entry.deadline_at:
                self._finish(
                    entry,
                    exc=DeadlineExceeded(
                        entry.request.deadline, now - entry.submit_t,
                        entry.label,
                    ),
                    outcome="deadline_exceeded",
                )
                continue
            if self.refill:
                # continuous wave refill (docs/22_refill.md): the wave
                # is driven chunk-by-chunk with a boundary controller
                # that retires finished requests' lanes early and
                # splices queued compatible requests into them —
                # failure containment lives inside (_batch_failed on
                # the still-active members; delivered results stay
                # delivered)
                self._serve_refill_wave(entry)
                continue
            # one program span per wave, over the pack, assemble, run,
            # fold and deliver spans its phases open (docs/17)
            with _span("serve.wave"):
                slots, members, fused = self._pack(entry)
                try:
                    # the fold is inside the guard too: a summary_path
                    # whose SHAPE preflights fine but whose fold-trace
                    # raises (e.g. a non-Summary statistic fed to the
                    # Pébay merge) must fail the REQUESTS, never kill
                    # the dispatcher thread — a dead dispatcher hangs
                    # every outstanding future (the fused kwarg is only
                    # passed when set, so the retry tests' _run_batch
                    # seams keep their signature)
                    sims = (
                        self._run_batch(slots) if fused is None
                        else self._run_batch(slots, fused=fused)
                    )
                    self._fold_slots(slots, sims)
                except Exception as e:
                    self._batch_failed(members, e)
                    continue
                self._complete_members(members)

    def _pack(self, lead: _Entry):
        """Build one wave: the lead's slots first (its own wave
        partition — only whole slots, never clipped, so the fold stays
        bitwise the direct call's), then greedily fill remaining lanes
        with queued requests of the SAME compatibility class in
        priority order (the bucket-fill policy: seed/params/R/horizon
        mixes pack, docs/14_wave_packing.md) — and, with fusion on and
        the lead roster-bound, with queued requests of the lead's
        FUSION class (docs/26_wave_fusion.md: distinct specs, one
        switch-dispatch superprogram; returns the bundle as a third
        result, None when the packed members stay single-spec — a
        homogeneous wave dispatches the historical exact-class program
        even with fusion on).  The lead arrives
        already CLAIMED (in_flight, set by the loop under the service
        lock); fill candidates are claimed here the same way — one that
        was cancelled in the gap between leaving the queue and the
        claim is dropped, never dispatched (cancel() stays truthful)."""
        with _span("serve.pack") as sp:
            return self._pack_wave(lead, sp)

    def _pack_wave(self, lead: _Entry, sp):
        """:meth:`_pack`'s body, inside its ``serve.pack`` span ``sp``,
        which it gives the wave's number, its members' seqs and its live
        and padded lanes."""
        budget = self.max_wave

        def plan(entry) -> list:
            """The entry's whole-slot partition that fits the budget."""
            nonlocal budget
            out = []
            lo = entry.next_lo
            R = entry.request.n_replications
            while lo < R:
                n = min(entry.eff_wave, R - lo)
                if n > budget:
                    break
                out.append((lo, n))
                budget -= n
                lo += n
            return out

        slots = [(lead, lo, n) for lo, n in plan(lead)]
        members = [lead]
        planned: list = []
        if budget > 0 and not lead.solo:
            now = time.monotonic()
            dropped: list = []

            def want(e: _Entry) -> bool:
                if e.done.is_set():
                    return True      # cancelled tombstone: just remove
                if e.deadline_at is not None and now > e.deadline_at:
                    dropped.append(e)
                    return True
                if e.solo:
                    return False
                if e.cls != lead.cls and not (
                    lead.fuse_cls is not None
                    and e.fuse_cls == lead.fuse_cls
                ):
                    # neither the exact class nor (fusion on, both
                    # roster-bound) the lead's fusion class
                    return False
                p = plan(e)
                if not p:
                    return False
                planned.append((e, p))
                return True

            self._queue.take(want)
            for e in dropped:
                self._finish(
                    e,
                    exc=DeadlineExceeded(
                        e.request.deadline, now - e.submit_t, e.label,
                    ),
                    outcome="deadline_exceeded",
                )
        with self._lock:
            for e, p in planned:
                if e.done.is_set():  # cancelled before the claim: drop
                    continue
                e.in_flight = True
                members.append(e)
                slots.extend((e, lo, n) for lo, n in p)
            for e in members:
                if e.first_dispatch_t is None:
                    e.first_dispatch_t = time.monotonic()
            total, padded = self._plan_pad(slots)
            self._counters["batches"] += 1
            batch_no = self._counters["batches"]
            self._counters["waves"] += len(slots)
            self._counters["lanes_dispatched"] += total
            self._counters["lanes_padded"] += padded
            # a wave is FUSED only when its members actually span more
            # than one exact class (distinct specs); roster membership
            # guarantees the bundle below covers every packed member
            needs_fuse = any(m.cls != lead.cls for m in members)
            if needs_fuse:
                self._counters["fused_batches"] += 1
                self._counters["fused_lanes"] += total
            k = len(members)
            self._occupancy[k] = self._occupancy.get(k, 0) + 1
            self._depth_samples.append((
                time.monotonic(), self._queue.depth(),
                self._class_sample(), total, padded,
            ))
        sp.attrs.update(wave=batch_no, seqs=[e.seq for e in members],
                        lanes=total, padded=padded)
        rec = self._tel.spans if self._tel is not None else None
        if rec is not None:
            for e in members:
                if e.trace is None:
                    continue
                if e.span_queue is not None:
                    rec.end(e.span_queue)
                    e.span_queue = None
                e.span_wave = rec.start(
                    e.trace, "wave", parent=e.span_root,
                    batch=batch_no,
                    members=len(members), lanes=total, padded=padded,
                )
        fused = (
            self._fused_bundle(lead.fuse_cls) if needs_fuse else None
        )
        return slots, members, fused

    def _run_batch(self, slots, fused=None):
        """Dispatch ONE packed wave: init the concatenated lanes —
        per-slot replication indices, seed columns, horizon columns,
        and parameter rows, plus the dead pad lanes that quantize the
        wave shape — and drive the shared chunk program to completion.
        The wave runs at the LEAD's ``chunk_steps`` (chunking is
        trajectory-invariant, so mates with other budgets still get
        bitwise-exact results).  Separated out as the failure-injection
        seam for the retry tests.

        ``fused`` (a FusedSpec bundle) switches the wave onto the
        fusion superprogram (docs/26_wave_fusion.md): a per-slot
        spec-id column joins the lane data, init dispatches each lane
        through its member's own model, the chunk program is the
        merged spec's ordinary one, and the horizon column is ALWAYS
        materialized (bitwise-inert — ``t_stop=t_end`` reproduces the
        static cond and no result reads the leaf).  Folds are
        untouched: each request's slot still folds its own lanes
        through its own fold program, so results stay bitwise the solo
        run's.

        Two program spans: ``serve.assemble`` from the class check up
        to and including the init dispatch, ``serve.run`` until a poll
        finds no live lane."""
        import jax
        import jax.numpy as jnp

        from cimba_tpu.core.loop import drive_chunks
        from cimba_tpu.runner import experiment as ex

        from cimba_tpu.obs import metrics as _metrics

        with _span("serve.assemble"):
            lead = slots[0][0]
            req = lead.request
            cls_now = _pcache.program_class_key(
                req.spec, _metrics.enabled(), mesh=self.mesh,
            )
            if cls_now != lead.cls[0]:
                # the program CLASS (dtype profile, obs.metrics/trace
                # flags...) was frozen
                # into the compatibility key at submit; tracing now under
                # drifted globals would cache a program whose behavior
                # contradicts that key (and silently serve it to every
                # later request at this key).  ValueError = permanent:
                # fail the request loudly instead.
                raise ValueError(
                    "serve: a trace-time global (dtype profile or "
                    "obs.metrics/obs.trace state) changed between this "
                    "request's submit and its dispatch — the "
                    "compatibility key binds at submit time; resubmit "
                    "after settling the globals"
                )
            if fused is None:
                init_j, chunk_j = _pcache.get_programs(
                    self.cache, req.spec, mesh=self.mesh,
                    chunk_steps=req.chunk_steps,
                    with_metrics=lead.with_metrics,
                )
                sid_of = None
            else:
                init_j, chunk_j = _pcache.get_fused_wave_programs(
                    self.cache, fused, mesh=self.mesh,
                    chunk_steps=req.chunk_steps,
                    with_metrics=lead.with_metrics,
                )
                sid_of = {
                    _pcache.spec_fingerprint(s): k
                    for k, s in enumerate(fused.members)
                }
            # each member's summary_path preflights against ITS params
            # shapes (paths may differ — every request folds its own slice
            # through its own fold program); fingerprint-cached, so a warm
            # cache skips the re-trace.  On the fused path the member's
            # spec-id is pinned into an adapter so the preflight traces the
            # member's OWN init branch (the preflight key is per member
            # fingerprint either way).
            seen: set = set()
            for e, _, n in slots:
                if id(e) in seen:
                    continue
                seen.add(id(e))
                if fused is None:
                    member_init = init_j
                else:
                    sid = self._entry_sid(sid_of, e)

                    def member_init(r, s, t, p, _f=init_j, _sid=sid):
                        return _f(
                            r, s, t, jnp.full(r.shape, _sid, jnp.int32), p,
                        )

                _pcache.preflight_summary_path(
                    self.cache, e.request.spec, member_init,
                    e.request.summary_path, e.request.params,
                    e.request.n_replications, n, e.with_metrics,
                )
            total, pad = self._plan_pad(slots)
            reps = [jnp.arange(lo, lo + n) for _, lo, n in slots]
            seeds = [
                ex._seed_column(e.request.seed, n) for e, _, n in slots
            ]
            sids = (
                None if fused is None else [
                    jnp.full((n,), self._entry_sid(sid_of, e), jnp.int32)
                    for e, _, n in slots
                ]
            )
            if fused is None and pad == 0 and all(
                e.request.t_end is None for e, _, n in slots
            ):
                # unpadded all-run-to-completion wave: omit the t_stop leaf
                # entirely, like the direct stream path — the chunk cond
                # then skips the per-event horizon check (same program key;
                # jit re-specializes per pytree structure).  Fused waves
                # always carry the column (one program per class).
                t_stops = None
            else:
                t_stops = [
                    ex._horizon_column(e.request.t_end, n)
                    for e, _, n in slots
                ]
            pws = [
                ex._slice_params(
                    e.request.params, e.request.n_replications, lo, n
                )
                for e, lo, n in slots
            ]
            if pad:
                # dead masked lanes: t_stop=-inf means the liveness cond is
                # false at entry — the lane never dispatches an event, and
                # its (sliced-off) state never joins any fold.  Its params
                # are the lead's row 0 (real, valid values, so user_init
                # cannot trip on them); rep/seed values are irrelevant.
                reps.append(jnp.zeros((pad,), reps[0].dtype))
                seeds.append(ex._seed_column(0, pad))
                t_stops.append(jnp.full((pad,), -jnp.inf, t_stops[0].dtype))
                if sids is not None:
                    # dead lanes dispatch no events; member 0's init runs
                    # on them only to produce a valid (masked-off) row
                    sids.append(jnp.zeros((pad,), jnp.int32))
                row0 = ex._slice_params(
                    req.params, req.n_replications, 0, 1
                )
                pws.append(jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (pad,) + x.shape[1:]),
                    row0,
                ))
            if len(reps) == 1:
                reps_cat, seed_cat, pw_cat = reps[0], seeds[0], pws[0]
                ts_cat = None if t_stops is None else t_stops[0]
                sid_cat = None if sids is None else sids[0]
            else:
                reps_cat = jnp.concatenate(reps, axis=0)
                seed_cat = jnp.concatenate(seeds, axis=0)
                ts_cat = (
                    None if t_stops is None
                    else jnp.concatenate(t_stops, axis=0)
                )
                sid_cat = (
                    None if sids is None else jnp.concatenate(sids, axis=0)
                )
                pw_cat = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *pws
                )
            sims = (
                init_j(reps_cat, seed_cat, ts_cat, pw_cat) if fused is None
                else init_j(reps_cat, seed_cat, ts_cat, sid_cat, pw_cat)
            )
        on_chunk = self._on_chunk
        tel = self._tel
        if tel is not None:
            user_hook = self._on_chunk
            src = f"serve.{self._tel_name}.chunk"
            rec = tel.spans

            def on_chunk(n):
                # per-chunk telemetry tick (heartbeat + counter) and —
                # with spans on — an instant event on the LEAD's wave
                # span: the chunk leg of the request-scoped trace
                tel.tick(src)
                if rec is not None and lead.span_wave is not None:
                    rec.event(lead.trace, "chunk",
                              parent=lead.span_wave, n=n)
                if user_hook is not None:
                    user_hook(n)

        # per-chunk live-lane readback (docs/22_refill.md): a tiny
        # non-donated vmapped-cond dispatch per boundary feeds the live
        # ``lane_occupancy`` gauge — the device vector is stored as-is
        # (no host sync on the dispatch path; stats() converts at
        # scrape time), so /varz and the fleet health scraper see a
        # long wave's occupancy DECAY in real time instead of the
        # pack-time snapshot.  SERVICE-local cache, not the shared
        # ProgramCache: the readback is an observability detail of
        # this dispatcher, and it must not perturb the shared cache's
        # size/miss accounting ("a warmed service adds no program
        # entries" is a pinned contract); dispatcher-thread only, and
        # each entry pins its spec (the class key embeds function ids)
        live_key = (
            lead.cls if fused is None
            else ("fused",) + tuple(
                _pcache.spec_fingerprint(s) for s in fused.members
            )
        )
        ent = self._live_cache.get(live_key)
        if ent is None:
            from cimba_tpu.runner import experiment as ex

            live_spec = req.spec if fused is None else fused.spec
            pin = req.spec if fused is None else fused
            ent = (ex._live_program(live_spec, self.mesh), pin)
            self._live_cache[live_key] = ent
        live_j = ent[0]
        wave_lanes = total + pad
        every = self.refill_every

        def on_boundary(c, s, _live=live_j, _L=wave_lanes):
            if c % every:
                return None
            self._note_occupancy(_live(s), _L)
            return None

        with _span("serve.run"):
            return drive_chunks(
                chunk_j, sims, poll_every=self.poll_every,
                on_chunk=on_chunk, on_boundary=on_boundary,
            )

    def _note_occupancy(self, live, lanes: int) -> None:
        """Append one per-chunk occupancy sample — ``live`` is either a
        host int (refill boundaries, already synced) or a device [L]
        bool vector (the plain path's asynchronous readback)."""
        with self._lock:
            self._occ_samples.append((live, lanes))

    # -- continuous wave refill (docs/22_refill.md) --------------------------

    def _serve_refill_wave(self, lead: _Entry) -> None:
        """Drive ONE refill-managed wave to retirement: pack the lead
        (plus queued compatible requests, one whole slot each), then
        re-dispatch the shared chunk program with a boundary controller
        that (a) retires each request's lanes the chunk they die —
        folding THAT request through its own fold program and
        delivering its ResultHandle immediately, not at whole-wave
        retirement — (b) frees the lanes of cancelled / deadline-
        expired requests (flipped to ``t_stop=-inf`` pad capacity),
        and (c) splices queued compatible requests' (seed, t_stop,
        params) rows into freed lanes through the donated refill
        program — all without recompiling anything after warmup."""
        from cimba_tpu.core.loop import drive_chunks
        from cimba_tpu.obs import metrics as _metrics

        req = lead.request
        wave = None
        try:
            cls_now = _pcache.program_class_key(
                req.spec, _metrics.enabled(), mesh=self.mesh,
            )
            if cls_now != lead.cls[0]:
                raise ValueError(
                    "serve: a trace-time global (dtype profile or "
                    "obs.metrics/obs.trace state) changed between this "
                    "request's submit and its dispatch — the "
                    "compatibility key binds at submit time; resubmit "
                    "after settling the globals"
                )
            wave = self._pack_refill(lead)
            sims = self._init_refill_wave(wave)
            on_chunk = self._on_chunk
            tel = self._tel
            if tel is not None:
                user_hook = self._on_chunk
                src = f"serve.{self._tel_name}.chunk"
                rec = tel.spans

                def on_chunk(n):
                    tel.tick(src)
                    if rec is not None and lead.span_wave is not None:
                        rec.event(lead.trace, "chunk",
                                  parent=lead.span_wave, n=n)
                    if user_hook is not None:
                        user_hook(n)

            every = self.refill_every

            def on_boundary(n, s):
                if n % every:
                    return None
                return self._refill_boundary(wave, n, s)

            sims = drive_chunks(
                wave.chunk_j, sims, poll_every=self.poll_every,
                on_chunk=on_chunk, on_boundary=on_boundary,
            )
            # final pass: every lane is dead — fold and deliver
            # whatever retired during the last (unpolled) chunks
            self._refill_boundary(wave, -1, sims, final=True)
        except Exception as e:
            with self._lock:
                self._free_lanes = 0   # no in-flight wave, no headroom
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
                # every member already delivered/finished before the
                # failure: nothing to fail — surface the error without
                # killing the dispatcher thread (a dead dispatcher
                # hangs every outstanding future)
                import warnings

                warnings.warn(
                    "serve refill: late wave error after every member "
                    f"delivered ({type(e).__name__}: {e})",
                    RuntimeWarning,
                )
                return
            self._batch_failed(members, e)

    def _refill_slot_size(self, entry: _Entry) -> int:
        """The entry's next WHOLE slot — ``min(eff_wave, R - next_lo)``,
        the same partition the direct ``run_experiment_stream`` call
        walks, so per-request folds stay bitwise the direct call's.
        Refill admits one slot per request at a time: a request's
        slots always fold in ``lo`` order (the accumulator's merge
        order is part of the bitwise contract)."""
        return min(
            entry.eff_wave,
            entry.request.n_replications - entry.next_lo,
        )

    @staticmethod
    def _entry_sid(sid_of: dict, entry: _Entry) -> int:
        """The entry's lane spec-id in a fused wave.  An entry claimed
        through the EXACT tier may predate its fusion binding
        (``spec_fp=None`` — e.g. submitted before a tuned schedule
        flipped fusion on); its exact class still pins the same spec as
        a roster member, so the fingerprint lookup cannot miss."""
        fp = entry.spec_fp
        if fp is None:
            fp = _pcache.spec_fingerprint(entry.request.spec)
        return sid_of[fp]

    def _claim_compatible(self, cls, budget: int, now: float, *,
                          strict_priority: bool, fuse_cls=None,
                          fuse_members=None) -> list:
        """The ONE queue scan both refill claim sites use (initial
        fill and boundary admission — one definition, so the paths
        cannot drift): take same-class entries, ONE whole slot each,
        in priority order, within ``budget`` lanes; drop cancelled
        tombstones and finish deadline-expired entries with
        ``DeadlineExceeded`` on the way.

        ``strict_priority=True`` is the boundary-admission fairness
        valve (docs/22_refill.md): only the priority-order PREFIX of
        compatible entries is taken — the first live entry of another
        class (or a solo retry) STOPS the scan, so a long-lived refill
        wave can never starve other classes by letting its own class
        jump the queue; with foreign work waiting, the wave stops
        admitting, drains, and retires (the same bound the plain
        dispatcher has).  Returns ``[(entry, n)]`` — NOT yet claimed;
        the caller marks ``in_flight`` under the service lock.

        ``fuse_cls`` widens compatibility to the wave's FUSION class
        (docs/26_wave_fusion.md): an entry of a different exact class
        still packs when its fusion class matches and — when
        ``fuse_members`` (the wave's member-fingerprint map) is given —
        its spec is one of the wave's superprogram members.  A
        fusion-class entry whose spec is NOT a member is foreign (it
        would need a different compiled superprogram): under
        strict_priority it trips the same fairness valve any other
        class does, so a stale fused wave drains instead of starving a
        grown roster."""
        if self.qos:
            # the QoS plane (docs/27_qos.md) swaps the priority-order
            # prefix for the deficit-weighted fair claim — same
            # compatibility and valve semantics, tenant-fair lanes
            return self._claim_fair(
                cls, budget, now, strict_priority=strict_priority,
                fuse_cls=fuse_cls, fuse_members=fuse_members,
            )
        planned: list = []
        dropped: list = []
        state = {"budget": int(budget), "blocked": False}

        def compatible(e: _Entry) -> bool:
            if e.cls == cls:
                return True
            if fuse_cls is None or e.fuse_cls != fuse_cls:
                return False
            if fuse_members is None:
                return True
            return e.spec_fp is not None and e.spec_fp in fuse_members

        def want(e: _Entry) -> bool:
            if e.done.is_set():
                return True      # cancelled tombstone: just remove
            if e.deadline_at is not None and now > e.deadline_at:
                dropped.append(e)
                return True
            if state["blocked"]:
                return False
            if e.solo or not compatible(e) or e.cancelled:
                if strict_priority:
                    state["blocked"] = True
                return False
            n = self._refill_slot_size(e)
            if n > state["budget"]:
                return False
            planned.append((e, n))
            state["budget"] -= n
            return True

        self._queue.take(want)
        for e in dropped:
            self._finish(
                e,
                exc=DeadlineExceeded(
                    e.request.deadline, now - e.submit_t, e.label,
                ),
                outcome="deadline_exceeded",
            )
        return planned

    def _claim_fair(self, cls, budget: int, now: float, *,
                    strict_priority: bool, fuse_cls=None,
                    fuse_members=None) -> list:
        """The QoS twin of :meth:`_claim_compatible` (docs/27_qos.md):
        identical compatibility, tombstone, and deadline semantics, and
        the SAME cross-class fairness valve under ``strict_priority`` —
        but the freed lanes apportion across TENANTS by the
        deficit-weighted round robin of
        :class:`cimba_tpu.qos.FairScheduler` (priority, then EDF, then
        fmix64 within a tenant) instead of going to the global
        priority-order prefix, so one flooding tenant's backlog can no
        longer occupy every freed lane.  The whole ready set is offered
        under the queue lock (``take_selected``) and the selection is
        pure host arithmetic: two fresh services replaying one stream
        produce identical admission logs (the determinism contract
        tests/test_qos.py pins)."""
        planned: list = []
        dropped: list = []

        def compatible(e: _Entry) -> bool:
            if e.cls == cls:
                return True
            if fuse_cls is None or e.fuse_cls != fuse_cls:
                return False
            if fuse_members is None:
                return True
            return e.spec_fp is not None and e.spec_fp in fuse_members

        def selector(offered):
            take: list = []
            cands: list = []
            blocked = False
            for e in offered:
                if e.done.is_set():
                    take.append(e)   # cancelled tombstone: just remove
                    continue
                if e.deadline_at is not None and now > e.deadline_at:
                    dropped.append(e)
                    take.append(e)
                    continue
                if blocked:
                    continue
                if e.solo or not compatible(e) or e.cancelled:
                    # the cross-class fairness valve is UNCHANGED by
                    # tenant fairness (docs/22_refill.md): foreign work
                    # still stops a boundary admission scan cold, so a
                    # long-lived wave drains instead of starving other
                    # classes — QoS reorders WITHIN the claimable set
                    if strict_priority:
                        blocked = True
                    continue
                cands.append(e)
            chosen = self._qos_sched.select(
                cands, int(budget),
                lanes_of=self._refill_slot_size,
                tenant_of=lambda e: (
                    e.tenant if e.tenant is not None
                    else self._tenants.resolve(None)
                ),
            )
            for e in chosen:
                planned.append((e, self._refill_slot_size(e)))
            take.extend(chosen)
            return take

        self._queue.take_selected(selector)
        if planned:
            with self._lock:
                for e, m in planned:
                    self._qos_log.append(
                        ("claim", e.tenant, int(e.seq), int(m))
                    )
                    tc = self._qos_tenant(e.tenant)
                    tc["claims"] += 1
                    tc["lanes_claimed"] += m
        for e in dropped:
            self._finish(
                e,
                exc=DeadlineExceeded(
                    e.request.deadline, now - e.submit_t, e.label,
                ),
                outcome="deadline_exceeded",
            )
        return planned

    def _pack_refill(self, lead: _Entry) -> _RefillWave:
        """The refill twin of :meth:`_pack`: build the initial wave —
        the lead's next whole slot plus queued same-class requests
        (ONE whole slot each, priority order) — and the per-lane
        request ownership table the boundary controller works against.
        Pad lanes are born into the free pool: reclaimable capacity,
        not dead weight."""
        wave = _RefillWave(lead.cls, bool(lead.solo))
        # a refill wave is born FUSED whenever the lead's fusion class
        # has >= 2 roster members (docs/26_wave_fusion.md): even a
        # wave whose initial slots are single-spec runs the class
        # superprogram, so later boundary splices can admit ANY member
        # without retracing.  The member set — and hence the compiled
        # program — is frozen at birth (wave.sid_of).
        if not lead.solo and lead.fuse_cls is not None:
            wave.fused = self._fused_bundle(lead.fuse_cls)
            if wave.fused is not None:
                wave.sid_of = {
                    _pcache.spec_fingerprint(s): k
                    for k, s in enumerate(wave.fused.members)
                }
        budget = self.max_wave - self._refill_slot_size(lead)
        planned: list = []
        if budget > 0 and not lead.solo:
            planned = self._claim_compatible(
                lead.cls, budget, time.monotonic(),
                strict_priority=False,
                fuse_cls=(
                    lead.fuse_cls if wave.fused is not None else None
                ),
                fuse_members=wave.sid_of,
            )
        members = [lead]
        with self._lock:
            slots = [_RefillSlot(
                lead, lead.next_lo, self._refill_slot_size(lead)
            )]
            for e, n in planned:
                if e.done.is_set():  # cancelled before the claim: drop
                    continue
                e.in_flight = True
                members.append(e)
                slots.append(_RefillSlot(e, e.next_lo, n))
            for e in members:
                if e.first_dispatch_t is None:
                    e.first_dispatch_t = time.monotonic()
            total = sum(s.n for s in slots)
            # a refill wave's shape is FROZEN for its whole (open-ended)
            # life, and under sustained load it never retires — a wave
            # born small would cap the service's throughput at its
            # birth shape forever.  With pad_waves on, refill waves are
            # therefore born at FULL quantized capacity: the pad lanes
            # are reclaimable admission headroom (t_stop=-inf, bitwise
            # inert), not waste (docs/22_refill.md).  pad_waves=False
            # keeps the exact packed shape (the latency-insensitive /
            # test-deterministic arm).
            if self.pad_waves and not wave.no_admit:
                cap = self.max_wave
                if self.mesh is not None:
                    unit = int(self.mesh.devices.size)
                    cap -= cap % unit
                pad = max(cap, total) - total
            elif self.pad_waves:
                # a solo (no-admit) wave can never USE admission
                # headroom — quantize like the plain path instead of
                # dispatching max_wave-wide chunks for nothing
                pad = self._wave_shape(total) - total
            else:
                pad = 0
            self._counters["batches"] += 1
            wave.batch_no = self._counters["batches"]
            self._counters["waves"] += len(slots)
            self._counters["lanes_dispatched"] += total
            self._counters["lanes_padded"] += pad
            if wave.fused is not None:
                self._counters["fused_waves"] += 1
                self._counters["fused_lanes"] += total
            k = len(members)
            self._occupancy[k] = self._occupancy.get(k, 0) + 1
            self._depth_samples.append((
                time.monotonic(), self._queue.depth(),
                self._class_sample(), total, pad,
            ))
        off = 0
        for s in slots:
            s.lanes = list(range(off, off + s.n))
            off += s.n
        wave.slots = slots
        wave.free = list(range(total, total + pad))
        wave.L = total + pad
        with self._lock:
            self._free_lanes = len(wave.free)
        rec = self._tel.spans if self._tel is not None else None
        if rec is not None:
            for e in members:
                if e.trace is None:
                    continue
                if e.span_queue is not None:
                    rec.end(e.span_queue)
                    e.span_queue = None
                e.span_wave = rec.start(
                    e.trace, "wave", parent=e.span_root,
                    batch=wave.batch_no, members=len(members),
                    lanes=total, padded=pad, refill=True,
                )
        return wave

    def _init_refill_wave(self, wave: _RefillWave):
        """Compile/fetch the wave's programs and init its lanes.  Like
        :meth:`_run_batch`'s init leg, except the per-lane ``t_stop``
        column is ALWAYS materialized (``t_end=None`` rides as
        ``+inf`` — bitwise the no-horizon cond, docs/14) because lane
        death, reclamation, and splicing are all horizon-driven."""
        import jax
        import jax.numpy as jnp

        from cimba_tpu.runner import experiment as ex

        lead = wave.slots[0].entry
        req = lead.request
        if wave.fused is None:
            wave.init_j, wave.chunk_j = _pcache.get_programs(
                self.cache, req.spec, mesh=self.mesh,
                chunk_steps=req.chunk_steps,
                with_metrics=lead.with_metrics,
            )
            wave.refill_j, wave.live_j = _pcache.get_refill_programs(
                self.cache, req.spec, mesh=self.mesh,
                with_metrics=lead.with_metrics,
            )
        else:
            # the fusion superprogram set (docs/26_wave_fusion.md):
            # spec-id-switched init/refill, the merged spec's ordinary
            # chunk/live programs — one compiled set per fusion class,
            # shared by every member
            wave.init_j, wave.chunk_j = _pcache.get_fused_wave_programs(
                self.cache, wave.fused, mesh=self.mesh,
                chunk_steps=req.chunk_steps,
                with_metrics=lead.with_metrics,
            )
            wave.refill_j, wave.live_j = (
                _pcache.get_fused_refill_programs(
                    self.cache, wave.fused, mesh=self.mesh,
                    with_metrics=lead.with_metrics,
                )
            )
        for s in wave.slots:
            self._preflight_wave_member(wave, s.entry, s.n)
        wave.pad_row = ex._slice_params(
            req.params, req.n_replications, 0, 1
        )
        reps, seeds, t_stops, sids, pws = [], [], [], [], []
        for s in wave.slots:
            e = s.entry
            reps.append(jnp.arange(s.lo, s.lo + s.n))
            seeds.append(ex._seed_column(e.request.seed, s.n))
            t_stops.append(ex._horizon_column(e.request.t_end, s.n))
            if wave.fused is not None:
                sids.append(jnp.full(
                    (s.n,), self._entry_sid(wave.sid_of, e), jnp.int32,
                ))
            pws.append(ex._slice_params(
                e.request.params, e.request.n_replications, s.lo, s.n
            ))
        pad = len(wave.free)
        if pad:
            reps.append(jnp.zeros((pad,), reps[0].dtype))
            seeds.append(ex._seed_column(0, pad))
            t_stops.append(jnp.full((pad,), -jnp.inf, t_stops[0].dtype))
            if wave.fused is not None:
                sids.append(jnp.zeros((pad,), jnp.int32))
            pws.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (pad,) + x.shape[1:]),
                wave.pad_row,
            ))
        if len(reps) == 1:
            cat = (reps[0], seeds[0], t_stops[0], pws[0])
            sid_cat = sids[0] if sids else None
        else:
            cat = (
                jnp.concatenate(reps, axis=0),
                jnp.concatenate(seeds, axis=0),
                jnp.concatenate(t_stops, axis=0),
                jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *pws
                ),
            )
            sid_cat = (
                jnp.concatenate(sids, axis=0) if sids else None
            )
        if wave.fused is None:
            return wave.init_j(*cat)
        return wave.init_j(cat[0], cat[1], cat[2], sid_cat, cat[3])

    def _preflight_wave_member(self, wave: _RefillWave, entry: _Entry,
                               n: int) -> None:
        """Preflight one member's ``summary_path`` against the wave's
        init program — on a fused wave through a spec-id adapter, so
        the trace runs the member's OWN init branch (the preflight
        cache key is per member fingerprint either way)."""
        import jax.numpy as jnp

        if wave.fused is None:
            member_init = wave.init_j
        else:
            sid = self._entry_sid(wave.sid_of, entry)
            init_j = wave.init_j

            def member_init(r, s, t, p, _f=init_j, _sid=sid):
                return _f(
                    r, s, t, jnp.full(r.shape, _sid, jnp.int32), p,
                )

        _pcache.preflight_summary_path(
            self.cache, entry.request.spec, member_init,
            entry.request.summary_path, entry.request.params,
            entry.request.n_replications, n, entry.with_metrics,
        )

    def _fold_refill_slot(self, s: _RefillSlot, sims) -> None:
        """Retire one slot: gather its lanes (ascending lane order ==
        replication order) and fold them through the REQUEST's own
        fold program — the same accumulator walk the direct call's
        contiguous wave slice takes, so the result stays bitwise."""
        import jax
        import jax.numpy as jnp

        e = s.entry
        fold_j = _pcache.get_fold(
            self.cache, e.with_metrics, e.request.summary_path,
        )
        sl = _pcache.get_gather(self.cache)(sims, jnp.asarray(s.lanes))
        if e.acc is None:
            e.acc = _pcache.stream_acc(e.request.spec, e.with_metrics)
        e.acc = fold_j(e.acc, sl)
        e.n_waves += 1
        e.next_lo = s.lo + s.n
        if e.trace is not None:
            self._tel.spans.event(
                e.trace, "fold", parent=e.span_wave, lo=s.lo, n=s.n,
            )

    def _refill_boundary(self, wave: _RefillWave, n: int, sims,
                         final: bool = False):
        """The boundary controller, fired after every chunk: read the
        per-lane liveness, retire slots whose lanes all died (fold +
        deliver / requeue the remainder), reclaim the lanes of
        cancelled and deadline-expired requests, and splice queued
        compatible admissions into the free pool.  Returns the
        respliced Sim when the wave changed (``drive_chunks`` then
        discards its stale liveness polls), else None."""
        import numpy as np

        import jax
        import jax.numpy as jnp

        from cimba_tpu.runner import experiment as ex

        live = np.asarray(wave.live_j(sims))
        with self._lock:
            self._counters["refill_boundaries"] += 1
            self._occ_samples.append((int(live.sum()), wave.L))
        rec = self._tel.spans if self._tel is not None else None
        now = time.monotonic()

        # 1) retire: fold slots whose lanes all died this chunk —
        # completion wins over a simultaneous cancel/deadline
        for s in wave.slots:
            e = s.entry
            if s.folded or e.done.is_set():
                continue
            if live[s.lanes].any():
                continue
            self._fold_refill_slot(s, sims)
            s.folded = True
            wave.free.extend(s.lanes)
            with self._lock:
                self._counters["refill_retirements"] += 1
                e.in_flight = False
            if rec is not None and e.span_wave is not None:
                rec.end(e.span_wave, outcome="ok")
                e.span_wave = None
            if e.next_lo >= e.request.n_replications:
                if not final:
                    with self._lock:
                        self._counters["mid_wave_deliveries"] += 1
                self._finish_completed(e)
            elif e.cancelled:
                # cancelled while its slot was draining: its lanes are
                # already free — finish NOW instead of requeueing the
                # remainder (a requeued remainder would be re-admitted
                # and burn a whole slot of device work before the
                # Cancelled landed)
                self._finish(e, exc=Cancelled(e.label),
                             outcome="cancelled")
            else:
                # remaining slots go back through the queue — the
                # admission scan below (or a later wave) picks the
                # next whole slot up
                if e.trace is not None:
                    e.span_queue = rec.start(
                        e.trace, "queue", parent=e.span_root,
                        requeue=True,
                    )
                self._queue.requeue(e)

        # 2) reclaim: free the lanes of cancelled / deadline-expired
        # requests — their lanes flip to t_stop=-inf pad capacity, and
        # the span tree closes exactly once with the right outcome
        kills: list = []
        for s in wave.slots:
            e = s.entry
            if s.folded or e.done.is_set():
                continue
            expired = e.deadline_at is not None and now > e.deadline_at
            if not (e.cancelled or expired):
                continue
            s.folded = True  # retired without a fold
            wave.free.extend(s.lanes)
            kills.extend(s.lanes)
            with self._lock:
                e.in_flight = False
                self._counters["lanes_reclaimed"] += s.n
            if rec is not None and e.span_wave is not None:
                rec.end(
                    e.span_wave,
                    outcome="cancelled" if e.cancelled else "deadline",
                )
                e.span_wave = None
            if e.cancelled:
                self._finish(e, exc=Cancelled(e.label),
                             outcome="cancelled")
            else:
                self._finish(
                    e,
                    exc=DeadlineExceeded(
                        e.request.deadline, now - e.submit_t, e.label,
                    ),
                    outcome="deadline_exceeded",
                )

        # 3) admit: splice queued compatible requests into free lanes
        admitted: list = []
        with self._lock:
            stopping = self._stop
        if not final and not stopping and wave.free and not wave.no_admit:
            # strict_priority: the fairness valve — a refill wave only
            # admits the priority-order PREFIX of compatible entries,
            # so queued work of OTHER classes (which cannot splice
            # into this wave) stops the refill instead of being
            # starved behind an endlessly-refilled wave; the wave
            # then drains and retires like a plain one
            # a fused wave admits any MEMBER spec of its frozen birth
            # roster (wave.sid_of) — later-grown roster entries are
            # foreign here, so the same strict_priority valve drains
            # the wave and the next one picks up the grown roster
            planned = self._claim_compatible(
                wave.cls, len(wave.free), now, strict_priority=True,
                fuse_cls=(
                    wave.slots[0].entry.fuse_cls
                    if wave.fused is not None else None
                ),
                fuse_members=wave.sid_of,
            )
            free_sorted = sorted(wave.free)
            with self._lock:
                for e, m in planned:
                    if e.done.is_set():
                        continue
                    e.in_flight = True
                    if e.first_dispatch_t is None:
                        e.first_dispatch_t = time.monotonic()
                    s = _RefillSlot(e, e.next_lo, m)
                    s.lanes = free_sorted[:m]
                    free_sorted = free_sorted[m:]
                    wave.slots.append(s)
                    admitted.append(s)
                    self._counters["refill_admissions"] += 1
                    self._counters["lanes_refilled"] += m
                    self._counters["waves"] += 1
                    self._counters["lanes_dispatched"] += m
            wave.free = free_sorted
            if rec is not None:
                for s in admitted:
                    e = s.entry
                    if e.trace is None:
                        continue
                    if e.span_queue is not None:
                        rec.end(e.span_queue)
                        e.span_queue = None
                    # the per-admission refill span (docs/22_refill.md)
                    sp = rec.start(
                        e.trace, "refill", parent=e.span_root,
                        boundary=n, batch=wave.batch_no, lanes=s.n,
                        lo=s.lo,
                    )
                    e.span_wave = rec.start(
                        e.trace, "wave", parent=e.span_root,
                        batch=wave.batch_no, refill=True, lanes=s.n,
                    )
                    rec.end(sp)
            for s in admitted:
                self._preflight_wave_member(wave, s.entry, s.n)

        with self._lock:
            # the scrapeable free-lane headroom tracks the pool across
            # retire/reclaim/admit; a retiring wave has no pool
            self._free_lanes = 0 if final else len(wave.free)

        if final or (not kills and not admitted):
            # (a final pass never splices — the wave is being retired,
            # and any killed entries were already finished above)
            return None

        # 4) splice: one donated refill dispatch re-seeds exactly the
        # masked lanes (admissions at their own (seed, horizon, rep,
        # params) rows; reclaimed lanes as t_stop=-inf pads)
        L = wave.L
        rep_dt = np.asarray(jnp.arange(1)).dtype
        mask = np.zeros((L,), bool)
        reps = np.zeros((L,), rep_dt)
        seeds = np.zeros((L,), np.uint64)
        ts = np.full(
            (L,), -np.inf,
            np.asarray(ex._horizon_column(None, 1)).dtype,
        )
        sids = np.zeros((L,), np.int32)
        if kills:
            mask[np.asarray(kills)] = True
        pw = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (L,) + x.shape[1:]),
            wave.pad_row,
        )
        for s in admitted:
            e = s.entry
            idx = np.asarray(s.lanes)
            mask[idx] = True
            reps[idx] = np.arange(s.lo, s.lo + s.n, dtype=rep_dt)
            seeds[idx] = np.uint64(e.request.seed)
            ts[idx] = np.asarray(
                ex._horizon_column(e.request.t_end, 1)
            )[0]
            if wave.fused is not None:
                sids[idx] = self._entry_sid(wave.sid_of, e)
            rows = ex._slice_params(
                e.request.params, e.request.n_replications, s.lo, s.n
            )
            jidx = jnp.asarray(idx)
            pw = jax.tree.map(
                lambda b, r, j=jidx: b.at[j].set(r), pw, rows
            )
        if wave.fused is None:
            return wave.refill_j(
                sims, jnp.asarray(mask), jnp.asarray(reps),
                jnp.asarray(seeds), jnp.asarray(ts), pw,
            )
        # the fused refill takes the per-lane spec-id column too:
        # killed lanes re-seed as sid-0 pads (t_stop=-inf keeps them
        # dead), admitted lanes as their member's own init branch
        return wave.refill_j(
            sims, jnp.asarray(mask), jnp.asarray(reps),
            jnp.asarray(seeds), jnp.asarray(ts), jnp.asarray(sids), pw,
        )

    def _fold_slots(self, slots, sims) -> None:
        """Slice the finished wave back per slot and fold each into its
        request's accumulator — in slot order, through the REQUEST's
        own fold program (``summary_path`` is per-request, not part of
        the compatibility class), so a multi-slot request folds exactly
        as its direct stream call would.  Pad lanes sit past the last
        slot's offset and are never sliced into any fold.  May raise
        (the fold traces user code); acc and next_lo advance together
        per slot, so a retry after a mid-batch failure resumes exactly
        at the first unfolded slot."""
        import jax.numpy as jnp

        with _span("serve.fold"):
            off = 0
            for entry, lo, n in slots:
                fold_j = _pcache.get_fold(
                    self.cache, entry.with_metrics,
                    entry.request.summary_path,
                )
                sl = _pcache.get_gather(self.cache)(
                    sims, jnp.arange(off, off + n)
                )
                if entry.acc is None:
                    entry.acc = _pcache.stream_acc(
                        entry.request.spec, entry.with_metrics
                    )
                entry.acc = fold_j(entry.acc, sl)
                entry.n_waves += 1
                entry.next_lo = lo + n
                off += n
                if entry.trace is not None:
                    self._tel.spans.event(
                        entry.trace, "fold", parent=entry.span_wave,
                        lo=lo, n=n,
                    )

    def _complete_members(self, members) -> None:
        """After a successful fold: finish done requests, requeue the
        rest.  No user code runs here — it must not raise (a raise
        after partial requeues could double-queue an entry)."""
        with _span("serve.deliver"):
            for entry in members:
                with self._lock:
                    entry.in_flight = False
                if entry.trace is not None and entry.span_wave is not None:
                    self._tel.spans.end(entry.span_wave, outcome="ok")
                    entry.span_wave = None
                if entry.next_lo >= entry.request.n_replications:
                    self._finish_completed(entry)
                else:
                    # a request larger than one packed wave: remaining
                    # slots go back through the queue at its own
                    # priority
                    if entry.trace is not None:
                        entry.span_queue = self._tel.spans.start(
                            entry.trace, "queue", parent=entry.span_root,
                            requeue=True,
                        )
                    self._queue.requeue(entry)

    def _finish_completed(self, entry: _Entry) -> None:
        """Deliver a fully-folded request's StreamResult — the same
        shape the direct ``run_experiment_stream`` call returns.

        Digest leg (docs/18_audit.md): when the request carries an
        ``expect_digest`` or the telemetry plane records spans, the
        result's bitwise digest is computed here (a host transfer of a
        few scalars) and recorded on the span tree; an expectation
        mismatch bumps ``digest_mismatches`` (the ``/healthz`` degraded
        signal) and marks the tree — the result is still delivered.
        With neither active, nothing is computed: results stay
        untouched device arrays (the zero-cost default)."""
        from cimba_tpu.runner.experiment import StreamResult

        acc = entry.acc
        result = StreamResult(
            summary=acc[0],
            n_failed=acc[1],
            total_events=acc[2],
            n_waves=entry.n_waves,
            n_regrows=0,
            metrics=acc[3] if entry.with_metrics else None,
        )
        expect = entry.request.expect_digest
        rec = self._tel.spans if self._tel is not None else None
        spans_on = rec is not None and entry.trace is not None
        if expect is not None or spans_on:
            from cimba_tpu.obs import audit as _audit

            dig = _audit.stream_result_digest(result)
            entry.result_digest = dig
            if spans_on:
                rec.event(
                    entry.trace, "digest", parent=entry.span_root,
                    digest=dig,
                )
            if expect is not None and expect != dig:
                with self._lock:
                    self._counters["digest_mismatches"] += 1
                if spans_on:
                    rec.event(
                        entry.trace, "digest_mismatch",
                        parent=entry.span_root, expected=expect,
                        got=dig,
                    )
        self._finish(entry, result=result, outcome="completed")

    def _batch_failed(self, members, exc: Exception) -> None:
        """Dispatch (or fold) failed.  Every member retries SOLO after
        exponential backoff — in the delay heap, so the dispatcher
        keeps serving other requests meanwhile.  The retry BUDGET is
        only charged for solo failures: when a PACKED batch fails,
        blame is unattributable, so members are demoted to solo and
        re-queued uncharged — an innocent request packed with a poison
        peer keeps its full budget of attributable solo attempts (and
        typically just succeeds on the first one).  ValueError/
        TypeError are treated as permanent (bad request, e.g. a
        summary_path that doesn't exist on the model) and surface
        immediately; anything else is presumed transient until the
        budget runs out.  ``stats()["retries"]`` counts every retry
        re-queue, charged or not."""
        permanent = isinstance(exc, (ValueError, TypeError))
        charged = len(members) == 1  # solo failure: blame attributable
        with self._lock:
            stopping = self._stop
        for entry in members:
            with self._lock:
                entry.in_flight = False
            if entry.trace is not None and entry.span_wave is not None:
                self._tel.spans.end(
                    entry.span_wave, outcome="error",
                    error=type(exc).__name__,
                )
                entry.span_wave = None
            if entry.next_lo >= entry.request.n_replications:
                # every one of ITS slots folded before the batch died
                # (a later member's fold failed): the result is whole —
                # deliver it; requeueing a slotless entry would crash
                # the next dispatch and discard computed work
                self._finish_completed(entry)
                continue
            with self._lock:
                entry.solo = True
                if charged:
                    entry.retries += 1
            if permanent:
                self._finish(entry, exc=exc, outcome="failed")
            elif charged and entry.retries > self.max_retries:
                err = RetriesExhausted(entry.retries, entry.label)
                err.__cause__ = exc
                self._finish(entry, exc=err, outcome="failed")
            elif stopping:
                # non-graceful shutdown already ran: a retry requeued
                # into the delay heap now could outlive the dispatcher
                # and strand its future — cancel instead
                self._finish(entry, exc=Cancelled(entry.label),
                             outcome="cancelled")
            else:
                with self._lock:
                    self._counters["retries"] += 1
                if entry.trace is not None:
                    entry.span_queue = self._tel.spans.start(
                        entry.trace, "queue", parent=entry.span_root,
                        retry=entry.retries, backoff=True,
                    )
                self._queue.requeue(
                    entry,
                    delay=self.backoff.delay(max(entry.retries, 1)),
                )
