"""The preemptive device scheduler (docs/24_device_scheduler.md).

Contracts pinned here:

* **preempt → restore is bitwise-invisible, both profiles**: a
  background wave checkpoint-evicted at a quantum boundary for an
  urgent class and restored later returns results bitwise its direct
  solo run (the Sim pytree is the complete per-lane state — the PR 3
  resumable-checkpoint determinism contract, extended to scheduling);
* **concurrent waves**: with ``waves_per_device=2`` an urgent request
  of a foreign class is admitted as a SECOND live wave while the
  background wave is mid-flight — it completes while the background is
  still live, no preemption needed;
* **preempt-during-refill**: a wave carrying boundary-spliced members
  and mid-wave-delivery history survives evict/restore with its
  ``_RefillWave`` ownership table intact — deliveries resume, every
  member bitwise;
* **memory-aware admission**: a request whose wave could never fit the
  budget fails fast with structured
  :class:`~cimba_tpu.serve.sched.MemoryBudgetExceeded` (needed/budget
  bytes attached), counted, span tree closed;
* **span hygiene**: preempted-and-restored requests close their span
  tree exactly once, with ``preempt``/``restore`` events in the log;
* **the device_sched trace gate**: ``CIMBA_DEVICE_SCHED`` never binds
  into a traced chunk program, is registered in ``config.ENV_KNOBS``,
  and resolves ``Service(device_sched=None)``;
* **autotuner fold**: the three policy knobs ride ``Schedule`` /
  ``ScheduleSpace`` (format 2), collapse to canonical None at their
  defaults, fold through ``resolve_entry``, and are adopted by a
  service whose constructor left them None;
* **footprint ladder**: ``wave_footprint_bytes`` returns a positive
  memoized estimate; the store manifest persists measured
  ``footprint_bytes`` (format 2) and hydrated programs surface it via
  ``footprint_for``.

Deterministic scheduling comes from a gated Service subclass (the
test_refill idiom): the pack gate holds wave birth until the queue is
staged, and a boundary SEMAPHORE releases chunk boundaries one at a
time, so admissions and preemptions land at constructed points.
"""

import threading
import time

import jax
import numpy as np
import pytest

from cimba_tpu import config, serve
from cimba_tpu.core import api, cmd
from cimba_tpu.core.model import Model
from cimba_tpu.runner import experiment as ex
from cimba_tpu.serve import cache as pc
from cimba_tpu.stats import summary as sm


def _tiny_spec(t_stop=600.0):
    """Smallest chunkable model (hold/exit only); a long default
    ``t_stop`` so the horizon column (``t_end``) governs lane death —
    one spec, one compile, every horizon in the file."""
    m = Model("tiny", event_cap=1, guard_cap=2)

    @m.block
    def work(sim, p, sig):
        done = api.clock(sim) > t_stop
        return sim, cmd.select(
            done, cmd.exit_(), cmd.hold(1.0, next_pc=work.pc)
        )

    m.process("w", entry=work)
    return m.build()


def _clock_path(sims):
    return jax.vmap(lambda c: sm.add(sm.empty(), c))(sims.clock)


def _assert_results_equal(a, b):
    assert a.n_waves == b.n_waves
    al = jax.tree.leaves((a.summary, a.n_failed, a.total_events))
    bl = jax.tree.leaves((b.summary, b.n_failed, b.total_events))
    for x, y in zip(al, bl):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope="module")
def tiny():
    return _tiny_spec()


@pytest.fixture(scope="module")
def shared_cache():
    return pc.ProgramCache(capacity=256)


def _req(spec, R, *, seed=1, t_end=None, wave=None, **kw):
    return serve.Request(
        spec, (), R, seed=seed, t_end=t_end, chunk_steps=4,
        wave_size=wave, summary_path=_clock_path, **kw,
    )


def _direct(spec, R, cache, *, seed, t_end=None, wave=None):
    return ex.run_experiment_stream(
        spec, (), R, wave_size=wave or R, chunk_steps=4, seed=seed,
        t_end=t_end, summary_path=_clock_path, program_cache=cache,
    )


class _GatedSched(serve.Service):
    """Device-sched service with deterministic control points:
    ``pack_gate`` holds wave birth (every request meant to race the
    start is queued first), ``started`` flips at the first chunk
    boundary, and boundaries block on a semaphore —
    ``step(n)`` releases exactly n of them, ``open_boundaries()``
    floods the rest of the run.  Horizon buckets are ON (16.0): a
    short-horizon and a long-horizon request land in different
    compatibility classes, which is what forces a second wave (or a
    preemption) instead of a same-wave splice."""

    def __init__(self, **kw):
        self.pack_gate = threading.Event()
        self.started = threading.Event()
        self._sem = threading.Semaphore(0)
        self._flood = threading.Event()
        kw.setdefault("device_sched", True)
        kw.setdefault("horizon_bucket", 16.0)
        kw.setdefault("refill_every", 1)
        kw.setdefault("preempt_quantum", 1)
        super().__init__(**kw)

    def step(self, n=1):
        self._sem.release(n)

    def open_boundaries(self):
        self._flood.set()
        self._sem.release(10 ** 6)

    def _pack_refill(self, lead):
        assert self.pack_gate.wait(120), "pack gate never opened"
        return super()._pack_refill(lead)

    def _refill_boundary(self, wave, n, sims, final=False):
        self.started.set()
        if not self._flood.is_set():
            assert self._sem.acquire(timeout=120), \
                "boundary gate never opened"
        return super()._refill_boundary(wave, n, sims, final=final)


def _release_all(svc):
    svc.pack_gate.set()
    svc.open_boundaries()


# --------------------------------------------------------------------------
# preempt -> evict -> restore, bitwise, both dtype profiles
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "profile",
    [
        "f64",
        # displaced for the qos suite: the f64 twin stays tier-1 and
        # ci.sh "preempt smoke" restores the evicted background bitwise
        # every pass
        pytest.param("f32", marks=pytest.mark.slow),
    ],
)
def test_preempt_restore_bitwise_equals_solo(profile):
    """The headline contract: with one wave slot, a high-priority
    foreign-class request checkpoint-evicts the running background
    wave at a quantum boundary, runs to completion first, and the
    restored background delivers bitwise its direct solo run — on both
    dtype profiles (the checkpoint round-trips the profile's exact
    dtypes)."""
    with config.profile(profile):
        spec = _tiny_spec()
        cache = pc.ProgramCache(capacity=64)
        svc = _GatedSched(
            max_wave=8, cache=cache, pad_waves=False,
            waves_per_device=1,
        )
        try:
            bg = svc.submit(_req(
                spec, 4, seed=1, t_end=40.0, priority=0, label="bg",
            ))
            svc.pack_gate.set()
            assert svc.started.wait(120)
            # background is parked at its first boundary; the urgent
            # (bucket 0 vs the background's bucket 2 — a different
            # class) must preempt, not splice
            ur = svc.submit(_req(
                spec, 4, seed=2, t_end=6.0, priority=10, label="ur",
            ))
            svc.open_boundaries()
            r_ur = ur.result(300)
            bg_done_at_urgent = bg.done()
            r_bg = bg.result(300)
            st = svc.stats()["device_sched"]
        finally:
            _release_all(svc)
            svc.shutdown()
        assert st["preemptions"] >= 1, st
        assert st["evictions"] >= 1
        assert st["restores"] >= 1
        assert st["sched_waves_started"] == 2
        # the urgent class really did run FIRST: the background (its
        # ~41 chunks preempted after at most one quantum) was still
        # unfinished when the urgent result landed
        assert not bg_done_at_urgent
        _assert_results_equal(
            r_bg, _direct(spec, 4, cache, seed=1, t_end=40.0)
        )
        _assert_results_equal(
            r_ur, _direct(spec, 4, cache, seed=2, t_end=6.0)
        )


# --------------------------------------------------------------------------
# concurrent waves: urgent admitted while the background wave is live
# --------------------------------------------------------------------------


def test_urgent_second_wave_while_background_live(tiny, shared_cache):
    """With ``waves_per_device=2`` the urgent foreign-class request is
    admitted as a SECOND concurrent wave — zero preemptions, urgent
    completes while the background is still mid-flight, both bitwise."""
    spec, cache = tiny, shared_cache
    svc = _GatedSched(
        max_wave=8, cache=cache, pad_waves=False, waves_per_device=2,
    )
    try:
        bg = svc.submit(_req(
            spec, 4, seed=3, t_end=40.0, priority=0, label="bg",
        ))
        svc.pack_gate.set()
        assert svc.started.wait(120)
        ur = svc.submit(_req(
            spec, 4, seed=4, t_end=6.0, priority=10, label="ur",
        ))
        svc.open_boundaries()
        r_ur = ur.result(300)
        bg_done_at_urgent = bg.done()
        r_bg = bg.result(300)
        st = svc.stats()["device_sched"]
    finally:
        _release_all(svc)
        svc.shutdown()
    assert st["sched_waves_started"] == 2, st
    assert st["preemptions"] == 0, st
    assert not bg_done_at_urgent
    _assert_results_equal(
        r_bg, _direct(spec, 4, cache, seed=3, t_end=40.0)
    )
    _assert_results_equal(
        r_ur, _direct(spec, 4, cache, seed=4, t_end=6.0)
    )


# --------------------------------------------------------------------------
# preempt-during-refill: the ownership table survives evict/restore
# --------------------------------------------------------------------------


@pytest.mark.slow  # displaced for the qos suite: ci.sh "preempt smoke" evicts and bitwise-restores a refill_every=1 background wave every pass
def test_preempt_during_refill_ownership_survives(tiny, shared_cache):
    """The refill satellite: a wave that has already delivered one
    member mid-wave AND boundary-spliced a queued request is then
    preempted.  After restore, retirements and mid-wave deliveries
    resume exactly where they left off — the ``_RefillWave`` host-side
    ownership table rides the eviction untouched — and every member is
    bitwise its direct run."""
    spec, cache = tiny, shared_cache
    svc = _GatedSched(
        max_wave=8, cache=cache, pad_waves=True, waves_per_device=1,
    )
    try:
        lead = svc.submit(_req(
            spec, 3, seed=5, t_end=13.0, priority=0, label="lead",
        ))
        short = svc.submit(_req(
            spec, 2, seed=6, t_end=2.0, priority=0, label="short",
        ))
        svc.pack_gate.set()
        assert svc.started.wait(120)
        # parked at boundary 1: queue the same-bucket splice, then let
        # boundaries run until it is admitted into the pad headroom
        splice = svc.submit(_req(
            spec, 2, seed=7, t_end=5.0, priority=0, label="splice",
        ))
        deadline = time.monotonic() + 120
        while (svc.stats()["refill"]["refill_admissions"] < 1
               and time.monotonic() < deadline):
            svc.step()
            time.sleep(0.01)
        assert svc.stats()["refill"]["refill_admissions"] >= 1
        # now preempt the whole (lead + splice) wave with an urgent
        # foreign-bucket class; flood the remaining boundaries
        ur = svc.submit(_req(
            spec, 2, seed=8, t_end=40.0, priority=10, label="ur",
        ))
        svc.open_boundaries()
        results = {
            "ur": (ur.result(300), 8, 40.0, 2),
            "lead": (lead.result(300), 5, 13.0, 3),
            "short": (short.result(300), 6, 2.0, 2),
            "splice": (splice.result(300), 7, 5.0, 2),
        }
        st = svc.stats()
    finally:
        _release_all(svc)
        svc.shutdown()
    ds = st["device_sched"]
    assert ds["preemptions"] >= 1 and ds["restores"] >= 1, ds
    assert st["refill"]["refill_admissions"] >= 1
    # short retired before the wave did; splice delivered after restore
    assert st["refill"]["mid_wave_deliveries"] >= 2, st["refill"]
    assert st["completed"] == 4
    for label, (res, seed, t_end, R) in results.items():
        _assert_results_equal(
            res, _direct(spec, R, cache, seed=seed, t_end=t_end)
        )


# --------------------------------------------------------------------------
# restore order: priority, not eviction order
# --------------------------------------------------------------------------


def test_two_evicted_waves_restore_in_priority_order(tiny, shared_cache):
    """With TWO preempted waves parked, the freed slot goes to the
    higher-priority one — priority order (max live-member priority),
    NOT eviction order: the mid-priority wave evicted LAST still comes
    back before the background wave evicted first.  Observed through
    completion order under ``waves_per_device=1``: the background
    cannot even restore until the mid wave retires."""
    spec, cache = tiny, shared_cache
    svc = _GatedSched(
        max_wave=8, cache=cache, pad_waves=False, waves_per_device=1,
    )
    try:
        bg = svc.submit(_req(
            spec, 4, seed=20, t_end=300.0, priority=0, label="bg",
        ))
        svc.pack_gate.set()
        assert svc.started.wait(120)
        # bucket ladder (16.0): 300 / 40 / 6 are three distinct
        # classes, so each request is its own wave and the priority
        # ladder forces two stacked preemptions
        mid = svc.submit(_req(
            spec, 4, seed=21, t_end=40.0, priority=5, label="mid",
        ))
        deadline = time.monotonic() + 120
        while (svc.stats()["device_sched"]["preemptions"] < 1
               and time.monotonic() < deadline):
            svc.step()
            time.sleep(0.01)
        assert svc.stats()["device_sched"]["preemptions"] >= 1
        ur = svc.submit(_req(
            spec, 4, seed=22, t_end=6.0, priority=10, label="ur",
        ))
        svc.open_boundaries()
        r_ur = ur.result(300)
        r_mid = mid.result(300)
        bg_done_at_mid = bg.done()
        r_bg = bg.result(300)
        st = svc.stats()["device_sched"]
    finally:
        _release_all(svc)
        svc.shutdown()
    assert st["sched_waves_started"] == 3, st
    assert st["preemptions"] >= 2 and st["restores"] >= 2, st
    # priority order: mid (restored ahead of bg) finished while the
    # first-evicted background was still unfinished
    assert not bg_done_at_mid
    _assert_results_equal(
        r_ur, _direct(spec, 4, cache, seed=22, t_end=6.0)
    )
    _assert_results_equal(
        r_mid, _direct(spec, 4, cache, seed=21, t_end=40.0)
    )
    _assert_results_equal(
        r_bg, _direct(spec, 4, cache, seed=20, t_end=300.0)
    )


# --------------------------------------------------------------------------
# memory-aware admission: structured backpressure
# --------------------------------------------------------------------------


def test_memory_budget_rejection_structured(tiny, shared_cache):
    """A request whose estimated wave footprint exceeds the WHOLE
    budget fails fast with ``MemoryBudgetExceeded`` carrying the
    needed/budget byte counts — structured backpressure, counted in
    ``mem_rejects``, outcome ``failed`` — and the service keeps
    serving (a fitting request completes afterwards)."""
    spec, cache = tiny, shared_cache
    with serve.Service(
        device_sched=True, max_wave=8, cache=cache,
        horizon_bucket=None, mem_budget_bytes=16,
    ) as svc:
        doomed = svc.submit(_req(spec, 4, seed=9, t_end=4.0))
        with pytest.raises(serve.MemoryBudgetExceeded) as ei:
            doomed.result(120)
        assert ei.value.budget_bytes == 16
        assert ei.value.needed_bytes > 16
        assert isinstance(ei.value, serve.ServeError)
        st = svc.stats()
        assert st["device_sched"]["mem_rejects"] == 1
        assert st["failed"] == 1
    with serve.Service(
        device_sched=True, max_wave=8, cache=cache,
        horizon_bucket=None,
    ) as svc:
        ok = svc.submit(_req(spec, 4, seed=9, t_end=4.0))
        _assert_results_equal(
            ok.result(300), _direct(spec, 4, cache, seed=9, t_end=4.0)
        )


# --------------------------------------------------------------------------
# span hygiene across preemption
# --------------------------------------------------------------------------


def test_span_tree_closes_once_including_preempted(
    tiny, shared_cache, tmp_path,
):
    """Every outcome closes its span tree exactly once — including a
    wave that was preempted and restored mid-request — and the span
    log carries the ``preempt``/``restore`` instants."""
    from cimba_tpu.obs import telemetry as tm

    spec, cache = tiny, shared_cache
    tel = tm.Telemetry(
        interval=0, spans=True, span_path=tmp_path / "spans.jsonl",
    )
    svc = _GatedSched(
        max_wave=8, cache=cache, pad_waves=False, waves_per_device=1,
        telemetry=tel,
    )
    try:
        bg = svc.submit(_req(
            spec, 4, seed=10, t_end=40.0, priority=0, label="bg",
        ))
        svc.pack_gate.set()
        assert svc.started.wait(120)
        ur = svc.submit(_req(
            spec, 4, seed=11, t_end=6.0, priority=10, label="ur",
        ))
        svc.open_boundaries()
        assert ur.result(300) is not None
        assert bg.result(300) is not None
        st = svc.stats()["device_sched"]
    finally:
        _release_all(svc)
        svc.shutdown()
    assert st["preemptions"] >= 1 and st["restores"] >= 1, st
    assert tel.spans.open_count() == 0
    assert (
        tel.spans.counters["traces_started"]
        == tel.spans.counters["traces_ended"]
        == 2
    )
    log = (tmp_path / "spans.jsonl").read_text()
    assert '"preempt"' in log and '"restore"' in log
    tel.close()


# --------------------------------------------------------------------------
# the device_sched trace gate + knob registration
# --------------------------------------------------------------------------


def test_device_sched_knob_registered_and_gated():
    """CIMBA_DEVICE_SCHED is in ``config.ENV_KNOBS`` as a trace gate
    and the check/gates.py registry carries exactly one
    ``device_sched`` gate — registry pins only (cheap); the actual
    inertness sweep compiles and runs in the slow twin below and in
    every ``tools/ci.sh`` static-analysis pass."""
    from cimba_tpu.check import gates as G

    ds_gates = [g for g in G.GATES if g.name == "device_sched"]
    assert len(ds_gates) == 1
    assert ds_gates[0].env == ("CIMBA_DEVICE_SCHED",)
    assert "CIMBA_DEVICE_SCHED" in G.claimed_env_knobs()
    assert config.ENV_KNOBS["CIMBA_DEVICE_SCHED"]["trace_gate"] is True


@pytest.mark.slow
def test_device_sched_gate_off_is_baseline():
    """The ``device_sched`` gate sweep: CIMBA_DEVICE_SCHED never binds
    into a traced chunk program — explicit-off, ambient-set, and
    env-off arms are all character-identical to the baseline, both
    profiles (scheduling is a host-side dispatch policy).  slow: every
    ``tools/ci.sh`` static-analysis cell runs this sweep too."""
    from cimba_tpu.check import gates as G

    findings, report = G.sweep(
        gates=[g for g in G.GATES if g.name == "device_sched"],
        model="tiny",
    )
    assert not findings, findings
    for prof in ("f64", "f32"):
        assert "ambient-inert" in report[f"device_sched/{prof}"]
        assert "env-off==off" in report[f"device_sched/{prof}"]


def test_device_sched_env_knob_resolves_service_default(
    shared_cache, monkeypatch,
):
    """``Service(device_sched=None)`` defers to CIMBA_DEVICE_SCHED;
    explicit arguments win either way."""
    monkeypatch.delenv("CIMBA_DEVICE_SCHED", raising=False)
    with serve.Service(max_wave=4, cache=shared_cache) as svc:
        assert svc.device_sched is False
        assert svc.stats()["device_sched"]["enabled"] is False
    monkeypatch.setenv("CIMBA_DEVICE_SCHED", "1")
    with serve.Service(max_wave=4, cache=shared_cache) as svc:
        assert svc.device_sched is True
    with serve.Service(
        max_wave=4, cache=shared_cache, device_sched=False,
    ) as svc:
        assert svc.device_sched is False


# --------------------------------------------------------------------------
# autotuner fold: Schedule format, canonical collapse, adoption
# --------------------------------------------------------------------------


def test_schedule_knobs_roundtrip_resolve_and_adoption(shared_cache):
    """The three scheduler knobs ride the tuned-schedule plane:
    versioned JSON round-trip, canonical collapse at the defaults,
    ``resolve_entry`` surfacing them in ``applied``/``block()``, and
    ``Service._adopt_sched_knobs`` taking them only where the
    constructor left None (explicit wins, first adoption sticks)."""
    from cimba_tpu.tune import registry as reg
    from cimba_tpu.tune import space

    assert space.SCHEDULE_FORMAT == 5
    s = space.Schedule(
        waves_per_device=4, preempt_quantum=16, mem_fraction=0.5,
    )
    rt = space.Schedule.from_json(s.to_json())
    assert rt.waves_per_device == 4
    assert rt.preempt_quantum == 16
    assert rt.mem_fraction == 0.5
    # at the defaults the knobs collapse to canonical None — one
    # representation per policy, digests stable
    c = space.Schedule(
        waves_per_device=space.DEFAULT_WAVES_PER_DEVICE,
        preempt_quantum=space.DEFAULT_PREEMPT_QUANTUM,
        mem_fraction=space.DEFAULT_MEM_FRACTION,
    ).canonical()
    assert c.waves_per_device is None
    assert c.preempt_quantum is None
    assert c.mem_fraction is None
    assert c.digest() == space.Schedule().canonical().digest()
    # the search space carries the axes only when asked
    assert space.default_space().waves_per_device == ()
    assert space.default_space(device_sched=True).waves_per_device
    # resolve_entry folds them into applied + the audit block
    spec = _tiny_spec()
    rs = reg.resolve_entry(spec, 8, schedule=s)
    assert rs.applied["waves_per_device"] == 4
    assert rs.applied["preempt_quantum"] == 16
    assert rs.applied["mem_fraction"] == 0.5
    assert rs.block()["knobs"]["waves_per_device"] == 4
    # adoption: None constructor knobs take the schedule's values;
    # explicit ones keep theirs; the first adoption sticks
    with serve.Service(
        max_wave=4, cache=shared_cache, device_sched=False,
        preempt_quantum=32,
    ) as svc:
        svc._adopt_sched_knobs(s)
        assert svc._waves_per_device == 4
        assert svc._preempt_quantum == 32      # explicit wins
        assert svc._mem_fraction == 0.5
        svc._adopt_sched_knobs(space.Schedule(waves_per_device=1))
        assert svc._waves_per_device == 4      # first adoption sticks


# --------------------------------------------------------------------------
# the footprint ladder + the store manifest satellite
# --------------------------------------------------------------------------


def test_wave_footprint_ladder_and_store_manifest(tiny, tmp_path):
    """``wave_footprint_bytes`` returns a positive, memoized estimate;
    ``_memory_analysis_bytes`` sums what the backend exposes; the
    format-2 store manifest persists measured ``footprint_bytes`` and
    a hydrated chunk program surfaces it through ``footprint_for``."""
    from cimba_tpu.serve import store as ps

    spec = tiny
    programs: dict = {}
    fp = pc.wave_footprint_bytes(
        programs, spec, mesh=None, chunk_steps=4,
        with_metrics=False, lanes=8, params=(), n_replications=8,
    )
    assert isinstance(fp, int) and fp > 0
    n_keys = len(programs)
    fp2 = pc.wave_footprint_bytes(
        programs, spec, mesh=None, chunk_steps=4,
        with_metrics=False, lanes=8, params=(), n_replications=8,
    )
    assert fp2 == fp and len(programs) == n_keys   # memoized
    # a wider wave can only cost more
    fp_wide = pc.wave_footprint_bytes(
        programs, spec, mesh=None, chunk_steps=4,
        with_metrics=False, lanes=64, params=(), n_replications=64,
    )
    assert fp_wide > fp

    class _MA:
        temp_size_in_bytes = 100
        output_size_in_bytes = 20
        argument_size_in_bytes = 3

    assert pc._memory_analysis_bytes(_MA()) == 123
    assert pc._memory_analysis_bytes(None) is None

    # the manifest satellite: measured footprints persist (format 2,
    # device ids since format 3) and ride hydration
    assert ps.FORMAT == 3
    store = ps.ProgramStore(tmp_path / "store")
    report = store.save_programs(
        spec, (), 8, wave_sizes=(8,), chunk_steps=4,
        with_metrics=False, horizon_modes=("none",), summary_paths=(),
    )
    recs = [
        p for p in report["programs"] if p["role"] in ("init", "chunk")
    ]
    assert recs, report
    for rec in recs:
        # CPU PjRt implements memory_analysis(), so the footprint is
        # measured and positive here
        assert rec.get("footprint_bytes", 0) > 0, rec
        # ... and the executable's devices, for execution_devices at load
        assert rec["devices"] == [jax.devices()[0].id], rec
    hyd = store.hydrate(
        spec, chunk_steps=4, with_metrics=False,
    )
    assert hyd is not None
    # the hydrated chunk program carries the measured table under the
    # same args-sig digests its dispatch table uses — the
    # ``footprint_for`` lookup (cache rung 1) hits for every stored
    # shape and misses cleanly for an unseen one
    fps = hyd.chunk._footprints
    assert fps and all(
        isinstance(v, int) and v > 0 for v in fps.values()
    ), fps
    assert set(fps) <= set(hyd.chunk._table)
    assert hyd.chunk.footprint_for(np.zeros((3, 3))) is None


# --------------------------------------------------------------------------
# soak
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_device_sched_soak_mixed_priorities(tiny, shared_cache):
    """Soak: a free-running scheduler under a burst of mixed-priority,
    mixed-horizon requests (repeated preempt/restore churn across two
    wave slots) — every one of them bitwise its direct run."""
    spec, cache = tiny, shared_cache
    rng = np.random.RandomState(0)
    with serve.Service(
        device_sched=True, max_wave=8, cache=cache, pad_waves=False,
        horizon_bucket=16.0, refill_every=1, waves_per_device=2,
        preempt_quantum=1,
    ) as svc:
        futs = []
        for i in range(24):
            seed = 100 + i
            t_end = float(rng.choice([4.0, 40.0, 300.0]))
            prio = int(rng.choice([0, 5, 10]))
            futs.append((
                svc.submit(_req(
                    spec, 4, seed=seed, t_end=t_end, priority=prio,
                    label=f"r{i}",
                )),
                seed, t_end,
            ))
        for fut, seed, t_end in futs:
            _assert_results_equal(
                fut.result(600),
                _direct(spec, 4, cache, seed=seed, t_end=t_end),
            )
        st = svc.stats()
        assert st["completed"] == 24
        assert st["device_sched"]["sched_waves_started"] >= 2
