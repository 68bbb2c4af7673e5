"""Benchmark battery: one JSON line per BASELINE.json config.

Headline (no args) = M/M/1 events/second (reference: benchmark/MM1_multi).
Reference ground truth (BASELINE.md): 100 trials x 1e6 objects in 0.56 s on
a 64-core Threadripper 3970X ~= 375M events/s aggregate (~2.1 events per
object).  ``vs_baseline`` is the ratio of this machine's events/s to that
aggregate; the north star is >= 10.

``--config {mm1,mm1_stream,mm1_single,serve,serve_cold,serve_fleet,serve_mixed,serve_refill,serve_fused,serve_qos,mmc,mg1,sweep,tandem,tune,jobshop,awacs,compile_wall}``
runs one named config (``serve`` is the open-loop serving-layer load,
docs/13_serving.md; ``serve_cold`` measures cold-start time-to-first-
result with and without a hydrated AOT program store,
docs/15_program_store.md; ``serve_fleet``, the multi-process fleet,
stops with an error until its slices can be pinned one per chip
(ROADMAP R5), docs/20_fleet.md; ``serve_mixed`` is the heterogeneous-traffic
mix measuring wave-packing occupancy and padding waste,
docs/14_wave_packing.md; ``serve_qos`` is the adversarial
multi-tenant flood measuring victim-tail protection under
weighted-fair lane shares + rate limits, docs/27_qos.md;
``sweep`` races fixed-R against adaptive-R
sequential stopping on the M/G/1 grid, docs/16_sweeps.md; ``tandem``
is the two-station Jackson network over its scenario grid; ``tune``
runs the schedule-autotuner search on mm1 + the step probe and
reports winner-vs-default speedup with the noise floor alongside,
docs/21_autotune.md);
``--config all`` runs the whole battery but ``serve_cold`` and
``serve_fleet`` (whose children need the chip), one JSON line each
(BASELINE.json configs[0..4]).  Only mm1 has a published machine-wide rate, so only mm1
reports a non-null vs_baseline; the others carry the published reference
wall-clock (where any exists) in ``detail`` for context.

Replications are vmapped lanes on one chip (and would shard over a mesh on
a pod — see __graft_entry__.dryrun_multichip).  Workloads are sized for
the chip, bounded by the ~3 min device-program watchdog (BENCH_NOTES.md).
The *rate* is the metric and is workload-size independent once the loop is
warm.

The bench measures a TPU or nothing: with no chip it exits non-zero and
prints no number (there is no CPU fallback).  One process holds the chip;
the configs that start children (``serve_cold``, ``serve_fleet``) keep
their parent off jax.
"""

import argparse
import json
import os
import subprocess
import sys
import time

BASELINE_EVENTS_PER_SEC = 375e6  # 64-core reference aggregate

import jax
import jax.numpy as jnp

from cimba_tpu.core import loop as cl


def _scale(r_default, n_default):
    """Backend-sized defaults with the standard env overrides applied
    (CIMBA_BENCH_R lanes, CIMBA_BENCH_OBJECTS per-lane workload) — every
    config honors them, e.g. for dodging the device watchdog on slow
    machines."""
    return (
        int(os.environ.get("CIMBA_BENCH_R", r_default)),
        int(os.environ.get("CIMBA_BENCH_OBJECTS", n_default)),
    )


def _bench_profile():
    """Dtype profile for the accelerator battery: **f32** — the TPU-native
    width and the same profile the Pallas kernel path requires (Mosaic has
    no 64-bit types); its statistics are pinned against theory and the f64
    scalar-oracle path in tests/ (test_mm1, test_kernel_fuzz).  The mm1
    headline also measures and reports the exact-f64 rate alongside
    (``detail.exact_f64_events_per_sec``) so the double-width number the
    reference's benchmark uses is never hidden.  Override:
    ``CIMBA_BENCH_PROFILE={f32,f64}``."""
    return os.environ.get("CIMBA_BENCH_PROFILE") or "f32"


def _time_vmapped(spec, init_one, R, warm_args, real_args, pack=None):
    """jit(vmap(run ∘ init)), warm up on tiny traced workload args (same
    shapes → one compile), then time the real workload.  Returns
    (total_events, failed_lanes, wall_s).  Call under the same
    ``config.profile`` the spec was built under — dtypes bind at trace
    time, which happens inside this function.  ``pack`` selects the
    while-loop carry layout (see loop.make_run; None = backend auto)."""
    run = cl.make_run(spec, pack=pack, batched=True)

    def experiment(args):
        def one(rep):
            return run(init_one(rep, args))

        sims = jax.vmap(one)(jnp.arange(R))
        return (
            # n_events is i32 under the f32 profile: sum in i64 so wide
            # batteries (131072 lanes x 1000+ events) cannot wrap
            jnp.sum(sims.n_events.astype(jnp.int64)),
            jnp.sum((sims.err != 0).astype(jnp.int32)),
        )

    fn = jax.jit(experiment)
    jax.block_until_ready(fn(warm_args))
    t0 = time.perf_counter()
    events, failed = jax.block_until_ready(fn(real_args))
    wall = time.perf_counter() - t0
    return int(events), int(failed), wall


# the battery's telemetry plane (obs/telemetry.py — stdlib-only, no
# sampler thread: interval=0 means on-demand only): the watchdog reads
# its heartbeat ages, progress hooks tick it, and every config line
# embeds its compact snapshot.  This replaced the old module-global
# `_last_activity` timestamp — one liveness mechanism for bench, serve,
# and the exposition endpoints instead of three.
from cimba_tpu.obs import telemetry as _telemetry  # noqa: E402

_TEL = _telemetry.Telemetry(interval=0.0, autostart=False)
_TEL.heartbeat("bench")  # the battery is alive at import

def _watchdog(which):
    """A hung device call blocks ``block_until_ready`` forever, which
    would leave the run with NO output line at all.  This daemon thread
    guarantees a structured failure line instead: if no progress
    heartbeat lands for CIMBA_BENCH_DEADLINE seconds (default 40 min),
    it prints a line with no value and hard-exits (a thread blocked in a
    device call cannot be interrupted; ``os._exit`` is the only way
    out).  No jax call in the thread: it may be what hangs."""
    import threading

    deadline = int(os.environ.get("CIMBA_BENCH_DEADLINE", "2400"))
    if deadline <= 0:
        return
    metric = ("mm1" if which == "all" else which) + "_events_per_sec"
    line = {
        "metric": metric,
        "value": None,
        "unit": "events/s",
        "vs_baseline": None,
        "detail": {
            "error": f"no progress for {deadline}s — device hang mid-run",
        },
    }

    def run():
        while True:
            time.sleep(30)
            # freshest heartbeat across every source (config lines,
            # wave/chunk/round ticks, serve dispatch) — the deadline
            # measures INACTIVITY, not one config's honest wall time
            if _TEL.heartbeat_age() > deadline:
                print(json.dumps(line), flush=True)
                os._exit(2)

    threading.Thread(target=run, daemon=True).start()


def _obs_section():
    """The BENCH_*.json ``metrics`` section: a small SEPARATE run with the
    obs registry enabled (attaching it to the timed run would change the
    jaxpr being benchmarked — observability is zero-op only when off),
    reported through ``run_experiment(with_report=True)`` so the section
    carries the compile-vs-execute wall split and device memory stats
    alongside the dispatcher metrics.  Disable with CIMBA_BENCH_METRICS=0."""
    from cimba_tpu.models import mm1
    from cimba_tpu.obs import metrics as om
    from cimba_tpu.runner import experiment as ex

    R = int(os.environ.get("CIMBA_BENCH_METRICS_R", "8"))
    N = int(os.environ.get("CIMBA_BENCH_METRICS_OBJECTS", "200"))
    om.enable()
    try:
        spec, _ = mm1.build(record=False)
        _, report = ex.run_experiment(
            spec, mm1.params(N), R, seed=2026, with_report=True
        )
        out = report.to_dict()
        out["note"] = (
            "separate metrics-enabled probe run (R=%d, N=%d) — the timed "
            "headline runs with observability off (zero-op contract)"
            % (R, N)
        )
        return out
    finally:
        om.disable()


def _device():
    """The device every number was measured on, as jax reports it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def _require_tpu():
    """The bench measures a TPU or nothing: no CPU fallback."""
    dev = _device()
    if dev["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU (default device: {dev['platform']}); bench.py "
            "measures the chip or nothing"
        )


def _line(metric, rate, vs_baseline, detail, unit=None, device=None):
    """Print one config line.  ``device``: where the number was measured,
    when that was not this process (serve_cold's children)."""
    _heartbeat()
    dev = device or _device()
    detail["backend"] = dev["platform"]
    detail["device_kind"] = dev["kind"]
    detail["device_count"] = dev["count"]
    line = {
        "metric": metric,
        "value": rate,
        "unit": unit or "events/s",
        "vs_baseline": vs_baseline,
        "detail": detail,
    }
    if (
        metric == "mm1_events_per_sec"
        and os.environ.get("CIMBA_BENCH_METRICS", "1") != "0"
    ):
        # the observability story rides the headline line: dispatcher
        # metrics + profiling split from a small separate probe run
        try:
            line["metrics"] = _obs_section()
        except Exception as e:  # the probe must never kill the headline
            line["metrics"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    # Headline honesty: masked lane failures are an estimator-bias
    # signal, not a detail — surface them at the top level (0 on every
    # healthy run; the fixed-capacity trade is documented in
    # models/mm1.py:38-47 with the stationary overflow probability)
    if "failed_replications" in detail:
        line["failed_replications"] = detail["failed_replications"]
        if detail["failed_replications"]:
            line["bias_note"] = (
                "failed replications are masked out of the pooled "
                "estimate (fixed-capacity overflow, P~1.4e-6/event for "
                "the mm1 ring at rho=0.9); regrow detail reports the "
                "unbiased re-run where attempted"
            )
    # the per-battery telemetry snapshot (docs/17_telemetry.md):
    # heartbeat ages and progress-tick counters accumulated since the
    # battery started — how live the run was, not just how fast
    line["telemetry"] = _TEL.snapshot()
    # provenance: with CIMBA_BENCH_RUN_CARD=<dir>, every battery line
    # also lands as a content-addressed run card (docs/18_audit.md) —
    # the env block + full line, so a BENCH number is citable against
    # the process that produced it (tools/bench_history.py collates)
    card_dir = os.environ.get("CIMBA_BENCH_RUN_CARD")
    if card_dir:
        try:
            from cimba_tpu.obs import audit as _audit

            card = _audit.run_card(
                "bench",
                label=metric,
                geometry={"metric": metric, "unit": line["unit"]},
                extra={
                    "value": rate,
                    "vs_baseline": vs_baseline,
                    "detail": detail,
                },
                telemetry=line["telemetry"],
            )
            line["run_card"] = _audit.write_run_card(card, card_dir)
        except Exception as e:  # a card bug must never kill the line
            line["run_card_error"] = f"{type(e).__name__}: {e}"[:200]
    print(json.dumps(line), flush=True)


def _regrow_pass(spec, params, R, t_end=None):
    """Unbiased re-run through the capacity escape hatch, attached to a
    config's detail whenever the timed run masked failures: doubling
    re-runs the whole batch (healthy lanes reproduce bit-identically —
    counter-derived streams), so ``failed_after`` tells whether the
    failures were growable capacity (event table) or a structural cap
    (e.g. the documented mm1 ring trade, models/mm1.py:38-47)."""
    import numpy as np

    from cimba_tpu.runner import experiment as ex

    t0 = time.perf_counter()
    try:
        res, final_spec, n_regrows = ex.run_experiment_regrow(
            spec, params, R, seed=2026, t_end=t_end
        )
    except RuntimeError as e:  # overflow persisted through max doublings
        return {"error": str(e)[:200]}
    wall = time.perf_counter() - t0
    err = np.asarray(res.sims.err)
    return {
        "n_regrows": n_regrows,
        "event_cap_final": final_spec.event_cap,
        "failed_after": int((err != 0).sum()),
        "total_events": int(np.asarray(res.sims.n_events).sum()),
        "wall_s": wall,
    }


def _kernel_mesh():
    """CIMBA_BENCH_MESH=1 on a multi-chip host: shard lanes over all
    devices (per-device chunk kernels under shard_map + lockstep host
    loop) — the single command for the v5e-8 number."""
    if os.environ.get("CIMBA_BENCH_MESH") and jax.device_count() > 1:
        from jax.sharding import Mesh as _Mesh

        return _Mesh(jax.devices(), ("rep",))
    return None


def _time_kernel(spec, make_batch, warm_arg, real_arg, chunk, mesh=None):
    """Warm-compile + time the Pallas kernel path on a vmapped-init
    batch; returns (events, failed, wall).  Shared by every config that
    rides the kernel so the warm-up/timing protocol cannot diverge."""
    from cimba_tpu.core import pallas_run as _pr

    krun = _pr.make_kernel_run(spec, chunk_steps=chunk, mesh=mesh)
    fn = jax.jit(make_batch)
    jax.block_until_ready(jax.tree.leaves(krun(fn(warm_arg))))
    sims = fn(real_arg)
    jax.block_until_ready(jax.tree.leaves(sims))
    t0 = time.perf_counter()
    out = krun(sims)
    jax.block_until_ready(jax.tree.leaves(out))
    wall = time.perf_counter() - t0
    return int(out.n_events.sum()), int((out.err != 0).sum()), wall


def bench_mm1():
    """BASELINE configs[0]: M/M/1 single-server queue.

    TPU note (measured, v5e, round 2): the rate saturates at R~1024 and the
    device program's wall time grows linearly with R*N beyond that; a
    single while_loop running >~3 min trips the runtime watchdog
    (UNAVAILABLE "kernel fault").  See BENCH_NOTES.md for the scaling
    curve.

    With no ``CIMBA_BENCH_KERNEL`` set, both paths run in THIS process
    (one process per chip): the Pallas kernel arm and the XLA arms, and
    the faster is the headline with the other's rate in detail.  A
    kernel failure fails the run — it is never swapped for the XLA arm
    in silence (Mosaic refusals are caught before a chip run by the
    offline compiles in tests/test_mosaic_aot.py)."""
    # Operating point of the 2026-07-31 scaling campaign (BENCH_NOTES.md):
    # R=131072 lanes was the throughput peak (262144 regressed), and
    # long per-lane workloads amortize warm-up and the lane-finish tail.
    R, N = _scale(131072, 16000)
    kern_env = os.environ.get("CIMBA_BENCH_KERNEL")
    prof = _bench_profile()
    if kern_env and kern_env != "0":
        rate, detail = _mm1_kernel(R, N)
        _line("mm1_events_per_sec", rate, rate / BASELINE_EVENTS_PER_SEC,
              detail)
        return
    if kern_env is not None or prof == "f64":
        # the kernel path is f32-only (Mosaic has no 64-bit types): an
        # exact-profile request never auto-selects an f32 measurement
        rate, detail = _mm1_xla_arms(R, N, prof)
        if prof == "f32":
            _attach_f64_twin(detail, R, N)
        _line("mm1_events_per_sec", rate, rate / BASELINE_EVENTS_PER_SEC,
              detail)
        return

    # auto-select: the kernel arm at its own (lane-capped) operating
    # point, then the XLA arms at the headline point
    # (per-lane workload capped at 2000 unless CIMBA_BENCH_OBJECTS says
    # otherwise: each chunk of 512 events/lane is one dispatch)
    k_rate, k_detail = _mm1_kernel(
        R, int(os.environ.get("CIMBA_BENCH_OBJECTS", 2000))
    )
    xla_rate, xla_detail = _mm1_xla_arms(R, N, prof)
    _attach_f64_twin(xla_detail, R, N)
    k_r = k_detail["replications"]
    k_n = k_detail["objects_per_replication"]
    for d in (xla_detail, k_detail):
        d["xla_arm"] = {"replications": R, "objects_per_replication": N,
                        "events_per_sec": xla_rate}
        d["kernel_arm"] = {"replications": k_r,
                           "objects_per_replication": k_n,
                           "events_per_sec": k_rate}
    xla_cmp = xla_rate
    if (k_r, k_n) != (R, N) and k_rate * 2 >= xla_rate:
        # kernel within 2x: decide at the SAME operating point —
        # re-measure the XLA arm at the kernel's (R, N)
        xla_cmp, _ = _mm1_xla_arms(k_r, k_n, prof, stream=False)
        for d in (xla_detail, k_detail):
            d["xla_at_kernel_point"] = {
                "replications": k_r, "objects_per_replication": k_n,
                "events_per_sec": xla_cmp,
            }
    if k_rate > xla_cmp:
        for k in ("dispatch_arms",) + _F64_TWIN_KEYS:
            if k in xla_detail:
                k_detail[k] = xla_detail[k]
        rate, detail = k_rate, k_detail
    else:
        rate, detail = xla_rate, xla_detail
    _line("mm1_events_per_sec", rate, rate / BASELINE_EVENTS_PER_SEC, detail)


def _mm1_kernel(R, N):
    """Time the Pallas mega-kernel path (f32 profile): whole-run
    stepping in VMEM — the per-event dispatch + HBM cost of the XLA
    while-loop path disappears (core/pallas_run.py).  Lanes cap at the
    largest Mosaic-AOT-verified width (the whole Sim lives in VMEM; the
    XLA path has no such cap).  Returns (rate, detail)."""
    from cimba_tpu import config as _cfg
    from cimba_tpu.models import mm1

    R = min(R, int(os.environ.get("CIMBA_BENCH_KERNEL_RMAX", 8192)))
    chunk = int(os.environ.get("CIMBA_BENCH_KERNEL_CHUNK", 512))
    mesh = _kernel_mesh()
    with _cfg.profile("f32"):
        spec, _ = mm1.build(record=False)

        def batch(n):
            return jax.vmap(
                lambda r: cl.init_sim(spec, 2026, r, mm1.params(n))
            )(jnp.arange(R))

        ev, failed, wall = _time_kernel(spec, batch, 1, N, chunk, mesh)
    return ev / wall, {
        "path": "pallas_kernel",
        "profile": "f32",
        "mesh_devices": mesh.devices.size if mesh else 1,
        "chunk_steps": chunk,
        "replications": R,
        "objects_per_replication": N,
        "total_events": ev,
        "wall_s": wall,
        "failed_replications": failed,
    }


#: detail keys carrying the exact-f64 twin (the both-profiles headline
#: contract, BENCH_NOTES round 5)
_F64_TWIN_KEYS = (
    "exact_f64_events_per_sec",
    "exact_f64_wall_s",
    "exact_f64_failed_replications",
)


class _dispatch_arm:
    """Scoped dispatch-cost layout (docs/11_dispatch_cost.md):
    ``"packed_hier"`` = packed while-loop carry + hierarchical event-set
    minima (the new arm), ``"flat"`` = per-leaf carry + flat-scan oracle
    (the historical arm), ``None`` = the backend-auto defaults.  Both
    arms are trajectory-identical (pinned by tests/test_xla_pack.py and
    tests/test_eventset_hier.py); the bench measures them side by side
    at the SAME R x N so the layout cost is the only variable."""

    def __init__(self, arm):
        self.arm = arm

    def __enter__(self):
        from cimba_tpu import config as _cfg

        self._prev = (_cfg.XLA_PACK, _cfg.EVENTSET_HIER)
        if self.arm == "flat":
            _cfg.XLA_PACK, _cfg.EVENTSET_HIER = False, False
        elif self.arm == "packed_hier":
            _cfg.XLA_PACK, _cfg.EVENTSET_HIER = True, True
        return self

    def __exit__(self, *exc):
        from cimba_tpu import config as _cfg

        _cfg.XLA_PACK, _cfg.EVENTSET_HIER = self._prev


def _arm_repeats():
    """Best-of-k depth for the interleaved arm batteries (matching the
    stream/telemetry arms' CPU-vs-accelerator defaults)."""
    return max(1, int(os.environ.get(
        "CIMBA_BENCH_ARM_REPEATS", "1"
    )))


def _measure_dispatch_arms(spec_of, init_one_of, R, warm_args, real_args,
                           prof):
    """The packed+hierarchical-vs-flat battery on ONE timing
    implementation: ``tune.measure.measure_arms`` (docs/21_autotune.md)
    — each arm's trace+warm is its untimed prepare leg, the timed
    rounds interleave both arms best-of-k at the same args, and the
    watchdog heartbeat refreshes per round.  Returns ``(report,
    {arm: {events, failed, rate, wall_s, compile_s}})``."""
    from cimba_tpu import config as _cfg
    from cimba_tpu.tune import measure as _tm

    fns = {}

    def make(arm):
        def prepare(arm=arm):
            with _cfg.profile(prof), _dispatch_arm(arm):
                spec = spec_of()
                init_one = init_one_of(spec)
                run = cl.make_run(spec, batched=True)

                def experiment(args):
                    def one(rep):
                        return run(init_one(rep, args))

                    sims = jax.vmap(one)(jnp.arange(R))
                    return (
                        jnp.sum(sims.n_events.astype(jnp.int64)),
                        jnp.sum((sims.err != 0).astype(jnp.int32)),
                    )

                fn = jax.jit(experiment)
                jax.block_until_ready(fn(warm_args))
                fns[arm] = fn

        def runf(arm=arm):
            out = fns[arm](real_args)
            jax.block_until_ready(out)
            return {"events": int(out[0]), "failed": int(out[1])}

        return _tm.Arm(name=arm, run=runf, prepare=prepare)

    report = _tm.measure_arms(
        [make("packed_hier"), make("flat")],
        repeats=_arm_repeats(), noise_twin=False,
        on_round=lambda r: _heartbeat(),
    )
    out = {}
    for res in report.arms:
        pay = res.payload or {}
        out[res.name] = {
            "events": pay.get("events"),
            "failed": pay.get("failed"),
            "rate": res.rate(pay.get("events")),
            "wall_s": res.best_wall,
            "compile_s": res.compile_s,
        }
    return report, out


def _mm1_xla_arms(R, N, prof="f64", stream=True):
    """Measure the mm1 XLA path in BOTH dispatch arms at the same
    R x N — interleaved best-of-k through
    ``tune.measure.measure_arms`` (one timing implementation in the
    repo, docs/21_autotune.md); returns (best_rate, detail-of-best)
    with the per-arm numbers under ``detail.dispatch_arms`` and
    (``stream=True``) the chunked/streamed arm at the same R x N under
    ``detail.stream_arm`` (docs/12_streaming.md)."""
    from cimba_tpu import config as _cfg
    from cimba_tpu.models import mm1

    report, measured = _measure_dispatch_arms(
        lambda: mm1.build(record=False)[0],
        lambda spec: (
            lambda rep, n: cl.init_sim(spec, 2026, rep, mm1.params(n))
        ),
        R, jnp.int32(1), jnp.int32(N), prof,
    )
    arms = {
        name: {
            "events_per_sec": m["rate"],
            "wall_s": m["wall_s"],
            "replications": R,
            "objects_per_replication": N,
            "failed_replications": m["failed"],
            "repeats_best_of": report.rounds_done,
        }
        for name, m in measured.items()
    }
    best_arm = max(
        (n for n in measured if measured[n]["rate"]),
        key=lambda n: measured[n]["rate"],
    )
    m = measured[best_arm]
    rate = m["rate"]
    detail = {
        "path": "xla_while",
        "profile": prof,
        "dispatch_arm": best_arm,
        "replications": R,
        "objects_per_replication": N,
        "total_events": m["events"],
        "wall_s": m["wall_s"],
        "failed_replications": m["failed"],
        "dispatch_arms": arms,
    }
    if m["failed"]:
        with _cfg.profile(prof):
            spec, _ = mm1.build(record=False)
            detail["regrow"] = _regrow_pass(spec, mm1.params(N), R)
    if stream and os.environ.get("CIMBA_BENCH_STREAM", "1") != "0":
        try:
            detail["stream_arm"] = _mm1_stream_arm(R, N, prof, rate)
        except Exception as e:  # the arm must never kill the headline
            detail["stream_arm"] = {
                "error": f"{type(e).__name__}: {e}"[:200]
            }
    return rate, detail


def _heartbeat(*_args):
    """Watchdog heartbeat for loop-internal progress: a long streamed
    battery refreshes per wave/chunk, not only per config line — the
    2400 s deadline must measure inactivity, not one config's honest
    wall time (the kernel-child spawn fix of round 6, applied to the
    chunk loop).  Now a telemetry tick (obs/telemetry.py — heartbeat +
    counter): the watchdog reads `_TEL.heartbeat_age()`, any
    runner/serve path given `telemetry=_TEL` refreshes the same
    deadline automatically, and the per-battery snapshot in every
    config line reports how many progress ticks the run produced."""
    _TEL.tick("bench")


def _stream_chunk_default():
    """Default chunk size for the chunked/streamed arms: big enough that
    per-chunk dispatch amortizes, small enough that one chunk's device
    program stays well under the ~3 min runtime watchdog."""
    return int(
        os.environ.get(
            "CIMBA_BENCH_STREAM_CHUNK", "4096"
        )
    )


def _telemetry_overhead_arm(spec, R, wave, chunk, N, cache):
    """Measure the telemetry plane's cost where it claims to be ~free:
    the mm1 stream at the SAME R x N, telemetry+spans ON (sampler
    thread running, per-wave/per-chunk ticks, span JSONL streaming to
    disk) vs OFF, interleaved best-of-k like the chunked arm — on a
    noisy shared host the load difference between two non-interleaved
    runs can dwarf the real tick cost.  The acceptance bar is < 2%
    overhead on the CPU window (docs/17_telemetry.md); the event counts
    of both arms must be EQUAL (telemetry must never perturb programs
    or results — asserted, not assumed)."""
    import tempfile

    from cimba_tpu.models import mm1
    from cimba_tpu.runner import experiment as ex

    from cimba_tpu.tune import measure as _tm

    repeats = max(1, int(os.environ.get(
        "CIMBA_BENCH_TEL_REPEATS", "1"
    )))
    fd, span_path = tempfile.mkstemp(suffix=".spans.jsonl")
    os.close(fd)
    interval = 0.1
    tel = _telemetry.Telemetry(
        interval=interval, spans=True, span_path=span_path,
    )
    tel.start()

    def run_arm(telemetry):
        def run():
            st = ex.run_experiment_stream(
                spec, mm1.params(N), R, wave_size=wave,
                chunk_steps=chunk, seed=2026, program_cache=cache,
                telemetry=telemetry,
            )
            return int(jax.block_until_ready(st.total_events))

        return run

    try:
        # interleaved best-of-k through tune.measure.measure_arms (the
        # one timing implementation, docs/21_autotune.md); the caller's
        # warm cache keeps compiles out of every timed round
        report = _tm.measure_arms(
            [
                _tm.Arm("telemetry_off", run_arm(None)),
                _tm.Arm("telemetry_on", run_arm(tel)),
            ],
            repeats=repeats, noise_twin=False,
            on_round=lambda r: _heartbeat(),
        )
    finally:
        tel.close()
        try:
            with open(span_path) as f:
                span_lines = sum(1 for _ in f)
        finally:
            os.unlink(span_path)
    off = report.arm("telemetry_off")
    on = report.arm("telemetry_on")
    ev_off, ev_on = off.payload, on.payload
    assert ev_on == ev_off, (
        f"telemetry arm changed the event count: {ev_on} != {ev_off} — "
        "telemetry must never perturb programs"
    )
    rate_off = ev_off / off.best_wall
    rate_on = ev_on / on.best_wall
    return {
        "repeats_best_of": report.rounds_done,
        "sampler_interval_s": interval,
        "events_per_sec_off": rate_off,
        "events_per_sec_on": rate_on,
        "overhead_pct": (rate_off - rate_on) / rate_off * 100.0,
        "span_jsonl_lines": span_lines,
        "ticks": {
            k: v for k, v in tel.snapshot()["ticks"].items()
            if k.startswith("stream.")
        },
    }


def _warm_stream(spec, R, wave, chunk, cache):
    """Warm the stream's init/chunk/fold programs at one full wave PLUS
    the ragged remainder shape (when R does not tile into waves): the
    timed stream then reuses every compiled shape — a remainder-shaped
    compile inside the timed region would dominate a CPU measurement.
    Tiny per-lane workload; reuse requires the timed call to pass the
    SAME spec object and cache dict."""
    from cimba_tpu.models import mm1
    from cimba_tpu.runner import experiment as ex

    ex.run_experiment_stream(
        spec, mm1.params(1), wave + R % wave, wave_size=wave,
        chunk_steps=chunk, seed=2026, on_wave=_heartbeat,
        on_chunk=_heartbeat, program_cache=cache,
    )


def _audit_rerun(spec, N, R, wave, chunk, cache, timed_result):
    """One UNTIMED audited re-run of the streamed point (docs/18):
    digest trail + result digest + content-addressed run card written
    to ``CIMBA_BENCH_RUN_CARD`` make the battery's "bitwise" statement
    citable.  Never inside a timed region (audit on costs a digest
    program per chunk).  A card/IO failure degrades to an ``error``
    field — it must never kill the config line — but a digest MISMATCH
    between the audited and timed runs raises: that assert is the
    measurement's integrity, not bookkeeping."""
    from cimba_tpu.models import mm1
    from cimba_tpu.obs import audit as _audit
    from cimba_tpu.runner import experiment as ex

    try:
        aud = _audit.Audit(
            out_dir=os.environ["CIMBA_BENCH_RUN_CARD"],
            label="mm1_stream",
        )
        ast_ = ex.run_experiment_stream(
            spec, mm1.params(N), R, wave_size=wave, chunk_steps=chunk,
            seed=2026, on_wave=_heartbeat, on_chunk=_heartbeat,
            program_cache=cache, audit=aud,
        )
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"[:200]}
    assert (
        _audit.stream_result_digest(timed_result)
        == ast_.audit["result_digest"]
    ), "audited stream re-run diverged from the timed run"
    return {
        "result_digest": ast_.audit["result_digest"],
        "card_digest": ast_.audit["card_digest"],
        "run_card": aud.card_path,
        "trail_chunks": len(ast_.audit["digest_trail"]),
    }


def _mm1_stream_arm(R, N, prof, mono_rate):
    """The chunked + streamed arms at the SAME R x N as the monolithic
    headline (warm-then-time, mirroring ``_time_vmapped``): chunked =
    one donated chunk program re-dispatched by the host
    (loop.make_chunked_run — the watchdog-proof path), streamed = the
    same chunk program fed waves of R/4 lanes with on-device pooled-
    summary folding (runner.run_experiment_stream).

    The chunked arm's overhead is the number the donation contract
    promises stays small (<= ~5% at the CPU default point).  It is
    computed against a monolithic TWIN measured HERE, interleaved
    best-of-``CIMBA_BENCH_STREAM_REPEATS`` with the chunked arm — the
    headline monolithic rate is measured at a different moment in the
    battery, and on a noisy shared host the load difference alone can
    dwarf the real per-chunk cost (the headline rate still rides along
    as ``headline_monolithic_events_per_sec``)."""
    from cimba_tpu import config as _cfg
    from cimba_tpu.models import mm1
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.stats import summary as sm

    chunk = _stream_chunk_default()
    repeats = max(1, int(os.environ.get(
        "CIMBA_BENCH_STREAM_REPEATS", "1"
    )))
    with _cfg.profile(prof):
        spec, _ = mm1.build(record=False)
        crun = cl.make_chunked_run(
            spec, chunk_steps=chunk, poll_every=4, on_chunk=_heartbeat
        )
        mrun = jax.jit(jax.vmap(cl.make_run(spec, batched=True)))
        ijit = jax.jit(
            jax.vmap(
                lambda r, n: cl.init_sim(spec, 2026, r, mm1.params(n)),
                in_axes=(0, None),
            )
        )
        # warm both arms at the real shapes
        jax.block_until_ready(
            jax.tree.leaves(mrun(ijit(jnp.arange(R), jnp.int32(1))))
        )
        jax.block_until_ready(
            jax.tree.leaves(crun(ijit(jnp.arange(R), jnp.int32(1))))
        )
        mono_wall, wall = None, None
        for _ in range(repeats):
            t0 = time.perf_counter()
            mout = mrun(ijit(jnp.arange(R), jnp.int32(N)))
            jax.block_until_ready(jax.tree.leaves(mout))
            dt = time.perf_counter() - t0
            mono_wall = dt if mono_wall is None else min(mono_wall, dt)
            _heartbeat()
            t0 = time.perf_counter()
            out = crun(ijit(jnp.arange(R), jnp.int32(N)))
            jax.block_until_ready(jax.tree.leaves(out))
            dt = time.perf_counter() - t0
            wall = dt if wall is None else min(wall, dt)
        ev = int(jnp.sum(out.n_events.astype(jnp.int64)))
        failed = int((out.err != 0).sum())
        rate = ev / wall
        twin_rate = ev / mono_wall
        detail = {
            "chunk_steps": chunk,
            "replications": R,
            "objects_per_replication": N,
            "repeats_best_of": repeats,
            "monolithic_twin_events_per_sec": twin_rate,
            "headline_monolithic_events_per_sec": mono_rate,
            "chunked": {
                "events_per_sec": rate,
                "total_events": ev,
                "wall_s": wall,
                "failed_replications": failed,
                "overhead_vs_monolithic_pct": (
                    (twin_rate - rate) / twin_rate * 100.0
                ),
            },
        }
        # streamed leg: 4 waves through the one compiled chunk program,
        # pooled on device (program_cache keeps the timed call warm)
        wave = max(R // 4, 1)
        cache = {}
        _warm_stream(spec, R, wave, chunk, cache)
        t0 = time.perf_counter()
        st = ex.run_experiment_stream(
            spec, mm1.params(N), R, wave_size=wave, chunk_steps=chunk,
            seed=2026, on_wave=_heartbeat, on_chunk=_heartbeat,
            program_cache=cache,
        )
        sev = int(jax.block_until_ready(st.total_events))
        swall = time.perf_counter() - t0
        detail["streamed"] = {
            "events_per_sec": sev / swall,
            "total_events": sev,
            "wall_s": swall,
            "wave_size": wave,
            "n_waves": st.n_waves,
            "failed_replications": int(st.n_failed),
            "pooled_mean_sojourn": float(sm.mean(st.summary)),
        }
        if os.environ.get("CIMBA_BENCH_RUN_CARD"):
            detail["streamed"]["audit"] = _audit_rerun(
                spec, N, R, wave, chunk, cache, st
            )
    return detail


def _attach_f64_twin(detail, R, N):
    """Measure the exact-profile (double-width, oracle-grade) mm1 XLA
    rate and record it in ``detail``: the reference's benchmark runs
    doubles, so every f32 headline carries the f64 number beside it."""
    f64_rate, f64_detail = _mm1_xla(R, N, "f64")
    detail["exact_f64_events_per_sec"] = f64_rate
    detail["exact_f64_wall_s"] = f64_detail["wall_s"]
    detail["exact_f64_failed_replications"] = f64_detail[
        "failed_replications"
    ]


def _mm1_xla(R, N, prof="f64", arm=None):
    """Time the mm1 XLA while-loop path under dtype profile ``prof`` and
    dispatch arm ``arm`` (see :class:`_dispatch_arm`); (rate, detail)
    for the caller to print (bench_mm1 compares it against the kernel
    child and the exact-f64 twin)."""
    from cimba_tpu import config as _cfg
    from cimba_tpu.models import mm1

    with _cfg.profile(prof), _dispatch_arm(arm):
        spec, _ = mm1.build(record=False)

        def init_one(rep, n):
            return cl.init_sim(spec, 2026, rep, mm1.params(n))

        ev, failed, wall = _time_vmapped(
            spec, init_one, R, jnp.int32(1), jnp.int32(N)
        )
        detail = {
            "path": "xla_while",
            "profile": prof,
            "dispatch_arm": arm or "auto",
            "replications": R,
            "objects_per_replication": N,
            "total_events": ev,
            "wall_s": wall,
            "failed_replications": failed,
        }
        if failed:
            detail["regrow"] = _regrow_pass(spec, mm1.params(N), R)
    return ev / wall, detail


def bench_mm1_stream():
    """Large-R streamed M/M/1: pooled sojourn statistics for R beyond
    the single-dispatch lane budget (the "heavy traffic from millions of
    users" shape of the ROADMAP north star).  Waves of
    ``CIMBA_BENCH_STREAM_WAVE`` lanes stream through one compiled,
    donated chunk program; per-wave Pébay summaries fold on device, so
    device memory holds ONE wave of sims regardless of R — the
    monolithic path at these R would need every Sim HBM-resident
    simultaneously (131072 lanes was its measured ceiling).

    Overrides: CIMBA_BENCH_STREAM_R (total replications),
    CIMBA_BENCH_STREAM_WAVE (lanes per wave), CIMBA_BENCH_OBJECTS
    (per-lane workload), CIMBA_BENCH_STREAM_CHUNK (events per chunk
    dispatch)."""
    from cimba_tpu import config as _cfg
    from cimba_tpu.models import mm1
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.stats import summary as sm

    R = int(
        os.environ.get(
            "CIMBA_BENCH_STREAM_R", str(2**20)
        )
    )
    wave = min(
        int(
            os.environ.get(
                "CIMBA_BENCH_STREAM_WAVE", str(65536)
            )
        ),
        R,
    )
    _, N = _scale(0, 2000)
    chunk = _stream_chunk_default()
    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = mm1.build(record=False)
        cache = {}
        _warm_stream(spec, R, wave, chunk, cache)
        t0 = time.perf_counter()
        st = ex.run_experiment_stream(
            spec, mm1.params(N), R, wave_size=wave, chunk_steps=chunk,
            seed=2026, on_wave=_heartbeat, on_chunk=_heartbeat,
            program_cache=cache,
        )
        ev = int(jax.block_until_ready(st.total_events))
        wall = time.perf_counter() - t0
        # telemetry-overhead arm: same R x N, telemetry+spans on vs
        # off, interleaved best-of-k (the < 2% acceptance bar of
        # docs/17_telemetry.md); reuses the warm cache so no compile
        # lands inside the timed region
        try:
            tel_overhead = _telemetry_overhead_arm(
                spec, R, wave, chunk, N, cache
            )
        except Exception as e:  # the arm must never kill the config line
            tel_overhead = {"error": f"{type(e).__name__}: {e}"[:200]}
        audit_info = None
        if os.environ.get("CIMBA_BENCH_RUN_CARD"):
            audit_info = _audit_rerun(spec, N, R, wave, chunk, cache, st)
    rate = ev / wall
    detail = {
        "path": "xla_while_streamed",
        "profile": prof,
        "replications": R,
        "wave_size": wave,
        "n_waves": st.n_waves,
        "chunk_steps": chunk,
        "objects_per_replication": N,
        "total_events": ev,
        "wall_s": wall,
        "failed_replications": int(st.n_failed),
        "pooled_mean_sojourn": float(sm.mean(st.summary)),
        "pooled_n": float(st.summary.n),
        # 1/(mu - lambda) for the config's rates — the sanity anchor
        "theory_mean_sojourn": 10.0,
        "telemetry_overhead": tel_overhead,
    }
    if audit_info is not None:
        detail["audit"] = audit_info
    _line(
        "mm1_stream_events_per_sec",
        rate,
        rate / BASELINE_EVENTS_PER_SEC,
        detail,
    )


def bench_serve():
    """The serving layer under synthetic open-loop load at the same
    R x N as ``mm1_stream`` (docs/13_serving.md): the total lane count
    is split into requests of ``CIMBA_BENCH_SERVE_REQ_R`` replications
    submitted by ``CIMBA_BENCH_SERVE_CLIENTS`` client threads on a
    fixed arrival schedule (``CIMBA_BENCH_SERVE_IAT`` seconds apart;
    0 = burst), all compatible, so the dispatcher packs them into
    shared waves.  Reports throughput (replications/s and events/s),
    p50/p95/p99 request latency, the batch-occupancy histogram, and
    the program-cache counters; every request's result is checked
    against one direct single-caller run (identical events and pooled
    mean — the serve correctness anchor inside the bench).  The
    watchdog heartbeat refreshes per chunk of every dispatched wave."""
    from cimba_tpu import config as _cfg
    from cimba_tpu import serve
    from cimba_tpu.models import mm1
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.stats import summary as sm

    R = int(
        os.environ.get(
            "CIMBA_BENCH_STREAM_R", str(2**20)
        )
    )
    wave = min(
        int(
            os.environ.get(
                "CIMBA_BENCH_STREAM_WAVE", str(65536)
            )
        ),
        R,
    )
    _, N = _scale(0, 2000)
    chunk = _stream_chunk_default()
    req_r = min(
        int(os.environ.get("CIMBA_BENCH_SERVE_REQ_R", max(wave // 4, 1))),
        wave,
    )
    n_requests = max(R // req_r, 1)
    clients = int(os.environ.get("CIMBA_BENCH_SERVE_CLIENTS", "4"))
    iat = float(os.environ.get("CIMBA_BENCH_SERVE_IAT", "0"))
    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = mm1.build(record=False)
        cache = serve.ProgramCache()

        def make_reqs(n_objects, count, tag):
            return [
                serve.Request(
                    spec, mm1.params(n_objects), req_r, seed=2026,
                    wave_size=req_r, chunk_steps=chunk,
                    label=f"{tag}{i}",
                )
                for i in range(count)
            ]

        # warm OUTSIDE the timed service: slot shape + a small packed
        # burst so the common concat shapes are compiled, then a fresh
        # service over the same cache starts with clean stats
        serve.warm(
            cache, spec, mm1.params(1), req_r, chunk_steps=chunk,
            seed=2026, on_wave=_heartbeat, on_chunk=_heartbeat,
        )
        with serve.Service(
            max_wave=wave, cache=cache, on_chunk=_heartbeat,
        ) as warm_svc:
            serve.run_load(
                warm_svc, make_reqs(1, min(4, n_requests), "warm"),
                n_clients=clients,
            )
        _heartbeat()
        svc = serve.Service(
            max_wave=wave, cache=cache, on_chunk=_heartbeat,
        )
        report = serve.run_load(
            svc, make_reqs(N, n_requests, "req"), n_clients=clients,
            inter_arrival_s=iat,
        )
        stats = svc.stats()
        svc.shutdown()
        direct = ex.run_experiment_stream(
            spec, mm1.params(N), req_r, wave_size=req_r,
            chunk_steps=chunk, seed=2026, program_cache=cache,
            on_wave=_heartbeat, on_chunk=_heartbeat,
        )
    assert report.n_completed == n_requests, report.errors
    total_ev = 0
    for _, res in report.results:
        assert int(res.total_events) == int(direct.total_events)
        assert float(sm.mean(res.summary)) == float(
            sm.mean(direct.summary)
        )
        total_ev += int(res.total_events)
    rate = total_ev / report.wall_s
    _line(
        "serve_events_per_sec",
        rate,
        rate / BASELINE_EVENTS_PER_SEC,
        {
            "path": "serve_packed_waves",
            "profile": prof,
            "replications_total": n_requests * req_r,
            "replications_per_request": req_r,
            "requests": n_requests,
            "clients": clients,
            "inter_arrival_s": iat,
            "objects_per_replication": N,
            "chunk_steps": chunk,
            "max_wave": wave,
            "wall_s": report.wall_s,
            "replications_per_sec": report.replications_per_sec,
            "total_events": total_ev,
            "latency": report.latency_percentiles(),
            "batch_occupancy": stats["batch_occupancy"],
            "batches": stats["batches"],
            "queue_depth_hwm": stats["queue_depth_hwm"],
            "time_to_first_wave": stats["time_to_first_wave"],
            "program_cache": stats.get("program_cache"),
            "pooled_mean_sojourn": float(sm.mean(direct.summary)),
        },
    )


def bench_serve_mixed():
    """Heterogeneous wave packing under a mixed open-loop load
    (docs/14_wave_packing.md): a weighted mix of ≥3 mm1 request
    templates differing only in (params, R, seed) plus two more in
    different finite horizon buckets, driven by
    ``serve.run_mixed_load``.  The acceptance metric is the
    batch-occupancy histogram — before compatibility classes this mix
    degraded to all-solo waves (mean occupancy 1.0); the arm reports
    ``mean_batch_occupancy`` (target > 1.5), the padding-waste
    fraction of the pad-and-mask lanes, per-template latency
    percentiles, and per-template correctness anchors (every completed
    request's events + pooled mean equal one direct
    ``run_experiment_stream`` call of its template)."""
    from cimba_tpu import config as _cfg
    from cimba_tpu import serve
    from cimba_tpu.models import mm1
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.stats import summary as sm

    wave = int(
        os.environ.get(
            "CIMBA_BENCH_STREAM_WAVE", str(65536)
        )
    )
    _, N = _scale(0, 2000)
    chunk = _stream_chunk_default()
    req_r = max(
        int(os.environ.get("CIMBA_BENCH_SERVE_REQ_R", str(wave // 4))),
        2,
    )
    n_requests = int(os.environ.get("CIMBA_BENCH_SERVE_MIXED_REQS", "24"))
    clients = int(os.environ.get("CIMBA_BENCH_SERVE_CLIENTS", "4"))
    iat = float(os.environ.get("CIMBA_BENCH_SERVE_IAT", "0"))
    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = mm1.build(record=False)
        cache = serve.ProgramCache()

        def templates(n_objects, R):
            # three templates differing only in (params, R, seed) — one
            # compatibility class — plus two finite horizons landing in
            # DIFFERENT buckets (16x apart at the default ratio), so
            # the load exercises both the pack-anything tier and the
            # bucket boundary
            def req(seed, t_end=None, n=n_objects, r=R):
                return serve.Request(
                    spec, mm1.params(n), r, seed=seed, t_end=t_end,
                    wave_size=r, chunk_steps=chunk,
                )

            return [
                serve.RequestTemplate("params-a", req(11), 2.0),
                serve.RequestTemplate(
                    "params-b", req(22, n=n_objects + 10), 2.0
                ),
                serve.RequestTemplate(
                    "half-r", req(33, r=max(R // 2, 1)), 2.0
                ),
                serve.RequestTemplate("short-h", req(44, t_end=30.0)),
                serve.RequestTemplate("long-h", req(55, t_end=500.0)),
            ]

        # warm OUTSIDE the timed service: the class's common shapes
        serve.warm(
            cache, spec, mm1.params(1), req_r, chunk_steps=chunk,
            seed=11, on_wave=_heartbeat, on_chunk=_heartbeat,
        )
        with serve.Service(
            max_wave=wave, cache=cache, on_chunk=_heartbeat,
        ) as warm_svc:
            serve.run_mixed_load(
                warm_svc, templates(1, req_r), min(10, n_requests),
                n_clients=clients,
            )
        _heartbeat()
        svc = serve.Service(
            max_wave=wave, cache=cache, on_chunk=_heartbeat,
        )
        report = serve.run_mixed_load(
            svc, templates(N, req_r), n_requests, n_clients=clients,
            inter_arrival_s=iat,
        )
        stats = svc.stats()
        svc.shutdown()
        # per-template correctness anchors: every completed request of
        # a template equals ONE direct call of that template
        tmpl_by_name = {
            t.name: t.request for t in templates(N, req_r)
        }
        direct = {}
        for name, req in tmpl_by_name.items():
            direct[name] = ex.run_experiment_stream(
                req.spec, req.params, req.n_replications,
                wave_size=req.wave_size, chunk_steps=req.chunk_steps,
                seed=req.seed, t_end=req.t_end, program_cache=cache,
                on_wave=_heartbeat, on_chunk=_heartbeat,
            )
    assert report.n_completed == n_requests, report.errors
    total_ev = 0
    for i, res in report.results:
        d = direct[report.template_names[i]]
        assert int(res.total_events) == int(d.total_events)
        assert float(sm.mean(res.summary)) == float(sm.mean(d.summary))
        total_ev += int(res.total_events)
    occ = stats["batch_occupancy"]
    n_batches = sum(occ.values())
    mean_occ = (
        sum(k * v for k, v in occ.items()) / n_batches if n_batches
        else 0.0
    )
    rate = total_ev / report.wall_s
    _line(
        "serve_mixed_events_per_sec",
        rate,
        rate / BASELINE_EVENTS_PER_SEC,
        {
            "path": "serve_heterogeneous_waves",
            "profile": prof,
            "requests": n_requests,
            "clients": clients,
            "inter_arrival_s": iat,
            "objects_per_replication": N,
            "replications_per_request": req_r,
            "chunk_steps": chunk,
            "max_wave": wave,
            "wall_s": report.wall_s,
            "total_events": total_ev,
            "latency": report.latency_percentiles(),
            "latency_per_template": report.per_template(),
            "batch_occupancy": occ,
            "mean_batch_occupancy": mean_occ,
            "lane_occupancy": stats["lane_occupancy"],
            "classes_seen": stats["classes_seen"],
            "queue_depth_hwm": stats["queue_depth_hwm"],
            "program_cache": stats.get("program_cache"),
        },
    )


def bench_serve_refill():
    """Continuous wave refill vs the frozen-wave dispatcher at the SAME
    offered open-loop mixed-horizon load (docs/22_refill.md), measured
    through ``tune.measure.measure_arms`` (refill-off is the baseline
    arm; its self-twin gives the noise floor).  The acceptance story:
    refill-on steady-state mean lane occupancy >= 1.5x refill-off with
    p99 submit->deliver latency no worse, ZERO program-cache misses
    during the timed refill rounds (boundary splices dispatch cached
    programs), and every completed request's digest bitwise-equal to
    its direct solo run (``obs.audit.stream_result_digest``) — lane
    recycling is invisible to results.  Reports per-arm occupancy
    series (mean + histogram from periodic stats polls), per-template
    p50/p95/p99, and the refill counters as the run card's ``refill``
    block."""
    import threading as _threading

    from cimba_tpu import config as _cfg
    from cimba_tpu import serve
    from cimba_tpu.models import mm1
    from cimba_tpu.obs import audit as _audit
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.tune import measure as _tm

    wave = int(os.environ.get(
        "CIMBA_BENCH_REFILL_WAVE", str(4096)
    ))
    _, N = _scale(0, 2000)
    chunk = int(os.environ.get(
        "CIMBA_BENCH_REFILL_CHUNK", str(256)
    ))
    req_r = max(int(os.environ.get(
        "CIMBA_BENCH_REFILL_REQ_R", str(max(wave // 4, 1))
    )), 1)
    n_requests = int(os.environ.get("CIMBA_BENCH_REFILL_REQS", "32"))
    clients = int(os.environ.get("CIMBA_BENCH_SERVE_CLIENTS", "4"))
    iat = float(os.environ.get("CIMBA_BENCH_REFILL_IAT", "0.002"))
    repeats = int(os.environ.get("CIMBA_BENCH_REFILL_REPEATS", "2"))
    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = mm1.build(record=False)
        cache = serve.ProgramCache()

        def templates(n_objects, R):
            # one compatibility class (same params signature, all
            # run-to-completion = one horizon bucket), three WORKLOAD
            # lengths 4x/20x apart via n_objects — mm1 is finite-
            # population, so n_objects IS the trajectory length.  The
            # mixed-horizon decay shape: short lanes die at ~5% of a
            # long wave-mate's life.
            def req(seed, n, r=R):
                return serve.Request(
                    spec, mm1.params(n), r, seed=seed,
                    wave_size=r, chunk_steps=chunk,
                )

            return [
                serve.RequestTemplate("long", req(11, 40 * n_objects)),
                serve.RequestTemplate(
                    "mid", req(22, 10 * n_objects), 2.0
                ),
                serve.RequestTemplate(
                    "short", req(33, 2 * n_objects), 3.0
                ),
            ]

        def load_round(refill, n_reqs, timed):
            """One full open-loop round at the offered load; returns
            (report, stats, occupancy polls)."""
            svc = serve.Service(
                max_wave=wave, cache=cache, refill=refill,
                refill_every=2, horizon_bucket=None,
                on_chunk=_heartbeat,
            )
            polls: list = []
            stop = _threading.Event()

            def poller():
                while not stop.wait(0.05):
                    occ = svc.stats()["lane_occupancy"]
                    if occ["lanes_in_wave"]:
                        polls.append(occ["occupancy_now"])

            th = _threading.Thread(target=poller, daemon=True)
            if timed:
                th.start()
            try:
                report = serve.run_mixed_load(
                    svc, templates(N, req_r), n_reqs,
                    n_clients=clients, inter_arrival_s=iat,
                )
                stats = svc.stats()
            finally:
                stop.set()
                if timed:
                    th.join()
                svc.shutdown()
            return report, stats, polls

        payloads: dict = {}
        # misses snapshot taken at the FIRST timed run (after every
        # prepare leg): on_round fires AFTER a round completes, so a
        # round-indexed snapshot would silently exclude round 1 — the
        # round most likely to compile
        misses_at_first_run: dict = {}

        def make_arm(name, refill):
            def prepare():
                # warm every program this arm dispatches — incl. the
                # refill/liveness pair and at least one boundary splice
                load_round(refill, min(6, n_requests), timed=False)

            def run():
                misses_at_first_run.setdefault(
                    "misses", cache.stats()["misses"]
                )
                payloads[name] = load_round(refill, n_requests, True)
                return payloads[name]

            return _tm.Arm(name=name, run=run, prepare=prepare)

        arms = [
            make_arm("refill_off", False), make_arm("refill_on", True),
        ]
        mreport = _tm.measure_arms(
            arms, repeats=repeats, baseline=0, on_round=_heartbeat,
        )
        # zero compiles during the timed rounds (acceptance): the
        # prepare legs warmed every program — boundary splices must
        # dispatch, never compile.  Snapshot BEFORE the direct digest
        # runs below, which warm nothing new but keep this honest.
        compiled_in_timed = (
            cache.stats()["misses"] - misses_at_first_run["misses"]
            if misses_at_first_run else None
        )
        assert compiled_in_timed == 0, (
            "programs compiled during the timed refill rounds",
            compiled_in_timed, cache.stats(),
        )
        # per-template digest anchors vs direct solo runs — every
        # completed request bitwise its solo twin, refilled or not
        direct_digest = {}
        for t in templates(N, req_r):
            r = t.request
            direct_digest[t.name] = _audit.stream_result_digest(
                ex.run_experiment_stream(
                    r.spec, r.params, r.n_replications,
                    wave_size=r.wave_size, chunk_steps=r.chunk_steps,
                    seed=r.seed, t_end=r.t_end, program_cache=cache,
                    on_wave=_heartbeat, on_chunk=_heartbeat,
                )
            )  # noqa: t_end is None for every template (natural end)
        digest_checked = digest_equal = 0
        arm_detail = {}
        for name, (report, stats, polls) in payloads.items():
            for i, res in report.results:
                digest_checked += 1
                digest_equal += (
                    _audit.stream_result_digest(res)
                    == direct_digest[report.template_names[i]]
                )
            hist: dict = {}
            for f in polls:
                b = round(min(max(f, 0.0), 1.0) * 10) / 10
                hist[f"{b:.1f}"] = hist.get(f"{b:.1f}", 0) + 1
            total_ev = sum(
                int(res.total_events) for _, res in report.results
            )
            arm_detail[name] = {
                "completed": report.n_completed,
                "errors": dict(report.errors),
                "wall_s": report.wall_s,
                "events_per_sec": (
                    total_ev / report.wall_s if report.wall_s else 0.0
                ),
                "latency": report.latency_percentiles(),
                "latency_per_template": report.per_template(),
                "occupancy_mean": stats["lane_occupancy"][
                    "occupancy_mean"
                ],
                "occupancy_poll_mean": (
                    sum(polls) / len(polls) if polls else None
                ),
                "occupancy_hist": dict(sorted(hist.items())),
                "refill": stats["refill"],
                "mid_wave_deliveries": stats["refill"][
                    "mid_wave_deliveries"
                ],
            }
    on_d = arm_detail.get("refill_on", {})
    off_d = arm_detail.get("refill_off", {})
    occ_ratio = (
        on_d.get("occupancy_mean", 0.0)
        / off_d["occupancy_mean"]
        if off_d.get("occupancy_mean") else None
    )
    rate = on_d.get("events_per_sec", 0.0)
    assert digest_checked and digest_equal == digest_checked, (
        "refilled results drifted from their solo digests",
        digest_equal, digest_checked,
    )
    _line(
        "serve_refill_events_per_sec",
        rate,
        rate / BASELINE_EVENTS_PER_SEC,
        {
            "path": "serve_continuous_refill",
            "profile": prof,
            "requests": n_requests,
            "clients": clients,
            "inter_arrival_s": iat,
            "objects_per_replication": N,
            "replications_per_request": req_r,
            "chunk_steps": chunk,
            "max_wave": wave,
            "measure": mreport.to_json(),
            "refill": {
                "arms": arm_detail,
                "occupancy_ratio_on_vs_off": occ_ratio,
                "p99_on_s": on_d.get("latency", {}).get("p99_s"),
                "p99_off_s": off_d.get("latency", {}).get("p99_s"),
                "compiles_in_timed_rounds": compiled_in_timed,
                "digest_anchors": {
                    "checked": digest_checked, "equal": digest_equal,
                },
            },
            "program_cache": cache.stats(),
        },
    )


def bench_serve_fused():
    """Cross-spec wave fusion vs per-spec exact-class dispatch at the
    SAME adversarial offered load (docs/26_wave_fusion.md): K small
    DISTINCT models (same fusion shape class, different block
    programs), each driven closed-loop by its own tenant client —
    submit, wait, submit — so at most ONE request per spec is ever
    outstanding.  That shape is maximally adversarial for exact-class
    dispatch: a wave can never pack two requests (no same-class
    sibling exists to claim, and the strict-priority boundary valve
    blocks foreign-class splices), so every unfused wave strands at
    R/max_wave occupancy and pays full birth overhead per request.
    Fuse-on packs all K tenants into one resident branch-dispatch
    superprogram wave and splices each next request into the lanes
    its predecessor just retired.  Measured through
    ``tune.measure.measure_arms`` (fuse-off is the baseline arm; its
    self-twin gives the noise floor).  Acceptance: fused mean lane
    occupancy >= 1.5x unfused and events/s >= 1.3x at the same
    offered load, ZERO program-cache misses during the timed rounds
    (a fixed-order primer sequence binds the fusion roster and warms
    the identical bundle ladder every round), every completed
    request's digest bitwise-equal to its direct solo run, and the
    fused superprogram's equation count sublinear in the members'
    solo sum (the JXL004 fused budget,
    ``check.jaxprlint.fused_size_findings``)."""
    import dataclasses as _dc
    import threading as _threading

    from cimba_tpu import config as _cfg
    from cimba_tpu import serve
    from cimba_tpu.check import jaxprlint as _jxl
    from cimba_tpu.core import api, cmd
    from cimba_tpu.core.model import Model
    from cimba_tpu.obs import audit as _audit
    from cimba_tpu.obs import program_size as _ps
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.tune import measure as _tm

    wave = int(os.environ.get(
        "CIMBA_BENCH_FUSED_WAVE", str(4096)
    ))
    # chunk small relative to trajectory length: occupancy is sampled
    # at refill boundaries (every refill_every chunks), so each wave
    # must cross many boundaries during its life
    chunk = int(os.environ.get(
        "CIMBA_BENCH_FUSED_CHUNK", str(256)
    ))
    # K distinct specs; each request asks for wave/K lanes, so an
    # unfused wave stranded with one tenant's request pads 1-1/K of
    # its lanes — the adversarial shape fusion exists for
    n_specs = int(os.environ.get("CIMBA_BENCH_FUSED_SPECS", "4"))
    req_r = max(wave // n_specs, 1)
    t_stop = float(os.environ.get(
        "CIMBA_BENCH_FUSED_TSTOP", str(2048.0)
    ))
    n_requests = int(os.environ.get("CIMBA_BENCH_FUSED_REQS", "48"))
    per_spec = max(n_requests // n_specs, 1)
    repeats = int(os.environ.get("CIMBA_BENCH_FUSED_REPEATS", "3"))
    prof = _bench_profile()

    def _build_spec(i):
        # distinct model IDENTITY (different trace-time hold constant
        # = different block program), same fusion shape class
        step = 0.5 + 0.25 * i
        m = Model(f"fz{i}", event_cap=1, guard_cap=2)

        @m.block
        def work(sim, p, sig):
            done = api.clock(sim) > t_stop
            return sim, cmd.select(
                done, cmd.exit_(), cmd.hold(step, next_pc=work.pc)
            )

        m.process("w", entry=work)
        return m.build()

    with _cfg.profile(prof):
        import jax

        from cimba_tpu.stats import summary as _sm

        def clock_path(sims):
            return jax.vmap(lambda c: _sm.add(_sm.empty(), c))(
                sims.clock
            )

        specs = [_build_spec(i) for i in range(n_specs)]
        cache = serve.ProgramCache()

        def requests():
            return [
                serve.Request(
                    s, (), req_r, seed=11 + i, wave_size=req_r,
                    chunk_steps=chunk, summary_path=clock_path,
                )
                for i, s in enumerate(specs)
            ]

        def load_round(fuse, per, collect=None):
            """One closed-loop round: K tenant threads, one spec
            each, ``per`` sequential submit->wait requests; returns
            (wall_s, total_events, stats)."""
            svc = serve.Service(
                max_wave=wave, cache=cache, refill=True,
                refill_every=1, horizon_bucket=None, fuse=fuse,
                fuse_max_specs=n_specs, on_chunk=_heartbeat,
            )
            errs: list = []
            ev = [0] * n_specs
            try:
                # primer: one request per spec, sequentially, in a
                # FIXED order — binds the fusion roster s0<s1<...
                # identically every round, so prepare and timed
                # rounds trace the same bundle ladder ({s0,s1},
                # {s0..s2}, ...) and the timed rounds compile nothing
                for i, r in enumerate(requests()):
                    svc.submit(_dc.replace(
                        r, label=f"primer:{r.spec.name}"
                    )).result(600)

                def tenant(i, r):
                    try:
                        for j in range(per):
                            res = svc.submit(_dc.replace(
                                r, label=f"{r.spec.name}#{j}"
                            )).result(600)
                            ev[i] += int(res.total_events)
                            if collect is not None:
                                collect(i, res)
                            _heartbeat()
                    except Exception as e:  # surfaced after join
                        errs.append(e)

                ths = [
                    _threading.Thread(target=tenant, args=(i, r))
                    for i, r in enumerate(requests())
                ]
                t0 = time.perf_counter()
                for th in ths:
                    th.start()
                for th in ths:
                    th.join()
                wall = time.perf_counter() - t0
                stats = svc.stats()
            finally:
                svc.shutdown()
            if errs:
                raise errs[0]
            return wall, sum(ev), stats

        payloads: dict = {}
        results: dict = {}
        misses_at_first_run: dict = {}

        def make_arm(name, fuse, program_size=None):
            def prepare():
                load_round(fuse, 2)

            def run():
                misses_at_first_run.setdefault(
                    "misses", cache.stats()["misses"]
                )
                got = payloads.setdefault(name, [])
                kept = results.setdefault(name, [])
                got.append(load_round(
                    fuse, per_spec,
                    collect=lambda i, r: kept.append((i, r)),
                ))
                return got[-1]

            return _tm.Arm(
                name=name, run=run, prepare=prepare,
                program_size=program_size,
            )

        # program size as a first-class cost (docs/25): the fused
        # superprogram vs the sum of its members' solo programs —
        # the JXL004 sublinearity budget is the price ceiling the
        # occupancy win is bought under
        solo_sizes = [
            _ps.chunk_program_size(
                s, (), lanes=4, max_steps=chunk, lower=False
            )
            for s in specs
        ]
        fused_size = _ps.fused_program_size(
            specs, (), lanes=4, max_steps=chunk, lower=False
        )
        size_findings = _jxl.fused_size_findings(
            fused_size.eqns, [s.eqns for s in solo_sizes],
            "serve_fused",
        )
        assert not size_findings, (
            "fused superprogram over the JXL004 sublinearity budget",
            [f.message for f in size_findings],
        )
        fused_size_detail = {
            "fused": fused_size.to_dict(),
            "solo_eqns": [s.eqns for s in solo_sizes],
            "sublinearity": (
                fused_size.eqns
                / max(sum(s.eqns for s in solo_sizes), 1)
            ),
            "budget_factor": _jxl.FUSED_EQN_FACTOR,
        }

        arms = [
            make_arm("fuse_off", False),
            make_arm("fuse_on", True, program_size=fused_size_detail),
        ]
        mreport = _tm.measure_arms(
            arms, repeats=repeats, baseline=0, on_round=_heartbeat,
        )
        compiled_in_timed = (
            cache.stats()["misses"] - misses_at_first_run["misses"]
            if misses_at_first_run else None
        )
        assert compiled_in_timed == 0, (
            "programs compiled during the timed fused rounds",
            compiled_in_timed, cache.stats(),
        )
        # per-spec digest anchors vs direct solo runs — fusion is
        # invisible to results, branch-dispatched or not
        direct_digest = {}
        for i, r in enumerate(requests()):
            direct_digest[i] = _audit.stream_result_digest(
                ex.run_experiment_stream(
                    r.spec, r.params, r.n_replications,
                    wave_size=r.wave_size, chunk_steps=r.chunk_steps,
                    seed=r.seed, t_end=r.t_end,
                    summary_path=clock_path, program_cache=cache,
                    on_wave=_heartbeat, on_chunk=_heartbeat,
                )
            )
        digest_checked = digest_equal = 0
        arm_detail = {}
        for name, rounds in payloads.items():
            for i, res in results.get(name, ()):
                digest_checked += 1
                digest_equal += (
                    _audit.stream_result_digest(res)
                    == direct_digest[i]
                )
            # per-round (wall, events, stats); events are identical
            # every round (same requests, deterministic trajectories)
            best = min(rounds, key=lambda r: r[0])
            arm_detail[name] = {
                "rounds": len(rounds),
                "walls_s": [round(r[0], 6) for r in rounds],
                "best_wall_s": best[0],
                "total_events": best[1],
                "events_per_sec": best[1] / best[0] if best[0] else 0.0,
                "occupancy_mean": max(
                    r[2]["lane_occupancy"]["occupancy_mean"]
                    for r in rounds
                ),
                "fusion": rounds[-1][2]["fusion"],
                "refill": rounds[-1][2]["refill"],
            }
    on_d = arm_detail.get("fuse_on", {})
    off_d = arm_detail.get("fuse_off", {})
    occ_ratio = (
        on_d.get("occupancy_mean", 0.0) / off_d["occupancy_mean"]
        if off_d.get("occupancy_mean") else None
    )
    ev_ratio = (
        on_d.get("events_per_sec", 0.0) / off_d["events_per_sec"]
        if off_d.get("events_per_sec") else None
    )
    rate = on_d.get("events_per_sec", 0.0)
    assert digest_checked and digest_equal == digest_checked, (
        "fused results drifted from their solo digests",
        digest_equal, digest_checked,
    )
    assert occ_ratio is not None and occ_ratio >= 1.5, (
        "fused occupancy below the 1.5x acceptance floor", occ_ratio,
    )
    assert ev_ratio is not None and ev_ratio >= 1.3, (
        "fused events/s below the 1.3x acceptance floor", ev_ratio,
    )
    _line(
        "serve_fused_events_per_sec",
        rate,
        rate / BASELINE_EVENTS_PER_SEC,
        {
            "path": "serve_wave_fusion",
            "profile": prof,
            "requests": n_requests,
            "tenants": n_specs,
            "requests_per_tenant": per_spec,
            "n_specs": n_specs,
            "replications_per_request": req_r,
            "chunk_steps": chunk,
            "max_wave": wave,
            "measure": mreport.to_json(),
            "fusion": {
                "arms": arm_detail,
                "occupancy_ratio_on_vs_off": occ_ratio,
                "events_ratio_on_vs_off": ev_ratio,
                "compiles_in_timed_rounds": compiled_in_timed,
                "digest_anchors": {
                    "checked": digest_checked, "equal": digest_equal,
                },
                "program_size": fused_size_detail,
            },
            "program_cache": cache.stats(),
        },
    )


def bench_serve_qos():
    """The multi-tenant QoS plane under an adversarial flood
    (docs/27_qos.md), measured through ``tune.measure.measure_arms``:
    a ``flood`` tenant offers 2x the victims' combined arrival rate at
    the SAME request shape (same compiled program, same compatibility
    class — tenancy is never part of the class key), and the victim
    tenant's tail is the metric.  Three arms: ``noflood`` (victims
    alone, qos off — the reference), ``flood_qos_off`` (the damage),
    ``flood_qos_on`` (weighted-fair DRR shares + the flood tenant's
    token-bucket rate limit + lane quota).  The acceptance story: with
    qos ON under flood, victim p99 <= 1.3x and goodput >= 0.9x the
    no-flood reference, the flooder is throttled via structured
    ``RetryAfter`` (the client honors ``delay_s`` and tallies
    throttles per tenant), ZERO program-cache misses during the timed
    rounds, and every delivered result's digest bitwise-equal to its
    direct solo run — fairness shaping is invisible to results."""
    from cimba_tpu import config as _cfg
    from cimba_tpu import serve
    from cimba_tpu.models import mm1
    from cimba_tpu.obs import audit as _audit
    from cimba_tpu.qos import TenantPolicy, TenantRegistry
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.tune import measure as _tm

    wave = int(os.environ.get(
        "CIMBA_BENCH_QOS_WAVE", str(2048)
    ))
    _, N = _scale(0, 2000)
    chunk = int(os.environ.get(
        "CIMBA_BENCH_QOS_CHUNK", str(256)
    ))
    # requests are wave/8 lanes each: the flood's 2-request lane quota
    # then caps it at a quarter of the wave, leaving the victims
    # near-full parallelism when qos is on
    req_r = max(int(os.environ.get(
        "CIMBA_BENCH_QOS_REQ_R", str(max(wave // 8, 1))
    )), 1)
    n_victim = int(os.environ.get("CIMBA_BENCH_QOS_VICTIMS", "12"))
    clients = int(os.environ.get("CIMBA_BENCH_SERVE_CLIENTS", "4"))
    iat = float(os.environ.get("CIMBA_BENCH_QOS_IAT", "0.002"))
    repeats = int(os.environ.get("CIMBA_BENCH_QOS_REPEATS", "2"))
    flood_rate = float(os.environ.get(
        "CIMBA_BENCH_QOS_FLOOD_RATE", "10.0"
    ))
    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = mm1.build(record=False)
        cache = serve.ProgramCache()

        def req(seed, n, r=req_r):
            return serve.Request(
                spec, mm1.params(n), r, seed=seed,
                wave_size=r, chunk_steps=chunk,
            )

        # the flood shares the victims' params SIGNATURE (one compiled
        # program, one compatibility class — tenancy never splits the
        # class) but runs 10x the trajectory length at 2x the victims'
        # combined arrival weight: lanes it grabs stay held long, which
        # is exactly the hog a fair share has to arbitrate
        def templates(flood):
            base = [
                serve.RequestTemplate(
                    "victim_short", req(11, 2 * N), 1.0,
                    tenant="victim",
                ),
                serve.RequestTemplate(
                    "victim_mid", req(22, 6 * N), 1.0,
                    tenant="victim",
                ),
            ]
            if flood:
                base.append(serve.RequestTemplate(
                    "flood", req(33, 20 * N), 4.0, tenant="flood",
                ))
            return base

        def registry():
            # fresh per round: token buckets are per-service state
            return TenantRegistry([
                TenantPolicy("victim", weight=4.0,
                             deadline_class=60.0),
                TenantPolicy(
                    "flood", weight=1.0, rate=flood_rate, burst=2,
                    lane_quota=2 * req_r,
                ),
            ])

        def load_round(flood, qos, n_reqs, iat_s):
            svc = serve.Service(
                max_wave=wave, cache=cache, refill=True,
                refill_every=2, horizon_bucket=None,
                qos=qos, tenants=registry(), on_chunk=_heartbeat,
            )
            try:
                report = serve.run_mixed_load(
                    svc, templates(flood), n_reqs,
                    n_clients=clients, inter_arrival_s=iat_s,
                )
                stats = svc.stats()
            finally:
                svc.shutdown()
            return report, stats

        payloads: dict = {}
        # misses snapshot at the FIRST timed run (after every prepare
        # leg) — the round-1 run is the one most likely to compile
        misses_at_first_run: dict = {}

        def make_arm(name, flood, qos):
            # victims see the same offered stream in every arm: under
            # flood the 1:1:4 mix gives victims 1/3 of 3*n_victim
            # requests, so the no-flood arm stretches its arrival
            # spacing 3x to keep victim inter-arrival identical
            n_reqs = 3 * n_victim if flood else n_victim
            iat_s = iat if flood else 3.0 * iat

            def prepare():
                load_round(flood, qos, min(6, n_reqs), 0.0)

            def run():
                misses_at_first_run.setdefault(
                    "misses", cache.stats()["misses"]
                )
                payloads[name] = load_round(flood, qos, n_reqs, iat_s)
                return payloads[name]

            return _tm.Arm(name=name, run=run, prepare=prepare)

        arms = [
            make_arm("noflood", False, False),
            make_arm("flood_qos_off", True, False),
            make_arm("flood_qos_on", True, True),
        ]
        mreport = _tm.measure_arms(
            arms, repeats=repeats, baseline=0, on_round=_heartbeat,
        )
        compiled_in_timed = (
            cache.stats()["misses"] - misses_at_first_run["misses"]
            if misses_at_first_run else None
        )
        assert compiled_in_timed == 0, (
            "programs compiled during the timed qos rounds",
            compiled_in_timed, cache.stats(),
        )
        # per-template digest anchors vs direct solo runs — delivered
        # results are bitwise their solo twins, throttled or fair-
        # shared or not
        direct_digest = {}
        for t in templates(True):
            r = t.request
            direct_digest[t.name] = _audit.stream_result_digest(
                ex.run_experiment_stream(
                    r.spec, r.params, r.n_replications,
                    wave_size=r.wave_size, chunk_steps=r.chunk_steps,
                    seed=r.seed, t_end=r.t_end, program_cache=cache,
                    on_wave=_heartbeat, on_chunk=_heartbeat,
                )
            )
        digest_checked = digest_equal = 0
        arm_detail = {}
        for name, (report, stats) in payloads.items():
            for i, res in report.results:
                digest_checked += 1
                digest_equal += (
                    _audit.stream_result_digest(res)
                    == direct_digest[report.template_names[i]]
                )
            arm_detail[name] = {
                "completed": report.n_completed,
                "errors": dict(report.errors),
                "wall_s": report.wall_s,
                "latency": report.latency_percentiles(),
                "per_template": report.per_template(),
                "per_tenant": report.per_tenant(),
                "throttles_by_tenant": dict(
                    report.throttles_by_tenant
                ),
                "qos_tenants": stats["qos"]["tenants"],
            }
    assert digest_checked and digest_equal == digest_checked, (
        "qos-shaped results drifted from their solo digests",
        digest_equal, digest_checked,
    )
    ref = arm_detail["noflood"]["per_tenant"]["victim"]
    on_v = arm_detail["flood_qos_on"]["per_tenant"]["victim"]
    off_v = arm_detail["flood_qos_off"]["per_tenant"].get("victim", {})
    p99_ratio_on = (
        on_v["p99_s"] / ref["p99_s"] if ref.get("p99_s") else None
    )
    p99_ratio_off = (
        off_v.get("p99_s", 0.0) / ref["p99_s"]
        if ref.get("p99_s") else None
    )
    flood_throttles = arm_detail["flood_qos_on"][
        "throttles_by_tenant"
    ].get("flood", 0)
    # the acceptance contract (docs/27_qos.md): protection + shaping
    assert flood_throttles > 0, (
        "the flooding tenant was never throttled with qos on",
        arm_detail["flood_qos_on"]["throttles_by_tenant"],
    )
    assert p99_ratio_on is not None and p99_ratio_on <= 1.3, (
        "victim p99 under flood with qos on exceeded 1.3x the "
        "no-flood reference", p99_ratio_on,
    )
    assert on_v["goodput"] >= 0.9 * ref["goodput"], (
        "victim goodput under flood with qos on fell below 0.9x the "
        "no-flood reference", on_v["goodput"], ref["goodput"],
    )
    _line(
        "serve_qos_victim_p99_ratio",
        p99_ratio_on,
        None,
        {
            "path": "serve_qos_fair_share",
            "profile": prof,
            "victims_per_round": n_victim,
            "clients": clients,
            "inter_arrival_s": iat,
            "objects_per_replication": N,
            "replications_per_request": req_r,
            "chunk_steps": chunk,
            "max_wave": wave,
            "flood_rate_per_s": flood_rate,
            "measure": mreport.to_json(),
            "qos": {
                "arms": arm_detail,
                "victim_p99_ratio_qos_on": p99_ratio_on,
                "victim_p99_ratio_qos_off": p99_ratio_off,
                "victim_goodput_qos_on": on_v["goodput"],
                "victim_goodput_ref": ref["goodput"],
                "flood_throttles_qos_on": flood_throttles,
                "compiles_in_timed_rounds": compiled_in_timed,
                "digest_anchors": {
                    "checked": digest_checked, "equal": digest_equal,
                },
            },
            "program_cache": cache.stats(),
        },
        unit="ratio",
    )


def bench_serve_preempt():
    """The preemptive device scheduler vs run-to-completion dispatch
    at the SAME offered load (docs/24_device_scheduler.md): one long
    low-priority background request is mid-wave when a burst of short
    HIGH-priority urgent requests arrives in a different horizon-bucket
    class.  sched_off (the baseline arm) makes the urgents wait out
    the background wave; sched_on checkpoint-evicts the background at
    a quantum boundary, runs the urgent class first, and restores.
    Acceptance: urgent p99 submit->deliver latency improves >= 2x,
    EVERY result (preempted background included, 64 digests across
    arms x repeats) is bitwise its direct solo run, and ZERO programs
    compile during the timed rounds (preempt/evict/restore is pure
    dispatch — the prepare legs warm everything, including one full
    preemption)."""
    import time as _time

    from cimba_tpu import config as _cfg
    from cimba_tpu import serve
    from cimba_tpu.models import mm1
    from cimba_tpu.obs import audit as _audit
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.tune import measure as _tm

    wave = int(os.environ.get(
        "CIMBA_BENCH_PREEMPT_WAVE", str(4096)
    ))
    _, N = _scale(0, 2000)
    chunk = int(os.environ.get(
        "CIMBA_BENCH_PREEMPT_CHUNK", str(256)
    ))
    req_r = max(int(os.environ.get(
        "CIMBA_BENCH_PREEMPT_REQ_R", str(max(wave // 4, 1))
    )), 1)
    n_urgent = int(os.environ.get("CIMBA_BENCH_PREEMPT_URGENT", "15"))
    # mm1 is finite-population: n_objects IS the trajectory length, so
    # the background's 400x object count is what makes it long-lived;
    # the t_end caps exist to put the two classes in DIFFERENT horizon
    # buckets (16.0: 60000 -> bucket 3, 60 -> bucket 1), which is what
    # forbids splicing and forces the scheduling decision
    bg_objs = int(os.environ.get(
        "CIMBA_BENCH_PREEMPT_BG_OBJS", str(400 * N)
    ))
    ur_objs = 2 * N
    bg_t_end = float(os.environ.get(
        "CIMBA_BENCH_PREEMPT_BG_T", "60000.0"
    ))
    ur_t_end = float(os.environ.get("CIMBA_BENCH_PREEMPT_UR_T", "60.0"))
    repeats = int(os.environ.get("CIMBA_BENCH_PREEMPT_REPEATS", "2"))
    ur_seeds = (11, 22, 33)
    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = mm1.build(record=False)
        cache = serve.ProgramCache()

        def _req(n_objects, seed, t_end, prio, label):
            return serve.Request(
                spec, mm1.params(n_objects), req_r, seed=seed,
                t_end=t_end, wave_size=req_r, chunk_steps=chunk,
                priority=prio, label=label,
            )

        def load_round(sched_on, timed):
            """One round: background submitted, wave observed live,
            then the urgent burst; returns (results keyed by (seed,
            t_end), urgent latencies, stats)."""
            svc = serve.Service(
                max_wave=wave, cache=cache, device_sched=sched_on,
                waves_per_device=1, preempt_quantum=2, refill_every=2,
                horizon_bucket=16.0, pad_waves=False,
                on_chunk=_heartbeat,
            )
            try:
                bg = svc.submit(_req(bg_objs, 1, bg_t_end, 0, "bg"))
                # the urgents must arrive against a RUNNING wave —
                # poll until the background's lanes are live
                deadline = _time.monotonic() + 120
                while (svc.stats()["lane_occupancy"]["lanes_in_wave"]
                       == 0 and _time.monotonic() < deadline):
                    _time.sleep(0.002)
                t0 = {}
                handles = []
                for i in range(n_urgent):
                    seed = ur_seeds[i % len(ur_seeds)]
                    h = svc.submit(_req(
                        ur_objs, seed, ur_t_end, 10, f"ur{i}"
                    ))
                    t0[i] = _time.monotonic()
                    handles.append((i, seed, h))
                lat = []
                results = {}
                for i, seed, h in handles:
                    results.setdefault(
                        (ur_objs, seed, ur_t_end), []
                    ).append(h.result(600))
                    lat.append(_time.monotonic() - t0[i])
                results[(bg_objs, 1, bg_t_end)] = [bg.result(600)]
                stats = svc.stats()
            finally:
                svc.shutdown()
            return results, lat, stats

        payloads: dict = {}
        misses_at_first_run: dict = {}

        def make_arm(name, sched_on):
            def prepare():
                # warm every program this arm dispatches — the
                # sched_on leg includes a full preempt/restore cycle
                load_round(sched_on, timed=False)

            def run():
                misses_at_first_run.setdefault(
                    "misses", cache.stats()["misses"]
                )
                res, lat, stats = load_round(sched_on, True)
                payloads.setdefault(name, []).append(
                    (res, lat, stats)
                )
                return stats

            return _tm.Arm(name=name, run=run, prepare=prepare)

        arms = [
            make_arm("sched_off", False), make_arm("sched_on", True),
        ]
        mreport = _tm.measure_arms(
            arms, repeats=repeats, baseline=0, on_round=_heartbeat,
        )
        compiled_in_timed = (
            cache.stats()["misses"] - misses_at_first_run["misses"]
            if misses_at_first_run else None
        )
        assert compiled_in_timed == 0, (
            "programs compiled during the timed preempt rounds",
            compiled_in_timed, cache.stats(),
        )
        # digest anchors: every (objects, seed, t_end) point's direct
        # solo run
        direct_digest = {}
        for key in (
            [(ur_objs, s, ur_t_end) for s in ur_seeds]
            + [(bg_objs, 1, bg_t_end)]
        ):
            n_obj, seed, t_end = key
            direct_digest[key] = _audit.stream_result_digest(
                ex.run_experiment_stream(
                    spec, mm1.params(n_obj), req_r, wave_size=req_r,
                    chunk_steps=chunk, seed=seed, t_end=t_end,
                    program_cache=cache, on_wave=_heartbeat,
                    on_chunk=_heartbeat,
                )
            )
        digest_checked = digest_equal = 0
        arm_detail: dict = {}
        for name, rounds in payloads.items():
            all_lat: list = []
            preempts = restores = 0
            for res, lat, stats in rounds:
                all_lat.extend(lat)
                for key, rs in res.items():
                    for r in rs:
                        digest_checked += 1
                        digest_equal += (
                            _audit.stream_result_digest(r)
                            == direct_digest[key]
                        )
                ds = stats.get("device_sched", {})
                preempts += ds.get("preemptions", 0)
                restores += ds.get("restores", 0)
            s = sorted(all_lat)

            def pct(p, s=s):
                return s[min(int(len(s) * p), len(s) - 1)]

            arm_detail[name] = {
                "urgent_latency_s": {
                    "p50": pct(0.50), "p95": pct(0.95),
                    "p99": pct(0.99), "n": len(s),
                },
                "preemptions": preempts,
                "restores": restores,
            }
    on_d = arm_detail["sched_on"]
    off_d = arm_detail["sched_off"]
    p99_on = on_d["urgent_latency_s"]["p99"]
    p99_off = off_d["urgent_latency_s"]["p99"]
    assert digest_checked and digest_equal == digest_checked, (
        "preempted results drifted from their solo digests",
        digest_equal, digest_checked,
    )
    assert on_d["preemptions"] >= 1 and on_d["restores"] >= 1, on_d
    assert p99_on * 2.0 <= p99_off, (
        "urgent p99 under preemption not >= 2x better",
        p99_on, p99_off,
    )
    _line(
        "serve_preempt_urgent_p99_s",
        p99_on,
        p99_off / p99_on if p99_on else None,
        {
            "path": "serve_device_scheduler",
            "profile": prof,
            "urgent_requests": n_urgent,
            "bg_objects": bg_objs,
            "urgent_objects": ur_objs,
            "bg_t_end": bg_t_end,
            "urgent_t_end": ur_t_end,
            "objects_per_replication": N,
            "replications_per_request": req_r,
            "chunk_steps": chunk,
            "max_wave": wave,
            "measure": mreport.to_json(),
            "preempt": {
                "arms": arm_detail,
                "p99_speedup_on_vs_off": (
                    p99_off / p99_on if p99_on else None
                ),
                "compiles_in_timed_rounds": compiled_in_timed,
                "digest_anchors": {
                    "checked": digest_checked, "equal": digest_equal,
                },
            },
            "program_cache": cache.stats(),
        },
        unit="s",
    )


#: the serve_cold child: one fresh process per trial per arm, timing
#: import / programs-ready / first-result legs of a single serve-shaped
#: request.  The hydrated arm warms from the AOT store manifest (NO
#: execution, main thread — the docs/15 deploy recipe); the fresh arm
#: warms by compiling (the bench_serve protocol).  Digest of the result
#: leaves proves hydrated == freshly-compiled bitwise.
_COLD_CHILD = r"""
import hashlib, json, os, time
t_start = time.monotonic()
import jax, numpy as np
from cimba_tpu import config as _cfg, serve
from cimba_tpu.models import mm1
from cimba_tpu.runner import experiment as ex
from cimba_tpu.serve import cache as _pc
t_import = time.monotonic() - t_start

prof = os.environ["COLD_PROFILE"]
R = int(os.environ["COLD_R"])
N = int(os.environ["COLD_N"])
chunk = int(os.environ["COLD_CHUNK"])
seed = int(os.environ["COLD_SEED"])
store = os.environ.get("CIMBA_PROGRAM_STORE")
with _cfg.profile(prof):
    spec, _ = mm1.build(record=False)
    params = mm1.params(N)
    cache = _pc.ProgramCache()
    t0 = time.monotonic()
    if store:
        serve.warm(cache, spec, params, R, manifest=store,
                   chunk_steps=chunk)
    else:
        serve.warm(cache, spec, params, R, chunk_steps=chunk, seed=seed)
    t_ready = time.monotonic() - t0
    t0 = time.monotonic()
    with serve.Service(max_wave=R, cache=cache) as svc:
        res = svc.submit(serve.Request(
            spec, params, R, seed=seed, wave_size=R, chunk_steps=chunk,
        )).result(1800)
        stats = svc.stats()
    t_first = time.monotonic() - t0
    dig = hashlib.sha256(b"".join(
        np.asarray(x).tobytes()
        for x in jax.tree.leaves(
            (res.summary, res.n_failed, res.total_events))
    )).hexdigest()
    split = None
    if os.environ.get("COLD_REPORT"):
        # monolithic-path trace/compile/execute split at the same
        # shape (with_report goes through the AOT legs cleanly)
        _, report = ex.run_experiment(
            spec, params, R, seed=seed, with_report=True,
        )
        split = {
            "trace_lower_s": report.trace_lower_s,
            "compile_s": report.compile_s,
            "execute_s": report.execute_s,
        }
st = stats.get("program_store")
if store:
    assert st and st["hits"] >= 1 and st["misses"] == 0, st
    assert st["fallback_shapes"] == 0, st
print(json.dumps({
    "t_import_s": t_import, "t_ready_s": t_ready,
    "t_first_result_s": t_first, "t_total_s": t_ready + t_first,
    "digest": dig, "store": st, "compile_split": split,
    "device": {"platform": jax.devices()[0].platform,
               "kind": jax.devices()[0].device_kind,
               "count": jax.device_count()},
}))
"""


def bench_serve_fleet():
    """The multi-process fleet (docs/20_fleet.md): 1, 2 and 4 slice
    subprocesses behind the front-door router.  It cannot run on a TPU
    yet: each slice process needs a chip of its own, pinned (ROADMAP
    R5), and this parent holds every chip it initializes.  Until then it
    stops with that error; tools/ci.sh's fleet smoke covers the fleet's
    behaviour on the CPU."""
    _require_tpu()
    raise RuntimeError(
        "serve_fleet cannot run on a TPU yet: each slice process needs a "
        "chip of its own, pinned (ROADMAP R5), and this parent already "
        f"holds all {jax.device_count()} chip(s) it initialized"
    )


def bench_serve_cold():
    """Cold-start time-to-first-result with and without a hydrated AOT
    program store (docs/15_program_store.md), at the ``serve`` arm's
    per-request shape.  Each trial is a CLEAN subprocess: the fresh arm
    pays trace+XLA compile via ``serve.warm`` (the bench_serve
    protocol); the hydrated arm warms from the store manifest —
    deserialized executables, zero compiles for store-covered programs
    (asserted via the store hit/fallback counters inside the child).
    Emits ``detail.cold_start``: p50/p99 of the ready/first-result/
    total legs per arm, the speedup, per-profile bitwise digests
    (hydrated == freshly compiled, f64 AND f32), the store's per-entry
    compile seconds + artifact bytes, and a monolithic-path
    trace/compile/execute split probe (``with_report=True``).

    This parent never touches jax: every trial child needs the chip to
    itself.  The store is a fresh directory per run on purpose (the
    hydrated arm must load what this run saved); jax's compile cache is
    never turned on in the children, so the fresh arm compiles."""
    import tempfile

    from cimba_tpu import serve

    wave = int(
        os.environ.get(
            "CIMBA_BENCH_STREAM_WAVE", str(65536)
        )
    )
    req_r = int(
        os.environ.get("CIMBA_BENCH_SERVE_REQ_R", max(wave // 4, 1))
    )
    _, N = _scale(0, 2000)
    chunk = _stream_chunk_default()
    trials = int(os.environ.get("CIMBA_BENCH_COLD_TRIALS", "3"))
    prof = _bench_profile()
    profiles = [prof] + [p for p in ("f64", "f32") if p != prof]
    store_dir = os.environ.get("CIMBA_PROGRAM_STORE") or tempfile.mkdtemp(
        prefix="cimba-store-"
    )

    # build the warm-store artifact per dtype profile (subprocesses, so
    # the battery's own jax config is never rewired mid-run)
    store_info = {}
    for p in profiles:
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools", "warm_store.py"),
                "--store", store_dir, "--configs", "mm1",
                "--wave", str(req_r), "--objects", str(N),
                "--chunk-steps", str(chunk), "--horizons", "none",
                "--profile", p,
            ],
            capture_output=True, text=True, timeout=3600,
        )
        _heartbeat()
        if proc.returncode != 0:
            raise RuntimeError(
                f"warm_store failed for profile {p}: {proc.stderr[-2000:]}"
            )
        store_info[p] = json.loads(proc.stdout.strip().splitlines()[-1])

    def child(arm, p, report=False):
        env = dict(os.environ)
        env.pop("CIMBA_PROGRAM_STORE", None)
        if arm == "hydrated":
            env["CIMBA_PROGRAM_STORE"] = store_dir
        env.update(
            COLD_PROFILE=p, COLD_R=str(req_r), COLD_N=str(N),
            COLD_CHUNK=str(chunk), COLD_SEED="2026",
        )
        if report:
            env["COLD_REPORT"] = "1"
        proc = subprocess.run(
            [sys.executable, "-c", _COLD_CHILD], env=env,
            capture_output=True, text=True, timeout=3600,
        )
        _heartbeat()
        if proc.returncode != 0:
            raise RuntimeError(
                f"serve_cold {arm}/{p} child failed: "
                f"{proc.stderr[-2000:]}"
            )
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        if out["device"]["platform"] != "tpu":
            raise RuntimeError(
                f"serve_cold child ran on {out['device']['platform']}, "
                "not a TPU"
            )
        return out

    runs = {"fresh": [], "hydrated": []}
    split = None
    for i in range(trials):
        fresh = child("fresh", prof, report=(i == 0))
        split = split or fresh["compile_split"]
        runs["fresh"].append(fresh)
        runs["hydrated"].append(child("hydrated", prof))

    # bitwise anchors: hydrated == freshly compiled, BOTH dtype
    # profiles (the bench-profile pair reuses the timed trials)
    bitwise = {}
    for p in profiles:
        if p == prof:
            f, h = runs["fresh"][0], runs["hydrated"][0]
        else:
            f, h = child("fresh", p), child("hydrated", p)
        assert f["digest"] == h["digest"], (
            f"serve_cold: hydrated result diverged from the freshly "
            f"compiled one under {p}"
        )
        bitwise[p] = True

    def leg(arm, key):
        xs = [r[key] for r in runs[arm]]
        return {
            "p50_s": serve.percentile(xs, 50),
            "p99_s": serve.percentile(xs, 99),
        }

    arms = {
        arm: {
            "trials": trials,
            "import": leg(arm, "t_import_s"),
            "ready": leg(arm, "t_ready_s"),
            "first_result": leg(arm, "t_first_result_s"),
            "total": leg(arm, "t_total_s"),
        }
        for arm in ("fresh", "hydrated")
    }
    speedup_ready = (
        arms["fresh"]["ready"]["p50_s"]
        / max(arms["hydrated"]["ready"]["p50_s"], 1e-9)
    )
    detail = {
        "profile": prof,
        "replications_per_request": req_r,
        "objects_per_replication": N,
        "chunk_steps": chunk,
        "store_dir": store_dir,
        "cold_start": {
            "arms": arms,
            # ready = programs-ready (the time-to-first-COMPILE leg the
            # store removes); total = post-import time-to-first-result
            "speedup_ready_p50": speedup_ready,
            "speedup_ttfr_p50": (
                arms["fresh"]["total"]["p50_s"]
                / max(arms["hydrated"]["total"]["p50_s"], 1e-9)
            ),
            "bitwise_vs_fresh": bitwise,
            "compile_split_probe": split,
            "store": {
                p: {
                    "compile_s_total": info["compile_s_total"],
                    "artifact_bytes_total": info["artifact_bytes_total"],
                }
                for p, info in store_info.items()
            },
            "hydrated_store_stats": runs["hydrated"][-1]["store"],
        },
    }
    _line("serve_cold_ttfc_speedup", speedup_ready, None, detail,
          device=runs["fresh"][0]["device"])


def bench_mm1_single():
    """BASELINE configs[0] twin: ``benchmark/MM1_single.c`` — ONE
    replication, the single-stream latency number (reference: ~32M
    events/s on one 3970X core, `docs/background.rst:1443-1445`).

    At R=1 the engine is op-count-bound, not element-bound (every op
    issues once regardless of width): the measured rate validates the
    op-count half of the cost model in tools/kernel_cost.py (~874
    ops/step -> ~1M steps/s/chip predicted on the kernel path).  This
    is a LATENCY config; the throughput story is the vmapped headline.
    ``CIMBA_BENCH_KERNEL=1`` rides the kernel at L=1 (AOT-verified
    offline), default is the XLA while-loop — the closest analog of the
    reference's single-threaded loop."""
    from cimba_tpu.models import mm1

    _, N = _scale(1, 20_000)
    kern = os.environ.get("CIMBA_BENCH_KERNEL")
    if kern and kern != "0":
        from cimba_tpu import config as _cfg

        chunk = int(os.environ.get("CIMBA_BENCH_KERNEL_CHUNK", 512))
        with _cfg.profile("f32"):
            spec, _ = mm1.build(record=False)

            def batch(n):
                return jax.vmap(
                    lambda r: cl.init_sim(spec, 2026, r, mm1.params(n))
                )(jnp.arange(1))

            ev, failed, wall = _time_kernel(spec, batch, 1, N, chunk)
        rate = ev / wall
        _line(
            "mm1_single_events_per_sec",
            rate,
            None,
            {
                "path": "pallas_kernel",
                "profile": "f32",
                "replications": 1,
                "objects": N,
                "total_events": ev,
                "wall_s": wall,
                "failed_replications": failed,
                "reference_single_core_events_per_sec": 32e6,
            },
        )
        return

    from cimba_tpu import config as _cfg
    from cimba_tpu import native

    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = mm1.build(record=False)

        def init_one(rep, n):
            return cl.init_sim(spec, 2026, rep, mm1.params(n))

        ev, failed, wall = _time_vmapped(
            spec, init_one, 1, jnp.int32(1), jnp.int32(N)
        )
    xla_rate = ev / wall
    if native.available():
        # single-stream latency is a serial, cache-resident problem — a
        # CPU-core shape, exactly like the reference's MM1_single on one
        # 3970X core.  The framework's answer is its native C++ engine
        # (native/cimba_native.cpp run_mm1_fast): engine semantics,
        # bitwise-equal trajectories to the scalar oracle (pinned in
        # test_native.py).  The accelerator lanes are the throughput
        # story (the mm1 headline); this is the latency one.
        n_native = max(N, 2_000_000)  # long stream: amortize, steady-state
        arr_mean, srv_mean, _ = mm1.params(1)  # the config's own rates
        native.mm1_single(2026, 0, 50_000, arr_mean, srv_mean)  # warm
        t0 = time.perf_counter()
        r = native.mm1_single(2026, 0, n_native, arr_mean, srv_mean)
        nwall = time.perf_counter() - t0
        _line(
            "mm1_single_events_per_sec",
            r["events"] / nwall,
            None,
            {
                "path": "native_cpp_single_core",
                # True = the 4-slot fast path tripped its invariant and
                # the number above came from the run_mm1 fallback — a
                # structured failure signal, never an abort
                "native_fast_path_overflow": r.get(
                    "fast_path_overflow", False
                ),
                "replications": 1,
                "objects": n_native,
                "total_events": r["events"],
                "wall_s": nwall,
                "failed_replications": 0,
                "mean_sojourn": r["mean"],
                "xla_while_events_per_sec": xla_rate,
                "xla_profile": prof,
                "reference_single_core_events_per_sec": 32e6,
            },
        )
        return
    _line(
        "mm1_single_events_per_sec",
        xla_rate,
        None,
        {
            "path": "xla_while",
            "profile": prof,
            "replications": 1,
            "objects": N,
            "total_events": ev,
            "wall_s": wall,
            "failed_replications": failed,
            "reference_single_core_events_per_sec": 32e6,
        },
    )


def bench_mmc():
    """BASELINE configs[1]: M/M/c resource-pool queue (c=3, rho~0.83)."""
    from cimba_tpu.models import mmc

    from cimba_tpu import config as _cfg

    c = 3
    # R raised after the 2026-07-31 probe showed the engine still
    # overhead-bound at 2048 lanes (mm1 scaled 4x from 4096->65536)
    R, N = _scale(65536, 1000)
    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = mmc.build(c)

        def init_one(rep, n):
            return cl.init_sim(spec, 2026, rep, mmc.params(n, 2.5, 1.0))

        ev, failed, wall = _time_vmapped(
            spec, init_one, R, jnp.int32(1), jnp.int32(N)
        )
        detail = {
            "c": c,
            "profile": prof,
            "replications": R,
            "objects_per_replication": N,
            "total_events": ev,
            "wall_s": wall,
            "failed_replications": failed,
        }
        if failed:
            detail["regrow"] = _regrow_pass(
                spec, mmc.params(N, 2.5, 1.0), R
            )
    _line("mmc_events_per_sec", ev / wall, None, detail)


def bench_mg1():
    """BASELINE configs[2]: the M/G/1 lognormal-service sweep — the
    reference's 4 CVs x 5 utilizations x 10 reps experiment array
    (README.md:283-294, ~1.5 s for 200 trials x 1e6 time units on the
    64-core box)."""
    from cimba_tpu.models import mg1

    from cimba_tpu import config as _cfg

    # reps_per_cell raised after the 2026-07-31 probe (R = 20 cells x
    # reps; 400 lanes left the chip overhead-bound like mm1 at 4096)
    reps, N = _scale(2000, 2000)
    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = mg1.build()
        # the declarative grid (docs/16_sweeps.md) — rows() reproduces
        # the historical hand-rolled experiment array bitwise
        grid = mg1.sweep_grid(N)
        params, cell_ids = grid.rows(reps)
        warm, _ = mg1.sweep_grid(1).rows(reps)
        R = len(cell_ids)

        def init_one(rep, args):
            lane = tuple(a[rep] for a in args)
            return cl.init_sim(spec, 2026, rep, lane)

        # the packed+hierarchical-vs-flat battery runs the sweep too
        # (same R x N per arm, interleaved best-of-k through
        # tune.measure.measure_arms — one timing implementation), so
        # the layout cost is measured on a second model class beside
        # the mm1 headline
        report, measured = _measure_dispatch_arms(
            lambda: spec, lambda s: init_one, R, warm, params, prof,
        )
        arms = {
            name: {
                "events_per_sec": m["rate"],
                "wall_s": m["wall_s"],
                "replications": R,
                "objects_per_replication": N,
                "failed_replications": m["failed"],
                "repeats_best_of": report.rounds_done,
            }
            for name, m in measured.items()
        }
        arm = max(
            (n for n in measured if measured[n]["rate"]),
            key=lambda n: measured[n]["rate"],
        )
        m = measured[arm]
        rate, ev, failed, wall = (
            m["rate"], m["events"], m["failed"], m["wall_s"],
        )
        detail = {
            "cells": "4cv x 5rho",
            "sweep_grid": {
                "axes": {k: list(v) for k, v in grid.axes.items()},
                "n_cells": grid.n_cells,
            },
            "profile": prof,
            "dispatch_arm": arm,
            "dispatch_arms": arms,
            "reps_per_cell": reps,
            "replications": R,
            "objects_per_replication": N,
            "total_events": ev,
            "wall_s": wall,
            "failed_replications": failed,
            "reference_wall_s_200x1e6_units": 1.5,
        }
        if failed:
            detail["regrow"] = _regrow_pass(spec, params, R)
    _line("mg1_sweep_events_per_sec", rate, None, detail)


def bench_sweep():
    """Fixed-R vs adaptive-R sequential stopping on the SAME M/G/1 grid
    (docs/16_sweeps.md): the adaptive arm runs each cell only until its
    CI halfwidth beats a relative target (freed lanes go to the cells
    still converging); the fixed arm sizes EVERY cell for the worst
    cell's demand — what you'd have to run without sequential stopping
    to make the same per-cell guarantee.  Reports cells/s, total
    replications spent per arm, per-cell halfwidth-target attainment,
    and the replication savings fraction (acceptance: >= 30%).  The
    watchdog heartbeat refreshes every round and every chunk.

    Overrides: CIMBA_BENCH_SWEEP_TARGET (relative halfwidth, default
    0.08), CIMBA_BENCH_SWEEP_ROUNDS (adaptive round cap), plus the
    standard CIMBA_BENCH_R (round replications per cell) and
    CIMBA_BENCH_OBJECTS (per-replication workload)."""
    from cimba_tpu import config as _cfg
    from cimba_tpu import sweep as sw
    from cimba_tpu.models import mg1
    from cimba_tpu.serve import cache as _pcache

    import numpy as np

    R0, N = _scale(64, 2000)
    target = float(os.environ.get("CIMBA_BENCH_SWEEP_TARGET", "0.08"))
    max_rounds = int(os.environ.get("CIMBA_BENCH_SWEEP_ROUNDS", "24"))
    chunk = _stream_chunk_default()
    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = mg1.build()
        grid = mg1.sweep_grid(N)
        # floored at R0: a large CIMBA_BENCH_R override must widen the
        # physical wave with it, not trip the cell_wave<=max_wave check
        max_wave = max(min(4096, max(4 * R0, 64)), R0)
        rule = sw.HalfwidthTarget(
            target=target, relative=True, min_reps=2 * R0
        )
        cache = _pcache.ProgramCache(capacity=256)
        # redistribute=False keeps the comparison honest: with freed
        # lanes redistributed, the last live cell's final round can
        # overshoot its actual demand by up to a whole oversized round,
        # and sizing the fixed arm from that inflated worst would
        # overstate the savings.  R0 per live cell per round means
        # adaptive.n_reps.max() IS the worst cell's demand at R0
        # granularity — the same granularity the fixed arm pays.
        common = dict(
            seed=2026, cell_wave=R0, max_wave=max_wave,
            chunk_steps=chunk, pad_waves=True, redistribute=False,
            program_cache=cache, on_round=_heartbeat,
            on_chunk=_heartbeat,
        )
        # warm the init/chunk/fold programs at the quantized wave
        # shapes with a tiny-workload twin grid (the _time_vmapped
        # warm-then-time protocol)
        sw.run_sweep(
            spec, mg1.sweep_grid(1), reps_per_cell=R0, **common
        )

        t0 = time.perf_counter()
        adaptive = sw.run_sweep(
            spec, grid, reps_per_cell=R0, stop=rule,
            max_rounds=max_rounds, **common,
        )
        wall_a = time.perf_counter() - t0
        _heartbeat()

        # fixed-R sized for the worst cell: every cell gets the most
        # replications ANY cell needed under the same target
        worst = int(adaptive.n_reps.max())
        t0 = time.perf_counter()
        fixed = sw.run_sweep(
            spec, grid, reps_per_cell=worst, **common
        )
        wall_f = time.perf_counter() - t0
        _heartbeat()
        fixed_met = rule.met(fixed.summaries, fixed.n_reps)

        reps_a = int(adaptive.n_reps.sum())
        reps_f = worst * grid.n_cells
        savings = 1.0 - reps_a / reps_f

        def arm_detail(res, wall, met, reps_total):
            return {
                "wall_s": wall,
                "cells_per_sec": grid.n_cells / wall,
                "total_replications": reps_total,
                "cells_met_target": int(np.asarray(met).sum()),
                "events": int(res.total_events.sum()),
                "rounds": res.n_rounds,
                "reps_by_cell": res.n_reps.tolist(),
                "halfwidth_by_cell": [
                    round(float(h), 6) for h in res.halfwidth
                ],
                "occupancy": {
                    k: v for k, v in res.occupancy.items()
                    if k != "slots_by_cell"
                },
            }

        detail = {
            "profile": prof,
            "grid": {
                "axes": {k: list(v) for k, v in grid.axes.items()},
                "n_cells": grid.n_cells,
            },
            "objects_per_replication": N,
            "round_reps_per_cell": R0,
            "halfwidth_target_rel": target,
            "confidence": rule.confidence,
            "adaptive": arm_detail(adaptive, wall_a, adaptive.met, reps_a),
            "fixed_worst_cell": arm_detail(
                fixed, wall_f, fixed_met, reps_f
            ),
            "replications_saved_frac": savings,
            "stop_round_by_cell": adaptive.stop_round.tolist(),
        }
    _line(
        "sweep_cells_per_sec", grid.n_cells / wall_a, None, detail,
        unit="cells/s",
    )


def bench_tandem():
    """Tandem Jackson network (models/tandem.py): the queueing-NETWORK
    workload, run across its (arr_rate, p_back) sweep grid at the
    monolithic dispatch — the model library's sweep-able network
    config, with the analytic per-station sojourns as the sanity
    anchor."""
    from cimba_tpu import config as _cfg
    from cimba_tpu.models import tandem

    R, N = _scale(65536, 400)
    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = tandem.build()
        grid = tandem.sweep_grid(N)
        reps = max(R // grid.n_cells, 1)
        params, cell_ids = grid.rows(reps)
        warm, _ = tandem.sweep_grid(1).rows(reps)
        R = len(cell_ids)

        def init_one(rep, args):
            lane = tuple(a[rep] for a in args)
            return cl.init_sim(spec, 2026, rep, lane)

        ev, failed, wall = _time_vmapped(
            spec, init_one, R, warm, params
        )
        detail = {
            "profile": prof,
            "sweep_grid": {
                "axes": {k: list(v) for k, v in grid.axes.items()},
                "n_cells": grid.n_cells,
            },
            "reps_per_cell": reps,
            "replications": R,
            "objects_per_replication": N,
            "total_events": ev,
            "wall_s": wall,
            "failed_replications": failed,
            "theory_mean_visit_sojourn_defaults": (
                tandem.mean_visit_sojourn(0.5, 1.0, 1.25, 0.25)
            ),
        }
        if failed:
            detail["regrow"] = _regrow_pass(spec, params, R)
    _line("tandem_events_per_sec", ev / wall, None, detail)


def bench_jobshop():
    """BASELINE configs[3]: job-shop network — buffers + condition vars
    (ref tut_4_2)."""
    from cimba_tpu.models import jobshop

    from cimba_tpu import config as _cfg

    # R raised after the 2026-07-31 probe (see bench_mmc)
    R, N = _scale(65536, 400)
    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = jobshop.build()

        def init_one(rep, n):
            return cl.init_sim(spec, 2026, rep, jobshop.params(n))

        ev, failed, wall = _time_vmapped(
            spec, init_one, R, jnp.int32(1), jnp.int32(N)
        )
        detail = {
            "profile": prof,
            "replications": R,
            "jobs_per_replication": N,
            "total_events": ev,
            "wall_s": wall,
            "failed_replications": failed,
        }
        if failed:
            detail["regrow"] = _regrow_pass(spec, jobshop.params(N), R)
    _line("jobshop_events_per_sec", ev / wall, None, detail)


def bench_awacs():
    """BASELINE configs[4]: AWACS — 1000 target processes + NN-scored radar
    dwells (ref tutorial/tut_5_1.c at n=1000; reference runs 300 trials x
    6 h simulated in 78 s on 3970X + 2x RTX 3090).  This is the engine at
    reference scale: 1001 process rows, dense wake-table pop over [P]."""
    from cimba_tpu.models import awacs

    n_targets = int(os.environ.get("CIMBA_BENCH_AWACS_TARGETS", 1000))
    # R=1024 measured 7.7M events/s on v5e under f64 (2026-07-31 probe;
    # R=16 left ~14x on the table).  4096 lanes under f32 follows the
    # mm1 lane-scaling curve (~50 KB/lane Sim -> ~200 MB HBM, ~1 s
    # device time) — validated end-to-end at the next hardware window.
    R, t_end = (4096, 40.0)
    # the standard overrides: R = lanes, OBJECTS = per-lane workload (here
    # the simulated horizon, the knob that scales events per lane)
    R = int(os.environ.get("CIMBA_BENCH_R", R))
    t_end = float(os.environ.get("CIMBA_BENCH_OBJECTS", t_end))

    kern = os.environ.get("CIMBA_BENCH_KERNEL")
    if kern and kern != "0":
        # kernel path: the ~90 KB/lane Sim caps VMEM residency at L=128
        # (BENCH_NOTES round 4); the XLA path above is HBM-resident and
        # has no such cap
        R = min(R, int(os.environ.get("CIMBA_BENCH_KERNEL_RMAX", 128)))
        # flagship through the kernel + boundary-block path: DES events
        # step in Pallas chunks, the NN dwell scorer runs between chunks
        # as batched MXU matmuls (models/awacs.py sensor_dwell)
        from cimba_tpu import config as _cfg

        chunk = int(os.environ.get("CIMBA_BENCH_KERNEL_CHUNK", 512))
        mesh = _kernel_mesh()
        with _cfg.profile("f32"):
            spec, _ = awacs.build(n_targets)

            def batch(t):
                return jax.vmap(
                    lambda r: cl.init_sim(spec, 2026, r, (t,))
                )(jnp.arange(R))

            ev, failed, wall = _time_kernel(
                spec, batch, jnp.asarray(0.5), jnp.asarray(t_end), chunk,
                mesh,
            )
        _line(
            "awacs_events_per_sec",
            ev / wall,
            None,
            {
                "path": "pallas_kernel+boundary",
                "profile": "f32",
                "n_targets": n_targets,
                "mesh_devices": mesh.devices.size if mesh else 1,
                "chunk_steps": chunk,
                "replications": R,
                "t_end": t_end,
                "total_events": ev,
                "wall_s": wall,
                "failed_replications": failed,
                "reference_wall_s_300x6h": 78.0,
            },
        )
        return

    from cimba_tpu import config as _cfg

    prof = _bench_profile()
    with _cfg.profile(prof):
        spec, _ = awacs.build(n_targets)

        def init_one(rep, t):
            return cl.init_sim(spec, 2026, rep, (t,))

        ev, failed, wall = _time_vmapped(
            spec, init_one, R, jnp.asarray(0.5), jnp.asarray(t_end)
        )
        detail = {
            "path": "xla_while",
            "profile": prof,
            "n_targets": n_targets,
            "replications": R,
            "t_end": t_end,
            "total_events": ev,
            "wall_s": wall,
            "failed_replications": failed,
            "reference_wall_s_300x6h": 78.0,
        }
        if failed:
            detail["regrow"] = _regrow_pass(spec, (t_end,), R)
    _line("awacs_events_per_sec", ev / wall, None, detail)


def bench_tune():
    """The schedule-autotuner battery (docs/21_autotune.md): run the
    budgeted search over the dispatch-knob arms on TWO workloads — the
    mm1 headline shape and the mutation-bursty step probe
    (``cimba_tpu/tune/probe.py``, whose hand-frozen default BENCH_NOTES
    round 6 proved wrong: the hierarchical event-set loses on
    re-arm-heavy workloads).  Every arm is bitwise-pinned against the
    default schedule inside the search; the line reports, per
    workload, the winner-vs-default speedup WITH the measured
    self-vs-self noise floor printed alongside (a win below the floor
    HOLDs the default — honesty over trophies).  With
    ``CIMBA_PROGRAM_STORE`` set, a winning schedule persists into the
    store manifest and every serving entry point resolves it from then
    on (``CIMBA_TUNE=0`` opts out).  Knobs:
    ``CIMBA_BENCH_TUNE_REPEATS`` (best-of-k depth),
    ``CIMBA_BENCH_TUNE_BUDGET_S`` (per-workload wall budget —
    successive halving past it), ``CIMBA_BENCH_TUNE_PROBE_R``."""
    from cimba_tpu import config as _cfg
    from cimba_tpu import tune as _tune
    from cimba_tpu.serve import store as pstore
    from cimba_tpu.models import mm1
    from cimba_tpu.tune import probe as _tprobe
    from cimba_tpu.tune.space import Schedule

    prof = _bench_profile()
    R, N = _scale(4096, 2000)
    repeats = max(1, int(os.environ.get(
        "CIMBA_BENCH_TUNE_REPEATS", "1"
    )))
    budget = float(os.environ.get("CIMBA_BENCH_TUNE_BUDGET_S", "600"))
    out_dir = os.environ.get("CIMBA_BENCH_RUN_CARD") or None
    # the bench arms: the round-6 dispatch knobs plus the chunk grid
    # (each a distinct compiled program — the full default_space grid
    # is a hardware-campaign budget, not a battery's)
    cands = [
        Schedule(),
        Schedule(eventset_hier=False),
        Schedule(pack=True),
        Schedule(pack=False),
        Schedule(chunk_steps=256),
        Schedule(chunk_steps=4096),
    ]

    def one(name, spec, params, reps, warm_params, t_end=None,
            candidates=None, runner=None):
        _heartbeat()
        rep = _tune.search_schedule(
            spec, params, reps,
            candidates=candidates if candidates is not None else cands,
            seed=2026, t_end=t_end,
            warm_params=warm_params, repeats=repeats, budget_s=budget,
            out_dir=out_dir, workload_label=name, runner=runner,
            on_round=lambda r: _heartbeat(),
        )
        _heartbeat()
        saved = None
        st = pstore.default_store()
        if st is not None and rep.decision == "tuned":
            saved = _tune.save_tuned(st, spec, reps, rep) is not None
        return rep, {
            "decision": rep.decision,
            "winner": rep.winner.to_json(),
            "winner_arm": rep.winner_name,
            "speedup_frac": rep.speedup_frac,
            "noise_floor_frac": rep.noise_floor_frac,
            "bucket": rep.bucket,
            "all_pinned": all(
                row["pinned"] is not False for row in rep.arms
            ),
            "persisted": saved,
            "arms": [
                {
                    "name": row["name"],
                    "status": row["status"],
                    "best_wall_s": row["best_wall_s"],
                    "rate": row["rate"],
                    "compile_s": row["compile_s"],
                    "pinned": row["pinned"],
                }
                for row in rep.arms
            ],
            "search_wall_s": rep.wall_s,
        }

    detail = {"profile": prof, "workloads": {}}
    with _cfg.profile(prof):
        spec, _ = mm1.build(record=False)
        rep_mm1, detail["workloads"]["mm1"] = one(
            "mm1", spec, mm1.params(N), R, mm1.params(1),
        )
        probe_R = int(os.environ.get("CIMBA_BENCH_TUNE_PROBE_R", "64"))
        pspec, _ = _tprobe.build()
        rep_probe, detail["workloads"]["step_probe"] = one(
            "step_probe", pspec, None, probe_R, None,
            t_end=float(os.environ.get(
                "CIMBA_BENCH_TUNE_PROBE_T", str(_tprobe.DEFAULT_T_END)
            )),
        )
        # third workload: the device-scheduler policy knobs
        # (docs/24_device_scheduler.md), invisible to the direct
        # stream path — the serve-backed runner hook races each
        # candidate through the same preempt-shaped contention load
        # (one long low-priority background + an urgent burst).  The
        # bitwise pin rides the serve contract: per-request results
        # never depend on scheduling policy, so every arm's merged
        # payload digests equal and only the wall moves.  A "tuned"
        # decision persists waves_per_device/preempt_quantum/
        # mem_fraction into the store manifest like any other knob,
        # and Service adopts them at submit time.
        from cimba_tpu import serve as _serve

        ds_wave = 1024
        ds_chunk = 256
        ds_r = max(ds_wave // 4, 1)
        n_ds = 2000
        bg_objs, ur_objs = 100 * n_ds, 2 * n_ds
        ds_cache = _serve.ProgramCache()
        ds_cands = [
            Schedule(),
            Schedule(waves_per_device=2),
            Schedule(waves_per_device=4),
            Schedule(preempt_quantum=1),
            Schedule(preempt_quantum=8),
            Schedule(mem_fraction=0.6),
        ]

        class _Merged:
            """StreamResult-shaped merge of one contention round, in
            submission order — what the pin digests and the rate
            counts events from."""

            def __init__(self, results):
                self.summary = tuple(r.summary for r in results)
                self.n_failed = sum(int(r.n_failed) for r in results)
                self.total_events = sum(
                    int(r.total_events) for r in results
                )
                self.metrics = None

        def _ds_req(n_obj, seed, t_end, prio, label):
            return _serve.Request(
                spec, mm1.params(n_obj), ds_r, seed=seed, t_end=t_end,
                wave_size=ds_r, chunk_steps=ds_chunk, priority=prio,
                label=label,
            )

        def ds_runner(sched, warm=False):
            svc = _serve.Service(
                max_wave=ds_wave, cache=ds_cache, device_sched=True,
                waves_per_device=sched.waves_per_device,
                preempt_quantum=sched.preempt_quantum,
                mem_fraction=sched.mem_fraction,
                refill_every=2, horizon_bucket=16.0, pad_waves=False,
                on_chunk=_heartbeat,
            )
            try:
                bg = svc.submit(_ds_req(bg_objs, 1, 60000.0, 0, "bg"))
                # urgents must land against a RUNNING wave or there
                # is no scheduling decision to measure
                deadline = time.monotonic() + 120
                while (svc.stats()["lane_occupancy"]["lanes_in_wave"]
                       == 0 and time.monotonic() < deadline):
                    time.sleep(0.002)
                urs = [
                    svc.submit(_ds_req(
                        ur_objs, 11 + i % 3, 60.0, 10, f"ur{i}"
                    ))
                    for i in range(6)
                ]
                results = [h.result(600) for h in urs]
                results.append(bg.result(600))
            finally:
                svc.shutdown()
            return _Merged(results)

        rep_ds, detail["workloads"]["device_sched"] = one(
            "device_sched", spec, mm1.params(bg_objs), ds_r,
            None, candidates=ds_cands, runner=ds_runner,
        )
    best = max(
        detail["workloads"].values(), key=lambda w: w["speedup_frac"],
    )
    detail["headline"] = (
        "winner-vs-default speedup on the best workload; HOLD "
        "decisions report 0 — the floor is printed per workload"
    )
    _line(
        "tune_winner_speedup_frac",
        best["speedup_frac"],
        None,
        detail,
        unit="frac",
    )


def bench_compile_wall():
    """BASELINE configs[+]: the compile wall (docs/25_compile_wall.md)
    — AWACS chunk-program trace+lower+compile wall seconds and program
    size across P (process-table height) for BOTH table-dispatch arms
    (dense one-hot vs scan-over-rows), interleaved best-of-k through
    ``tune.measure.measure_arms`` with the self-vs-self noise twin (the
    PR 14 measurement contract).  Runs on the CPU container: the wall
    being measured is XLA's, not the accelerator's — the Mosaic-AOT leg
    of the same story is tracked in BENCH_NOTES (dense AWACS at Lb=1024
    is compile-prohibitive, >25 min)."""
    from cimba_tpu import config as _cfg
    from cimba_tpu.models import awacs
    from cimba_tpu.obs import program_size as _ps
    from cimba_tpu.tune import measure as _tm

    lanes = int(os.environ.get("CIMBA_BENCH_R", 4))
    repeats = int(os.environ.get("CIMBA_BENCH_REPEATS", 2))
    scales = tuple(
        int(x) for x in os.environ.get(
            "CIMBA_BENCH_COMPILE_WALL_P", "32,256,1001"
        ).split(",")
    )
    max_steps = int(os.environ.get("CIMBA_BENCH_KERNEL_CHUNK", 64))
    prof = _bench_profile()

    def compile_once(spec, scan):
        """One full trace+lower+compile of a FRESH chunk program under
        the given table arm — fresh ``make_chunk`` closure per call so
        neither the jit cache nor tracing memos can shortcut the wall
        being measured."""
        prev = (_cfg.TABLE_SCAN, _cfg.TABLE_SCAN_BLOCK)
        _cfg.TABLE_SCAN = scan
        try:
            with _cfg.profile(prof):
                sims = jax.eval_shape(
                    jax.vmap(lambda r: cl.init_sim(spec, 2026, r, (1.0,))),
                    jnp.arange(lanes),
                )
                fn = cl.make_chunk(spec, max_steps=max_steps)
                jax.jit(fn).lower(sims).compile()
        finally:
            _cfg.TABLE_SCAN, _cfg.TABLE_SCAN_BLOCK = prev

    for n_p in scales:
        with _cfg.profile(prof):
            spec, _ = awacs.build(n_p - 1)   # + the sensor process = P rows
        sizes = {}
        for name, scan in (("dense", False), ("scan", True)):
            prev = (_cfg.TABLE_SCAN, _cfg.TABLE_SCAN_BLOCK)
            _cfg.TABLE_SCAN = scan
            try:
                sizes[name] = _ps.chunk_program_size(
                    spec, (1.0,), lanes=lanes, max_steps=max_steps,
                    profile=prof,
                ).to_dict()
            finally:
                _cfg.TABLE_SCAN, _cfg.TABLE_SCAN_BLOCK = prev
        report = _tm.measure_arms(
            [
                _tm.Arm("dense", run=lambda spec=spec: compile_once(spec, False),
                        program_size=sizes["dense"]),
                _tm.Arm("scan", run=lambda spec=spec: compile_once(spec, True),
                        program_size=sizes["scan"]),
            ],
            repeats=repeats, baseline=0, noise_twin=True,
        )
        dense_w = report.arm("dense").best_wall
        scan_w = report.arm("scan").best_wall
        _line(
            "awacs_compile_wall_speedup",
            dense_w / scan_w if dense_w and scan_w else None,
            None,
            {
                "path": "xla_compile",
                "profile": prof,
                "n_processes": n_p,
                "lanes": lanes,
                "max_steps": max_steps,
                "dense_wall_s": dense_w,
                "scan_wall_s": scan_w,
                "noise_floor_frac": report.noise_floor_frac,
                "rounds": report.rounds_done,
                "program_size": sizes,
            },
            unit="x",
        )


CONFIGS = {
    "mm1": bench_mm1,
    "mm1_stream": bench_mm1_stream,
    "mm1_single": bench_mm1_single,
    "serve": bench_serve,
    "serve_cold": bench_serve_cold,
    "serve_fleet": bench_serve_fleet,
    "serve_mixed": bench_serve_mixed,
    "serve_preempt": bench_serve_preempt,
    "serve_qos": bench_serve_qos,
    "serve_refill": bench_serve_refill,
    "serve_fused": bench_serve_fused,
    "mmc": bench_mmc,
    "mg1": bench_mg1,
    "sweep": bench_sweep,
    "tandem": bench_tandem,
    "tune": bench_tune,
    "jobshop": bench_jobshop,
    "awacs": bench_awacs,
    "compile_wall": bench_compile_wall,
}


#: configs whose parent starts children that need the chip: they run
#: alone (``--config <name>``), never inside ``--config all``, where an
#: earlier config would already hold the chip
_ALONE = ("serve_cold", "serve_fleet")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--config",
        default="mm1",
        choices=sorted(CONFIGS) + ["all"],
        help="which BASELINE config to run (default: the mm1 headline; "
        "'all' runs every config but " + ", ".join(_ALONE) + ")",
    )
    which = ap.parse_args().config
    _watchdog(which)
    if which == "all":
        names = [n for n in sorted(CONFIGS) if n not in _ALONE]
    else:
        names = [which]
    if which != "serve_cold":
        # serve_cold's parent stays off jax (its children hold the chip)
        _require_tpu()
        from cimba_tpu.serve import store as _pstore

        _pstore.maybe_enable_persistent_cache()
    # headline first so line 1 is always the driver's metric
    if "mm1" in names:
        names.remove("mm1")
        names.insert(0, "mm1")
    for name in names:
        CONFIGS[name]()


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # structured failure beats a bare traceback
        print(
            json.dumps(
                {
                    "metric": "events_per_sec",
                    "value": None,
                    "unit": "events/s",
                    "vs_baseline": None,
                    "detail": {"error": f"{type(e).__name__}: {e}"},
                }
            )
        )
        raise
