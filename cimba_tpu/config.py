"""Global configuration for cimba-tpu.

The reference (cimba) does platform detection and TLS-model selection in
``src/cmi_config.h``.  The TPU-native analog is dtype discipline and JAX
global configuration:

* Simulated **time is float64**.  A clock near 1e6 with unit-scale increments
  needs ~1e-10 relative resolution for stable event ordering; float32's
  epsilon at 1e6 is 0.0625 which would corrupt waiting-time statistics.
  float64 is software-emulated on TPU but only the clock/event-time arrays
  pay that cost.
* **Sample values, amounts and statistics accumulate in float64** as well so
  that per-replication summaries are reproducible against the scalar oracle.
* **Indices, handles, program counters are int32** (TPU-native width).
* **RNG internals are uint32** (threefry2x32 counters/keys), which is the
  natively fast integer width on TPU.

Importing :mod:`cimba_tpu` enables ``jax_enable_x64``.  All framework arrays
carry explicit dtypes, so user code that wants pure-32-bit models can still
build them.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

#: Simulated-time dtype (see module docstring).  Mutable — see
#: :func:`use_profile`; read it through :data:`TIME` at trace time.
TIME_DTYPE = jnp.float64
#: Continuous sample / statistics dtype.  Mutable — see :func:`use_profile`.
REAL_DTYPE = jnp.float64
#: Wide event-counter dtype (``sim.n_events``).  Mutable with the profile:
#: int64 in the exact profile, int32 in the f32 profile (Mosaic has no i64).
COUNT_DTYPE = jnp.int64
#: Index / handle dtype.
INDEX_DTYPE = jnp.int32
#: Signal codes are int32 (the reference uses int64 signals; int32 covers the
#: protocol and all practical user signals; see core/signals.py).
SIGNAL_DTYPE = jnp.int32
#: RNG word dtype.
BITS_DTYPE = jnp.uint32

#: Sentinel "time" for empty event slots: +inf sorts after every real event.
TIME_NEVER = float("inf")


def argmax32(x, axis: int = 0):
    """``jnp.argmax`` with an int32 result.  Under x64, jnp's arg-reductions
    return int64 — and Mosaic's int64→int32 convert rule recurses forever,
    so everything in a potential kernel path uses these.  Mosaic's
    arg-reduction lowering supports only f32 operands, so bool/int masks
    (every call site's operand is a mask, a small int, or a time) are cast;
    ties keep lowest-index semantics either way."""
    from jax import lax

    if x.dtype != jnp.float32 and x.dtype != jnp.float64:
        x = x.astype(jnp.float32)
    return lax.argmax(x, axis, jnp.int32)


def argmin32(x, axis: int = 0):
    """``jnp.argmin`` with an int32 result (see :func:`argmax32`)."""
    from jax import lax

    if x.dtype != jnp.float32 and x.dtype != jnp.float64:
        x = x.astype(jnp.float32)
    return lax.argmin(x, axis, jnp.int32)


class _DtypeHandle:
    """A live view of a mutable config dtype.

    numpy's dtype protocol resolves any object with a ``.dtype`` attribute,
    so a handle can stand wherever a dtype literal can: ``jnp.asarray(x, _R)``,
    ``x.astype(_R)``, ``jnp.zeros((), _R)``.  Calling it casts a scalar,
    mirroring ``jnp.float64(x)``.  Modules alias these once
    (``_R = config.REAL``) and automatically follow :func:`use_profile`
    switches at trace time — which is how the same interpreter traces in
    float64 for the exact XLA path and in float32 inside the Pallas
    mega-kernel (Mosaic/TPU has no 64-bit types).
    """

    def __init__(self, name: str):
        self._name = name

    @property
    def dtype(self):
        return jnp.dtype(globals()[self._name])

    def __call__(self, x):
        return jnp.asarray(x, globals()[self._name])

    def __repr__(self):
        return f"config.{self._name}(={self.dtype.name})"

    # Hash/eq follow the CURRENT resolution, not object identity: jax's
    # ``canonicalize_dtype`` memoizes on the dtype argument, and with
    # id-based hashing the first profile to resolve a handle poisoned
    # every later trace under the other profile (f64 clocks inside an
    # f32 trace — the cross-profile branch-dtype mismatches this fixes).
    def __hash__(self):
        return hash(self.dtype)

    def __eq__(self, other):
        if isinstance(other, _DtypeHandle):
            return self.dtype == other.dtype
        try:
            return self.dtype == jnp.dtype(other)
        except TypeError:
            return NotImplemented


TIME = _DtypeHandle("TIME_DTYPE")
REAL = _DtypeHandle("REAL_DTYPE")
COUNT = _DtypeHandle("COUNT_DTYPE")

_PROFILES = {
    # exact profile: matches the scalar oracle bit-for-bit; default.
    "f64": dict(TIME_DTYPE=jnp.float64, REAL_DTYPE=jnp.float64,
                COUNT_DTYPE=jnp.int64),
    # TPU-kernel profile: every array Mosaic-representable (no 64-bit
    # types).  Clock resolution is f32 (documented envelope: fine for runs
    # with t_end * eps32 well below the smallest meaningful interval);
    # statistics accumulate in f32.
    "f32": dict(TIME_DTYPE=jnp.float32, REAL_DTYPE=jnp.float32,
                COUNT_DTYPE=jnp.int32),
}

_ACTIVE_PROFILE = "f64"

# --- CIMBA_* environment knob registry (docs/19_static_analysis.md) ---------
#
# Every environment variable the PACKAGE reads is declared here and read
# through :func:`env_raw` — the round-trip rule CHK005 in tools/check.py
# enforces statically.  ``trace_gate=True`` marks knobs that change what
# a traced program looks like; each of those must be claimed by a gate
# in :mod:`cimba_tpu.check.gates`, whose registry sweep proves the
# off-state is jaxpr-identical to the baseline (tests/test_check.py has
# the completeness test).  Operator-tool knobs (CIMBA_BENCH_*, sweep
# probes, examples) stay outside: they configure host scripts, never
# library trace state.

ENV_KNOBS = {
    # trace-time program gates (registry-swept in check/gates.py)
    "CIMBA_AUDIT": dict(
        default="", trace_gate=True,
        doc="determinism audit collection (obs/audit.py; the chunk "
            "program's audit arm is an explicit argument — the env var "
            "only selects host-side collection, pinned ambient-inert)",
    ),
    "CIMBA_TUNE": dict(
        default="1", trace_gate=True,
        doc="tuned-schedule resolution (tune/registry.py): =0 opts "
            "every entry point out of resolving searched dispatch "
            "schedules from the program store — programs are then "
            "jaxpr-identical to the hand-frozen defaults (the 'tune' "
            "gate in check/gates.py pins this); explicit kwargs "
            "always win either way (docs/21_autotune.md)",
    ),
    "CIMBA_REFILL": dict(
        default="", trace_gate=True,
        doc="continuous wave refill (docs/22_refill.md): =1 makes "
            "Service(refill=None) recycle dead lanes at chunk "
            "boundaries — retire a finished request's lanes early and "
            "splice queued compatible requests into them.  Purely a "
            "HOST-side dispatch policy: the chunk program is untouched "
            "(the 'refill' gate in check/gates.py pins ambient "
            "inertness), and the refill/liveness programs are separate "
            "compiles keyed by the same compatibility class",
    ),
    "CIMBA_TABLE_SCAN": dict(
        default="", trace_gate=True,
        doc="scan-over-rows process-table dispatch (core/dyn.py, "
            "docs/25_compile_wall.md): =1 replaces the dense one-hot "
            "expand/select over full [P, ...] component tables with a "
            "counted loop over fixed-size row blocks, so emitted "
            "program text references one block regardless of P.  Off "
            "(the default) is jaxpr character-identical to the dense "
            "dispatch; on is bitwise result-identical (same one-hot "
            "pick within the owning block).  Only engages on axes "
            "strictly taller than the block size — structurally inert "
            "for small-P models",
    ),
    "CIMBA_TABLE_SCAN_BLOCK": dict(
        default="128", trace_gate=True,
        doc="row-block height for the scan-over-rows table dispatch "
            "(sublane-friendly multiple; axes <= the block stay dense)",
    ),
    "CIMBA_WAVE_FUSE": dict(
        default="", trace_gate=True,
        doc="cross-spec wave fusion (docs/26_wave_fusion.md): =1 makes "
            "Service(fuse=None) pack compatible-shape DIFFERENT specs "
            "into one fused wave whose init/refill lax.switch each "
            "lane through its own member's model on a per-lane "
            "spec-id column.  Off (the default) every wave stays "
            "single-class and traces the character-identical "
            "historical programs (the 'wave_fuse' gate in "
            "check/gates.py pins ambient inertness); on, a member "
            "lane's trajectory is bitwise its solo per-spec wave's "
            "(core/fuse.py has the argument)",
    ),
    "CIMBA_QOS": dict(
        default="", trace_gate=True,
        doc="multi-tenant QoS plane (docs/27_qos.md): =1 makes "
            "Service(qos=None) apportion freed refill lanes across "
            "tenants by deficit-weighted round robin, order equal-"
            "priority requests within a class by earliest deadline "
            "(EDF), and enforce per-tenant quotas/rate limits at "
            "submit with structured RetryAfter backpressure.  Purely "
            "a HOST-side admission policy: the tenant id never joins "
            "the program/compatibility class key and the chunk "
            "program is untouched (the 'qos' gate in check/gates.py "
            "pins ambient inertness); delivered results stay bitwise "
            "their direct solo calls regardless of admission order",
    ),
    "CIMBA_DEVICE_SCHED": dict(
        default="", trace_gate=True,
        doc="preemptive device scheduler "
            "(docs/24_device_scheduler.md): =1 makes "
            "Service(device_sched=None) run concurrent refill waves "
            "per device with memory-aware admission and checkpoint-"
            "evict-restore preemption of lower-priority waves.  Purely "
            "a HOST-side dispatch policy: the chunk program is "
            "untouched (the 'device_sched' gate in check/gates.py pins "
            "ambient inertness); checkpoints ride the PR 3 resumable "
            "path, so a preempted wave restores bit-identically",
    ),
    # kernel-path knobs: Mosaic programs, covered by the dedicated
    # kernel parity batteries (test_mosaic_aot / test_pallas_run), not
    # the XLA-path gate sweep (interpret-mode tracing is over tier-1
    # budget)
    "CIMBA_KERNEL_PACK": dict(
        default="0", trace_gate=False,
        doc="packed carry inside the Pallas mega-kernel",
    ),
    "CIMBA_KERNEL_LANE_BLOCK": dict(
        default="", trace_gate=False,
        doc="Pallas lane-block grid size (core/pallas_run.py)",
    ),
    "CIMBA_KERNEL_VMEM_LIMIT": dict(
        default="", trace_gate=False,
        doc="Mosaic scoped-vmem budget override, bytes",
    ),
    "CIMBA_KERNEL_DEBUG": dict(
        default="", trace_gate=False,
        doc="dump 64-bit-typed jaxpr values before Mosaic lowering",
    ),
    # host-side state (no traced-program effect)
    "CIMBA_PROGRAM_CACHE_CAP": dict(
        default="64", trace_gate=False,
        doc="bounded program-cache capacity (serve/cache.py)",
    ),
    "CIMBA_PROGRAM_STORE": dict(
        default="", trace_gate=False,
        doc="persistent AOT program store root (serve/store.py)",
    ),
    # fleet plane (docs/20_fleet.md): host-side process topology and
    # fault injection — no traced-program effect
    "CIMBA_FLEET_CHAOS": dict(
        default="", trace_gate=False,
        doc="fleet fault injection (fleet/chaos.py): comma-separated "
            "k=v knobs — seed=<u64>, drop=<k> (drop first-attempt wire "
            "responses deterministically by fmix64(seed, slice, "
            "request id)), kill=<n> (SIGKILL the slice after n served "
            "requests), scrape_delay_ms=<ms> (stall /healthz + "
            "/metrics responses)",
    ),
    "CIMBA_FLEET_DIST": dict(
        default="", trace_gate=False,
        doc="opt-in jax.distributed multi-controller init at slice "
            "startup (fleet/dist.py): coordinator_address,"
            "num_processes,process_id — off (the default) never "
            "touches jax.distributed",
    ),
    "CIMBA_FLEET_TELEMETRY": dict(
        default="", trace_gate=False,
        doc="fleet trace plane (docs/23_fleet_observability.md): a "
            "DIRECTORY path makes every slice process attach a "
            "Telemetry plane and write its span JSONL to "
            "<dir>/<slice>.spans.jsonl, adopting the trace context "
            "carried by run headers so slice span trees graft under "
            "the router's wire spans; empty (the default) = no slice "
            "telemetry, zero cost — a host-side observability knob "
            "with no traced-program effect",
    ),
    "CIMBA_FLEET_CAPACITY": dict(
        default="1", trace_gate=False,
        doc="capacity-aware fleet placement "
            "(docs/23_fleet_observability.md): on (the default), the "
            "router ranks candidate slices by scraped free-lane "
            "headroom whenever EVERY candidate reports the refill "
            "capacity signal, falling back to least-loaded otherwise; "
            "=0 pins least-loaded placement.  Host-side policy only — "
            "results are bitwise identical either way",
    ),
    # assertion tiers: compile-out is the FEATURE (utils/dbc.py); the
    # gated-handler invariant battery (test_gated_invariant.py) owns
    # their correctness, so they are not registry gates
    "CIMBA_NDEBUG": dict(
        default="0", trace_gate=False,
        doc="disable the heavyweight debug assertion tier",
    ),
    "CIMBA_NASSERT": dict(
        default="0", trace_gate=False,
        doc="disable the release assertion tier too",
    ),
}


def env_raw(name: str, default=None) -> str:
    """Read one registered ``CIMBA_*`` environment knob (the CHK005
    round-trip point: package code reads env through here, never
    ``os.environ`` directly, so :data:`ENV_KNOBS` can never drift from
    what the package actually consults).  ``default=None`` uses the
    registered default; an unregistered name raises — register the knob
    (and, for a trace gate, its identity gate in check/gates.py)
    first."""
    import os

    knob = ENV_KNOBS.get(name)
    if knob is None:
        raise KeyError(
            f"{name} is not a registered CIMBA_* environment knob — add "
            "it to cimba_tpu.config.ENV_KNOBS (and check/gates.py if it "
            "gates trace-time program structure); see "
            "docs/19_static_analysis.md"
        )
    if default is None:
        default = knob["default"]
    return os.environ.get(name, default)

#: True while tracing inside the Pallas mega-kernel (set by
#: core.pallas_run).  Data-dependent while-loops in the interpreter become
#: masked bounded fori-loops under this flag: Mosaic cannot lower a
#: batched (vector) loop condition.
KERNEL_MODE = False

# --- table dispatch levers (docs/25_compile_wall.md) ------------------------
#
# Both tri-state: ``None`` defers to the environment variable (and its
# default), ``True``/``False`` override it programmatically.  Both bind
# at TRACE time (like the dtype profile): arrays and jaxprs already
# built keep their layout.

#: Scan-over-rows table dispatch (core/dyn.py).  ``None`` ->
#: ``CIMBA_TABLE_SCAN`` (default off — dense one-hot dispatch, today's
#: jaxpr character-identical); ``True`` blocks every table access whose
#: row axis is taller than :func:`table_scan_block`.
TABLE_SCAN = None

#: Row-block height for the scan-over-rows dispatch.  ``None`` ->
#: ``CIMBA_TABLE_SCAN_BLOCK`` (default 128).
TABLE_SCAN_BLOCK = None


def table_scan_enabled() -> bool:
    if TABLE_SCAN is not None:
        return bool(TABLE_SCAN)
    raw = env_raw("CIMBA_TABLE_SCAN").strip()
    return bool(raw) and raw != "0"


def table_scan_block() -> int:
    if TABLE_SCAN_BLOCK is not None:
        return int(TABLE_SCAN_BLOCK)
    return int(env_raw("CIMBA_TABLE_SCAN_BLOCK"))


def active_profile() -> str:
    return _ACTIVE_PROFILE


def use_profile(name: str) -> None:
    """Switch the trace-time dtype profile ("f64" exact / "f32" kernel).

    Affects subsequent *tracing* only; arrays already built keep their
    dtypes.  Model builds and runs under different profiles coexist in one
    process (specs carry no dtypes; all arrays are created at trace time).
    """
    global _ACTIVE_PROFILE
    if name not in _PROFILES:
        raise ValueError(f"unknown profile {name!r}; one of {sorted(_PROFILES)}")
    globals().update(_PROFILES[name])
    _ACTIVE_PROFILE = name


@contextlib.contextmanager
def profile(name: str):
    """Scoped :func:`use_profile` (restores the previous profile on exit)."""
    prev = _ACTIVE_PROFILE
    use_profile(name)
    try:
        yield
    finally:
        use_profile(prev)


def x64_scope(enable: bool):
    """``jax.enable_x64(enable)``: a scope with 64-bit types on or off."""
    return jax.enable_x64(enable)


def setup() -> None:
    """Enable the JAX global flags cimba-tpu requires (idempotent)."""
    jax.config.update("jax_enable_x64", True)


setup()
