"""The trace-time feature-gate registry and its identity sweep.

Every trace-time feature gate this repo ships — the flight recorder
(:mod:`obs.trace`), the metrics registry (:mod:`obs.metrics`), the
determinism-audit chunk arm (:mod:`obs.audit` via ``make_chunk``), the
table-scan dispatch (``CIMBA_TABLE_SCAN``/``_BLOCK``), tuned-schedule
resolution and the serving plane's host-side policies — carries the same
contract: its OFF state must trace a program jaxpr-identical to the
baseline, under both dtype profiles, and ambient environment state must
never leak into a traced program except through the gate's documented
resolution point.

Historically each gate pinned that contract with its own hand-written
test (test_trace / test_audit), which a NEW gate could simply forget.
This registry inverts the burden: a gate registers once as a
:class:`Gate` and :func:`sweep` auto-generates its identity checks; the
completeness test in tests/test_check.py fails if a trace-gate env knob
exists in ``config.ENV_KNOBS`` but no gate here claims it — so
forgetting is now a test failure, not a latent soundness hole.

Checks per gate, per dtype profile (``f64`` and ``f32``):

1. **off == baseline** — the program with the gate explicitly OFF is
   character-identical to the default-state program.
2. **ambient inertness** — for gates whose env knob must NOT bind at
   trace time (``ambient_env``): the default program with the env var
   set is still the OFF program.
3. **env off-state** — for gates whose env knob IS the resolution point
   (``off_env``): the env-disabled default reproduces the OFF program.
4. **the knob is live** — the explicit-ON program differs (skipped for
   structurally-inert gates like the table scan at small table
   heights).

The sweep traces jaxprs only (``jax.make_jaxpr`` — nothing compiles or
executes), restores every global it touches, and memoizes identical
arms so the whole registry costs a handful of small mm1 traces.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Callable, Dict, Optional, Tuple

from cimba_tpu.check import Finding

__all__ = ["Gate", "GATES", "sweep", "claimed_env_knobs", "PROFILES"]

PROFILES = ("f64", "f32")


# -- gate state context managers ---------------------------------------------


@contextlib.contextmanager
def _trace_state(enabled: bool, capacity: int = 16):
    from cimba_tpu.obs import trace as ot

    prev_enabled, prev_cap = ot.enabled(), ot.capacity()
    try:
        if enabled:
            ot.enable(capacity)
        else:
            # a full enable/disable CYCLE, not a no-op: the off arm
            # proves no sticky state (capacity, partial enables)
            # survives into later traces
            ot.enable(capacity)
            ot.disable()
        yield
    finally:
        if prev_enabled:
            ot.enable(prev_cap)
        else:
            ot.disable()


@contextlib.contextmanager
def _metrics_state(enabled: bool):
    from cimba_tpu.obs import metrics as om

    prev = om.enabled()
    try:
        if enabled:
            om.enable()
        else:
            om.enable()
            om.disable()
        yield
    finally:
        om.enable() if prev else om.disable()


@contextlib.contextmanager
def _noop_state():
    yield


@contextlib.contextmanager
def _table_scan_state(scan: Optional[bool], block: Optional[int] = None):
    from cimba_tpu import config

    prev_s, prev_b = config.TABLE_SCAN, config.TABLE_SCAN_BLOCK
    try:
        config.TABLE_SCAN = scan
        if block is not None:
            config.TABLE_SCAN_BLOCK = block
        yield
    finally:
        config.TABLE_SCAN = prev_s
        config.TABLE_SCAN_BLOCK = prev_b


@contextlib.contextmanager
def _tune_state(on: bool):
    """The tune gate's arms: a resolved :class:`~cimba_tpu.tune.space.
    Schedule` binds through its ``scope()`` (the config tri-states) —
    the ON arm applies a schedule whose knob provably changes the
    traced program (the table scan at a one-row block, below every
    sweep model's tallest table), the OFF arm applies the empty default
    schedule (which must be the baseline)."""
    from cimba_tpu.tune.space import Schedule

    sched = (
        Schedule(table_scan=True, table_block=1) if on else Schedule()
    )
    with sched.scope():
        yield


# -- the registry -------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Gate:
    """One registered trace-time feature gate.

    ``program`` picks the traced program ("run" = ``make_run`` on an
    mm1 Sim, "chunk" = ``make_chunk`` on a vmapped wave).  The OFF/ON
    arms are forced either through builder kwargs (explicit program
    arguments like ``audit=``) or a state context manager (module
    globals like the recorder's enable flag)."""

    name: str
    env: Tuple[str, ...]          # ENV_KNOBS names this gate claims
    program: str                  # "run" | "chunk"
    off_kwargs: dict = dataclasses.field(default_factory=dict)
    on_kwargs: Optional[dict] = None
    off_ctx: Callable = _noop_state
    on_ctx: Optional[Callable] = None
    ambient_env: dict = dataclasses.field(default_factory=dict)
    off_env: dict = dataclasses.field(default_factory=dict)
    on_differs: bool = True


GATES: Tuple[Gate, ...] = (
    Gate(
        name="trace",
        env=(),
        program="run",
        off_ctx=lambda: _trace_state(False),
        on_ctx=lambda: _trace_state(True),
    ),
    Gate(
        name="metrics",
        env=(),
        program="run",
        off_ctx=lambda: _metrics_state(False),
        on_ctx=lambda: _metrics_state(True),
    ),
    Gate(
        name="table_scan",
        env=("CIMBA_TABLE_SCAN", "CIMBA_TABLE_SCAN_BLOCK"),
        program="run",
        off_ctx=lambda: _table_scan_state(False),
        on_ctx=lambda: _table_scan_state(True),
        off_env={"CIMBA_TABLE_SCAN": "0"},
        # the scan-over-rows dispatch only engages on table axes
        # STRICTLY taller than the block (docs/25_compile_wall.md) —
        # every sweep-model axis is <= the default block, so the ON
        # program must equal the OFF one (that small-P structural
        # inertness is itself the pinned claim; knob liveness at tall-P
        # is pinned in tests/test_table_scan.py where the model height
        # is controlled).  The ambient arm rides the same inertness:
        # the env knob DOES bind at trace time, but at sweep-model
        # scale it must still trace the baseline program.
        ambient_env={"CIMBA_TABLE_SCAN": "1"},
        on_differs=False,
    ),
    Gate(
        name="audit",
        env=("CIMBA_AUDIT",),
        program="chunk",
        off_kwargs={"audit": False},
        on_kwargs={"audit": True},
        # the audit knob is an explicit program ARGUMENT; the env var
        # only selects host-side collection and must never bind into a
        # traced program (the test_audit pin, generalized)
        ambient_env={"CIMBA_AUDIT": "1"},
    ),
    Gate(
        name="tune",
        env=("CIMBA_TUNE",),
        program="run",
        off_ctx=lambda: _tune_state(False),
        on_ctx=lambda: _tune_state(True),
        # with no tuned entry in reach (the sweep clears CIMBA_* env,
        # so no store resolves), the env knob must be ambient-inert in
        # BOTH states: resolution is a host-side decision that binds
        # programs only through the Schedule scope / explicit kwargs
        # (docs/21_autotune.md); CIMBA_TUNE=0 (tuned-resolution off)
        # must therefore be jaxpr-identical to the default
        ambient_env={"CIMBA_TUNE": "1"},
        off_env={"CIMBA_TUNE": "0"},
    ),
    Gate(
        name="refill",
        env=("CIMBA_REFILL",),
        program="chunk",
        # continuous wave refill (docs/22_refill.md) is a HOST-side
        # dispatch policy: the knob selects lane recycling in the
        # serve dispatcher and must never bind into a traced chunk
        # program — the refilled wave runs the SAME chunk program as
        # the refill-off one (the splice is a separate program).  No
        # ON arm: there is no chunk-program state to flip.
        ambient_env={"CIMBA_REFILL": "1"},
        off_env={"CIMBA_REFILL": "0"},
    ),
    Gate(
        name="device_sched",
        env=("CIMBA_DEVICE_SCHED",),
        program="chunk",
        # the preemptive device scheduler
        # (docs/24_device_scheduler.md) is, like refill, a HOST-side
        # dispatch policy: the knob selects concurrent-wave admission
        # and checkpoint-evict-restore preemption in the serve
        # dispatcher and must never bind into a traced chunk program —
        # a scheduled wave runs the SAME chunk program as the plain
        # one (checkpointing reuses the PR 3 resumable path, outside
        # any trace).  No ON arm: no chunk-program state to flip.
        ambient_env={"CIMBA_DEVICE_SCHED": "1"},
        off_env={"CIMBA_DEVICE_SCHED": "0"},
    ),
    Gate(
        name="qos",
        env=("CIMBA_QOS",),
        program="chunk",
        # the multi-tenant QoS plane (docs/27_qos.md) is, like refill,
        # a HOST-side admission policy: the knob selects weighted-fair
        # lane apportionment / EDF ordering / quota throttling in the
        # serve dispatcher, and the tenant id must never bind into a
        # traced chunk program — a request admitted under QoS runs the
        # SAME chunk program as one admitted in raw priority order
        # (tenant is carried beside trace_context, outside the
        # compatibility class key).  No ON arm: no chunk-program
        # state to flip.
        ambient_env={"CIMBA_QOS": "1"},
        off_env={"CIMBA_QOS": "0"},
    ),
    Gate(
        name="wave_fuse",
        env=("CIMBA_WAVE_FUSE",),
        program="chunk",
        # cross-spec wave fusion (docs/26_wave_fusion.md) is, like
        # refill, a HOST-side packing policy: the knob selects whether
        # the serve dispatcher groups compatible-shape specs into
        # fused waves, and must never bind into a traced chunk
        # program — a single-spec wave runs the SAME chunk program
        # whether fusion is on or off (the fused superprogram is a
        # separate compile on the merged spec, and only forms when a
        # wave actually spans >1 exact class).  No ON arm: no
        # chunk-program state to flip.
        ambient_env={"CIMBA_WAVE_FUSE": "1"},
        off_env={"CIMBA_WAVE_FUSE": "0"},
    ),
)


def claimed_env_knobs() -> set:
    """Every ENV_KNOBS name some registered gate claims — what the
    completeness test checks ``config.ENV_KNOBS``'s trace gates
    against."""
    out: set = set()
    for g in GATES:
        out.update(g.env)
    return out


# -- program builders ---------------------------------------------------------


def _tiny_spec():
    """A minimal hold/exit model: every gate's code path (dispatch site,
    carry layout, event-set minima, chunk digest) with a ~7x cheaper
    trace than mm1 — the tier-1 sweep model.  Two processes, so its
    process tables are two rows tall and the tune gate's one-row table
    scan engages."""
    from cimba_tpu.core import api, cmd
    from cimba_tpu.core.model import Model

    m = Model("gatecheck", event_cap=1, guard_cap=2)

    @m.block
    def work(sim, p, sig):
        done = api.clock(sim) > 4.0
        return sim, cmd.select(
            done, cmd.exit_(), cmd.hold(1.0, next_pc=work.pc)
        )

    m.process("w", entry=work)
    m.process("w2", entry=work)
    return m.build(), ()


def _model_point(model: str):
    if model == "tiny":
        return _tiny_spec()
    if model == "mm1":
        from cimba_tpu.models import mm1

        spec, _ = mm1.build(record=False)
        return spec, mm1.params(10)
    raise ValueError(f"unknown gate-sweep model {model!r}")


def _trace_program(
    profile: str, program: str, kwargs: dict, model: str,
) -> str:
    """One traced jaxpr as text — spec/Sim built INSIDE the profile and
    gate state, since gated leaves (trace ring, metrics registry) ride
    the Sim pytree."""
    import jax
    import jax.numpy as jnp

    from cimba_tpu import config
    from cimba_tpu.core import loop as cl

    with config.profile(profile):
        spec, params = _model_point(model)
        if program == "run":
            sim = cl.init_sim(spec, 1, 0, params)
            return str(jax.make_jaxpr(cl.make_run(spec, **kwargs))(sim))
        if program == "chunk":
            sims = jax.vmap(
                lambda r: cl.init_sim(spec, 3, r, params)
            )(jnp.arange(4))
            return str(
                jax.make_jaxpr(
                    cl.make_chunk(spec, max_steps=8, **kwargs)
                )(sims)
            )
        raise ValueError(f"unknown gate program {program!r}")


@contextlib.contextmanager
def _env(overrides: dict):
    saved = {k: os.environ.get(k) for k in overrides}
    try:
        for k, v in overrides.items():
            os.environ[k] = v
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@contextlib.contextmanager
def _clean_env(names) -> None:
    saved = {k: os.environ.pop(k, None) for k in names}
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is not None:
                os.environ[k] = v


# -- the sweep ----------------------------------------------------------------


def sweep(profiles=PROFILES, gates=None, model="mm1") -> Tuple[list, dict]:
    """Run every registered gate's identity checks.  ``model`` picks the
    traced spec: ``"mm1"`` (the shipped model every historical pin
    used — the CLI/ci.sh default) or ``"tiny"`` (a minimal model with
    ~7x cheaper traces — what tier-1 sweeps on budget).  Returns
    ``(findings, report)`` — findings carry rule ``"GATE"`` (empty =
    every gate holds); the report maps ``gate/profile`` to the list of
    checks that ran (what the ``--json`` output embeds)."""
    gates = GATES if gates is None else tuple(gates)
    findings: list = []
    report: Dict[str, list] = {}
    memo: Dict[tuple, str] = {}
    all_env = [k for g in gates for k in g.env]

    def build(profile, gate, arm_key, kwargs, ctx_factory, env):
        key = (
            profile, gate.program, tuple(sorted(kwargs.items())),
            arm_key, tuple(sorted(env.items())),
        )
        if key not in memo:
            with _env(env), ctx_factory():
                memo[key] = _trace_program(
                    profile, gate.program, kwargs, model,
                )
        return memo[key]

    with _clean_env(all_env):
        for gate in gates:
            for profile in profiles:
                ran = []
                gid = f"{gate.name}/{profile}"

                def fail(msg):
                    findings.append(Finding(
                        rule="GATE", path=f"gate:{gid}", line=0,
                        message=msg,
                    ))

                baseline = build(
                    profile, gate, "default", {}, _noop_state, {},
                )
                off = build(
                    profile, gate, f"{gate.name}:off", gate.off_kwargs,
                    gate.off_ctx, {},
                )
                on = None
                if gate.on_kwargs is not None or gate.on_ctx is not None:
                    on = build(
                        profile, gate, f"{gate.name}:on",
                        gate.on_kwargs or {},
                        gate.on_ctx or _noop_state, {},
                    )
                ran.append("off==baseline")
                if off != baseline:
                    fail(
                        "explicit-off program differs from the "
                        "default-state program — the gate's off "
                        "state is not the baseline"
                    )
                if gate.ambient_env:
                    ran.append("ambient-inert")
                    ambient = build(
                        profile, gate, "default", {}, _noop_state,
                        gate.ambient_env,
                    )
                    if ambient != off:
                        fail(
                            f"ambient env {gate.ambient_env} leaked "
                            "into the traced default program — the "
                            "knob must stay an explicit argument"
                        )
                if gate.off_env:
                    # CIMBA_<GATE>=0 must reproduce the explicit-off
                    # program on EVERY backend
                    ran.append("env-off==off")
                    env_off = build(
                        profile, gate, "default", {}, _noop_state,
                        gate.off_env,
                    )
                    if env_off != off:
                        fail(
                            f"env off-state {gate.off_env} does not "
                            "reproduce the explicit-off program"
                        )
                if gate.name == "table_scan":
                    # second block size, still above every sweep-model
                    # axis: "axes <= the block stay dense" must hold at
                    # any block, not just the default
                    ran.append("block-inert")
                    blocked = build(
                        profile, gate, "table_scan:block1024", {},
                        lambda: _table_scan_state(True, 1024), {},
                    )
                    if blocked != off:
                        fail(
                            "CIMBA_TABLE_SCAN_BLOCK=1024 changed the "
                            "traced program for a model whose every "
                            "table axis fits one block (small-table "
                            "structural inertness broken)"
                        )
                if on is not None:
                    if gate.on_differs:
                        ran.append("on-differs")
                        if on == off:
                            fail(
                                "explicit-on program equals the off "
                                "program — the gate knob is dead"
                            )
                    else:
                        ran.append("on-inert")
                        if on != off:
                            fail(
                                "gate declared structurally inert but "
                                "its ON program differs"
                            )
                report[gid] = ran
    return findings, report
