"""The schedule space: typed axes over the existing dispatch knobs.

A :class:`Schedule` is a record of the knobs one run binds; a
:class:`ScheduleSpace` is the declarative grid of candidate schedules
the search measures.  Nothing here is new mechanism — every field maps
onto a knob the runner/serve/sweep entry points already accept
(``chunk_steps=``, ``wave_size=``) or a trace-time tri-state
``cimba_tpu.config`` already exposes (``TABLE_SCAN`` /
``TABLE_SCAN_BLOCK``), made a first-class object.  Schedules never
change results, only speed:

* ``chunk_steps`` — chunked trajectories ARE the monolithic ones
  bitwise, and folds happen per wave, not per chunk (docs/12);
* ``wave_size`` — per-lane trajectories and the exact counters are
  identical; the pooled Pébay summary may differ in merge-ORDER
  rounding (docs/12), which is why the search pins each candidate
  against a default-knob twin at the candidate's OWN geometry
  (:mod:`cimba_tpu.tune.search`);
* ``lane_block`` — the Pallas kernel grid (``CIMBA_KERNEL_LANE_BLOCK``),
  only meaningful where the kernel path is live;
* ``table_scan`` / ``table_block`` — scan-over-rows process-table
  dispatch (docs/25_compile_wall.md): bitwise the dense access
  (tests/test_table_scan.py), trades per-access work for O(1)-in-P
  program text — a compile-time/run-time dial, not a results knob.

Validity predicates prune instead of measuring: the table scan is
structurally inert whenever no table axis of the model is taller than
the block (the core/dyn.py small-P inertness contract) — for such a
model every ``table_scan``/``table_block`` setting traces the SAME
program, so :meth:`ScheduleSpace.candidates` canonicalizes those axes
away rather than timing identical arms.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
from typing import Optional, Tuple

__all__ = ["Schedule", "ScheduleSpace", "default_space"]

#: schema version of the persisted schedule record — bump on field
#: changes so stale tuned entries invalidate loudly instead of
#: resolving garbage knobs.  2: PR 17 added the device-scheduler knobs
#: (``waves_per_device`` / ``preempt_quantum`` / ``mem_fraction``).
#: 3: the table-scan dispatch knobs (``table_scan`` / ``table_block``
#: — docs/25_compile_wall.md).  4: the wave-fusion knobs (``fuse`` /
#: ``fuse_max_specs`` — docs/26_wave_fusion.md).  5: the carry layout
#: and the hierarchical event set left the space (``pack`` /
#: ``eventset_hier`` / ``eventset_block``).
SCHEDULE_FORMAT = 5

#: the knob fields, in canonical order (the JSON/digest field set)
_FIELDS = (
    "chunk_steps", "wave_size", "lane_block",
    "table_scan", "table_block",
    "waves_per_device", "preempt_quantum", "mem_fraction",
    "fuse", "fuse_max_specs",
)

#: device-scheduler knob defaults (docs/24_device_scheduler.md) — ONE
#: definition: ``serve.Service`` resolves its ``None`` constructor
#: values against these, and :meth:`Schedule.canonical` collapses
#: explicit settings equal to them (an arm binding the default is the
#: default arm — prune, don't measure)
DEFAULT_WAVES_PER_DEVICE = 2
DEFAULT_PREEMPT_QUANTUM = 8
DEFAULT_MEM_FRACTION = 0.8

#: wave-fusion roster cap default (docs/26_wave_fusion.md) — the same
#: ONE-definition rule: ``serve.Service`` resolves ``fuse_max_specs=
#: None`` against this, and :meth:`Schedule.canonical` collapses an
#: explicit equal setting.  4 keeps the fused superprogram's size
#: growth comfortably under the JXL004 sublinearity budget.
DEFAULT_FUSE_MAX_SPECS = 4

#: schedule fields that change the *geometry* of a run (wave partition
#: / chunk boundaries) rather than the traced step program — the
#: search pins these against a default-knob twin at the same geometry,
#: and ``tools/audit_diff.py`` treats drift in the bitwise-invariant
#: ones as env drift, not divergence
GEOMETRY_FIELDS = ("chunk_steps", "wave_size")


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One point of the schedule space.  ``None`` per field means
    "leave that knob at its ambient default" — so ``Schedule()`` IS
    the default schedule, and a resolved/tuned schedule only ever
    overrides the knobs it was actually searched over.

    Delivery is two-channel, matching how the knobs already bind:

    * :meth:`stream_kwargs` — the argument knobs (``chunk_steps``,
      ``wave_size``) as kwargs for ``run_experiment_stream`` /
      ``Request`` / ``run_sweep``;
    * :meth:`scope` — the trace-time knobs (table-scan layout, the
      kernel lane block) as a context manager over the
      ``cimba_tpu.config`` tri-states, restoring the previous state on
      exit.
    """

    chunk_steps: Optional[int] = None
    wave_size: Optional[int] = None
    lane_block: Optional[int] = None
    # table-scan dispatch knobs (docs/25_compile_wall.md): scan-over-
    # rows process-table access on/off plus the row-block size.  Trace-
    # time, results bitwise either way (tests/test_table_scan.py) —
    # pure program-size/compile-time trade
    table_scan: Optional[bool] = None
    table_block: Optional[int] = None
    # device-scheduler policy knobs (docs/24_device_scheduler.md):
    # concurrent waves per device, the preemption quantum (chunks
    # between preemption points), and the device-memory admission
    # fraction.  Host-side dispatch policy only — results are bitwise
    # whatever these bind — consumed by serve.Service when its own
    # constructor knobs are left None.
    waves_per_device: Optional[int] = None
    preempt_quantum: Optional[int] = None
    mem_fraction: Optional[float] = None
    # wave-fusion policy knobs (docs/26_wave_fusion.md): cross-spec
    # fused-wave packing on/off plus the per-class member roster cap.
    # Host-side packing policy consumed by serve.Service when its own
    # constructor knobs are left None — member lanes are bitwise their
    # solo runs either way; the searched trade is occupancy versus
    # fused-program size (obs/program_size.py prices it)
    fuse: Optional[bool] = None
    fuse_max_specs: Optional[int] = None

    def knobs(self) -> dict:
        """The non-default fields only (what this schedule binds)."""
        out = {}
        for f in _FIELDS:
            v = getattr(self, f)
            if v is not None:
                out[f] = v
        return out

    def is_default(self) -> bool:
        return not self.knobs()

    def stream_kwargs(self) -> dict:
        """The argument-knob subset: kwargs every stream-shaped entry
        point accepts (only knobs this schedule binds appear)."""
        out = {}
        if self.chunk_steps is not None:
            out["chunk_steps"] = int(self.chunk_steps)
        if self.wave_size is not None:
            out["wave_size"] = int(self.wave_size)
        return out

    @contextlib.contextmanager
    def scope(self):
        """Bind the trace-time knobs for the duration: the
        ``config.TABLE_SCAN`` / ``TABLE_SCAN_BLOCK`` tri-states (set
        only for the fields this schedule carries)
        plus ``CIMBA_KERNEL_LANE_BLOCK`` for the kernel grid.  Restores
        the previous state on exit.  Like the dtype profile, these bind
        at TRACE time: programs already compiled keep their layout, and
        the serve/stream program keys resolve the state at key-build
        time so a scope switch misses the cache rather than replaying a
        stale arm (docs/11_dispatch_cost.md)."""
        import os

        from cimba_tpu import config

        prev = (config.TABLE_SCAN, config.TABLE_SCAN_BLOCK)
        # the lane-block knob has no config tri-state — its documented
        # binding point IS the env var (core/pallas_run.py reads it via
        # env_raw), so this scope writes/restores the var itself; the
        # suppressions below mark the one sanctioned non-env_raw site
        prev_lane = os.environ.get("CIMBA_KERNEL_LANE_BLOCK")  # cimba: noqa(CHK005) — save/restore, not a knob read
        try:
            if self.table_scan is not None:
                config.TABLE_SCAN = bool(self.table_scan)
            if self.table_block is not None:
                config.TABLE_SCAN_BLOCK = int(self.table_block)
            if self.lane_block is not None:
                os.environ["CIMBA_KERNEL_LANE_BLOCK"] = str(  # cimba: noqa(CHK005) — the binding site
                    int(self.lane_block)
                )
            yield self
        finally:
            config.TABLE_SCAN, config.TABLE_SCAN_BLOCK = prev
            if self.lane_block is not None:
                if prev_lane is None:
                    os.environ.pop("CIMBA_KERNEL_LANE_BLOCK", None)
                else:
                    os.environ["CIMBA_KERNEL_LANE_BLOCK"] = prev_lane  # cimba: noqa(CHK005) — restore

    def canonical(self, spec=None) -> "Schedule":
        """The structurally-effective form of this schedule for
        ``spec``: knobs that cannot change the traced program collapse
        to their default, so two candidates that would trace identical
        programs compare equal and the search never times both
        (prune, don't measure — docs/21_autotune.md).  Rules:

        * a knob explicitly set to what the ambient default already
          resolves to (the default block size, the entry points'
          ``chunk_steps=1024``) is the default arm;
        * ``table_block`` is dead when the table scan resolves off,
          and both table-scan knobs are dead when no dyn-accessed
          table axis of ``spec`` exceeds the effective block (the
          core/dyn.py small-P inertness contract: a block covering the
          whole axis traces the dense program character-identically).
        """
        from cimba_tpu import config

        chunk = self.chunk_steps
        if chunk is not None and int(chunk) == 1024:
            chunk = None
        tscan, tblock = self.table_scan, self.table_block
        if tscan is not None and (
            bool(tscan) == config.table_scan_enabled()
        ):
            tscan = None
        if tblock is not None and (
            int(tblock) == config.table_scan_block()
        ):
            tblock = None
        eff_tscan = (
            bool(tscan) if tscan is not None
            else config.table_scan_enabled()
        )
        if not eff_tscan:
            tblock = None
        if spec is not None and eff_tscan:
            eff_tblock = (
                int(tblock) if tblock is not None
                else config.table_scan_block()
            )
            # the tallest axis core/dyn.py can row-block for this
            # spec: process tables [P], queue/pqueue rings, guard
            # slots — the scan only engages when an axis exceeds the
            # block, so below that every setting traces dense
            tallest = max(
                len(spec.proc_entry),
                int(getattr(spec, "queue_cap_max", 0) or 0),
                int(getattr(spec, "pqueue_cap_max", 0) or 0),
                int(getattr(spec, "guard_cap", 0) or 0),
            )
            if tallest <= eff_tblock:
                tscan, tblock = None, None
        # device-scheduler knobs: an arm binding the stock default IS
        # the default arm (host-side policy; never traced)
        wpd, quantum, memf = (
            self.waves_per_device, self.preempt_quantum,
            self.mem_fraction,
        )
        if wpd is not None and int(wpd) == DEFAULT_WAVES_PER_DEVICE:
            wpd = None
        if quantum is not None and (
            int(quantum) == DEFAULT_PREEMPT_QUANTUM
        ):
            quantum = None
        if memf is not None and float(memf) == DEFAULT_MEM_FRACTION:
            memf = None
        # wave-fusion knobs: fusion defaults OFF (the CIMBA_WAVE_FUSE
        # ambient default), so an explicit fuse=False is the default
        # arm; the roster cap is dead when fusion resolves off, and
        # the stock cap is the default arm when it resolves on
        fuse, fmax = self.fuse, self.fuse_max_specs
        if fuse is not None and not bool(fuse):
            fuse = None
        if fuse is None:
            fmax = None
        elif fmax is not None and int(fmax) == DEFAULT_FUSE_MAX_SPECS:
            fmax = None
        return dataclasses.replace(
            self, chunk_steps=chunk, table_scan=tscan,
            table_block=tblock, waves_per_device=wpd,
            preempt_quantum=quantum, mem_fraction=memf,
            fuse=fuse, fuse_max_specs=fmax,
        )

    # -- persistence ---------------------------------------------------------

    def to_json(self) -> dict:
        out = {"format": SCHEDULE_FORMAT}
        out.update({f: getattr(self, f) for f in _FIELDS})
        return out

    @classmethod
    def from_json(cls, doc: dict) -> "Schedule":
        if doc.get("format") != SCHEDULE_FORMAT:
            raise ValueError(
                f"schedule record format {doc.get('format')!r} != "
                f"{SCHEDULE_FORMAT} — stale tuned entry (re-run the "
                "search)"
            )
        kw = {}
        for f in _FIELDS:
            v = doc.get(f)
            if v is not None:
                if f in ("table_scan", "fuse"):
                    v = bool(v)
                elif f == "mem_fraction":
                    v = float(v)
                else:
                    v = int(v)
            kw[f] = v
        return cls(**kw)

    # cimba-check: content-path
    def digest(self) -> str:
        """sha256 hex of the canonical JSON — how run cards and the
        store manifest cite one schedule by value."""
        return hashlib.sha256(
            json.dumps(self.to_json(), sort_keys=True).encode("utf-8")
        ).hexdigest()

    def label(self) -> str:
        """A short human arm name: ``default`` or the bound knobs."""
        k = self.knobs()
        if not k:
            return "default"
        return ",".join(f"{n}={v}" for n, v in sorted(k.items()))


@dataclasses.dataclass(frozen=True)
class ScheduleSpace:
    """The declarative candidate grid: per-knob value tuples (empty =
    the knob is not searched and stays default everywhere).  Axis
    values of ``None`` inside a tuple mean "the default arm of that
    knob" — every space implicitly contains the all-default schedule
    even when no axis lists ``None``."""

    chunk_steps: Tuple = ()
    wave_size: Tuple = ()
    lane_block: Tuple = ()
    table_scan: Tuple = ()
    table_block: Tuple = ()
    waves_per_device: Tuple = ()
    preempt_quantum: Tuple = ()
    mem_fraction: Tuple = ()
    fuse: Tuple = ()
    fuse_max_specs: Tuple = ()

    def axes(self) -> dict:
        """The non-empty axes, name -> value tuple."""
        out = {}
        for f in _FIELDS:
            vals = tuple(getattr(self, f))
            if vals:
                out[f] = vals
        return out

    def candidates(self, spec=None) -> list:
        """Every valid, structurally-distinct :class:`Schedule` of the
        grid, default first.  Each axis is augmented with the default
        arm (``None``), the cartesian product is canonicalized against
        ``spec`` (inert knob settings collapse — prune, don't
        measure), and duplicates are dropped keeping first-seen
        order."""
        import itertools

        axes = self.axes()
        names = list(axes)
        pools = [
            (None,) + tuple(v for v in axes[n] if v is not None)
            for n in names
        ]
        seen = set()
        out = []
        # the default schedule always leads: it is the incumbent every
        # candidate is pinned and raced against
        for values in itertools.product(*pools) if names else [()]:
            sched = Schedule(**dict(zip(names, values)))
            canon = sched.canonical(spec)
            key = tuple(
                getattr(canon, f) for f in _FIELDS
            )
            if key in seen:
                continue
            seen.add(key)
            out.append(canon)
        if not out or not out[0].is_default():
            out.insert(0, Schedule())
        return out


def default_space(
    spec=None, *, kernel: bool = False, device_sched: bool = False,
    fuse: bool = False,
) -> ScheduleSpace:
    """The stock search space over the dispatch knobs of
    docs/11_dispatch_cost.md: a small ``chunk_steps`` grid around the
    entry points' default.  ``wave_size`` is not
    searched by default (its pooled summary is merge-order-sensitive —
    opt in explicitly when counts-exact statistics are what you
    serve); ``lane_block`` joins only with ``kernel=True`` (the Pallas
    path); the device-scheduler policy knobs (``waves_per_device``,
    ``preempt_quantum`` — docs/24_device_scheduler.md) join only with
    ``device_sched=True``, since they are inert outside a
    ``CIMBA_DEVICE_SCHED`` serve loop (``mem_fraction`` joins them —
    the admission fraction is only live under the scheduler); the
    wave-fusion pair (``fuse`` / ``fuse_max_specs`` —
    docs/26_wave_fusion.md) joins only with ``fuse=True``, since
    fusion is inert on single-spec workloads.  The table-scan pair
    (docs/25_compile_wall.md) is always in the grid — for small-table
    models every setting collapses to the default arm, so it only
    costs candidates where a table actually exceeds a block.  Axes
    that are structurally inert for ``spec`` cost nothing:
    :meth:`ScheduleSpace.candidates` collapses them."""
    space = ScheduleSpace(
        chunk_steps=(256, 1024, 4096),
        table_scan=(True, False),
        table_block=(64, 128, 256),
        lane_block=(8, 16, 32) if kernel else (),
        waves_per_device=(1, 2, 4) if device_sched else (),
        preempt_quantum=(4, 8, 16) if device_sched else (),
        mem_fraction=(0.6, 0.8) if device_sched else (),
        fuse=(True, False) if fuse else (),
        fuse_max_specs=(2, 4, 8) if fuse else (),
    )
    return space
