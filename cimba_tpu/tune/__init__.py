"""cimba_tpu.tune — the schedule autotuner (docs/21_autotune.md).

Every dispatch knob on the hot path — the chunk budget
(``chunk_steps``), the wave quantum (``wave_size``), the table-scan
layout (``CIMBA_TABLE_SCAN`` / ``_BLOCK``), the Pallas lane-block grid
where the kernel path is live, and the serving plane's scheduler and
fusion policy — is a *schedule*: it changes how fast a program runs,
never what it computes (the per-knob bitwise pins of docs/12/14/25).
The right setting can flip by workload, so hand-frozen defaults may be
wrong for someone.  This package searches the schedule space per
(program, backend, workload bucket), pins every candidate bitwise
against the default schedule, persists the winner in the PR 6
program-store manifest, and makes every entry point —
``run_experiment_stream``, ``serve.Service``, ``sweep.run_sweep``,
fleet slices — resolve the tuned schedule at program-build time
(``CIMBA_TUNE=0`` opts out; explicit kwargs always win).

Submodules: :mod:`~cimba_tpu.tune.space` (the declarative
``ScheduleSpace`` and the ``Schedule`` record),
:mod:`~cimba_tpu.tune.measure` (the interleaved best-of-k measurement
harness), :mod:`~cimba_tpu.tune.search` (budgeted search emitting a
crash-atomic ``TuneReport`` JSON), :mod:`~cimba_tpu.tune.registry`
(store persistence + resolution), :mod:`~cimba_tpu.tune.probe` (a
big-table ticker workload for the search's tests).
"""

from cimba_tpu.tune.space import Schedule, ScheduleSpace, default_space
from cimba_tpu.tune.measure import Arm, ArmResult, MeasureReport, measure_arms
from cimba_tpu.tune.search import TuneReport, search_schedule, write_report
from cimba_tpu.tune.registry import (
    TUNE_ENV,
    resolve_schedule,
    save_tuned,
    tune_enabled,
    tune_key,
    workload_bucket,
)

__all__ = [
    "Schedule", "ScheduleSpace", "default_space",
    "Arm", "ArmResult", "MeasureReport", "measure_arms",
    "TuneReport", "search_schedule", "write_report",
    "TUNE_ENV", "tune_enabled", "tune_key", "workload_bucket",
    "resolve_schedule", "save_tuned",
]
