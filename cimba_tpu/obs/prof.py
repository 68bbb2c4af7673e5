"""Run profiling: the compile-vs-execute split, device memory, RunReport.

On TPU the wall time of an experiment is dominated by two very different
costs — tracing+XLA compilation (host, once per (spec, shape)) and device
execution (the thing a benchmark measures) — and conflating them is the
single most common profiling mistake with jit code.  :func:`profiled_call`
splits them with the AOT API (``lower``/``compile``), and
:class:`RunReport` packages the split with device memory stats and a
metrics snapshot: the run's whole observability story in one JSON-able
object, surfaced by ``run_experiment(..., with_report=True)``.
"""

from __future__ import annotations

import dataclasses
import time
from contextlib import contextmanager
from typing import Any, Optional

import jax


@dataclasses.dataclass
class RunReport:
    """What one experiment run cost and did (all host-side scalars)."""

    trace_lower_s: float          # python tracing + StableHLO lowering
    compile_s: float              # XLA/backend compilation
    execute_s: float              # device execution (block_until_ready)
    n_replications: int
    n_failed: int
    total_events: int
    events_per_sec: float
    backend: str
    device_memory: Optional[dict] = None   # jax Device.memory_stats()
    metrics: Optional[dict] = None         # obs.metrics.snapshot (pooled)
    profile_dir: Optional[str] = None      # jax.profiler trace output, if any

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def device_memory_stats() -> Optional[dict]:
    """``memory_stats()`` of the first local device, None where the
    backend doesn't report (CPU)."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    # ints only: the dict goes straight into BENCH_*.json
    return {k: int(v) for k, v in stats.items() if isinstance(v, (int, float))}


@contextmanager
def trace_ctx(profile_dir: Optional[str]):
    """``jax.profiler.trace`` scoped around the execute leg when a
    directory is given; a no-op otherwise.  View the output with
    Perfetto/TensorBoard."""
    if not profile_dir:
        yield
        return
    with jax.profiler.trace(profile_dir):
        yield


def profiled_call(fn, *args, profile_dir: Optional[str] = None):
    """Run jitted ``fn(*args)`` with the compile/execute split measured.

    Returns ``(out, timings)`` where timings is a dict with
    ``trace_lower_s``, ``compile_s``, ``execute_s``.  Uses the AOT path
    (``fn.lower().compile()``) so the three legs are cleanly separated;
    ``fn`` must be a ``jax.jit`` callable.
    """
    t0 = time.perf_counter()
    lowered = fn.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    with trace_ctx(profile_dir):
        out = jax.block_until_ready(compiled(*args))
    t3 = time.perf_counter()
    return out, {
        "trace_lower_s": t1 - t0,
        "compile_s": t2 - t1,
        "execute_s": t3 - t2,
    }


def build_report(
    timings: dict,
    *,
    n_replications: int,
    n_failed: int,
    total_events: int,
    metrics: Optional[dict] = None,
    profile_dir: Optional[str] = None,
) -> RunReport:
    ex = max(timings["execute_s"], 1e-12)
    return RunReport(
        trace_lower_s=timings["trace_lower_s"],
        compile_s=timings["compile_s"],
        execute_s=timings["execute_s"],
        n_replications=int(n_replications),
        n_failed=int(n_failed),
        total_events=int(total_events),
        events_per_sec=float(total_events) / ex,
        backend=jax.default_backend(),
        device_memory=device_memory_stats(),
        metrics=metrics,
        profile_dir=profile_dir,
    )
