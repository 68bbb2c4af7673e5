"""The comparison that decides ``correct``.

A unit is one answer the timed path delivered: an experiment's pooled
result, or one served request's.  The plain reference runs the same
replications lane by lane; its lanes are pooled here, on the host in
float64, into the same numbers the program delivers: the sojourn count,
mean and standard deviation over every object of every replication, the
events popped and the replications failed.  Each unit then gives three
gaps, and a run reads the worst of each over its units:

- ``count_gap``: the largest relative gap of the object count, the
  event count and the failed replications (those over the lanes);
- ``mean_gap``: the relative gap of the pooled mean sojourn;
- ``sd_gap``: the relative gap of the pooled standard deviation.

A lane whose float32 rounding differs from the reference's by one ulp can
flip a near-tie of two events and take another path from there; such
lanes move the pooled numbers by little, and the limits (``limits/``)
were set above what sound runs read and below what the control reads
(PERF.md, "How correct is decided").
"""

from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

NUMBERS = ("count_gap", "mean_gap", "sd_gap")


class Pooled(NamedTuple):
    n: float
    mean: float
    sd: float
    events: float
    failed: float
    lanes: int


def pool_lanes(out) -> Pooled:
    """Pool a reference's per-lane results (benchmark.refs.mg1.Out)."""
    n = out.n.astype(np.float64)
    mean = out.mean.astype(np.float64)
    m2 = out.m2.astype(np.float64)
    N = n.sum()
    M = float((n * mean).sum() / N) if N else 0.0
    M2 = float(m2.sum() + (n * (mean - M) ** 2).sum())
    sd = math.sqrt(M2 / (N - 1)) if N > 1 else 0.0
    return Pooled(float(N), M, sd, float(out.events.astype(np.int64).sum()),
                  float(out.failed.sum()), len(n))


def pool_program(res, lanes: int) -> Pooled:
    """The program's delivered result (a cimba-tpu ``StreamResult``)."""
    s = res.summary
    n = float(s.n)
    sd = math.sqrt(float(s.m2) / (float(s.w) - 1.0)) if float(s.w) > 1 else 0.0
    return Pooled(n, float(s.m1), sd, float(res.total_events),
                  float(res.n_failed), lanes)


def _rel(a: float, b: float) -> float:
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / abs(b) if b else (0.0 if a == b else math.inf)


def gaps(prog: Pooled, ref: Pooled) -> dict:
    return {
        "count_gap": max(_rel(prog.n, ref.n), _rel(prog.events, ref.events),
                         abs(prog.failed - ref.failed) / max(ref.lanes, 1)),
        "mean_gap": _rel(prog.mean, ref.mean),
        "sd_gap": _rel(prog.sd, ref.sd),
    }


def worst(per_unit: list[dict]) -> dict:
    """The worst reading of each number over the units (inf if none)."""
    if not per_unit:
        return {k: math.inf for k in NUMBERS}
    return {k: max(g[k] for g in per_unit) for k in NUMBERS}


def verdict(read: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit.  A number that is not
    finite fails, as does a run with no unit to compare."""
    out, ok = {}, True
    for k in NUMBERS:
        v, lim = read[k], float(limits[k])
        ok = ok and math.isfinite(v) and v <= lim
        out[k] = {"value": v if math.isfinite(v) else None, "limit": lim}
    return ok, out
