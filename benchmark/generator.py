"""The one load generator: turns a traffic mix's parameters into the
requests of one window.

The arrival instants are a fixed trace, the same for every seed: the
stratified quantiles of exponential gaps at ``rate_per_s``, in one fixed
order, scaled so that the window holds ``rate_per_s x seconds`` of them.
Only the arrivals' timing is fixed: fresh arrivals per seed made the
served tail swing by far more than the work did (PERF.md, section 2).
Each request's size and scenario come in balanced blocks whose order the
seed draws, and each request has its own seed.  Two seeds then differ in
which work comes when, not in its amount or its bursts.

A mix's parameters:

- ``rate_per_s``: mean offered requests per second;
- ``reps``: replication counts, used equally often.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

#: the one order of the trace's gaps
_TRACE_ORDER = 0


class Arrival(NamedTuple):
    due_s: float   # seconds after the window opens
    row: int       # scenario index in the configuration
    reps: int      # replications asked for
    seed: int      # the request's own experiment seed


def _blocks(values, n, rng) -> np.ndarray:
    """``n`` of ``values``, in blocks that each hold every value once, so
    that every stretch of the window carries the same work."""
    values = np.asarray(values)
    blocks = [rng.permutation(values) for _ in range(-(-n // len(values)))]
    return np.concatenate(blocks)[:n]


def open_loop(mix: dict, seed: int, seconds: float, n_rows: int) -> list[Arrival]:
    """The requests due in a window of ``seconds``, in due order: the fixed
    trace of instants, with ``seed`` ordering the requests over them and
    seeding each one."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = np.random.default_rng(_TRACE_ORDER).permutation(-np.log1p(-q))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due *= seconds / gaps.sum()
    rows = _blocks(np.arange(n_rows), n, rng)
    reps = _blocks(mix.get("reps", [1024]), n, rng)
    seeds = rng.integers(0, 2**62, n)
    return [Arrival(float(due[i]), int(rows[i]), int(reps[i]), int(seeds[i]))
            for i in range(n)]


def experiment_seed(seed: int, i: int) -> int:
    """The seed of the i-th experiment of a run (back-to-back traffic)."""
    ss = np.random.SeedSequence([int(seed), int(i)])
    return int(ss.generate_state(2, np.uint32).view(np.uint64)[0] >> 2)
