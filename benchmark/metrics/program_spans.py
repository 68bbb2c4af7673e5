"""The program's own spans and compile instants, shared by the readers
beside this file that read them.

They come from the ring the program keeps in memory
(``cimba_tpu.obs.telemetry.program_spans``), read in the harness's
process after the window; a program without that ring gives ``None``.
The ring stamps ``time.monotonic_ns()``, the harness's records the same
clock in seconds, and the device trace counts seconds from the
profiler's start.  :func:`clock_offset` is the one offset that puts the
most ``serve.submit`` spans inside the harness's ``bench.submit`` spans
around the same calls: trace time = monotonic time + offset, so the
profiler started at monotonic time ``-offset``.
"""

from __future__ import annotations

import math

NS = 1e-9
COMPILE = "jax.compile"
#: the dispatcher's host phases of a wave; ``serve.run`` is the device's
HOST_PHASES = ("serve.pack", "serve.assemble", "serve.fold", "serve.deliver")
#: spans that start later than this before the profiler's start are left
#: out of the served medians: its start holds the host
PROFILER_MARGIN_S = 1.0


def records():
    """The ring's records, or ``None`` where the program keeps none."""
    try:
        from cimba_tpu.obs import telemetry
    except ImportError:
        return None
    get = getattr(telemetry, "program_spans", None)
    return None if get is None else get()


def start(r) -> float:
    return r["t0_ns"] * NS


def end(r) -> float:
    return r["t1_ns"] * NS


def compiles_in(recs, lo: float, hi: float) -> int:
    """Compile instants from ``lo`` to ``hi`` (monotonic seconds)."""
    return sum(1 for r in recs if r["name"] == COMPILE and lo <= start(r) <= hi)


def served_window(run):
    """``(t0, t1)`` in monotonic seconds: from the window's opening (the
    first request's due time less its place in the arrivals) to the last
    retirement of the window's requests, or its last due time."""
    reqs = run.get("counters", {}).get("requests")
    if not reqs:
        return None
    t0 = reqs[0]["due"] - reqs[0]["arrival"].due_s
    ends = [r["due"] for r in reqs]
    ends += [r["finish"] for r in reqs
             if r.get("finish") is not None and math.isfinite(r["finish"])]
    return t0, max(ends)


def clock_offset(run, recs):
    """Trace seconds minus monotonic seconds: the offset at which the most
    ``serve.submit`` spans lie inside ``bench.submit`` spans (the middle
    of the offsets that do), or ``None``."""
    t = run.get("trace")
    if not t:
        return None
    outer = [(s, e) for n, s, e in t["spans"] if n == "bench.submit"]
    inner = [(start(r), end(r)) for r in recs if r["name"] == "serve.submit"]
    edges = []
    for a, b in outer:
        for s, e in inner:
            if a - s <= b - e:
                edges += [(a - s, 0), (b - e, 1)]
    if not edges:
        return None
    edges.sort()
    best, n, at = 0, 0, 0
    for i, (_, closing) in enumerate(edges):
        n += -1 if closing else 1
        if n > best:
            best, at = n, i
    lo = edges[at][0]
    hi = next(x for x, closing in edges[at + 1:] if closing)
    return (lo + hi) / 2


def waves(recs):
    """One ``(wave, phases)`` per ``serve.wave`` span: ``phases`` maps each
    of its child spans' names to the span."""
    kids = {}
    for r in recs:
        if r.get("ph") != "i" and r["parent"] is not None:
            kids.setdefault(r["parent"], []).append(r)
    return [(w, {k["name"]: k for k in kids.get(w["span"], [])})
            for w in recs if w["name"] == "serve.wave"], kids


def self_s(r, kids) -> float:
    """A span's own seconds: its length less its child spans'."""
    return end(r) - start(r) - sum(end(k) - start(k)
                                   for k in kids.get(r["span"], []))


def window_waves(run):
    """``(waves, kids)`` of the served window's waves that started before
    the profiler's start less :data:`PROFILER_MARGIN_S`, or ``None``."""
    recs = records()
    win = served_window(run) if recs is not None else None
    off = clock_offset(run, recs) if win is not None else None
    if off is None:
        return None
    cut = min(win[1], -off - PROFILER_MARGIN_S)
    ws, kids = waves(recs)
    return [(w, p) for w, p in ws if win[0] <= start(w) < cut], kids
