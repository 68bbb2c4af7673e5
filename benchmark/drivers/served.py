"""Open-loop requests to one ``serve.Service``.

Set-up builds the model and a Service at its defaults (``max_wave`` from
the mix), and warms every wave the window can dispatch: each ordered way
of packing the mix's request sizes into one wave of at most ``max_wave``
lanes, run once.  A gate on the Service's per-chunk hook holds the
dispatcher inside a wave while a packing's requests are queued, so the
next wave packs exactly them, in that order; after set-up the gate is
open and the hook returns at once.

The window sends the generator's requests at their due times from this
thread, never blocking: a refused submit is a failed request.  How long
each submit held the thread is printed on standard error.  Each
request is timed from when it was due to when the Service retired it;
one that fails or is not done ``drain_s`` after the window counts as
beyond every limit.
"""

from __future__ import annotations

import math
import sys
import threading
import time

import numpy as np

from benchmark import generator


class _Gate:
    """The Service's ``on_chunk`` hook: holds the dispatcher while shut."""

    def __init__(self):
        self.open = threading.Event()
        self.open.set()
        self.held = threading.Event()

    def __call__(self, n):
        if not self.open.is_set():
            self.held.set()
            self.open.wait()


def packings(sizes, budget: int) -> list[tuple]:
    """Every ordered sequence of ``sizes`` summing to at most ``budget``."""
    out = []

    def grow(seq, left):
        for s in sizes:
            if s <= left:
                out.append(seq + (s,))
                grow(seq + (s,), left - s)

    grow((), budget)
    return out


def _percentile(xs, q: float) -> float:
    """Nearest rank: the smallest value with at least q of all at or
    below it."""
    xs = sorted(xs)
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


class Served:
    """The Service, its model and its warmed programs: set-up, once."""

    def __init__(self, cell):
        from cimba_tpu import serve

        cell.note("imported")
        self.serve = serve
        self.cfg, self.sut = cell.cfg, cell.sut
        self.spec = self.sut.build(self.cfg)
        self.rows = self.sut.rows(self.cfg)
        self.horizon = float(self.cfg["horizon"])
        self.gate = _Gate()
        self.svc = serve.Service(max_wave=int(cell.mix["max_wave"]),
                                 on_chunk=self.gate)
        try:
            self._warm(sorted(set(int(r) for r in cell.mix["reps"])),
                       int(cell.mix["max_wave"]), cell)
        except BaseException:
            self.close(wait=False)
            raise

    def request(self, row, reps, seed, t_end):
        return self.serve.Request(
            self.spec, self.sut.params(self.cfg, self.rows[row], N_OBJECTS),
            reps, seed=seed, t_end=t_end)

    def _warm(self, sizes, max_wave, cell):
        gate = self.gate
        for k, pack in enumerate(packings(sizes, max_wave)):
            cell.note(f"warming the packing {pack}")
            gate.open.clear()
            gate.held.clear()
            holder = self.svc.submit(self.request(0, sizes[0], k, self.horizon))
            if not gate.held.wait(timeout=600):
                raise RuntimeError("the dispatcher never reached the gate")
            hs = [self.svc.submit(self.request(i % len(self.rows), r, k,
                                               self.horizon))
                  for i, r in enumerate(pack)]
            gate.open.set()
            for h in [holder] + hs:
                h.result()

    def window(self, cell, arrivals, seconds, drain_s) -> list[dict]:
        """Send ``arrivals`` at their due times and wait for them; one
        record per request, timed on the monotonic clock the Service
        stamps with."""
        recs = []
        cell.open_window()
        t0 = time.monotonic()
        for a in arrivals:
            due = t0 + a.due_s
            pause = due - time.monotonic()
            if pause > 0:
                time.sleep(pause)
            sent = time.monotonic()
            try:
                with cell.span("bench.submit"):
                    h = self.svc.submit(
                        self.request(a.row, a.reps, a.seed, self.horizon),
                        block=False)
            except self.serve.ServeError:
                h = None
            recs.append({"arrival": a, "due": due, "sent": sent,
                         "submitted": time.monotonic(), "handle": h})
        deadline = t0 + seconds + drain_s
        with cell.span("bench.result_wait"):
            for r in recs:
                if r["handle"] is not None:
                    try:
                        r["handle"].exception(
                            timeout=max(0.0, deadline - time.monotonic()))
                    except TimeoutError:
                        pass
        cell.close_window()
        for r in recs:
            h = r["handle"]
            r["ok"] = (h is not None and h.done()
                       and h.exception(timeout=0) is None)
            r["finish"] = h.finish_t if r["ok"] else math.inf
            r["latency_ms"] = (r["finish"] - r["due"]) * 1e3
            entry = getattr(h, "_entry", None)
            r["first_dispatch"] = getattr(entry, "first_dispatch_t", None)
            r["waited_ms"] = (deadline - t0) * 1e3
        return recs

    def close(self, wait=True):
        self.gate.open.set()
        self.svc.shutdown(wait=wait)


N_OBJECTS = 2**30


def tails(recs, q: float) -> float:
    """The q-th latency over all requests; a failed one is beyond every
    limit, and where it decides the tail the tail is the time waited."""
    v = _percentile([r["latency_ms"] for r in recs], q)
    return v if math.isfinite(v) else recs[0]["waited_ms"]


def run(cell) -> dict:
    sv = Served(cell)
    try:
        arrivals = generator.open_loop(cell.mix, cell.seed, cell.seconds,
                                       len(sv.rows))
        before = sv.svc.stats()
        recs = sv.window(cell, arrivals, cell.seconds,
                         float(cell.mix["drain_s"]))
        after = sv.svc.stats()
        _report_submits(recs)
        done = [r for r in recs if r["ok"]]
        units = [
            (done[i]["handle"].result(),
             sv.sut.lanes(sv.cfg, sv.rows[done[i]["arrival"].row],
                          done[i]["arrival"].seed, done[i]["arrival"].reps,
                          N_OBJECTS, sv.horizon))
            for i in _sample(cell, sv.rows, done)
        ]
    finally:
        sv.close(wait=False)
    return {
        "attempted": len(recs),
        "failed": len(recs) - len(done),
        "metrics": {"request_p95_ms": tails(recs, 0.95),
                    "request_p50_ms": tails(recs, 0.50)},
        "counters": {
            "requests": recs,
            "lanes_live": after["lanes_dispatched"] - before["lanes_dispatched"],
            "lanes_padded": after["lanes_padded"] - before["lanes_padded"],
        },
        "units": units,
    }


def _report_submits(recs):
    """How long the Service's ``submit`` held the sending thread, on
    standard error: the host's own share of the served tail."""
    took = sorted((r["submitted"] - r["sent"]) * 1e3 for r in recs)
    worst = max(recs, key=lambda r: r["submitted"] - r["sent"])
    print(f"submit ms: p50 {_percentile(took, 0.5)!r} p95 "
          f"{_percentile(took, 0.95)!r} max {took[-1]!r} at "
          f"{worst['sent'] - recs[0]['due']!r} s; over 100 ms: "
          f"{sum(t > 100 for t in took)}", file=sys.stderr)


def _sample(cell, rows, done) -> list[int]:
    """``check_requests`` of the finished requests, drawn from the seed,
    and the longest among them: most replications, then the heaviest
    scenario."""
    if not done:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(cell.seed), 7]))
    k = min(int(cell.mix["check_requests"]), len(done))
    pick = set(rng.choice(len(done), size=k, replace=False).tolist())

    def weight(i):
        a = done[i]["arrival"]
        return (a.reps, rows[a.row].get("rho", 0),
                rows[a.row].get("cv", 0))

    pick.add(max(range(len(done)), key=weight))
    return sorted(pick)
