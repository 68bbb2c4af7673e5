"""Median, in ms, of the program's ``serve.run`` spans over the served
window's waves: how long the chunk program ran a wave, from the first
chunk's dispatch to the poll that found no live lane.  Waves that start
less than a second before the profiler's start are left out."""

import statistics

from benchmark.metrics import program_spans as ps


def read(run):
    got = ps.window_waves(run)
    if got is None:
        return None
    runs = [ps.end(p["serve.run"]) - ps.start(p["serve.run"])
            for _, p in got[0] if "serve.run" in p]
    return statistics.median(runs) * 1e3 if runs else None
