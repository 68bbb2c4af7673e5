"""Median, in ms, per wave of the served window, of the own time of the
program's ``serve.pack``, ``serve.assemble``, ``serve.fold`` and
``serve.deliver`` spans: the dispatcher's host turn around a wave.
Waves that start less than a second before the profiler's start are
left out."""

import statistics

from benchmark.metrics import program_spans as ps


def read(run):
    got = ps.window_waves(run)
    if got is None:
        return None
    waves, kids = got
    turns = [sum(ps.self_s(p[n], kids) for n in ps.HOST_PHASES)
             for _, p in waves if all(n in p for n in ps.HOST_PHASES)]
    return statistics.median(turns) * 1e3 if turns else None
