"""Median device-idle time, in ms, from one experiment's last chunk
program to the next experiment's first: init, fold, readback and the
host's turn between experiments.  Experiments are told apart by the
harness's ``bench.experiment`` spans; a window of one experiment has no
gap to read."""

import statistics

from benchmark import trace


def read(run):
    t = run.get("trace")
    if not t or not t["n_devices"]:
        return None
    exps = [(s, e) for n, s, e in t["spans"] if n == "bench.experiment"]
    chunks = [(s, e) for n, s, e in t["program_runs"] if trace.chunk_program(n)]
    ends, starts = [], []
    for s, e in sorted(exps):
        mine = [c for c in chunks if s <= c[0] < e]
        if mine:
            starts.append(mine[0][0])
            ends.append(mine[-1][1])
    gaps = [trace.idle_between(t["gaps"], a, b)
            for a, b in zip(ends[:-1], starts[1:])]
    return statistics.median(gaps) * 1e3 if gaps else None
