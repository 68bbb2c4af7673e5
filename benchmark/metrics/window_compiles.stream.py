"""Compiles the program counted from the start of the window's first
experiment to the end of its last: the last ``attempted`` of the
program's ``runner.stream`` spans (``jax.compile`` instants in its ring,
persistent-cache loads included).  0 where set-up warmed every shape."""

from benchmark.metrics import program_spans as ps


def read(run):
    recs = ps.records()
    n = run.get("attempted", 0)
    if recs is None or n <= 0:
        return None
    streams = sorted((r for r in recs if r["name"] == "runner.stream"),
                     key=ps.start)[-n:]
    if len(streams) < n:
        return None
    return ps.compiles_in(recs, ps.start(streams[0]), ps.end(streams[-1]))
