"""Compiles the program counted from the served window's opening to the
last retirement of its requests (``jax.compile`` instants in the
program's ring, persistent-cache loads included): 0 where set-up warmed
every shape."""

from benchmark.metrics import program_spans as ps


def read(run):
    recs = ps.records()
    win = ps.served_window(run) if recs is not None else None
    return None if win is None else ps.compiles_in(recs, *win)
