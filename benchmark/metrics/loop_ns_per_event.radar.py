"""Device time of the event loop's chunk program (matched by name in
``benchmark/trace.py``) less the scorer kernel's, over the events of the
traced slice, in nanoseconds per event.  The slice's events are its
scorer calls (one a boundary round) times the window's events a round."""

from benchmark import trace
from benchmark.metrics import awacs_trace as at


def read(run):
    t = run.get("trace")
    k = at.scorer(run)
    events_per_round = at.per_round(run, "lane_steps_dispatched")
    if not t or not k or not events_per_round:
        return None
    seconds, calls = k
    chunk = sum(v for n, v in t["programs"].items() if trace.chunk_program(n))
    if chunk <= seconds:
        return None
    return (chunk - seconds) * 1e9 / (calls * events_per_round)
