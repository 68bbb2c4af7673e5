"""Device time of the event loop's chunk program (matched by name in
``benchmark/trace.py``) over the events the traced window completed, in
nanoseconds per event."""

from benchmark import trace


def read(run):
    t = run.get("trace")
    events = run["counters"].get("events")
    if not t or not events:
        return None
    s = sum(v for k, v in t["programs"].items() if trace.chunk_program(k))
    return s * 1e9 / events if s > 0 else None
