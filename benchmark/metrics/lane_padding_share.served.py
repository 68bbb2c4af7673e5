"""Padded lanes over dispatched lanes in the window, in %, from the
difference of ``Service.stats()["lane_occupancy"]``'s counters across the
window."""


def read(run):
    c = run["counters"]
    total = c.get("lanes_live", 0) + c.get("lanes_padded", 0)
    return 100.0 * c["lanes_padded"] / total if total else None
