"""Median, in ms, of the time from a request's due time to the Service's
first wave dispatch of it (the Service's ``first_dispatch_t`` stamp, on
the same monotonic clock as the harness's)."""

import statistics


def read(run):
    waits = [(r["first_dispatch"] - r["due"]) * 1e3
             for r in run["counters"]["requests"]
             if r.get("first_dispatch") is not None]
    return statistics.median(waits) if waits else None
