"""95th percentile, in ms, of how late the load generator sent each
request: send time minus due time, on the harness's clock."""

import math


def read(run):
    lags = sorted((r["sent"] - r["due"]) * 1e3 for r in run["counters"]["requests"])
    if not lags:
        return None
    return lags[max(0, math.ceil(0.95 * len(lags)) - 1)]
