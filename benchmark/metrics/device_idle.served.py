"""Device idle share of the served window, from the trace: 100 x (1 - the
union of operation intervals over the window)."""


def read(run):
    t = run.get("trace")
    if not t or not t["n_devices"] or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
