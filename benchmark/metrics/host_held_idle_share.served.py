"""The share, in %, of the traced slice's device-idle time that lies
inside a wave's dispatcher spans, from ``serve.pack``'s start to
``serve.deliver``'s end, placed on the trace's clock by the program
spans' clock offset: the idle the serving plane causes.  The rest is
idle for want of load, with the queue empty."""

import bisect

from benchmark.metrics import program_spans as ps


def read(run):
    t = run.get("trace")
    recs = ps.records()
    if not t or not t["n_devices"] or recs is None:
        return None
    gaps = t["gaps"]
    idle = sum(e - s for s, e in gaps)
    off = ps.clock_offset(run, recs)
    if idle <= 0 or off is None:
        return None
    starts = [s for s, _ in gaps]
    inside = 0.0
    # one dispatcher runs the waves one after another: they do not overlap
    for _, p in ps.waves(recs)[0]:
        if "serve.pack" not in p or "serve.deliver" not in p:
            continue
        a, b = ps.start(p["serve.pack"]) + off, ps.end(p["serve.deliver"]) + off
        for s, e in gaps[max(0, bisect.bisect_right(starts, a) - 1):
                         bisect.bisect_left(starts, b)]:
            inside += max(0.0, min(e, b) - max(s, a))
    return 100.0 * inside / idle
