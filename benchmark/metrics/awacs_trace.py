"""What the AWACS cell's readers share: the scorer kernel in the traced
slice of the window, and the work the scorer has to do.

The kernel is the Pallas call named ``awacs_nn_score`` (a number is
appended to its operation's name).  Its device time and its calls are
read from the trace the harness reduced, still on disk while the readers
run (``run["trace_dir"]``), on the cell's first chip, clipped to the
``bench.window`` span: the traced slice (the mix's ``trace_s``).  The
boundary phase calls it once a round, so its calls count the slice's
rounds; the window's counters (``core.loop.BOUNDARY_COUNTERS``) give the
dwells and events of a round on average, and so the slice's.

The work is fixed, whatever implements it: per target scored, the
matmuls 8x32, 32x32 and 33x1 at two operations a multiply-add, and 20
bytes (position and velocity in, the probability out, in float32).
"""

from __future__ import annotations

import json
import os

from benchmark import trace

KERNEL = "awacs_nn_score"
FLOP_PER_ROW = 2 * (8 * 32 + 32 * 32 + 33)
BYTES_PER_ROW = 5 * 4
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_seen: dict = {}


def scorer(run):
    """``(seconds, calls)`` of the scorer in the traced slice, or
    ``None``."""
    d = run.get("trace_dir")
    if not d:
        return None
    try:
        path = trace.find(d)
    except FileNotFoundError:
        return None
    if path not in _seen:
        _seen[path] = _kernel(path)
    return _seen[path]


def _kernel(path):
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    window, device = None, None
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            device = plane
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW and window is None:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None or device is None:
        return None
    lo, hi = window
    ns, calls = 0, 0
    for line in device.lines:
        if line.name != trace.OPS_LINE:
            continue
        for ev in line.events:
            if KERNEL not in trace._op_name(ev.name):
                continue
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e > lo and s < hi:
                ns += min(hi, e) - max(lo, s)
                calls += lo <= (s + e) / 2 < hi
    return (ns * 1e-9, calls) if calls else None


def per_round(run, counter: str):
    """The window's ``counter`` over its boundary rounds, or ``None``."""
    b = run.get("counters", {}).get("boundary")
    if not b or not b.get("boundary_rounds"):
        return None
    return b[counter] / b["boundary_rounds"]


def targets() -> int:
    with open(os.path.join(_BENCH, "configs", "awacs.json")) as f:
        return int(json.load(f)["targets"])


def peaks(device_kind: str):
    with open(os.path.join(_BENCH, "peaks.json")) as f:
        return json.load(f).get(device_kind)
