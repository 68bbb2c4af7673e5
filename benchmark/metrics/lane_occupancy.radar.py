"""Lane-steps that dispatched an event over lane-steps run, in %, over
the window's experiments: the boundary chunk's counters
(``core.loop.BOUNDARY_COUNTERS``), folded per experiment by the stream
runner.  A lane that waits for the boundary phase, is done, or is out of
its chunk's budget runs a step that dispatches nothing; the boundary
phase's own step counts for every lane."""


def read(run):
    b = run.get("counters", {}).get("boundary")
    if not b or not b.get("lane_steps_run"):
        return None
    return 100.0 * b["lane_steps_dispatched"] / b["lane_steps_run"]
