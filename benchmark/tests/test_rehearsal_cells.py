"""The cell ``awacs.radar`` end to end on the CPU at a size a test run
holds, through ``run.main``'s rehearsal arguments: a sound run comes out
correct, the control comes out not correct, and the traced run reads the
program's counters."""

import os

import jax.numpy as jnp
import numpy as np

from benchmark import compare, run
from benchmark.refs import awacs as ref

SMALL = {
    "awacs.radar": {
        "config": {"targets": 32, "trials": 16, "horizon": 3.0},
        "traffic": {"wave": 16, "chunk_steps": 256, "warm_horizon": 0.2,
                    "trace_s": [0, 100]},
    },
}


def _argv(cell, seed, trace=0):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace)]


def test_awacs_sound_run_is_correct():
    res = run.main(_argv("awacs.radar", 2**31 + 99), allow_cpu=True,
                   overrides=SMALL["awacs.radar"])
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert {"events_per_s", "setup_s"} <= set(res["metrics"])


def test_awacs_traced_run_reads_the_occupancy():
    res = run.main(_argv("awacs.radar", 4000000007, trace=1), allow_cpu=True,
                   overrides=SMALL["awacs.radar"])
    assert res["correct"], res["checks"]
    occ = res["metrics"]["lane_occupancy.radar"]["value"]
    assert 0 < occ <= 100
    assert res["metrics"]["window_compiles.stream"]["value"] == 0
    # a CPU trace has no TPU plane: no device metric is made up
    assert "scorer_roofline.radar" not in res["metrics"]
    assert "loop_ns_per_event.radar" not in res["metrics"]


def test_awacs_control_is_not_correct():
    """The reference in bfloat16, put in the program's place, against the
    reference in float32 on the same lanes."""
    cfg = {**run._read(os.path.join(run.ROOT, "benchmark/configs/awacs.json")),
           **SMALL["awacs.radar"]["config"]}
    limits = run._read(os.path.join(run.ROOT, "benchmark/limits/awacs.radar.json"))
    R = cfg["trials"]
    lanes = ref.Lanes(np.full(R, 12345), np.arange(R),
                      np.full(R, float(cfg["horizon"])), cfg["targets"],
                      cfg["dwell"])
    f32 = compare.pool_lanes(ref.run(lanes, dtype=jnp.float32))
    bf16 = compare.pool_lanes(ref.run(lanes, dtype=jnp.bfloat16))
    ok, checks = compare.verdict(compare.gaps(bf16, f32), limits)
    assert not ok, checks
