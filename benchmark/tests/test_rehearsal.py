"""Both cells end to end on the CPU at sizes a test run holds, through
``run.main``'s rehearsal arguments: sound runs come out correct, the
control and each planted fault come out not correct, and a cell made of
new files and new entries alone runs."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, run
from benchmark.refs import mg1 as ref

SMALL = {
    "mm1_multi.stream": {
        "config": {"trials": 256, "objects_per_trial": 60},
        "traffic": {"wave": 256, "warm_objects": 4},
    },
    "mg1_sweep.served": {
        "config": {"horizon": 40},
        "traffic": {"reps": [64, 128], "max_wave": 256, "rate_per_s": 10,
                    "check_requests": 4, "drain_s": 30, "trace_s": [0, 100]},
    },
}
CELLS = sorted(SMALL)


def _run(cell, seed=2**31 + 99, root=run.ROOT, trace=0, overrides=None):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace)]
    return run.main(argv, root=root, allow_cpu=True,
                    overrides=overrides or SMALL[cell])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"]


def test_the_cpu_is_refused():
    with pytest.raises(run.Refused):
        run.main(["--workload", "mm1_multi.stream", "--seed", "1",
                  "--seconds", "1"])


def test_traced_run_reads_the_host_metrics():
    res = _run("mg1_sweep.served", trace=1)
    assert res["correct"]
    for name in ("generator_lag_ms.served", "queue_wait_ms.served",
                 "lane_padding_share.served"):
        assert name in res["metrics"]
    # a CPU trace has no TPU plane: no device metric is made up
    assert "device_idle.served" not in res["metrics"]


def _unchanged_step(monkeypatch):
    """The chunk driver hands the state back as it came."""
    def drive(chunk, sims, *, on_chunk=None, **_):
        if on_chunk is not None:
            on_chunk(1)
        return sims

    monkeypatch.setattr("cimba_tpu.core.loop.drive_chunks", drive)
    monkeypatch.setattr("cimba_tpu.runner.experiment.drive_chunks", drive)


def _half_batch(monkeypatch):
    """The fold pools the first half of the lanes only."""
    from cimba_tpu.stats import summary as sm

    orig = sm.merge_tree

    def half(s):
        return orig(jax.tree.map(lambda x: x[: max(1, x.shape[0] // 2)], s))

    monkeypatch.setattr(sm, "merge_tree", half)


def _altered_answer(monkeypatch):
    """The fold delivers a mean 1% off."""
    from cimba_tpu.stats import summary as sm

    orig = sm.merge_tree

    def off(s):
        p = orig(s)
        return p._replace(m1=p.m1 * 1.01)

    monkeypatch.setattr(sm, "merge_tree", off)


FAULTS = {"unchanged_step": _unchanged_step, "half_batch": _half_batch,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_a_planted_fault_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = _run(cell, seed=4000000007)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell):
    """The reference in bfloat16, put in the program's place, against the
    reference in float32 on the same lanes."""
    cfg = {**run._read(os.path.join(run.ROOT, f"benchmark/configs/{cell.split('.')[0]}.json")),
           **SMALL[cell]["config"]}
    limits = run._read(os.path.join(run.ROOT, f"benchmark/limits/{cell}.json"))
    sut = run.load_module(os.path.join(run.ROOT, "benchmark/sut", cfg["model"] + ".py"),
                          "test_sut_" + cfg["model"])
    row = sut.rows(cfg)[-1]
    if "trials" in cfg:
        lanes = sut.lanes(cfg, row, 12345, cfg["trials"], cfg["objects_per_trial"],
                          float("inf"))
    else:
        lanes = sut.lanes(cfg, row, 12345, 128, 2**30, float(cfg["horizon"]))
    kw = dict(service=sut.SERVICE, cap=int(cfg["queue_cap"]))
    f32 = compare.pool_lanes(ref.run(lanes, dtype=jnp.float32, **kw))
    bf16 = compare.pool_lanes(ref.run(lanes, dtype=jnp.bfloat16, **kw))
    ok, checks = compare.verdict(compare.gaps(bf16, f32), limits)
    assert not ok, checks


def test_a_new_cell_is_new_files_and_entries(tmp_path):
    """A dummy traffic mix, registered from a temporary file, runs through
    the harness untouched."""
    src = os.path.join(run.ROOT, "benchmark")
    dst = tmp_path / "benchmark"
    dst.mkdir()
    for name in os.listdir(src):
        if name not in ("traffic", "limits"):
            os.symlink(os.path.join(src, name), dst / name)
    shutil.copytree(os.path.join(src, "traffic"), dst / "traffic")
    shutil.copytree(os.path.join(src, "limits"), dst / "limits")
    bench = run._read(os.path.join(run.ROOT, "BENCHMARK.json"))
    bench["workloads"].append({"name": "mm1_multi.dummy", "config": "mm1_multi",
                               "traffic": "dummy", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "mm1_multi.stream" in m.get("workloads", []):
            m["workloads"].append("mm1_multi.dummy")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (dst / "traffic" / "dummy.json").write_text(json.dumps(
        {"driver": "stream", "wave": 128, "chunk_steps": 256,
         "warm_objects": 2}))
    shutil.copy(dst / "limits" / "mm1_multi.stream.json",
                dst / "limits" / "mm1_multi.dummy.json")
    res = _run("mm1_multi.dummy", root=str(tmp_path), overrides={
        "config": {"trials": 128, "objects_per_trial": 30}})
    assert res["correct"], res["checks"]
    assert res["metrics"]["events_per_s"]["value"] > 0
    assert np.isfinite(res["metrics"]["setup_s"]["value"])
