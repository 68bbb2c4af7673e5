"""The schedule autotuner (cimba_tpu/tune/, docs/21_autotune.md).

Tier-1 pins, in dependency order:

* the space — candidate enumeration prunes structurally-inert knob
  settings instead of measuring them, schedules round-trip JSON, and
  the digest is value-stable;
* the measurement harness — interleaved rounds, self-vs-self noise
  floor, budget skips recorded (never silent), the compile/run split;
* the search — every arm bitwise-pinned against the default schedule
  (including wave-geometry arms against a default-knob twin at their
  own wave size), a crash-atomic TuneReport;
* the registry — winners persist in the program-store manifest under
  the artifact invalidation ladder (env drift invalidates tuned
  entries exactly like executables), ``CIMBA_TUNE=0`` opts out;
* resolution — ``run_experiment_stream`` / ``serve.Service`` /
  ``run_sweep`` resolve the tuned schedule at program-build time,
  results stay bitwise the default schedule's, and the resolution
  source surfaces in run cards and ``Service.stats()``;
* run-card diffing — schedule drift is env drift, never divergence.

The clean-subprocess serve twin is marked ``slow`` (tools/ci.sh's
"tune smoke" cell runs the same protocol on every CI pass).
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from cimba_tpu import config
from cimba_tpu import tune
from cimba_tpu.obs import audit as obs_audit
from cimba_tpu.serve import store as pstore
from cimba_tpu.tune import measure as tmeasure
from cimba_tpu.tune import probe as tprobe
from cimba_tpu.tune.space import Schedule, ScheduleSpace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

T_END = 4.0
R = 8


@pytest.fixture(scope="module")
def probe_spec():
    """A tiny probe twin: cap 8, one process (every table below the
    scan block, so the table-scan axes canonicalize away), ~8 resumes
    per lane at t_end=4.0 — cheap compiles, real trajectories, a
    recorded ``wait`` summary for the default summary_path."""
    spec, _ = tprobe.build(event_cap=8, per_resume=1, hold=0.5)
    return spec


@pytest.fixture(scope="module")
def tall_spec():
    """A model with 201-row process tables: taller than a 64-row scan
    block, so that table-scan setting survives canonicalization."""
    from cimba_tpu.models import awacs

    spec, _ = awacs.build(200)
    return spec


def _run(spec, **kw):
    from cimba_tpu.runner import experiment as ex

    kw.setdefault("seed", 3)
    kw.setdefault("t_end", T_END)
    return ex.run_experiment_stream(spec, None, R, **kw)


def _saved_report(spec, winner=Schedule(chunk_steps=8)):
    """A minimal search + a forced-tuned report for persistence tests
    (a noisy CI machine may legitimately HOLD; persistence mechanics
    are what these tests pin)."""
    rep = tune.search_schedule(
        spec, None, R, t_end=T_END, seed=7, repeats=1,
        candidates=[Schedule(), winner], workload_label="test",
    )
    return dataclasses.replace(
        rep, decision="tuned", winner=winner, winner_name=winner.label(),
    )


# ---------------------------------------------------------------------------
# knob registration
# ---------------------------------------------------------------------------


def test_tune_knob_registered_and_gated():
    knob = config.ENV_KNOBS["CIMBA_TUNE"]
    assert knob["trace_gate"] is True
    from cimba_tpu.check import gates

    assert "CIMBA_TUNE" in gates.claimed_env_knobs()
    # an UNREGISTERED tune knob raises at runtime (the CHK005 fixture
    # tree carries the matching seeded static violation)
    with pytest.raises(KeyError):
        config.env_raw("CIMBA_TUNE_EXPERIMENTAL")


# ---------------------------------------------------------------------------
# the space
# ---------------------------------------------------------------------------


def test_schedule_roundtrip_digest_label():
    s = Schedule(table_scan=True, chunk_steps=256, table_block=64)
    assert Schedule.from_json(s.to_json()) == s
    assert s.digest() == Schedule.from_json(s.to_json()).digest()
    assert s.label() == "chunk_steps=256,table_block=64,table_scan=True"
    assert Schedule().label() == "default"
    with pytest.raises(ValueError):
        Schedule.from_json({"format": 999})


def test_candidates_prune_inert_knobs(probe_spec, tall_spec):
    space = ScheduleSpace(
        table_scan=(True, False), table_block=(64, 256),
        chunk_steps=(256,),
    )
    small = space.candidates(probe_spec)
    big = space.candidates(tall_spec)
    # every table of the probe fits one block: every table-scan setting
    # traces the dense program — the whole scan x block sub-grid
    # collapses (prune, don't measure)
    assert all(
        c.table_scan is None and c.table_block is None for c in small
    )
    # 201 rows: the 64-row and the default 128-row blocks engage, the
    # 256-row one does not
    assert {(c.table_scan, c.table_block) for c in big} == {
        (None, None), (True, None), (True, 64),
    }
    assert len(big) > len(small)
    for cands in (small, big):
        assert cands[0].is_default()
        keys = [tuple(sorted(c.knobs().items())) for c in cands]
        assert len(keys) == len(set(keys)), "duplicate candidates"
    # ambient-default-equal values are the default arm: the scan off
    # (the default-off env), chunk_steps=1024
    assert Schedule(table_scan=False).canonical(tall_spec).is_default()
    assert Schedule(chunk_steps=1024).canonical().is_default()
    # block is a dead knob when the scan is off
    assert Schedule(table_block=64).canonical(tall_spec).is_default()


def test_schedule_scope_binds_and_restores():
    prev = (config.TABLE_SCAN, config.TABLE_SCAN_BLOCK)
    with Schedule(table_scan=True, table_block=64).scope():
        assert config.TABLE_SCAN is True
        assert config.TABLE_SCAN_BLOCK == 64
    assert (config.TABLE_SCAN, config.TABLE_SCAN_BLOCK) == prev


# ---------------------------------------------------------------------------
# the measurement harness
# ---------------------------------------------------------------------------


def test_measure_arms_interleaves_with_noise_twin():
    calls = []

    def arm(name):
        def run():
            calls.append(name)
            return name

        return tmeasure.Arm(name, run)

    rep = tmeasure.measure_arms(
        [arm("base"), arm("ch")], repeats=2,
    )
    # per round: baseline, its blind twin, then the challenger
    assert calls == ["base", "base", "ch", "base", "base", "ch"]
    assert rep.rounds_done == 2
    assert rep.noise_floor_frac is not None
    assert rep.noise_floor_frac >= 0.0
    assert all(a.status == "ok" and len(a.walls) == 2 for a in rep.arms)
    assert rep.arm("ch").payload == "ch"


def test_measure_arms_budgets_record_skips():
    import time as _time

    def slow_prepare():
        _time.sleep(0.05)

    rep = tmeasure.measure_arms(
        [
            tmeasure.Arm("base", lambda: 1),
            tmeasure.Arm("heavy", lambda: 2, prepare=slow_prepare),
            tmeasure.Arm("ok", lambda: 3),
        ],
        repeats=1, compile_budget_s=0.01, noise_twin=False,
    )
    heavy = rep.arm("heavy")
    assert heavy.status == "skipped"
    assert "compile" in heavy.skip_reason
    assert heavy.compile_s is not None  # measured, not silently dropped
    assert rep.arm("ok").status == "ok"
    # the BASELINE is exempt from budget skips: there must always be
    # an incumbent to race, however slow its compile was
    rep2 = tmeasure.measure_arms(
        [tmeasure.Arm("base", lambda: 1, prepare=slow_prepare)],
        repeats=1, compile_budget_s=1e-9, noise_twin=False,
    )
    assert rep2.arm("base").status == "ok"
    assert rep2.arm("base").compile_s > 1e-9


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ci.sh "tune smoke" runs the full search/pin/persist loop every pass
def test_search_pins_arms_bitwise_and_writes_report(
    probe_spec, tmp_path,
):
    rep = tune.search_schedule(
        probe_spec, None, R, t_end=T_END, seed=7, repeats=2,
        candidates=[
            Schedule(), Schedule(chunk_steps=64), Schedule(chunk_steps=8),
            # wave-geometry arm: pinned against a default-knob twin at
            # ITS OWN wave size (merge order follows the partition)
            Schedule(wave_size=4),
        ],
        out_dir=str(tmp_path), workload_label="pin-test",
    )
    by_name = {row["name"]: row for row in rep.arms}
    assert set(by_name) == {
        "default", "chunk_steps=64", "chunk_steps=8", "wave_size=4",
    }
    for row in rep.arms:
        assert row["status"] == "ok", row
        assert row["pinned"] is True, row
        assert row["events"] == by_name["default"]["events"]
    # same-geometry arms reproduce the default digest EXACTLY
    assert (
        by_name["chunk_steps=64"]["digest"]
        == by_name["default"]["digest"]
    )
    assert (
        by_name["chunk_steps=8"]["digest"]
        == by_name["default"]["digest"]
    )
    assert rep.noise_floor_frac is not None
    assert rep.decision in ("tuned", "hold")
    if rep.decision == "hold":
        assert rep.winner.is_default()
    # the crash-atomic artifact round-trips
    paths = list(tmp_path.glob("tunereport_*.json"))
    assert len(paths) == 1
    from cimba_tpu.tune.search import load_report

    doc = load_report(paths[0])
    assert doc["report_digest"] == rep.digest()
    assert doc["baseline"] == "default"
    assert Schedule.from_json(doc["winner"]).label() == rep.winner_name


def test_search_strict_pin_is_loud(probe_spec, monkeypatch):
    from cimba_tpu.tune import search as tsearch

    # sabotage the digest so a "divergence" is observed: strict_pin
    # must raise, not quietly crown a wrong-answer arm
    real = obs_audit.stream_result_digest
    count = {"n": 0}

    def lying(res):
        count["n"] += 1
        return "deadbeef" if count["n"] == 2 else real(res)

    monkeypatch.setattr(
        "cimba_tpu.obs.audit.stream_result_digest", lying,
    )
    with pytest.raises(tsearch.SchedulePinError):
        tune.search_schedule(
            probe_spec, None, R, t_end=T_END, seed=7, repeats=1,
            candidates=[Schedule(), Schedule(chunk_steps=8)],
        )


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


def test_registry_roundtrip_env_invalidation_and_optout(
    probe_spec, tmp_path, monkeypatch,
):
    st = pstore.ProgramStore(str(tmp_path))
    rep = _saved_report(probe_spec)
    assert tune.save_tuned(st, probe_spec, R, rep) is not None
    assert st.stats()["tuned_saves"] == 1
    # a HOLD saves nothing: the default needs no entry
    hold = dataclasses.replace(rep, decision="hold")
    assert tune.save_tuned(st, probe_spec, R, hold) is None

    sched, source, dig = tune.resolve_schedule(
        probe_spec, R, store=st,
    )
    assert source == "tuned" and sched.chunk_steps == 8
    assert dig == rep.winner.digest()
    assert st.stats()["tuned_hits"] == 1
    # workload bucketing: a different R bucket misses
    _, source2, _ = tune.resolve_schedule(probe_spec, 4096, store=st)
    assert source2 == "default"
    assert st.stats()["tuned_misses"] == 1

    # environment drift invalidates tuned entries exactly like
    # artifacts: loud warning, counted, default schedule runs
    mpath = st._manifest_path()
    manifest = json.load(open(mpath))
    key = next(iter(manifest["tuned"]))
    manifest["tuned"][key]["env"]["jax"] = "0.0.0-drifted"
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.warns(pstore.StoreInvalidationWarning):
        sched3, source3, _ = tune.resolve_schedule(
            probe_spec, R, store=st,
        )
    assert sched3 is None and source3 == "default"
    assert st.stats()["tuned_invalidated"] == 1

    # CIMBA_TUNE=0 opts out before any store is consulted
    monkeypatch.setenv("CIMBA_TUNE", "0")
    sched4, source4, _ = tune.resolve_schedule(probe_spec, R, store=st)
    assert sched4 is None and source4 == "off"


def test_resolve_entry_explicit_kwargs_always_win(
    probe_spec, tmp_path,
):
    from cimba_tpu.tune import registry as treg

    st = pstore.ProgramStore(str(tmp_path))
    tune.save_tuned(st, probe_spec, R, _saved_report(probe_spec))
    # unset knobs fill from the tuned entry
    rs = treg.resolve_entry(probe_spec, R, store=st)
    assert rs.source == "tuned" and rs.chunk_steps == 8
    assert rs.applied == {"chunk_steps": 8}
    # an explicit kwarg pre-empts the tuned knob — and with every
    # tuned knob overridden the source reads override, not tuned
    rs2 = treg.resolve_entry(probe_spec, R, chunk_steps=512, store=st)
    assert rs2.chunk_steps == 512 and rs2.source == "override"
    # an explicit schedule= pre-empts the registry entirely
    rs3 = treg.resolve_entry(
        probe_spec, R, schedule=Schedule(chunk_steps=16), store=st,
    )
    assert rs3.source == "override" and rs3.chunk_steps == 16
    # no store in reach -> the historical defaults
    rs4 = treg.resolve_entry(probe_spec, R, store=False)
    assert rs4.source == "default" and rs4.chunk_steps == 1024
    assert tune.workload_bucket(R) == 8
    assert tune.workload_bucket(100) == 128


# ---------------------------------------------------------------------------
# entry-point resolution, bitwise
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tuned_store(probe_spec, tmp_path_factory):
    """ONE persisted winner (chunk_steps=8) shared by the resolution
    tests — the search+save runs once per module."""
    st = pstore.get_store(str(tmp_path_factory.mktemp("tunestore")))
    tune.save_tuned(st, probe_spec, R, _saved_report(probe_spec))
    return st


@pytest.fixture()
def tuned_store_env(tuned_store, monkeypatch):
    monkeypatch.setenv("CIMBA_PROGRAM_STORE", tuned_store.root)
    return tuned_store


@pytest.mark.slow  # ci.sh "tune smoke" resolves a persisted winner in a clean subprocess, bitwise vs default, every pass
def test_stream_resolution_bitwise_and_run_card(
    probe_spec, tuned_store_env, monkeypatch,
):
    tuned = _run(probe_spec, audit=True)
    blk = tuned.audit["schedule"]
    assert blk["source"] == "tuned"
    assert blk["knobs"]["chunk_steps"] == 8
    assert blk["tune_entry"]
    default = _run(probe_spec, chunk_steps=1024, audit=True)
    assert default.audit["schedule"]["source"] == "override"
    # schedules never change results: tuned == default bitwise
    assert obs_audit.stream_result_digest(
        tuned
    ) == obs_audit.stream_result_digest(default)
    # CIMBA_TUNE=0 restores the default resolution bitwise
    monkeypatch.setenv("CIMBA_TUNE", "0")
    off = _run(probe_spec, audit=True)
    assert off.audit["schedule"]["source"] == "off"
    assert off.audit["schedule"]["knobs"]["chunk_steps"] == 1024
    assert obs_audit.stream_result_digest(
        off
    ) == obs_audit.stream_result_digest(default)


def test_service_resolves_and_surfaces_schedule(
    probe_spec, tuned_store_env,
):
    from cimba_tpu import serve
    from cimba_tpu.runner import experiment as ex

    cache = serve.ProgramCache(store=tuned_store_env)
    with serve.Service(max_wave=16, cache=cache) as svc:
        req = serve.Request(probe_spec, None, R, seed=3, t_end=T_END)
        h_tuned = svc.submit(req)
        h_override = svc.submit(serve.Request(
            probe_spec, None, R, seed=3, t_end=T_END, chunk_steps=1024,
        ))
        r_tuned = h_tuned.result(120)
        r_override = h_override.result(120)
        stats = svc.stats()
    # the caller's Request object is never mutated by resolution
    assert req.chunk_steps is None
    srcs = stats["schedule"]["sources"]
    assert srcs["tuned"] == 1 and srcs["override"] == 1
    by_class = stats["schedule"]["by_class"]
    assert by_class  # the class's latest resolved block is visible
    direct = ex.run_experiment_stream(
        probe_spec, None, R, seed=3, t_end=T_END, chunk_steps=1024,
        program_cache=cache,
    )
    d = obs_audit.stream_result_digest(direct)
    assert obs_audit.stream_result_digest(r_tuned) == d
    assert obs_audit.stream_result_digest(r_override) == d


def test_sweep_resolution_records_schedule(
    probe_spec, tuned_store_env,
):
    import numpy as np

    from cimba_tpu import sweep as sw

    grid = sw.SweepGrid(
        name="probe", axes={"x": (1.0, 2.0)},
        row=lambda x: (np.float64(x),),
    )
    res = sw.run_sweep(
        probe_spec, grid, reps_per_cell=R, seed=1, t_end=T_END,
        audit=True,
    )
    blk = res.audit["schedule"]
    assert blk["source"] == "tuned"
    assert blk["knobs"]["chunk_steps"] == 8
    # fixed-R cells stay bitwise the direct per-cell stream calls
    # under the resolved schedule (the docs/16 contract, tuned arm)
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.sweep.adaptive import round_seed

    direct = ex.run_experiment_stream(
        probe_spec, (np.float64(1.0),), R,
        seed=round_seed(1, 0, 0), t_end=T_END,
    )
    assert res.audit["cells"][0][
        "result_digest"
    ] == obs_audit.result_digest(
        (direct.summary, direct.n_failed, direct.total_events)
    )


# ---------------------------------------------------------------------------
# run-card diffing: schedule drift is env drift
# ---------------------------------------------------------------------------


def test_diff_cards_schedule_drift_is_env_drift(
    probe_spec, tuned_store_env, tmp_path,
):
    tuned = _run(probe_spec, audit=True)
    default = _run(probe_spec, chunk_steps=1024, audit=True)
    rep = obs_audit.diff_cards(tuned.audit, default.audit)
    assert rep["comparable"] is True
    assert "chunk_steps" in rep["schedule_drift"]
    assert any(
        k.startswith("schedule.") for k in rep["env_drift"]
    )
    # the chunk boundaries moved, so the trails are honestly skipped —
    # but the RESULTS compare, and they are equal
    assert rep["trail_skipped"] is True
    assert rep["result_equal"] is True
    assert rep["identical"] is True
    # through the jax-free CLI: exit 0 (identical), drift printed
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(tuned.audit, default=str))
    pb.write_text(json.dumps(default.audit, default=str))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "audit_diff.py"),
         str(pa), str(pb), "--json"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["identical"] is True
    assert doc["schedule_drift"]


# ---------------------------------------------------------------------------
# the clean-subprocess twin (ci.sh runs this protocol every pass)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_clean_subprocess_serves_persisted_winner(
    probe_spec, tuned_store_env,
):
    code = r"""
import os
from cimba_tpu import serve
from cimba_tpu.obs import audit
from cimba_tpu.serve import store as pstore
from cimba_tpu.tune import probe

spec, _ = probe.build(event_cap=8, per_resume=1, hold=0.5)
with serve.Service(max_wave=16) as svc:
    res = svc.submit(serve.Request(spec, None, 8, seed=3, t_end=4.0)
                     ).result(300)
    stats = svc.stats()
st = pstore.default_store().stats()
assert st["tuned_hits"] >= 1 and st["tuned_misses"] == 0, st
assert st["tuned_saves"] == 0, st      # resolution only, no re-search
assert stats["schedule"]["sources"]["tuned"] >= 1, stats["schedule"]
from cimba_tpu.runner import experiment as ex
default = ex.run_experiment_stream(spec, None, 8, seed=3, t_end=4.0,
                                   chunk_steps=1024)
assert (audit.stream_result_digest(res)
        == audit.stream_result_digest(default))
print("OK")
"""
    env = dict(os.environ)
    env["CIMBA_PROGRAM_STORE"] = tuned_store_env.root
    env.pop("CIMBA_TUNE", None)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "OK" in proc.stdout
