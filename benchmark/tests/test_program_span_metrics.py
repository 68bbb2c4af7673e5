"""The readers of the program's spans and compile counter, on the CPU: on
hand-made rings and traces with known answers, on a program that keeps
no ring, and through ``run.main`` on both cells."""

import os
import types

import pytest

from benchmark import run
from benchmark.metrics import program_spans as ps
from benchmark.tests.test_rehearsal import SMALL

NAMES = ["wave_run_ms.served", "wave_host_ms.served", "host_held_idle_share.served",
         "window_compiles.served", "window_compiles.stream"]
READERS = {n: run.load_module(os.path.join(run.ROOT, "benchmark", "metrics", n + ".py"),
                              "test_metric_" + n.replace(".", "_"))
           for n in NAMES}
NS = 10**9
T0 = 5000.0            # the window opens at monotonic second 5000
OFF = -5002.5          # trace seconds = monotonic seconds + OFF


class Ring:
    """Hand-made program records, on the monotonic clock in seconds."""

    def __init__(self):
        self.recs, self.n = [], 0

    def span(self, name, t0, t1, parent=None, **attrs):
        self.n += 1
        sid = f"s{self.n}"
        self.recs.append({"span": sid, "parent": parent, "name": name,
                          "t0_ns": round(t0 * NS), "t1_ns": round(t1 * NS),
                          "attrs": attrs})
        return sid

    def compile(self, t, parent=None):
        self.recs.append({"span": None, "parent": parent, "name": ps.COMPILE,
                          "ph": "i", "t0_ns": round(t * NS), "t1_ns": round(t * NS),
                          "attrs": {"seconds": 0.1}})

    def wave(self, t, run_s, host_s=(0.001, 0.002, 0.003, 0.004)):
        """pack, assemble, run, fold and deliver back to back from t."""
        w = self.span("serve.wave", t, t + run_s + sum(host_s) + 1e-6)
        at = t
        for name, d in zip(["serve.pack", "serve.assemble", "serve.run", "serve.fold",
                            "serve.deliver"],
                           [host_s[0], host_s[1], run_s, host_s[2], host_s[3]]):
            self.span(name, at, at + d, parent=w)
            at += d
        return w


def _served_run(ring, submits, gaps=(), n_devices=1):
    """Requests due at ``submits`` (seconds after T0), each with a
    ``serve.submit`` in the ring and a ``bench.submit`` around it in the
    trace, 20 us before and 30 us after, on the trace's clock."""
    reqs, spans = [], []
    for i, d in enumerate(submits):
        due = T0 + d
        ring.span("serve.submit", due + 1e-4, due + 2e-3, seq=i + 1)
        spans.append(("bench.submit", due + 1e-4 - 20e-6 + OFF, due + 2e-3 + 30e-6 + OFF))
        reqs.append({"arrival": types.SimpleNamespace(due_s=d), "due": due,
                     "finish": due + 0.5})
    return {"attempted": len(reqs), "counters": {"requests": reqs},
            "trace": {"spans": spans, "gaps": list(gaps), "n_devices": n_devices}}


@pytest.fixture
def ring(monkeypatch):
    r = Ring()
    monkeypatch.setattr(ps, "records", lambda: r.recs)
    return r


def test_the_offset_is_found_back(ring):
    run_ = _served_run(ring, [0.5, 0.7, 1.3, 2.9, 3.0, 4.4])
    got = ps.clock_offset(run_, ring.recs)
    assert abs(got - OFF) <= 30e-6


def test_no_bench_span_gives_no_offset(ring):
    run_ = _served_run(ring, [0.5, 1.5])
    run_["trace"]["spans"] = []
    assert ps.clock_offset(run_, ring.recs) is None


def test_a_compile_inside_the_served_window_reads_one(ring):
    run_ = _served_run(ring, [0.5, 1.0, 2.0])
    ring.compile(T0 - 3.0)            # set-up
    ring.compile(T0 + 1.2)            # inside
    ring.compile(T0 + 60.0)           # after the last retirement
    assert READERS["window_compiles.served"].read(run_) == 1


def test_a_compile_inside_the_window_experiments_reads_one(ring):
    ring.span("runner.stream", 10.0, 11.0)         # set-up's warm experiment
    ring.compile(10.5)
    ring.span("runner.stream", 20.0, 30.0)
    ring.span("runner.stream", 30.01, 40.0)
    ring.compile(35.0)
    ring.compile(41.0)                             # after the window
    run_ = {"attempted": 2}
    assert READERS["window_compiles.stream"].read(run_) == 1
    assert READERS["window_compiles.stream"].read({"attempted": 4}) is None


def test_waves_after_the_profilers_start_are_left_out(ring):
    # the profiler starts at trace second 0, monotonic second -OFF = T0 + 2.5:
    # waves from T0 + 1.5 on are left out
    run_ = _served_run(ring, [0.1, 0.2, 0.3, 1.6, 2.6])
    for k in range(5):
        ring.wave(T0 + 0.2 * k, run_s=0.1)         # 0 .. 0.8 s: 100 ms each
    for k in range(9):
        ring.wave(T0 + 1.6 + 0.1 * k, run_s=0.9)   # the profiler's stall
    ring.wave(T0 - 5.0, run_s=0.9)                 # set-up's warm wave
    assert READERS["wave_run_ms.served"].read(run_) == pytest.approx(100.0)
    assert READERS["wave_host_ms.served"].read(run_) == pytest.approx(10.0)


def test_the_host_turn_is_the_phases_own_time(ring):
    run_ = _served_run(ring, [0.1])
    w = ring.wave(T0 + 0.3, run_s=0.2, host_s=(0.002, 0.004, 0.006, 0.008))
    pack = [r for r in ring.recs if r["parent"] == w][0]
    ring.span("serve.inner", ps.start(pack), ps.start(pack) + 0.0015, parent=pack["span"])
    assert READERS["wave_host_ms.served"].read(run_) == pytest.approx(18.5)


def test_the_idle_held_by_the_dispatcher(ring):
    run_ = _served_run(ring, [0.1])
    ring.wave(T0 + 3.0, run_s=0.2)                 # 3.0 .. 3.21 monotonic
    a = T0 + 3.0 + OFF                             # the wave on the trace's clock
    run_["trace"]["gaps"] = [(a - 0.05, a + 0.001),  # 1 ms of 51 inside
                             (a + 0.203, a + 0.209),  # inside: fold, deliver
                             (a + 0.5, a + 0.543)]    # the queue ran empty
    got = READERS["host_held_idle_share.served"].read(run_)
    # 7 ms of 100, to within the offset's 5 us of play
    assert got == pytest.approx(7.0, abs=0.01)


def test_no_device_plane_gives_no_idle_share(ring):
    run_ = _served_run(ring, [0.1], gaps=[(0.0, 1.0)], n_devices=0)
    ring.wave(T0 + 0.2, run_s=0.2)
    assert READERS["host_held_idle_share.served"].read(run_) is None


def test_a_program_without_the_ring_reads_nothing(monkeypatch):
    from cimba_tpu.obs import telemetry

    monkeypatch.delattr(telemetry, "program_spans")
    ring = Ring()
    run_ = _served_run(ring, [0.1, 0.5], gaps=[(0.0, 1.0)])
    for n in NAMES:
        assert READERS[n].read(run_) is None, n


def _traced(cell, overrides, seconds):
    argv = ["--workload", cell, "--seed", str(2**31 + 7), "--seconds", str(seconds),
            "--trace", "1"]
    return run.main(argv, allow_cpu=True, overrides=overrides)


def test_traced_served_rehearsal_reads_the_program_metrics():
    """The profiler starts at 2 s: the waves of the first second are read."""
    ov = {k: dict(v) for k, v in SMALL["mg1_sweep.served"].items()}
    ov["traffic"]["trace_s"] = [3, 100]
    res = _traced("mg1_sweep.served", ov, 4)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["wave_run_ms.served"]["value"] > 0
    assert m["wave_host_ms.served"]["value"] > 0
    assert m["window_compiles.served"]["value"] >= 0
    # a CPU trace has no TPU plane: no device metric is made up
    assert "host_held_idle_share.served" not in m


def test_traced_stream_rehearsal_counts_no_compile():
    res = _traced("mm1_multi.stream", SMALL["mm1_multi.stream"], 1)
    assert res["correct"], res["checks"]
    assert res["metrics"]["window_compiles.stream"]["value"] == 0
