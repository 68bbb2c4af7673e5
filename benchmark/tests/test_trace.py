"""The trace reduction, offline: a hand-made trace with known answers, and
a small trace recorded on a v5e (mm1 stream, 1024 lanes, two
experiments) kept in ``data/``."""

import glob
import gzip
import os

import jax
import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))

# times in ns: window 1000..11000 on the host; on the chip, ops 1000..3000
# (fusion) and 6000..7000 (all-reduce), one program run 1000..7000; host
# spans bench.experiment 1000..5000 and bench.result_wait 5500..9000
_XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.3" } }
  event_metadata { key: 3 value { id: 3 name: "jit_chunk(7)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 5500000 duration_ps: 3500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.experiment" } }
  event_metadata { key: 3 value { id: 3 name: "bench.result_wait" } }
}
"""


@pytest.fixture
def made(tmp_path):
    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(_XSPACE)
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    return str(tmp_path)


def test_hand_made_trace(made):
    r = trace.reduce(made)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(3e-6)
    assert r["idle_share"] == pytest.approx(0.7)
    assert r["programs"] == {"jit_chunk": pytest.approx(6e-6)}
    assert r["collective_s"] == pytest.approx(1e-6)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(2e-6)]
    # gaps 3000..6000 (mostly under bench.experiment) and 7000..11000
    # (mostly under bench.result_wait)
    assert r["idle_gaps"] == [["bench.result_wait", pytest.approx(4e-6)],
                              ["bench.experiment", pytest.approx(3e-6)]]
    assert trace.idle_between(r["gaps"], 0.0, 1.0) == pytest.approx(7e-6)


@pytest.mark.parametrize("gap, name", [
    ((1000, 3000), "bench.submit"),      # covered whole
    ((1000, 5000), "bench.submit"),      # covered half
    ((1000, 9000), "host"),              # a short span in a long gap
])
def test_a_gap_is_named_by_a_span_covering_half(gap, name):
    spans = [("bench.submit", 1000, 3000), ("bench.result_wait", 8500, 9500)]
    assert trace.name_gap(spans, *gap) == name


def test_recorded_v5e_trace(tmp_path):
    (path,) = glob.glob(os.path.join(HERE, "data", "*.xplane.pb.gz"))
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(path) as f:
        (d / "t.xplane.pb").write_bytes(f.read())
    r = trace.reduce(str(tmp_path))
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert any(trace.chunk_program(p) for p in r["programs"])
    idle = trace.idle_between(r["gaps"], 0.0, 1e12)
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert sum(1 for n, *_ in r["spans"] if n == "bench.experiment") == 2
    assert all(" = " not in name for name, _ in r["device_ops"])
    # recorded: 1024 lanes x 20 objects, two experiments, 38 ms window
    assert r["window_s"] == pytest.approx(0.03807029)
    assert r["busy_s"] == pytest.approx(0.01115113, rel=1e-6)
