"""The program's own spans and compile counter (docs/17_telemetry.md).

Contracts pinned here:

* the Service's plain wave loop records, per wave, one ``serve.wave``
  span holding ``serve.pack``, ``serve.assemble``, ``serve.run``,
  ``serve.fold`` and ``serve.deliver`` in that order, as many as
  ``stats()["batches"]``, and one ``serve.submit`` per request with its
  seq; the stream runner records ``runner.init``/``run``/``check``/
  ``fold`` per wave under one ``runner.stream``;
* under ``jax.profiler`` every program span shows on the host plane of
  the trace, and one clock offset maps the ring's stamps onto it;
* the compile counter counts a fresh program once, charged to the span
  open on the compiling thread, and a warm call not at all;
* the ring stays bounded, results are bitwise the same with and without
  a profiler session, and both paths' chunk programs compile to the
  module name the benchmark's trace reduction matches.
"""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import trace as bench_trace
from cimba_tpu import serve
from cimba_tpu.core import api, cmd
from cimba_tpu.core.model import Model
from cimba_tpu.obs import telemetry as tm
from cimba_tpu.runner import experiment as ex
from cimba_tpu.serve import cache as pc
from cimba_tpu.stats import summary as sm

SERVE_PHASES = ["serve.pack", "serve.assemble", "serve.run", "serve.fold",
                "serve.deliver"]


def _tiny_spec():
    m = Model("tinyspans", event_cap=1, guard_cap=2)

    @m.block
    def work(sim, p, sig):
        done = api.clock(sim) > 12.0
        return sim, cmd.select(
            done, cmd.exit_(), cmd.hold(1.0, next_pc=work.pc)
        )

    m.process("w", entry=work)
    return m.build()


def _clock_path(sims):
    return jax.vmap(lambda c: sm.add(sm.empty(), c))(sims.clock)


@pytest.fixture(scope="module")
def tiny():
    return _tiny_spec()


@pytest.fixture(scope="module")
def cache():
    return pc.ProgramCache(capacity=256)


def _req(spec, R=4, seed=1):
    return serve.Request(spec, (), R, seed=seed, chunk_steps=16,
                         summary_path=_clock_path)


def _stream(spec, cache, **kw):
    return ex.run_experiment_stream(
        spec, (), 8, wave_size=4, chunk_steps=16, seed=7,
        summary_path=_clock_path, program_cache=cache, **kw,
    )


def _since(t0_ns):
    return [r for r in tm.program_spans() if r["t0_ns"] >= t0_ns]


def _spans(recs, name):
    return sorted((r for r in recs if r["name"] == name),
                  key=lambda r: r["t0_ns"])


def _serve(spec, cache, n=5, max_wave=8):
    with serve.Service(max_wave=max_wave, cache=cache) as svc:
        hs = [svc.submit(_req(spec, seed=s)) for s in range(n)]
        res = [h.result(120) for h in hs]
        stats = svc.stats()
    return hs, res, stats


def test_plain_service_records_one_phase_set_per_wave(tiny, cache):
    t0 = time.monotonic_ns()
    hs, _, stats = _serve(tiny, cache)
    recs = _since(t0)
    waves = _spans(recs, "serve.wave")
    assert len(waves) == stats["batches"] >= 1
    seen = []
    for w in waves:
        kids = sorted((r for r in recs if r["parent"] == w["span"]
                       and r.get("ph") != "i"), key=lambda r: r["t0_ns"])
        assert [k["name"] for k in kids] == SERVE_PHASES
        for a, b in zip(kids, kids[1:]):
            assert a["t1_ns"] <= b["t0_ns"]
        assert w["t0_ns"] <= kids[0]["t0_ns"]
        assert kids[-1]["t1_ns"] <= w["t1_ns"]
        pack = kids[0]["attrs"]
        assert pack["lanes"] == 4 * len(pack["seqs"])
        assert pack["padded"] >= 0
        seen += pack["seqs"]
    seqs = [h._entry.seq for h in hs]
    assert sorted(seen) == sorted(seqs)
    subs = _spans(recs, "serve.submit")
    assert [s["attrs"]["seq"] for s in subs] == seqs
    assert all(s["attrs"]["lanes"] == 4 and s["attrs"]["lock_wait_ns"] >= 0
               and s["parent"] is None for s in subs)
    assert [p["attrs"]["wave"] for p in _spans(recs, "serve.pack")] == list(
        range(1, stats["batches"] + 1))


@pytest.mark.parametrize("max_regrows, phases", [
    (0, ["runner.init", "runner.run", "runner.fold"]),
    (1, ["runner.init", "runner.run", "runner.check", "runner.fold"]),
])
def test_stream_records_its_phases_per_wave(tiny, cache, max_regrows,
                                            phases):
    t0 = time.monotonic_ns()
    res = _stream(tiny, cache, max_regrows=max_regrows)
    recs = _since(t0)
    (root,) = _spans(recs, "runner.stream")
    assert root["attrs"] == {"spec": "tinyspans", "R": 8}
    kids = sorted((r for r in recs if r["parent"] == root["span"]
                   and r.get("ph") != "i"), key=lambda r: r["t0_ns"])
    assert res.n_waves == 2
    assert [k["name"] for k in kids] == phases * 2
    assert [k["attrs"]["wave"] for k in kids] == (
        [0] * len(phases) + [1] * len(phases))
    for a, b in zip(kids, kids[1:]):
        assert a["t1_ns"] <= b["t0_ns"]


def _host_events(trace_dir, names):
    pd = jax.profiler.ProfileData.from_file(bench_trace.find(trace_dir))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return sorted(out, key=lambda e: e[1])


def test_profiler_shows_every_span_at_one_offset(tiny, cache, tmp_path):
    _serve(tiny, cache, n=2)          # warm: no compile inside the trace
    _stream(tiny, cache)
    jax.profiler.start_trace(str(tmp_path))
    t0 = time.monotonic_ns()
    try:
        _serve(tiny, cache, n=3)
        _stream(tiny, cache)
    finally:
        t1 = time.monotonic_ns()
        jax.profiler.stop_trace()
    recs = [r for r in _since(t0) if r.get("ph") != "i"
            and r["t1_ns"] <= t1]
    names = {r["name"] for r in recs}
    assert {"serve.submit", "serve.wave", "runner.stream"} <= names
    assert set(SERVE_PHASES) <= names
    evs = _host_events(str(tmp_path), names)
    for name in names:
        mine = _spans(recs, name)
        theirs = [e for e in evs if e[0] == name]
        assert len(theirs) == len(mine), name
    pairs = []
    for name in names:
        theirs = [e for e in evs if e[0] == name]
        pairs += list(zip(_spans(recs, name), theirs))
    off = float(np.median([e[1] - r["t0_ns"] for r, e in pairs]))
    worst = max(max(abs(e[1] - r["t0_ns"] - off),
                    abs(e[2] - r["t1_ns"] - off)) for r, e in pairs)
    assert worst < 100_000, worst


def test_a_fresh_compile_counts_once_in_its_span():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.ones((13,), jnp.float32)
    with tm.span("test.compile") as sp:
        f(x).block_until_ready()
    hits = [r for r in tm.program_spans()
            if r["name"] == tm.COMPILE and r["parent"] == sp.sid]
    assert len(hits) == 1
    assert hits[0]["attrs"]["in"] == "test.compile"
    assert hits[0]["attrs"]["seconds"] > 0
    assert sp.t0_ns <= hits[0]["t0_ns"] <= sp.t1_ns
    n, s = tm.compiles_between(sp.t0_ns, sp.t1_ns)
    assert n == 1 and s == hits[0]["attrs"]["seconds"]
    with tm.span("test.warm") as warm:
        f(x).block_until_ready()
    assert tm.compiles_between(warm.t0_ns, warm.t1_ns) == (0, 0.0)
    assert not [r for r in tm.program_spans() if r["parent"] == warm.sid]


def test_the_ring_stays_bounded():
    extra = 10
    for i in range(tm.PROGRAM_RING + extra):
        with tm.span("test.ring", i=i):
            pass
    recs = tm.program_spans()
    assert len(recs) == tm.PROGRAM_RING
    assert [r["attrs"]["i"] for r in recs[:2]] == [extra, extra + 1]
    assert recs[-1]["attrs"]["i"] == tm.PROGRAM_RING + extra - 1


def _leaves(res):
    return [np.asarray(a) for a in jax.tree.leaves(
        (res.summary, res.n_failed, res.total_events))]


def test_results_bitwise_with_and_without_a_profiler(tiny, cache, tmp_path):
    off_stream = _stream(tiny, cache)
    _, off_served, _ = _serve(tiny, cache, n=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        on_stream = _stream(tiny, cache)
        _, on_served, _ = _serve(tiny, cache, n=2)
    finally:
        jax.profiler.stop_trace()
    for a, b in zip(_leaves(off_stream), _leaves(on_stream)):
        np.testing.assert_array_equal(a, b)
    for x, y in zip(off_served, on_served):
        for a, b in zip(_leaves(x), _leaves(y)):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("path", ["stream", "served"])
def test_the_chunk_program_is_named_for_the_trace_reduction(tiny, path):
    fresh = pc.ProgramCache(capacity=64)
    if path == "stream":
        _stream(tiny, fresh)
    else:
        _serve(tiny, fresh, n=1)
    pairs = [v for v in (fresh[k] for k in list(fresh))
             if isinstance(v, tuple) and len(v) == 3 and v[2] is tiny]
    assert pairs
    for init_j, chunk_j, _ in pairs:
        sims = init_j(jnp.arange(4), ex._seed_column(7, 4), None, ())
        text = chunk_j.lower(sims).as_text()
        name = re.search(r"module @(\S+)", text).group(1)
        assert name == "jit_chunk"
        assert bench_trace.chunk_program(name)
