"""The persistent AOT program store (docs/15_program_store.md).

Contracts pinned here:

* **value-based identity**: a spec RECONSTRUCTED from source (fresh
  function objects — the fresh-process shape) and a
  ``dataclasses.replace`` twin both map to the same store key and
  hydrate a store hit, with results bitwise the freshly-compiled
  run's — the persistence-hostile ``id(spec)`` semantics of the
  in-memory key never leak into the store (minding the
  ``_infer_used_tags`` eval-shape memo lesson from PR 3: the twin runs
  through the full stream path, not just the key builder);
* **strict invalidation ladder**: corrupt/truncated artifacts,
  checksum mismatches, jax-version drift, and backend drift each
  reject LOUDLY (``StoreInvalidationWarning`` + counter) and degrade
  to recompile — never a wrong or crashed program;
* **downgrades**: an executable that cannot be serialized records a
  downgrade at save time instead of crashing, and an unstable spec
  fingerprint raises :class:`UnstableStoreKey` from ``store_key`` but
  only counts a miss from ``hydrate``;
* **observability**: store hit/miss/downgrade counters surface through
  ``Service.stats()`` (top-level ``program_store``) and the chrome
  trace stays validator-clean over a store-hydrated service;
* **warm AOT mode**: ``serve.warm(manifest=...)`` reaches
  first-request readiness with zero executions when init/chunk/fold
  artifacts cover the key, and raises ``LookupError`` loudly on a
  store miss.

The battery rides the fast-compiling tiny model (the test_serve
discipline) with one module-scoped saved store; every test stays well
under the 15 s tier-1 budget.
"""

import dataclasses
import json
import os
import shutil

import jax
import numpy as np
import pytest

from cimba_tpu import config as _cfg
from cimba_tpu import serve
from cimba_tpu.core import api, cmd
from cimba_tpu.core.model import Model
from cimba_tpu.runner import experiment as ex
from cimba_tpu.serve import cache as pc
from cimba_tpu.serve import store as ps
from cimba_tpu.stats import summary as sm

CHUNK = 64
R = 8


def _tiny_spec(t_stop=9.0):
    """The smallest chunkable model (hold/exit only), rebuilt per call
    so every build carries FRESH function objects — the fresh-process
    reconstruction shape the store must hit across."""
    m = Model("tiny-store", event_cap=1, guard_cap=2)

    @m.block
    def work(sim, p, sig):
        done = api.clock(sim) > t_stop
        return sim, cmd.select(
            done, cmd.exit_(), cmd.hold(1.0, next_pc=work.pc)
        )

    m.process("w", entry=work)
    return m.build()


def _clock_path(sims):
    """Module-level summary path (fold programs and fold ARTIFACTS both
    key on its identity/content digest)."""
    return jax.vmap(lambda c: sm.add(sm.empty(), c))(sims.clock)


def _stream(spec, cache, r=R, wave=R, seed=5):
    return ex.run_experiment_stream(
        spec, (), r, wave_size=wave, chunk_steps=CHUNK, seed=seed,
        summary_path=_clock_path, program_cache=cache,
    )


def _assert_bitwise(a, b):
    al = jax.tree.leaves((a.summary, a.n_failed, a.total_events))
    bl = jax.tree.leaves((b.summary, b.n_failed, b.total_events))
    for x, y in zip(al, bl):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    """(store, direct StreamResult): artifacts saved once for the
    whole module + the freshly-compiled reference result — the tier-1
    compile-budget discipline."""
    root = tmp_path_factory.mktemp("store")
    st = ps.ProgramStore(str(root))
    spec = _tiny_spec()
    report = st.save_programs(
        spec, (), R, wave_sizes=(R,), chunk_steps=CHUNK,
        horizon_modes=("none",), summary_paths=(_clock_path,),
    )
    assert {p["role"] for p in report["programs"]} == {
        "init", "chunk", "fold"
    }, report
    assert report["downgrades"] == [], report
    direct = _stream(spec, pc.ProgramCache(store=False))
    return st, direct


def _copy_store(saved, tmp_path):
    """A throwaway copy of the saved store for destructive tests."""
    st, _ = saved
    root = tmp_path / "store"
    shutil.copytree(st.root, root)
    return ps.ProgramStore(str(root))


def test_reconstructed_spec_hydrates_store_hit(saved):
    """THE persistence regression: a reconstructed spec (fresh function
    objects, as in a fresh process) and its dataclasses.replace twin
    both hydrate the saved entry — zero compiles for covered shapes —
    and stream results are bitwise the freshly-compiled run's."""
    st, direct = saved
    rebuilt = _tiny_spec()          # fresh function objects
    twin = dataclasses.replace(rebuilt)  # same-value twin
    assert ps.store_key(
        rebuilt, False, mesh=None, chunk_steps=CHUNK,
    ) == ps.store_key(
        twin, False, mesh=None, chunk_steps=CHUNK,
    )
    h0 = st.stats()["hits"]
    for spec in (rebuilt, twin):
        cache = pc.ProgramCache(store=st)
        res = _stream(spec, cache)
        _assert_bitwise(res, direct)
    stats = st.stats()
    assert stats["hits"] == h0 + 2, stats
    assert stats["fallback_shapes"] == 0, stats
    assert stats["artifact_dispatches"] > 0, stats


def test_f32_profile_roundtrip_bitwise(saved, tmp_path):
    """The other dtype profile: save + hydrate under f32 is its own
    store key and the hydrated result is bitwise the f32 compile."""
    st = ps.ProgramStore(str(tmp_path / "f32"))
    with _cfg.profile("f32"):
        spec = _tiny_spec()
        st.save_programs(
            spec, (), R, wave_sizes=(R,), chunk_steps=CHUNK,
            horizon_modes=("none",), summary_paths=(_clock_path,),
        )
        res = _stream(_tiny_spec(), pc.ProgramCache(store=st))
        direct = _stream(spec, pc.ProgramCache(store=False))
    _assert_bitwise(res, direct)
    assert st.stats()["hits"] == 1
    assert st.stats()["fallback_shapes"] == 0


def test_corrupt_artifact_rejected_loudly_and_recompiles(saved, tmp_path):
    st2 = _copy_store(saved, tmp_path)
    _, direct = saved
    art_dir = os.path.join(st2.root, ps.ARTIFACT_DIR)
    victim = sorted(os.listdir(art_dir))[0]
    with open(os.path.join(art_dir, victim), "r+b") as f:
        f.truncate(17)  # torn write
    spec = _tiny_spec()
    with pytest.warns(ps.StoreInvalidationWarning, match="corrupt"):
        assert st2.hydrate(spec, chunk_steps=CHUNK) is None
    assert st2.stats()["corrupt"] == 1
    # ...and the serving path degrades to recompile, bitwise correct
    cache = pc.ProgramCache(store=st2)
    with pytest.warns(ps.StoreInvalidationWarning):
        res = _stream(spec, cache)
    _assert_bitwise(res, direct)


def test_version_drift_invalidates(saved, tmp_path):
    st2 = _copy_store(saved, tmp_path)
    mpath = os.path.join(st2.root, ps.MANIFEST)
    with open(mpath) as f:
        manifest = json.load(f)
    for entry in manifest["entries"].values():
        entry["env"]["jax"] = "0.0.0"
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.warns(ps.StoreInvalidationWarning, match="environment"):
        assert st2.hydrate(_tiny_spec(), chunk_steps=CHUNK) is None
    assert st2.stats()["invalidated"] == 1


def test_backend_drift_invalidates(saved, tmp_path):
    st2 = _copy_store(saved, tmp_path)
    mpath = os.path.join(st2.root, ps.MANIFEST)
    with open(mpath) as f:
        manifest = json.load(f)
    for entry in manifest["entries"].values():
        entry["env"]["backend"] = "tpu"
        entry["env"]["device_kind"] = "TPU v9"
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.warns(ps.StoreInvalidationWarning, match="environment"):
        assert st2.hydrate(_tiny_spec(), chunk_steps=CHUNK) is None
    assert st2.stats()["invalidated"] == 1


def test_fingerprint_drift_misses(saved):
    """A structurally different model (different closed-over constant)
    is a different store key: plain miss, nothing served."""
    st, _ = saved
    other = _tiny_spec(t_stop=3.0)
    assert ps.store_key(
        other, False, mesh=None, chunk_steps=CHUNK,
    ) != ps.store_key(
        _tiny_spec(), False, mesh=None, chunk_steps=CHUNK,
    )
    m0 = st.stats()["misses"]
    assert st.hydrate(other, chunk_steps=CHUNK) is None
    assert st.stats()["misses"] == m0 + 1


def _handler_a(sim, p, sig):
    return sim


def _handler_b(sim, p, sig):
    return sim


def test_shared_callable_multiplicity_distinguishes_keys(saved):
    """Back-reference regression: handler lists (a, b, a) and
    (a, b, b) — same functions, different sharing — must NOT collapse
    to one store key (a shared key would hydrate the wrong model's
    programs)."""
    base = _tiny_spec()
    s1 = dataclasses.replace(base, user_handlers=[_handler_a,
                                                  _handler_b,
                                                  _handler_a])
    s2 = dataclasses.replace(base, user_handlers=[_handler_a,
                                                  _handler_b,
                                                  _handler_b])
    assert ps.store_key(
        s1, False, mesh=None, chunk_steps=CHUNK,
    ) != ps.store_key(
        s2, False, mesh=None, chunk_steps=CHUNK,
    )


def test_corrupt_manifest_counted_not_hung(saved, tmp_path):
    """A truncated manifest.json degrades to an (empty-store) miss with
    the corrupt counter bumped — and must not deadlock the store lock
    that hydrate holds around the read."""
    st2 = _copy_store(saved, tmp_path)
    with open(os.path.join(st2.root, ps.MANIFEST), "w") as f:
        f.write('{"format": 1, "entr')  # torn write
    with pytest.warns(ps.StoreInvalidationWarning, match="unreadable"):
        assert st2.hydrate(_tiny_spec(), chunk_steps=CHUNK) is None
    stats = st2.stats()
    assert stats["corrupt"] == 1 and stats["misses"] == 1, stats


def test_two_summary_paths_both_keep_fold_artifacts(saved, tmp_path):
    """Fold records for different summary paths share arg shapes; the
    manifest merge must keep BOTH (keyed by path digest), in distinct
    artifact files."""
    st = ps.ProgramStore(str(tmp_path / "2p"))
    spec = _tiny_spec()

    def _n_path(sims):
        return jax.vmap(
            lambda c: sm.add(sm.empty(), c * 2.0)
        )(sims.clock)

    st.save_programs(
        spec, (), R, wave_sizes=(R,), chunk_steps=CHUNK,
        horizon_modes=("none",), summary_paths=(_clock_path, _n_path),
    )
    with open(os.path.join(st.root, ps.MANIFEST)) as f:
        entry = next(iter(json.load(f)["entries"].values()))
    folds = [p for p in entry["programs"] if p["role"] == "fold"]
    assert len(folds) == 2, folds
    assert len({p["path"] for p in folds}) == 2
    assert len({p["file"] for p in folds}) == 2


def test_unstable_fingerprint_raises_and_misses(saved):
    """A spec closing over an object with no value digest has no store
    identity: store_key raises the structured error; hydrate just
    counts a miss (and the in-memory cache path keeps working)."""
    st, _ = saved
    anchor = object()

    def unstable_init(*args, **kwargs):
        return anchor  # closure over a bare object(): no value digest

    spec = dataclasses.replace(_tiny_spec(), user_init=unstable_init)
    with pytest.raises(ps.UnstableStoreKey):
        ps.store_key(spec, False, mesh=None, chunk_steps=CHUNK)
    m0 = st.stats()["misses"]
    assert st.hydrate(spec, chunk_steps=CHUNK) is None
    assert st.stats()["misses"] == m0 + 1


def test_serialize_failure_downgrades_not_crashes(tmp_path, monkeypatch):
    """The jax.export-cannot-roundtrip contingency from the issue: when
    executable serialization fails, save records a DOWNGRADE (mechanism
    (a) still covers the program) and hydrate misses — never crashes,
    never serves a mismatched program."""
    from jax.experimental import serialize_executable as se

    def boom(compiled):
        raise RuntimeError("backend cannot serialize executables")

    monkeypatch.setattr(se, "serialize", boom)
    st = ps.ProgramStore(str(tmp_path / "dg"))
    spec = _tiny_spec()
    report = st.save_programs(
        spec, (), R, wave_sizes=(R,), chunk_steps=CHUNK,
        horizon_modes=("none",), summary_paths=(),
    )
    assert report["programs"] == []
    assert len(report["downgrades"]) == 2, report
    assert st.stats()["downgrades"] == 2
    monkeypatch.undo()
    m0 = st.stats()["misses"]
    assert st.hydrate(spec, chunk_steps=CHUNK) is None
    assert st.stats()["misses"] == m0 + 1


def test_service_stats_surface_and_chrome_trace(saved):
    """Store counters ride Service.stats() (top-level program_store)
    and the chrome trace stays validator-clean over a store-hydrated
    service; the served result is bitwise the freshly-compiled one."""
    from cimba_tpu.obs import export as obs_export

    st, direct = saved
    spec = _tiny_spec()
    cache = pc.ProgramCache(store=st)
    with serve.Service(max_wave=R, cache=cache) as svc:
        res = svc.submit(serve.Request(
            spec, (), R, seed=5, wave_size=R, chunk_steps=CHUNK,
            summary_path=_clock_path,
        )).result(60)
        stats = svc.stats()
        trace = svc.chrome_trace()
    _assert_bitwise(res, direct)
    assert stats["program_store"]["hits"] >= 1, stats
    assert stats["program_store"]["fallback_shapes"] == 0, stats
    assert stats["program_cache"]["store"]["hits"] >= 1
    obs_export.validate_chrome_trace(trace)


def test_warm_manifest_no_execute_and_loud_miss(saved):
    """serve.warm(manifest=...) hydrates init+chunk+fold into the cache
    with ZERO executions (params=None) and a later stream call runs on
    artifacts; a key the store does not cover raises LookupError."""
    st, direct = saved
    spec = _tiny_spec()
    cache = pc.ProgramCache(store=st)
    d0 = st.stats()["artifact_dispatches"]
    out = serve.warm(
        cache, spec, None, None, manifest=st, chunk_steps=CHUNK,
        summary_path=_clock_path,
    )
    assert out is st
    assert st.stats()["artifact_dispatches"] == d0  # truly no-execute
    key = pc.program_key(
        spec, False, mesh=None, chunk_steps=CHUNK,
    )
    assert key in cache
    assert ("fold", False, _clock_path) in cache
    res = _stream(spec, cache)
    _assert_bitwise(res, direct)
    assert st.stats()["artifact_dispatches"] > d0
    with pytest.raises(LookupError, match="warm_store"):
        serve.warm(
            pc.ProgramCache(store=st), spec, None, None, manifest=st,
            chunk_steps=CHUNK + 1, summary_path=_clock_path,
        )


def test_unseen_shape_falls_back_to_jit(saved):
    """A wave shape the store never saw falls back to the ordinary jit
    compile (counted, loud in stats) — and stays bitwise correct."""
    st, _ = saved
    spec = _tiny_spec()
    cache = pc.ProgramCache(store=st)
    f0 = st.stats()["fallback_shapes"]
    res = _stream(spec, cache, r=6, wave=6)
    direct = _stream(_tiny_spec(), pc.ProgramCache(store=False), r=6,
                     wave=6)
    _assert_bitwise(res, direct)
    assert st.stats()["fallback_shapes"] > f0


@pytest.mark.parametrize("env_dir", [False, True])
def test_persistent_cache_placement_and_default_store(
    tmp_path, monkeypatch, env_dir
):
    """Mechanism (a) has one owner: a set JAX_COMPILATION_CACHE_DIR is
    left alone, an unset one puts the cache at the fixed
    <repo>/.jax_cache — never under the store root.  default_store()
    resolves the per-root singleton."""
    prev_dir = jax.config.jax_compilation_cache_dir
    root = tmp_path / "envstore"
    monkeypatch.setenv(ps.STORE_ENV, str(root))
    outside = str(tmp_path / "outside")
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        cdir = ps.maybe_enable_persistent_cache()
        if env_dir:
            assert cdir == outside
            assert jax.config.jax_compilation_cache_dir == prev_dir
        else:
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert cdir == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == cdir
        st = ps.default_store()
        assert st is not None and st.root == str(root)
        assert not (root / "xla").exists()
        assert ps.get_store(str(root)) is st  # per-root singleton
        # a cache built with store=None resolves the env store...
        assert pc.ProgramCache().store is st
        # ...and store=False opts out
        assert pc.ProgramCache(store=False).store is None
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)


# -- concurrent-writer safety (PR 13) ----------------------------------------


_RACE_CHILD = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from cimba_tpu.serve import store as ps

root, tag, n = sys.argv[1], sys.argv[2], int(sys.argv[3])
st = ps.ProgramStore(root)
for i in range(n):
    def add(m, i=i):
        m["entries"][f"{tag}:{i}"] = {"model": tag, "i": i}
    st._update_manifest(add)
print("done", tag)
"""


def test_manifest_lock_two_process_race(tmp_path):
    """Two PROCESSES hammering read-merge-write on one manifest must
    lose no entries: the O_EXCL lockfile serializes the update window.
    (Without the lock, interleaved read-modify-write reliably drops one
    side's entries — the two-warm_store-runs corruption mode.)"""
    import subprocess
    import sys

    root = str(tmp_path / "race_store")
    n = 25
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _RACE_CHILD, root, tag, str(n)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for tag in ("alpha", "beta")
    ]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err
    with open(os.path.join(root, ps.MANIFEST)) as f:
        manifest = json.load(f)   # valid JSON or the test dies here
    entries = manifest["entries"]
    for tag in ("alpha", "beta"):
        missing = [
            i for i in range(n) if f"{tag}:{i}" not in entries
        ]
        assert not missing, (tag, missing)
    assert len(entries) == 2 * n
    # the lockfile does not outlive the writers
    assert not os.path.exists(os.path.join(root, ps.MANIFEST_LOCK))


def test_manifest_stale_lock_broken_loudly(tmp_path):
    """A lockfile left by a dead writer is broken with a LOUD
    structured warning naming the holder — a live save must not hang
    forever on a corpse's lock, and the operator must hear about the
    lost save."""
    root = str(tmp_path / "stale_store")
    st = ps.ProgramStore(
        root, lock_stale_s=3600.0,
    )
    lock = st._manifest_lock_path()
    # a dead pid on THIS host: provably stale regardless of age
    with open(lock, "w") as f:
        json.dump({"pid": 2 ** 22 + 11, "host": __import__(
            "socket").gethostname(), "t": 0}, f)
    with pytest.warns(ps.StaleStoreLockWarning, match="stale"):
        st._update_manifest(
            lambda m: m["entries"].update(ok={"model": "x"})
        )
    with open(st._manifest_path()) as f:
        assert "ok" in json.load(f)["entries"]
    assert not os.path.exists(lock)

    # a LIVE foreign lock within the staleness window times out loudly
    # instead of being broken (the not-stale arm)
    st2 = ps.ProgramStore(
        root, lock_stale_s=3600.0,
        lock_timeout_s=0.2,
    )
    with open(lock, "w") as f:
        json.dump({"pid": os.getpid(), "host": "elsewhere", "t": 0}, f)
    try:
        with pytest.raises(TimeoutError, match="manifest lock"):
            st2._update_manifest(
                lambda m: m["entries"].update(no={"model": "y"})
            )
    finally:
        os.unlink(lock)

    # a PROVABLY-ALIVE same-host holder is never age-broken, however
    # old: a slow writer past the staleness window must hit the
    # Timeout path, not have its lock stolen mid-write (the
    # double-writer hole the review closed)
    st3 = ps.ProgramStore(
        root, lock_stale_s=0.0,
        lock_timeout_s=0.2,
    )
    with open(lock, "w") as f:
        json.dump({"pid": os.getpid(), "host": __import__(
            "socket").gethostname(), "t": 0}, f)
    os.utime(lock, (1, 1))   # ancient — age alone would break it
    try:
        with pytest.raises(TimeoutError, match="manifest lock"):
            st3._update_manifest(
                lambda m: m["entries"].update(no={"model": "z"})
            )
        assert os.path.exists(lock)   # the live holder's lock survived
    finally:
        os.unlink(lock)

    # an EMPTY lock body (a writer SIGKILLed between O_EXCL-create and
    # write — the chaos kill knob can do exactly this) must not spin
    # saves forever: liveness is unknowable, so past the staleness
    # window it is age-broken like a foreign-host lock
    st4 = ps.ProgramStore(
        root, lock_stale_s=0.5,
        lock_timeout_s=30.0,
    )
    open(lock, "w").close()
    os.utime(lock, (1, 1))
    with pytest.warns(ps.StaleStoreLockWarning):
        st4._update_manifest(
            lambda m: m["entries"].update(torn={"model": "w"})
        )
    with open(st4._manifest_path()) as f:
        assert "torn" in json.load(f)["entries"]
    assert not os.path.exists(lock)
