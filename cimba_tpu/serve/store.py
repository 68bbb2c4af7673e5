"""The persistent AOT program store — zero-cold-start serving.

The bounded in-memory :class:`~cimba_tpu.serve.cache.ProgramCache` dies
with the process: at production scale every rollout re-pays minutes of
XLA compile per (spec, settings) program point before the first request
is served.  This module makes the **compiler artifact** the unit of
caching (the "Compiler-First … Portable O(1) Caching for Inference"
frame, PAPERS.md): compiled executables are serialized against a frozen,
*value-based* program key and a fresh process hydrates to warm-serving
without ever invoking XLA.  Two mechanisms, layered
(docs/15_program_store.md):

(a) **JAX's persistent compilation cache** — an entry point
    (``chip_smoke.py``, ``benchmark/run.py``) calls :func:`maybe_enable_
    persistent_cache` once at start, so *every* jit in that process
    becomes a disk hit on recompile.  The cache lives where
    ``JAX_COMPILATION_CACHE_DIR`` says, or at the fixed
    ``<repo>/.jax_cache``; the store never moves it.  This mechanism
    keys on jax's own HLO fingerprint and needs no help from us; it
    saves the XLA compile but still re-pays tracing and jax's
    dispatch-path setup per program.

(b) **The explicit artifact layer** — :class:`ProgramStore` AOT-
    compiles the ``(init, chunk)`` program pair per wave shape
    (``jit.lower(...).compile()``), serializes the loaded executables
    (``jax.experimental.serialize_executable``), and records them in a
    manifest under :func:`store_key` — a sha256 over the spec's
    **stable fingerprint** (functions hashed by code + closure
    *values*, never ``id()`` — entries must survive a process
    boundary, unlike the in-memory key) plus every trace-time setting
    the program bakes in.  Hydration returns shim callables that
    dispatch stored shapes straight to the deserialized executable and
    fall back to an ordinary ``jax.jit`` (mechanism (a) softening the
    recompile) for shapes the store has never seen.

Invalidation is strict and LOUD — the same contract as the
dispatch-time key verification in ``serve/service.py``: a jax/jaxlib
version bump, backend/platform drift, manifest-format bump, checksum
mismatch, truncated pickle, or fingerprint drift each produce a counted
miss (and a :class:`StoreInvalidationWarning` where there is a body to
point at), **never a wrong program and never a crash** — every failure
path degrades to recompiling exactly what the cache would have compiled
anyway.  When an executable cannot be serialized at save time (e.g. a
backend whose PjRt client does not implement executable serialization),
the entry records a **downgrade**: mechanism (a) still covers that
program, and ``stats()["downgrades"]`` says so instead of crashing the
save.
"""

from __future__ import annotations

# cimba-check: persist-path  (CHK001: no id() may feed what this module
# writes to disk — store keys must be value-based)

import contextlib
import dataclasses
import hashlib
import json
import os
import pickle
import socket
import threading
import time
import types
import warnings
from typing import Any, Optional

#: environment knob: the store root directory.  Setting it makes
#: :func:`default_store` attach a :class:`ProgramStore` to every
#: :class:`~cimba_tpu.serve.cache.ProgramCache` lookup.
STORE_ENV = "CIMBA_PROGRAM_STORE"

#: where mechanism (a)'s compile cache lives when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed path inside the
#: checkout (the path is part of the cache key, so one that moved
#: between runs would never hit)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)

#: manifest format version: bump on any layout/semantic change — old
#: stores then invalidate loudly instead of deserializing garbage.
#: 2: PR 17 added per-program ``footprint_bytes`` (the device
#: scheduler's memory-aware admission reads it off hydrated programs
#: without re-lowering, docs/24_device_scheduler.md).  Per-program
#: ``program_size`` (docs/25_compile_wall.md) is additive-optional —
#: readers tolerate its absence, so it needed no bump.
#: 3: per-program ``devices`` — the device ids the executable was
#: compiled for, handed back as ``execution_devices`` at load (jax 0.9
#: otherwise loads it for every local device and the call fails).
FORMAT = 3

MANIFEST = "manifest.json"
ARTIFACT_DIR = "artifacts"
MANIFEST_LOCK = "manifest.lock"


class StoreInvalidationWarning(UserWarning):
    """A store entry was rejected (corrupt, truncated, or from a
    different jax/backend/format) and the program will be recompiled."""


class StaleStoreLockWarning(UserWarning):
    """A manifest lockfile outlived its holder (dead pid or past the
    staleness window) and was broken.  Loud by design: a stale lock
    means some writer died mid-update — the manifest it left behind is
    still the previous consistent one (writes are atomic), but whoever
    operates the store should know a save was lost."""


class UnstableStoreKey(Exception):
    """The spec's structure cannot be fingerprinted by value (e.g. a
    block closes over an object with no deterministic content digest),
    so it has no process-independent store identity.  The in-memory
    cache still works; the store records a downgrade."""


# -- the value-based fingerprint ---------------------------------------------
#
# The in-memory ``cache.spec_fingerprint`` keys function-valued
# structure by ``id()`` — correct within one process (entries pin their
# spec against id recycling) but meaningless across a process boundary.
# The store's fingerprint digests functions by VALUE: module, qualname,
# bytecode, recursively-resolved constants, defaults, and closure cell
# *contents*.  A spec rebuilt from the same source in a fresh process
# (or a ``dataclasses.replace`` twin) digests identically; a model
# whose code or closed-over values changed digests differently and
# misses — never a wrong program.


# cimba-check: content-path
def _stable_code(code: types.CodeType, seen: dict) -> tuple:
    consts = tuple(
        _stable_code(c, seen) if isinstance(c, types.CodeType)
        else _stable_obj(c, seen)
        for c in code.co_consts
    )
    return (
        "code", code.co_code, consts, code.co_names, code.co_varnames,
        code.co_freevars, code.co_argcount, code.co_kwonlyargcount,
        code.co_flags,
    )


# cimba-check: content-path
def _stable_callable(fn, seen: dict) -> tuple:
    import functools

    if isinstance(fn, functools.partial):
        kw = tuple(sorted((fn.keywords or {}).items()))
        return (
            "partial", _stable_callable(fn.func, seen),
            _stable_obj(tuple(fn.args), seen), _stable_obj(kw, seen),
        )
    if isinstance(fn, types.MethodType):
        # a bound method's behavior depends on its instance too
        return (
            "method", _stable_callable(fn.__func__, seen),
            _stable_obj(fn.__self__, seen),
        )
    if id(fn) in seen:  # cimba: noqa(CHK001) — in-process revisit key only
        # revisited callable (a closure cycle, or one function shared
        # by several slots): a back-reference to its first-visit
        # ordinal, NOT a bare marker — (f, g, f) and (f, g, g) must
        # digest differently or two different models could share a
        # store key and hydrate each other's programs.  Only the
        # ORDINAL is digested; the id() is a transient dict key that
        # never leaves this call (hence the CHK001 suppressions).
        return ("ref", seen[id(fn)])  # cimba: noqa(CHK001)
    seen[id(fn)] = len(seen)  # cimba: noqa(CHK001) — ordinal is the value
    code = getattr(fn, "__code__", None)
    if code is None:
        mod = getattr(fn, "__module__", None)
        qn = getattr(fn, "__qualname__", None) or getattr(
            fn, "__name__", None
        )
        if qn is None:
            raise UnstableStoreKey(
                f"callable {fn!r} has no code object and no qualified "
                "name — it cannot be fingerprinted by value"
            )
        return ("c", mod, qn)
    cells: tuple = ()
    if fn.__closure__:
        cells = tuple(
            _stable_obj(c.cell_contents, seen) for c in fn.__closure__
        )
    defaults = (
        None if fn.__defaults__ is None
        else _stable_obj(tuple(fn.__defaults__), seen)
    )
    return (
        "fn", fn.__module__, fn.__qualname__, _stable_code(code, seen),
        cells, defaults,
    )


# cimba-check: content-path
def _stable_obj(v, seen: dict) -> tuple:
    """A deterministic, process-independent digestable view of ``v``.
    Raises :class:`UnstableStoreKey` for anything whose repr would
    embed a memory address — a weak component would let two different
    models share a store slot, which is the one failure mode the store
    must never have."""
    import numpy as np

    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return ("p", repr(v))
    if isinstance(v, np.ndarray):
        return ("nd", str(v.dtype), v.shape, v.tobytes())
    if isinstance(v, np.generic):
        return ("ns", str(v.dtype), v.tobytes())
    if isinstance(v, np.dtype):
        return ("dt", str(v))
    if isinstance(v, (list, tuple)):
        return (
            "seq", type(v).__name__,
            tuple(_stable_obj(x, seen) for x in v),
        )
    if isinstance(v, (set, frozenset)):
        return (
            "set", tuple(sorted(_stable_obj(x, seen) for x in v)),
        )
    if isinstance(v, dict):
        items = sorted(
            ((_stable_obj(k, seen), _stable_obj(x, seen))
             for k, x in v.items())
        )
        return ("map", tuple(items))
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return (
            "dc", type(v).__module__, type(v).__qualname__,
            tuple(
                (f.name, _stable_obj(getattr(v, f.name), seen))
                for f in dataclasses.fields(v)
            ),
        )
    try:
        import jax
    except ImportError:
        jax = None  # jax-less tooling digests everything else the same way
    if jax is not None and isinstance(v, jax.Array):
        try:
            a = np.asarray(v)
        except Exception as e:
            # a donated/deleted buffer or leaked tracer: structured,
            # degradable failure — callers catch UnstableStoreKey and
            # record a downgrade (the invalidation-ladder contract),
            # never a raw RuntimeError out of the serving layer
            raise UnstableStoreKey(
                f"jax array in spec structure failed host conversion "
                f"({type(e).__name__}: {e}) — it has no stable value "
                "digest"
            ) from e
        return ("jx", str(a.dtype), a.shape, a.tobytes())
    if callable(v):
        return _stable_callable(v, seen)
    raise UnstableStoreKey(
        f"{type(v).__module__}.{type(v).__qualname__} has no "
        "deterministic value digest — the spec closing over it cannot "
        "be stored persistently"
    )


# cimba-check: content-path
def stable_spec_fingerprint(spec) -> tuple:
    """The VALUE-based structural identity of a ModelSpec — the
    persistent twin of ``cache.spec_fingerprint`` with every ``id()``
    replaced by a content digest, so a spec reconstructed in a fresh
    process (or a ``dataclasses.replace`` twin) maps to the same store
    entry.  Raises :class:`UnstableStoreKey` when any function-valued
    structure resists value fingerprinting."""
    import numpy as np

    cached = getattr(spec, "_cimba_stable_fingerprint", None)
    if cached is not None:
        return cached

    seen: dict = {}  # id -> first-visit ordinal (back-references)
    fp = (
        spec.name,
        tuple(_stable_callable(b, seen) for b in spec.blocks),
        np.asarray(spec.proc_entry).tobytes(),
        np.asarray(spec.proc_prio).tobytes(),
        np.asarray(spec.proc_start).tobytes(),
        tuple(spec.proc_names),
        tuple(_stable_obj(q, seen) for q in spec.queues),
        tuple(_stable_obj(r, seen) for r in spec.resources),
        tuple(_stable_obj(p, seen) for p in spec.pools),
        tuple(_stable_obj(b, seen) for b in spec.buffers),
        tuple(_stable_obj(q, seen) for q in spec.pqueues),
        tuple(_stable_obj(c, seen) for c in spec.conditions),
        spec.n_guards, spec.guard_cap, spec.event_cap,
        spec.queue_cap_max, spec.pqueue_cap_max,
        spec.n_flocals, spec.n_ilocals, spec.max_chain,
        None if spec.user_init is None
        else _stable_callable(spec.user_init, seen),
        tuple(_stable_callable(h, seen) for h in spec.user_handlers),
        tuple(spec.boundary_pcs),
    )
    if spec.boundary_round_steps is not None:
        fp = fp + (spec.boundary_round_steps,)
    try:
        object.__setattr__(spec, "_cimba_stable_fingerprint", fp)
    except (AttributeError, TypeError):
        pass  # slotted/frozen spec: recompute per call
    return fp


# cimba-check: content-path
def callable_digest(fn) -> str:
    """The stable content digest of one callable (sha256 hex) — how
    fold artifacts are keyed to their ``summary_path`` across process
    boundaries.  Raises :class:`UnstableStoreKey` when the callable
    resists value fingerprinting."""
    return hashlib.sha256(
        repr(_stable_callable(fn, {})).encode("utf-8")
    ).hexdigest()


# cimba-check: content-path
def _mesh_descriptor(mesh) -> Optional[tuple]:
    if mesh is None:
        return None
    kinds = sorted(
        {
            f"{d.platform}:{getattr(d, 'device_kind', '?')}"
            for d in mesh.devices.flat
        }
    )
    return (
        "mesh", tuple(mesh.axis_names), tuple(mesh.devices.shape),
        tuple(kinds),
    )


# cimba-check: content-path
def store_key(spec, with_metrics: bool, *, mesh, chunk_steps: int) -> str:
    """The persistent program key: sha256 hex over the stable spec
    fingerprint plus every trace-time setting a compiled program bakes
    in — the value-based image of ``cache.program_key`` (same field
    set, trace-time globals resolved NOW), so "same store key" implies
    "same program" exactly as it does in memory.  Raises
    :class:`UnstableStoreKey` when the spec has no value identity."""
    from cimba_tpu import config as _config
    from cimba_tpu.obs import trace as _trace

    key = (
        FORMAT,
        stable_spec_fingerprint(spec),
        _config.active_profile(),
        bool(with_metrics),
        _trace.enabled(),
        _mesh_descriptor(mesh),
        int(chunk_steps),
    )
    return hashlib.sha256(repr(key).encode("utf-8")).hexdigest()


def _environment() -> dict:
    """The strict-match environment guard recorded per entry: an
    executable is an opaque backend artifact, so ANY drift here
    invalidates rather than risking a misload."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "?"),
        "n_devices": jax.device_count(),
        "x64": bool(jax.config.jax_enable_x64),
    }


# cimba-check: content-path
def _args_sig_digest(args) -> str:
    """The shape signature of one compiled specialization: pytree
    structure plus per-leaf (dtype, shape, weak_type).  The hydration
    shim dispatches to a stored executable only on an EXACT match —
    anything else falls back to jit, never to a near-miss program."""
    import jax
    from jax.api_util import shaped_abstractify

    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = (
        str(treedef),
        tuple(
            (str(a.dtype), tuple(a.shape), bool(a.weak_type))
            for a in map(shaped_abstractify, leaves)
        ),
    )
    return hashlib.sha256(repr(sig).encode("utf-8")).hexdigest()


# -- mechanism (a): jax's persistent compilation cache ------------------------

def maybe_enable_persistent_cache() -> str:
    """Turn on jax's persistent compilation cache (mechanism (a)) and
    return its directory.  The one place that decides where it lives:
    a set ``JAX_COMPILATION_CACHE_DIR`` is jax's own setting and is left
    alone; otherwise the cache goes to :data:`CACHE_DIR`.  Entry points
    call this once at start."""
    import jax

    cdir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cdir:
        cdir = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cdir)
    return cdir


# -- the store ----------------------------------------------------------------

_STORES: dict = {}


def get_store(root: str) -> "ProgramStore":
    """The process-wide :class:`ProgramStore` for ``root`` — one
    instance per (absolute) root, so hit/miss counters aggregate across
    every cache and ``serve.warm`` call in the process, which is what
    ``Service.stats()`` reports."""
    key = os.path.abspath(root)
    st = _STORES.get(key)
    if st is None:
        st = _STORES[key] = ProgramStore(key)
    return st


def default_store() -> Optional["ProgramStore"]:
    """The process-wide store named by ``CIMBA_PROGRAM_STORE`` (None
    when unset)."""
    from cimba_tpu import config as _config

    root = _config.env_raw(STORE_ENV).strip()
    if not root:
        return None
    return get_store(root)


class _LazyArtifact:
    """One checksum-verified artifact blob whose
    ``deserialize_and_load`` is deferred until first use (and memoized).
    Hydration reads+verifies every blob eagerly — corruption is still
    detected at hydrate time — but a lookup that only ever dispatches
    one wave shape never pays deserialization for the others.
    ``serve.warm(manifest=...)`` resolves eagerly on the calling
    thread (deserialization measured ~4.6x slower on the dispatcher
    thread, BENCH_NOTES round 8)."""

    __slots__ = ("_blob", "_devices", "_loaded", "file")

    def __init__(self, blob: bytes, file: str, devices):
        self._blob = blob
        self._devices = devices
        self._loaded = None
        self.file = file

    def resolve(self):
        if self._loaded is None:
            import jax
            from jax.experimental import serialize_executable as _se

            by_id = {d.id: d for d in jax.devices()}
            self._loaded = _se.deserialize_and_load(
                *pickle.loads(self._blob),
                execution_devices=[by_id[i] for i in self._devices],
            )
            self._blob = None
        return self._loaded


def _execution_device_ids(compiled) -> list:
    """The ids of the devices ``compiled`` runs on, in the order of its
    device assignment: the mesh's for a sharded program, the one device
    of its inputs otherwise."""
    import jax

    sh = jax.tree_util.tree_leaves(compiled.input_shardings)[0]
    mesh = getattr(sh, "mesh", None)
    devs = mesh.devices.flat if mesh is not None else sh.device_set
    return [int(d.id) for d in devs]


class HydratedPrograms(tuple):
    """What :meth:`ProgramStore.hydrate` returns: ``(init, chunk)``
    shims plus the loaded fold executables keyed by
    ``(summary_path digest, shape digest)`` — indexable like the old
    2-tuple (``hyd[0]``/``hyd[1]``) for ``get_programs``."""

    __slots__ = ()

    def __new__(cls, init, chunk, folds):
        return tuple.__new__(cls, (init, chunk, folds))

    @property
    def init(self):
        return self[0]

    @property
    def chunk(self):
        return self[1]

    @property
    def folds(self) -> dict:
        return self[2]


def hydrated_fold(jit_fn, table: dict, store: "ProgramStore"):
    """Wrap a jitted fold program with a store-artifact dispatch table
    (the ``serve.warm(manifest=...)`` fold path)."""
    return _HydratedProgram(jit_fn, table, store, "fold")


class _HydratedProgram:
    """A callable standing where a jitted ``init``/``chunk`` program
    stands: stored shapes dispatch straight to the deserialized
    executable (zero compiles); unseen shapes — and abstract tracers,
    e.g. the preflight's ``eval_shape`` — fall back to the wrapped
    ``jax.jit`` program, which mechanism (a) softens to a disk hit."""

    __slots__ = ("_jit", "_table", "_store", "_role", "_fallback_seen",
                 "_footprints")

    def __init__(self, jit_fn, table: dict, store: "ProgramStore",
                 role: str, footprints: Optional[dict] = None):
        self._jit = jit_fn
        self._table = table
        self._store = store
        self._role = role
        self._fallback_seen: set = set()
        # per-shape measured device footprint (bytes), from the
        # manifest's ``footprint_bytes`` — the memory-aware admission
        # input that needs no re-lowering (docs/24_device_scheduler.md)
        self._footprints: dict = footprints or {}

    def __call__(self, *args):
        import jax

        leaves = jax.tree_util.tree_leaves(args)
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return self._jit(*args)
        sig = _args_sig_digest(args)
        art = self._table.get(sig)
        if art is None:
            if sig not in self._fallback_seen:
                self._fallback_seen.add(sig)
                self._store._count("fallback_shapes")
            return self._jit(*args)
        try:
            fn = art.resolve()
        except Exception as e:
            # a blob that checksummed but won't deserialize: reject
            # loudly, drop it, and recompile — never serve a maybe
            self._table.pop(sig, None)
            warnings.warn(
                f"program store artifact {art.file} failed to "
                f"deserialize ({type(e).__name__}: {e}); recompiling",
                StoreInvalidationWarning,
            )
            self._store._count("corrupt")
            return self._jit(*args)
        self._store._count("artifact_dispatches")
        return fn(*args)

    def resolve_all(self) -> None:
        """Eagerly deserialize every stored shape (the
        ``serve.warm(manifest=...)`` main-thread path)."""
        for art in self._table.values():
            art.resolve()

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def footprint_for(self, *args) -> Optional[int]:
        """The store-measured device footprint (bytes) of this
        program at the given arg shapes, or None when the manifest
        carries none for that shape (``cache.wave_footprint_bytes``
        then falls through to its next rung)."""
        return self._footprints.get(_args_sig_digest(args))


class ProgramStore:
    """A directory of serialized compiled programs keyed by
    :func:`store_key`, with a JSON manifest and strict invalidation.

    Layout::

        <root>/manifest.json     entries: key -> {env, programs, meta}
                                 tuned:   key -> {env, schedule, meta}
        <root>/artifacts/*.bin   pickled (payload, in_tree, out_tree)

    The ``tuned`` section is the schedule-autotuner registry
    (docs/21_autotune.md, written/read via
    :mod:`cimba_tpu.tune.registry`): searched dispatch-schedule
    winners keyed by (value-based spec fingerprint, backend, device
    kind, workload bucket), invalidated by environment drift exactly
    like artifacts.

    Writes are crash-atomic (mkstemp + fsync + ``os.replace`` — the
    checkpoint discipline): a killed save leaves the previous manifest
    intact, and a torn artifact fails its checksum on load instead of
    deserializing garbage.  Manifest UPDATES additionally serialize
    across processes through an ``O_EXCL`` lockfile
    (:meth:`_manifest_lock`): two processes warming the same store
    merge their entries instead of losing one side's, and a stale lock
    (dead writer) is broken with a loud
    :class:`StaleStoreLockWarning`."""

    # cimba-check: must-hold(_lock) _stats

    def __init__(self, root: str, *, lock_timeout_s: float = 60.0,
                 lock_stale_s: float = 30.0):
        self.root = os.path.abspath(root)
        self._lock_timeout_s = float(lock_timeout_s)
        self._lock_stale_s = float(lock_stale_s)
        os.makedirs(os.path.join(self.root, ARTIFACT_DIR), exist_ok=True)
        # RLock: _read_manifest counts corrupt/invalidated manifests
        # via _count while hydrate/save/covered already hold the lock
        self._lock = threading.RLock()
        self._stats = {
            "saves": 0,
            "hits": 0,
            "misses": 0,
            "invalidated": 0,
            "corrupt": 0,
            "downgrades": 0,
            "fallback_shapes": 0,
            "artifact_dispatches": 0,
            # the tuned-schedule registry (docs/21_autotune.md): the
            # manifest's "tuned" section rides the same lock + atomic
            # write + env invalidation ladder as the artifacts
            "tuned_saves": 0,
            "tuned_hits": 0,
            "tuned_misses": 0,
            "tuned_invalidated": 0,
        }

    # -- observability -------------------------------------------------------

    def _count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._stats[name] += n

    def stats(self) -> dict:
        with self._lock:
            out = dict(self._stats)
        out["root"] = self.root
        # boolean health flags for /healthz (docs/17_telemetry.md): any
        # of these true means the store degraded at least once this
        # process — serving still works (every rung degrades to
        # recompile), but an operator should look before trusting
        # cold-start numbers
        out["flags"] = {
            "corruption": out["corrupt"] > 0,
            "invalidated": out["invalidated"] > 0,
            "downgraded": out["downgrades"] > 0,
        }
        return out

    # -- manifest ------------------------------------------------------------

    def _manifest_path(self) -> str:
        return os.path.join(self.root, MANIFEST)

    def _manifest_lock_path(self) -> str:
        return os.path.join(self.root, MANIFEST_LOCK)

    @staticmethod
    def _pid_alive(pid: int) -> bool:
        try:
            os.kill(int(pid), 0)
        except (ProcessLookupError, ValueError):
            return False
        except PermissionError:
            return True  # alive, just not ours
        return True

    @contextlib.contextmanager
    def _manifest_lock(self):
        """Inter-PROCESS mutual exclusion around manifest
        read-merge-write (the in-process ``_lock`` covers threads; this
        covers two processes warming the same store — two
        ``warm_store`` runs, or a fleet of slices saving autotuned
        programs — whose unlocked read-modify-write would silently lose
        one side's entries).

        Mechanics: an ``O_CREAT | O_EXCL`` lockfile beside the manifest
        holding ``{pid, host, t}``; losers poll.  A lock held by a
        provably-DEAD pid on this host — or older than
        ``lock_stale_s`` when the holder's liveness is unknowable
        (foreign host, unreadable body) — is broken by atomic rename
        with a LOUD :class:`StaleStoreLockWarning` naming the holder
        (the atomic manifest write guarantees what's on disk is the
        previous consistent generation).  A provably-ALIVE same-host
        holder is never broken, however old: waiting past
        ``lock_timeout_s`` raises ``TimeoutError`` — better a loud
        failed save than two writers in the manifest."""
        path = self._manifest_lock_path()
        me = {
            "pid": os.getpid(),
            "host": socket.gethostname(),
            "t": time.time(),
        }
        deadline = time.monotonic() + self._lock_timeout_s
        while True:
            try:
                fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            except FileExistsError:
                try:
                    age = time.time() - os.stat(path).st_mtime
                except FileNotFoundError:
                    continue     # released between open and stat: retry
                except OSError:
                    time.sleep(0.02)
                    continue
                holder: dict = {}
                try:
                    with open(path, "r") as f:
                        holder = json.load(f)
                except (OSError, json.JSONDecodeError):
                    # empty/torn body: either a writer between O_EXCL
                    # and write (age ~0 — wait) or one that CRASHED in
                    # that window (age grows).  Liveness is unknowable,
                    # so fall through with an empty holder and let the
                    # age/timeout ladder decide — a blind retry here
                    # would spin forever on a permanently-empty lock.
                    holder = {}
                same_host = holder.get("host") == me["host"]
                has_pid = holder.get("pid") is not None
                dead = (
                    same_host and has_pid
                    and not self._pid_alive(holder["pid"])
                )
                # a holder whose liveness is PROVABLE (same host, pid
                # answers kill-0) is never age-broken: a slow-but-alive
                # writer past lock_stale_s must hit the Timeout path
                # below, not have its lock stolen mid-write (the
                # double-writer corruption this lock exists to close).
                # Age-breaking applies only where liveness is
                # unknowable: foreign hosts and unreadable pids.
                alive_here = same_host and has_pid and not dead
                if dead or (
                    age > self._lock_stale_s and not alive_here
                ):
                    # break by ATOMIC rename, not unlink: two waiters
                    # may both judge the same lock stale, and a bare
                    # unlink from the loser could delete the winner's
                    # freshly-acquired lock — exactly the double-writer
                    # hole this lockfile exists to close.  rename
                    # succeeds for exactly one breaker; everyone else
                    # gets FileNotFoundError and just re-contends.
                    broken = f"{path}.broken.{os.getpid()}"
                    try:
                        os.rename(path, broken)
                    except OSError:
                        continue  # someone else broke/released it first
                    warnings.warn(
                        f"broke stale program-store manifest lock "
                        f"{path} (holder pid={holder.get('pid')} "
                        f"host={holder.get('host')!r}, age {age:.1f}s, "
                        f"{'dead' if dead else 'past staleness window'})"
                        " — a writer died mid-update; its save was lost",
                        StaleStoreLockWarning,
                    )
                    try:
                        os.unlink(broken)
                    except OSError:
                        pass
                    continue
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"program-store manifest lock {path} held by "
                        f"pid={holder.get('pid')} "
                        f"host={holder.get('host')!r} for {age:.1f}s — "
                        f"gave up after {self._lock_timeout_s:.0f}s"
                    )
                time.sleep(0.02)
                continue
            try:
                os.write(fd, json.dumps(me).encode("utf-8"))
            finally:
                os.close(fd)
            break
        try:
            yield
        finally:
            # release only what is still OURS: if another process
            # judged us stale and stole the lock (we ran past
            # lock_stale_s), the file now holds THEIR identity and a
            # blind unlink would hand the manifest to a third writer
            try:
                with open(path, "r") as f:
                    cur = json.load(f)
                if (
                    cur.get("pid") == me["pid"]
                    and cur.get("host") == me["host"]
                ):
                    os.unlink(path)
            except (OSError, json.JSONDecodeError):
                pass  # already broken/released — nothing of ours left

    def _update_manifest(self, mutate) -> dict:
        """One atomic cross-process read-merge-write of the manifest:
        ``mutate(manifest)`` runs with the inter-process lockfile held
        (which serializes THREADS too — an O_EXCL create fails the same
        way for a sibling thread as for a foreign process), then the
        result lands via the crash-atomic write.  Deliberately NOT
        under ``self._lock``: the file-lock wait can last seconds
        (another process saving), and holding the thread lock across
        it would stall ``stats()`` — and with it the telemetry scrape
        behind ``/healthz`` — long enough to fake a dead slice.
        Returns the written manifest."""
        with self._manifest_lock():
            manifest = self._read_manifest()
            mutate(manifest)
            self._write_manifest(manifest)
        return manifest

    def _read_manifest(self) -> dict:
        try:
            with open(self._manifest_path(), "r") as f:
                m = json.load(f)
        except FileNotFoundError:
            return {"format": FORMAT, "entries": {}}
        except (json.JSONDecodeError, OSError) as e:
            warnings.warn(
                f"program store manifest at {self._manifest_path()} is "
                f"unreadable ({e!r}); treating the store as empty",
                StoreInvalidationWarning,
            )
            self._count("corrupt")
            return {"format": FORMAT, "entries": {}}
        if m.get("format") != FORMAT:
            warnings.warn(
                f"program store manifest format {m.get('format')!r} != "
                f"{FORMAT} — the whole store is invalidated (rebuild "
                "with tools/warm_store.py)",
                StoreInvalidationWarning,
            )
            self._count("invalidated")
            return {"format": FORMAT, "entries": {}}
        return m

    def _atomic_write(self, path: str, data: bytes) -> None:
        import tempfile

        d = os.path.dirname(path)
        fd, tmp = tempfile.mkstemp(
            dir=d, prefix=os.path.basename(path) + ".", suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def _write_manifest(self, manifest: dict) -> None:
        self._atomic_write(
            self._manifest_path(),
            json.dumps(manifest, indent=1, sort_keys=True).encode(),
        )

    # -- save ----------------------------------------------------------------

    def save_programs(
        self,
        spec,
        params: Any,
        n_replications: int,
        *,
        wave_sizes,
        mesh=None,
        chunk_steps: int = 1024,
        with_metrics: Optional[bool] = None,
        horizon_modes=("none", "column"),
        summary_paths=None,
        seed: int = 0,
    ) -> dict:
        """AOT-compile and serialize the ``(init, chunk)`` pair for
        every ``wave_sizes`` × ``horizon_modes`` point of this (spec,
        settings) program key, exactly as the stream runner / service
        would dispatch them (``horizon_modes``: ``"none"`` = the
        run-to-completion pytree without the ``t_stop`` leaf, the
        stream default; ``"column"`` = the per-lane horizon column the
        serving layer's padded / finite-horizon waves carry).
        ``summary_paths`` (default: the runner's
        ``default_summary_path``) additionally compiles + serializes
        the wave-FOLD program per path × shape, keyed by the path's
        :func:`callable_digest` — so ``serve.warm(manifest=...)``
        reaches first-request readiness with zero executions; pass
        ``()`` to skip folds.  Returns a report dict with per-program
        compile seconds and artifact bytes.  A program whose
        executable cannot be serialized (or a fold whose path is
        unstable / fails to trace on this model) records a
        **downgrade** (mechanism (a) still covers it) instead of
        raising; only an unstable spec fingerprint raises
        (:class:`UnstableStoreKey` — there is no key to save under)."""
        import jax
        from jax.experimental import serialize_executable as _se

        from cimba_tpu.obs import metrics as _metrics
        from cimba_tpu.runner import experiment as ex

        if with_metrics is None:
            with_metrics = _metrics.enabled()
        key = store_key(
            spec, with_metrics, mesh=mesh,
            chunk_steps=chunk_steps,
        )
        init_j = ex._init_program(spec, mesh)
        chunk_j = ex._chunk_program(spec, None, chunk_steps, mesh)

        programs = []
        downgrades = []
        report = {
            "key": key, "model": spec.name, "programs": [],
            "downgrades": downgrades,
        }

        def psize(fn, fn_args, lowered, lower_s):
            """Program-size record for one saved program
            (docs/25_compile_wall.md): the trace-only obs probe plus
            the HLO text bytes off the ALREADY-lowered module (no
            re-lower).  Sits next to ``footprint_bytes`` in the
            manifest — device memory and program text are the two
            sizes that gate a deploy.  Best-effort: a spec the probe
            can't re-trace degrades to None, never a failed save."""
            try:
                from cimba_tpu.obs import program_size as _psz

                d = _psz.measure(fn, *fn_args, lower=False).to_dict()
                d["lower_s"] = round(lower_s, 4)
                try:
                    d["hlo_bytes"] = len(lowered.as_text().encode())
                except Exception:
                    d["hlo_bytes"] = None
                return d
            except Exception:
                return None

        def emit(role, args_sig_args, compiled, compile_s, path=None,
                 size=None):
            sig = _args_sig_digest(args_sig_args)
            try:
                payload = _se.serialize(compiled)
                blob = pickle.dumps(payload, protocol=4)
            except Exception as e:
                self._count("downgrades")
                downgrades.append(
                    {"role": role, "shape": sig,
                     "reason": f"{type(e).__name__}: {e}"}
                )
                return
            frag = f"{path[:8]}-" if path else ""
            fname = f"{key[:16]}-{role}-{frag}{sig[:16]}.bin"
            self._atomic_write(
                os.path.join(self.root, ARTIFACT_DIR, fname), blob
            )
            rec = {
                "role": role,
                "shape": sig,
                "file": fname,
                "sha256": hashlib.sha256(blob).hexdigest(),
                "bytes": len(blob),
                "compile_s": compile_s,
                "devices": _execution_device_ids(compiled),
            }
            # measured device footprint, where the backend implements
            # memory_analysis() — hydrated admission reads it instead
            # of re-lowering (docs/24_device_scheduler.md); absent on
            # backends without the API (estimate rung covers them)
            try:
                from cimba_tpu.serve import cache as _pcache

                fp = _pcache._memory_analysis_bytes(
                    compiled.memory_analysis()
                )
            except Exception:
                fp = None
            if fp is not None:
                rec["footprint_bytes"] = int(fp)
            if size is not None:
                rec["program_size"] = size
            if path is not None:
                rec["path"] = path
            programs.append(rec)
            report["programs"].append(dict(rec))

        if summary_paths is None:
            summary_paths = (ex.default_summary_path,)
        folds = []
        for sp in summary_paths:
            try:
                pdig = callable_digest(sp)
            except UnstableStoreKey as e:
                self._count("downgrades")
                downgrades.append(
                    {"role": "fold", "shape": "?",
                     "reason": f"unstable summary_path: {e}"}
                )
                continue
            folds.append((sp, pdig))

        for n in wave_sizes:
            n = int(n)
            reps = jax.numpy.arange(n)
            seeds = ex._seed_column(seed, n)
            pw = ex._slice_params(params, int(n_replications), 0, n)
            for hz in horizon_modes:
                t_stops = (
                    None if hz == "none" else ex._horizon_column(None, n)
                )
                args = (reps, seeds, t_stops, pw)
                t0 = time.monotonic()
                init_low = init_j.lower(*args)
                t_init_low = time.monotonic() - t0
                init_c = init_low.compile()
                t_init = time.monotonic() - t0
                emit("init", args, init_c, t_init,
                     size=psize(init_j, args, init_low, t_init_low))
                sims_aval = jax.eval_shape(init_j, *args)
                t0 = time.monotonic()
                chunk_low = chunk_j.lower(sims_aval)
                t_chunk_low = time.monotonic() - t0
                chunk_c = chunk_low.compile()
                t_chunk = time.monotonic() - t0
                emit("chunk", (sims_aval,), chunk_c, t_chunk,
                     size=psize(chunk_j, (sims_aval,), chunk_low,
                                t_chunk_low))
                for sp, pdig in folds:
                    from cimba_tpu.serve import cache as _pcache

                    acc = _pcache.stream_acc(spec, with_metrics)
                    fold_j = _pcache._fold_program(with_metrics, sp)
                    try:
                        t0 = time.monotonic()
                        fold_low = fold_j.lower(acc, sims_aval)
                        t_fold_low = time.monotonic() - t0
                        fold_c = fold_low.compile()
                        t_fold = time.monotonic() - t0
                    except Exception as e:
                        # a path that doesn't exist on this model's Sim
                        # (or doesn't trace) — record, don't crash
                        self._count("downgrades")
                        downgrades.append(
                            {"role": "fold", "shape": "?",
                             "reason": f"{type(e).__name__}: {e}"}
                        )
                        continue
                    emit(
                        "fold", (acc, sims_aval), fold_c, t_fold,
                        path=pdig,
                        size=psize(fold_j, (acc, sims_aval), fold_low,
                                   t_fold_low),
                    )

        # the merge key carries the summary-path digest too: fold
        # records for different paths share arg shapes, and a
        # shape+role key would silently keep only the last path's
        def mkey(p):
            return (p["role"], p["shape"], p.get("path"))

        def merge_entry(manifest):
            # runs under BOTH the thread lock and the inter-process
            # manifest lockfile: a second process saving a different
            # program key concurrently merges instead of clobbering
            # (the two-subprocess race test in tests/test_store.py)
            entry = manifest["entries"].get(key, {})
            merged = {mkey(p): p for p in entry.get("programs", [])}
            for p in programs:
                merged[mkey(p)] = p
            manifest["entries"][key] = {
                "model": spec.name,
                "env": _environment(),
                "created": time.time(),
                "meta": {
                    "chunk_steps": int(chunk_steps),
                    "with_metrics": bool(with_metrics),
                    "wave_sizes": [int(n) for n in wave_sizes],
                    "horizon_modes": list(horizon_modes),
                },
                "programs": sorted(
                    merged.values(),
                    key=lambda p: (p["role"], p["shape"],
                                   p.get("path") or ""),
                ),
                "downgrades": downgrades,
            }

        self._update_manifest(merge_entry)
        with self._lock:
            self._stats["saves"] += 1
        return report

    # -- hydrate -------------------------------------------------------------

    def hydrate(
        self,
        spec,
        *,
        mesh=None,
        chunk_steps: int = 1024,
        with_metrics: bool = False,
    ):
        """Second-chance lookup for ``cache.get_programs``: return a
        hydrated ``(init, chunk)`` pair for this program key, or None
        on any miss.  The invalidation ladder — key absent, jax/jaxlib
        version drift, backend/platform drift, checksum mismatch,
        truncated/corrupt artifact, deserialization failure — each
        step degrades to a counted miss (with a
        :class:`StoreInvalidationWarning` where a rejected body
        exists), NEVER to a mismatched program: one corrupt artifact
        rejects the whole entry so init and chunk can never come from
        different generations.  Artifact BYTES are read and
        checksum-verified here; deserialization is lazy per dispatched
        shape (see :class:`_LazyArtifact`)."""
        from cimba_tpu.runner import experiment as ex

        try:
            key = store_key(
                spec, with_metrics, mesh=mesh,
                chunk_steps=chunk_steps,
            )
        except UnstableStoreKey:
            self._count("misses")
            return None
        with self._lock:
            manifest = self._read_manifest()
        entry = manifest["entries"].get(key)
        if entry is None:
            self._count("misses")
            return None
        env = _environment()
        if entry.get("env") != env:
            drift = {
                k: (entry.get("env", {}).get(k), env[k])
                for k in env
                if entry.get("env", {}).get(k) != env[k]
            }
            warnings.warn(
                f"program store entry {key[:16]} was built in a "
                f"different environment ({drift}); recompiling instead "
                "of loading a foreign executable",
                StoreInvalidationWarning,
            )
            self._count("invalidated")
            return None
        tables: dict = {"init": {}, "chunk": {}}
        footprints: dict = {"init": {}, "chunk": {}}
        folds: dict = {}
        for rec in entry.get("programs", []):
            path = os.path.join(self.root, ARTIFACT_DIR, rec["file"])
            try:
                with open(path, "rb") as f:
                    blob = f.read()
                if hashlib.sha256(blob).hexdigest() != rec["sha256"]:
                    raise ValueError("artifact checksum mismatch")
                # checksum verified NOW; deserialization is deferred to
                # first dispatch of the shape (or warm's resolve_all)
                loaded = _LazyArtifact(blob, rec["file"], rec["devices"])
            except Exception as e:
                warnings.warn(
                    f"program store artifact {rec['file']} is "
                    f"corrupt/unloadable ({type(e).__name__}: {e}); "
                    "rejecting the whole entry and recompiling",
                    StoreInvalidationWarning,
                )
                self._count("corrupt")
                return None
            if rec["role"] == "fold":
                folds[(rec.get("path"), rec["shape"])] = loaded
            else:
                tables.setdefault(rec["role"], {})[rec["shape"]] = loaded
                if rec.get("footprint_bytes") is not None:
                    footprints.setdefault(rec["role"], {})[
                        rec["shape"]
                    ] = int(rec["footprint_bytes"])
        if not tables["init"] and not tables["chunk"]:
            self._count("misses")
            return None
        self._count("hits")
        init_j = ex._init_program(spec, mesh)
        chunk_j = ex._chunk_program(spec, None, chunk_steps, mesh)
        return HydratedPrograms(
            _HydratedProgram(init_j, tables["init"], self, "init",
                             footprints["init"]),
            _HydratedProgram(chunk_j, tables["chunk"], self, "chunk",
                             footprints["chunk"]),
            folds,
        )

    def covered(
        self, spec, *, mesh=None, chunk_steps: int = 1024,
        with_metrics: bool = False,
    ) -> bool:
        """True when a valid-looking manifest entry exists for this
        program key (environment checked; artifact bytes are only
        verified at :meth:`hydrate` time)."""
        try:
            key = store_key(
                spec, with_metrics, mesh=mesh,
                chunk_steps=chunk_steps,
            )
        except UnstableStoreKey:
            return False
        with self._lock:
            entry = self._read_manifest()["entries"].get(key)
        return bool(entry) and entry.get("env") == _environment()
