"""The shared compiled-program cache behind streaming and serving.

``run_experiment_stream`` historically built its program table as an
unbounded per-call dict (the nested ``get_programs``): correct, but a
long-lived process sweeping many specs — exactly the serving shape —
accumulates one ``(init, chunk)`` program pair per (spec, settings)
point forever.  This module factors that table out into a **bounded,
thread-safe LRU** (:class:`ProgramCache`) plus the key builders both
:func:`cimba_tpu.runner.experiment.run_experiment_stream` and
:mod:`cimba_tpu.serve.service` share, so

* the stream runner's default cache is bounded (env
  ``CIMBA_PROGRAM_CACHE_CAP``, default 64 entries — generous: one entry
  per distinct (spec structure, profile, arm, mesh, chunk) point, not
  per wave shape; jit re-specializes per shape internally);
* the serving layer's *compatibility class* — which requests may share
  a wave — is definitionally a prefix of the key that selects a
  compiled program (:func:`program_class_key` vs :func:`program_key`),
  so "compatible" can never drift from "same program".  Seed, horizon,
  params values, and R are per-lane DATA columns, not program
  constants, so they appear in NEITHER key — the heterogeneous-wave
  contract of docs/14_wave_packing.md;
* hit/miss/eviction counters make cache health observable
  (:meth:`ProgramCache.stats`, surfaced by ``Service.stats()``).

Entry-pinning invariant: every key that embeds object identities (the
structural fingerprint's block/handler/predicate function ids) stores
the spec object (or a tuple containing it) as part of its value, so a
cached id can never be recycled by the allocator while its entry lives.
Eviction drops the entry *and* its pin together — a later call with a
recycled id cannot hit a stale entry, because the stale entry is gone.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, MutableMapping, Optional

#: environment knob for the default capacity; the default value (64 —
#: generous: entries are per (spec, settings) point, not per wave
#: shape) lives in ``config.ENV_KNOBS``, the one registry of knob
#: defaults
CAP_ENV = "CIMBA_PROGRAM_CACHE_CAP"


def default_capacity() -> int:
    from cimba_tpu import config

    cap = int(config.env_raw(CAP_ENV))
    if cap <= 0:
        raise ValueError(
            f"{CAP_ENV}={cap}: the program cache capacity must be "
            "positive (compiled programs are required to run at all)"
        )
    return cap


class ProgramCache(MutableMapping):
    """A bounded, thread-safe LRU mapping for compiled programs.

    Drop-in for the plain dict ``run_experiment_stream(program_cache=)``
    historically took (same mapping protocol), plus:

    * **bounded**: inserting past ``capacity`` evicts the
      least-recently-used entry (compiled programs are pure caches —
      an evicted point merely recompiles on next use);
    * **thread-safe**: every operation holds an internal lock, so a
      service dispatcher and direct-calling client threads can share
      one cache (the recommended deployment — shared warm programs);
    * **observable**: ``hits``/``misses``/``evictions`` counters and
      :meth:`stats` (misses are counted in :meth:`get_or_create`, the
      accessor the runner and service use).
    """

    # cimba-check: must-hold(_lock) _od, hits, misses, evictions

    def __init__(self, capacity: Optional[int] = None, *, store=None):
        self._cap = default_capacity() if capacity is None else int(capacity)
        if self._cap <= 0:
            raise ValueError(f"capacity must be positive, got {self._cap}")
        self._od: OrderedDict = OrderedDict()
        self._lock = threading.RLock()
        self._store = store
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def store(self):
        """The persistent AOT program store behind this cache
        (docs/15_program_store.md): an explicit
        :class:`~cimba_tpu.serve.store.ProgramStore`, or — when the
        constructor got ``store=None`` — whatever
        ``CIMBA_PROGRAM_STORE`` names *right now* (resolved per lookup,
        so a cache built before the env var is irrelevant; pass
        ``store=False`` to opt a cache out entirely)."""
        if self._store is False:
            return None
        if self._store is not None:
            return self._store
        from cimba_tpu.serve import store as _pstore

        return _pstore.default_store()

    # -- mapping protocol ---------------------------------------------------

    def __getitem__(self, key):
        with self._lock:
            val = self._od[key]
            self._od.move_to_end(key)
            return val

    def __setitem__(self, key, val):
        with self._lock:
            self._od[key] = val
            self._od.move_to_end(key)
            while len(self._od) > self._cap:
                self._od.popitem(last=False)
                self.evictions += 1

    def __delitem__(self, key):
        with self._lock:
            del self._od[key]

    def __contains__(self, key):
        with self._lock:
            return key in self._od

    def __iter__(self):
        with self._lock:
            return iter(list(self._od))

    def __len__(self):
        with self._lock:
            return len(self._od)

    # -- the accessor the runner/service use --------------------------------

    def get_or_create(self, key, factory: Callable[[], Any]):
        """Return the cached value for ``key``, building it with
        ``factory()`` on a miss.  The factory runs OUTSIDE the lock (it
        may trace/compile for seconds — other threads must not block on
        it); if another thread won the race, its value wins and the
        duplicate build is discarded (benign: compiled programs are
        pure)."""
        with self._lock:
            if key in self._od:
                self.hits += 1
                self._od.move_to_end(key)
                return self._od[key]
        val = factory()
        with self._lock:
            if key in self._od:  # lost a benign build race
                self.hits += 1
                self._od.move_to_end(key)
                return self._od[key]
            self.misses += 1
            self[key] = val
            return val

    @property
    def capacity(self) -> int:
        return self._cap

    def stats(self) -> dict:
        """An atomic counter snapshot (one lock acquisition for every
        cache-local value — a scrape never sees a hits/misses pair from
        two different moments).  This dict is what the telemetry
        sampler mirrors into the ``cimba_program_cache_*`` metric
        families (docs/17_telemetry.md); ``hit_ratio`` is the
        cache-health headline, in the spirit of compiler-artifact
        caching stacks where hit ratio is a first-class signal."""
        with self._lock:
            lookups = self.hits + self.misses
            out = {
                "capacity": self._cap,
                "size": len(self._od),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "hit_ratio": self.hits / lookups if lookups else 0.0,
            }
        st = self.store
        if st is not None:
            out["store"] = st.stats()
        return out


def _get_or_create(programs: MutableMapping, key, factory):
    """``get_or_create`` against either a :class:`ProgramCache` or the
    plain dict legacy callers still pass."""
    if isinstance(programs, ProgramCache):
        return programs.get_or_create(key, factory)
    if key not in programs:
        programs[key] = factory()
    return programs[key]


def cached(programs: MutableMapping, key, factory):
    """Public get-or-create for SUBSYSTEM-specific compiled programs
    sharing the stream/serve cache (same dict-or-ProgramCache duality
    as the core accessors).  Callers namespace their keys with a
    leading tag — the sweep engine keys its serve-result merge as
    ``("sweep_serve_merge",)`` and its metrics reduce as
    ``("sweep_metrics_merge",)`` — and follow the entry-pinning
    invariant above: any object identity in the key must be kept alive
    by the cached value (a closure referencing the keyed object pins
    it)."""
    return _get_or_create(programs, key, factory)


# -- key builders (the stream runner's cache contract, factored out) --------


# cimba-check: content-path
def spec_fingerprint(spec) -> tuple:
    """STRUCTURAL identity of a ModelSpec for program keys.

    Function-valued structure — blocks, user handlers, ``user_init``,
    condition predicates — keys by object identity (``id``): what the
    tracer closes over IS the function object, so two specs sharing the
    same function objects and the same static data trace the same
    program.  That is exactly the ``dataclasses.replace`` twin shape
    (sweep drivers rebuilding a spec with an unchanged field set —
    ``replace`` copies the function references), which under the old
    ``id(spec)`` key could never share a cache slot.  A model re-built
    from source gets fresh function objects and merely recompiles —
    safe.  The id-recycling hazard is unchanged: every cache entry
    keyed by a fingerprint still pins a spec carrying those function
    objects, so the ids cannot be recycled while the entry lives.
    """
    import dataclasses

    import numpy as np

    cached = getattr(spec, "_cimba_fingerprint", None)
    if cached is not None:
        return cached

    def ref_key(r):
        # component refs are flat dataclasses of scalars/strings plus
        # the occasional callable (condition predicates) or tuple
        out = []
        for f in dataclasses.fields(r):
            v = getattr(r, f.name)
            if callable(v):
                out.append(("fn", id(v)))
            elif isinstance(v, (list, tuple)):
                out.append(tuple(v))
            else:
                out.append(v)
        return tuple(out)

    fp = (
        spec.name,
        tuple(id(b) for b in spec.blocks),
        np.asarray(spec.proc_entry).tobytes(),
        np.asarray(spec.proc_prio).tobytes(),
        np.asarray(spec.proc_start).tobytes(),
        tuple(spec.proc_names),
        tuple(ref_key(q) for q in spec.queues),
        tuple(ref_key(r) for r in spec.resources),
        tuple(ref_key(p) for p in spec.pools),
        tuple(ref_key(b) for b in spec.buffers),
        tuple(ref_key(q) for q in spec.pqueues),
        tuple(ref_key(c) for c in spec.conditions),
        spec.n_guards, spec.guard_cap, spec.event_cap,
        spec.queue_cap_max, spec.pqueue_cap_max,
        spec.n_flocals, spec.n_ilocals, spec.max_chain,
        None if spec.user_init is None else id(spec.user_init),
        tuple(id(h) for h in spec.user_handlers),
        tuple(spec.boundary_pcs),
    )
    if spec.boundary_round_steps is not None:
        fp = fp + (spec.boundary_round_steps,)
    try:
        object.__setattr__(spec, "_cimba_fingerprint", fp)
    except (AttributeError, TypeError):
        pass  # slotted/frozen spec: recompute per call (cheap)
    return fp


# cimba-check: content-path
def program_class_key(spec, with_metrics: bool, *, mesh) -> tuple:
    """The Tier-A **compatibility class**: everything a compiled chunk
    program bakes in EXCEPT ``chunk_steps`` — the spec's structural
    fingerprint, the dtype profile, the ``obs.metrics``/``obs.trace``
    flags, and the mesh — with the trace-time globals resolved NOW so a
    flag flip between calls misses the cache rather than replaying the
    stale arm.

    Seed, horizon (``t_end``), params values, R, and priority are all
    per-lane DATA on this path (``runner.experiment._init_program``'s
    seed/horizon columns), so they join neither this class nor the
    program key: requests differing only in them share one wave of one
    compiled program.  ``chunk_steps`` is excluded because chunking is
    trajectory-invariant (chunked == monolithic bitwise, docs/12): two
    requests with different chunk budgets may share a wave — the wave
    simply runs at its lead's chunk size — but each distinct
    ``chunk_steps`` actually dispatched still compiles its own program
    (:func:`program_key` appends it)."""
    from cimba_tpu import config as _config
    from cimba_tpu.obs import trace as _trace

    return (
        spec_fingerprint(spec),
        _config.active_profile(),
        bool(with_metrics),
        _trace.enabled(),
        mesh,
    )


# cimba-check: content-path
def program_key(spec, with_metrics: bool, *, mesh, chunk_steps: int) -> tuple:
    """The full key of one compiled ``(init, chunk)`` program pair:
    the compatibility class plus the chunk budget the program bakes in.
    Any component silently replaying stale would return a DIFFERENT
    model's trajectories with no error — which is why the trace-time
    globals resolve into the class at key-build time."""
    return program_class_key(spec, with_metrics, mesh=mesh) + (chunk_steps,)


def get_programs(
    programs: MutableMapping,
    spec,
    *,
    mesh,
    chunk_steps: int,
    with_metrics: bool,
    audit: bool = False,
    pack=None,
):
    """The stream runner's ``get_programs``, shared with the service:
    one compiled ``(init, chunk)`` pair per :func:`program_key` point
    (jit re-specializes per wave shape internally, so full waves share
    one compile).  The chunk program is built with ``t_end=None``: the
    horizon is the per-lane ``t_stop`` column the init program plants
    (see ``Sim.t_stop``).  Returns ``(init_j, chunk_j)``.

    A memory miss gets a SECOND-CHANCE lookup in the persistent AOT
    program store (docs/15_program_store.md) before compiling: when the
    cache (or ``CIMBA_PROGRAM_STORE``) names a store holding a valid
    artifact for this program key, the entry hydrates deserialized
    executables instead of tracing and invoking XLA — the
    zero-cold-start path.  Every store failure mode (corrupt artifact,
    version/backend drift, unstable fingerprint, plain bug) degrades to
    the compile below, never to a wrong program.

    ``audit=True`` selects the determinism-audit chunk program (a
    third digest output per chunk, docs/18_audit.md): its key gets a
    distinct suffix — ``audit=False`` keys are byte-identical to the
    historical ones — and store hydration is skipped, because stored
    artifacts are always the unaudited two-output program.

    ``pack`` is accepted only as ``None``: the loop's carry layout
    follows the backend (``core.loop._packed_carry``), and the keyword
    stays for the benchmark's offline compile test, which still passes
    it."""
    from cimba_tpu.serve import store as _pstore

    if pack is not None:
        raise TypeError(
            "get_programs: the carry layout follows the backend; pass "
            "no pack"
        )

    key = program_key(spec, with_metrics, mesh=mesh, chunk_steps=chunk_steps)
    if audit:
        key = key + ("audit",)

    def build():
        import warnings as _warnings

        from cimba_tpu.runner import experiment as ex

        st = getattr(programs, "store", None)
        if st is None and not isinstance(programs, ProgramCache):
            st = _pstore.default_store()
        if audit:
            st = None  # store artifacts are unaudited programs
        if st is not None:
            try:
                hyd = st.hydrate(
                    spec, mesh=mesh, chunk_steps=chunk_steps,
                    with_metrics=with_metrics,
                )
            except Exception as e:  # a store bug must never block serving
                _warnings.warn(
                    f"program store lookup failed ({type(e).__name__}: "
                    f"{e}); compiling instead",
                    _pstore.StoreInvalidationWarning,
                )
                hyd = None
            if hyd is not None:
                return (hyd[0], hyd[1], spec)
        return (
            ex._init_program(spec, mesh),
            ex._chunk_program(
                spec, None, chunk_steps, mesh, audit=audit
            ),
            spec,  # pins the fingerprint's function ids while cached
        )

    return _get_or_create(programs, key, build)[:2]


def get_refill_programs(
    programs: MutableMapping,
    spec,
    *,
    mesh,
    with_metrics: bool,
):
    """The refill plane's compiled pair for one compatibility class:
    ``(refill_j, live_j)`` — the donated lane-splice program and the
    per-lane liveness readback (docs/22_refill.md).  Keyed by the SAME
    compatibility class the chunk program keys by (the Sim pytree a
    splice must reproduce is the class's — profile, metrics/trace
    leaves), so a refill can never splice rows laid
    out for a different program.  No store hydration: both programs
    are small host compiles (the chunk program dominates cold start),
    though jax's persistent compilation cache, where an entry point
    turned it on, still softens them to disk hits."""
    key = ("refill",) + program_class_key(spec, with_metrics, mesh=mesh)

    def build():
        from cimba_tpu.runner import experiment as ex

        return (
            ex._refill_program(spec, mesh),
            ex._live_program(spec, mesh),
            spec,  # pins the fingerprint's function ids while cached
        )

    return _get_or_create(programs, key, build)[:2]


# -- the fusion rung (docs/26_wave_fusion.md) --------------------------------
#
# The class ladder grows a SECOND rung above the exact compatibility
# class: a **fusion class** groups compatible-shape specs
# (core/fuse.fusion_shape_key + a shared Sim-structure signature) so
# cross-spec requests can share ONE compiled superprogram.  The merged
# spec is a real ModelSpec, so its chunk program, store entries and
# program-size probes ride the existing machinery unchanged; only init
# and refill need fused twins (a per-lane spec-id switch).


def fusion_order_key(spec) -> str:
    """Canonical member ordering for fused bundles: members sort by the
    VALUE-based ``stable_spec_fingerprint`` digest (docs/15), so the
    same member SET always builds the same merged table — and hence the
    same compiled superprogram — regardless of arrival order.  A spec
    that resists value fingerprinting falls back to an in-process key
    (name + id): deterministic within the process, which is all the
    ordering needs (programs cache per process)."""
    cached = getattr(spec, "_cimba_fusion_order", None)
    if cached is not None:
        return cached
    import hashlib

    from cimba_tpu.serve import store as _pstore

    try:
        key = "s:" + hashlib.sha256(
            repr(_pstore.stable_spec_fingerprint(spec)).encode("utf-8")
        ).hexdigest()
    except Exception:
        key = f"u:{spec.name}:{id(spec):x}"
    try:
        object.__setattr__(spec, "_cimba_fusion_order", key)
    except (AttributeError, TypeError):
        pass
    return key


def sim_structure_sig(
    programs: MutableMapping,
    spec,
    params,
    n_replications: int,
    with_metrics: bool,
    *,
    mesh,
) -> tuple:
    """The full Sim STRUCTURE signature of one lane of this request —
    treedef plus per-leaf (lane-row shape, dtype) from ``eval_shape``
    over the init program (no device work).  The fusion class embeds it
    so two specs only ever share a fused wave when their lanes' pytrees
    are identical — a structure mismatch lands in a different fusion
    class instead of exploding inside ``lax.switch`` at trace time
    (docs/26_wave_fusion.md).  Memoized beside the programs it guards."""
    key = ("sim_sig",) + program_class_key(
        spec, with_metrics, mesh=mesh,
    ) + (_params_sig(params, n_replications),)

    def build():
        import jax
        import jax.numpy as jnp

        from cimba_tpu.core.loop import init_sim
        from cimba_tpu.runner import experiment as ex

        def one_lane():
            reps = jnp.arange(0, 1)
            seeds = ex._seed_column(0, 1)
            ts = ex._horizon_column(None, 1)
            pw = ex._slice_params(params, n_replications, 0, 1)
            return jax.vmap(
                lambda r, s, t, q: init_sim(spec, s, r, q, t_stop=t)
            )(reps, seeds, ts, pw)

        sim = jax.eval_shape(one_lane)
        leaves, treedef = jax.tree.flatten(sim)
        sig = (
            str(treedef),
            tuple(
                (tuple(l.shape[1:]), str(l.dtype)) for l in leaves
            ),
        )
        return (sig, spec)  # pins the fingerprint's ids while cached

    return _get_or_create(programs, key, build)[0]


def _params_sig(params, n_replications: int) -> tuple:
    """The params-row tree signature (treedef + per-lane leaf shapes and
    dtypes) — the same signature ``request_class_key`` embeds, shared
    here so the fusion class keys it identically."""
    import jax

    from cimba_tpu.runner import experiment as ex

    row = jax.eval_shape(
        lambda: ex._slice_params(params, n_replications, 0, 1)
    )
    leaves, treedef = jax.tree.flatten(row)
    return (
        str(treedef),
        tuple((tuple(l.shape[1:]), str(l.dtype)) for l in leaves),
    )


def get_fused(programs: MutableMapping, specs) -> "object":
    """The cached fused bundle (:class:`cimba_tpu.core.fuse.FusedSpec`)
    for an ORDERED member tuple.  Caching the bundle — not just its
    programs — is load-bearing: :func:`cimba_tpu.core.fuse.fuse_specs`
    creates fresh rebasing wrappers per call, so an uncached re-fuse
    would mint a fresh merged fingerprint and recompile everything.
    One bundle per member tuple makes the merged spec's fingerprint
    stable for the life of the cache entry (which pins every member)."""
    from cimba_tpu.core import fuse as _fuse

    specs = tuple(specs)
    key = ("fuse_bundle",) + tuple(spec_fingerprint(s) for s in specs)

    def build():
        return (_fuse.fuse_specs(specs),)

    return _get_or_create(programs, key, build)[0]


def get_fused_wave_programs(
    programs: MutableMapping,
    fused,
    *,
    mesh,
    chunk_steps: int,
    with_metrics: bool,
):
    """The fused wave's compiled pair: ``(finit_j, chunk_j)``.  The
    chunk program is the ORDINARY :func:`get_programs` entry for the
    merged superspec (block dispatch is already a per-lane pc switch,
    so the merged table needs no special chunk program — and the store,
    warmers and program-size probes all see a normal spec); only init
    is fused (``runner.experiment._fused_init_program`` — the per-lane
    spec-id switch, docs/26_wave_fusion.md)."""
    init_key = ("fused_init",) + program_class_key(
        fused.spec, with_metrics, mesh=mesh,
    )

    def build():
        from cimba_tpu.runner import experiment as ex

        return (
            ex._fused_init_program(fused, mesh),
            fused,  # pins members + merged fingerprints while cached
        )

    finit_j = _get_or_create(programs, init_key, build)[0]
    _, chunk_j = get_programs(
        programs, fused.spec, mesh=mesh,
        chunk_steps=chunk_steps, with_metrics=with_metrics,
    )
    return finit_j, chunk_j


def get_fused_refill_programs(
    programs: MutableMapping,
    fused,
    *,
    mesh,
    with_metrics: bool,
):
    """The fused refill plane's compiled pair: ``(frefill_j, live_j)``
    — the spec-id-switched lane splice and the per-lane liveness
    readback.  Liveness is member-independent (``make_cond`` reads
    horizon/done/err, never the block table), so the merged spec's
    ordinary live program serves every member's lanes."""
    key = ("fused_refill",) + program_class_key(
        fused.spec, with_metrics, mesh=mesh,
    )

    def build():
        from cimba_tpu.runner import experiment as ex

        return (
            ex._fused_refill_program(fused, mesh),
            ex._live_program(fused.spec, mesh),
            fused,  # pins members + merged fingerprints while cached
        )

    return _get_or_create(programs, key, build)[:2]


#: conservative working-set multiplier when no measured program
#: footprint is available: the chunk program donates its carry, so the
#: steady state holds roughly input + output + XLA temps — 3x the lane
#: buffers bounds that from above without a compile
_FOOTPRINT_SAFETY = 3


def wave_footprint_bytes(
    programs: MutableMapping,
    spec,
    *,
    mesh,
    chunk_steps: int,
    with_metrics: bool,
    lanes: int,
    params,
    n_replications: int,
) -> int:
    """Estimated device bytes ONE wave of ``lanes`` lanes holds while
    live — the memory-aware admission cost of the device scheduler
    (docs/24_device_scheduler.md): the Sim pytree's lane buffers (from
    ``jax.eval_shape`` over the init program — no device work) plus
    the chunk program's own working set, resolved down a ladder:

    1. a store-persisted ``footprint_bytes`` on the hydrated chunk
       program (measured by ``save_programs`` at AOT-compile time —
       no re-lowering, the PR 17 manifest satellite);
    2. ``chunk_j.lower(aval).compile().memory_analysis()`` where the
       backend implements it (one AOT compile per (class, shape)
       point, memoized here like any program);
    3. a conservative estimate (``_FOOTPRINT_SAFETY`` x the lane
       buffers) when neither is available.

    Memoized in ``programs`` under a ``("footprint", ...)`` key beside
    the programs it describes, so steady-state admission never
    recomputes (and never compiles) anything."""
    import jax

    from cimba_tpu.runner import experiment as ex

    row_aval = jax.eval_shape(
        lambda: ex._slice_params(params, n_replications, 0, 1)
    )
    psig = (
        jax.tree.structure(row_aval),
        tuple(
            (tuple(l.shape[1:]), str(l.dtype))
            for l in jax.tree.leaves(row_aval)
        ),
    )
    key = ("footprint",) + program_key(
        spec, with_metrics, mesh=mesh,
        chunk_steps=chunk_steps,
    ) + (int(lanes), psig)

    def build():
        import jax.numpy as jnp
        import numpy as np

        init_j, chunk_j = get_programs(
            programs, spec, mesh=mesh,
            chunk_steps=chunk_steps, with_metrics=with_metrics,
        )
        L = int(lanes)
        pw = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (L,) + x.shape[1:]),
            ex._slice_params(params, n_replications, 0, 1),
        )
        sims_aval = jax.eval_shape(
            init_j, jnp.arange(L), ex._seed_column(0, L),
            ex._horizon_column(None, L), pw,
        )
        buf = sum(
            int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(sims_aval)
        )
        prog = None
        # rung 1: the store-measured footprint riding the hydrated
        # chunk program (no lowering, no compile)
        fp_for = getattr(chunk_j, "footprint_for", None)
        if fp_for is not None:
            prog = fp_for(sims_aval)
        elif hasattr(chunk_j, "lower"):
            # rung 2: AOT memory_analysis — unimplemented on some
            # backends (and the whole rung is best-effort: admission
            # must never fail because a compiler API moved)
            try:
                mem = chunk_j.lower(sims_aval).compile() \
                    .memory_analysis()
                prog = _memory_analysis_bytes(mem)
            except Exception:
                prog = None
        if prog is None or prog <= 0:
            # rung 3: conservative estimate — the extra copies bound
            # donated-carry temps from above
            prog = (_FOOTPRINT_SAFETY - 1) * buf
        return int(buf + prog)

    return _get_or_create(programs, key, build)


def _memory_analysis_bytes(mem) -> "int | None":
    """Sum the working-set fields a PjRt ``memory_analysis()`` object
    exposes (field names vary by backend/version — absent ones count
    0; a backend returning None yields None)."""
    if mem is None:
        return None
    total = 0
    for f in ("temp_size_in_bytes", "output_size_in_bytes",
              "argument_size_in_bytes"):
        try:
            total += int(getattr(mem, f, 0) or 0)
        except (TypeError, ValueError):
            pass
    return total if total > 0 else None


def get_fold(programs: MutableMapping, with_metrics: bool, summary_path):
    """The jitted wave-fold program shared by the stream runner and the
    service's per-request accumulators: merge the wave's pooled Pébay
    summary, failure count, event total, and (when enabled) pooled
    metrics registry into the accumulator tuple.  Keyed by the metrics
    flag and ``summary_path`` identity — a different statistic is a
    different program.  Folds have no explicit store artifact, but
    jax's persistent compilation cache, where an entry point turned it
    on, still softens their recompile to a disk hit (mechanism (a),
    docs/15_program_store.md)."""
    key = ("fold", with_metrics, summary_path)

    def build():
        return _fold_program(with_metrics, summary_path)

    return _get_or_create(programs, key, build)


def _fold_program(with_metrics: bool, summary_path):
    """Build the jitted wave-fold program (the body of
    :func:`get_fold`, factored out so the store can AOT-compile it for
    fold artifacts and ``warm(manifest=...)`` can wrap it in a
    hydration shim)."""
    import jax
    import jax.numpy as jnp

    from cimba_tpu.obs import metrics as _metrics
    from cimba_tpu.stats import summary as sm

    def fold(acc, sims):
        if (sims.metrics is None) == with_metrics:
            raise RuntimeError(
                "run_experiment_stream: obs.metrics was "
                f"{'enabled' if with_metrics else 'disabled'} when "
                "the stream started but flipped mid-stream — the "
                "flag binds for the whole stream"
            )
        pooled = sm.merge_tree(summary_path(sims))
        out = (
            sm.merge(acc[0], pooled),
            acc[1] + jnp.sum((sims.err != 0).astype(jnp.int64)),
            acc[2] + jnp.sum(sims.n_events.astype(jnp.int64)),
        )
        if with_metrics:
            out = out + (
                _metrics.merge(acc[3], _metrics.pool(sims.metrics)),
            )
        return out

    # no donation on the accumulator: its leaves are scalars
    # (aliasing buys nothing) and sm.empty() aliases one zero buffer
    # across moments, which XLA's donation path rejects as a
    # double-donate
    return jax.jit(fold)


def get_gather(programs: MutableMapping):
    """The jitted lane-gather the fold sites slice waves with: ONE
    compiled program per (Sim structure, index shape) instead of a
    per-leaf eager dispatch chain — a wave Sim is ~40 leaves, and 40
    eager ``x[idx]`` dispatches cost ~1 ms each on a loaded host, so
    the gather (not the fold, which is already jitted) was the serve
    dispatcher's per-retirement wall.  Pure integer indexing: the
    gathered leaves are bitwise the eager slice, so every fold
    downstream stays bitwise its direct call's."""
    def build():
        import jax

        return jax.jit(
            lambda sims, idx: jax.tree.map(lambda x: x[idx], sims)
        )

    return _get_or_create(programs, ("gather",), build)


def stream_acc(spec, with_metrics: bool):
    """A zeroed accumulator tuple for :func:`get_fold`'s program:
    ``(Summary, n_failed i64, total_events i64[, Metrics])``."""
    import jax.numpy as jnp

    from cimba_tpu.core import loop as _cl
    from cimba_tpu.obs import metrics as _metrics
    from cimba_tpu.stats import summary as sm

    acc = (
        sm.empty(),
        jnp.zeros((), jnp.int64),
        jnp.zeros((), jnp.int64),
    )
    if with_metrics:
        acc = acc + (
            _metrics.create(
                _cl.N_KINDS + len(spec.user_handlers), len(spec.queues)
            ),
        )
    return acc


def preflight_summary_path(
    programs: MutableMapping,
    spec,
    init_j,
    summary_path,
    params,
    n_total: int,
    n_first: int,
    with_metrics: bool,
) -> None:
    """Trace ``summary_path`` over the first wave's ABSTRACT sims
    (``eval_shape`` of init∘path — milliseconds, tracers not structs so
    compute-style paths work) so a path that doesn't exist on this
    model's Sim fails here with the knob named, not as an opaque
    KeyError from inside the fold after a full wave of compute.  Cached
    by the spec's structural fingerprint (twin specs share the check)
    so a warmed cache skips the re-trace inside a benchmark's timed region
    (the entry pins spec, keeping the fingerprint's ids valid)."""
    key = (
        "preflight", spec_fingerprint(spec), summary_path, with_metrics,
    )
    if key in programs:
        return

    def check():
        import jax
        import jax.numpy as jnp

        from cimba_tpu.runner import experiment as ex

        try:
            jax.eval_shape(
                lambda r, s, t, p: summary_path(init_j(r, s, t, p)),
                jnp.arange(n_first),
                ex._seed_column(0, n_first),
                ex._horizon_column(None, n_first),
                ex._slice_params(params, n_total, 0, n_first),
            )
        except Exception as e:
            raise ValueError(
                "run_experiment_stream: summary_path failed on this "
                f"model's Sim structure ({e!r}) — pass summary_path= "
                "pointing at a statistic this model records"
            ) from e
        return spec  # pins the fingerprint's function ids while cached

    _get_or_create(programs, key, check)


def warm(
    cache: MutableMapping,
    spec,
    params,
    wave_size: int,
    *,
    manifest=None,
    **stream_kwargs,
):
    """Warm-up precompile, two modes.

    Default (``manifest=None``): run ONE full wave through the stream
    runner against ``cache``, so a service built over the same cache
    (and a structurally-identical spec / settings — seed and horizon
    don't matter, they are per-lane data) serves its first real
    request from already-compiled programs.  Returns the warm-up
    wave's ``StreamResult`` (callers usually discard it).

    AOT mode (``manifest=`` a store root path or
    :class:`~cimba_tpu.serve.store.ProgramStore`): no dummy wave — the
    (spec, settings) program key hydrates from the store's serialized
    executables straight into ``cache`` (docs/15_program_store.md).  A
    missing or invalidated entry raises ``LookupError`` LOUDLY — a
    fleet rollout must find out at warm time, not discover a
    minutes-long compile on its first request — and the store's
    counters say why (corrupt / version drift / plain miss).  Returns
    the :class:`~cimba_tpu.serve.store.ProgramStore`.

    The wave-FOLD program hydrates too when the store carries a fold
    artifact for ``summary_path`` (saved by default —
    ``ProgramStore.save_programs(summary_paths=...)``); with no fold
    artifact and ``params`` given, the fold is instead built on THIS
    thread with one fold application over an init'd (never
    chunk-driven) wave of ``wave_size`` lanes — deferring it to the
    service's dispatcher thread costs several times the main-thread
    build (measured ~4.6x on the CPU window, BENCH_NOTES round 8).
    Pass ``params=None`` to hydrate strictly from artifacts."""
    from cimba_tpu.runner import experiment as ex

    if manifest is None:
        res = ex.run_experiment_stream(
            spec, params, wave_size, wave_size=wave_size,
            program_cache=cache, **stream_kwargs,
        )
        # the serve fold sites slice waves through the jitted lane
        # gather — a once-per-cache program the direct stream path
        # never builds; prime it so the warmed service's first
        # retirement is a cache hit, not a compile
        get_gather(cache)
        return res

    from cimba_tpu.obs import metrics as _metrics
    from cimba_tpu.serve import store as _pstore

    st = (
        manifest if isinstance(manifest, _pstore.ProgramStore)
        else _pstore.get_store(str(manifest))
    )
    if isinstance(cache, ProgramCache) and cache._store is None:
        # bind the cache to THIS store so later lookups (and the
        # service's stats) hit the same instance/counters the warm did
        cache._store = st
    mesh = stream_kwargs.pop("mesh", None)
    chunk_steps = stream_kwargs.pop("chunk_steps", None)
    summary_path = stream_kwargs.pop("summary_path", None)
    n_replications = stream_kwargs.pop("n_replications", None)
    if stream_kwargs:
        raise TypeError(
            "serve.warm(manifest=...): unsupported kwargs in AOT mode: "
            f"{sorted(stream_kwargs)} (only mesh/chunk_steps/"
            "summary_path/n_replications select a program)"
        )
    if chunk_steps is None:
        # tuned-schedule resolution at program-build time
        # (docs/21_autotune.md): the hydrated program key must be the
        # one the service will actually dispatch, and the service
        # resolves at the REQUEST's workload bucket — pass
        # ``n_replications=`` when requests will carry a different R
        # than ``wave_size`` (the bucket is pow2(R), not pow2(wave)),
        # or explicit kwargs to pin the knobs outright
        from cimba_tpu.tune import registry as _tune_reg

        rs = _tune_reg.resolve_entry(
            spec, int(n_replications or wave_size or 0),
            chunk_steps=chunk_steps, store=st,
        )
        chunk_steps = rs.chunk_steps
    if summary_path is None:
        summary_path = ex.default_summary_path
    with_metrics = _metrics.enabled()
    key = program_key(spec, with_metrics, mesh=mesh, chunk_steps=chunk_steps)
    folds: dict = {}
    if key not in cache:
        hyd = st.hydrate(
            spec, mesh=mesh, chunk_steps=chunk_steps,
            with_metrics=with_metrics,
        )
        if hyd is None:
            raise LookupError(
                f"serve.warm(manifest=...): the store at {st.root} has "
                "no loadable artifact for this (spec, settings) "
                "program key — build one with tools/warm_store.py "
                f"(store stats: {st.stats()})"
            )
        # deserialize NOW, on the calling thread: lazy resolution would
        # land on the service's dispatcher thread, which pays ~4.6x
        hyd.init.resolve_all()
        hyd.chunk.resolve_all()
        cache[key] = (hyd.init, hyd.chunk, spec)
        folds = hyd.folds

    fold_key = ("fold", with_metrics, summary_path)
    if fold_key not in cache:
        try:
            pdig = _pstore.callable_digest(summary_path)
        except _pstore.UnstableStoreKey:
            pdig = None
        table = {
            shape: fn for (d, shape), fn in folds.items() if d == pdig
        }
        fold_j = _fold_program(with_metrics, summary_path)
        if table:
            for art in table.values():
                art.resolve()
            cache[fold_key] = _pstore.hydrated_fold(fold_j, table, st)
        elif params is not None and wave_size:
            # no fold artifact: build it HERE (main thread) with one
            # fold application over an init'd, never-driven wave
            cache[fold_key] = fold_j
            n = int(wave_size)
            init_fn = cache[key][0]
            sims0 = init_fn(
                ex.jnp.arange(n), ex._seed_column(0, n), None,
                ex._slice_params(params, n, 0, n),
            )
            fold_j(stream_acc(spec, with_metrics), sims0)
    get_gather(cache)
    return st
