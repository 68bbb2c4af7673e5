"""Cross-spec wave fusion: branch-dispatch superprograms.

A wave (docs/14_wave_packing.md) packs lanes of ONE compatibility
class — same spec, same chunk geometry.  A fleet serving many small
*different* models degenerates to all-solo waves: each spec compiles
its own program and occupies its own (mostly padded) wave, and the
refill/occupancy machinery (docs/22, docs/24) cannot help because no
two requests are ever compatible.

Fusion extends the per-lane seed/horizon-column trick (docs/14) to
per-lane *model identity*.  :func:`fuse_specs` merges N
compatible-shape member specs into one **superspec** whose block table
is the concatenation of the members' tables, each member's entry pcs
rebased by its table offset.  The chunk program built from the
superspec is the ordinary :func:`cimba_tpu.core.loop.make_chunk` —
block dispatch is already a per-lane branch on ``procs.pc`` (the
batched step's ``loop._vswitch``), so once a lane's pcs live in member
k's slice of the merged table, the existing dispatch IS the per-lane
model switch.  Only *initialisation*
needs an explicit branch: :func:`make_fused_init` switches each lane's
``init_sim`` through its member's own process table / ``user_init`` on
a per-lane ``spec_id`` column, and :func:`make_fused_refill` does the
same for mid-wave lane splices (docs/22_refill.md).

Why lanes stay BITWISE equal to their solo runs (docs/26_wave_fusion.md):

* dispatch is value-exact: the batched block dispatch computes every
  branch and *selects* per lane, and selection never perturbs the
  selected values — a member lane runs exactly its own block functions
  (member 0's table entries are the original function objects; other
  members' entries are thin wrappers that add the pc base to
  ``Command.next_pc`` and change nothing else);
* pc values are shifted by the member's base but pc never reaches a
  result: summaries fold user state, ``n_events`` and metrics only,
  and the machinery never compares pcs across specs;
* the merged spec's command-tag union can only *add* machinery arms,
  and every arm is tag-selected per command — a lane whose commands
  carry only its member's tags computes exactly what its solo program
  computes;
* member shape compatibility (:func:`fusion_shape_key`) pins every
  capacity and component layout, so all Sim leaves have identical
  shapes and dtypes across members — no re-layout, no padding drift.

What CANNOT fuse (and why — docs/26_wave_fusion.md#when-not-to-fuse):

* specs with spawn pools (``m.process(..., start=False)``):
  ``api.spawn`` bakes the pool's *unrebased* ``entry_pc`` into the
  traced program at build time (``loop.spawn_process``), so a spawned
  row would dispatch into the wrong member's table slice;
* specs with ``boundary_pcs``: the kernel boundary protocol keys block
  *indices*, which rebasing renumbers;
* specs whose component geometry, caps, local counts, condition
  predicates or user handlers differ: the merged program keeps ONE
  copy of the machinery, so all members must agree on it exactly
  (predicates/handlers by function identity, everything else by
  value).

The serving layer (docs/26) additionally requires members to share a
params-row signature and a Sim *structure* signature (user state /
metrics / trace leaves), so a structure mismatch is rejected at class
formation — never at trace time inside ``lax.switch``.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cimba_tpu.core import loop as _loop
from cimba_tpu.core.model import ModelSpec


class FusionError(ValueError):
    """The spec (or spec set) cannot participate in wave fusion; the
    message names the disqualifying structure.  Callers treat this as
    "serve it solo", never as a hard failure."""


def _ref_shape(r):
    # the identity-free twin of cache.spec_fingerprint's ref_key: drop
    # the display name, keep ids/capacities/guards; callables (condition
    # predicates) key by object identity — members must SHARE them,
    # because the merged spec keeps a single copy of the machinery
    out = []
    for f in dataclasses.fields(r):
        if f.name == "name":
            continue
        v = getattr(r, f.name)
        if callable(v):
            out.append((f.name, "fn", id(v)))
        elif isinstance(v, (list, tuple)):
            out.append((f.name, tuple(v)))
        else:
            out.append((f.name, v))
    return (type(r).__name__, tuple(out))


def fusion_shape_key(spec: ModelSpec) -> tuple:
    """The structural-geometry key of a spec MINUS its model identity:
    two specs with equal keys can share one fused superprogram.  Keeps
    process count, local/caps/component layout, condition predicate and
    user-handler identities; excludes the name, the block table, the
    per-process entry/prio/start data and ``user_init`` (all per-member
    — consumed only inside :func:`~cimba_tpu.core.loop.init_sim`, which
    fused waves dispatch per lane).  Raises :class:`FusionError` for
    structurally unfusable specs."""
    cached = getattr(spec, "_cimba_fusion_shape", None)
    if cached is not None:
        return cached
    if tuple(spec.boundary_pcs):
        raise FusionError(
            f"spec {spec.name!r} has boundary_pcs: the kernel boundary "
            "protocol keys block indices, which fusion renumbers"
        )
    if not all(bool(s) for s in np.asarray(spec.proc_start).tolist()):
        raise FusionError(
            f"spec {spec.name!r} declares a spawn pool (start=False): "
            "api.spawn bakes the unrebased entry_pc into the trace "
            "(loop.spawn_process), so spawned rows cannot be rebased"
        )
    key = (
        int(spec.n_procs),
        tuple(_ref_shape(q) for q in spec.queues),
        tuple(_ref_shape(r) for r in spec.resources),
        tuple(_ref_shape(p) for p in spec.pools),
        tuple(_ref_shape(b) for b in spec.buffers),
        tuple(_ref_shape(q) for q in spec.pqueues),
        tuple(_ref_shape(c) for c in spec.conditions),
        spec.n_guards, spec.guard_cap, spec.event_cap,
        spec.queue_cap_max, spec.pqueue_cap_max,
        spec.n_flocals, spec.n_ilocals, spec.max_chain,
        tuple(id(h) for h in spec.user_handlers),
    )
    try:
        object.__setattr__(spec, "_cimba_fusion_shape", key)
    except (AttributeError, TypeError):
        pass
    return key


def _rebase_block(fn, base: int):
    """Wrap one member block so every pc it yields lands back in the
    member's slice of the merged table.  ``Command.next_pc`` is the
    ONLY pc-bearing command field (core/process.py), and blocks yield
    pcs exclusively through it — ``cmd.select`` merges whole Commands,
    so a data-dependent next_pc is still a single field to shift.  The
    shift is value-preserving for results: exit commands ignore
    next_pc, and nothing downstream compares pcs across members."""

    def rebased(sim, p, sig, _fn=fn, _base=base):
        sim, c = _fn(sim, p, sig)
        return sim, c._replace(next_pc=c.next_pc + _base)

    return rebased


@dataclasses.dataclass(frozen=True)
class FusedSpec:
    """A fused superspec bundle.

    ``spec`` is a real :class:`ModelSpec` — the merged block table over
    member 0's machinery — so every downstream consumer (chunk
    programs, program caches, stores, ``obs.program_size``) handles it
    unchanged.  ``rebased[k]`` is member k's spec twin carrying the
    merged table and rebased ``proc_entry`` — the spec a lane's
    ``init_sim`` branch runs, and the ONLY place member identity
    survives (prio/start/user_init are init-time data).  ``members``
    keeps the original specs pinned (cache entries embedding function
    ids must pin the objects — serve/cache.py's entry-pinning
    invariant)."""

    spec: ModelSpec
    members: Tuple[ModelSpec, ...]
    rebased: Tuple[ModelSpec, ...]
    bases: Tuple[int, ...]

    @property
    def n_members(self) -> int:
        return len(self.members)


def fuse_specs(specs: Sequence[ModelSpec]) -> FusedSpec:
    """Merge compatible-shape member specs into one superspec.

    The merged block table is the concatenation of the members' tables
    (member 0's blocks verbatim — base 0 needs no wrapper, so a
    single-member "fusion" degenerates to the original functions).
    The merged spec keeps member 0's process arrays and machinery; a
    lane only ever reaches the merged table through its member's
    rebased ``init_sim``, so the merged spec's own entry data is never
    consulted for foreign lanes (``proc_entry``/``prio``/``start`` are
    consumed exclusively by :func:`~cimba_tpu.core.loop.init_sim`)."""
    specs = tuple(specs)
    if not specs:
        raise FusionError("fuse_specs: empty member set")
    shape0 = fusion_shape_key(specs[0])
    for s in specs[1:]:
        if fusion_shape_key(s) != shape0:
            raise FusionError(
                f"fuse_specs: {s.name!r} is not shape-compatible with "
                f"{specs[0].name!r} (component geometry, caps, locals, "
                "predicates and handlers must match exactly)"
            )
    merged: list = []
    bases: list = []
    for k, s in enumerate(specs):
        base = len(merged)
        bases.append(base)
        if base == 0:
            merged.extend(s.blocks)
        else:
            merged.extend(_rebase_block(b, base) for b in s.blocks)
    table = tuple(merged)
    name = "fused(" + "+".join(s.name for s in specs) + ")"
    spec = dataclasses.replace(
        specs[0], name=name, blocks=table, boundary_pcs=(),
    )
    rebased = tuple(
        dataclasses.replace(
            s,
            blocks=table,
            proc_entry=np.asarray(s.proc_entry) + b,
        )
        for s, b in zip(specs, bases)
    )
    return FusedSpec(
        spec=spec, members=specs, rebased=rebased, bases=tuple(bases),
    )


def _switched_init(fused: FusedSpec):
    # one lane: dispatch init_sim through the lane's member spec.  The
    # index is clipped like block dispatch (lax.switch clamps anyway;
    # the clip keeps the contract explicit) — pad lanes carry sid 0.
    branches = tuple(
        (lambda r, s, t, q, _sp=sp: _loop.init_sim(_sp, s, r, q, t_stop=t))
        for sp in fused.rebased
    )
    if len(branches) == 1:
        only = branches[0]
        return lambda r, s, t, sid, q: only(r, s, t, q)

    def init1(r, s, t, sid, q):
        return jax.lax.switch(
            jnp.clip(sid, 0, len(branches) - 1), branches, r, s, t, q,
        )

    return init1


def make_fused_init(fused: FusedSpec):
    """Build ``init(reps, seeds, t_stops, sids, params) -> Sim`` — the
    fused twin of the serving init program: per-lane ``lax.switch`` on
    the ``sids`` column routes each lane's :func:`init_sim` through its
    own member spec (rebased entry pcs, own prio/start/``user_init``).
    Under ``vmap`` the switch computes every member's init and selects
    per lane — selection is value-exact, so a member lane's born state
    is bitwise its solo wave's.  All members of a fusion class share
    one params-row signature (the class key guarantees it), so a single
    batched params tree serves every branch."""
    init1 = _switched_init(fused)

    def init(reps, seeds, t_stops, sids, params):
        return jax.vmap(init1)(reps, seeds, t_stops, sids, params)

    return init


def make_fused_refill(fused: FusedSpec):
    """Build ``refill(sims, mask, reps, seeds, t_stops, sids, params)
    -> sims`` — the fused twin of :func:`cimba_tpu.core.loop.make_refill`:
    masked lanes are re-born through :func:`make_fused_init`'s per-lane
    member dispatch and spliced in with the same per-leaf masked select
    (unmasked lanes pass through bit-identically; dead/pad rows carry
    ``t_stop=-inf`` and sid 0).  One refill program serves the whole
    fusion class — a boundary splice admits any member without
    retracing."""
    init1 = _switched_init(fused)

    def refill(sims: _loop.Sim, mask, reps, seeds, t_stops, sids, params):
        if sims.t_stop is None:
            raise ValueError(
                "make_fused_refill: the wave carries no per-lane t_stop "
                "leaf — fused refill waves always materialize the "
                "horizon column (docs/22_refill.md, docs/26)"
            )
        fresh = jax.vmap(init1)(reps, seeds, t_stops, sids, params)

        def sel(a, b):
            m = mask.reshape(mask.shape + (1,) * (a.ndim - 1))
            return jnp.where(m, a, b)

        return jax.tree.map(sel, fresh, sims)

    return refill
