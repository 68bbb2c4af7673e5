"""The Pallas mega-kernel event loop: whole-run stepping in VMEM.

Reference parity: this is the TPU answer to the reference's hot loop —
``cmb_event_queue_execute`` (`src/cmb_event.c:296-335`) popping from the
hashheap (`src/cmi_hashheap.c:454-522`) at ~6M events/s/core.

Why it exists: running the interpreter as a plain XLA ``lax.while_loop``
costs ~3.5 ms of sequential fused-kernel latency *per event* plus one HBM
round-trip of the whole batched Sim per step (measured, BENCH_NOTES.md) —
five orders of magnitude off the reference.  Here the *entire run* executes
inside one ``pallas_call``: every Sim leaf lives in VMEM for the duration,
steps happen back-to-back on the VPU with no kernel-dispatch or HBM cost
per event.

Design:

* **Same interpreter.**  The kernel body evaluates the jaxpr of
  ``loop.make_step(spec)`` — the exact dispatcher the XLA path runs; there
  is no second implementation of the engine semantics (the f64 XLA path
  stays the bit-exact oracle; tests compare the two).
* **f32 profile.**  Mosaic has no 64-bit types, so the kernel traces under
  ``config.profile("f32")`` (f32 clock/statistics, i32 counters).  The
  caller owns profile selection: build spec + init under f32, run here.
* **Lane-LAST layout, hand-batched.**  In the kernel a batched leaf is
  ``[component_dims..., L]`` with the replication lane axis last, so lanes
  sit on the 128-wide minor dim of every Mosaic tile and per-lane scalars
  (clock, pc — the hot values) are full native rows.  Crucially the
  batching is NOT ``jax.vmap``: vmap's reshape/broadcast batching rules
  normalize batch dims to axis 0 and emit minor-axis transposes that the
  Mosaic layout pass rejects (bisected in round 2).  ``core/lanelast.py``
  re-batches the per-lane step jaxpr with lanes pinned last;
  ``core/bool32.py`` then rewrites every i1 vector to an i32 carrier
  (i1 logic chains and i1<->i32 converts also crash the layout pass).
* **Chunked calls.**  One kernel invocation advances every lane by up to
  ``chunk_steps`` events (VMEM residency bounds per-call wall time under
  the device watchdog); an outer host loop re-invokes until every lane is
  done.  Each re-invocation costs one HBM round-trip of the Sim —
  amortized over ``chunk_steps`` events it is noise.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cimba_tpu import config
from cimba_tpu.core import bool32, carry, dyn, lanelast
from cimba_tpu.core import loop as cl
from cimba_tpu.core.model import ModelSpec


def _vmem_limit_bytes(lane_block=None) -> int:
    """Mosaic scoped-vmem budget for the chunk kernel, in bytes.

    Default 96 MiB (v5e has 128 MiB; the 16 MiB Mosaic default rejects
    the whole-Sim-resident kernel above L≈1024 — measured offline,
    BENCH_NOTES round 4); 110 MiB under lane blocking (the grid's DMA
    double-buffering adds a few MiB — an Lb=8192 block measured 97.3
    MiB offline, 1.3 over the plain budget).  Override with
    ``CIMBA_KERNEL_VMEM_LIMIT``."""
    raw = config.env_raw("CIMBA_KERNEL_VMEM_LIMIT").strip()
    if not raw:
        return (110 if lane_block else 96) * 1024 * 1024
    try:
        return int(raw)
    except ValueError as e:
        raise ValueError(
            f"CIMBA_KERNEL_VMEM_LIMIT must be an integer byte count, "
            f"got {raw!r}"
        ) from e


# Carry packing (see core/carry.py — ONE implementation serves this
# kernel chunk loop and loop.make_run's packed XLA while-loop, so the
# two hot paths can never diverge on buffer layout).  The lane-last
# aliases below keep this module's historical API: the chunk's batched
# leaves are [comp..., L] and pack into [rows, L] buffers.  Why packing
# at all: Mosaic's per-iteration cost of the chunk while-loop scales
# super-linearly with the number of narrow carried leaves — measured on
# v5e (BENCH_NOTES round-5 floor probes): mm1's real 54-leaf carry
# costs ~135 us/step with a TRIVIAL body, the same bytes in a few wide
# f32 buffers <1 us.


def _pack_plan(avals):
    return carry.pack_plan(avals, lane_last=True)


def _pack(leaves, plan):
    return carry.pack(leaves, plan)


def _unpack(packed, plan, L):
    return carry.unpack(packed, plan, L)


def make_kernel_run(
    spec: ModelSpec,
    t_end: Optional[float] = None,
    chunk_steps: int = 512,
    max_chunks: int = 10_000,
    interpret: bool = False,
    single_step: bool = False,
    mesh=None,
    packed: Optional[bool] = None,
    lane_block: Optional[int] = None,
):
    """Build ``run(sims) -> sims`` where ``sims`` is a lane-FIRST batched
    Sim (the shape ``jax.vmap(init_sim)`` produces) and every lane is
    advanced to completion inside Pallas kernels.

    Must be built and called under the f32 profile
    (``config.use_profile("f32")``); raises otherwise — Mosaic cannot
    represent 64-bit leaves.

    ``mesh``: a 1-D ``jax.sharding.Mesh`` to shard lanes over.  Each
    device runs the SAME chunk kernel on its local lane block
    (``shard_map`` over the minor lane axis — reference parity: one event
    loop per worker thread, `src/cimba.c:156-221`); the host loop drives
    all devices in lockstep on a global any-lane-live check, so devices
    whose lanes finished early idle-mask until the slowest is done.  This
    composes with the all_gather statistics merge in
    ``runner.experiment`` — together they are the v5e-8 path.
    """
    if config.active_profile() != "f32":
        raise ValueError(
            "make_kernel_run requires config.profile('f32') — Mosaic has "
            "no 64-bit types; build the spec and init_sim under f32 too"
        )
    if packed is None:
        # carry packing (see _pack_plan): opt-in via env until measured
        # faster on hardware, then flip the default
        packed = config.env_raw("CIMBA_KERNEL_PACK") != "0"
    if lane_block is None:
        # lane blocking: run the chunk as a pallas GRID over lane
        # blocks — VMEM holds ONE block's Sim (so total lanes are no
        # longer VMEM-capped), Mosaic compiles a block-sized program
        # (so compile time stops growing with total lanes), and one
        # launch advances every block (amortizing the ~75 ms/launch
        # host overhead over L/lane_block more events).  Lanes are
        # independent, so per-block while-loops are trajectory-
        # identical to the monolithic form: each block just exits its
        # loop when its own lanes are done.
        raw = config.env_raw("CIMBA_KERNEL_LANE_BLOCK").strip()
        try:
            lane_block = int(raw) if raw else None
        except ValueError as e:
            raise ValueError(
                f"CIMBA_KERNEL_LANE_BLOCK must be an integer lane count, "
                f"got {raw!r}"
            ) from e
    step = cl.make_step(spec)
    cond = cl.make_cond(spec, t_end)

    def trace_chunk(leaves, treedef):
        """``leaves`` are LANE-LAST ([comp..., L]).  Trace the per-lane
        step/cond, batch them lane-last (core/lanelast.py), assemble the
        chunk loop, and bool32-rewrite it.  Returns ``(flat_chunk,
        bool_idx, carrier_avals)`` — the exact program the kernel runs
        (tools/mosaic_eqn_bisect.py bisects THIS, so tool and kernel can
        never diverge)."""
        L = leaves[0].shape[-1]
        per_avals = [
            jax.ShapeDtypeStruct(l.shape[:-1], l.dtype) for l in leaves
        ]
        config.KERNEL_MODE = True
        try:
            # one-hot memo scoped per trace: repeated accesses at the
            # same pid/slot index share a single iota==i mask (cleared
            # between traces so no tracer crosses jaxprs)
            with dyn.oh_cache():
                step_j = jax.make_jaxpr(
                    lambda *ls: jax.tree.leaves(
                        step(jax.tree.unflatten(treedef, ls))
                    )
                )(*per_avals)
            with dyn.oh_cache():
                cond_j = jax.make_jaxpr(
                    lambda *ls: cond(jax.tree.unflatten(treedef, ls))
                )(*per_avals)
        finally:
            config.KERNEL_MODE = False
        _maybe_dump_64bit(step_j)

        def vstep(ls):
            outs = lanelast.eval_lanelast(
                step_j.jaxpr,
                step_j.consts,
                L,
                [lanelast._Val(x, True) for x in ls],
            )
            return [
                lanelast._promote(o, v.aval, L)
                for o, v in zip(outs, step_j.jaxpr.outvars)
            ]

        def vcond(ls):
            (o,) = lanelast.eval_lanelast(
                cond_j.jaxpr,
                cond_j.consts,
                L,
                [lanelast._Val(x, True) for x in ls],
            )
            return lanelast._promote(o, cond_j.jaxpr.outvars[0].aval, L)

        def batched_chunk(*ls):
            """Advance every lane by up to chunk_steps events: a scalar
            any-lane-live condition with per-lane select masking.  The
            [L] mask broadcasts against [comp..., L] leaves over leading
            dims — the one broadcast direction Mosaic always supports."""

            def wcond(carry):
                ls, k = carry
                return (k < chunk_steps) & jnp.any(vcond(list(ls)))

            def wbody(carry):
                ls, k = carry
                live = vcond(list(ls))
                new = vstep(list(ls))
                out = tuple(
                    x if x is y else jnp.where(live, x, y)
                    for x, y in zip(new, ls)
                )
                return out, k + 1

            if single_step:
                # bisect aid (tools/mosaic_bisect.py): one masked step,
                # no loop — separates step bugs from loop bugs
                out, _ = wbody((tuple(ls), jnp.zeros((), jnp.int32)))
                return list(out)
            if packed:
                # packed carry: the while loop carries 2-5 wide buffers
                # instead of ~54 narrow leaves (see _pack_plan); the
                # body unpacks, steps, repacks, and applies the live
                # mask per BUFFER (one wide select each) instead of
                # per leaf
                plan = _pack_plan(
                    [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in ls]
                )

                def pcond(carry):
                    bufs, k = carry
                    return (k < chunk_steps) & jnp.any(
                        vcond(_unpack(list(bufs), plan, L))
                    )

                def pbody(carry):
                    bufs, k = carry
                    ls2 = _unpack(list(bufs), plan, L)
                    live = vcond(ls2)
                    new = vstep(ls2)
                    nbufs = _pack(new, plan)
                    merged = tuple(
                        b if b is nb else jnp.where(live, nb, b)
                        for b, nb in zip(bufs, nbufs)
                    )
                    return merged, k + 1

                out, _ = lax.while_loop(
                    pcond,
                    pbody,
                    (tuple(_pack(list(ls), plan)), jnp.zeros((), jnp.int32)),
                )
                return _unpack(list(out), plan, L)
            out, _ = lax.while_loop(
                wcond, wbody, (tuple(ls), jnp.zeros((), jnp.int32))
            )
            return list(out)

        flat_chunk = jax.make_jaxpr(batched_chunk)(*leaves)

        # eliminate i1 vectors: bool leaves become i32 carriers at the
        # kernel boundary and every logic op inside runs bitwise on i32
        # (core/bool32.py — the Mosaic layout pass check-fails on i1
        # logic chains and i1<->i32 converts, bisected)
        bool_idx = frozenset(
            i for i, l in enumerate(leaves) if l.dtype == jnp.bool_
        )
        carrier_avals = [
            jax.ShapeDtypeStruct(
                l.shape, jnp.int32 if i in bool_idx else l.dtype
            )
            for i, l in enumerate(leaves)
        ]
        flat_chunk = bool32.transform(flat_chunk, carrier_avals)
        return flat_chunk, bool_idx, carrier_avals

    def build_chunk_call(leaves, treedef):
        """trace_chunk + constant hoisting to SMEM + the pallas_call.
        Returns ``(chunk_fn, consts_in)`` where ``chunk_fn(*leaves)``
        advances every lane by one chunk.  With ``lane_block`` the call
        becomes a 1-D grid over lane blocks (see make_kernel_run)."""
        n = len(leaves)
        L = leaves[0].shape[-1]
        Lb = lane_block or L
        if lane_block and not interpret and Lb % 1024:
            # per-lane scalars batch to 1-D [L] leaves, which XLA lays
            # out in 1024-wide tiles — a lane block that splits a tile
            # fails Mosaic's operand-layout check (measured offline:
            # "XLA layout T(1024) does not match Mosaic layout T(128)")
            raise ValueError(
                f"lane_block={Lb} must be a multiple of 1024 (the XLA "
                "tile width of 1-D per-lane leaves)"
            )
        if L % Lb:
            raise ValueError(
                f"lanes={L} must divide evenly by lane_block={Lb}"
                + (
                    " (under mesh= the chunk is built at the PER-DEVICE "
                    "local lane width, so lane_block applies per shard)"
                    if mesh is not None
                    else ""
                )
            )
        block_avals = [
            jax.ShapeDtypeStruct(l.shape[:-1] + (Lb,), l.dtype)
            for l in leaves
        ]
        flat_chunk, bool_idx, block_carriers = trace_chunk(
            block_avals, treedef
        )
        # out_shape is FULL width; the kernel sees block-shaped refs.
        # Derive it from trace_chunk's carriers (widen the lane axis)
        # so the carrier dtype rule has one source of truth.
        carrier_avals = [
            jax.ShapeDtypeStruct(a.shape[:-1] + (L,), a.dtype)
            for a in block_carriers
        ]

        const_info, smem_in, vmem_in = route_consts(flat_chunk.consts)
        consts_in = smem_in + vmem_in
        if Lb == L:
            grid_kwargs = {}
            state_specs = [pl.BlockSpec(memory_space=pltpu.VMEM)] * n
        else:
            def _spec_of(a):
                nd = len(a.shape)
                return pl.BlockSpec(
                    a.shape[:-1] + (Lb,),
                    lambda i, _nd=nd: (0,) * (_nd - 1) + (i,),
                )

            grid_kwargs = {"grid": (L // Lb,)}
            state_specs = [_spec_of(a) for a in carrier_avals]
        chunk_call = pl.pallas_call(
            partial(_kernel_body, flat_chunk.jaxpr, const_info, n),
            out_shape=[
                jax.ShapeDtypeStruct(a.shape, a.dtype)
                for a in carrier_avals
            ],
            in_specs=state_specs + const_specs(const_info),
            out_specs=state_specs,
            input_output_aliases={i: i for i in range(n)},
            interpret=interpret,
            # Mosaic's scoped-vmem budget defaults to 16 MiB; the
            # whole-Sim-resident kernel's temporaries pass that around
            # L≈2048 lanes (measured offline: 20.4M @ 2048, 24.0M @
            # 4096) while v5e has 128 MiB of VMEM.  Budget for the
            # bench's L=4096 with headroom; harmless when interpret or
            # on CPU (ignored).
            compiler_params=(
                None
                if interpret
                else pltpu.CompilerParams(
                    vmem_limit_bytes=_vmem_limit_bytes(lane_block)
                )
            ),
            **grid_kwargs,
        )

        def chunk_fn(*ls):
            boxed = [
                l.astype(jnp.int32) if i in bool_idx else l
                for i, l in enumerate(ls)
            ]
            outs = chunk_call(*boxed, *consts_in)
            return [
                (o != 0) if i in bool_idx else o for i, o in enumerate(outs)
            ]

        return chunk_fn, consts_in

    _validated = []

    def run(sims):
        # Host-level driver, NOT for use under an outer jit.  The whole
        # kernel path — tracing, Mosaic lowering AND compilation — must
        # happen with x64 off: under x64, loop counters, weak Python-int
        # literals and iinfo bounds materialize as int64 (Mosaic's 64->32
        # convert rule recurses forever), and Mosaic's own lower_fun
        # helpers re-trace reduction identities as f64.  Lowering runs at
        # first call of the inner jit, so the first chunk invocation sits
        # inside this scope too.  Init (u64 seed mixing) stays outside,
        # under the session's x64 setting.
        if not _validated:
            # debug tier: enforce the _vswitch zero-merge invariant
            # structurally — every self-gated handler is a bitwise no-op
            # under gate=False on a concrete lane-0 Sim (eager, once per
            # kernel build; a violation corrupts OTHER lanes only under
            # vmap, far from its cause)
            from cimba_tpu.utils import dbc

            if dbc.debug_enabled() and not any(
                isinstance(l, jax.core.Tracer)
                for l in jax.tree.leaves(sims)
            ):
                cl.validate_gated_handlers(
                    spec, jax.tree.map(lambda x: x[0], sims)
                )
            _validated.append(True)
        with config.x64_scope(False):
            return _run(sims)

    _built = {}  # (treedef, leaf avals) -> (chunk_jit, alive_jit)

    def _lane_specs(leaves):
        from jax.sharding import PartitionSpec as P

        (axis,) = mesh.axis_names
        return tuple(
            P(*([None] * (l.ndim - 1) + [axis])) for l in leaves
        )

    def _get_built(leaves, treedef):
        key = (treedef, tuple((l.shape, str(l.dtype)) for l in leaves))
        if key not in _built:
            if mesh is None:
                chunk_fn, _ = build_chunk_call(leaves, treedef)
                chunk_jit = jax.jit(chunk_fn)
            else:
                # per-device kernel: build the chunk at LOCAL lane width
                # (L is a static kernel shape), then shard_map it over
                # the minor lane axis
                from jax import shard_map

                n_dev = mesh.devices.size
                L = leaves[0].shape[-1]
                if L % n_dev:
                    raise ValueError(
                        f"lanes={L} must divide evenly over {n_dev} "
                        "devices"
                    )
                local = [
                    jax.ShapeDtypeStruct(
                        l.shape[:-1] + (L // n_dev,), l.dtype
                    )
                    for l in leaves
                ]
                chunk_fn, _ = build_chunk_call(local, treedef)
                specs = _lane_specs(leaves)
                sharded = shard_map(
                    lambda *ls: tuple(chunk_fn(*ls)),
                    mesh=mesh,
                    in_specs=specs,
                    out_specs=specs,
                    check_vma=False,
                )
                chunk_jit = jax.jit(lambda *ls: list(sharded(*ls)))
            vcond1 = jax.vmap(cond)  # lane-first, for host-side liveness
            alive_jit = jax.jit(
                lambda *ls: jnp.any(
                    vcond1(
                        jax.tree.unflatten(
                            treedef,
                            [jnp.moveaxis(l, -1, 0) for l in ls],
                        )
                    )
                )
            )
            if spec.boundary_pcs:
                # host-side application of boundary-block dispatches:
                # ONE ordinary XLA engine step (KERNEL_MODE off — MXU
                # matmuls, gathers, everything) on exactly the frozen
                # lanes, between chunks: the boundary phase the XLA
                # chunk runs in its program (cl.make_boundary_apply)
                apply = cl.make_boundary_apply(spec)

                def _boundary_apply(*ls):
                    sims = jax.tree.unflatten(
                        treedef, [jnp.moveaxis(l, -1, 0) for l in ls]
                    )
                    return [
                        jnp.moveaxis(l, 0, -1)
                        for l in jax.tree.leaves(apply(sims))
                    ]

                pending_any = jax.jit(
                    lambda *ls: jnp.any(
                        jax.tree.unflatten(
                            treedef, list(ls)
                        ).boundary_pending
                    )
                )
                boundary_jit = jax.jit(_boundary_apply)
            else:
                pending_any = boundary_jit = None
            _built[key] = (chunk_jit, alive_jit, pending_any, boundary_jit)
        return _built[key]

    def _run(sims):
        first, treedef = jax.tree.flatten(sims)
        # kernel boundary: lane axis moves last (XLA-side moveaxis, cheap)
        leaves = [jnp.moveaxis(l, 0, -1) for l in first]
        if mesh is not None:
            from jax.sharding import NamedSharding

            leaves = [
                jax.device_put(l, NamedSharding(mesh, s))
                for l, s in zip(leaves, _lane_specs(leaves))
            ]

        # Chunks are dispatched from the host: each call is bounded device
        # time (well under the runtime watchdog), the any-lane-live check
        # costs one tiny jitted reduction between chunks, and — decisive —
        # compilation of the chunk happens on its first call, still inside
        # the x64-off scope above.  The build (trace + lanelast + bool32 +
        # jit wrappers) is cached per leaf-shape so repeat runs — and a
        # warmup before a timed run — reuse the compiled chunk.
        chunk_jit, alive_jit, pending_any, boundary_jit = _get_built(
            leaves, treedef
        )
        # budget accounting: a boundary freeze can cut a chunk short (the
        # frozen lane stops stepping mid-chunk), so boundary rounds get
        # their own budget — each dispatches >= 1 event per pending lane,
        # bounding them by the same total-event budget instead of eating
        # the full-chunk counter 1:1
        it = rounds = 0
        max_rounds = max_chunks * chunk_steps
        while bool(alive_jit(*leaves)) and it < max_chunks:
            leaves = chunk_jit(*leaves)
            if boundary_jit is not None and bool(pending_any(*leaves)):
                leaves = boundary_jit(*leaves)
                rounds += 1
                if rounds >= max_rounds:
                    break
            else:
                it += 1
        if bool(alive_jit(*leaves)) and (
            it >= max_chunks or rounds >= max_rounds
        ):
            raise RuntimeError(
                f"make_kernel_run: lanes still live after {it} full chunks"
                f" (max {max_chunks} x {chunk_steps} events) and {rounds} "
                "boundary rounds — raise chunk_steps/max_chunks (a silent "
                "partial run would corrupt statistics)"
            )
        leaves = [jnp.moveaxis(l, -1, 0) for l in leaves]
        return jax.tree.unflatten(treedef, leaves)

    run.build_chunk_call = build_chunk_call
    run.trace_chunk = trace_chunk
    return run


def route_consts(consts):
    """Const routing, shared by the kernel and tools/mosaic_eqn_bisect.py
    so tool and kernel can never diverge on const placement.  Three kinds
    (python literals stay captured; arrays must become kernel inputs or
    pallas rejects the trace):

    * ``smem``: small integer tables / scalars — flattened, rebuilt by
      per-element scalar loads (dynamic indexing friendly);
    * ``vmem``: float or large arrays (e.g. the AWACS NN weights,
      lane-ready [K,n,1]) — whole-ref VMEM reads in natural shape, no
      reshape at the boundary (Mosaic shape casts from flattened form are
      exactly the crash class core/lanelast.py exists to avoid).

    Returns ``(const_info, smem_in, vmem_in)``; kernel arg order is
    ``*smem_in, *vmem_in`` after the state leaves.
    """
    const_info = []  # ("lit", value) | ("smem", (shape, size)) | ("vmem",)
    smem_in, vmem_in = [], []
    for c in consts:
        if not (hasattr(c, "dtype") and hasattr(c, "shape")):
            const_info.append(("lit", c))
            continue
        arr = jnp.asarray(c)  # normalizes TypedNdArray / np scalars
        if arr.ndim == 0 or (
            jnp.issubdtype(arr.dtype, jnp.integer) and arr.size <= 256
        ):
            const_info.append(("smem", (arr.shape, arr.size)))
            smem_in.append(jnp.reshape(arr, (-1,)))
        else:
            const_info.append(("vmem",))
            vmem_in.append(arr)
    return const_info, smem_in, vmem_in


def const_specs(const_info):
    """BlockSpecs for the const inputs, in ``*smem_in, *vmem_in`` order."""
    n_smem = sum(1 for info in const_info if info[0] == "smem")
    n_vmem = sum(1 for info in const_info if info[0] == "vmem")
    return [pl.BlockSpec(memory_space=pltpu.SMEM)] * n_smem + [
        pl.BlockSpec(memory_space=pltpu.VMEM)
    ] * n_vmem


def materialize_consts(const_info, const_refs):
    """Rebuild const VALUES from their kernel refs inside a kernel body.
    ``const_refs``: the refs for ``*smem_in, *vmem_in``, in order."""
    n_smem = sum(1 for info in const_info if info[0] == "smem")
    smem_refs = list(const_refs[:n_smem])
    vmem_refs = list(const_refs[n_smem:])
    consts = []
    for info in const_info:
        if info[0] == "smem":
            shape, size = info[1]
            ref = smem_refs.pop(0)
            vals = [ref[i] for i in range(size)]  # SMEM: scalar loads
            c = vals[0] if shape == () else jnp.stack(vals).reshape(shape)
            consts.append(c)
        elif info[0] == "vmem":
            consts.append(vmem_refs.pop(0)[...])
        else:
            consts.append(info[1])
    return consts


def _kernel_body(jaxpr, const_info, n, *refs):
    nc = sum(1 for info in const_info if info[0] != "lit")
    in_refs = refs[:n]
    out_refs = refs[n + nc :]
    consts = materialize_consts(const_info, refs[n : n + nc])
    # the jaxpr is bool32-transformed: ex-bool leaves are i32 at this
    # boundary already, and no i1 vector survives inside
    args = [r[...] for r in in_refs]
    outs = jax.core.eval_jaxpr(jaxpr, consts, *args)
    for r, leaf in zip(out_refs, outs):
        r[...] = leaf


def _maybe_dump_64bit(closed_jaxpr):
    """CIMBA_KERNEL_DEBUG=1: print every 64-bit-typed value in the chunk
    jaxpr with its source line (Mosaic has no 64-bit types; anything listed
    here will fail to lower)."""
    if not config.env_raw("CIMBA_KERNEL_DEBUG"):
        return
    seen = set()

    def _walk(jaxpr):
        for eqn in jaxpr.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if (
                    aval is not None
                    and hasattr(aval, "dtype")
                    and aval.dtype.itemsize == 8
                ):
                    src = jax._src.source_info_util.summarize(eqn.source_info)
                    key = (str(eqn.primitive), str(aval.dtype), src)
                    if key not in seen:
                        seen.add(key)
                        print("KERNEL64:", key)
            for val in eqn.params.values():
                vals = val if isinstance(val, (list, tuple)) else [val]
                for v2 in vals:
                    j2 = getattr(v2, "jaxpr", None)
                    if j2 is not None:
                        _walk(j2 if hasattr(j2, "eqns") else j2.jaxpr)

    _walk(closed_jaxpr.jaxpr)
