"""Dynamic indexing as one-hot select/reduce — the Mosaic-safe idiom.

Written scalar-style (one replication) like the rest of the engine and
batched by vmap.  ``arr[i]`` / ``arr.at[i].set(v)`` with a traced index
lower to ``gather`` / ``scatter`` HLOs once vmapped, and Mosaic supports
almost none of that (only full same-shape ``take_along_axis``).  A one-hot
compare + select + reduce over the small component axes (event slots,
processes, guard slots — all <= a few hundred) expresses the same thing
with ops every backend vectorizes; under vmap the lane dimension rides
along untouched.  On the VPU this is also *faster* than a gather for these
sizes: a handful of full-width vector ops, no serialized address math.

All helpers accept an optional ``pred``: ``dset(a, i, v, pred)`` is
``a.at[i].set(jnp.where(pred, v, a[i]))`` fused into the mask — the
dominant call pattern in the engine's handlers.

Out-of-range semantics differ from jnp deliberately: a negative or too-big
index matches no slot, so reads return the dtype's zero and writes are
no-ops.  Every engine call site either pre-clips or guards with ``pred``;
"no match -> no effect" is the *safer* default for the -1 sentinel handles
threaded through the loop.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from cimba_tpu import config

_I32 = jnp.int32


def kfori(lo: int, hi, body, init):
    """``lax.fori_loop`` outside kernel mode; a while-loop on an unbatched
    scalar counter inside it.  jax lowers a static-trip-count fori as
    ``lax.scan``, and scan's vmap batching rule normalizes every carry's
    batch axis to 0 — under the lane-LAST mega-kernel layout that wraps
    the loop in transposes of every carried leaf, which the Mosaic layout
    pass check-fails on (measured round 2, tools/mosaic_eqn_bisect).  The
    while form keeps carries in their batched layout: its condition reads
    only the counter, which vmap leaves unbatched, so the lowered
    condition is the scalar Mosaic requires."""
    if not config.KERNEL_MODE:
        return lax.fori_loop(lo, hi, body, init)

    def wbody(carry):
        k, c = carry
        return k + jnp.int32(1), body(k, c)

    return lax.while_loop(
        lambda kc: kc[0] < hi, wbody, (jnp.int32(lo), init)
    )[1]


def bwhere(pred, x, y):
    """``jnp.where`` with a lower-rank bool ``pred``, Mosaic-safe.

    Broadcasting a bool against a higher-rank operand inserts a minor
    dim on an i1 vector, which Mosaic only supports for 32-bit types;
    routing the rank expansion through int32 sidesteps it.  Semantically
    identical to ``jnp.where(pred, x, y)``.

    The expanded predicate is memoized per (pred, shape) under
    :func:`oh_cache` — a tree-select merge applies ONE predicate to many
    leaves, and without the memo every leaf of a given shape re-emitted
    the reshape/broadcast/compare chain (measured round 4: the mask
    plumbing, not the selects, was the majority of merge ops).
    """
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    p = jnp.asarray(pred)
    rank = max(x.ndim, y.ndim)
    extra = rank - p.ndim
    if x.dtype == jnp.bool_ and y.dtype == jnp.bool_:
        # bool select entirely in i32: Mosaic's select_n on i1 payloads
        # needs an i32->i1 truncation it does not support, and elementwise
        # i1 and/or chains mix mask layouts the layout pass check-fails on
        # (measured: `layout.h Check failed: arr.size() >= layout_rank`
        # on the rank-1 `or` this used to emit) — so combine as 0/1 ints
        # and produce i1 once, from the trailing comparison: the xor form
        # ((x^y)&p)^y needs one op fewer than (p&x)|(~p&y)
        shape = jnp.broadcast_shapes(
            x.shape, y.shape, p.shape + (1,) * max(extra, 0)
        )
        pi = _pexp_i32(p, shape, max(extra, 0))
        xi = jnp.broadcast_to(x, shape).astype(_I32)
        yi = jnp.broadcast_to(y, shape).astype(_I32)
        return (((xi ^ yi) & pi) ^ yi) != 0
    if extra <= 0 or p.dtype != jnp.bool_:
        return jnp.where(p, x, y)
    shape = jnp.broadcast_shapes(x.shape, y.shape)
    return jnp.where(_pexp_mask(p, shape, extra), x, y)


def _pexp_i32(p, shape, extra: int):
    """``broadcast_to(p.astype(i32).reshape(p.shape + (1,)*extra), shape)``,
    memoized per (pred, shape) while :func:`oh_cache` is active."""
    return _cached(
        ("pexp_i32", shape, extra), (p,),
        lambda: jnp.broadcast_to(
            p.astype(_I32).reshape(p.shape + (1,) * extra), shape
        ),
    )


def _pexp_mask(p, shape, extra: int):
    """Rank-expanded i1 mask of ``shape`` from bool ``p``, via i32
    (Mosaic-safe), memoized per (pred, shape)."""
    return _cached(
        ("pexp_mask", shape, extra), (p,),
        lambda: _pexp_i32(p, shape, extra) != 0,
    )


def _expand_mask(mask, shape, extra: int):
    """bool mask -> bool of ``shape`` without i1 rank-expansion.

    Memoized per (mask, shape): reads/writes at one cached one-hot reuse
    the expansion across every same-shaped component array they touch."""
    if extra == 0:
        if mask.shape == tuple(shape):
            return mask
        return _cached(
            ("mexp", shape, 0), (mask,),
            lambda: jnp.broadcast_to(mask, shape),
        )
    return _pexp_mask(mask, shape, extra)


# One-hot memo: the dispatcher reads/writes many component arrays at the
# SAME traced index inside one step (procs.pc, .status, .prio ... all at
# pid p), and every dget/dset re-derived the iota==i mask — at AWACS
# scale ([P]=1001) the dominant per-access cost.  Keyed by (dims, id) of
# the live index tracer; entries hold a strong ref to the index so ids
# cannot be reused while cached.  Enabled around kernel-mode step
# tracing (pallas_run), where the trace is built once per spec; the
# bounded leak of one trace's masks is reclaimed by oh_cache_clear().
_oh_cache = None


def oh_cache_enable() -> None:
    global _oh_cache
    _oh_cache = {}


def oh_cache_clear() -> None:
    global _oh_cache
    _oh_cache = None


import contextlib as _contextlib


@_contextlib.contextmanager
def oh_cache():
    """Scope the one-hot memo around exactly one jaxpr trace."""
    oh_cache_enable()
    try:
        yield
    finally:
        oh_cache_clear()


def _key_of(i):
    # concrete ints key by value (a fresh const tracer per asarray call
    # would never hit); live tracers key by identity, pinned in the entry
    try:
        return ("v", int(i))
    except Exception:
        return ("t", id(i))


def _cached(key_dims, idx_objs, make):
    if _oh_cache is None:
        return make()
    # the CURRENT trace scopes the entry: a mask built inside a
    # while/cond body sub-trace must never be served to the enclosing
    # trace (leaked tracer) or vice versa
    from jax._src import core as _jcore

    trace = _jcore.trace_ctx.trace
    key = (id(trace), key_dims, tuple(_key_of(i) for i in idx_objs))
    hit = _oh_cache.get(key)
    if hit is None:
        hit = (make(), idx_objs, trace)  # refs pin the ids
        _oh_cache[key] = hit
    return hit[0]


def _oh1(n: int, i):
    """One-hot bool mask [n] for scalar index i (batched by vmap).

    A size-1 dim needs no compare: component ids come from build-time
    Refs, so a valid index over [1] is always 0 and the mask is
    constant-true (single-queue/resource models skip the iota+eq pass
    entirely)."""
    if n == 1:
        return jnp.ones((1,), jnp.bool_)
    i = jnp.asarray(i, _I32)
    return _cached(
        (n,), (i,),
        lambda: _iota((n,), 0) == i,
    )


def _iota(shape, axis: int):
    """``lax.broadcasted_iota`` memoized per (shape, axis): one-hots at
    DIFFERENT indices over the same table share the ramp."""
    return _cached(
        ("iota", shape, axis), (),
        lambda: lax.broadcasted_iota(_I32, shape, axis),
    )


def first_true32(mask):
    """Lowest True index (i32; ``mask.shape[0]`` when none) WITHOUT an
    arg-reduction.  ``lax.argmax`` over a mask with several True entries
    is a tie among equal maxima: XLA resolves ties lowest-index, but
    Mosaic's hardware arg-reduction lowering does not honor that rule —
    first on-device contact caught the spawn free-row pick choosing a
    different row than the XLA path, swapping two symmetric processes'
    trajectories (kernel-vs-XLA fuzz, seed 1).  Free-slot/row/column
    picks therefore use this explicit iota-min, whose tie semantics are
    backend-independent by construction.  Out-of-range on an all-False
    mask is safe at every call site: the derived one-hot is then
    all-False and the write/read it gates is masked off."""
    n = mask.shape[0]
    return jnp.min(jnp.where(mask, _iota((n,), 0), jnp.asarray(n, _I32)))


def _oh2(n0: int, n1: int, i0, i1):
    """One-hot bool mask [n0, n1] for a 2-D index (size-1 dims skip
    their compare — see :func:`_oh1`)."""
    i0 = jnp.asarray(i0, _I32)
    i1 = jnp.asarray(i1, _I32)

    def make():
        if n0 == 1 and n1 == 1:
            return jnp.ones((1, 1), jnp.bool_)
        if n0 == 1:
            return _iota((1, n1), 1) == i1
        if n1 == 1:
            return _iota((n0, 1), 0) == i0
        m0 = _iota((n0, n1), 0) == i0
        m1 = _iota((n0, n1), 1) == i1
        return m0 & m1

    keys = (() if n0 == 1 else (i0,)) + (() if n1 == 1 else (i1,))
    if not keys:
        return make()
    return _cached((n0, n1), keys, make)


def _reduce_pick(mask, arr):
    """Sum-reduce ``arr`` where ``mask``, over the mask's dims.

    With a one-hot (or empty) mask this *is* the indexed read; zero when
    nothing matches.  Bool arrays reduce with any() to stay bool.
    """
    k = mask.ndim
    m = _expand_mask(mask, arr.shape, arr.ndim - k)
    if arr.dtype == jnp.bool_:
        return jnp.any(m & arr, axis=tuple(range(k)))
    # dtype pinned: under x64, jnp.sum would promote i32 -> i64
    return jnp.sum(jnp.where(m, arr, jnp.zeros((), arr.dtype)),
                   axis=tuple(range(k)), dtype=arr.dtype)


# --- scan-over-rows table dispatch (docs/25_compile_wall.md) ----------------
#
# Dense one-hot dispatch materializes every access as full-table-width
# ops.  That is the right trade for event slots and guard tables, but it
# puts the process-table height P into every access's program text, and
# on the kernel path Mosaic tile-unrolls those ``[P, Lb]`` vector ops —
# AWACS (P=1001) is compile-prohibitive at the lane-block grid
# (BENCH_NOTES round 5).  With ``CIMBA_TABLE_SCAN`` on, accesses to axes
# strictly taller than ``CIMBA_TABLE_SCAN_BLOCK`` run a counted loop over
# fixed-size row blocks instead: dynamic-slice one block (the loop
# counter is unbatched, so vmap emits a slice, never a gather), apply the
# SAME one-hot pick/write within the owning block, write the block back.
# Emitted program text then references one ``[B, ...]`` block regardless
# of table height, and results stay bitwise identical: reads accumulate
# the same zeros the dense sum adds, writes put back non-matching rows
# unchanged, and the block-ownership predicate keeps the clamped tail
# block's overlap write-once while preserving the out-of-range no-op.
# (One documented exception: blocked ``dadd``/``dadd2`` can wash a
# ``-0.0`` result to ``+0.0`` in the tail block's overlap rows — only
# when the added value is itself a signed zero.)
#
# The loop rides :func:`kfori`, so the XLA path lowers it as a scan and
# the mega-kernel keeps the scalar-counter while form Mosaic needs.


def _blk(n: int):
    """``(block, n_blocks)`` when the scan engages on an axis of height
    ``n`` (knob on AND the axis strictly taller than the block), else
    ``None`` — small tables stay dense, which is both the perf answer
    and the small-P structural-inertness contract."""
    if n <= 1 or not config.table_scan_enabled():
        return None
    B = config.table_scan_block()
    if n <= B:
        return None
    return B, -(-n // B)


def _blk2(n0: int, n1: int):
    """``(axis, block, n_blocks)`` for a 2-D table, blocking the taller
    engaging axis (the engine's 2-D tables are ``[components, slots]``,
    so axis 1 is the one that scales), else ``None``."""
    for ax, n in ((1, n1), (0, n0)):
        b = _blk(n)
        if b is not None:
            return (ax,) + b
    return None


def _blk_start(k, B: int, n: int):
    """Unbatched i32 start row of block ``k``, clamped so the tail block
    stays in range when ``B`` does not divide ``n`` (the resulting
    overlap is kept write-once by :func:`_blk_own`)."""
    return jnp.minimum(k * jnp.asarray(B, _I32), jnp.asarray(n - B, _I32))


def _blk_own(i, k, B: int, start):
    """Within-block index of row ``i`` under block ``k``'s ownership;
    ``-1`` (no one-hot match) when block ``k`` does not own row ``i``.
    Ownership is ``i div B == k`` with truncating division: every
    out-of-range or gated-off index (the ``-1`` sentinel included) owns
    no block, reproducing the dense helpers' no-op semantics."""
    i = jnp.asarray(i, _I32)
    own = lax.div(i, jnp.asarray(B, _I32)) == k
    return jnp.where(own, i - start, jnp.asarray(-1, _I32))


def _acc_pick(acc, mask, blk):
    """Accumulate one block's one-hot pick into ``acc`` (OR for bool —
    any() keeps bool — and the dense sum's add for everything else)."""
    r = _reduce_pick(mask, blk)
    return acc | r if blk.dtype == jnp.bool_ else acc + r


def _scan_get1(arrs, i):
    """Blocked ``dget`` over several same-height tables at ONE shared
    index: a single block loop slices each table once per block and
    applies one shared within-block one-hot."""
    n = arrs[0].shape[0]
    B, nb = _blk(n)
    accs = tuple(jnp.zeros(a.shape[1:], a.dtype) for a in arrs)

    def body(k, accs):
        start = _blk_start(k, B, n)
        m = _oh1(B, _blk_own(i, k, B, start))
        return tuple(
            _acc_pick(acc, m, lax.dynamic_slice_in_dim(a, start, B, 0))
            for a, acc in zip(arrs, accs)
        )

    return list(kfori(0, nb, body, accs))


def _scan_set1(arrs, i, vals, pred=True, add=False):
    """Blocked ``dset``/``dadd`` over several same-height tables at ONE
    shared (gated) index.  The gate always folds into the index here —
    a blocked axis is wide by construction, and ``-1`` owns no block."""
    n = arrs[0].shape[0]
    B, nb = _blk(n)
    if pred is not True:
        i = _gate_idx(i, pred)

    def body(k, arrs_k):
        start = _blk_start(k, B, n)
        m = _oh1(B, _blk_own(i, k, B, start))
        outs = []
        for a, v in zip(arrs_k, vals):
            blk = lax.dynamic_slice_in_dim(a, start, B, 0)
            if add:
                me = _expand_mask(m, blk.shape, blk.ndim - 1)
                v = jnp.asarray(v, a.dtype)
                blk = blk + jnp.where(me, v, jnp.zeros((), a.dtype))
            else:
                blk = _masked_write(blk, m, v, True)
            outs.append(lax.dynamic_update_slice_in_dim(a, blk, start, 0))
        return tuple(outs)

    return list(kfori(0, nb, body, tuple(arrs)))


def _blk_oh2(n0: int, n1: int, i0, i1, ax: int, B: int, k, start):
    """Within-block 2-D one-hot for block ``k`` of the blocked axis."""
    if ax == 0:
        return _oh2(B, n1, _blk_own(i0, k, B, start), i1)
    return _oh2(n0, B, i0, _blk_own(i1, k, B, start))


def _scan_get2(arr, i0, i1, ax: int, B: int, nb: int):
    n = arr.shape[ax]
    acc0 = jnp.zeros(arr.shape[2:], arr.dtype)

    def body(k, acc):
        start = _blk_start(k, B, n)
        m = _blk_oh2(arr.shape[0], arr.shape[1], i0, i1, ax, B, k, start)
        return _acc_pick(acc, m, lax.dynamic_slice_in_dim(arr, start, B, ax))

    return kfori(0, nb, body, acc0)


def _scan_set2(arr, i0, i1, v, pred, ax: int, B: int, nb: int, add=False):
    n = arr.shape[ax]
    if pred is not True:
        if ax == 0:
            i0 = _gate_idx(i0, pred)
        else:
            i1 = _gate_idx(i1, pred)

    def body(k, a):
        start = _blk_start(k, B, n)
        m = _blk_oh2(arr.shape[0], arr.shape[1], i0, i1, ax, B, k, start)
        blk = lax.dynamic_slice_in_dim(a, start, B, ax)
        if add:
            me = _expand_mask(m, blk.shape, blk.ndim - 2)
            vv = jnp.asarray(v, a.dtype)
            blk = blk + jnp.where(me, vv, jnp.zeros((), a.dtype))
        else:
            blk = _masked_write(blk, m, v, True)
        return lax.dynamic_update_slice_in_dim(a, blk, start, ax)

    return kfori(0, nb, body, arr)


def _scan_exchange1(arr, i, v, do_write, pred, B: int, nb: int):
    n = arr.shape[0]
    if pred is not True:
        i = _gate_idx(i, pred)
    v = jnp.asarray(v, arr.dtype)

    def body(k, carry):
        item, a = carry
        start = _blk_start(k, B, n)
        m = _oh1(B, _blk_own(i, k, B, start))
        blk = lax.dynamic_slice_in_dim(a, start, B, 0)
        it = _reduce_pick(m, blk)
        # the target row lives in exactly one block, so the owning
        # block's pick IS the full read and non-owning blocks write
        # back their rows bitwise-unchanged
        wv = jnp.where(do_write, v, it)
        blk = _masked_write(blk, m, wv, True)
        a = lax.dynamic_update_slice_in_dim(a, blk, start, 0)
        item = item | it if arr.dtype == jnp.bool_ else item + it
        return item, a

    item0 = jnp.zeros(arr.shape[1:], arr.dtype)
    return kfori(0, nb, body, (item0, arr))


def dget(arr, i):
    """``arr[i]`` (scalar if arr is 1-D, row if 2-D+) for a traced index."""
    if arr.shape[0] == 1:
        # single-member component table: the read is the row itself
        return lax.reshape(arr, arr.shape[1:])
    if _blk(arr.shape[0]) is not None:
        return _scan_get1([arr], i)[0]
    return _reduce_pick(_oh1(arr.shape[0], i), arr)


def dget_tree(tree, i):
    """:func:`dget` over every leaf of ``tree`` at ONE shared index.

    Dense mode is exactly ``jax.tree.map(lambda a: dget(a, i), tree)``
    (jaxpr character-identical to the historical per-leaf calls); scan
    mode serves every leaf from a single block loop — the grouped form
    is what keeps the blocked program's eqn count near the dense one's
    at the many-fields-one-pid dispatcher sites."""
    import jax

    leaves = jax.tree.leaves(tree)
    if (leaves and all(a.shape[0] == leaves[0].shape[0] for a in leaves)
            and leaves[0].shape[0] > 1 and _blk(leaves[0].shape[0]) is not None):
        outs = iter(_scan_get1(leaves, i))
        return jax.tree.map(lambda _: next(outs), tree)
    return jax.tree.map(lambda a: dget(a, i), tree)


def dset_tree(tree, i, vals, pred=True):
    """:func:`dset` over every leaf of ``tree`` at ONE shared gated
    index (``vals`` is a matching tree of written values).  Dense mode
    is exactly the per-leaf ``dset`` tree-map; scan mode shares one
    block loop across the leaves (see :func:`dget_tree`)."""
    import jax

    leaves = jax.tree.leaves(tree)
    if (leaves and all(a.shape[0] == leaves[0].shape[0] for a in leaves)
            and leaves[0].shape[0] > 1 and _blk(leaves[0].shape[0]) is not None):
        vleaves = jax.tree.leaves(vals)
        outs = iter(_scan_set1(leaves, i, vleaves, pred))
        return jax.tree.map(lambda _: next(outs), tree)
    return jax.tree.map(lambda a, v: dset(a, i, v, pred), tree, vals)


def dget2(arr, i0, i1):
    """``arr[i0, i1]`` for traced indices."""
    b2 = _blk2(arr.shape[0], arr.shape[1])
    if b2 is not None:
        return _scan_get2(arr, i0, i1, *b2)
    return _reduce_pick(_oh2(arr.shape[0], arr.shape[1], i0, i1), arr)


def _masked_write(arr, mask, v, pred):
    if pred is not True:
        mask = mask & pred
    m = _expand_mask(mask, arr.shape, arr.ndim - mask.ndim)
    v = jnp.asarray(v, arr.dtype)
    if arr.dtype == jnp.bool_:
        # i1 select_n needs a truncation Mosaic lacks; use logic
        return (m & jnp.broadcast_to(v, arr.shape)) | (~m & arr)
    return jnp.where(m, v, arr)


#: axes at least this wide gate writes through the index instead of a
#: mask AND — below it, losing the shared base one-hot costs more ops
#: than the table-wide AND saves elements
_GATE_IDX_MIN = 32


def _gate_idx(i, pred):
    """Fold a scalar write predicate into the index: out-of-range matches
    no slot, so ``pred=False -> i=-1`` makes the write a no-op with ONE
    scalar select instead of a table-wide ``mask & pred`` AND (on the
    256-slot queue ring that AND was a full-width op per put).  Only
    applied to wide axes (``_GATE_IDX_MIN``): narrow tables keep the
    shared cached one-hot plus a cheap AND.  Memoized per (i, pred) so
    several same-slot writes under one gate share one one-hot."""
    return _cached(
        ("gidx",), (i, pred),
        lambda: jnp.where(pred, jnp.asarray(i, _I32), jnp.asarray(-1, _I32)),
    )


def dset(arr, i, v, pred=True):
    """``arr.at[i].set(v)``, gated by ``pred`` (no-op where false)."""
    if _blk(arr.shape[0]) is not None:
        return _scan_set1([arr], i, [v], pred)[0]
    if pred is not True and arr.shape[0] >= _GATE_IDX_MIN:
        return _masked_write(arr, _oh1(arr.shape[0], _gate_idx(i, pred)), v, True)
    return _masked_write(arr, _oh1(arr.shape[0], i), v, pred)


def dset2(arr, i0, i1, v, pred=True):
    """``arr.at[i0, i1].set(v)``, gated by ``pred``."""
    n0, n1 = arr.shape[0], arr.shape[1]
    b2 = _blk2(n0, n1)
    if b2 is not None:
        return _scan_set2(arr, i0, i1, v, pred, *b2)
    if pred is not True:
        # fold the gate into whichever axis actually compares (size-1
        # axes skip their compare in _oh2 and cannot carry the gate)
        if n1 >= _GATE_IDX_MIN:
            i1, pred = _gate_idx(i1, pred), True
        elif n0 >= _GATE_IDX_MIN:
            i0, pred = _gate_idx(i0, pred), True
    return _masked_write(arr, _oh2(n0, n1, i0, i1), v, pred)


def dadd(arr, i, v, pred=True):
    """``arr.at[i].add(v)``, gated by ``pred``."""
    if _blk(arr.shape[0]) is not None:
        return _scan_set1([arr], i, [v], pred, add=True)[0]
    if pred is not True and arr.shape[0] >= _GATE_IDX_MIN:
        i, pred = _gate_idx(i, pred), True
    mask = _oh1(arr.shape[0], i)
    if pred is not True:
        mask = mask & pred
    m = _expand_mask(mask, arr.shape, arr.ndim - mask.ndim)
    v = jnp.asarray(v, arr.dtype)
    return arr + jnp.where(m, v, jnp.zeros((), arr.dtype))


def dexchange(arr, i, v, do_write, pred=True):
    """Read-or-write at ONE shared one-hot: returns
    ``(arr[i], arr.at[i].set(where(do_write, v, arr[i])))`` gated by
    ``pred``.

    Where ``do_write`` is false the written value is the read itself — a
    bitwise no-op — so a single mask (and a single full-width select)
    serves both verbs.  This is how the combined queue handler halves the
    ring's full-width ops: put and get share the compare and the write
    pass, differing only in a scalar select of the value.
    """
    n = arr.shape[0]
    b = _blk(n)
    if b is not None:
        return _scan_exchange1(arr, i, v, do_write, pred, *b)
    if pred is not True and n >= _GATE_IDX_MIN:
        i, pred = _gate_idx(i, pred), True
    mask = _oh1(n, i)
    if pred is not True:
        mask = mask & pred
    item = _reduce_pick(mask, arr)
    wv = jnp.where(do_write, jnp.asarray(v, arr.dtype), item)
    return item, _masked_write(arr, mask, wv, True)


def set_col(arr, k: int, col):
    """``arr.at[:, k].set(col)`` for a *static* column index — expressed as
    a select over a constant column mask (``.at[:, k]`` lowers to a scatter,
    which Mosaic has no rule for)."""
    m = lax.broadcasted_iota(_I32, (1, arr.shape[1]), 1) == k
    return jnp.where(m, col[:, None].astype(arr.dtype), arr)


def dadd2(arr, i0, i1, v, pred=True):
    """``arr.at[i0, i1].add(v)``, gated by ``pred``."""
    n0, n1 = arr.shape[0], arr.shape[1]
    b2 = _blk2(n0, n1)
    if b2 is not None:
        return _scan_set2(arr, i0, i1, v, pred, *b2, add=True)
    if pred is not True:
        if n1 >= _GATE_IDX_MIN:
            i1, pred = _gate_idx(i1, pred), True
        elif n0 >= _GATE_IDX_MIN:
            i0, pred = _gate_idx(i0, pred), True
    mask = _oh2(n0, n1, i0, i1)
    if pred is not True:
        mask = mask & pred
    m = _expand_mask(mask, arr.shape, arr.ndim - mask.ndim)
    v = jnp.asarray(v, arr.dtype)
    return arr + jnp.where(m, v, jnp.zeros((), arr.dtype))
