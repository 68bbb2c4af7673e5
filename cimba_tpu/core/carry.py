"""Packed while-loop carries: many narrow leaves -> a few wide buffers.

Measured on v5e (BENCH_NOTES round-5 floor probes): the per-iteration
fixed cost of a while loop scales super-linearly with the number of
narrow carried leaves — mm1's real 54-leaf carry costs ~135 us/step with
a TRIVIAL body, while the same bytes in a few wide f32 buffers cost
<1 us.  Packing trades ~2 slice + reshape (+bitcast) ops per leaf per
iteration — all wide-array structural ops — for that per-leaf overhead.

One plan serves both hot paths:

* the Pallas chunk kernel (``core/pallas_run.py``, lane-LAST leaves
  ``[comp..., L]`` -> ``[rows, L]`` buffers — ``CIMBA_KERNEL_PACK``);
* the XLA while-loop path on accelerator backends
  (``core/loop.make_run``, per-replication leaves ``[comp...]`` ->
  ``[rows]`` buffers, vmapped after; see docs/11_dispatch_cost.md).

Same-width leaves share one buffer per dtype class: f32; i32 with u32
rows riding along via same-width bitcast (bitcast is bitwise, selects
and copies do not interpret the payload); f64/i64 classes exist for the
exact-profile XLA path (the kernel path can never produce them — Mosaic
has no 64-bit types, so its plans degenerate to the historical f32/i32
pair and trace the identical jaxpr).  Bool leaves and anything else
pass through per-leaf.

Packing is a CARRY-LAYOUT change, never a semantic one: pack followed by
unpack is bitwise identity (pinned by tests/test_kernel_fuzz.py and
tests/test_xla_pack.py), so the loop body computes on exactly the leaves
it always did.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

#: dtype classes that pack, in buffer order: (name, buffer dtype,
#: member dtypes bitcast into it).  32-bit classes first so kernel-mode
#: plans (which can only contain them) keep their historical buffer
#: order bit-for-bit.
_CLASSES = (
    ("f32", jnp.float32, (jnp.float32,)),
    ("i32", jnp.int32, (jnp.int32, jnp.uint32)),
    ("f64", jnp.float64, (jnp.float64,)),
    ("i64", jnp.int64, (jnp.int64, jnp.uint64)),
)


def pack_plan(avals, lane_last: bool = True):
    """Static packing plan over carried leaves.

    ``lane_last=True`` treats the trailing axis as the lane axis L
    (kernel layout): a ``[s..., L]`` leaf becomes ``prod(s)`` rows of a
    ``[rows, L]`` buffer.  ``lane_last=False`` packs whole per-
    replication leaves: ``[s...]`` becomes ``prod(s)`` elements of a
    flat ``[rows]`` buffer (vmap then batches the buffers, not the
    leaves).

    Returns a dict: ``groups`` maps class name to the list of leaf
    indices packed in that buffer (row-major, stable order),
    ``passthrough`` lists leaf indices carried per-leaf, and
    ``meta[i] = (rows_i, packed_shape_i, dtype_i)`` for every leaf.
    """
    groups = {name: [] for name, _, _ in _CLASSES}
    passthrough = []
    meta = []
    for i, a in enumerate(avals):
        s = tuple(a.shape[:-1]) if lane_last else tuple(a.shape)
        r = 1
        for d in s:
            r *= int(d)
        meta.append((r, s, a.dtype))
        for name, _, members in _CLASSES:
            if any(a.dtype == m for m in members):
                groups[name].append(i)
                break
        else:
            passthrough.append(i)
    return {
        "groups": groups,
        "passthrough": passthrough,
        "meta": meta,
        "lane_last": lane_last,
    }


def n_buffers(plan) -> int:
    """Carried values in the packed layout (buffers + passthroughs)."""
    return sum(1 for _, idxs in plan["groups"].items() if idxs) + len(
        plan["passthrough"]
    )


def _pack_rows(x, r, s, lane_last: bool):
    """lane_last: [s..., L] -> [r, L]; else [s...] -> [r] (reshape
    touches leading dims only in the lane-last form — the Mosaic-clean
    direction)."""
    if lane_last:
        L = x.shape[-1]
        if s == ():
            return lax.reshape(x, (1, L))
        if len(s) == 1:
            return x
        return lax.reshape(x, (r, L))
    if s == ():
        return lax.reshape(x, (1,))
    if len(s) == 1:
        return x
    return lax.reshape(x, (r,))


def pack(leaves, plan):
    """leaves (original order) -> packed carry list:
    [f32 buffer?, i32 buffer?, f64?, i64?, *passthrough leaves]."""
    lane_last = plan["lane_last"]
    out = []
    for name, dt, _ in _CLASSES:
        idxs = plan["groups"][name]
        if not idxs:
            continue
        parts = []
        for i in idxs:
            r, s, dtype = plan["meta"][i]
            p = _pack_rows(leaves[i], r, s, lane_last)
            if dtype != dt:  # u32/u64 rows ride the int buffer bitwise
                p = lax.bitcast_convert_type(p, dt)
            parts.append(p)
        out.append(
            parts[0] if len(parts) == 1 else lax.concatenate(parts, 0)
        )
    for i in plan["passthrough"]:
        out.append(leaves[i])
    return out


def unpack(packed, plan, L=None):
    """Inverse of :func:`pack`: packed carry list -> leaves in original
    order (row slices + bitcast + reshape, all wide-array structural
    ops).  ``L`` is the lane width (required for lane-last plans)."""
    lane_last = plan["lane_last"]
    n = len(plan["meta"])
    leaves = [None] * n
    k = 0
    for name, dt, _ in _CLASSES:
        idxs = plan["groups"][name]
        if not idxs:
            continue
        buf = packed[k]
        k += 1
        o = 0
        for i in idxs:
            r, s, dtype = plan["meta"][i]
            if len(idxs) == 1:
                p = buf
            elif lane_last:
                p = lax.slice(buf, (o, 0), (o + r, L))
            else:
                p = lax.slice(buf, (o,), (o + r,))
            o += r
            if dtype != dt:
                p = lax.bitcast_convert_type(p, dtype)
            if lane_last:
                if s == ():
                    p = lax.reshape(p, (L,))
                elif len(s) != 1:
                    p = lax.reshape(p, s + (L,))
            else:
                if s == ():
                    p = lax.reshape(p, ())
                elif len(s) != 1:
                    p = lax.reshape(p, s)
            leaves[i] = p
    for i in plan["passthrough"]:
        leaves[i] = packed[k]
        k += 1
    return leaves
