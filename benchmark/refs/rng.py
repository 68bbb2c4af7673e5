"""Counter-based random streams, written from the published algorithms.

Threefry-2x32 with 20 rounds (Salmon et al., "Parallel Random Numbers: As
Easy as 1, 2, 3", SC'11) keyed per replication by MurmurHash3's fmix64 of
``seed + 0x9E3779B97F4A7C15 * replication``: the stream contract cimba-tpu
documents for its replications (README "Determinism"), so replication r's
n-th draw is a pure function of (seed, r, n).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
_PARITY = 0x1BD11BDA
_GOLDEN = 0x9E3779B97F4A7C15
_M64 = (1 << 64) - 1


def _fmix64(h: int) -> int:
    h ^= h >> 33
    h = (h * 0xFF51AFD7ED558CCD) & _M64
    h ^= h >> 33
    h = (h * 0xC4CEB9FE1A85EC53) & _M64
    h ^= h >> 33
    return h


def stream_keys(seeds, reps) -> tuple[np.ndarray, np.ndarray]:
    """Per-lane (key0, key1) u32 words for (seed, replication) pairs, on
    the host in Python integers (exact 64-bit arithmetic)."""
    k0, k1 = [], []
    for s, r in zip(seeds, reps):
        m = _fmix64((int(s) + _GOLDEN * int(r)) & _M64)
        k0.append(m & 0xFFFFFFFF)
        k1.append(m >> 32)
    return np.asarray(k0, np.uint32), np.asarray(k1, np.uint32)


def _rotl(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1):
    """One Threefry-2x32 block: (key, counter) -> two u32 words."""
    ks2 = k0 ^ k1 ^ jnp.uint32(_PARITY)
    x0, x1 = c0 + k0, c1 + k1
    inject = ((k1, ks2), (ks2, k0), (k0, k1), (k1, ks2), (ks2, k0))
    for i, (a, b) in enumerate(inject):
        for r in _ROT_A if i % 2 == 0 else _ROT_B:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + a
        x1 = x1 + b + jnp.uint32(i + 1)
    return x0, x1


def uniform(k0, k1, ctr, dtype):
    """The ctr-th uniform on [0, 1) of each lane's stream, from the high
    word, at the widest significand ``dtype`` holds exactly (24 bits in
    float32, 8 in bfloat16; float64 adds 21 bits of the low word)."""
    lo, hi = threefry2x32(k0, k1, ctr, jnp.zeros_like(ctr))
    bits = jnp.finfo(dtype).nmant + 1
    if bits > 32:
        return hi.astype(dtype) * 2.0 ** -32 + (lo >> jnp.uint32(11)).astype(
            dtype) * 2.0 ** -53
    return (hi >> jnp.uint32(32 - bits)).astype(jnp.int32).astype(dtype) * (
        jnp.asarray(2.0 ** -bits, dtype)
    )
