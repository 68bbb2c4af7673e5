"""Program size as a first-class cost (docs/25_compile_wall.md).

The compile wall is invisible in wall-clock benchmarks until it is hit:
a program whose TEXT grows with a model dimension (the dense ``[P, ...]``
table dispatch before the scan-over-rows arm) compiles fine at dev scale
and then takes >25 minutes at AWACS scale on the kernel path
(BENCH_NOTES round 5).  This module makes the growth measurable *before*
any compile: a probe that traces and lowers a program — never compiles,
never executes — and reports

* ``eqns`` — jaxpr equation count, recursing into sub-jaxprs (the
  check/jaxprlint walker, so JXL004's budget and this probe can never
  disagree on what an equation is);
* ``jaxpr_bytes`` — the jaxpr pretty-printed text size;
* ``hlo_bytes`` — the lowered module text size (StableHLO);
* ``hlo_proto_bytes`` — the serialized HLO proto size when the backend
  exposes it (0 otherwise);
* ``trace_s`` / ``lower_s`` — wall seconds for the two stages.

Surfaces: ``tools/program_size.py`` (CLI), ``tune/measure.py`` arm
reports, and the serve/store manifest (next to ``footprint_bytes``).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ProgramSize:
    eqns: int
    jaxpr_bytes: int
    hlo_bytes: int
    hlo_proto_bytes: int
    trace_s: float
    lower_s: float

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "ProgramSize":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)})


def count_eqns(jaxpr) -> int:
    """Total equation count including sub-jaxprs (scan/while/pjit bodies
    and friends) — the same walk JXL004 budgets against."""
    from cimba_tpu.check.jaxprlint import collect_primitives

    return sum(collect_primitives(jaxpr).values())


def measure(fn, *avals, lower: bool = True) -> ProgramSize:
    """Probe ``fn`` at abstract arguments (arrays or ShapeDtypeStructs):
    trace, optionally lower, report sizes.  Nothing compiles or runs —
    at AWACS scale the *compile* is the wall this probe exists to
    predict, so the probe itself must stay cheap."""
    import jax

    t0 = time.perf_counter()
    closed = jax.make_jaxpr(fn)(*avals)
    trace_s = time.perf_counter() - t0
    eqns = count_eqns(closed.jaxpr)
    jaxpr_bytes = len(str(closed).encode())
    hlo_bytes = 0
    hlo_proto_bytes = 0
    lower_s = 0.0
    if lower:
        t0 = time.perf_counter()
        lowered = jax.jit(fn).lower(*avals)
        lower_s = time.perf_counter() - t0
        hlo_bytes = len(lowered.as_text().encode())
        try:
            proto = lowered.compiler_ir("hlo").as_serialized_hlo_module_proto()
            hlo_proto_bytes = len(proto)
        except Exception:
            hlo_proto_bytes = 0  # dialect not exposed on this backend
    return ProgramSize(
        eqns=eqns, jaxpr_bytes=jaxpr_bytes, hlo_bytes=hlo_bytes,
        hlo_proto_bytes=hlo_proto_bytes,
        trace_s=round(trace_s, 4), lower_s=round(lower_s, 4),
    )


def fused_program_size(
    specs, params=(), *, lanes: int = 4, max_steps: int = 64,
    profile: Optional[str] = None, seed: int = 2026, lower: bool = True,
) -> ProgramSize:
    """Probe the fused superprogram of ``specs``
    (docs/26_wave_fusion.md): merge them through
    :func:`cimba_tpu.core.fuse.fuse_specs` and measure the merged
    spec's chunk program.  This is THE number the fusion trade buys
    its occupancy with — the JXL004 sublinearity budget
    (:func:`cimba_tpu.check.jaxprlint.fused_size_findings`) holds it
    under a fraction of the members' solo-program sum (the machinery
    is shared; only the block tables concatenate)."""
    from cimba_tpu.core import fuse as _fuse

    fused = _fuse.fuse_specs(specs)
    return chunk_program_size(
        fused.spec, params, lanes=lanes, max_steps=max_steps,
        profile=profile, seed=seed, lower=lower,
    )


def chunk_program_size(
    spec, params=(), *, lanes: int = 4, max_steps: int = 64,
    profile: Optional[str] = None, seed: int = 2026, lower: bool = True,
) -> ProgramSize:
    """Probe a model's chunk program (the serve/kernel unit of work) at
    ``lanes`` replications.  Builds only abstract values — no arrays are
    materialized."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from cimba_tpu import config
    from cimba_tpu.core import loop as cl

    ctx = config.profile(profile) if profile else contextlib.nullcontext()
    with ctx:
        sims = jax.eval_shape(
            jax.vmap(lambda r: cl.init_sim(spec, seed, r, params)),
            jnp.arange(lanes),
        )
        fn = cl.make_chunk(spec, max_steps=max_steps)
        return measure(fn, sims, lower=lower)
