"""Program-level lints over traced jaxprs — static w.r.t. execution.

These checks trace and lower real programs (``jax.make_jaxpr`` /
``jit.lower``) but never compile or execute anything.  Four rules plus
the gate-registry sweep (:mod:`cimba_tpu.check.gates`):

* **JXL001 — donation coverage.**  Every carry input of a
  ``make_chunk`` program must be donated/aliased (the PR 3 invariant:
  chunk n+1 aliases chunk n's buffers — zero inter-chunk copies, flat
  steady-state memory).  Verified against the lowered StableHLO's
  ``tf.aliasing_output`` markers: one per carry leaf, exactly.
* **JXL002 — hot-path purity.**  The chunk program's jaxpr must contain
  no host round-trips (``pure_callback``/``io_callback``/
  ``debug_callback``/print, infeed/outfeed) — a callback would
  serialize the very dispatch loop it observes — and no ``gather``
  primitives beyond the model's registered budget (shipped models
  compile to zero gathers; an unexpected gather is usually an advanced
  indexing slip that Mosaic will refuse and XLA will scatter-gather
  slowly).
* **JXL003 — weak-type hygiene.**  No weakly-typed leaf may enter the
  packed carry: a weak Python scalar re-specializes jit caches and is
  exactly the dtype-profile memo-leak hazard behind the PR 1
  ``_DtypeHandle`` bug.  Verified over the init program's abstract
  output under both dtype profiles.
* **JXL004 — program-size budget.**  The chunk program's total jaxpr
  equation count must stay under the model's registered ceiling
  (:data:`EQN_BUDGET`).  Program TEXT growth is the compile wall
  (docs/25_compile_wall.md): a ceiling breach means something started
  emitting per-row or per-step equations (a Python loop over processes,
  an unrolled scan) — the class of regression that compiles fine at dev
  scale and takes >25 minutes at AWACS scale.  Counted with the same
  walker as ``cimba_tpu.obs.program_size``.

Run by ``tools/check.py`` (skipped under ``--ast-only``) and tier-1's
tests/test_check.py.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from cimba_tpu.check import Finding

__all__ = [
    "BANNED_PRIMITIVES", "GATHER_BUDGET", "EQN_BUDGET",
    "FUSED_EQN_FACTOR",
    "donation_findings", "purity_findings", "weak_type_findings",
    "size_findings", "fused_size_findings", "check_programs",
    "collect_primitives",
]

#: primitives that must never appear in a chunk program (host
#: round-trips serialize the dispatch loop; debug prints don't survive
#: serialization into the program store)
BANNED_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed", "host_local_array_to_global_array",
})

#: per-model gather budget for JXL002 (primitive name "gather"); every
#: shipped model compiles to zero — raise a model's budget here ONLY
#: with a comment justifying the access pattern
GATHER_BUDGET: Dict[str, int] = {}

#: per-model chunk-program equation ceiling for JXL004.  Calibrated
#: ~1.3x over the measured default-knob counts (mm1 8675; awacs 8057
#: dense / 8990 scan-on, both profiles within a few eqns — its chunk
#: traces the step twice since the boundary phase runs in it: the
#: deferring per-event step and the boundary phase's full one; it was
#: 4273 / 4711 before) so dtype profiles and the table-scan arm fit, but
#: a per-row unroll (which multiplies the count by table height) cannot.
#: Raise only with a program_size measurement justifying the new floor.
EQN_BUDGET: Dict[str, int] = {"mm1": 11000, "awacs": 11700}

#: JXL004 sublinearity factor for fused superprograms
#: (docs/26_wave_fusion.md): a K-member fused chunk program's equation
#: count must stay under this fraction of the SUM of the K members'
#: solo counts — the members share ONE copy of the machinery (event
#: heap, guards, queues; the bulk of every chunk program) and only
#: their block tables concatenate, so the merged program must be far
#: sublinear in K.  Linear growth here means the machinery duplicated
#: per member — the compile wall fusion exists to avoid.
FUSED_EQN_FACTOR = 0.6

_ALIAS_MARKER = re.compile(r"tf\.aliasing_output")


def collect_primitives(jaxpr) -> Dict[str, int]:
    """Primitive-name histogram of a (Closed)Jaxpr, recursing into
    every sub-jaxpr (while bodies, pjit calls, cond branches)."""
    from jax.extend import core as jex_core

    counts: Dict[str, int] = {}

    def walk(jx):
        for eq in jx.eqns:
            counts[eq.primitive.name] = (
                counts.get(eq.primitive.name, 0) + 1
            )
            for v in eq.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub)

    def _sub_jaxprs(v):
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from _sub_jaxprs(x)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return counts


def _mm1_wave(profile: str):
    import jax
    import jax.numpy as jnp

    from cimba_tpu import config
    from cimba_tpu.core import loop as cl
    from cimba_tpu.models import mm1

    with config.profile(profile):
        spec, _ = mm1.build(record=False)
        sims = jax.vmap(
            lambda r: cl.init_sim(spec, 3, r, mm1.params(10))
        )(jnp.arange(4))
    return spec, sims


def donation_findings(
    chunk_j, sims, label: str,
) -> List[Finding]:
    """JXL001 for one jitted chunk program: every carry leaf aliased in
    the lowered text."""
    import jax

    n_leaves = len(jax.tree_util.tree_leaves(sims))
    text = chunk_j.lower(sims).as_text()
    n_aliased = len(_ALIAS_MARKER.findall(text))
    if n_aliased != n_leaves:
        return [Finding(
            rule="JXL001", path=f"program:{label}", line=0,
            message=(
                f"chunk program donates {n_aliased} of {n_leaves} "
                "carry leaves — every carry input must alias its "
                "output (the PR 3 zero-copy invariant; an undonated "
                "leaf doubles its steady-state memory and copies per "
                "chunk)"
            ),
        )]
    return []


def purity_findings(
    jaxpr, label: str, gather_budget: int = 0,
) -> List[Finding]:
    """JXL002 for one traced program."""
    counts = collect_primitives(jaxpr)
    out: List[Finding] = []
    hit = sorted(set(counts) & BANNED_PRIMITIVES)
    if hit:
        out.append(Finding(
            rule="JXL002", path=f"program:{label}", line=0,
            message=(
                f"host round-trip primitive(s) {hit} in a chunk "
                "program — callbacks/prints serialize the dispatch "
                "loop and cannot ride the program store"
            ),
        ))
    n_gather = counts.get("gather", 0)
    if n_gather > gather_budget:
        out.append(Finding(
            rule="JXL002", path=f"program:{label}", line=0,
            message=(
                f"{n_gather} gather primitive(s) in the chunk program "
                f"(budget {gather_budget}) — an unexpected gather is "
                "usually an advanced-indexing slip; register a budget "
                "in check.jaxprlint.GATHER_BUDGET only with a "
                "justified access pattern"
            ),
        ))
    return out


def size_findings(
    eqns: int, label: str, budget: Optional[int],
) -> List[Finding]:
    """JXL004 for one traced program: total equation count (recursive —
    count with the :func:`collect_primitives` walk or
    ``obs.program_size``) under the model's ceiling."""
    if budget is None:
        return []
    n = int(eqns)
    if n > budget:
        return [Finding(
            rule="JXL004", path=f"program:{label}", line=0,
            message=(
                f"chunk program has {n} jaxpr equations (budget "
                f"{budget}) — program text growth is the compile wall "
                "(docs/25_compile_wall.md); look for a Python loop "
                "over table rows or an unrolled scan, or raise "
                "check.jaxprlint.EQN_BUDGET with a program_size "
                "measurement justifying the new floor"
            ),
        )]
    return []


def fused_size_findings(
    fused_eqns: int, solo_eqns, label: str,
) -> List[Finding]:
    """JXL004 for one fused superprogram: the merged chunk program's
    equation count against ``FUSED_EQN_FACTOR`` x the sum of its
    members' solo counts (``solo_eqns`` — one entry per member).  The
    budget is derived, not tabled: it scales with whatever the members
    actually cost, so the pinned claim is pure SUBLINEARITY."""
    budget = int(sum(int(n) for n in solo_eqns) * FUSED_EQN_FACTOR)
    n = int(fused_eqns)
    if n > budget:
        return [Finding(
            rule="JXL004", path=f"program:{label}", line=0,
            message=(
                f"fused superprogram has {n} jaxpr equations — over "
                f"{FUSED_EQN_FACTOR}x the {sum(int(x) for x in solo_eqns)}"
                "-eqn sum of its members' solo programs (budget "
                f"{budget}).  Fusion must share one machinery copy "
                "and concatenate only block tables "
                "(docs/26_wave_fusion.md); near-linear growth means "
                "per-member duplication — the compile wall fusion "
                "exists to avoid"
            ),
        )]
    return []


def weak_type_findings(tree, label: str) -> List[Finding]:
    """JXL003 over a pytree of (abstract or concrete) carry values."""
    import jax
    from jax.api_util import shaped_abstractify

    out: List[Finding] = []
    leaves_with_path = jax.tree_util.tree_flatten_with_path(tree)[0]
    weak = [
        jax.tree_util.keystr(p)
        for p, leaf in leaves_with_path
        if shaped_abstractify(leaf).weak_type
    ]
    if weak:
        out.append(Finding(
            rule="JXL003", path=f"program:{label}", line=0,
            message=(
                f"weakly-typed leaves in the packed carry: {weak} — a "
                "weak Python scalar re-specializes jit caches per "
                "profile (the PR 1 dtype-memo leak); cast through "
                "config.TIME/REAL/COUNT at creation"
            ),
        ))
    return out


def check_programs(
    profiles: Tuple[str, ...] = ("f64", "f32"),
    with_gates: bool = True,
    gate_model: str = "mm1",
) -> Tuple[List[Finding], dict]:
    """The full program-lint battery over the shipped reference model
    (mm1, the model every historical pin used): donation + purity +
    weak types per dtype profile, plus the gate-registry sweep.
    Returns ``(findings, report)``."""
    import jax

    from cimba_tpu import config
    from cimba_tpu.runner import experiment as ex

    findings: List[Finding] = []
    report: dict = {"programs": {}}
    for profile in profiles:
        # trace under the SAME profile the Sim was built in — mixing
        # is exactly the cross-profile hazard JXL003 polices
        with config.profile(profile):
            spec, sims = _mm1_wave(profile)
            label = f"mm1/{profile}"
            chunk_j = ex._chunk_program(spec, None, False, 8, None)
            findings.extend(donation_findings(chunk_j, sims, label))
            jaxpr = jax.make_jaxpr(lambda s: chunk_j(s))(sims)
        findings.extend(purity_findings(
            jaxpr, label, GATHER_BUDGET.get("mm1", 0)
        ))
        findings.extend(size_findings(
            sum(collect_primitives(jaxpr).values()), label,
            EQN_BUDGET.get("mm1"),
        ))
        findings.extend(weak_type_findings(sims, label))
        report["programs"][label] = {
            "carry_leaves": len(jax.tree_util.tree_leaves(sims)),
            "checks": ["JXL001", "JXL002", "JXL003", "JXL004"],
        }
        # JXL004 additionally covers the model whose table height IS
        # the compile wall (awacs: [P, ...] tables); trace-only, small
        # P — the eqn count is P-independent unless something unrolls
        with config.profile(profile):
            from cimba_tpu.models import awacs as _awacs

            a_spec, _ = _awacs.build(16)
            a_label = f"awacs/{profile}"
            from cimba_tpu.obs import program_size as _ps

            a_size = _ps.chunk_program_size(
                a_spec, _awacs.params(2.0), profile=None, lower=False,
            )
        findings.extend(size_findings(
            a_size.eqns, a_label, EQN_BUDGET.get("awacs"),
        ))
        report["programs"][a_label] = {
            "eqns": a_size.eqns, "checks": ["JXL004"],
        }
    if with_gates:
        from cimba_tpu.check import gates as _gates

        gate_findings, gate_report = _gates.sweep(
            profiles, model=gate_model,
        )
        findings.extend(gate_findings)
        report["gates"] = gate_report
    return findings, report
