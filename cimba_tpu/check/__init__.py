"""cimba-check: the static verification plane (docs/19_static_analysis.md).

Every hard bug in PRs 1-9 was an instance of a mechanically checkable
class: tracing-cache leakage across dtype profiles, ``id()`` semantics
leaking into persisted keys, torn reads in the threaded serving layer,
and gated features whose off state must stay jaxpr-identical.  This
package shifts those checks left of pytest — they run before anything
executes:

* :mod:`cimba_tpu.check.astlint` — stdlib-``ast`` lints over the repo's
  own source (no jax import): CHK001 persisted ``id()``, CHK002 lock
  discipline against declared must-hold maps, CHK003 blind exception
  swallows, CHK004 wall-clock/RNG in digest content paths, CHK005
  un-proxied ``CIMBA_*`` environment reads.
* :mod:`cimba_tpu.check.jaxprlint` — program-level lints over traced
  jaxprs (static with respect to execution): JXL001 donation coverage
  of chunk-program carries, JXL002 hot-path purity (no callbacks, no
  gathers), JXL003 weak-type hygiene of the packed carry.
* :mod:`cimba_tpu.check.gates` — the trace-time feature-gate registry:
  every gate (trace, metrics, audit, table scan, tune and the serving
  plane's host-side policies) registers
  once and the sweep auto-generates its off == baseline jaxpr-identity
  check under both dtype profiles, replacing N hand-written pins.

``tools/check.py`` is the CLI (exit 0 clean / 1 findings / 2 error,
``--json``, per-rule suppression via ``# cimba: noqa(RULE)``).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

__all__ = ["Finding", "JSON_VERSION", "findings_to_json"]

#: --json schema version (bump on incompatible layout changes)
JSON_VERSION = 1


@dataclasses.dataclass(frozen=True)
class Finding:
    """One checker finding: a rule firing at a source coordinate."""

    rule: str              # "CHK001".."CHK005", "JXL001".."JXL003", "GATE"
    path: str              # repo-relative where possible
    line: int              # 1-based; 0 = whole-file / program-level
    message: str
    suppressed: bool = False   # a `# cimba: noqa(RULE)` hit this line

    def format(self) -> str:
        sup = "  [suppressed]" if self.suppressed else ""
        return f"{self.path}:{self.line}: {self.rule} {self.message}{sup}"


def findings_to_json(
    findings: List[Finding],
    suppressed: List[Finding],
    *,
    checked_files: int,
    program_checks: Optional[dict] = None,
) -> dict:
    """The ``--json`` report body (schema :data:`JSON_VERSION`)."""

    def rec(f: Finding) -> dict:
        return {
            "rule": f.rule, "path": f.path, "line": f.line,
            "message": f.message,
        }

    counts: dict = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    out = {
        "version": JSON_VERSION,
        "status": "clean" if not findings else "findings",
        "checked_files": checked_files,
        "counts": counts,
        "findings": [rec(f) for f in findings],
        "suppressed": [rec(f) for f in suppressed],
    }
    if program_checks is not None:
        out["program_checks"] = program_checks
    return out
