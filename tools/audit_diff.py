#!/usr/bin/env python
"""Compare two run cards / digest trails (docs/18_audit.md).

Usage::

    python tools/audit_diff.py A.json B.json [--json]

``A``/``B`` are run cards (written by
``run_experiment_stream(audit=)`` or ``run_sweep(audit=)``) or bare
digest-trail JSON lists. The report names the FIRST divergent (wave,
chunk, carry-class), environment drift, and result-digest equality.

CI-friendly exit codes::

    0  identical (comparable, no trail divergence, results not unequal)
    1  divergence (trail or result digest differs)
    2  incomparable (different spec/geometry/kind) or usage error

Stdlib-fast: the diff logic lives in ``cimba_tpu/obs/audit.py`` (the
one in-repo definition), which is file-loaded directly so this tool
never pays the jax import.
"""

import argparse
import importlib.util
import json
import os
import sys


def _load_audit():
    """Load cimba_tpu/obs/audit.py WITHOUT importing the package (the
    package __init__ pulls jax; the diff half of audit.py is
    stdlib-only by design).  Falls back to the package import when the
    file is not beside this tool (installed-wheel usage)."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "..", "cimba_tpu", "obs", "audit.py",
    )
    if os.path.exists(path):
        spec = importlib.util.spec_from_file_location("_cimba_audit", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    from cimba_tpu.obs import audit

    return audit


def _version() -> str:
    """The cimba_tpu package version WITHOUT importing the package (the
    stdlib-fast property: this tool never pays the jax import).  Reads
    ``__version__`` out of the package __init__ beside this tool;
    installed-wheel usage falls back to importlib.metadata."""
    init = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "..", "cimba_tpu", "__init__.py",
    )
    if os.path.exists(init):
        with open(init) as f:
            for line in f:
                if line.startswith("__version__"):
                    return line.split("=", 1)[1].strip().strip("\"'")
    from importlib import metadata

    return metadata.version("cimba-tpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compare two run cards / digest trails"
    )
    ap.add_argument("a", nargs="?", help="run card (or trail list) JSON")
    ap.add_argument("b", nargs="?", help="run card (or trail list) JSON")
    ap.add_argument(
        "--json", action="store_true",
        help="emit the full report as JSON instead of text",
    )
    ap.add_argument(
        "--force", action="store_true",
        help="compare trails even when the cards look incomparable "
        "(different spec fingerprint / geometry)",
    )
    ap.add_argument(
        "--version", action="store_true",
        help="print the cimba_tpu package version (fleet provenance: "
        "pairs with run cards' env block) and exit",
    )
    args = ap.parse_args(argv)
    if args.version:
        print(_version())
        return 0
    if args.a is None or args.b is None:
        ap.error("two run cards (or trail lists) are required")

    audit = _load_audit()
    try:
        a = audit.load_run_card(args.a)
        b = audit.load_run_card(args.b)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"audit_diff: {e}", file=sys.stderr)
        return 2

    rep = audit.diff_cards(a, b)
    if args.json:
        print(json.dumps(rep, indent=2, default=str))
    else:
        for r in rep["reasons"]:
            print(f"incomparable: {r}")
        if rep["env_drift"]:
            ea, eb = a.get("env") or {}, b.get("env") or {}

            def _sched_view(card, k):
                s = card.get("schedule") or {}
                if k == "source":
                    return s.get("source")
                return (s.get("knobs") or {}).get(k)

            for k in rep["env_drift"]:
                if k.startswith("schedule."):
                    # a different dispatch schedule ran (docs/21):
                    # env-class drift, never divergence
                    knob = k.split(".", 1)[1]
                    print(
                        f"env drift: {k}: "
                        f"{_sched_view(a, knob)!r} vs "
                        f"{_sched_view(b, knob)!r}"
                    )
                else:
                    print(
                        f"env drift: {k}: {ea.get(k)!r} vs {eb.get(k)!r}"
                    )
        if rep.get("trail_skipped"):
            print(
                "trail comparison skipped: the schedule drift moved "
                "the chunk boundaries (result digests still compared)"
            )
        if rep["seeds_differ"]:
            print(
                f"seed schedule differs: {a.get('seed_schedule')} vs "
                f"{b.get('seed_schedule')}"
            )
        d = rep["first_divergence"]
        if d is not None:
            print(
                f"FIRST DIVERGENCE at wave {d.get('wave')} chunk "
                f"{d.get('chunk')} class(es) {','.join(d['classes'])} "
                f"(trail row {d['index']}; lengths {rep['trail_len']})"
            )
            if "a" in d:
                print(f"  a: {d['a']}")
                print(f"  b: {d['b']}")
        if rep["result_equal"] is False:
            print(
                f"result digest differs: {a.get('result_digest')} vs "
                f"{b.get('result_digest')}"
            )
        if rep["identical"]:
            print(
                f"identical: {rep['trail_len'][0]} trail rows match"
                + (
                    ", result digests equal"
                    if rep["result_equal"] else ""
                )
            )

    if not rep["comparable"] and not args.force:
        return 2
    if rep["first_divergence"] is not None or rep["result_equal"] is False:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
