"""cimba-check: the static verification plane (docs/19_static_analysis.md).

Contracts pinned here:

* **seeded violations fire exactly**: every ``# expect: RULE`` marker in
  tests/fixtures/check_violations/ produces one finding at that line,
  nothing else fires, and ``# expect-suppressed`` lines land in the
  suppressed list (noqa honored AND counted) — via the real CLI.
* **the repo is clean**: ``tools/check.py --ast-only`` exits 0 on the
  default target set (the package + the operator CLIs).
* **--json round-trips**: schema version, counts consistent with the
  findings list, suppressed reported separately.
* **gate-registry completeness**: every ``trace_gate=True`` knob in
  ``config.ENV_KNOBS`` is claimed by a gate in ``check/gates.py`` and
  every gate-claimed knob is registered — a new trace gate cannot dodge
  the registry without failing here.
* **the registry sweep holds**: off == baseline jaxpr identity for
  every registered gate under BOTH dtype profiles (this sweep replaces
  the retired per-gate pins of test_trace/test_audit;
  one sentinel each remains there).
* **program lints**: donation/purity/weak-type clean on the shipped
  model, and each fires on a seeded-bad program.
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "check_violations")

_EXPECT = re.compile(r"#\s*expect(-suppressed)?:\s*(CHK\d+)")


def _run_cli(*args, timeout=120):
    return subprocess.run(
        [sys.executable, os.path.join("tools", "check.py"), *args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout,
    )


def _expected_markers():
    """``(expected_findings, expected_suppressed)`` as
    {(relpath, line, rule)} from the fixture tree's markers."""
    want, want_sup = set(), set()
    for fn in sorted(os.listdir(FIXTURES)):
        if not fn.endswith(".py"):
            continue
        rel = os.path.join(
            "tests", "fixtures", "check_violations", fn
        )
        with open(os.path.join(FIXTURES, fn)) as f:
            for i, line in enumerate(f, start=1):
                for m in _EXPECT.finditer(line):
                    (want_sup if m.group(1) else want).add(
                        (rel, i, m.group(2))
                    )
    assert want, "fixture tree has no expect markers?"
    return want, want_sup


@pytest.fixture(scope="module")
def fixture_report():
    proc = _run_cli("--ast-only", "--json", FIXTURES)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    return json.loads(proc.stdout)


def test_fixture_rules_fire_exactly(fixture_report):
    """Every seeded violation fires at its seeded (file, line, rule) —
    and NOTHING else fires: the marker set and the finding set are
    equal, so a rule regression (silent or over-firing) both fail."""
    want, want_sup = _expected_markers()
    got = {
        (f["path"], f["line"], f["rule"])
        for f in fixture_report["findings"]
    }
    got_sup = {
        (f["path"], f["line"], f["rule"])
        for f in fixture_report["suppressed"]
    }
    assert got == want, (sorted(got - want), sorted(want - got))
    assert got_sup == want_sup, (got_sup, want_sup)
    # every AST rule is represented in the fixture tree
    assert {r for _, _, r in want} == {
        "CHK001", "CHK002", "CHK003", "CHK004", "CHK005"
    }


def test_noqa_suppression_honored_and_counted(fixture_report):
    """noqa'd lines never reach findings, but are REPORTED as
    suppressed (a suppression is visible, not a silent hole)."""
    sup = fixture_report["suppressed"]
    assert len(sup) >= 2
    sup_keys = {(f["path"], f["line"]) for f in sup}
    find_keys = {(f["path"], f["line"]) for f in fixture_report["findings"]}
    assert not (sup_keys & find_keys)


def test_json_schema_roundtrip(fixture_report):
    d = fixture_report
    assert d["version"] == 1
    assert d["status"] == "findings"
    assert d["checked_files"] >= 5
    assert sum(d["counts"].values()) == len(d["findings"])
    for f in d["findings"] + d["suppressed"]:
        assert set(f) == {"rule", "path", "line", "message"}
        assert isinstance(f["line"], int) and f["line"] > 0


def test_repo_ast_front_clean():
    """The dogfood gate: the checker exits 0 on its own repo (package +
    operator CLIs), with the handful of justified suppressions
    reported."""
    proc = _run_cli("--ast-only")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_cli_exit_2_on_bad_path():
    proc = _run_cli("--ast-only", "no/such/path.py")
    assert proc.returncode == 2, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# gate registry
# ---------------------------------------------------------------------------


def test_gate_registry_completeness():
    """A CIMBA_* trace gate declared in config.ENV_KNOBS but not
    registered in check/gates.py fails here — new gates cannot forget
    the registry.  The reverse holds too: a gate cannot claim an
    unregistered knob."""
    from cimba_tpu import config
    from cimba_tpu.check import gates

    trace_gates = {
        name for name, knob in config.ENV_KNOBS.items()
        if knob["trace_gate"]
    }
    claimed = gates.claimed_env_knobs()
    assert trace_gates <= claimed, (
        f"trace-gate env knobs with no registered gate: "
        f"{sorted(trace_gates - claimed)} — register a Gate in "
        "cimba_tpu/check/gates.py with its off==baseline identity"
    )
    assert claimed <= set(config.ENV_KNOBS), (
        f"gates claim unregistered env knobs: "
        f"{sorted(claimed - set(config.ENV_KNOBS))}"
    )
    # the issue's gate list is the floor, not the ceiling
    names = {g.name for g in gates.GATES}
    assert {"trace", "metrics", "audit", "table_scan", "tune"} <= names
    assert not {"pack", "eventset_hier"} & names


def test_env_raw_registry():
    from cimba_tpu import config

    assert config.env_raw("CIMBA_TABLE_SCAN_BLOCK") == "128"
    os.environ["CIMBA_TABLE_SCAN_BLOCK"] = "64"
    try:
        assert config.env_raw("CIMBA_TABLE_SCAN_BLOCK") == "64"
    finally:
        del os.environ["CIMBA_TABLE_SCAN_BLOCK"]
    with pytest.raises(KeyError, match="not a registered"):
        config.env_raw("CIMBA_NOT_A_KNOB")


@pytest.mark.slow  # heavyweight: the same full both-profile gate sweep runs in the
# tools/ci.sh "static analysis" cell (tools/check.py) on every ci run
def test_gate_sweep_off_is_baseline_both_profiles():
    """The registry sweep: off == baseline jaxpr identity for EVERY
    registered gate under both dtype profiles (plus the ambient-env,
    env-off, and knob-liveness arms each gate declares).  Runs on the
    tiny sweep model for tier-1 budget; tools/ci.sh runs the same sweep
    on mm1 through the full CLI."""
    from cimba_tpu.check import gates

    findings, report = gates.sweep(model="tiny")
    assert findings == [], [f.format() for f in findings]
    for g in gates.GATES:
        for profile in gates.PROFILES:
            ran = report[f"{g.name}/{profile}"]
            assert "off==baseline" in ran, (g.name, profile, ran)


def test_gate_sweep_catches_a_lying_gate():
    """Negative arm: a gate whose off state is NOT the baseline (its
    off ctx enables the flight recorder) must produce a GATE finding —
    the sweep is a real check, not a tautology."""
    from cimba_tpu.check import gates

    liar = gates.Gate(
        name="liar", env=(), program="run",
        off_ctx=lambda: gates._trace_state(True),
        on_ctx=lambda: gates._trace_state(True),
    )
    findings, _ = gates.sweep(
        profiles=("f64",), gates=(liar,), model="tiny",
    )
    assert findings and findings[0].rule == "GATE"
    assert "off" in findings[0].message


# ---------------------------------------------------------------------------
# program lints
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ci.sh "static analysis" runs the full check battery (program lints included) every pass
def test_program_lints_clean_on_shipped_model():
    from cimba_tpu.check import jaxprlint

    findings, report = jaxprlint.check_programs(with_gates=False)
    assert findings == [], [f.format() for f in findings]
    assert set(report["programs"]) == {
        "mm1/f64", "mm1/f32", "awacs/f64", "awacs/f32"}


def test_donation_lint_fires_on_undonated_program():
    import jax

    from cimba_tpu.check import jaxprlint

    sims = {"x": jax.numpy.arange(4.0)}
    undonated = jax.jit(lambda s: {"x": s["x"] + 1.0})
    found = jaxprlint.donation_findings(undonated, sims, "fx")
    assert found and found[0].rule == "JXL001"


def test_purity_lint_fires_on_callback_and_gather():
    import jax
    import jax.numpy as jnp

    from cimba_tpu.check import jaxprlint

    def with_callback(x):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct((), jnp.float64), x
        )

    jaxpr = jax.make_jaxpr(with_callback)(jnp.float64(1.0))
    found = jaxprlint.purity_findings(jaxpr, "fx")
    assert any(
        f.rule == "JXL002" and "pure_callback" in f.message
        for f in found
    )

    def with_gather(x):
        return x[jnp.array([0, 2])]

    jaxpr2 = jax.make_jaxpr(with_gather)(jnp.arange(4.0))
    found2 = jaxprlint.purity_findings(jaxpr2, "fx", gather_budget=0)
    assert any(
        f.rule == "JXL002" and "gather" in f.message for f in found2
    )
    # a registered budget silences exactly the budgeted count
    assert not jaxprlint.purity_findings(jaxpr2, "fx", gather_budget=1)


def test_weak_type_lint_fires_on_weak_scalar():
    import jax.numpy as jnp

    from cimba_tpu.check import jaxprlint

    strong = {"t": jnp.float64(1.0)}
    assert not jaxprlint.weak_type_findings(strong, "fx")
    weak = {"t": 1.0}   # a bare Python scalar: weak-typed
    found = jaxprlint.weak_type_findings(weak, "fx")
    assert found and found[0].rule == "JXL003"
    assert "t" in found[0].message  # the offending leaf path is named
