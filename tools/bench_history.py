#!/usr/bin/env python
"""Collate BENCH_r*.json rounds into a trend table + regression check.

The perf trajectory lives in per-round driver artifacts
(``BENCH_r01.json`` ...: ``{"n", "cmd", "rc", "tail", "parsed"}`` with
``parsed`` = the bench line(s) of that round) plus whatever run cards
(docs/18_audit.md) a round left behind — but nothing collates them.
This tool prints the round-by-round series per metric, with the
backend each round ran on, and checks the newest round against the
previous one for a regression.

Usage::

    python tools/bench_history.py [--dir .] [--cards DIR] [--tune DIR]
        [--compile] [--fused] [--metric mm1_events_per_sec]
        [--max-regression 10]

``--tune DIR`` additionally collates the autotuner's TuneReport JSONs
(``tunereport_*.json``, docs/21_autotune.md) into a per-(spec
fingerprint, backend, workload-bucket) winner table beside the BENCH
rounds, flagging groups whose winning schedule CHURNS across rounds.

``--compile`` additionally collates the compile-wall lines (the
retired pre-chip harness's ``compile_wall`` config,
docs/25_compile_wall.md) into a per-(metric, table height) trend of
compile wall seconds and program size across rounds, and flags a round
whose scan-arm compile wall or equation count regressed beyond
``--max-regression`` percent — the compile-cost twin of the events/s
regression check.

``--fused`` additionally collates the wave-fusion lines (the retired
pre-chip harness's ``serve_fused`` config, docs/26_wave_fusion.md)
into a per-round trend of the fused arm's events/s with the on-vs-off
occupancy and events ratios and the superprogram's sublinearity, and
flags a round whose ratios dropped beyond ``--max-regression`` percent
or whose sublinearity crossed the JXL004 budget.

Exit codes: 0 ok, 1 regression beyond ``--max-regression`` percent,
2 nothing to collate.  Stdlib-only (no jax import) — safe in any CI
leg.
"""

import argparse
import glob
import json
import os
import re
import sys


def load_rounds(d):
    """[(round_n, rc, [bench line dicts])] sorted by round."""
    out = []
    for path in sorted(glob.glob(os.path.join(d, "BENCH_r*.json"))):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            doc = json.load(open(path))
        except (OSError, json.JSONDecodeError) as e:
            print(f"warning: {path}: {e}", file=sys.stderr)
            continue
        n = doc.get("n", int(m.group(1)))
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            lines = [parsed]
        elif isinstance(parsed, list):
            lines = [x for x in parsed if isinstance(x, dict)]
        else:
            lines = []
        out.append((int(n), doc.get("rc"), lines))
    out.sort(key=lambda t: t[0])
    return out


def _load_json_dir(d, pattern):
    """Every ``pattern`` JSON object under ``d`` as [(path, doc)] —
    malformed files are warned about, never fatal (the one loader run
    cards and TuneReports share)."""
    out = []
    for path in sorted(glob.glob(os.path.join(d, pattern))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"warning: {path}: {e}", file=sys.stderr)
            continue
        if isinstance(doc, dict):
            out.append((path, doc))
    return out


def load_cards(d):
    """Run cards under ``d`` as [(path, card)]."""
    return _load_json_dir(d, "runcard_*.json")


def load_tune_reports(d):
    """TuneReports under ``d`` as [(path, doc)] sorted by creation
    time."""
    out = _load_json_dir(d, "tunereport_*.json")
    out.sort(key=lambda pd: pd[1].get("created_unix") or 0)
    return out


def _winner_str(doc):
    w = doc.get("winner") or {}
    knobs = {
        k: v for k, v in w.items()
        if k != "format" and v is not None
    }
    if doc.get("decision") != "tuned" or not knobs:
        return "default (hold)" if doc.get("decision") == "hold" \
            else "default"
    return ",".join(f"{k}={v}" for k, v in sorted(knobs.items()))


def print_tune_table(reports):
    """Per-(spec fingerprint, backend, device, bucket, workload)
    winner table across rounds, flagging winner CHURN — a fingerprint
    whose winning schedule flip-flops between reports is either a
    noisy machine or a workload on a knob boundary, and either way an
    operator should look before trusting the tuned entry."""
    groups = {}      # key -> [(created, winner_str, speedup, floor, path)]
    for path, doc in reports:
        wl = doc.get("workload") or {}
        key = (
            doc.get("spec_name"),
            (doc.get("spec_fingerprint") or "?")[:12],
            doc.get("backend"), doc.get("device_kind"),
            doc.get("bucket"), wl.get("label"),
        )
        groups.setdefault(key, []).append((
            doc.get("created_unix") or 0, _winner_str(doc),
            doc.get("speedup_frac"), doc.get("noise_floor_frac"),
            os.path.basename(path),
        ))
    print(f"\ntune reports: {len(reports)} "
          f"({len(groups)} fingerprint/workload groups)")
    churn = 0
    for key in sorted(groups, key=str):
        name, fp, backend, dev, bucket, label = key
        rows = groups[key]
        winners = [w for _, w, _, _, _ in rows]
        flip = len(set(winners)) > 1
        churn += flip
        head = (
            f"  {name} [{fp}] {backend}/{dev} bucket={bucket}"
            + (f" ({label})" if label else "")
            + ("  ** WINNER CHURN **" if flip else "")
        )
        print(head)
        for _, w, sp, fl, base in rows:
            sp_s = "-" if sp is None else f"{sp * 100:+.1f}%"
            fl_s = "-" if fl is None else f"{fl * 100:.1f}%"
            print(
                f"    {base}: winner {w} (speedup {sp_s}, "
                f"noise floor {fl_s})"
            )
    if churn:
        print(f"  {churn} group(s) show winner churn across rounds")
    return churn


def print_compile_table(rounds, max_regression):
    """Round-by-round compile-wall trend: one row per (metric, table
    height) with dense/scan wall seconds, the speedup, and the scan
    arm's equation count.  Returns the number of regressions — the
    newest round's scan wall or eqn count growing beyond
    ``max_regression`` percent over the previous round at the same
    height (compile cost is a budget like any other;
    docs/25_compile_wall.md)."""
    groups = {}   # (metric, n_processes) -> {round: detail-with-value}
    for n, _rc, lines in rounds:
        for line in lines:
            metric = line.get("metric") or ""
            if "compile_wall" not in metric:
                continue
            det = dict(line.get("detail") or {})
            det["speedup"] = line.get("value")
            key = (metric, det.get("n_processes"))
            groups.setdefault(key, {})[n] = det
    if not groups:
        print("\ncompile-wall trend: no compile_wall lines in any round")
        return 0
    print("\ncompile-wall trend (dense_s / scan_s / speedup / scan eqns):")
    regressions = 0
    for (metric, np_) in sorted(groups, key=str):
        rows = groups[(metric, np_)]
        print(f"  {metric} P={np_}")
        for n in sorted(rows):
            det = rows[n]
            scan_ps = (det.get("program_size") or {}).get("scan") or {}
            sp = det.get("speedup")
            print(
                f"    r{n}: {det.get('dense_wall_s', 0) or 0:.1f}s / "
                f"{det.get('scan_wall_s', 0) or 0:.1f}s / "
                + (f"{sp:.2f}x" if sp else "-")
                + f" / {scan_ps.get('eqns', '-')}"
            )
        have = sorted(rows)
        if len(have) >= 2:
            prev, last = rows[have[-2]], rows[have[-1]]
            for field, get in (
                ("scan wall", lambda d: d.get("scan_wall_s")),
                ("scan eqns", lambda d: (
                    (d.get("program_size") or {}).get("scan") or {}
                ).get("eqns")),
            ):
                pv, lv = get(prev), get(last)
                if not pv or not lv:
                    continue
                growth = (lv - pv) / pv * 100.0
                if growth > max_regression:
                    regressions += 1
                    print(
                        f"    ** {field} REGRESSION: r{have[-2]} "
                        f"{pv:.6g} -> r{have[-1]} {lv:.6g} "
                        f"(+{growth:.1f}% > {max_regression:.0f}%) **"
                    )
    return regressions


def print_fused_table(rounds, max_regression):
    """Round-by-round wave-fusion trend: the ``serve_fused`` lines
    (docs/26_wave_fusion.md) as one row per round with the fused arm's
    events/s, the on-vs-off occupancy and events ratios, and the
    superprogram's measured sublinearity (fused eqns / sum of solo
    eqns).  Returns the number of regressions — the newest round's
    occupancy or events ratio dropping beyond ``max_regression``
    percent of the previous round's, or its sublinearity crossing the
    JXL004 budget factor the bench pins.  Fusion is a PERF feature
    whose wins are exactly these two ratios, so the trend check guards
    them the way the headline metric check guards raw events/s."""
    rows = {}   # round -> (value, fusion-detail)
    for n, _rc, lines in rounds:
        for line in lines:
            if "serve_fused" not in (line.get("metric") or ""):
                continue
            det = line.get("detail") or {}
            rows[n] = (line.get("value"), det.get("fusion") or {})
    if not rows:
        print("\nwave-fusion trend: no serve_fused lines in any round")
        return 0
    print("\nwave-fusion trend (fused ev/s / occ ratio / ev ratio "
          "/ sublinearity):")
    regressions = 0
    for n in sorted(rows):
        v, fu = rows[n]
        ps = fu.get("program_size") or {}
        sub = ps.get("sublinearity")
        occ, ev = (
            fu.get("occupancy_ratio_on_vs_off"),
            fu.get("events_ratio_on_vs_off"),
        )
        print(
            f"  r{n}: {_fmt_rate(v)} ev/s / "
            + (f"{occ:.2f}x" if occ else "-") + " / "
            + (f"{ev:.2f}x" if ev else "-") + " / "
            + (f"{sub:.3f}" if sub is not None else "-")
        )
        budget = ps.get("budget_factor")
        if sub is not None and budget is not None and sub > budget:
            regressions += 1
            print(
                f"    ** SUBLINEARITY over JXL004 budget: "
                f"{sub:.3f} > {budget} **"
            )
    have = sorted(rows)
    if len(have) >= 2:
        prev, last = rows[have[-2]][1], rows[have[-1]][1]
        for field in (
            "occupancy_ratio_on_vs_off", "events_ratio_on_vs_off",
        ):
            pv, lv = prev.get(field), last.get(field)
            if not pv or not lv:
                continue
            drop = (pv - lv) / pv * 100.0
            if drop > max_regression:
                regressions += 1
                print(
                    f"    ** {field} REGRESSION: r{have[-2]} "
                    f"{pv:.3f} -> r{have[-1]} {lv:.3f} "
                    f"(-{drop:.1f}% > {max_regression:.0f}%) **"
                )
    return regressions


def _fmt_rate(v):
    if v is None:
        return "-"
    if v >= 1e6:
        return f"{v / 1e6:.1f}M"
    if v >= 1e3:
        return f"{v / 1e3:.0f}k"
    return f"{v:.0f}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="collate BENCH_r*.json into a trend table"
    )
    ap.add_argument("--dir", default=".", help="where BENCH_r*.json live")
    ap.add_argument(
        "--cards", default=None,
        help="also list run cards (runcard_*.json) from this directory",
    )
    ap.add_argument(
        "--tune", default=None,
        help="also collate autotuner TuneReports (tunereport_*.json) "
        "from this directory: per-fingerprint winner table + "
        "winner-churn flags (docs/21_autotune.md)",
    )
    ap.add_argument(
        "--compile", action="store_true",
        help="also collate compile-wall lines (the retired "
        "harness's compile_wall config) into a per-table-height trend with its own "
        "regression check (docs/25_compile_wall.md)",
    )
    ap.add_argument(
        "--fused", action="store_true",
        help="also collate wave-fusion lines (the retired "
        "harness's serve_fused config) into a per-round occupancy/events-ratio trend "
        "with its own regression check (docs/26_wave_fusion.md)",
    )
    ap.add_argument(
        "--metric", default="mm1_events_per_sec",
        help="the headline metric the regression check tracks",
    )
    ap.add_argument(
        "--max-regression", type=float, default=10.0,
        help="max tolerated drop (percent) of the headline metric vs "
        "the previous round before exit 1",
    )
    args = ap.parse_args(argv)

    rounds = load_rounds(args.dir)
    if not rounds:
        print(f"no BENCH_r*.json under {args.dir}", file=sys.stderr)
        return 2

    # -- per-metric series ---------------------------------------------------
    series = {}          # metric -> {round: (value, backend, profile)}
    for n, rc, lines in rounds:
        for line in lines:
            metric = line.get("metric")
            if metric is None:
                continue
            det = line.get("detail") or {}
            series.setdefault(metric, {})[n] = (
                line.get("value"), det.get("backend"),
                det.get("profile"),
            )

    all_rounds = [n for n, _, _ in rounds]
    print("bench history:", ", ".join(
        f"r{n}(rc={rc})" for n, rc, _ in rounds
    ))
    print()
    width = max((len(m) for m in series), default=10)
    header = f"{'metric':<{width}} " + " ".join(
        f"{'r' + str(n):>8}" for n in all_rounds
    )
    print(header)
    print("-" * len(header))
    for metric in sorted(series):
        cells = []
        for n in all_rounds:
            v = series[metric].get(n)
            cells.append(f"{_fmt_rate(v[0]) if v else '-':>8}")
        print(f"{metric:<{width}} " + " ".join(cells))
    for metric in sorted(series):
        backends = {
            n: v[1] for n, v in series[metric].items() if v[1]
        }
        if backends:
            print(f"  {metric} backends: " + ", ".join(
                f"r{n}={b}" for n, b in sorted(backends.items())
            ))
            break

    if args.tune:
        print_tune_table(load_tune_reports(args.tune))

    compile_regressions = 0
    if getattr(args, "compile"):
        compile_regressions = print_compile_table(
            rounds, args.max_regression
        )
    if args.fused:
        compile_regressions += print_fused_table(
            rounds, args.max_regression
        )

    if args.cards:
        cards = load_cards(args.cards)
        print(f"\nrun cards under {args.cards}: {len(cards)}")
        for path, card in cards:
            rd = card.get("result_digest")
            print(
                f"  {os.path.basename(path)}: kind={card.get('kind')}"
                f" label={card.get('label')}"
                f" trail={len(card.get('digest_trail') or [])}"
                + (f" result={rd[:16]}…" if rd else "")
            )

    # -- regression check ----------------------------------------------------
    s = series.get(args.metric, {})
    have = sorted(n for n, v in s.items() if v[0] is not None)
    if len(have) < 2:
        print(
            f"\nregression check: <2 rounds carry {args.metric} — skipped"
        )
        return 1 if compile_regressions else 0
    prev_n, last_n = have[-2], have[-1]
    prev_v, last_v = s[prev_n][0], s[last_n][0]
    drop_pct = (prev_v - last_v) / prev_v * 100.0
    verdict = "REGRESSION" if drop_pct > args.max_regression else "ok"
    print(
        f"\nregression check [{args.metric}]: r{prev_n} "
        f"{_fmt_rate(prev_v)} -> r{last_n} {_fmt_rate(last_v)} "
        f"({-drop_pct:+.1f}%; threshold -{args.max_regression:.0f}%) "
        f"{verdict}"
    )
    return 1 if (verdict == "REGRESSION" or compile_regressions) else 0


if __name__ == "__main__":
    sys.exit(main())
