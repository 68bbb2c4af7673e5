#!/usr/bin/env python
"""Program-size probe CLI (docs/25_compile_wall.md).

Traces and lowers a model's chunk program — never compiles, never
executes — and prints the size numbers that predict the compile wall:
jaxpr equation count, jaxpr/HLO text bytes, HLO proto bytes, and the
trace/lower wall seconds.  The library half is
``cimba_tpu.obs.program_size`` (shared with tune/measure and the
serve store manifest).

Usage:
    python tools/program_size.py --model awacs --scale 1001 --scan on
    python tools/program_size.py --model awacs --scale 32 --scale 256 \
        --scale 1001 --scan both --profile f32 --json

Exit codes: 0 ok, 2 usage/model error.
"""

import argparse
import contextlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build_model(name: str, scale: int):
    """(spec, params) for a model at a size knob: AWACS target count,
    mm1/mmc object count (mm1/mmc table heights are capacity-fixed, so
    ``scale`` feeds the workload params instead)."""
    if name == "awacs":
        from cimba_tpu.models import awacs

        spec, _ = awacs.build(scale)
        return spec, awacs.params(2.0)
    if name == "mm1":
        from cimba_tpu.models import mm1

        spec, _ = mm1.build(record=False)
        return spec, mm1.params(scale)
    if name == "mmc":
        from cimba_tpu.models import mmc

        spec, _ = mmc.build(3)
        return spec, mmc.params(scale, 2.5, 1.0)
    raise SystemExit(f"unknown model {name!r} (one of: awacs, mm1, mmc)")


@contextlib.contextmanager
def scan_arm(arm: str, block):
    """Pin the table-scan tri-state for one probe arm ('on'/'off'/'env')."""
    from cimba_tpu import config

    prev = (config.TABLE_SCAN, config.TABLE_SCAN_BLOCK)
    try:
        if arm != "env":
            config.TABLE_SCAN = arm == "on"
        if block is not None:
            config.TABLE_SCAN_BLOCK = block
        yield
    finally:
        config.TABLE_SCAN, config.TABLE_SCAN_BLOCK = prev


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="awacs", help="awacs | mm1 | mmc")
    ap.add_argument("--scale", type=int, action="append",
                    help="model size knob (repeatable); default 32")
    ap.add_argument("--scan", default="env",
                    choices=("on", "off", "env", "both"),
                    help="table-scan arm; 'both' probes off and on")
    ap.add_argument("--block", type=int, default=None,
                    help="row-block size override (CIMBA_TABLE_SCAN_BLOCK)")
    ap.add_argument("--profile", default="f64", help="dtype profile")
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--max-steps", type=int, default=64)
    ap.add_argument("--no-lower", action="store_true",
                    help="trace only (skip HLO lowering)")
    ap.add_argument("--json", action="store_true", help="one JSON line per row")
    args = ap.parse_args(argv)

    from cimba_tpu.obs import program_size as ps

    scales = args.scale or [32]
    arms = ("off", "on") if args.scan == "both" else (args.scan,)
    rows = []
    for scale in scales:
        spec, params = build_model(args.model, scale)
        for arm in arms:
            with scan_arm(arm, args.block):
                r = ps.chunk_program_size(
                    spec, params, lanes=args.lanes, max_steps=args.max_steps,
                    profile=args.profile, lower=not args.no_lower)
            rows.append(dict(model=args.model, scale=scale, scan=arm,
                             **r.to_dict()))

    if args.json:
        for row in rows:
            print(json.dumps(row))
        return 0
    hdr = ("model", "scale", "scan", "eqns", "jaxpr_bytes", "hlo_bytes",
           "hlo_proto_bytes", "trace_s", "lower_s")
    print("  ".join(f"{h:>15}" for h in hdr))
    for row in rows:
        print("  ".join(f"{row[h]:>15}" for h in hdr))
    return 0


if __name__ == "__main__":
    sys.exit(main())
