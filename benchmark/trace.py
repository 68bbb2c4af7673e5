"""Reduce one profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData`` alone.  A TPU shows up as a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per operation
run on the chip and whose line ``XLA Modules`` holds one event per program
run; the harness's own host spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``) sit on the host plane, on the same
clock.  The window is the harness's ``bench.window`` span.

- busy: the union of the operation intervals inside the window, per chip,
  averaged over the chips the cell uses; idle share is 1 - busy / window;
- program time: device seconds per program name (``jit_chunk`` and so on),
  from the module line, clipped to the window;
- idle gaps: the intervals of the first chip with no operation running,
  each named by the ``bench.*`` span that covers the most of it, where
  that is at least half (``host`` where none does);
- collective time: device seconds of the operations that exchange data
  between chips (all-reduce, all-gather, reduce-scatter, all-to-all,
  collective-permute), for cells on several chips.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: where the chunk program is matched by name: the event loop's program
CHUNK_PROGRAM = re.compile(r"^jit_chunk\b")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|allreduce|allgather", re.I)
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def find(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _op_name(name: str) -> str:
    """``%fusion.1 = f32[...] fusion(...)`` is the operation ``fusion.1``."""
    m = re.match(r"%?([^\s=]+) = ", name)
    return m.group(1) if m else name


def _module_name(name: str) -> str:
    """``jit_chunk(123)`` and ``jit_chunk`` are one program."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(trace_dir: str, chips: int = 1) -> dict:
    """The trace's numbers (seconds throughout)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(find(trace_dir))
    spans, devices = [], {}
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in the trace")
    lo, hi = windows[0]
    spans = [sp for sp in spans if sp[0] != WINDOW]

    busy, ops, programs, program_runs, collective = [], {}, {}, [], 0.0
    first = None
    for idx in sorted(devices)[:chips]:
        plane = devices[idx]
        ivs = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                    if e <= s:
                        continue
                    ivs.append((s, e))
                    op = _op_name(ev.name)
                    ops[op] = ops.get(op, 0.0) + (e - s) * 1e-9 / chips
                    if _COLLECTIVE.search(ev.name):
                        collective += (e - s) * 1e-9 / chips
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                    if e <= s:
                        continue
                    name = _module_name(ev.name)
                    programs[name] = programs.get(name, 0.0) + (e - s) * 1e-9 / chips
                    if first is None or idx == first:
                        program_runs.append((name, s, e))
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if first is None:
            first = idx
            gaps = []
            prev = lo
            for s, e in merged + [[hi, hi]]:
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / len(busy) if busy else 0.0

    idle = sorted(((name_gap(spans, s, e), (e - s) * 1e-9) for s, e in
                   (gaps if devices else [])), key=lambda x: -x[1])
    program_runs.sort(key=lambda r: r[1])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "programs": programs,
        "program_runs": [(n, s * 1e-9, e * 1e-9) for n, s, e in program_runs],
        "gaps": [(s * 1e-9, e * 1e-9) for s, e in (gaps if devices else [])],
        "spans": [(n, s * 1e-9, e * 1e-9) for n, s, e in spans],
        "collective_s": collective,
        "device_ops": [[n, v] for n, v in
                       sorted(ops.items(), key=lambda x: -x[1])[:10]],
        "idle_gaps": [[n, v] for n, v in idle[:10]],
        "n_devices": len(devices),
    }


def name_gap(spans, s, e) -> str:
    """The span covering the most of the gap ``s..e``, the shortest among
    equals, where it covers at least half of it; ``host`` where none does
    (a short span inside a long gap did not hold the device back)."""
    cover = [(min(b, e) - max(a, s), a - b, n) for n, a, b in spans]
    cover = [c for c in cover if 2 * c[0] >= e - s]
    return max(cover)[2] if cover else "host"


def idle_between(gaps, start: float, end: float) -> float:
    """Device-idle seconds inside [start, end] (gap list in seconds)."""
    return sum(max(0.0, min(e, end) - max(s, start)) for s, e in gaps)


def chunk_program(name: str) -> bool:
    return bool(CHUNK_PROGRAM.search(name))
