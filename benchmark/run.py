"""The benchmark's one harness.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the configuration file it names, the traffic mix
``benchmark/traffic/<traffic>.json`` (whose ``driver`` picks the general
window driver ``benchmark/drivers/<driver>.py``), the adapter of the
configuration's model ``benchmark/sut/<model>.py``, the limits of the
comparison ``benchmark/limits/<cell>.json`` and one reader
``benchmark/metrics/<metric>.py`` per per-layer metric.  A new cell,
configuration or metric is new files and new entries, never an edit.

A run loads, warms every shape its window uses (``setup_s``, from the
start of this process to the opening of the window), measures for
``--seconds``, reads the device's peak memory, compares what the window
delivered with the plain reference, and prints one JSON line last.  With
``--trace 1`` the window runs under the profiler, and the line carries the
per-layer metrics instead of the end-to-end ones.  Anything but a TPU, or
fewer chips than the cell asks for, exits non-zero with no result.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROFILES = {"float32": "f32", "float64": "f64"}


class Refused(Exception):
    """No result can be measured here (no TPU, too few chips)."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One cell's inputs and the window's bounds, handed to its driver."""

    def __init__(self, root, bench, workload, seed, seconds, trace,
                 overrides=None):
        ov = overrides or {}
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        self.entry = cells[workload]
        self.name = workload
        cfgs = {c["name"]: c for c in bench["configs"]}
        self.cfg = {**_read(os.path.join(root, cfgs[self.entry["config"]]["file"])),
                    **ov.get("config", {})}
        bdir = os.path.join(root, "benchmark")
        self.mix = {**_read(os.path.join(bdir, "traffic",
                                         self.entry["traffic"] + ".json")),
                    **ov.get("traffic", {})}
        self.limits = {**_read(os.path.join(bdir, "limits", workload + ".json")),
                       **ov.get("limits", {})}
        self.sut = load_module(os.path.join(bdir, "sut", self.cfg["model"] + ".py"),
                               "bench_sut_" + self.cfg["model"])
        self.driver = load_module(
            os.path.join(bdir, "drivers", self.mix["driver"] + ".py"),
            "bench_driver_" + self.mix["driver"])
        self.bench, self.bdir = bench, bdir
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.trace_dir = os.path.join(root, ".bench_trace", workload)
        self.window_open = None
        self._window_span = None

    def note(self, what: str):
        """A set-up mark on standard error, in seconds since the start."""
        print(f"setup {time.monotonic() - _T_START:.3f} s: {what}",
              file=sys.stderr)

    def span(self, name: str):
        """A host span on the profiler's timeline (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open_window(self):
        """The window opens: set-up ends here.  A traced run traces the
        whole window; where the mix gives ``trace_s`` = [from, to], it
        traces the seconds from..to after the opening only: a thread of its
        own starts the profiler a second before ``from`` (its first second
        slows the host) and stops it at ``to``, so that no thread of the
        driver ever waits on the profiler."""
        self.window_open = time.monotonic()
        self._profiling = False
        self._tracer = None
        if not self.trace:
            return
        if "trace_s" not in self.mix:
            self._start_profiler()
            self._open_span()
            return
        self._closing = threading.Event()
        self._tracer = threading.Thread(target=self._trace_slice, daemon=True)
        self._tracer.start()

    def _trace_slice(self):
        start, stop = (float(x) for x in self.mix["trace_s"])

        def closed_before(t):
            left = self.window_open + t - time.monotonic()
            return self._closing.wait(max(0.0, left))

        if closed_before(start - 1.0):
            return
        self._start_profiler()
        if not closed_before(start):
            self._open_span()
            closed_before(stop)
        self._stop_profiler()

    def _start_profiler(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._profiling = True

    def _open_span(self):
        self._window_span = self.span("bench.window")
        self._window_span.__enter__()

    def _stop_profiler(self):
        if self._profiling:
            import jax

            if self._window_span is not None:
                self._window_span.__exit__(None, None, None)
                self._window_span = None
            t = time.monotonic()
            jax.profiler.stop_trace()
            self._profiling = False
            print(f"trace: stopped in {time.monotonic() - t!r} s", file=sys.stderr)

    def close_window(self):
        if self._tracer is not None:
            self._closing.set()
            self._tracer.join()
        else:
            self._stop_profiler()


def check_devices(chips: int, allow_cpu: bool = False) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise Refused(f"JAX finds no TPU here (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
    if not allow_cpu and devs[0].device_kind not in _read(
            os.path.join(ROOT, "benchmark", "peaks.json")):
        raise Refused(f"{devs[0].device_kind!r} is not in benchmark/peaks.json")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(chips: int):
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def compare(cell, units) -> tuple[bool, dict]:
    from benchmark import compare as cmp

    ref = load_module(os.path.join(os.path.dirname(cell.bdir),
                                   cell.cfg["reference"]),
                      "bench_ref_" + cell.cfg["name"])
    per_unit = []
    for res, lanes in units:
        out = ref.run(lanes, service=cell.sut.SERVICE, cap=int(cell.cfg["queue_cap"]))
        per_unit.append(cmp.gaps(cmp.pool_program(res, len(lanes.seed)),
                                 cmp.pool_lanes(out)))
    return cmp.verdict(cmp.worst(per_unit), cell.limits)


def per_layer(cell, run: dict) -> dict:
    out = {}
    for m in cell.bench["per_layer"]:
        if "workloads" in m and cell.name not in m["workloads"]:
            continue
        reader = load_module(os.path.join(cell.bdir, "metrics", m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def end_to_end(cell, run: dict, setup_s: float) -> dict:
    out = {}
    for m in cell.bench["end_to_end"]:
        if "workloads" in m and cell.name not in m["workloads"]:
            continue
        v = setup_s if m["name"] == "setup_s" else run["metrics"][m["name"]]
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None, *, root: str = ROOT, allow_cpu: bool = False,
         overrides: dict | None = None) -> dict:
    """Run one cell and print its result line; returns the result.
    ``allow_cpu`` and ``overrides`` are for the CPU rehearsal tests only:
    the command line reaches neither."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not allow_cpu:
        # before jax is imported: the cache inside the checkout, every
        # program however small, and no eviction (which needs an access-time
        # file beside every entry, and the cache holds only this cell's
        # programs), whatever the machine's environment says
        os.environ.update({
            "JAX_COMPILATION_CACHE_DIR": os.path.join(root, ".jax_cache"),
            "JAX_COMPILATION_CACHE_MAX_SIZE": "-1",
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    cell = Cell(root, bench, args.workload, args.seed, args.seconds,
                bool(args.trace), overrides)
    device = check_devices(int(cell.entry["chips"]), allow_cpu)

    import jax

    from cimba_tpu import config, serve

    if not allow_cpu:
        serve.maybe_enable_persistent_cache()
    config.use_profile(_PROFILES[cell.cfg["precision"]])
    # compiles and compile-cache reads, before and after the window opens
    seen = {p: {"compiles": 0, "compile_s": 0.0, "cache_hits": 0, "cache_read_s": 0.0}
            for p in ("setup", "window")}

    def phase():
        return seen["setup" if cell.window_open is None else "window"]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            phase()["compiles"] += 1
            phase()["compile_s"] += duration
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            phase()["cache_read_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            phase()["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        run = cell.driver.run(cell)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
    setup_s = cell.window_open - _T_START
    device["memory_peak_bytes"] = peak_bytes(int(cell.entry["chips"]))
    for p, c in seen.items():
        print(f"{p}: {c}", file=sys.stderr)
    print(f"compiles inside the window: {seen['window']['compiles']}",
          file=sys.stderr)
    print(f"compile cache: {jax.config.jax_compilation_cache_dir} max size "
          f"{jax.config.jax_compilation_cache_max_size}", file=sys.stderr)

    result = {"correct": False, "attempted": run["attempted"],
              "failed": run["failed"]}
    if cell.trace:
        from benchmark import trace as tr

        t = time.monotonic()
        red = tr.reduce(cell.trace_dir, int(cell.entry["chips"]))
        print(f"trace: {os.path.getsize(tr.find(cell.trace_dir))} bytes, "
              f"reduced in {time.monotonic() - t!r} s", file=sys.stderr)
        run["trace"] = red
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        result["metrics"] = per_layer(cell, run)
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
    else:
        result["metrics"] = end_to_end(cell, run, setup_s)
    result["device"] = device
    correct, checks = compare(cell, run.pop("units"))
    result["correct"] = correct
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result, default=float))
    sys.stdout.flush()
    return result


if __name__ == "__main__":
    sys.path[0] = ROOT
    try:
        main()
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        sys.exit(3)
