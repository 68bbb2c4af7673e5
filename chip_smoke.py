"""Drive cimba-tpu's main path once on a TPU, through its public API.

    python chip_smoke.py             # one chip: phases 1-5 below
    python chip_smoke.py --chips 4   # sharded replications on 4 chips
                                     # against the 1-device run, only

The quickest proof that the system still starts on the chip.  The phases:

1. device   -- the default device is a TPU; anything else exits non-zero.
2. runner   -- mm1 through ``run_experiment_stream`` at the round-5
               operating point (R=131072 lanes x N=16000 objects, f32)
               and under the exact f64 profile, checked against the
               native C++ oracle run on the host for the same (seed,
               replication ids), plus bit-identical ``uniform01``
               streams against the CPU backend.
3. serving  -- a ``serve.Service`` answers mm1, mg1 and awacs (1000
               targets, Pallas scorer) bitwise its direct calls; then a
               program-store save -> hydrate -> serve round trip with
               zero downgrades.
4. kernels  -- the mm1 chunk kernel and the awacs scorer compiled by
               Mosaic, against the XLA path and the plain-jnp trace.
5. result   -- the last line of standard output is one JSON object,
               ``{"ok": true, "device": {...}}``, printed only when every
               phase passed.

Each phase prints one JSON progress line: wall seconds, its compiles and
their seconds (from the program's compile counter, persistent-cache loads
included), events/s where it counts events, and the device's peak bytes
in use.  No timed window may hold a compile.  One process holds the chip
for the whole run and starts no child that needs it (the native oracle's
``make`` touches no device).  The phase functions take their sizes, so
the tests run them tiny on the CPU with ``interpret=True``; ``main``
never runs on a CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

SEED = 2026


class SmokeFailure(AssertionError):
    """A phase's output disagreed with its reference."""


def _check(cond, what):
    if not cond:
        raise SmokeFailure(what)


# -- measurement --------------------------------------------------------------

def compiled(sp) -> dict:
    """The compiles inside a finished program span
    (``cimba_tpu.obs.telemetry.span``), on any thread, from the program's
    compile counter: their count and their seconds."""
    from cimba_tpu.obs import telemetry

    n, secs = telemetry.compiles_between(sp.t0_ns, sp.t1_ns)
    return {"compiles": n, "compile_s": secs}


def peak_bytes():
    """``peak_bytes_in_use`` of the default device (None where the
    backend reports no memory stats, as the CPU does)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


# -- summary paths (module-level: program caches key on their identity) ------


def rep_mean_wait(sims):
    """One observation per replication, its mean wait; failed lanes
    (err != 0) contribute nothing.  Replications are independent, so a
    confidence interval over these is honest, where one over the pooled
    per-object waits (autocorrelated within a lane) would be too narrow."""
    import jax
    import jax.numpy as jnp

    from cimba_tpu.stats import summary as sm

    def one(w, err):
        s = sm.add(sm.empty(), w.m1)
        return jax.tree.map(
            lambda a, b: jnp.where(err == 0, a, b), s, sm.empty()
        )

    return jax.vmap(one)(sims.user["wait"], sims.err)


def awacs_detections(sims):
    """The awacs per-dwell detection-count summary."""
    return sims.user["detections"]


# -- phase 1 -----------------------------------------------------------------


def phase_device() -> dict:
    info = device_info()
    _check(
        info["platform"] == "tpu",
        f"the default device is {info['platform']!r}, not a TPU",
    )
    return info


# -- phase 2 -----------------------------------------------------------------


def _stream_timed(spec, n_objects, R, wave, chunk_steps, seed):
    """Warm the stream programs on short waves (set-up; two, so the
    later waves' replication-id offset is warm too), then time the full
    run with no compile inside the window."""
    import jax

    from cimba_tpu.models import mm1
    from cimba_tpu.obs.telemetry import span
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.serve import ProgramCache

    cache = ProgramCache(store=False)
    kw = dict(
        wave_size=wave, chunk_steps=chunk_steps, seed=seed,
        summary_path=rep_mean_wait, program_cache=cache,
    )
    t0 = time.perf_counter()
    with span("smoke.setup") as setup:
        jax.block_until_ready(
            ex.run_experiment_stream(
                spec, mm1.params(16), min(R, 2 * wave), **kw
            )
        )
    setup_wall = time.perf_counter() - t0
    with span("smoke.window") as window:
        t0 = time.perf_counter()
        res = ex.run_experiment_stream(spec, mm1.params(n_objects), R, **kw)
        jax.block_until_ready(res)
        wall = time.perf_counter() - t0
    n_window = compiled(window)["compiles"]
    _check(n_window == 0, f"{n_window} compiles inside the timed window")
    events = int(res.total_events)
    return res, {
        "R": R, "N": n_objects, "wave": wave, "chunk_steps": chunk_steps,
        "setup_s": setup_wall, **compiled(setup),
        "wall_s": wall, "events": events, "events_per_s": events / wall,
        "n_failed": int(res.n_failed),
    }


def _mean_hw(summary):
    from cimba_tpu.stats import summary as sm

    return float(sm.mean(summary)), float(sm.halfwidth(summary, 0.95))


def phase_runner(
    *, R=131072, N=16000, wave=131072, R64=8192, wave64=8192,
    chunk_steps=1024, oracle_reps=2048, lanes_exact=64,
    uniform_reps=(0, 1, 2, 3, 131071), n_draws=64, seed=SEED,
) -> dict:
    """mm1 through the stream runner in both profiles, against the
    native scalar oracle on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cimba_tpu import config, native
    from cimba_tpu.models import mm1
    from cimba_tpu.runner import experiment as ex

    out = {}
    with config.profile("f32"):
        spec32, _ = mm1.build(record=False)
        res32, out["f32"] = _stream_timed(
            spec32, N, R, wave, chunk_steps, seed
        )
    with config.profile("f64"):
        spec64, _ = mm1.build(record=False)
        res64, out["f64"] = _stream_timed(
            spec64, N, R64, wave64, chunk_steps, seed
        )

    # the host oracle: the same seed and replication ids, run by the
    # sequential C++ engine (no jax), one rep-mean per replication
    _check(native.available(), "the native oracle did not build")
    t0 = time.perf_counter()
    ora = [
        native.oracle_mm1(seed, r, N, 1.0 / 0.9, 1.0)
        for r in range(oracle_reps)
    ]
    host_wall = time.perf_counter() - t0
    means = np.array([o["mean"] for o in ora])
    o_mean = float(means.mean())
    o_hw = float(1.96 * means.std(ddof=1) / np.sqrt(len(means)))
    out["oracle"] = {
        "reps": oracle_reps, "mean": o_mean, "hw95": o_hw,
        "host_s": host_wall,
    }
    for prof, res in (("f32", res32), ("f64", res64)):
        m, hw = _mean_hw(res.summary)
        out[prof].update(mean_wait=m, hw95=hw)
        _check(
            abs(m - o_mean) <= hw + o_hw,
            f"{prof} pooled mean wait {m} +- {hw} disagrees with the "
            f"host oracle {o_mean} +- {o_hw}",
        )

    # per replication, exact profile: the first lanes against the
    # oracle's same replications -- counts exactly, moments to the
    # engine-vs-oracle contract of tests/test_native.py (libm and the
    # device's log1p differ in the last ulps)
    with config.profile("f64"):
        sims = ex.run_experiment(
            spec64, mm1.params(N), lanes_exact, seed=seed
        ).sims
        jax.block_until_ready(sims)
    err = np.asarray(sims.err)
    n_ev = np.asarray(sims.n_events)
    clock = np.asarray(sims.clock)
    w = jax.tree.map(np.asarray, sims.user["wait"])
    checked = 0
    _check(lanes_exact <= oracle_reps, "lanes_exact > oracle_reps")
    for r in range(lanes_exact):
        if err[r]:
            continue  # a ring overflow (models/mm1.py), counted below
        o = ora[r]
        _check(int(w.n[r]) == int(o["n"]) and int(n_ev[r]) == o["events"],
               f"replication {r}: counts differ from the oracle")
        _check(np.isclose(clock[r], o["clock"], rtol=1e-9, atol=0.0)
               and np.isclose(w.m1[r], o["mean"], rtol=1e-8, atol=0.0),
               f"replication {r}: clock {clock[r]}, mean wait {w.m1[r]} "
               f"vs the oracle's {o['clock']}, {o['mean']}")
        checked += 1
    _check(checked >= lanes_exact - 2,
           f"only {checked}/{lanes_exact} lanes finished cleanly")
    out["per_lane"] = {"lanes": lanes_exact, "matched": checked}

    # the determinism contract: uniform01 streams are bit-identical
    # across backends (README "Determinism")
    import cimba_tpu.random as cr

    def streams(reps):
        def one(rep):
            def draw(st, _):
                return cr.uniform01(st)

            _, u = jax.lax.scan(
                draw, cr.initialize(seed, rep), None, length=n_draws
            )
            return u

        return jax.vmap(one)(reps)

    reps = jnp.asarray(uniform_reps)
    with config.profile("f64"):
        dev_u = np.asarray(jax.jit(streams)(reps))
        with jax.default_device(jax.devices("cpu")[0]):
            cpu_u = np.asarray(jax.jit(streams)(jnp.asarray(uniform_reps)))
    _check(dev_u.view(np.uint64).tolist() == cpu_u.view(np.uint64).tolist(),
           "uniform01 streams differ from the CPU backend's")
    out["uniform01"] = {"reps": list(uniform_reps), "draws": n_draws,
                        "bit_identical": True}
    out["events_per_s"] = out["f32"]["events_per_s"]
    return out


# -- phase 3 -----------------------------------------------------------------


def _requests(mm1_R, mg1_R, awacs_R, N, n_targets, awacs_t_end,
              chunk_steps):
    from cimba_tpu import serve
    from cimba_tpu.models import awacs, mg1, mm1

    kw = dict(seed=SEED, chunk_steps=chunk_steps)
    return {
        "mm1": serve.Request(
            mm1.build(record=False)[0], mm1.params(N), mm1_R,
            wave_size=mm1_R, **kw,
        ),
        "mg1": serve.Request(
            mg1.build()[0], (1.25, 1.0, 1.5, N), mg1_R, wave_size=mg1_R,
            **kw,
        ),
        # awacs's nn_scores picks its Pallas scorer on a TPU
        "awacs": serve.Request(
            awacs.build(n_targets)[0], awacs.params(awacs_t_end), awacs_R,
            wave_size=awacs_R, summary_path=awacs_detections, **kw,
        ),
    }


def _direct(req, cache):
    from cimba_tpu.runner import experiment as ex

    return ex.run_experiment_stream(
        req.spec, req.params, req.n_replications, wave_size=req.wave_size,
        seed=req.seed, chunk_steps=req.chunk_steps,
        summary_path=req.summary_path, program_cache=cache,
    )


def phase_serving(
    *, mm1_R=4096, mg1_R=2048, awacs_R=256, N=2000, n_targets=1000,
    awacs_t_end=10.0, chunk_steps=1024,
) -> dict:
    """A Service answers three models bitwise their direct calls; then
    the program store saves, hydrates and serves with no downgrade.
    Under the f32 profile, the chip's width (f64 programs compile
    several times slower)."""
    from cimba_tpu import config

    with config.profile("f32"):
        return _serve_and_store(_requests(
            mm1_R, mg1_R, awacs_R, N, n_targets, awacs_t_end, chunk_steps
        ))


def _serve_and_store(reqs):
    import jax

    from cimba_tpu import serve
    from cimba_tpu.obs.audit import stream_result_digest
    from cimba_tpu.obs.telemetry import span

    cache = serve.ProgramCache(store=False)
    out = {}
    events = 0
    t0 = time.perf_counter()
    with span("smoke.serve") as cc:
        max_wave = max(r.n_replications for r in reqs.values())
        with serve.Service(max_wave=max_wave, cache=cache) as svc:
            handles = {k: svc.submit(r) for k, r in reqs.items()}
            served = {k: h.result(900) for k, h in handles.items()}
        for k, r in reqs.items():
            got = stream_result_digest(served[k])
            want = stream_result_digest(_direct(r, cache))
            _check(got == want, f"{k}: served digest {got} != direct {want}")
            _check(int(served[k].n_failed) == 0,
                   f"{k}: {int(served[k].n_failed)} failed replications")
            events += int(served[k].total_events)
            out[k] = {"R": r.n_replications, "digest": got,
                      "events": int(served[k].total_events)}
    out["served_wall_s"] = time.perf_counter() - t0
    out["serve_compile_s"] = compiled(cc)["compile_s"]

    # program store: save -> hydrate into a fresh cache -> serve
    req = reqs["mm1"]
    with tempfile.TemporaryDirectory(prefix="cimba_smoke_store_") as root:
        st = serve.ProgramStore(root)
        with span("smoke.store_save") as cc:
            report = st.save_programs(
                req.spec, req.params, req.n_replications,
                wave_sizes=(req.wave_size,), chunk_steps=req.chunk_steps,
                horizon_modes=("none",),
            )
        _check(report["downgrades"] == [],
               f"program store downgrades: {report['downgrades']}")
        hyd = serve.ProgramCache(store=st)
        serve.warm(hyd, req.spec, None, None, manifest=st,
                   chunk_steps=req.chunk_steps)
        with serve.Service(max_wave=req.wave_size, cache=hyd) as svc:
            res = svc.submit(req).result(900)
            pstats = svc.stats()["program_store"]
        jax.block_until_ready(res)
    digest = stream_result_digest(res)
    _check(digest == out["mm1"]["digest"],
           f"store-served digest {digest} != {out['mm1']['digest']}")
    _check(pstats["hits"] >= 1 and pstats["corrupt"] == 0
           and pstats["fallback_shapes"] == 0
           and pstats["downgrades"] == 0,
           f"program store stats {pstats}")
    out["store"] = {
        k: pstats[k] for k in (
            "hits", "misses", "corrupt", "fallback_shapes", "downgrades",
            "artifact_dispatches",
        )
    }
    out["store"]["save_compile_s"] = compiled(cc)["compile_s"]
    out["events_per_s"] = events / out["served_wall_s"]
    return out


# -- phase 4 -----------------------------------------------------------------


def kernel_matches_xla(xla, ker, what):
    """tests/test_kernel_fuzz.py's contract: every integer leaf bitwise,
    float accumulators within a few ulp of layout-dependent rounding."""
    import jax
    import numpy as np

    xl, kl = jax.tree.leaves(xla), jax.tree.leaves(ker)
    _check(len(xl) == len(kl), f"{what}: the Sim pytrees differ")
    for a, b in zip(xl, kl):
        a, b = np.asarray(a), np.asarray(b)
        if np.issubdtype(a.dtype, np.integer) or a.dtype == np.bool_:
            _check(np.array_equal(a, b), f"{what}: integer leaf differs")
        else:
            _check(np.allclose(a, b, rtol=5e-6, atol=1e-5, equal_nan=True),
                   f"{what}: float leaf beyond the few-ulp contract")


def phase_kernels(
    *, L=4096, N=2000, chunk_steps=512, n_targets=1000, interpret=False,
) -> dict:
    """The Pallas kernels, Mosaic-compiled (``interpret=False``), against
    the paths they replace."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cimba_tpu import config
    from cimba_tpu.core import loop as cl
    from cimba_tpu.core import pallas_run
    from cimba_tpu.models import awacs, mm1
    from cimba_tpu.obs.telemetry import span

    out = {}
    with config.profile("f32"):
        spec, _ = mm1.build(record=False)
        sims = jax.jit(jax.vmap(
            lambda r: cl.init_sim(spec, SEED, r, mm1.params(N))
        ))(jnp.arange(L))
        xla = jax.jit(jax.vmap(cl.make_run(spec)))(sims)
        jax.block_until_ready(xla)
        krun = pallas_run.make_kernel_run(
            spec, chunk_steps=chunk_steps, interpret=interpret
        )
        with span("smoke.kernel_first_call") as cc:
            t0 = time.perf_counter()
            ker = krun(sims)
            jax.block_until_ready(ker)
            first = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(krun(sims))
        wall = time.perf_counter() - t0
    kernel_matches_xla(xla, ker, "mm1 kernel")
    events = int(np.asarray(ker.n_events).sum())
    out["mm1_kernel"] = {
        "L": L, "N": N, "chunk_steps": chunk_steps, "events": events,
        **compiled(cc), "first_call_s": first, "wall_s": wall,
        "events_per_s": events / wall,
    }

    rng = np.random.default_rng(SEED)
    pos = jnp.asarray(rng.uniform(-80, 80, (n_targets, 2)), jnp.float32)
    vel = jnp.asarray(rng.normal(0, awacs.SPEED, (n_targets, 2)),
                      jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(awacs.nn_scores(pos, vel, use_pallas=False))
    ker_s = np.asarray(
        awacs.nn_scores(pos, vel, use_pallas=True, interpret=interpret)
    )
    diff = float(np.max(np.abs(ker_s - ref)))
    # MXU passes round f32 operands; 5e-3 on a [0, 1] score is far
    # below the ~0.1 a row or padding fault would show
    _check(ker_s.shape == ref.shape == (n_targets,) and diff <= 5e-3,
           f"awacs scorer differs from the jnp trace by {diff}")
    out["awacs_scorer"] = {"targets": n_targets, "max_abs_diff": diff}
    out["events_per_s"] = out["mm1_kernel"]["events_per_s"]
    return out


# -- the four-chip path --------------------------------------------------------


def phase_mesh(*, n_devices=4, R=65536, N=2000, L=4096, chunk_steps=512,
               interpret=False) -> dict:
    """Sharded replications (``run_experiment(mesh=...)``,
    ``make_sharded_experiment``, the kernel under a mesh) against the
    same seed and R on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cimba_tpu import config
    from cimba_tpu.core import loop as cl
    from cimba_tpu.core import pallas_run
    from cimba_tpu.models import mm1
    from cimba_tpu.obs.telemetry import span
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.stats import summary as sm

    _check(len(jax.devices()) >= n_devices,
           f"{len(jax.devices())} devices, {n_devices} asked for")
    mesh = ex.make_mesh(n_devices)
    out = {}
    with config.profile("f32"):
        spec, _ = mm1.build(record=False)
        params = mm1.params(N)
        with span("smoke.mesh") as cc:
            t0 = time.perf_counter()
            one = ex.run_experiment(spec, params, R, seed=SEED)
            jax.block_until_ready(one)
            t1 = time.perf_counter()
            many = ex.run_experiment(spec, params, R, seed=SEED, mesh=mesh)
            jax.block_until_ready(many)
            t2 = time.perf_counter()
        for name, a, b in (
            ("n_events", one.sims.n_events, many.sims.n_events),
            ("err", one.sims.err, many.sims.err),
        ):
            _check(np.array_equal(np.asarray(a), np.asarray(b)),
                   f"per-replication {name} differs under the mesh")
        for a, b in zip(jax.tree.leaves(one.sims.user["wait"]),
                        jax.tree.leaves(many.sims.user["wait"])):
            _check(np.array_equal(np.asarray(a), np.asarray(b)),
                   "a per-lane wait summary differs under the mesh")
        pooled_one = ex.pooled_summary(one.sims.user["wait"])
        fused = ex.make_sharded_experiment(spec, R, mesh)
        pooled_many, failed, events = fused(params, seed=SEED)
        _check(int(failed) == int(one.n_failed)
               and int(events) == int(one.total_events),
               "sharded failure or event totals differ")
        # merge order differs across shards: rounding, not results
        _check(float(pooled_one.n) == float(pooled_many.n)
               and np.isclose(float(sm.mean(pooled_one)),
                              float(sm.mean(pooled_many)), rtol=1e-5)
               and np.isclose(float(sm.variance(pooled_one)),
                              float(sm.variance(pooled_many)), rtol=1e-4),
               "pooled statistics beyond reduction-order tolerance")
        ev = int(one.total_events)
        out["xla"] = {
            "R": R, "N": N, "devices": n_devices, "events": ev,
            "one_device_s": t1 - t0, "mesh_s": t2 - t1,
            "mean_wait": float(sm.mean(pooled_many)),
        }

        sims = jax.jit(jax.vmap(
            lambda r: cl.init_sim(spec, SEED, r, params)
        ))(jnp.arange(L))
        k_one = pallas_run.make_kernel_run(
            spec, chunk_steps=chunk_steps, interpret=interpret
        )(sims)
        k_many = pallas_run.make_kernel_run(
            spec, chunk_steps=chunk_steps, interpret=interpret, mesh=mesh
        )(sims)
    kernel_matches_xla(k_one, k_many, "the kernel under a mesh")
    out["kernel"] = {"L": L, "devices": n_devices}
    out["xla"].update(compiled(cc))
    out["events_per_s"] = ev / (t2 - t1)
    return out


# -- driver --------------------------------------------------------------------


def _run_phase(name, fn, **kw):
    from cimba_tpu.obs.telemetry import span

    t0 = time.perf_counter()
    line = {"phase": name}
    try:
        with span("smoke." + name) as sp:
            res = fn(**kw)
    except Exception as e:
        line.update(status="failed", wall_s=time.perf_counter() - t0,
                    error=f"{type(e).__name__}: {e}"[:2000])
        print(json.dumps(line), flush=True)
        raise
    line.update(
        status="passed",
        wall_s=time.perf_counter() - t0,
        **compiled(sp),
        events_per_s=res.pop("events_per_s", None),
        peak_bytes=peak_bytes(),
        detail=res,
    )
    print(json.dumps(line), flush=True)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    import traceback

    try:
        _run_phase("device", phase_device)
        from cimba_tpu import serve
    except Exception:
        traceback.print_exc()
        return 1
    serve.maybe_enable_persistent_cache()
    if args.chips == 4:
        phases = [("mesh", phase_mesh)]
    else:
        phases = [
            ("runner", phase_runner),
            ("serving", phase_serving),
            ("kernels", phase_kernels),
        ]
    # every phase runs even after one fails: a chip call then reports
    # all of what is wrong, and the result line still needs them all
    failed = []
    for name, fn in phases:
        try:
            _run_phase(name, fn)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    # the uniform01 check needs the CPU backend beside the TPU
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    sys.exit(main())
