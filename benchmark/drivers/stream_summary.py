"""Back-to-back experiments through ``runner.run_experiment_stream``,
pooling the statistic the configuration's model names (its adapter's
``summary_path``).

Set-up builds the model and one program cache, and runs one experiment
of ``warm_horizon`` simulated seconds at the full lane count, which
compiles the init, chunk and fold programs the window runs.  The window
then runs whole experiments of the configuration's ``horizon``, each
with its own seed, until ``--seconds`` have passed; the one in flight
finishes.  ``events_per_s`` is all their events over the time from the
first start to the last end.  The comparison checks ``check_experiments``
of them, drawn from ``--seed``.  The counters carry the program's
boundary counters where it keeps them, and a traced run hands the
readers the trace's directory.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import generator


def window(cell, experiment):
    """Run ``experiment(seed)`` back to back over the window; returns the
    ``(seed, result)`` pairs and the window's wall time."""
    import jax

    done = []
    cell.open_window()
    t0 = time.monotonic()
    while True:
        seed = generator.experiment_seed(cell.seed, len(done))
        with cell.span("bench.experiment"):
            res = experiment(seed)
            with cell.span("bench.result_wait"):
                jax.block_until_ready(res)
        done.append((seed, res))
        if time.monotonic() - t0 >= cell.seconds:
            break
    wall = time.monotonic() - t0
    cell.close_window()
    return done, wall


def sample(cell, done, k: int):
    """``k`` of the window's experiments (all, where it ran fewer), in
    window order, drawn from ``--seed``."""
    rng = np.random.default_rng(np.random.SeedSequence([int(cell.seed), 1]))
    idx = sorted(rng.choice(len(done), size=min(k, len(done)), replace=False))
    return [done[i] for i in idx]


def counters(cell, done) -> dict:
    import jax

    out = {"events": sum(int(res.total_events) for _, res in done),
           "device_kind": jax.devices()[0].device_kind}
    kept = [getattr(res, "boundary", None) for _, res in done]
    if kept and all(b is not None for b in kept):
        out["boundary"] = {k: sum(int(b[k]) for b in kept) for k in kept[0]}
    return out


def run(cell) -> dict:
    import jax

    from cimba_tpu import serve
    from cimba_tpu.runner import experiment as ex

    cfg, mix, sut = cell.cfg, cell.mix, cell.sut
    cell.note("imported")
    spec = sut.build(cfg)
    row = sut.rows(cfg)[0]
    R, horizon = int(cfg["trials"]), float(cfg["horizon"])
    cache = serve.ProgramCache(store=False)
    kw = dict(wave_size=int(mix["wave"]), chunk_steps=int(mix["chunk_steps"]),
              program_cache=cache, summary_path=sut.summary_path())
    warm = ex.run_experiment_stream(
        spec, sut.params(cfg, row, float(mix["warm_horizon"])), R, seed=0, **kw)
    jax.block_until_ready(warm)
    cell.note("warmed")

    params = sut.params(cfg, row, horizon)
    done, wall = window(
        cell, lambda seed: ex.run_experiment_stream(spec, params, R, seed=seed, **kw))
    c = counters(cell, done)
    units = [(res, sut.lanes(cfg, row, seed, R, horizon))
             for seed, res in sample(cell, done, int(mix["check_experiments"]))]
    out = {
        "attempted": len(done),
        "failed": 0,
        "metrics": {"events_per_s": c["events"] / wall},
        "counters": c,
        "units": units,
    }
    if cell.trace:
        out["trace_dir"] = cell.trace_dir
    return out
