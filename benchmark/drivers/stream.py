"""Back-to-back experiments through ``runner.run_experiment_stream``.

Set-up builds the model and one program cache, and runs one experiment
of ``warm_objects`` objects per trial at the full lane count, which
compiles the init, chunk and fold programs the window runs.  The window
then runs whole experiments, each with its own seed, until ``--seconds``
have passed; the one in flight finishes.  ``events_per_s`` is all their
events over the time from the first start to the last end.
"""

from __future__ import annotations

import time

from benchmark import generator


def run(cell) -> dict:
    import jax

    from cimba_tpu import serve
    from cimba_tpu.runner import experiment as ex

    cfg, mix, sut = cell.cfg, cell.mix, cell.sut
    cell.note("imported")
    spec = sut.build(cfg)
    row = sut.rows(cfg)[0]
    R, N = int(cfg["trials"]), int(cfg["objects_per_trial"])
    cache = serve.ProgramCache(store=False)
    kw = dict(wave_size=int(mix["wave"]), chunk_steps=int(mix["chunk_steps"]),
              program_cache=cache)
    warm = ex.run_experiment_stream(
        spec, sut.params(cfg, row, int(mix["warm_objects"])), R,
        seed=0, **kw)
    jax.block_until_ready(warm)
    cell.note("warmed")

    params = sut.params(cfg, row, N)
    done = []
    cell.open_window()
    t0 = time.monotonic()
    while True:
        seed = generator.experiment_seed(cell.seed, len(done))
        with cell.span("bench.experiment"):
            res = ex.run_experiment_stream(spec, params, R, seed=seed, **kw)
            with cell.span("bench.result_wait"):
                jax.block_until_ready(res)
        done.append((seed, res))
        if time.monotonic() - t0 >= cell.seconds:
            break
    wall = time.monotonic() - t0
    cell.close_window()

    events = sum(int(res.total_events) for _, res in done)
    units = [
        (res, sut.lanes(cfg, row, seed, R, N, float("inf")))
        for seed, res in done
    ]
    return {
        "attempted": len(done),
        "failed": 0,
        "metrics": {"events_per_s": events / wall},
        "counters": {"events": events},
        "units": units,
    }
