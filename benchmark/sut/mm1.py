"""cimba-tpu's M/M/1 model (``cimba_tpu.models.mm1``)."""

from __future__ import annotations

import numpy as np

SERVICE = "exponential"


def build(cfg: dict):
    from cimba_tpu.models import mm1

    return mm1.build(queue_cap=cfg["queue_cap"], record=False)[0]


def rows(cfg: dict) -> list[dict]:
    return [{"arrival_rate": cfg["arrival_rate"],
             "service_rate": cfg["service_rate"]}]


def params(cfg: dict, row: dict, n_objects: int):
    return (1.0 / row["arrival_rate"], 1.0 / row["service_rate"],
            int(n_objects))


def lanes(cfg: dict, row: dict, seed: int, reps: int, n_objects: int,
          t_end: float):
    from benchmark.refs.mg1 import Lanes

    full = lambda v: np.full(reps, v)
    return Lanes(full(seed), np.arange(reps), full(1.0 / row["arrival_rate"]),
                 full(1.0 / row["service_rate"]), full(1.0), full(n_objects),
                 full(t_end))
