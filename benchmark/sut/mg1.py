"""cimba-tpu's M/G/1 model with lognormal service
(``cimba_tpu.models.mg1``)."""

from __future__ import annotations

import numpy as np

SERVICE = "lognormal"


def build(cfg: dict):
    from cimba_tpu.models import mg1

    return mg1.build(queue_cap=cfg["queue_cap"])[0]


def rows(cfg: dict) -> list[dict]:
    """The sweep's scenarios, CV-major as upstream lays them out."""
    return [{"cv": cv, "rho": rho, "service_mean": cfg["service_mean"]}
            for cv in cfg["cvs"] for rho in cfg["utilizations"]]


def params(cfg: dict, row: dict, n_objects: int):
    m = row["service_mean"]
    return (m / row["rho"], m, row["cv"], int(n_objects))


def lanes(cfg: dict, row: dict, seed: int, reps: int, n_objects: int,
          t_end: float):
    from benchmark.refs.mg1 import Lanes

    full = lambda v: np.full(reps, v)
    m = row["service_mean"]
    return Lanes(full(seed), np.arange(reps), full(m / row["rho"]), full(m),
                 full(row["cv"]), full(n_objects), full(t_end))
