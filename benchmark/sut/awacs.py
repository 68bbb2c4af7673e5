"""cimba-tpu's AWACS radar study (``cimba_tpu.models.awacs``): targets
flying random legs, a sensor that scores every target once per dwell."""

from __future__ import annotations

import numpy as np

#: unused by the AWACS reference; the harness hands it to every one
SERVICE = None


def build(cfg: dict):
    from cimba_tpu.models import awacs

    return awacs.build(int(cfg["targets"]), dwell=float(cfg["dwell"]))[0]


def rows(cfg: dict) -> list[dict]:
    return [{"horizon": float(cfg["horizon"])}]


def params(cfg: dict, row: dict, horizon: float):
    from cimba_tpu.models import awacs

    return awacs.params(float(horizon))


def summary_path():
    """The statistic the experiment pools: detections per dwell."""
    from cimba_tpu.models import awacs

    return awacs.detections_path


def lanes(cfg: dict, row: dict, seed: int, reps: int, horizon: float):
    from benchmark.refs.awacs import Lanes

    return Lanes(np.full(reps, seed), np.arange(reps),
                 np.full(reps, float(horizon)), int(cfg["targets"]),
                 float(cfg["dwell"]))
