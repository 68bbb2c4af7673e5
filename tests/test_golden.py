"""Seed-pinned golden runs (the reference's golden-file mechanism,
`test/tools/test_stochastic.py` + `test/reference/*.txt`, translated):
full model runs with fixed seeds whose results are pinned to 1e-12.

Any semantic drift — event ordering, RNG consumption, guard protocol,
statistics accumulation — shows up here even if distributional tests
still pass.  Values were generated on the CPU backend; the engine's
within-backend determinism makes them stable across batching layouts, and
cross-backend agreement holds to f64-accumulation tolerance (the looser
rtol on m2).

Regenerate after an INTENTIONAL semantic change with:
    python -m tests.test_golden
"""

import hashlib

import jax
import numpy as np
import pytest

from cimba_tpu.core import loop as cl
from cimba_tpu.models import awacs, jobshop, mg1, mm1, mmc, tandem

GOLDEN = {
    # model: (seed, rep, params, stat_key) -> (clock, n_events, m1, m2, mn, mx)
    "mm1": (
        # regenerated round 5: the fused-verb flagship cycle
        # (cmd.put_hold/get_hold) pre-draws durations, shifting stream
        # order — an INTENTIONAL semantic change (docs/07, BENCH_NOTES)
        (777, 3, mm1.params(500), "wait"),
        (582.7368418397683, 1071, 6.533174518899063, 16034.159102488542,
         0.006382670414495806, 23.23331325167962),
    ),
    "mmc": (
        # regenerated round 5: fused-verb cycle (see mm1 entry)
        (777, 5, mmc.params(400, 2.4, 1.0), "wait"),
        (183.4501694416083, 1037, 1.9199510469125969, None, None, None),
    ),
    "mg1": (
        # regenerated round 5: fused-verb cycle (see mm1 entry)
        (777, 7, (1.25, 1.0, 1.5, 400), "wait"),
        (549.8327624123832, 887, 5.622122845944842, None, None, None),
    ),
    "jobshop": (
        (777, 11, jobshop.params(120), "done"),
        (186.45856514611054, 473, 97.12698622241122, 328903.1741311248,
         1.391091807326474, 186.45856514611054),
    ),
    "awacs": (
        (777, 13, awacs.params(200.0), "detections"),
        (200.0, 596, 2.6716417910447765, 1450.3283582089562,
         0.0, 8.0),
    ),
    "tandem": (
        # two queues of 8 places: the one golden model whose ring holds
        # a second queue (at offset 8), wrapping about 50 times a station
        (777, 17, tandem.params(300), "wait"),
        (610.2336635248299, 1405, 2.6120549214781335, 4702.333895629721,
         0.0005334725133820939, 12.385029218394266),
    ),
}

#: sha256 over the bytes of every leaf of the tandem run's final Sim,
#: in pytree order: ring contents included, so it pins the queue slots
#: element for element.  Taken while the ring was still stored
#: [queues, places]; the flat ring keeps the same row-major order.
TANDEM_DIGEST = (
    "96cba414ff7e19968353cbaed479b8e0ebf2a46762f5cdb0a7ea77e00afda04f"
)


def _run(name):
    if name == "mm1":
        spec, _ = mm1.build()
    elif name == "mmc":
        spec, _ = mmc.build(3)
    elif name == "mg1":
        spec, _ = mg1.build()
    elif name == "jobshop":
        spec, _ = jobshop.build()
    elif name == "tandem":
        spec, _ = tandem.build(queue_cap=8)
    else:
        spec, _ = awacs.build(8)
    (seed, rep, params, _key), _ = GOLDEN[name]
    return jax.jit(cl.make_run(spec))(cl.init_sim(spec, seed, rep, params))


def _check(name):
    sim = _run(name)
    (_, _, _, key), (clock, n_events, m1, m2, mn, mx) = GOLDEN[name]
    assert int(sim.err) == 0
    np.testing.assert_allclose(float(sim.clock), clock, rtol=1e-12)
    assert int(sim.n_events) == n_events
    w = sim.user[key]
    np.testing.assert_allclose(float(w.m1), m1, rtol=1e-12)
    if m2 is not None:
        np.testing.assert_allclose(float(w.m2), m2, rtol=1e-9)
        np.testing.assert_allclose(float(w.mn), mn, rtol=1e-12)
        np.testing.assert_allclose(float(w.mx), mx, rtol=1e-12)
    return sim


def test_golden_mm1():
    _check("mm1")


def test_golden_mmc():
    _check("mmc")


def test_golden_mg1():
    _check("mg1")


@pytest.mark.slow  # heavyweight: over the timed tier-1 budget; runs in tools/ci.sh cells
def test_golden_jobshop():
    _check("jobshop")


def test_golden_awacs():
    _check("awacs")


def test_golden_tandem():
    sim = _check("tandem")
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(sim):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == TANDEM_DIGEST


if __name__ == "__main__":  # regeneration helper
    for name in GOLDEN:
        sim = _run(name)
        key = GOLDEN[name][0][3]
        w = sim.user[key]
        print(
            name,
            repr(float(sim.clock)),
            int(sim.n_events),
            repr(float(w.m1)),
            repr(float(w.m2)),
            repr(float(w.mn)),
            repr(float(w.mx)),
        )