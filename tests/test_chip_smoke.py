"""chip_smoke.py's phases, run tiny on the 8-device CPU mesh.

The script itself only runs on a TPU (the driver's chip check); these
tests drive every phase function at toy sizes with the Pallas kernels in
interpret mode, passed explicitly, so a wrong path, argument or check
surfaces here and not in a chip call.  ``main`` must refuse the CPU.
"""

import json

import jax
import jax.numpy as jnp
import pytest

import chip_smoke as cs


def test_runner_phase_tiny():
    out = cs.phase_runner(
        R=64, N=200, wave=32, R64=64, wave64=32, chunk_steps=64,
        oracle_reps=64, lanes_exact=8, uniform_reps=(0, 1, 63), n_draws=16,
    )
    assert out["f32"]["events"] > 0 and out["f64"]["events"] > 0
    assert out["f32"]["compiles"] >= 1 and out["f32"]["compile_s"] > 0
    assert out["per_lane"]["matched"] >= 6
    assert out["uniform01"]["bit_identical"]


def test_serving_phase_tiny():
    out = cs.phase_serving(
        mm1_R=16, mg1_R=16, awacs_R=4, N=100, n_targets=16,
        awacs_t_end=2.0, chunk_steps=64,
    )
    assert out["store"]["hits"] >= 1
    assert out["store"]["fallback_shapes"] == out["store"]["corrupt"] == 0
    assert all(out[k]["events"] > 0 for k in ("mm1", "mg1", "awacs"))


def test_kernels_phase_tiny():
    out = cs.phase_kernels(
        L=8, N=50, chunk_steps=64, n_targets=137, interpret=True
    )
    assert out["mm1_kernel"]["events"] > 0
    assert out["awacs_scorer"]["max_abs_diff"] <= 5e-3


def test_mesh_phase_on_four_virtual_devices():
    assert jax.device_count() >= 4
    out = cs.phase_mesh(
        n_devices=4, R=64, N=100, L=8, chunk_steps=64, interpret=True
    )
    assert out["xla"]["events"] > 0


def test_a_phase_line_reports_its_compiles(capsys):
    def probe():
        f = jax.jit(lambda x: x * 2.0 + 5.0)
        f(jnp.ones((29,), jnp.float32)).block_until_ready()
        return {"events_per_s": None}

    cs._run_phase("probe", probe)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["status"] == "passed"
    assert line["compiles"] >= 1 and line["compile_s"] > 0


def test_device_phase_refuses_the_cpu():
    with pytest.raises(cs.SmokeFailure, match="not a TPU"):
        cs.phase_device()


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_fails_without_a_chip(capsys, argv):
    assert cs.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    lines = [json.loads(l) for l in out.splitlines() if l.strip()]
    assert lines and lines[-1]["phase"] == "device"
    assert lines[-1]["status"] == "failed"
