"""Test configuration: run on a virtual 8-device CPU mesh.

Real multi-chip TPU hardware is not available in CI; sharding correctness is
validated on 8 virtual CPU devices (the driver separately dry-run-compiles
the multi-chip path — see __graft_entry__.py).  Env vars must be set before
jax initializes its backends, hence before any cimba_tpu import.
"""

import os

import pytest

if os.environ.get("CIMBA_ON_DEVICE") != "1":  # see test_kernel_fuzz.py
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

import cimba_tpu  # noqa: E402, F401  (enables x64)


def pytest_report_header(config):
    return f"jax {jax.__version__} devices={jax.device_count()} backend={jax.default_backend()}"


@pytest.fixture
def carry_layout(monkeypatch):
    """``carry_layout(packed)`` sets the while-loop carry layout the next
    ``make_run`` / ``make_chunk`` builds: the backend rule
    (``core.loop._packed_carry``) overridden, so both layouts run here
    whatever the backend."""
    from cimba_tpu.core import loop

    def set_layout(packed):
        monkeypatch.setattr(loop, "_packed_carry", lambda: bool(packed))

    return set_layout
