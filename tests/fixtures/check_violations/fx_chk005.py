"""CHK005 fixture: CIMBA_* env reads bypassing config.env_raw."""

# cimba-check: env-proxied  (stand-in for a file under cimba_tpu/)

import os as _os

KNOB = "CIMBA_FIXTURE_KNOB"


def direct_literal():
    return _os.environ.get("CIMBA_FIXTURE_KNOB", "0")  # expect: CHK005


def via_constant():
    return _os.environ[KNOB]  # expect: CHK005


def via_getenv():
    return _os.getenv(KNOB, "")  # expect: CHK005


def unregistered_tune_knob():
    # a CIMBA_TUNE* knob nobody registered in config.ENV_KNOBS: the
    # static rule fires here, and config.env_raw raises KeyError at
    # runtime (tests/test_tune.py pins the runtime half)
    return _os.environ.get("CIMBA_TUNE_EXPERIMENTAL")  # expect: CHK005


def non_cimba_is_fine():
    return _os.environ.get("JAX_PLATFORMS", "")


def proxied_is_fine():
    from cimba_tpu import config

    return config.env_raw("CIMBA_TABLE_SCAN")
