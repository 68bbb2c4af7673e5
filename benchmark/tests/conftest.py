"""The benchmark's own tests run on the CPU, at sizes a test run holds:
``python -m pytest benchmark/tests``.  The harness refuses the CPU; the
tests reach it through ``run.main``'s rehearsal arguments."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
