"""Plain references for the benchmark's configurations.

They import nothing of ``cimba_tpu`` and take nothing the program made:
the same semantics written out again, in straightforward ``jax.numpy``.
"""
