"""System-under-test adapters: one module per cimba-tpu model a
configuration names (its ``"model"`` key).  Each gives the program's
spec and parameters for a configuration's scenarios, and the same
scenarios as lanes of the plain reference."""
