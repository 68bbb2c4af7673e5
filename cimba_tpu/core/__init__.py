"""cimba-tpu core: event set, guards, processes-as-state-machines, dispatcher.

The reference's L1-L4 (coroutine kernel, event queue, hashheap, process
layer — SURVEY.md §1) re-imagined as batched array state stepped by a
jit-compiled while-loop.
"""

from cimba_tpu.core import api, eventset, guard, loop, model, process
from cimba_tpu.core.loop import (
    Sim,
    drive_chunks,
    init_sim,
    make_chunk,
    make_chunked_run,
    make_lanes_run,
    make_run,
    make_step,
)
from cimba_tpu.core.model import Model, ModelSpec
from cimba_tpu.core import process as cmd  # command constructors namespace

__all__ = [
    "api",
    "cmd",
    "eventset",
    "guard",
    "loop",
    "model",
    "process",
    "Sim",
    "drive_chunks",
    "init_sim",
    "make_chunk",
    "make_chunked_run",
    "make_lanes_run",
    "make_run",
    "make_step",
    "Model",
    "ModelSpec",
]