"""Checkpoint/resume of batched experiment state.

The reference has **no checkpointing** (SURVEY.md §5: trials are short and
restartable); long pod-scale experiments need it, so this is new
capability.  It falls out of the architecture: a replication's complete
state is one pytree (including the counter-based RNG position), so
``save``/``restore`` round-trips the whole batch and ``make_run`` simply
continues — resumed runs are bit-identical to uninterrupted ones (tested).

Format: a flat numpy ``.npz`` of the pytree leaves with an atomic rename —
deliberately dependency-free (the state is a modest pytree of dense arrays;
an async/sharded checkpoint stack like orbax buys nothing at this size and
would be the only non-jax dependency in the hot path).

Restore validates **every leaf's shape and dtype** against ``like`` and
the saved **spec fingerprint** (leaf shapes/dtypes at save time, plus an
optional caller tag such as capacities/profile): a capacity regrow, dtype
profile switch, or model edit between save and restore fails loudly with
the first mismatching leaf named — never as downstream shape garbage.
"""

from __future__ import annotations

# cimba-check: persist-path  (CHK001: checkpoints are disk artifacts —
# the saved fingerprint must be value-based, never id()-derived)

import json
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: bumped when the on-disk layout changes
_FORMAT = 1


def _flatten(tree: Any):
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def _fingerprint(leaves, tag: Optional[str]) -> str:
    return json.dumps({
        "format": _FORMAT,
        "tag": tag,
        "leaves": [
            [list(np.shape(x)), str(np.asarray(x).dtype)] for x in leaves
        ],
    })


def save(path: str, sims: Any, *, tag: Optional[str] = None) -> None:
    """Write a batched Sim (or any pytree) to ``path`` (.npz).

    ``tag`` is an opaque caller string stored in the fingerprint and
    checked verbatim at restore — the runner passes the spec's identity
    (name, capacities, dtype profile) so a same-shape-different-model
    restore still fails loudly.

    Atomicity: the bytes land in a UNIQUELY-named temp file in the same
    directory (``mkstemp`` — two concurrent savers, e.g. a service
    checkpointing two runs to siblings of one dir, cannot clobber each
    other's half-written temp), are fsync'd to disk, and only then
    ``os.replace``d over ``path`` — a preemption or crash at ANY point
    leaves either the previous complete checkpoint or none, never a
    torn file, and ``restore`` only ever reads ``path``, so leftover
    ``*.tmp`` orphans from a killed process are ignored (tested in
    tests/test_checkpoint_atomic.py)."""
    import tempfile

    leaves, _ = _flatten(sims)
    arrays = {f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)}
    arrays["__spec__"] = np.frombuffer(
        _fingerprint(leaves, tag).encode(), dtype=np.uint8
    )
    path = os.path.abspath(path)
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(path),
        prefix=os.path.basename(path) + ".",
        suffix=".tmp",
    )
    try:
        with os.fdopen(fd, "wb") as fh:
            np.savez(fh, **arrays)
            fh.flush()
            os.fsync(fh.fileno())  # durable BEFORE the rename publishes it
        os.replace(tmp, path)  # atomic: never a torn checkpoint at `path`
    except BaseException:
        try:  # a failed save must not litter (or leave a decoy temp)
            os.unlink(tmp)
        except OSError:
            pass
        raise


def spec_tag(spec: Any) -> str:
    """Canonical fingerprint tag for a built ModelSpec: name, process
    count, capacities, and the active dtype profile — everything that
    changes the Sim's meaning without necessarily changing a leaf
    count."""
    from cimba_tpu import config

    return json.dumps({
        "model": getattr(spec, "name", "?"),
        "n_procs": getattr(spec, "n_procs", -1),
        "event_cap": getattr(spec, "event_cap", -1),
        "queue_cap_max": getattr(spec, "queue_cap_max", -1),
        "pqueue_cap_max": getattr(spec, "pqueue_cap_max", -1),
        "real": str(config.REAL),
        "time": str(config.TIME),
    })


def run_tag(
    spec: Any, *, seed: int, params: Any = None, t_end: Any = None,
) -> str:
    """:func:`spec_tag` extended with the run's ``seed``, horizon, and a
    digest of its (broadcast) params: a chunked checkpoint restored
    under a different seed, ``t_end``, or swept parameters would
    silently continue/hybridize the OLD run — the shapes all match — so
    the runner fingerprints every trajectory-changing knob and a
    mismatched resume fails loudly instead (chunk_steps and the carry
    layout are trajectory-neutral and stay out of the tag)."""
    import hashlib

    base = json.loads(spec_tag(spec))
    base["seed"] = int(seed)
    base["t_end"] = None if t_end is None else float(t_end)
    if params is not None:
        h = hashlib.sha256()
        for x in _flatten(params)[0]:
            a = np.asarray(x)
            h.update(f"{a.shape}:{a.dtype}:".encode())
            h.update(a.tobytes())
        base["params_sha256"] = h.hexdigest()
    return json.dumps(base)


def save_resumable(
    path: str, sims: Any, *, spec: Any = None, progress: int = 0,
    tag: Optional[str] = None,
) -> None:
    """Checkpoint a chunked run at a chunk boundary: the batched Sim
    plus its chunk counter, spec-fingerprinted (chunk boundaries are
    the natural checkpoints — between chunks the COMPLETE state of
    every replication, RNG position included, is the Sim pytree the
    host loop holds; ``run_experiment_chunked`` calls this from its
    ``on_state`` hook).  ``spec`` supplies the fingerprint tag via
    :func:`spec_tag` unless an explicit ``tag`` is given.

    ``spec_tag`` alone does NOT guard against resuming under a
    different seed, horizon, or swept params — those all produce
    identical shapes and spec identity.  Callers checkpointing a
    specific run should pass ``tag=run_tag(spec, seed=..., params=...,
    t_end=...)`` as ``run_experiment_chunked`` does; the bare ``spec=``
    form only proves the model matches."""
    if tag is None and spec is not None:
        tag = spec_tag(spec)
    save(
        path,
        (sims, jnp.asarray(int(progress), jnp.int32)),
        tag=tag,
    )


def restore_resumable(
    path: str, like: Any, *, spec: Any = None, tag: Optional[str] = None,
):
    """Inverse of :func:`save_resumable`: returns ``(sims, progress)``.
    ``like`` is a same-shaped batched Sim — a fresh init of the same
    experiment or its ``jax.eval_shape`` aval tree (no materialization);
    validation is :func:`restore`'s — the first mismatching leaf or a
    spec-fingerprint change fails loudly.  As with
    :func:`save_resumable`, pass ``tag=run_tag(...)`` to also pin the
    run's seed/params/horizon; ``spec=`` alone only proves the model
    matches."""
    if tag is None and spec is not None:
        tag = spec_tag(spec)
    sims, progress = restore(
        path, (like, jnp.zeros((), jnp.int32)), tag=tag
    )
    return sims, int(progress)


def restore(path: str, like: Any, *, tag: Optional[str] = None) -> Any:
    """Read a checkpoint written by :func:`save`; ``like`` supplies the
    pytree structure and every leaf's expected shape and dtype — a
    freshly-initialized batch, or its ``jax.eval_shape`` aval tree
    (``ShapeDtypeStruct`` leaves carry exactly what validation reads,
    without materializing a batch).  Raises ``ValueError`` naming the
    first mismatch if the file disagrees with ``like`` or with ``tag``."""
    leaves, treedef = _flatten(like)
    with np.load(path) as data:
        names = [f for f in data.files if f != "__spec__"]
        if "__spec__" not in data.files:
            if tag is not None:
                raise ValueError(
                    "checkpoint has no spec fingerprint (written by a "
                    "pre-fingerprint save?) but tag verification was "
                    "requested — cannot prove it matches this spec"
                )
        else:
            saved = json.loads(bytes(data["__spec__"]).decode())
            if saved.get("format") != _FORMAT:
                raise ValueError(
                    f"checkpoint format {saved.get('format')} != "
                    f"supported {_FORMAT}"
                )
            if tag is not None and saved.get("tag") != tag:
                raise ValueError(
                    "checkpoint spec fingerprint mismatch —\n"
                    f"  saved:    {saved.get('tag')}\n"
                    f"  restoring:{tag}\n"
                    "model/capacities/profile changed between save and "
                    "restore (e.g. a capacity regrow); re-init instead"
                )
        if len(names) != len(leaves):
            raise ValueError(
                f"checkpoint has {len(names)} leaves, expected "
                f"{len(leaves)} — model structure changed?"
            )
        new = []
        for i, x in enumerate(leaves):
            arr = data[f"leaf_{i}"]
            want_shape = tuple(np.shape(x))
            # ShapeDtypeStruct / jax array leaves carry .dtype; plain
            # python scalars fall back through asarray
            dt = getattr(x, "dtype", None)
            want_dtype = np.dtype(dt) if dt is not None else np.asarray(x).dtype
            if tuple(arr.shape) != want_shape:
                raise ValueError(
                    f"checkpoint leaf {i}: shape {tuple(arr.shape)} != "
                    f"expected {want_shape} — capacity or batch size "
                    "changed between save and restore?"
                )
            if arr.dtype != want_dtype:
                raise ValueError(
                    f"checkpoint leaf {i}: dtype {arr.dtype} != expected "
                    f"{want_dtype} — dtype profile changed between save "
                    "and restore?"
                )
            new.append(jnp.asarray(arr, want_dtype))
    return jax.tree.unflatten(treedef, new)
