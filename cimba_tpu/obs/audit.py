"""Determinism audit & provenance plane (docs/18_audit.md).

Every bitwise claim this repo makes — pad-and-mask inertness
(docs/14), store hydration (docs/15), sweep round seeds (docs/16),
chunked == monolithic trajectories (docs/12) — was checkable only
inside a pytest process.  This module turns those claims into
**citable artifacts**:

* **Chunk-boundary carry digests** — with auditing enabled, the chunk
  program folds each packed carry class (the f32 / i32+u32 / f64 / i64
  classes of :mod:`cimba_tpu.core.carry`) into a per-wave u64 digest
  vector: every carried leaf is bitcast to its class's unsigned
  payload, each element mixed (fmix64) with its global (lane, offset,
  leaf) position, and the mixes summed mod 2^64 — an order-independent
  exact integer reduction, so the digest is deterministic under any
  XLA reduction order and combines across mesh shards with a plain
  ``psum``.  The host appends one digest row per chunk: the **digest
  trail**.  Trace-time gated in the :mod:`obs.trace` idiom: a chunk
  program built with ``audit=False`` (the default) is jaxpr
  character-identical to one built before this module existed (pinned
  in tests/test_audit.py).
* **Run cards** — a content-addressed JSON artifact per run: spec
  fingerprint (the store's value-based identity), seed schedule,
  resolved program key, environment block (jax/jaxlib/backend/x64/
  package — the same :func:`~cimba_tpu.obs.telemetry.build_info` dict
  ``/varz`` exposes), wave/chunk geometry, the digest trail, the
  result digest, and an optional telemetry snapshot.  The card digest
  excludes the creation timestamp, so two clean same-seed runs in two
  processes produce byte-for-byte the SAME card digest — "bitwise
  reproducible" becomes an equality between two hex strings.
* **Divergence localization** — :func:`diff_cards` /
  :func:`diff_trails` compare two trails and report the FIRST
  divergent (wave, chunk, carry-class); ``tools/audit_diff.py`` wraps
  them with CI-friendly exit codes.

Module-level imports are stdlib-only by design: the diff/report half
must stay loadable without jax (``tools/audit_diff.py`` file-loads this
module directly), so every device-facing function imports jax locally.
"""

from __future__ import annotations

# cimba-check: persist-path  (CHK001: run cards are disk artifacts —
# nothing id()-derived may feed them)

import hashlib
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "AUDIT_ENV", "CARD_FORMAT", "CLASS_NAMES",
    "Audit", "resolve", "sim_digest", "format_digests",
    "result_digest", "stream_result_digest",
    "run_card", "card_digest", "write_run_card", "load_run_card",
    "diff_trails", "diff_cards", "environment",
]

#: environment knob: unset/"0" = off, "1" = collect in memory, any
#: other value = a directory run cards are written into
AUDIT_ENV = "CIMBA_AUDIT"

#: run-card schema version (bump on incompatible layout changes)
CARD_FORMAT = 1

#: the packed carry classes digested, in `core.carry._CLASSES` order
CLASS_NAMES = ("f32", "i32", "f64", "i64")

_CLASS_BITS = {"f32": 32, "i32": 32, "f64": 64, "i64": 64}

_U64 = (1 << 64) - 1

#: splitmix64 golden gamma — the per-leaf salt stride
_GAMMA = 0x9E3779B97F4A7C15


def _fmix64_host(x: int) -> int:
    """murmur3 fmix64 on a python int (the host twin of the traced
    mixer — used for per-leaf salts, which are trace-time constants)."""
    x &= _U64
    x ^= x >> 33
    x = (x * 0xFF51AFD7ED558CCD) & _U64
    x ^= x >> 33
    x = (x * 0xC4CEB9FE1A85EC53) & _U64
    x ^= x >> 33
    return x


# ---------------------------------------------------------------------------
# device-side digest
# ---------------------------------------------------------------------------


def _fmix64(x):
    """murmur3 fmix64 elementwise on a u64 array (wrapping mults —
    XLA integer arithmetic is modular, so this is exact and
    deterministic on every backend)."""
    import jax.numpy as jnp

    x = x ^ (x >> jnp.uint64(33))
    x = x * jnp.uint64(0xFF51AFD7ED558CCD)
    x = x ^ (x >> jnp.uint64(33))
    x = x * jnp.uint64(0xC4CEB9FE1A85EC53)
    x = x ^ (x >> jnp.uint64(33))
    return x


def sim_digest(sims, lane_offset=0):
    """Per-carry-class digest vector ``[4] u64`` of a BATCHED Sim
    (leading lane axis) — the on-device digest the audited chunk
    program appends at every chunk boundary.

    Per leaf in flatten order: bitcast to the class's unsigned payload
    (f32→u32, f64/i64→u64; i32/u32 ride as themselves — exactly the
    :mod:`core.carry` class membership), mix each element with its
    position key ``(lane + lane_offset) * inner + offset`` and a
    per-leaf salt through fmix64, and sum mod 2^64 into the class
    accumulator.  Summation is an exact commutative integer reduction:
    the digest is independent of XLA's reduction order, and a mesh
    shard's digest ``psum``s into the global one (``lane_offset`` =
    ``axis_index * local_lanes`` makes shard-local positions global, so
    a 1-device mesh digest equals the unsheltered one).  Bool leaves
    (and anything outside the four classes) pass through undigested —
    they are derived state, and any divergence in them is preceded by a
    divergence in the numeric carries that produced them.

    32-bit classes accumulate in full u64 (masking to u32 happens only
    at host formatting time — :func:`format_digests` — so shard sums
    still combine exactly)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from cimba_tpu.core import carry as _carry

    leaves = jax.tree.leaves(sims)
    sums: Dict[str, Any] = {
        name: jnp.zeros((), jnp.uint64) for name in CLASS_NAMES
    }
    off = jnp.asarray(lane_offset, jnp.uint64)
    for ordinal, leaf in enumerate(leaves):
        dt = jnp.result_type(leaf)
        cname = None
        for name, _, members in _carry._CLASSES:
            if any(dt == m for m in members):
                cname = name
                break
        if cname is None:
            continue
        wide = _CLASS_BITS[cname] == 64
        bits = lax.bitcast_convert_type(
            leaf, jnp.uint64 if wide else jnp.uint32
        ).astype(jnp.uint64)
        W = int(leaf.shape[0])
        inner = 1
        for d in leaf.shape[1:]:
            inner *= int(d)
        bits = bits.reshape((W, inner))
        lane = lax.broadcasted_iota(jnp.uint64, (W, inner), 0) + off
        within = lax.broadcasted_iota(jnp.uint64, (W, inner), 1)
        pos = lane * jnp.uint64(inner) + within
        salt = _fmix64_host((ordinal + 1) * _GAMMA)
        h = _fmix64(bits ^ _fmix64(pos ^ jnp.uint64(salt)))
        sums[cname] = sums[cname] + jnp.sum(h, dtype=jnp.uint64)
    return jnp.stack([sums[n] for n in CLASS_NAMES])


# cimba-check: content-path
def format_digests(vec) -> Dict[str, str]:
    """One digest vector as the JSON trail-row payload: hex strings,
    32-bit classes masked to their u32 payload width."""
    import numpy as np

    v = np.asarray(vec)
    out = {}
    for i, name in enumerate(CLASS_NAMES):
        x = int(v[i]) & _U64
        if _CLASS_BITS[name] == 32:
            out[name] = f"0x{x & 0xFFFFFFFF:08x}"
        else:
            out[name] = f"0x{x:016x}"
    return out


# ---------------------------------------------------------------------------
# result digests (host-side, exact)
# ---------------------------------------------------------------------------


# cimba-check: content-path
def result_digest(tree) -> str:
    """sha256 hex over a pytree of arrays: structure + per-leaf
    dtype/shape/bytes in flatten order.  Bitwise — two results digest
    equal iff every leaf is bit-for-bit equal."""
    import jax
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    h = hashlib.sha256()
    h.update(repr(treedef).encode("utf-8"))
    for leaf in leaves:
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode("utf-8"))
        h.update(repr(a.shape).encode("utf-8"))
        h.update(a.tobytes())
    return h.hexdigest()


# cimba-check: content-path
def stream_result_digest(res) -> str:
    """The canonical digest of a ``StreamResult``: summary + failure
    count + event total (+ pooled metrics when carried).  ``n_waves``/
    ``n_regrows`` are geometry bookkeeping, not results, and the audit
    card records geometry separately — so a served request's digest can
    equal its direct call's (the serve contract, docs/13_serving.md)."""
    parts: tuple = (res.summary, res.n_failed, res.total_events)
    if res.metrics is not None:
        parts = parts + (res.metrics,)
    return result_digest(parts)


# ---------------------------------------------------------------------------
# the host-side collector
# ---------------------------------------------------------------------------


class Audit:
    """Host-side audit collector for one run: accumulates the digest
    trail (device vectors appended per chunk, converted lazily) and
    finalizes into a run card.  ``out_dir`` (optional) is where
    :meth:`finalize` writes the content-addressed card."""

    def __init__(self, out_dir=None, label: Optional[str] = None):
        self.out_dir = None if out_dir is None else str(out_dir)
        self.label = label
        self._trail: List[Tuple[int, int, Any]] = []
        self.card: Optional[dict] = None
        self.card_path: Optional[str] = None

    def on_chunk(self, wave: int, chunk: int, vec) -> None:
        """Append one chunk boundary's digest vector (held as a device
        array — conversion is deferred so the drive loop stays
        asynchronous)."""
        self._trail.append((int(wave), int(chunk), vec))

    def __len__(self) -> int:
        return len(self._trail)

    def trail_rows(self) -> List[dict]:
        """The trail as JSON rows: ``{"wave", "chunk", "f32", "i32",
        "f64", "i64"}`` in append order."""
        rows = []
        for w, c, vec in self._trail:
            row: dict = {"wave": w, "chunk": c}
            row.update(format_digests(vec))
            rows.append(row)
        return rows

    def finalize(self, kind: str, **blocks) -> dict:
        """Build (and, with ``out_dir`` set, write) this run's card.
        Keyword blocks are passed through to :func:`run_card`."""
        card = run_card(
            kind, digest_trail=self.trail_rows(), label=self.label,
            **blocks,
        )
        self.card = card
        if self.out_dir:
            self.card_path = write_run_card(card, self.out_dir)
        return card


def resolve(audit) -> Optional[Audit]:
    """Normalize an ``audit=`` argument: ``None`` defers to the
    ``CIMBA_AUDIT`` env knob (unset/"0" = off, "1" = in-memory, a path
    = write cards there), ``False`` forces off, ``True`` collects in
    memory, a path string collects + writes, an :class:`Audit` is used
    as-is."""
    if audit is None:
        # local import: the diff half of this module stays loadable
        # without the package (tools/audit_diff.py file-loads it)
        from cimba_tpu import config as _config

        v = _config.env_raw(AUDIT_ENV)
        if v in ("", "0"):
            return None
        return Audit() if v == "1" else Audit(out_dir=v)
    if audit is False:
        return None
    if audit is True:
        return Audit()
    if isinstance(audit, Audit):
        return audit
    if isinstance(audit, (str, os.PathLike)):
        return Audit(out_dir=audit)
    raise TypeError(
        f"audit= expects None, bool, a directory path, or an "
        f"obs.audit.Audit — got {type(audit).__name__}"
    )


# ---------------------------------------------------------------------------
# run cards
# ---------------------------------------------------------------------------


def environment() -> dict:
    """The card's env block — the SAME dict ``/varz`` serves as its
    ``build`` section (:func:`cimba_tpu.obs.telemetry.build_info`), so
    a fleet audit can cross-check a scraped process against a stored
    artifact field-for-field."""
    from cimba_tpu.obs.telemetry import build_info

    return build_info()


# cimba-check: content-path
def spec_block(spec) -> dict:
    """The card's spec identity: name + sha256 of the store's
    VALUE-based structural fingerprint (stable across processes —
    ``cache.spec_fingerprint``'s ``id()``s are not).  A spec that
    resists value fingerprinting records why instead of crashing the
    run it documents."""
    out: dict = {"name": getattr(spec, "name", None)}
    try:
        from cimba_tpu.serve import store as _pstore

        fp = _pstore.stable_spec_fingerprint(spec)
        out["spec_fingerprint"] = hashlib.sha256(
            repr(fp).encode("utf-8")
        ).hexdigest()
    except Exception as e:
        out["spec_fingerprint"] = None
        out["unstable"] = f"{type(e).__name__}: {e}"
    return out


def run_card(
    kind: str,
    *,
    spec=None,
    geometry: Optional[dict] = None,
    seed_schedule: Optional[dict] = None,
    digest_trail: Optional[List[dict]] = None,
    result_digest: Optional[str] = None,
    cells: Optional[List[dict]] = None,
    telemetry: Optional[dict] = None,
    program_key: Optional[str] = None,
    label: Optional[str] = None,
    schedule: Optional[dict] = None,
    extra: Optional[dict] = None,
) -> dict:
    """Assemble one run card (omitted blocks are left out, not nulled)
    and stamp its content digest.  ``spec`` may be a ModelSpec (hashed
    via :func:`spec_block`) or a pre-built dict.  ``schedule`` is the
    resolved dispatch-schedule block (docs/21_autotune.md — knobs +
    resolution source + tuned-entry digest), so every bitwise claim
    names the schedule it ran under; :func:`diff_cards` treats drift
    there as environment drift, never divergence (schedules change
    speed, not results)."""
    card: dict = {
        "format": CARD_FORMAT,
        "kind": str(kind),
        "created_unix": time.time(),
        "env": environment(),
    }
    if label:
        card["label"] = str(label)
    if spec is not None:
        card["spec"] = spec if isinstance(spec, dict) else spec_block(spec)
    if seed_schedule is not None:
        card["seed_schedule"] = seed_schedule
    if geometry is not None:
        card["geometry"] = geometry
    if program_key is not None:
        card["program_key"] = program_key
    if schedule is not None:
        card["schedule"] = schedule
    if digest_trail is not None:
        card["digest_trail"] = digest_trail
    if result_digest is not None:
        card["result_digest"] = result_digest
    if cells is not None:
        card["cells"] = cells
    if telemetry is not None:
        card["telemetry"] = telemetry
    if extra is not None:
        card["extra"] = extra
    card["card_digest"] = card_digest(card)
    return card


# cimba-check: content-path
def card_digest(card: dict) -> str:
    """Content digest of a card: sha256 over the canonical JSON of
    everything EXCEPT ``card_digest`` itself and the creation
    timestamp — two clean same-seed runs (same machine/env) therefore
    produce the SAME digest, which is the whole point: "bitwise
    reproducible" becomes one string equality."""
    body = {
        k: v for k, v in card.items()
        if k not in ("card_digest", "created_unix")
    }
    return hashlib.sha256(
        json.dumps(body, sort_keys=True, default=str).encode("utf-8")
    ).hexdigest()


def write_run_card(card: dict, out_dir) -> str:
    """Write a card content-addressed (``runcard_<digest16>.json``),
    crash-atomic (tmp + rename).  Identical runs collide on the same
    path with identical content (minus timestamp) — benign."""
    out_dir = str(out_dir)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, f"runcard_{card['card_digest'][:16]}.json"
    )
    tmp = f"{path}.tmp{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(card, f, indent=2, sort_keys=True, default=str)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def load_run_card(path) -> dict:
    """Load a run card (or a bare digest-trail JSON list, wrapped) with
    a loud error naming the file on anything malformed."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, list):
        doc = {"format": CARD_FORMAT, "kind": "trail",
               "digest_trail": doc}
    if not isinstance(doc, dict) or "kind" not in doc:
        raise ValueError(
            f"{path}: not a run card (expected a JSON object with a "
            "'kind' field, or a bare digest-trail list)"
        )
    return doc


# ---------------------------------------------------------------------------
# divergence localization (stdlib-only — tools/audit_diff.py rides this)
# ---------------------------------------------------------------------------


# cimba-check: content-path
def diff_trails(a_rows: List[dict], b_rows: List[dict]) -> Optional[dict]:
    """First divergent trail row between two digest trails, or ``None``
    when identical.  The report names the (wave, chunk) coordinate and
    the carry classes that differ — ``classes`` is ``["geometry"]``
    when the coordinates themselves disagree and ``["length"]`` when
    one trail is a prefix of the other."""
    for i, (ra, rb) in enumerate(zip(a_rows, b_rows)):
        if (ra.get("wave"), ra.get("chunk")) != (
            rb.get("wave"), rb.get("chunk")
        ):
            return {
                "index": i, "wave": ra.get("wave"),
                "chunk": ra.get("chunk"), "classes": ["geometry"],
                "a": ra, "b": rb,
            }
        classes = [n for n in CLASS_NAMES if ra.get(n) != rb.get(n)]
        if classes:
            return {
                "index": i, "wave": ra.get("wave"),
                "chunk": ra.get("chunk"), "classes": classes,
                "a": {n: ra.get(n) for n in classes},
                "b": {n: rb.get(n) for n in classes},
            }
    if len(a_rows) != len(b_rows):
        i = min(len(a_rows), len(b_rows))
        longer = a_rows if len(a_rows) > len(b_rows) else b_rows
        row = longer[i] if i < len(longer) else {}
        return {
            "index": i, "wave": row.get("wave"),
            "chunk": row.get("chunk"), "classes": ["length"],
            "a_len": len(a_rows), "b_len": len(b_rows),
        }
    return None


#: geometry fields that must match for two trails to be comparable at
#: all (digests are geometry-specific: different wave partitions fold
#: different chunk boundaries, and ``poll_every`` changes how many
#: deterministic no-op trailing chunks each wave appends — a mismatch
#: there is geometry drift, not a determinism regression)
_GEOMETRY_KEYS = (
    "R", "wave_size", "chunk_steps", "poll_every", "t_end", "profile",
    "mesh", "with_metrics",
)

#: geometry keys that are SCHEDULE knobs with bitwise-invariant results
#: (docs/21_autotune.md): when both cards carry a ``schedule`` block,
#: drift here is env drift (a different tuned schedule ran), not
#: incomparability — chunk boundaries move, so the TRAIL comparison is
#: skipped, but the result digests must still be equal.  ``wave_size``
#: stays a hard geometry key: the pooled summary's merge order follows
#: the wave partition, so cross-wave-size results legitimately differ.
_SCHEDULE_GEOMETRY_KEYS = ("chunk_steps",)


# cimba-check: content-path
def diff_cards(a: dict, b: dict) -> dict:
    """Compare two run cards.  Returns a report dict:

    * ``comparable`` — False (with ``reasons``) when the cards describe
      different experiments (spec fingerprint, kind, or geometry
      drift) and a digest comparison would be meaningless;
    * ``env_drift`` — environment keys that differ (jax/jaxlib/
      backend/x64/...): reported, but not blocking — cross-environment
      divergence is exactly what an audit is for.  Dispatch-SCHEDULE
      drift (docs/21_autotune.md — a different tuned/override schedule
      ran) reports here too, as ``schedule.<knob>`` entries, never as
      divergence: schedules change speed, not results;
    * ``schedule_drift`` — the drifted schedule-block keys by
      themselves (``[]`` when both cards ran the same schedule or
      either card predates schedule blocks);
    * ``first_divergence`` — :func:`diff_trails` on the digest trails
      (skipped, with ``trail_skipped`` set, when the schedule drift
      moved the chunk boundaries — the RESULT digests still compare);
    * ``result_equal`` — result-digest equality (None when either card
      carries none);
    * ``identical`` — comparable, no trail divergence, and results not
      known unequal.
    """
    reasons: List[str] = []
    fa = (a.get("spec") or {}).get("spec_fingerprint")
    fb = (b.get("spec") or {}).get("spec_fingerprint")
    if fa and fb and fa != fb:
        reasons.append("spec fingerprint differs")
    if a.get("kind") != b.get("kind"):
        reasons.append(
            f"kind differs ({a.get('kind')!r} vs {b.get('kind')!r})"
        )
    sa, sb = a.get("schedule"), b.get("schedule")
    schedule_drift: List[str] = []
    if isinstance(sa, dict) and isinstance(sb, dict):
        ka = dict(sa.get("knobs") or {}, source=sa.get("source"))
        kb = dict(sb.get("knobs") or {}, source=sb.get("source"))
        schedule_drift = sorted(
            k for k in set(ka) | set(kb) if ka.get(k) != kb.get(k)
        )
    ga, gb = a.get("geometry") or {}, b.get("geometry") or {}
    geo_drift_all = [
        k for k in _GEOMETRY_KEYS
        if k in ga and k in gb and ga[k] != gb[k]
    ]
    # schedule-owned, bitwise-invariant geometry keys: with schedule
    # blocks on both cards they are env-class drift (the schedule
    # changed), not incomparability — results must still match
    sched_geo = [
        k for k in geo_drift_all
        if k in _SCHEDULE_GEOMETRY_KEYS
        and isinstance(sa, dict) and isinstance(sb, dict)
    ]
    geo_drift = [k for k in geo_drift_all if k not in sched_geo]
    if geo_drift:
        reasons.append("geometry differs: " + ", ".join(geo_drift))
    ea, eb = a.get("env") or {}, b.get("env") or {}
    env_drift = sorted(
        k for k in set(ea) | set(eb) if ea.get(k) != eb.get(k)
    )
    env_drift += [f"schedule.{k}" for k in schedule_drift]
    seeds_differ = (
        a.get("seed_schedule") is not None
        and b.get("seed_schedule") is not None
        and a["seed_schedule"] != b["seed_schedule"]
    )
    trail_skipped = bool(sched_geo)
    divergence = None
    if not trail_skipped:
        divergence = diff_trails(
            a.get("digest_trail") or [], b.get("digest_trail") or []
        )
    ra, rb = a.get("result_digest"), b.get("result_digest")
    result_equal = None if (ra is None or rb is None) else (ra == rb)
    comparable = not reasons
    return {
        "comparable": comparable,
        "reasons": reasons,
        "env_drift": env_drift,
        "schedule_drift": schedule_drift,
        "seeds_differ": seeds_differ,
        "first_divergence": divergence,
        "trail_skipped": trail_skipped,
        "result_equal": result_equal,
        "trail_len": (
            len(a.get("digest_trail") or []),
            len(b.get("digest_trail") or []),
        ),
        "identical": bool(
            comparable and divergence is None and result_equal is not False
        ),
    }
