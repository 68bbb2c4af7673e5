"""The measurement harness: interleaved best-of-k twin-arm timing.

Measure every arm at the SAME operating point, interleave the rounds so
machine drift on a noisy shared host lands on every arm equally, take
the best-of-k wall per arm, and never compare numbers measured at
different moments of the battery.  :func:`measure_arms` is that idiom,
and the schedule search builds on it.

Contract:

* **Interleaved rounds**: round ``r`` runs every live arm once, in
  order; an arm's headline wall is its best (min) across rounds.  A
  load spike hits whichever arm was running, not systematically the
  same one.
* **Compile/run split**: each arm's optional ``prepare()`` (trace +
  warm-compile — the ``with_report`` split's compile leg) is timed
  separately and never inside a timed round; an arm whose prepare
  exceeds ``compile_budget_s`` is recorded ``SKIPPED`` with the
  measured time — never silently dropped.
* **Noise floor from self-vs-self**: the baseline arm runs TWICE per
  round (a blind twin).  The relative rate gap between its two
  best-of-k measurements is the floor below which a "win" is
  indistinguishable from machine noise — the search HOLDs the default
  unless a challenger clears it.
* **Wall budget**: rounds stop early once ``budget_s`` is spent
  (every arm still has equal rounds — the budget cuts whole rounds);
  arms that never got a round are ``SKIPPED`` with the reason
  recorded.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List, Optional

__all__ = ["Arm", "ArmResult", "MeasureReport", "measure_arms"]

OK = "ok"
SKIPPED = "skipped"

#: the baseline twin's arm name suffix (never reported as its own arm —
#: it exists only to estimate the noise floor)
_TWIN = "__self_twin__"


@dataclasses.dataclass
class Arm:
    """One measurable arm.  ``run()`` is a single timed invocation and
    returns an opaque payload (the last round's payload is kept on the
    result — callers stash event counts / digests there);
    ``prepare()`` is the untimed-region compile/warm leg (timed
    separately as the arm's ``compile_s``)."""

    name: str
    run: Callable[[], Any]
    prepare: Optional[Callable[[], Any]] = None
    meta: Any = None
    #: optional program-size record for this arm (the
    #: ``obs.program_size`` dict — eqns / jaxpr_bytes / ...), carried
    #: verbatim onto the result and report so compile cost rides next
    #: to wall time (docs/25_compile_wall.md)
    program_size: Optional[dict] = None


@dataclasses.dataclass
class ArmResult:
    name: str
    status: str                    # "ok" | "skipped"
    walls: List[float]
    best_wall: Optional[float]
    compile_s: Optional[float]
    payload: Any = None
    skip_reason: Optional[str] = None
    meta: Any = None
    program_size: Optional[dict] = None

    def rate(self, units: Optional[float]) -> Optional[float]:
        """``units / best_wall`` (events, replications, ... — the
        caller's unit), or None when unmeasured."""
        if units is None or not self.best_wall:
            return None
        return units / self.best_wall


@dataclasses.dataclass
class MeasureReport:
    """What :func:`measure_arms` returns: per-arm results in input
    order, the rounds actually completed, and the self-vs-self noise
    floor (relative rate fraction; None when ``noise_twin=False`` or
    the twin never completed a round)."""

    arms: List[ArmResult]
    baseline: str
    rounds_done: int
    noise_floor_frac: Optional[float]
    wall_s: float

    def arm(self, name: str) -> ArmResult:
        for a in self.arms:
            if a.name == name:
                return a
        raise KeyError(name)

    def beats_floor(self, challenger: str, units_of=None) -> bool:
        """True when ``challenger``'s best wall beats the baseline's by
        MORE than the noise floor (the search's win criterion —
        docs/21_autotune.md).  With no floor measured, any win counts
        (the caller opted out of the twin)."""
        base = self.arm(self.baseline)
        ch = self.arm(challenger)
        if base.best_wall is None or ch.best_wall is None:
            return False
        # rates compare inversely to walls; units cancel
        gain = base.best_wall / ch.best_wall - 1.0
        floor = self.noise_floor_frac or 0.0
        return gain > floor

    def to_json(self, units_of=None) -> dict:
        """A JSON-safe summary (payloads are reduced through
        ``units_of(payload) -> float|None`` when given)."""
        arms = []
        for a in self.arms:
            units = units_of(a.payload) if (
                units_of is not None and a.payload is not None
            ) else None
            arms.append({
                "name": a.name,
                "status": a.status,
                "walls_s": [round(w, 6) for w in a.walls],
                "best_wall_s": a.best_wall,
                "compile_s": a.compile_s,
                "units": units,
                "rate": a.rate(units),
                "skip_reason": a.skip_reason,
                "program_size": a.program_size,
            })
        return {
            "arms": arms,
            "baseline": self.baseline,
            "rounds_done": self.rounds_done,
            "noise_floor_frac": self.noise_floor_frac,
            "wall_s": self.wall_s,
        }


def measure_arms(
    arms,
    *,
    repeats: int = 3,
    baseline: int = 0,
    budget_s: Optional[float] = None,
    compile_budget_s: Optional[float] = None,
    noise_twin: bool = True,
    on_round: Optional[Callable[[int], None]] = None,
) -> MeasureReport:
    """Measure ``arms`` (a list of :class:`Arm`) interleaved
    best-of-``repeats`` at one operating point.  ``baseline`` indexes
    the incumbent arm (run twice per round when ``noise_twin`` — its
    twin's gap is the noise floor).  ``on_round(r)`` is the per-round
    progress hook (a caller's watchdog heartbeat).  Budgets are wall
    seconds over the whole call; blowing one records SKIPPED arms /
    truncated rounds, never a silent drop."""
    arms = list(arms)
    if not arms:
        raise ValueError("measure_arms: no arms")
    if not 0 <= baseline < len(arms):
        raise ValueError(
            f"baseline index {baseline} out of range for {len(arms)} arms"
        )
    if repeats < 1:
        raise ValueError(f"repeats must be >= 1, got {repeats}")
    t_start = time.perf_counter()

    def spent() -> float:
        return time.perf_counter() - t_start

    results = [
        ArmResult(
            name=a.name, status=OK, walls=[], best_wall=None,
            compile_s=None, meta=a.meta, program_size=a.program_size,
        )
        for a in arms
    ]

    # -- prepare legs (the compile/run split): untimed-region, budgeted
    live: List[int] = []
    for i, arm in enumerate(arms):
        if budget_s is not None and spent() > budget_s and i != baseline:
            results[i].status = SKIPPED
            results[i].skip_reason = (
                f"wall budget ({budget_s:.1f}s) exhausted before prepare"
            )
            continue
        if arm.prepare is not None:
            # time inside try/finally: a prepare that RAISES (a caller
            # aborting a hung compile via its own timeout) still gets
            # its measured partial seconds attached to the skip record
            # — "slow compile" and "hung compile" must stay
            # distinguishable in the report (docs/25_compile_wall.md)
            t0 = time.perf_counter()
            try:
                arm.prepare()
            except Exception as e:
                results[i].compile_s = time.perf_counter() - t0
                if i == baseline:
                    raise
                results[i].status = SKIPPED
                results[i].skip_reason = (
                    f"prepare raised after {results[i].compile_s:.1f}s: "
                    f"{type(e).__name__}: {e}"
                )
                continue
            results[i].compile_s = time.perf_counter() - t0
            if (
                compile_budget_s is not None
                and results[i].compile_s > compile_budget_s
                and i != baseline
            ):
                results[i].status = SKIPPED
                results[i].skip_reason = (
                    f"compile {results[i].compile_s:.1f}s over the "
                    f"{compile_budget_s:.1f}s compile budget"
                )
                continue
        live.append(i)
    if baseline not in live:
        raise RuntimeError(
            "measure_arms: the baseline arm was skipped — there is no "
            "incumbent to race (raise the budgets)"
        )

    # -- interleaved rounds: [baseline twin?] + every live arm, in order
    twin_walls: List[float] = []
    rounds_done = 0
    for r in range(repeats):
        if budget_s is not None and rounds_done >= 1 and spent() > budget_s:
            break  # whole-round cut: every arm keeps equal rounds
        for i in live:
            t0 = time.perf_counter()
            payload = arms[i].run()
            wall = time.perf_counter() - t0
            results[i].walls.append(wall)
            results[i].payload = payload
            if i == baseline and noise_twin:
                t0 = time.perf_counter()
                arms[i].run()
                twin_walls.append(time.perf_counter() - t0)
        rounds_done += 1
        if on_round is not None:
            on_round(rounds_done)

    for i in live:
        res = results[i]
        if res.walls:
            res.best_wall = min(res.walls)
        elif res.status == OK:
            res.status = SKIPPED
            res.skip_reason = (
                f"wall budget ({budget_s:.1f}s) exhausted before any "
                "round"
            )

    floor = None
    base = results[baseline]
    if noise_twin and twin_walls and base.best_wall:
        tw = min(twin_walls)
        hi, lo = max(base.best_wall, tw), min(base.best_wall, tw)
        # relative RATE gap between two measurements of the same arm:
        # rate ~ 1/wall, so the gap is hi/lo - 1
        floor = hi / lo - 1.0

    return MeasureReport(
        arms=results,
        baseline=arms[baseline].name,
        rounds_done=rounds_done,
        noise_floor_frac=floor,
        wall_s=spent(),
    )
