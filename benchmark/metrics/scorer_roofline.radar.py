"""The scorer kernel's share of its roofline, in %, over the traced
slice: the least time the chip could take to score the slice's dwells
(every target once a dwell; the larger of the operations over peak
FLOP/s and the bytes over peak bytes/s, ``benchmark/peaks.json``) over
the kernel's device time there.  The slice's dwells are its scorer calls
(one a round) times the window's dwells a round.  The peak FLOP/s is the
chip's bf16 one, the only one published; the kernel runs float32
operands."""

from benchmark.metrics import awacs_trace as at


def read(run):
    k = at.scorer(run)
    dwells_per_round = at.per_round(run, "boundary_dispatches")
    peak = at.peaks(run.get("counters", {}).get("device_kind", ""))
    if not k or not dwells_per_round or not peak:
        return None
    seconds, calls = k
    rows = calls * dwells_per_round * at.targets()
    least = max(rows * at.FLOP_PER_ROW / peak["bf16_flops_per_s"],
                rows * at.BYTES_PER_ROW / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
