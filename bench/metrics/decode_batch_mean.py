"""Scheduler: mean live rows of the decode calls the engine launched in the
window (``StepStats.decode_batch`` of steps that decoded). Moves
``itl_p95_ms``."""


def read(run):
    rows = [s.decode_batch for s in run.win.stats if s.decode_batch > 0]
    return sum(rows) / len(rows) if rows else None
