"""Model step: mean device time of one decode step program
(``jit_decode_step`` executions wholly inside the traced span). Moves
``itl_p95_ms``: every decoded token waits one such step."""
from benchlib import spans


def read(run):
    ms = spans.program_ms(run.trace, "decode_step")
    return sum(ms) / len(ms) if ms else None
