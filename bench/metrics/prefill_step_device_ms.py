"""Model step: mean device time of one prefill step program
(``jit_prefill_step`` executions wholly inside the traced span). Moves
``ttft_p90_ms``: a prompt waits one such step per chunk."""
from benchlib import spans


def read(run):
    ms = spans.program_ms(run.trace, "prefill_step")
    return sum(ms) / len(ms) if ms else None
