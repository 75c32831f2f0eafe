"""Model step: dense-equivalent model FLOPs of the prompt tokens prefilled
in the traced span (the output head counted once per prompt that finished)
over the device time of the prefill step programs times the chip's peak.
Moves ``ttft_p90_ms``."""
import math

from benchlib import work


def read(run):
    seconds = run.trace.program_s("prefill")
    tokens = sum(s.prefill_tokens for s in run.traced_steps())
    ctx_mean = run.prompt_ctx_mean()
    if seconds <= 0 or tokens == 0 or math.isnan(ctx_mean):
        return None
    flops = work.prefill_flops(run.dims, tokens, tokens * ctx_mean,
                               run.prompts_finished())
    return 100.0 * flops / (seconds * run.peaks["bf16_flops"])
