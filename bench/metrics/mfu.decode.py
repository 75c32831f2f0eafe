"""Model step: dense-equivalent model FLOPs of the tokens decoded in the
traced span (2 x matmul weights, plus 4 x context x query width per layer
for attention) over the device time of the decode step programs times the
chip's peak. Moves ``itl_p95_ms``."""
from benchlib import work


def read(run):
    seconds = run.trace.program_s("decode")
    n, ctx = run.traced_tokens()
    if seconds <= 0 or n == 0:
        return None
    flops = n * 2.0 * run.dims.matmul_params + work.attn_flops(run.dims, ctx)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops"])
