"""Kernels: the paged decode attention kernel's share of its roofline in the
traced span. Least time: every decoded token's query reads its context's
cached K and V once in every layer (``work.attn_decode_call``); each call is
memory-bound, so the bound over the sum is the sum of the bounds. Measured:
the summed device time of the kernel's events. Moves ``itl_p95_ms``."""
from benchlib import work


def read(run):
    seconds = run.trace.kernel_s("attn_decode", "decode")
    n, ctx = run.traced_tokens()
    if seconds <= 0 or n == 0:
        return None
    d = run.dims
    flops, nbytes = work.attn_decode_call(d, ctx)
    nbytes += 2.0 * (n - 1) * d.q_width * d.dtype_bytes  # q in, out per row
    least = work.least_seconds(flops * d.layers, nbytes * d.layers,
                               run.peaks)
    return 100.0 * least / seconds
