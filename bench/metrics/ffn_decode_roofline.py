"""Kernels: the TwELL FFN kernels' (gate matmul with pack, fused up/down)
share of their roofline in the decode step programs of the traced span.
Least time per decode step and layer (``work.ffn_decode_call``): the gate
matrix read once, the pattern rows of one row's non-zeros (the program's
measured mean, ``serving_ffn_sparsity``), activations in and out; each call
is memory-bound. Measured: the summed device time of the kernels' events
inside decode programs. Moves ``itl_p95_ms``."""
from benchlib import work


def read(run):
    seconds = run.trace.kernel_s("ffn", "decode")
    steps = run.trace.program_count("decode")
    n, _ = run.traced_tokens()
    summary = run.engine.telemetry.summary().get("sparsity") or {}
    sparsity = summary.get("mean_ffn_sparsity")
    if seconds <= 0 or steps == 0 or n == 0 or sparsity is None:
        return None
    d = run.dims
    nnz = (1.0 - sparsity) * d.d_ff
    f_step, b_step = work.ffn_decode_call(d, 0, nnz)     # per call, no rows
    f_row, b_row = work.ffn_decode_call(d, 1, nnz)
    flops = d.layers * n * (f_row - f_step)
    nbytes = d.layers * (steps * b_step + n * (b_row - b_step))
    least = work.least_seconds(flops, nbytes, run.peaks)
    return 100.0 * least / seconds
