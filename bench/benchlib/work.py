"""Operations and bytes the model's work needs, from shapes alone.

It follows the accounting of ``repro.observability.accounting`` (2 FLOPs
per weight per token for the matmuls, Eq. 3 for the TwELL FFN: a dense gate
matmul, then ``2 d`` per non-zero on each pattern-side matrix), never a
kernel's own loop structure: what a kernel reads or computes beyond this is
its inefficiency, not work. Bytes count each tensor the algorithm must move
once, at the served dtype.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    dtype_bytes: int = 2

    @classmethod
    def from_model(cls, m: dict) -> "Dims":
        nbytes = {"bfloat16": 2, "float16": 2, "float32": 4}[m["dtype"]]
        vocab = -(-m["vocab_size"] // 128) * 128
        return cls(m["num_layers"], m["d_model"], m["num_heads"],
                   m["num_kv_heads"], m["head_dim"], m["d_ff"], vocab,
                   bool(m["gated"]), nbytes)

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def layer_matmul_params(self) -> int:
        attn = 2 * self.d * self.q_width + 2 * self.d * self.kv_width
        return attn + (3 if self.gated else 2) * self.d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.vocab * self.d

    @property
    def matmul_params(self) -> int:
        """Weights a token multiplies through: every layer and the head (the
        input embedding is a gather)."""
        return self.layers * self.layer_matmul_params + self.head_params

    @property
    def kv_bytes_per_token(self) -> int:
        """Cached K and V of one token over all layers."""
        return 2 * self.layers * self.kv_width * self.dtype_bytes


def attn_flops(dims: Dims, ctx: float) -> float:
    """One query row against ``ctx`` keys, all layers: QK^T and PV."""
    return 4.0 * ctx * dims.q_width * dims.layers


def decode_token_flops(dims: Dims, ctx: float) -> float:
    """Dense-equivalent model FLOPs of one decoded token whose query
    attends ``ctx`` keys (itself included)."""
    return 2.0 * dims.matmul_params + attn_flops(dims, ctx)


def prefill_flops(dims: Dims, tokens: float, ctx_sum: float,
                  heads_run: float) -> float:
    """Dense-equivalent model FLOPs of ``tokens`` prompt tokens whose
    queries attend ``ctx_sum`` keys in all, and ``heads_run`` output-head
    projections (one per prompt whose last position was computed)."""
    body = 2.0 * (dims.matmul_params - dims.head_params) * tokens
    return body + attn_flops(dims, ctx_sum) + 2.0 * dims.head_params \
        * heads_run


def attn_decode_call(dims: Dims, ctx: float):
    """(FLOPs, bytes) of the decode attention of one row in one layer: its
    query against ``ctx`` cached keys and values."""
    flops = 4.0 * ctx * dims.q_width
    nbytes = (2.0 * ctx * dims.kv_width + 2.0 * dims.q_width) \
        * dims.dtype_bytes
    return flops, nbytes


def ffn_decode_call(dims: Dims, rows: float, nnz: float):
    """(FLOPs, bytes) of one layer's TwELL FFN over ``rows`` tokens with
    ``nnz`` non-zero hidden units each (Eq. 3): the dense gate matmul reads
    the whole gate matrix once; the pattern side reads the rows of the up
    and down matrices for one row's non-zeros (a lower bound on the union
    over the batch), and the activations go in and out once."""
    pattern_mats = 2 if dims.gated else 1
    flops = rows * (2.0 * dims.d * dims.d_ff
                    + 2.0 * pattern_mats * dims.d * nnz)
    nbytes = (dims.d * dims.d_ff + pattern_mats * nnz * dims.d
              + 2.0 * rows * dims.d) * dims.dtype_bytes
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
