"""Plan/launch/collect step pipeline: shape bucketing and in-flight state.

The pipelined engine (``ServingEngine(pipeline=True)``) splits every step
into three phases:

* **plan** — pure host work: cancel processing, admission, preemption
  planning and block allocation. Runs while the device is still executing
  the previously launched step, so host scheduling comes off the critical
  path.
* **launch** — dispatch the jitted decode / draft+verify / prefill calls.
  KV pools are donated to each call (double-buffered: the consumed input
  buffer and the returned output buffer alternate), sampled-token outputs
  start their device→host copy immediately, and nothing blocks.
* **collect** — one step later, resolve the launched outputs (the only
  residual blocking, measured as ``StepStats.sync_ms``), commit tokens,
  emit events, and settle deferred cancels/preemptions.

The dataclasses below carry a launched phase's rows and unresolved device
values from launch(N) to collect(N) — i.e. they ARE the in-flight future.
They hold *references* to request objects on purpose: commit-time state
(sequence lengths, reservations) must be applied to the live requests.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

from repro.serving.request import Request

__all__ = [
    "DecodeLaunch", "InFlightStep", "PrefillLaunch", "SpecLaunch",
    "bucket", "bucket_grid", "start_host_copy",
]


def bucket(n: int, lo: int, hi: int) -> int:
    """Round ``n`` up to a power-of-two multiple of ``lo``, capped at
    ``hi`` — the shared bucketing rule for decode batch, prefill chunk and
    spec shapes. A finite bucket grid keeps the number of distinct jitted
    shapes small enough to precompile exhaustively (see
    ``ServingEngine.warmup``)."""
    b = lo
    while b < n:
        b *= 2
    return min(b, hi)


def bucket_grid(lo: int, hi: int) -> List[int]:
    """Every padded size ``bucket(n, lo, hi)`` can produce for n in
    [1, hi], ascending. This is the exact set of shapes steady-state
    serving can request, so walking it at startup precompiles everything."""
    return sorted({bucket(n, lo, hi) for n in range(1, hi + 1)})


def start_host_copy(value: Any) -> None:
    """Kick off the device→host transfer of a launched output without
    blocking. By collect time the copy has typically landed, so the
    residual ``sync_ms`` shrinks to the tail of the transfer instead of
    the full device step."""
    copy = getattr(value, "copy_to_host_async", None)
    if copy is not None:
        copy()


@dataclasses.dataclass
class DecodeLaunch:
    """One launched (unresolved) batched decode call."""
    rows: List[Request]
    batch: int                       # live rows (<= padded)
    padded: int
    next_toks: Any                   # device (padded,) int32, unresolved
    logits: Any                      # device last-position logits
    ffn_aux: Optional[dict]


@dataclasses.dataclass
class SpecLaunch:
    """One launched draft+verify pair. The verify token block is built on
    device from the draft output, so both dispatches go out back-to-back
    with no host readback in between."""
    rows: List[Request]
    batch: int
    padded: int
    k_effs: List[int]
    all_greedy: bool
    d_toks: Any                      # device (padded, k) int32
    d_logits: Any                    # device (padded, k, V); unused if greedy
    t_logits: Any                    # device (padded, k+1, V) float32


@dataclasses.dataclass
class PrefillLaunch:
    """One launched chunked-prefill call over every in-flight prefill row."""
    rows: List[Request]
    chunk_lens: List[int]
    tok: Any                         # device (padded,) int32 next tokens
    logits: Any
    ffn_aux: Optional[dict]


@dataclasses.dataclass
class InFlightStep:
    """Everything launch(N) dispatched, awaiting collect at step N+1 (or
    ``flush()``). While an InFlightStep exists the engine must not free or
    COW-copy any block its tables reference — cancels and preemptions on
    launched rows are deferred and settle at collect, right after the
    in-flight tokens commit."""
    decode: Optional[DecodeLaunch]
    spec: Optional[SpecLaunch]
    prefill: Optional[PrefillLaunch]
    t_launched: float                # perf_counter right after dispatch


def sequence_hash(tables: Sequence[Tuple[int, ...]]) -> int:
    """Order-sensitive fingerprint of a set of block tables (test helper
    for asserting launched tables stay untouched across a cancel)."""
    return hash(tuple(tuple(t) for t in tables))
