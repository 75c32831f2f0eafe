"""Sparsity & compute observability: per-layer FLOPs/occupancy accounting
(``accounting``) and structured JSONL run logs (``runlog``). See
docs/observability.md."""
from repro.observability.accounting import (PEAKS, V5E, DevicePeaks,
                                            LayerCost, SparsityReport,
                                            ffn_bytes_per_token,
                                            ffn_dense_flops_per_token,
                                            ffn_effective_flops_per_token,
                                            matmul_params, mfu, model_flops,
                                            param_count, stats_from_hidden,
                                            tile_occupancy_from_twell)
from repro.observability.runlog import (SCHEMA_VERSION, RunLogger,
                                        iter_runlog, read_runlog)

__all__ = [
    "PEAKS", "V5E", "DevicePeaks",
    "LayerCost", "SparsityReport",
    "ffn_bytes_per_token", "ffn_dense_flops_per_token",
    "ffn_effective_flops_per_token", "matmul_params", "mfu", "model_flops",
    "param_count", "stats_from_hidden", "tile_occupancy_from_twell",
    "SCHEMA_VERSION", "RunLogger", "iter_runlog", "read_runlog",
]
