"""Fig. 4 + Fig. 5 reproduction: inference/training efficiency vs sparsity.

What is *measured* on CPU vs what is *modeled* for TPU (full methodology in
EXPERIMENTS.md):

measured (CPU wall-time, inputs passed as args — no constant folding):
- GEMV/decode regime (small M): packed-gather FFN vs dense — the regime
  where sparse execution wins even without specialized hardware;
- batched regime: dense baseline timing (the CPU has no MXU to skip, so
  batched sparse wins are modeled, not measured);
- hybrid packed-activation bytes vs dense activation bytes (exact, the
  Fig. 5 / Table 1 peak-memory mechanism).

modeled (structural quantities that determine TPU gains):
- dead-(row-block x tile) fraction under *correlated* activation patterns
  (the paper's L2-hit observation: neighbouring tokens fire the same
  neurons) -> MXU work skipped by the tile-skip kernel;
- active-FLOP fraction (energy-per-token proxy).
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core import hybrid as hyb
from repro.core import twell

OUT = os.path.join(os.path.dirname(__file__), "..", "results",
                   "bench_fig4_fig5.json")

K, N = 512, 4096
TILE = 256


def _gate(key, m, sparsity, corr_block=8, corr=0.9):
    """Correlated sparse gate activations: tokens come in blocks sharing a
    base firing pattern (prob `corr`), plus idiosyncratic activations."""
    nb = m // corr_block
    p_active = 1 - sparsity
    base = jax.random.uniform(key, (nb, 1, N)) < p_active
    keep_base = jax.random.uniform(jax.random.fold_in(key, 1),
                                   (nb, corr_block, N)) < corr
    idio = jax.random.uniform(jax.random.fold_in(key, 2),
                              (nb, corr_block, N)) < p_active * (1 - corr)
    mask = ((base & keep_base) | idio).reshape(m, N)
    vals = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (m, N))) + 0.1
    return jnp.where(mask, vals, 0.0)


def run():
    key = jax.random.PRNGKey(0)
    results = []

    dense_fwd = jax.jit(lambda x, wu, wd, hg: ((x @ wu) * hg) @ wd)

    def sparse_fwd(x, wu, wd, vals, idx, nnz, dense, dmap, live):
        """Row-sparse FFN via the hybrid format (the training forward path):
        pattern-only h_u, elementwise gate, ELL down-projection."""
        pattern = hyb.HybridActs(vals, idx, nnz, ~live, dense, dmap,
                                 jnp.bool_(False), N)
        hu = hyb.dense_to_hybrid_matmul(x, wu, pattern)
        h = pattern._replace(
            ell_values=pattern.ell_values * hu.ell_values,
            dense_rows=pattern.dense_rows * hu.dense_rows)
        return hyb.hybrid_to_dense_matmul(h, wd)

    wu = jax.random.normal(jax.random.fold_in(key, 1), (K, N)) * 0.05
    wd = jax.random.normal(jax.random.fold_in(key, 2), (N, K)) * 0.05

    for regime, m in [("decode_gemv", 8), ("batched", 64)]:
        x = jax.random.normal(jax.random.fold_in(key, m), (m, K))
        hg0 = _gate(jax.random.fold_in(key, 100 + m), m, 0.0, corr=1.0)
        us_dense = timeit(dense_fwd, x, wu, wd, hg0, iters=5)
        for sp, c in [(0.9, 2), (0.99, 8), (0.999, 16)]:
            hg = _gate(jax.random.fold_in(key, int(sp * 1e4) + m), m, sp)
            act = twell.tile_activity(twell.pack(hg, TILE, 8, mask=hg > 0),
                                      row_block=8)
            dead_frac = float((act == 0).mean())
            nnz_mean = float((hg != 0).sum(-1).mean())
            ew = max(16, int(-(-2 * nnz_mean // 16) * 16))
            hb = hyb.pack(hg, ew, max(1, m // 8))
            mem_ratio = hyb.memory_bytes(hb) / (hg.size * 4)
            s_jit = jax.jit(sparse_fwd)
            us_sparse = timeit(s_jit, x, wu, wd, hb.ell_values,
                               hb.ell_indices, hb.row_nnz, hb.dense_rows,
                               hb.dense_map, ~hb.is_dense, iters=5)
            row = {
                "regime": regime, "m": m, "sparsity": sp,
                "us_dense": us_dense, "us_sparse": us_sparse,
                "cpu_speedup": us_dense / us_sparse,
                "nnz_mean": nnz_mean,
                "dead_tile_frac": dead_frac,
                "modeled_tileskip_speedup": 1.0 / max(1 - dead_frac, 1e-3),
                "active_flop_frac": 1 - sp,
                "ell_width": ew,
                "hybrid_mem_ratio": mem_ratio,
            }
            results.append(row)
            emit(f"fig4_{regime}_sparsity={sp}", us_sparse,
                 f"dense_us={us_dense:.0f};cpu_speedup={row['cpu_speedup']:.2f};"
                 f"dead_tile_frac={dead_frac:.3f};"
                 f"tileskip_model={row['modeled_tileskip_speedup']:.2f}")
            emit(f"fig5_train_mem_{regime}_sparsity={sp}", 0.0,
                 f"ell_width={ew};hybrid_mem_ratio={mem_ratio:.3f};"
                 f"peak_mem_reduction={1 - mem_ratio:.3f}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
