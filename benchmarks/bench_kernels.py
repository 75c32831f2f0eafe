"""Kernel-level microbenchmarks: TwELL pack epilogue overhead, tile-skip
effectiveness, hybrid matmul vs dense — interpret-mode correctness-scale
timings plus the structural quantities (skip fractions, packed bytes) that
determine TPU performance."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core import hybrid as hyb
from repro.core import twell
from repro.kernels import ref


def run():
    key = jax.random.PRNGKey(0)
    m, k, n, tile, c = 128, 256, 1024, 256, 8
    x = jax.random.normal(key, (m, k)) * 0.5
    col = jax.random.uniform(jax.random.fold_in(key, 4), (n,)) < 0.1
    wg = jax.random.normal(jax.random.fold_in(key, 1), (k, n)) * 0.08 * col
    wu = jax.random.normal(jax.random.fold_in(key, 2), (k, n)) * 0.08
    wd = jax.random.normal(jax.random.fold_in(key, 3), (n, k)) * 0.08

    # pack epilogue cost vs bare matmul (jnp reference semantics)
    us_mm = timeit(jax.jit(lambda x: jax.nn.relu(x @ wg)), x)
    us_pack = timeit(jax.jit(lambda x: ref.twell_gate_matmul(
        x, wg, tile, c).values), x)
    emit("kernel_twell_pack_epilogue", us_pack,
         f"bare_matmul_us={us_mm:.0f};overhead={(us_pack/us_mm-1)*100:.0f}%")

    tw = ref.twell_gate_matmul(x, wg, tile, c)
    act = twell.tile_activity(tw, row_block=16)
    emit("kernel_tile_skip_fraction", 0.0,
         f"dead_tile_frac={float((act==0).mean()):.3f};"
         f"nnz_mean={float(tw.nnz.sum(-1).mean()):.1f}")

    us_dense = timeit(jax.jit(lambda x: ((x @ wu) * jax.nn.relu(x @ wg)) @ wd), x)
    us_fused = timeit(jax.jit(lambda x, v, i, nz: twell.fused_ffn_reference(
        x, twell.TwellActs(v, i, nz, jnp.bool_(False), tile, c, n), wu, wd)),
        x, tw.values, tw.indices, tw.nnz)
    emit("kernel_fused_ffn_vs_dense_cpu", us_fused,
         f"dense_us={us_dense:.0f};ratio={us_dense/us_fused:.2f}")

    h = jax.nn.relu(x @ wg)
    hb = hyb.pack(h, 64, m // 8)
    us_h2d = timeit(jax.jit(lambda hb, wd: hyb.hybrid_to_dense_matmul(hb, wd)),
                    hb, wd)
    us_d = timeit(jax.jit(lambda h, wd: h @ wd), h, wd)
    emit("kernel_hybrid_to_dense_cpu", us_h2d,
         f"dense_us={us_d:.0f};mem_ratio={hyb.memory_bytes(hb)/(h.size*4):.3f}")

    # interpret-mode Pallas correctness timings (not perf: documents that the
    # kernels execute end-to-end; TPU timing requires hardware)
    import os
    from repro.kernels.twell_pack import twell_gate_matmul_pallas
    sm = jax.random.normal(key, (32, 64)) * 0.5
    wgs = jax.random.normal(key, (64, 256)) * 0.05 - 0.02
    t0 = timeit(lambda: twell_gate_matmul_pallas(sm, wgs, 256, 8, "relu",
                                                 bm=32, bk=64), iters=3,
                warmup=1)
    emit("kernel_pallas_interpret_twell_pack", t0, "interpret-mode")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
