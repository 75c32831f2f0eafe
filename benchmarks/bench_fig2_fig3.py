"""Fig. 2 + Fig. 3 reproduction: training curves and final quality/sparsity
across L1 regularization levels (held-out CE stands in for the downstream
suite, which needs external task data)."""
from __future__ import annotations

import json
import os

from benchmarks.common import emit, timeit, tiny_cfg, train_tiny

L1_LEVELS = [0.0, 0.3, 1.0, 3.0, 10.0]
OUT = os.path.join(os.path.dirname(__file__), "..", "results",
                   "bench_fig2_fig3.json")


def run(steps=250):
    results = []
    for l1 in L1_LEVELS:
        r = train_tiny(tiny_cfg(l1=l1), steps=steps)
        results.append({"l1": l1, "curve": r["curve"], "ce": r["ce"],
                        "nnz": r["nnz"], "nnz_max": r["nnz_max"]})
        emit(f"fig2_train_curve_l1={l1}", 0.0,
             f"final_ce={r['ce']:.4f};nnz={r['nnz']:.1f};nnz_max={r['nnz_max']}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1)
    # Fig. 3 headline: mild L1 costs little CE but slashes nnz
    base = results[0]
    for r in results[1:]:
        emit("fig3_quality_vs_sparsity", 0.0,
             f"l1={r['l1']};ce_ratio={r['ce'] / base['ce']:.4f};"
             f"nnz_ratio={r['nnz'] / max(base['nnz'], 1e-9):.4f}")
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
