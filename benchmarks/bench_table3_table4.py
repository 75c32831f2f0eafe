"""Table 3 + Table 4 reproduction (App. C): activation-function and
gated/non-gated ablations under the sparsity recipe."""
from __future__ import annotations

import json
import os

from benchmarks.common import emit, tiny_cfg, train_tiny

OUT = os.path.join(os.path.dirname(__file__), "..", "results",
                   "bench_table3_table4.json")


def run(steps=200):
    results = {}
    # --- Table 3: ReLU vs SiLU (SiLU cannot sparsify) -----------------------
    for act, l1 in [("relu", 0.0), ("silu", 0.0), ("relu", 3.0)]:
        r = train_tiny(tiny_cfg(l1=l1, activation=act), steps=steps)
        key = f"t3_{act}_l1={l1}"
        results[key] = {"ce": r["ce"], "nnz": r["nnz"]}
        emit(f"table3_{act}_l1={l1}", 0.0,
             f"ce={r['ce']:.4f};nnz={r['nnz']:.1f}")

    # --- Table 4: gated vs non-gated at two L1 levels ------------------------
    for gated in [True, False]:
        for l1 in [0.0, 1.0, 3.0]:
            # non-gated uses 4x wider FFN at equal params (paper App. B)
            cfg = tiny_cfg(l1=l1, gated=gated,
                           d_ff=256 if gated else 384)
            r = train_tiny(cfg, steps=steps)
            key = f"t4_{'gated' if gated else 'nongated'}_l1={l1}"
            results[key] = {"ce": r["ce"], "nnz": r["nnz"]}
            emit(f"table4_{'gated' if gated else 'nongated'}_l1={l1}", 0.0,
                 f"ce={r['ce']:.4f};nnz={r['nnz']:.1f}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
