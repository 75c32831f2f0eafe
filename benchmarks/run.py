"""Benchmark harness entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus a roofline summary when
dry-run artifacts exist). Budget-controlled via REPRO_BENCH_STEPS.

  PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

STEPS = int(os.environ.get("REPRO_BENCH_STEPS", "200"))


def main() -> None:
    print("name,us_per_call,derived")
    t0 = time.time()

    from benchmarks import (bench_fig2_fig3, bench_fig4_fig5, bench_fig6_fig7,
                            bench_kernels, bench_table1, bench_table3_table4,
                            bench_table5)

    bench_kernels.run()
    bench_fig4_fig5.run()
    bench_fig2_fig3.run(steps=STEPS)
    bench_table1.run(steps=max(STEPS // 2, 50))
    bench_fig6_fig7.run(steps=STEPS)
    bench_table3_table4.run(steps=STEPS)
    bench_table5.run(steps=STEPS)

    # roofline summary from dry-run artifacts, if present
    try:
        from benchmarks import roofline
        rows = [roofline.analyze_cell(d) for d in roofline.load_cells()]
        for r in rows:
            if r["mesh"].startswith("16"):
                print(f"roofline_{r['arch']}_{r['shape']},0.0,"
                      f"dominant={r['dominant']};mfu_upper={r['mfu_upper']};"
                      f"model_over_hlo={r['model_over_hlo']}")
    except Exception as e:  # dry-run artifacts absent
        print(f"roofline_summary,0.0,skipped({type(e).__name__})")

    print(f"bench_total,{(time.time()-t0)*1e6:.0f},wall_seconds="
          f"{time.time()-t0:.1f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
