"""Roofline analysis (deliverable g): derives the three terms per
(arch x shape x mesh) from the dry-run artifacts in results/dryrun/.

  compute    = dot_flops_per_device / PEAK_FLOPS          [s]
  memory     = hbm_bytes_per_device / HBM_BW              [s]
  collective = collective_bytes_per_device / LINK_BW      [s]

All numerators are per-device and trip-count-corrected from the post-SPMD
HLO (see repro/launch/hlo_analysis.py for methodology + caveats).

MODEL_FLOPS = 6*N*D (train) / 2*N*D (prefill/decode), N = matmul-visible
params (MoE: active experts only). The per-cell report adds:
  - dominant term (the bottleneck),
  - MODEL/HLO flop ratio (remat + masked-attention + dispatch waste),
  - mfu_upper = ideal MFU of this compiled program (model flops per chip /
    peak) / max(term) — the §Perf hillclimbing objective.

Usage: PYTHONPATH=src python -m benchmarks.roofline [--csv out.csv]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# the dry-run cells are priced at the TPU v5e's published per-chip peaks
# (the device table in repro.observability.accounting)
from repro.observability.accounting import PEAKS, V5E  # noqa: E402

PEAK_FLOPS = PEAKS[V5E].flops
HBM_BW = PEAKS[V5E].hbm_bw
LINK_BW = PEAKS[V5E].link_bw

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")


def model_flops(arch: str, shape_kind: str, tokens: int, param_count: int):
    """Matmul-visible params; MoE uses active-expert count. Delegates to the
    shared MODEL_FLOPS convention in repro.observability.accounting."""
    from repro.configs import get_config
    from repro.observability import accounting
    cfg = get_config(arch)
    return accounting.model_flops(cfg, param_count, tokens,
                                  train=shape_kind == "train")


def load_cells(results_dir: str = None):
    cells = []
    for f in sorted(glob.glob(os.path.join(results_dir or RESULTS,
                                           "*.json"))):
        d = json.load(open(f))
        if d.get("status") == "ok":
            cells.append(d)
    return cells


def analyze_cell(d: dict) -> dict:
    from repro.config import shape_by_name
    shape = shape_by_name(d["shape"])
    chips = d["n_devices"]
    tokens = shape.global_batch * (shape.seq_len if d["kind"] != "decode"
                                   else 1)
    terms = {
        "compute_s": d["dot_flops_per_device"] / PEAK_FLOPS,
        "memory_s": d.get("hbm_bytes_per_device", 0) / HBM_BW,
        "collective_s": d["collective_bytes_per_device"]["total"] / LINK_BW,
    }
    dom = max(terms, key=terms.get)
    mf = model_flops(d["arch"], d["kind"], tokens, d["param_count"])
    hlo_total = d["dot_flops_per_device"] * chips
    ratio = mf / hlo_total if hlo_total else float("nan")
    ideal = (mf / chips) / PEAK_FLOPS
    bound = max(terms.values())
    return {
        "arch": d["arch"], "shape": d["shape"], "mesh": d["mesh"],
        "kind": d["kind"], "chips": chips,
        **{k: round(v, 6) for k, v in terms.items()},
        "dominant": dom.replace("_s", ""),
        "model_flops": mf,
        "model_over_hlo": round(ratio, 4),
        "mfu_upper": round(ideal / bound, 4) if bound else float("nan"),
        "peak_gb": round(d["peak_bytes_per_device"] / 1e9, 2),
        "fits_16gb": d["peak_bytes_per_device"] <= 16e9,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default=None,
                    help="dry-run artifact dir (default results/dryrun)")
    ap.add_argument("--csv", default=os.path.join(RESULTS, "..",
                                                  "roofline.csv"))
    ap.add_argument("--md", default=os.path.join(RESULTS, "..",
                                                 "roofline.md"))
    args = ap.parse_args()
    rows = [analyze_cell(d) for d in load_cells(args.dir)]
    rows.sort(key=lambda r: (r["arch"], r["shape"], r["mesh"]))
    cols = ["arch", "shape", "mesh", "kind", "compute_s", "memory_s",
            "collective_s", "dominant", "model_over_hlo", "mfu_upper",
            "peak_gb", "fits_16gb"]
    with open(args.csv, "w") as f:
        f.write(",".join(cols) + "\n")
        for r in rows:
            f.write(",".join(str(r[c]) for c in cols) + "\n")
    with open(args.md, "w") as f:
        f.write("| " + " | ".join(cols) + " |\n")
        f.write("|" + "---|" * len(cols) + "\n")
        for r in rows:
            f.write("| " + " | ".join(str(r[c]) for c in cols) + " |\n")
    print(f"wrote {args.csv} ({len(rows)} cells)")
    for r in rows:
        if r["mesh"].startswith("16"):
            print(f"{r['arch']:24s} {r['shape']:12s} dom={r['dominant']:10s} "
                  f"mfu_up={r['mfu_upper']:7.3f} c={r['compute_s']:.4f} "
                  f"m={r['memory_s']:.4f} x={r['collective_s']:.4f} "
                  f"model/hlo={r['model_over_hlo']:.3f}")


if __name__ == "__main__":
    main()
