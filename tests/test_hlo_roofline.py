"""Tests for launch/hlo_analysis.py and benchmarks/roofline.py: FLOP/byte
extraction from HLO text (synthetic + a real jitted scan) and the roofline
term math over a synthetic dry-run artifact."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
import roofline  # noqa: E402


# A minimal post-SPMD-style module: entry calls while(cond, body) with a
# 4-trip condition; the body runs one dot (8x16 @ 16x32) and one all-reduce
# of f32[64].
_SYNTH_HLO = """\
HloModule synth

%wcond (p.0: (s32[], f32[8,16], f32[16,32], f32[8,32])) -> pred[] {
  %p.0 = (s32[], f32[8,16], f32[16,32], f32[8,32]) parameter(0)
  %iter = s32[] get-tuple-element(%p.0), index=0
  %limit = s32[] constant(4)
  ROOT %lt = pred[] compare(%iter, %limit), direction=LT
}

%wbody (p.1: (s32[], f32[8,16], f32[16,32], f32[8,32])) -> (s32[], f32[8,16], f32[16,32], f32[8,32]) {
  %p.1 = (s32[], f32[8,16], f32[16,32], f32[8,32]) parameter(0)
  %iter.1 = s32[] get-tuple-element(%p.1), index=0
  %one = s32[] constant(1)
  %next = s32[] add(%iter.1, %one)
  %lhs = f32[8,16] get-tuple-element(%p.1), index=1
  %rhs = f32[16,32] get-tuple-element(%p.1), index=2
  %mm = f32[8,32] dot(%lhs, %rhs), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %flat = f32[64] constant(0)
  %ar = f32[64] all-reduce(%flat), replica_groups={}, to_apply=%sum
  ROOT %out = (s32[], f32[8,16], f32[16,32], f32[8,32]) tuple(%next, %lhs, %rhs, %mm)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(%a, %b)
}

ENTRY %main (arg: (s32[], f32[8,16], f32[16,32], f32[8,32])) -> (s32[], f32[8,16], f32[16,32], f32[8,32]) {
  %arg = (s32[], f32[8,16], f32[16,32], f32[8,32]) parameter(0)
  ROOT %w = (s32[], f32[8,16], f32[16,32], f32[8,32]) while(%arg), condition=%wcond, body=%wbody
}
"""


class TestHloAnalysis:
    def test_split_computations(self):
        comps = hlo_analysis.split_computations(_SYNTH_HLO)
        assert {"wcond", "wbody", "sum", "main"} <= set(comps)

    def test_trip_count_multipliers(self):
        mult = hlo_analysis.computation_multipliers(_SYNTH_HLO)
        assert mult["wbody"] == 4
        assert mult["wcond"] == 4
        assert mult["main"] == 1

    def test_dot_flops_trip_corrected(self):
        # one dot of 2*8*32*16 FLOPs, run 4 times by the while loop
        assert hlo_analysis.dot_flops(_SYNTH_HLO) == 2 * 8 * 32 * 16 * 4

    def test_collective_bytes_trip_corrected(self):
        coll = hlo_analysis.collective_bytes(_SYNTH_HLO)
        # f32[64] all-reduce payload, 4 trips
        assert coll["all-reduce"] == 64 * 4 * 4
        assert coll["total"] == coll["all-reduce"]

    def test_analyze_shape(self):
        out = hlo_analysis.analyze(_SYNTH_HLO)
        assert out["dot_flops_corrected"] == 2 * 8 * 32 * 16 * 4
        assert out["collective_bytes"]["total"] > 0
        assert out["hbm_bytes_estimate"] > 0
        assert out["hbm_bytes_strict"] >= out["hbm_bytes_estimate"]

    def test_real_jitted_scan_undercount_fix(self):
        """cost_analysis counts a scanned matmul once; the text analysis
        must credit every trip."""
        n_layers, d = 6, 16
        ws = jnp.ones((n_layers, d, d), jnp.float32)

        def f(x, ws):
            def body(c, w):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, ws)
            return y

        hlo = jax.jit(f).lower(jnp.ones((4, d)), ws).compile().as_text()
        per_layer = 2 * 4 * d * d
        got = hlo_analysis.dot_flops(hlo)
        # all n_layers trips must be counted (XLA may add small extra dots)
        assert got >= n_layers * per_layer


class TestRoofline:
    def _cell(self):
        return {
            "status": "ok", "arch": "paper-0.5b", "shape": "train_4k",
            "mesh": "16x1", "kind": "train", "n_devices": 16,
            "param_count": 500_000_000,
            "dot_flops_per_device": 1e15,
            "hbm_bytes_per_device": 8e12,
            "collective_bytes_per_device": {"total": 1e11},
            "peak_bytes_per_device": 12e9,
        }

    def test_constants_shared_with_accounting(self):
        from repro.observability import accounting
        v5e = accounting.PEAKS["TPU v5 lite"]
        assert roofline.PEAK_FLOPS == v5e.flops == 197e12
        assert roofline.HBM_BW == v5e.hbm_bw == 819e9
        assert roofline.LINK_BW == v5e.link_bw
        assert "cpu" not in accounting.PEAKS

    def test_model_flops_convention(self):
        from repro.configs import get_config
        cfg = get_config("paper-0.5b")
        n = 500_000_000
        got = roofline.model_flops("paper-0.5b", "train", 1000, n)
        expect_n = n - (0 if cfg.tied_embeddings
                        else cfg.padded_vocab * cfg.d_model)
        assert got == 6 * expect_n * 1000
        assert roofline.model_flops("paper-0.5b", "decode", 1000, n) \
            == got / 3

    def test_analyze_cell_terms(self):
        d = self._cell()
        row = roofline.analyze_cell(d)
        assert row["compute_s"] == pytest.approx(1e15 / roofline.PEAK_FLOPS,
                                                 rel=1e-6)
        assert row["memory_s"] == pytest.approx(8e12 / roofline.HBM_BW,
                                                rel=1e-6)
        assert row["collective_s"] == pytest.approx(1e11 / roofline.LINK_BW,
                                                    rel=1e-6)
        # memory_s (~9.8s) dominates compute_s (~5.1s) here
        assert row["dominant"] == "memory"
        ideal = (row["model_flops"] / 16) / roofline.PEAK_FLOPS
        bound = max(1e15 / roofline.PEAK_FLOPS, 8e12 / roofline.HBM_BW,
                    1e11 / roofline.LINK_BW)
        assert row["mfu_upper"] == pytest.approx(ideal / bound, abs=1e-3)
        assert row["fits_16gb"] is True

    def test_load_cells_filters_status(self, tmp_path):
        good, bad = self._cell(), dict(self._cell(), status="oom")
        (tmp_path / "a.json").write_text(json.dumps(good))
        (tmp_path / "b.json").write_text(json.dumps(bad))
        cells = roofline.load_cells(str(tmp_path))
        assert len(cells) == 1 and cells[0]["status"] == "ok"

    def test_main_writes_reports(self, tmp_path, monkeypatch):
        (tmp_path / "cell.json").write_text(json.dumps(self._cell()))
        csv = tmp_path / "roofline.csv"
        md = tmp_path / "roofline.md"
        monkeypatch.setattr(sys, "argv", [
            "roofline", "--dir", str(tmp_path), "--csv", str(csv),
            "--md", str(md)])
        roofline.main()
        lines = csv.read_text().splitlines()
        assert len(lines) == 2 and lines[0].startswith("arch,")
        assert "paper-0.5b" in lines[1]
        assert md.read_text().count("|") > 0
