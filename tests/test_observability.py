"""Observability subsystem tests: the analytic FLOPs/bytes cost model,
the JSONL run logger (incl. an end-to-end training smoke whose per-layer
nnz trajectory must decrease under L1), the serving sparsity probe, and
the benchmarks/compare.py regression gate."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import twell
from repro.launch import train as train_cli
from repro.models import lm
from repro.observability import (RunLogger, SparsityReport, accounting,
                                 iter_runlog, read_runlog)
from repro.serving import SamplingParams, ServingEngine, finished_outputs

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
import compare  # noqa: E402


def _cfg(ffn_impl="dense", gated=True):
    base = get_config("paper-0.5b").reduced()
    return dataclasses.replace(
        base, gated=gated,
        sparsity=dataclasses.replace(base.sparsity, ffn_impl=ffn_impl))


# --------------------------------------------------------------------------- #
# analytic cost model
# --------------------------------------------------------------------------- #

class TestCostModel:
    def test_dense_flops_per_token(self):
        cfg = _cfg()
        n_mats = 3 if cfg.gated else 2
        assert accounting.ffn_dense_flops_per_token(cfg) \
            == 2 * cfg.d_model * cfg.d_ff * n_mats

    def test_gather_at_full_nnz_equals_dense(self):
        cfg = _cfg("gather")
        dense = accounting.ffn_dense_flops_per_token(cfg)
        assert accounting.ffn_effective_flops_per_token(
            cfg, "gather", cfg.d_ff) == pytest.approx(dense)

    def test_gather_scales_with_nnz(self):
        cfg = _cfg("gather")
        d = cfg.d_model
        lo = accounting.ffn_effective_flops_per_token(cfg, "gather", 10)
        hi = accounting.ffn_effective_flops_per_token(cfg, "gather", 100)
        assert hi - lo == pytest.approx(2 * d * 90 * 2)  # gated: 2 mats
        # nnz is clamped to [0, d_ff]
        assert accounting.ffn_effective_flops_per_token(
            cfg, "gather", 10 * cfg.d_ff) == \
            accounting.ffn_effective_flops_per_token(cfg, "gather", cfg.d_ff)

    def test_tile_skip_endpoints(self):
        cfg = _cfg("tile_skip")
        d, dff = cfg.d_model, cfg.d_ff
        dense = accounting.ffn_dense_flops_per_token(cfg)
        # all tiles dead: only the dense gate matmul remains
        assert accounting.ffn_effective_flops_per_token(
            cfg, "tile_skip", 0, tile_frac=0.0) == pytest.approx(2 * d * dff)
        # all tiles live: full dense cost
        assert accounting.ffn_effective_flops_per_token(
            cfg, "tile_skip", dff, tile_frac=1.0) == pytest.approx(dense)

    def test_tile_skip_non_gated_falls_back_dense(self):
        cfg = _cfg("tile_skip", gated=False)
        assert accounting.ffn_effective_flops_per_token(
            cfg, "tile_skip", 1, tile_frac=0.01) \
            == accounting.ffn_dense_flops_per_token(cfg)

    def test_hybrid_is_dense_on_flop_axis(self):
        cfg = _cfg("hybrid")
        assert accounting.ffn_effective_flops_per_token(cfg, "hybrid", 1) \
            == accounting.ffn_dense_flops_per_token(cfg)

    def test_unknown_impl_raises(self):
        with pytest.raises(ValueError):
            accounting.ffn_effective_flops_per_token(_cfg(), "nope", 1)

    def test_bytes_gather_below_dense(self):
        cfg = _cfg("gather")
        dense = accounting.ffn_bytes_per_token(cfg, "dense", cfg.d_ff)
        sparse = accounting.ffn_bytes_per_token(cfg, "gather", cfg.d_ff // 20)
        assert sparse < dense

    def test_model_flops_train_vs_decode(self):
        cfg = _cfg()
        n = 1_000_000
        assert accounting.model_flops(cfg, n, 10, train=True) \
            == 3 * accounting.model_flops(cfg, n, 10, train=False)

    def test_model_flops_drops_untied_embedding(self):
        cfg = _cfg()
        n = 1_000_000
        expect = n
        if not cfg.tied_embeddings:
            expect -= cfg.padded_vocab * cfg.d_model
        assert accounting.matmul_params(cfg, n) == expect

    def test_mfu(self):
        assert accounting.mfu(1e12, 1.0, chips=1, peak=1e13) \
            == pytest.approx(0.1)
        assert accounting.mfu(1e12, 0.0, chips=1, peak=1e13) == 0.0


class TestSparsityReport:
    def test_dense_report_no_reduction(self):
        cfg = _cfg("dense")
        rep = SparsityReport.build(cfg, 64, [100.0] * cfg.num_layers)
        assert rep.flops_reduction() == 0.0
        assert rep.mean_sparsity == pytest.approx(1 - 100.0 / cfg.d_ff)
        assert rep.mfu_estimate(1.0, 1e13) is None   # no n_params given

    def test_gather_report_reduces_flops(self):
        cfg = _cfg("gather")
        n = accounting.param_count(lm.init(jax.random.PRNGKey(0), cfg))
        rep = SparsityReport.build(cfg, 64, [10.0] * cfg.num_layers,
                                   n_params=n)
        assert 0 < rep.flops_reduction() < 1
        assert rep.model_effective_flops < rep.model_dense_flops
        assert rep.ffn_effective_flops < rep.ffn_dense_flops
        v5e = accounting.PEAKS[accounting.V5E].flops
        assert 0 < rep.mfu_estimate(1.0, v5e) < 1
        assert rep.mfu_estimate(1.0, None) is None  # device without peaks
        d = rep.to_dict()
        assert len(d["layers"]) == cfg.num_layers
        json.dumps(d)                              # JSON-able

    def test_ffn_present_masks_layers(self):
        cfg = _cfg("dense")
        present = [1.0, 0.0] + [1.0] * (cfg.num_layers - 2)
        rep = SparsityReport.build(cfg, 8, [100.0] * cfg.num_layers,
                                   ffn_present=present)
        assert len(rep.present_layers) == cfg.num_layers - 1
        assert rep.layers[1].dense_flops == 0.0

    def test_train_scales_ffn_savings(self):
        cfg = _cfg("gather")
        n = 10_000_000
        nnz = [10.0] * cfg.num_layers
        r2 = SparsityReport.build(cfg, 8, nnz, n_params=n, train=False)
        r6 = SparsityReport.build(cfg, 8, nnz, n_params=n, train=True)
        save2 = r2.model_dense_flops - r2.model_effective_flops
        save6 = r6.model_dense_flops - r6.model_effective_flops
        assert save6 == pytest.approx(3 * save2)

    def test_twell_bridge(self):
        h = jnp.zeros((8, 64)).at[:, 0].set(1.0)   # one live neuron per row
        tw = twell.pack(h, 16, 1)
        occ = accounting.tile_occupancy_from_twell(tw, row_block=4)
        assert occ["nnz_per_row_mean"] == pytest.approx(1.0)
        assert occ["tile_frac"] == pytest.approx(1 / 4)   # 1 of 4 tiles live
        assert occ["block_tile_frac"] == pytest.approx(1 / 4)

    def test_stats_from_hidden_bridge(self):
        h = jnp.zeros((4, 8)).at[:, :2].set(1.0)
        st = accounting.stats_from_hidden(h)
        assert st["nnz_mean"] == pytest.approx(2.0)
        assert isinstance(st["nnz_mean"], float)


# --------------------------------------------------------------------------- #
# JSONL run log
# --------------------------------------------------------------------------- #

class TestRunLog:
    def test_roundtrip_and_kinds(self, tmp_path):
        p = str(tmp_path / "run.jsonl")
        with RunLogger(p, meta={"arch": "tiny"}) as log:
            log.step(0, loss=2.0, nnz_per_layer=np.array([3.0, 4.0]))
            log.step(1, loss=1.5, nnz_per_layer=np.array([2.0, 3.0]))
            log.event("watchdog", message="slow step", step=1)
        recs = read_runlog(p)
        assert [r["kind"] for r in recs] == ["meta", "step", "step", "event"]
        meta = recs[0]
        assert meta["schema_version"] == 1 and meta["arch"] == "tiny"
        steps = read_runlog(p, kind="step")
        assert steps[0]["nnz_per_layer"] == [3.0, 4.0]   # arrays -> lists
        assert all("ts" in r for r in recs)
        assert read_runlog(p, kind="event")[0]["event"] == "watchdog"

    def test_append_and_torn_line(self, tmp_path):
        p = str(tmp_path / "run.jsonl")
        with RunLogger(p) as log:
            log.step(0, loss=1.0)
        with open(p, "a") as f:
            f.write('{"kind": "step", "truncat\n')  # simulated crash
        with RunLogger(p) as log:                   # resume appends
            log.step(1, loss=0.5)
        recs = list(iter_runlog(p))
        kinds = [r["kind"] for r in recs]
        assert kinds == ["meta", "step", "meta", "step"]   # torn line skipped

    def test_closed_logger_raises(self, tmp_path):
        log = RunLogger(str(tmp_path / "r.jsonl"))
        log.close()
        with pytest.raises(RuntimeError):
            log.step(0, loss=1.0)


def test_training_smoke_nnz_trajectory_decreases(tmp_path):
    """Acceptance criterion: a smoke training run under the L1 schedule
    emits a JSONL whose per-layer nnz trajectory decreases."""
    p = str(tmp_path / "run.jsonl")
    hist = train_cli.main([
        "--arch", "paper-0.5b", "--reduced", "--steps", "80",
        "--batch", "4", "--seq", "64", "--lr", "3e-3", "--l1", "3.0",
        "--log-every", "1000", "--ckpt-dir", str(tmp_path / "ckpt"),
        "--run-log", p])
    recs = read_runlog(p)
    meta = recs[0]
    assert meta["kind"] == "meta" and meta["l1_coeff"] == 3.0
    assert meta["n_params"] > 0
    steps = read_runlog(p, kind="step")
    assert len(steps) == len(hist) == 80
    first = np.array(steps[0]["nnz_per_layer"])
    last = np.array(steps[-1]["nnz_per_layer"])
    assert first.shape == last.shape == (meta["num_layers"],)
    assert np.all(last < first), (first, last)      # per-layer decrease
    # accounting fields ride along on every step record
    s = steps[-1]
    assert s["model_dense_flops"] > 0
    # MFU only on a device with a published peak (never this CPU's)
    if jax.devices()[0].device_kind not in accounting.PEAKS:
        assert s["mfu"] is None
    else:
        assert 0 <= s["mfu"] < 1
    assert s["tokens_per_s"] > 0 and s["step_time_s"] > 0
    assert s["ffn_effective_flops"] == s["ffn_dense_flops"]  # dense impl
    assert len(s["dead_frac_per_layer"]) == meta["num_layers"]
    # the run-completion event went through the logger
    events = read_runlog(p, kind="event")
    assert any(e["event"] == "done" for e in events)
    # returned history stays scalar-only (downstream json.dump / tests)
    assert all(np.ndim(v) == 0 for v in hist[0].values())


# --------------------------------------------------------------------------- #
# serving sparsity probe
# --------------------------------------------------------------------------- #

class TestServingProbe:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = _cfg("dense")
        return lm.init(jax.random.PRNGKey(0), cfg), cfg

    def _run(self, params, cfg, telemetry):
        eng = ServingEngine(params, cfg, block_size=4, max_batch=4,
                            max_seq_len=64, telemetry=telemetry)
        rng = np.random.RandomState(7)
        for n in (5, 9, 3):
            eng.submit(rng.randint(0, cfg.vocab_size, n).tolist(),
                       sampling=SamplingParams(), max_tokens=6)
        outs = {}
        while eng.has_unfinished():
            for o in finished_outputs(eng.step()):
                outs[o.rid] = list(o.token_ids)
        return eng, outs

    def test_probe_publishes_metrics_and_keeps_tokens_identical(self, model):
        params, cfg = model
        eng_off, outs_off = self._run(params, cfg, telemetry=False)
        eng_on, outs_on = self._run(params, cfg, telemetry=True)
        # the probe rides as extra scan outputs: token-identical serving
        assert outs_on == outs_off
        tm = eng_on.telemetry
        m = tm.metrics
        assert m.dense_flops_total.value() > 0
        assert m.effective_flops_total.value() > 0
        assert m.effective_flops_total.value() \
            <= m.dense_flops_total.value() + 1e-6
        # one gauge per model layer, each a valid sparsity
        layers = {ls["layer"] for ls in m.ffn_sparsity.label_sets()}
        assert layers == {str(i) for i in range(cfg.num_layers)}
        for i in range(cfg.num_layers):
            assert 0.0 <= m.ffn_sparsity.value(layer=str(i)) <= 1.0
        assert m.tile_occupancy.snapshot()["count"] > 0
        # exposition + summary rollups
        text = tm.registry.render_prometheus()
        assert 'serving_ffn_sparsity{layer="0"}' in text
        assert "serving_effective_flops_total" in text
        sp = tm.summary()["sparsity"]
        assert 0.0 <= sp["mean_ffn_sparsity"] <= 1.0
        assert sp["flops_reduction"] is not None
        assert len(sp["per_layer_sparsity"]) == cfg.num_layers

    def test_summary_sparsity_none_without_compute(self):
        from repro.serving import Telemetry
        tm = Telemetry(trace=False)
        assert tm.summary()["sparsity"] is None
        tm.on_ffn(8, [1.0, 2.0])                   # inert before attach
        assert tm.metrics.dense_flops_total.value() == 0


# --------------------------------------------------------------------------- #
# bench-regression gate
# --------------------------------------------------------------------------- #

class TestCompareGate:
    def _payloads(self):
        serving = {
            "bench": "serving", "schema_version": 1,
            "meta": {"git_commit": "abc", "smoke": True},
            "results": [{"backend": "dense", "tokens": 48, "steps": 32,
                         "prompt_tokens": 40, "prefill_tokens": 40,
                         "cached_tokens": 0, "cache_hit_rate": 0.0,
                         "toks_per_s": 100.0, "step_wall_ms_mean": 1.5}],
            "telemetry": {"outputs_identical": True},
            "attention": {"outputs_identical": True, "kernel": "interpret",
                          "sweep": [{"seq_len": 32, "pages": 4,
                                     "ref_step_wall_ms": 1.0,
                                     "kernel_step_wall_ms": 1.2}]},
            "tp_identity": None,
            "scheduler_identity": {"outputs_identical": True},
            "shared_prefix": {"cache_hit_rate": 0.571,
                              "prefill_tokens_saved_frac": 0.571},
            "churn": {"requests": 8, "cancelled": 1, "preempted": 1,
                      "steps": 48},
        }
        spec = {
            "bench": "spec_decode", "schema_version": 1,
            "meta": {"git_commit": "abc", "smoke": True},
            "results": [{"mode": "spec-k2", "tokens": 48, "steps": 20,
                         "acceptance_rate": 0.5, "toks_per_s": 50.0}],
        }
        return serving, spec

    def _write(self, d, serving, spec):
        os.makedirs(d, exist_ok=True)
        json.dump(serving, open(os.path.join(d, "BENCH_serving.json"), "w"))
        json.dump(spec, open(os.path.join(d, "BENCH_spec_decode.json"), "w"))

    def test_identical_passes(self, tmp_path):
        serving, spec = self._payloads()
        self._write(str(tmp_path / "base"), serving, spec)
        self._write(str(tmp_path / "fresh"), serving, spec)
        rc = compare.main(["--baseline", str(tmp_path / "base"),
                           "--fresh", str(tmp_path / "fresh")])
        assert rc == 0

    def test_perturbed_fails(self, tmp_path, capsys):
        serving, spec = self._payloads()
        self._write(str(tmp_path / "base"), serving, spec)
        bad = json.loads(json.dumps(serving))
        bad["results"][0]["tokens"] += 1            # determinism break
        self._write(str(tmp_path / "fresh"), bad, spec)
        report = str(tmp_path / "report.json")
        rc = compare.main(["--baseline", str(tmp_path / "base"),
                           "--fresh", str(tmp_path / "fresh"),
                           "--report", report])
        assert rc == 1
        assert "results[0].tokens" in capsys.readouterr().out
        rep = json.load(open(report))
        assert rep["files"]["BENCH_serving.json"]["failures"] == 1
        assert rep["files"]["BENCH_spec_decode.json"]["failures"] == 0

    def test_within_tolerance_passes(self, tmp_path):
        serving, spec = self._payloads()
        self._write(str(tmp_path / "base"), serving, spec)
        drift = json.loads(json.dumps(spec))
        drift["results"][0]["acceptance_rate"] += 0.1   # inside abs 0.15
        self._write(str(tmp_path / "fresh"), serving, drift)
        rc = compare.main(["--baseline", str(tmp_path / "base"),
                           "--fresh", str(tmp_path / "fresh")])
        assert rc == 0

    def test_missing_fresh_file_fails(self, tmp_path):
        serving, spec = self._payloads()
        self._write(str(tmp_path / "base"), serving, spec)
        os.makedirs(str(tmp_path / "fresh"), exist_ok=True)
        rc = compare.main(["--baseline", str(tmp_path / "base"),
                           "--fresh", str(tmp_path / "fresh")])
        assert rc == 1

    def test_schema_version_mismatch_fails(self, tmp_path):
        serving, spec = self._payloads()
        self._write(str(tmp_path / "base"), serving, spec)
        bumped = json.loads(json.dumps(serving))
        bumped["schema_version"] = 99
        self._write(str(tmp_path / "fresh"), bumped, spec)
        rc = compare.main(["--baseline", str(tmp_path / "base"),
                           "--fresh", str(tmp_path / "fresh")])
        assert rc == 1

    def test_committed_baselines_self_compare(self):
        """The committed baselines must pass against themselves (guards the
        gate's own config from drifting out of sync with the payloads)."""
        base = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                            "baselines")
        rc = compare.main(["--baseline", base, "--fresh", base])
        assert rc == 0
