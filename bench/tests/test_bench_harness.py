"""The harness end to end on the CPU at a tiny size: a run without a TPU
exits non-zero and prints no result; with the chip check skipped, a sound
run is correct, and ``correct`` comes out false with the float8 control in
the program's place and with a token altered where the engine produces it.
"""
import importlib.util
import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout

import jax.numpy as jnp
import pytest

from benchlib.spec import BENCH_DIR, ROOT

TINY_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 4, "head_dim": 16, "d_ff": 512,
              "vocab_size": 256, "dtype": "float32"}
TINY_SERVING = {"attn_backend": "ref", "block_size": 16, "num_blocks": 64,
                "max_batch": 2, "max_seq_len": 64, "prefill_chunk": 32,
                "min_prefill_bucket": 32}


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH_DIR / "run.py"), "--workload",
         "paper-1.5b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A checkout whose one cell is a two-layer model served on the CPU."""
    root = tmp_path_factory.mktemp("tiny")
    bench = root / "bench"
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    conf = json.load(open(bench / "configs" / "paper-1.5b.json"))
    conf["name"] = "tiny"
    conf["model"].update(TINY_MODEL)
    conf["serving"].update(TINY_SERVING)
    # at 0.02 a two-layer model's residual is its input embedding, so the
    # argmax repeats the input token whatever the precision: widen it
    conf["weights"].update(live_gate_share=0.0625, init_std=0.1)
    (bench / "configs" / "tiny.json").write_text(json.dumps(conf))
    mix = json.load(open(bench / "traffic" / "chat.json"))
    mix["prompt"].update(median=24, min=8, max=40)
    mix["output"].update(median=16, min=4, max=24)
    (bench / "traffic" / "tinychat.json").write_text(json.dumps(mix))
    (bench / "cells" / "tiny.tinychat.json").write_text(json.dumps(
        {"rate_per_s": 4.0,
         "limits": {"widest_gap": 1e-3, "logit_rel_err": 1e-4}}))
    peaks = json.load(open(bench / "peaks.json"))
    peaks["devices"]["cpu"] = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    (bench / "peaks.json").write_text(json.dumps(peaks))
    benchmark = json.load(open(ROOT / "BENCHMARK.json"))
    benchmark["workloads"] = [{"name": "tiny.tinychat", "config": "tiny",
                               "traffic": "tinychat", "chips": 1,
                               "why": "CPU test"}]
    for m in benchmark["per_layer"]:
        m["workloads"] = ["tiny.tinychat"]
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    spec = importlib.util.spec_from_file_location("bench_run_under_test",
                                                  bench / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return root, run


def _result(tiny, trace=0):
    root, run = tiny
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", "tiny.tinychat", "--seed", "2147483659",
                       "--seconds", "5", "--trace", str(trace)],
                      need_tpu=False, root=root, bench_dir=root / "bench")
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct(tiny):
    res = _result(tiny)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] == 20 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "output_tokens_per_s", "setup_s"}


def test_traced_run_reports_per_layer_metrics(tiny):
    res = _result(tiny, trace=1)
    assert res["correct"], res["checks"]
    assert {"queue_wait_p90_ms", "decode_batch_mean",
            "kv_live_peak_share"} <= set(res["metrics"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def fp8_weights(params):
    """Every weight matrix rounded to float8 e4m3, one scale per output
    channel, in its own dtype: the control at test size, put into the
    program's weights."""
    from benchlib import model
    q = lambda a: model._e4m3(a.astype(jnp.float32), 1).astype(a.dtype)
    out = dict(params, embed=q(params["embed"]))
    out["blocks"] = dict(params["blocks"], attn={
        k: q(v) for k, v in params["blocks"]["attn"].items()}, ffn={
        k: q(v) for k, v in params["blocks"]["ffn"].items()})
    return out


def test_float8_control_in_the_programs_place_is_not_correct(tiny,
                                                             monkeypatch):
    root, run = tiny
    base = run.Served.__init__

    def control(self, conf, seed):
        base(self, conf, seed)
        self.engine.params = fp8_weights(self.engine.params)
    monkeypatch.setattr(run.Served, "__init__", control)
    res = _result(tiny)
    assert not res["correct"]
    assert res["checks"]["logit_rel_err"]["value"] > \
        res["checks"]["logit_rel_err"]["limit"]


def test_token_altered_where_produced_is_not_correct(tiny, monkeypatch):
    from repro.serving import request
    append = request.Request.append

    def altered(self, tok):
        n = len(self.output_tokens)
        return append(self, (int(tok) + 1) % 256 if n % 7 == 3 else tok)
    monkeypatch.setattr(request.Request, "append", altered)
    res = _result(tiny)
    assert not res["correct"]
    assert res["checks"]["argmax_mismatches"]["value"] > 0
    assert res["checks"]["widest_gap"]["value"] > \
        res["checks"]["widest_gap"]["limit"]
