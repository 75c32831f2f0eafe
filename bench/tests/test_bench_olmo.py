"""OLMo-1B through the benchmark's served path on the CPU at a tiny size: a
cell built from ``configs/olmo-1b.json`` (non-parametric LayerNorm, gated
ReLU FFN, tied head, heads of 128 lanes) is correct, and ``correct`` comes
out false with the float8 control in the program's place and with a token
altered where the engine produces it. Also: every configuration the
benchmark runs is its ``serve.py --arch`` preset, which the file only
restates.
"""
import importlib.util
import json
import shutil

import pytest

from benchlib import measure
from benchlib.spec import BENCH_DIR, ROOT, Spec
# the harness's own checks, collected here so that they run on this
# module's ``tiny``
from test_bench_harness import (
    TINY_SERVING,
    test_float8_control_in_the_programs_place_is_not_correct as
    test_olmo_float8_control_is_not_correct,
    test_sound_run_is_correct as test_olmo_block_served_is_correct,
    test_token_altered_where_produced_is_not_correct as
    test_olmo_token_altered_where_produced_is_not_correct,
)

# OLMo's block kind at a tiny size: the norm, gating and tying stay as the
# file has them; heads keep their published 128 lanes
TINY_OLMO = {"num_layers": 2, "d_model": 256, "num_heads": 2,
             "num_kv_heads": 2, "head_dim": 128, "d_ff": 512,
             "vocab_size": 256, "dtype": "float32"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A checkout whose one cell, ``tiny.tinychat``, serves a two-layer
    OLMo-1B block stack on the CPU through the paged kernels."""
    root = tmp_path_factory.mktemp("tiny-olmo")
    bench = root / "bench"
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    conf = json.load(open(bench / "configs" / "olmo-1b.json"))
    conf["name"] = "tiny"
    conf["model"].update(TINY_OLMO)
    # the paged Pallas kernels, in interpret mode, on 128-lane heads
    conf["serving"].update(TINY_SERVING, attn_backend="interpret")
    conf["weights"].update(live_gate_share=0.0625, init_std=0.1)
    (bench / "configs" / "tiny.json").write_text(json.dumps(conf))
    mix = json.load(open(bench / "traffic" / "chat.json"))
    mix["prompt"].update(median=24, min=8, max=40)
    mix["output"].update(median=16, min=4, max=24)
    (bench / "traffic" / "tinychat.json").write_text(json.dumps(mix))
    (bench / "cells" / "tiny.tinychat.json").write_text(json.dumps(
        {"rate_per_s": 4.0,
         "limits": {"widest_gap": 1e-3, "logit_rel_err": 1e-4}}))
    benchmark = json.load(open(ROOT / "BENCHMARK.json"))
    benchmark["workloads"] = [{"name": "tiny.tinychat", "config": "tiny",
                               "traffic": "tinychat", "chips": 1,
                               "why": "CPU test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    spec = importlib.util.spec_from_file_location("bench_run_olmo_under_test",
                                                  bench / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return root, run


@pytest.mark.parametrize("name", [c["name"] for c in
                                  Spec.load().benchmark["configs"]])
def test_configuration_file_restates_its_preset(name):
    """``serve.py --arch <preset>`` builds the model the cell measures: the
    file's model section, stamped over the preset, changes nothing."""
    from repro.launch import serve
    conf = Spec.load().config(name)
    cfg = serve.model_config(serve.parse_args(
        ["--arch", conf["preset"], "--ffn-impl",
         conf["serving"]["ffn_impl"]]))
    assert measure.model_config(cfg, conf["model"]) == cfg


def test_olmo_preset_is_the_published_gated_mlp():
    from repro.launch import serve
    cfg = serve.model_config(serve.parse_args(["--arch", "olmo-1b"]))
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim) == (16, 2048, 16, 16, 128)
    assert cfg.gated and cfg.d_ff == 8192
    assert cfg.sparsity.activation == "relu"
    assert cfg.norm == "nonparametric_ln" and cfg.tied_embeddings
    assert (cfg.vocab_size, cfg.rope_theta) == (50304, 1e4)
