"""A configuration, a traffic mix, a cell and a per-layer metric are each
found by name as files of their own: adding one edits no existing file."""
import hashlib
import json
import os
import shutil

from benchlib.spec import BENCH_DIR, ROOT, Spec


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_real_benchmark_names_resolve():
    spec = Spec.load(ROOT, BENCH_DIR)
    for w in spec.benchmark["workloads"]:
        conf = spec.config(w["config"])
        assert conf["name"] == w["config"]
        assert "prompt" in spec.traffic(w["traffic"])
        assert spec.cell(w["name"])["rate_per_s"] > 0
        readers = spec.readers(w["name"])
        assert readers and all(callable(r.read) for r in readers.values())
    for c in spec.benchmark["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_new_files_are_found_without_editing_old_ones(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    benchmark = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    before = _digests(bench)
    conf = dict(json.load(open(bench / "configs" / "paper-1.5b.json")),
                name="new-model")
    (bench / "configs" / "new-model.json").write_text(json.dumps(conf))
    mix = dict(json.load(open(bench / "traffic" / "chat.json")),
               name="bursty", arrivals={"kind": "gamma", "cv": 2.0})
    (bench / "traffic" / "bursty.json").write_text(json.dumps(mix))
    (bench / "cells" / "new-model.bursty.json").write_text(json.dumps(
        {"rate_per_s": 1.5, "limits": {"widest_gap": 0.5}}))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    benchmark["workloads"].append({"name": "new-model.bursty",
                                   "config": "new-model",
                                   "traffic": "bursty", "chips": 1,
                                   "why": "test"})
    benchmark["per_layer"].append({"name": "new_metric", "unit": "%",
                                   "better": "higher",
                                   "source": "device_trace",
                                   "layer": "kernels",
                                   "moves": "itl_p95_ms",
                                   "workloads": ["new-model.bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(benchmark))
    spec = Spec.load(tmp_path, bench)
    w = spec.workload("new-model.bursty")
    assert spec.config(w["config"])["name"] == "new-model"
    assert spec.traffic(w["traffic"])["arrivals"]["kind"] == "gamma"
    assert spec.cell(w["name"])["rate_per_s"] == 1.5
    readers = spec.readers(w["name"])
    assert readers["new_metric"].read(None) == 42.0
    assert "new_metric" not in spec.readers("paper-1.5b.chat")
    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
