"""Tracing the serving path from inside: engine phases as profiler
annotations on the device's clock, named step programs, and the HTTP front
door's spans on each request's trace track.

The profiler runs on the CPU too: ``jax.profiler.start_trace`` writes the
host plane (``/host:CPU``) with one event per annotation, which is all the
engine-side tests read.
"""
import collections
import dataclasses
import glob
import http.client
import json
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import lm
from repro.serving import ServingEngine
from repro.serving.server import ServingServer

BS = 4


def _cfg():
    base = get_config("paper-0.5b").reduced()
    return dataclasses.replace(base, sparsity=dataclasses.replace(
        base.sparsity, ffn_impl="dense"))


@pytest.fixture(scope="module")
def dense_model():
    cfg = _cfg()
    return lm.init(jax.random.PRNGKey(0), cfg), cfg


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, n).tolist() for n in lens]


def _host_events(logdir):
    """Names of every event in the profile's host plane."""
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    names = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names += [e.name for e in line.events]
    return names


def _serve_traced(params, cfg, logdir, telemetry):
    """Two requests through a pipelined engine under the profiler."""
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=32, pipeline=True, telemetry=telemetry)
    jax.profiler.start_trace(str(logdir))
    try:
        engine.generate(_prompts(cfg, [5, 9], seed=3), max_tokens=4)
    finally:
        jax.profiler.stop_trace()
    return engine


def test_phase_annotations_match_recorder_spans(dense_model, tmp_path):
    params, cfg = dense_model
    engine = _serve_traced(params, cfg, tmp_path, telemetry=True)
    host = collections.Counter(_host_events(tmp_path))
    recorded = collections.Counter(
        ev["name"] for ev in engine.telemetry.trace.to_chrome()["traceEvents"]
        if ev["ph"] == "X" and ev["tid"] == 0)
    for phase in ("plan", "collect", "launch"):
        assert recorded[phase] > 0
        assert host[f"engine.{phase}"] == recorded[phase], phase
    # the first dispatch of each program compiled it, inside its span
    assert host["engine.compile.decode"] == recorded["compile.decode"] >= 1
    assert host["engine.compile.prefill"] == recorded["compile.prefill"] >= 1
    # one step marker per engine step; overlap spans two steps: no event
    assert host["engine.step"] == engine._step_idx
    assert recorded["overlap"] > 0 and host["engine.overlap"] == 0


def test_step_programs_are_named(dense_model):
    params, cfg = dense_model
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=32)
    for entry, size in (("decode", engine.table_width), ("prefill", 16)):
        text = engine.lower_step(entry, 1, size).as_text()
        assert f"module @jit_{entry}_step " in text


def test_telemetry_off_records_and_annotates_nothing(dense_model, tmp_path):
    params, cfg = dense_model
    engine = _serve_traced(params, cfg, tmp_path, telemetry=False)
    assert engine.telemetry is None
    assert not [n for n in _host_events(tmp_path)
                if n.startswith(("engine.", "http."))]


def test_http_stream_records_receive_and_writes(dense_model):
    params, cfg = dense_model
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=64, pipeline=True, telemetry=True)
    srv = ServingServer(engine, port=0).start()
    try:
        prompt = _prompts(cfg, [8], seed=5)[0]
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=120)
        conn.request("POST", "/v1/completions",
                     body=json.dumps({"prompt": prompt, "max_tokens": 7,
                                      "stream": True}),
                     headers={"Content-Type": "application/json"})
        streamed = 0
        for line in conn.getresponse().read().splitlines():
            if line.startswith(b"data: {"):
                streamed += len(json.loads(line[6:])["choices"][0]
                                ["token_ids"])
        conn.close()
        # the handler records a write after its flush: wait for the last,
        # which follows the request's FINISH
        deadline = time.monotonic() + 30
        while True:
            track = [e for e in engine.telemetry.trace.to_chrome()
                     ["traceEvents"] if e.get("tid") == 1]   # request 0
            finish = [e["ts"] for e in track if e["name"] == "FINISH"]
            writes = [e for e in track if e["name"] == "http.write"]
            if finish and writes and writes[-1]["ts"] >= finish[0] or \
                    time.monotonic() > deadline:
                break
            time.sleep(0.01)
    finally:
        srv.shutdown()
    assert streamed == 7
    receives = [e for e in track if e["name"] == "http.receive"]
    assert len(receives) == 1 and receives[0]["dur"] >= 0
    assert sum(e["args"]["tokens"] for e in writes) == streamed
    assert all(e["args"]["lag_ms"] >= 0 for e in writes)
    # the request retired at FINISH; the write that closed its stream
    # came after and is kept
    assert len(finish) == 1 and writes[-1]["ts"] >= finish[0]
    queued = [e for e in track if e["name"] == "QUEUED"]
    assert receives[0]["ts"] <= queued[0]["ts"]
