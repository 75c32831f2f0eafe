"""HTTP serving front end: /v1/completions (JSON + SSE streaming),
disconnect-triggered cancellation, cancel endpoint, clean shutdown.

Runs the real ``ServingServer`` (engine thread + ThreadingHTTPServer) on an
ephemeral port in-process; ``tests/http_smoke.py`` covers the same surface
end-to-end through the ``serve.py --http`` subprocess for CI.
"""
import http.client
import json
import time
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serving import SamplingParams, ServingEngine
from repro.serving.server import ServingServer


@pytest.fixture(scope="module")
def server():
    cfg = get_config("paper-0.5b").reduced()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(params, cfg, block_size=4, max_batch=4,
                           max_seq_len=64, scheduler="priority",
                           telemetry=True)
    srv = ServingServer(engine, port=0).start()
    yield srv, engine, cfg, params
    srv.shutdown()


def _url(srv, path):
    return f"http://{srv.host}:{srv.port}{path}"


def _post(srv, path, payload, timeout=120):
    req = urllib.request.Request(
        _url(srv, path), data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return json.load(urllib.request.urlopen(req, timeout=timeout))


def _sse_tokens(resp):
    """Parse an SSE stream: ([chunk dicts], [token ids])."""
    chunks, toks = [], []
    while True:
        line = resp.fp.readline()
        assert line, "stream ended without [DONE]"
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        payload = line[len(b"data: "):]
        if payload == b"[DONE]":
            return chunks, toks
        c = json.loads(payload)
        chunks.append(c)
        toks.extend(c["choices"][0]["token_ids"])


def test_healthz_and_bad_requests(server):
    srv, engine, cfg, params = server
    h = json.load(urllib.request.urlopen(_url(srv, "/healthz"), timeout=10))
    assert h["ok"] is True
    for bad in ({}, {"prompt": "text"}, {"prompt": []},
                {"prompt": [1.5, 2]}):
        req = urllib.request.Request(
            _url(srv, "/v1/completions"), data=json.dumps(bad).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(_url(srv, "/nope"), timeout=10)
    assert e.value.code == 404


def test_completion_matches_direct_engine(server):
    """A non-streaming HTTP completion returns exactly what a direct engine
    with the same params produces (greedy)."""
    srv, engine, cfg, params = server
    prompt = np.random.RandomState(1).randint(0, cfg.vocab_size, 8).tolist()
    ref = ServingEngine(params, cfg, block_size=4, max_batch=2,
                        max_seq_len=32).generate([prompt], max_tokens=6)[0]
    out = _post(srv, "/v1/completions", {"prompt": prompt, "max_tokens": 6})
    assert out["object"] == "text_completion"
    assert out["choices"][0]["token_ids"] == ref.token_ids
    assert out["choices"][0]["finish_reason"] == "length"
    assert out["usage"] == {"prompt_tokens": 8, "completion_tokens": 6}


def test_sse_stream_matches_non_stream(server):
    srv, engine, cfg, params = server
    prompt = np.random.RandomState(2).randint(0, cfg.vocab_size, 8).tolist()
    ref = _post(srv, "/v1/completions", {"prompt": prompt, "max_tokens": 6})
    conn = http.client.HTTPConnection(srv.host, srv.port, timeout=120)
    conn.request("POST", "/v1/completions",
                 body=json.dumps({"prompt": prompt, "max_tokens": 6,
                                  "stream": True}),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "text/event-stream"
    chunks, toks = _sse_tokens(resp)
    conn.close()
    assert toks == ref["choices"][0]["token_ids"]
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    assert all(c["choices"][0]["finish_reason"] is None
               for c in chunks[:-1])


def test_disconnect_mid_stream_cancels(server):
    """Dropping the SSE connection must cancel the request on the engine:
    its KV blocks free and the cancelled counter advances."""
    srv, engine, cfg, params = server
    before = engine.cancelled_total
    prompt = np.random.RandomState(3).randint(0, cfg.vocab_size, 8).tolist()
    conn = http.client.HTTPConnection(srv.host, srv.port, timeout=120)
    conn.request("POST", "/v1/completions",
                 body=json.dumps({"prompt": prompt, "max_tokens": 48,
                                  "stream": True}),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    resp.fp.readline()                  # first bytes, then vanish
    resp.close()
    conn.close()
    deadline = time.time() + 60
    while time.time() < deadline:
        if engine.cancelled_total > before and not engine.running:
            break
        time.sleep(0.05)
    assert engine.cancelled_total > before, "disconnect never cancelled"
    engine.kv.check_invariants()


def test_cancel_endpoint(server):
    srv, engine, cfg, params = server
    prompt = np.random.RandomState(4).randint(0, cfg.vocab_size, 8).tolist()
    # park a long request via the handle API, cancel it over HTTP
    h = engine.submit(prompt, sampling=SamplingParams(), max_tokens=48)
    out = _post(srv, "/v1/cancel", {"id": f"cmpl-{h.rid}"})
    assert out["cancelled"] is True
    deadline = time.time() + 60
    while time.time() < deadline and not h.finished:
        time.sleep(0.05)
    assert h.finished and h.result().finish_reason == "cancelled"
    assert _post(srv, "/v1/cancel",
                 {"id": f"cmpl-{h.rid}"})["cancelled"] is False
    assert _post(srv, "/v1/cancel", {"id": "bogus"})["cancelled"] is False


def test_priority_field_reaches_engine(server):
    srv, engine, cfg, params = server
    prompt = np.random.RandomState(5).randint(0, cfg.vocab_size, 6).tolist()
    out = _post(srv, "/v1/completions",
                {"prompt": prompt, "max_tokens": 2, "priority": 1,
                 "seed": 11, "temperature": 0.8, "top_k": 8})
    assert len(out["choices"][0]["token_ids"]) == 2
    stats = json.load(urllib.request.urlopen(_url(srv, "/v1/stats"),
                                             timeout=10))
    assert stats["finished"] >= 1
    assert stats["kv"]["num_blocks"] == engine.kv.num_blocks


def test_metrics_exposition(server):
    """GET /metrics returns Prometheus text covering step phases, KV
    occupancy, prefix-cache traffic, and latency histograms — and /v1/stats
    carries the telemetry rollup."""
    srv, engine, cfg, params = server
    prompt = np.random.RandomState(6).randint(0, cfg.vocab_size, 8).tolist()
    _post(srv, "/v1/completions", {"prompt": prompt, "max_tokens": 3})
    resp = urllib.request.urlopen(_url(srv, "/metrics"), timeout=10)
    assert resp.headers["Content-Type"].startswith("text/plain")
    text = resp.read().decode()
    # one spot-check per metric kind the catalog promises
    assert "# TYPE serving_step_phase_seconds histogram" in text
    assert 'serving_step_phase_seconds_bucket{phase="decode",le="+Inf"}' \
        in text
    assert 'serving_kv_blocks{state="free"}' in text
    assert "# TYPE serving_prefix_tokens_total counter" in text
    assert 'serving_ttft_seconds_count{priority="0",role="unified"}' in text
    assert "serving_steps_total" in text
    # counters agree with the engine's own books
    for line in text.splitlines():
        if line.startswith("serving_requests_total") and "finished" in line:
            assert float(line.split()[-1]) == engine.finished_total
    stats = json.load(urllib.request.urlopen(_url(srv, "/v1/stats"),
                                             timeout=10))
    tm = stats["telemetry"]
    assert tm["steps"] == pytest.approx(engine._step_idx)
    assert "decode" in tm["phases_ms_mean"]
    assert tm["jit_compiles"]["decode"] >= 1


def test_stats_sparsity_rollup(server):
    """An engine built with telemetry probes every forward: /metrics grows
    the per-layer FFN sparsity gauges + FLOPs counters, and /v1/stats adds
    the compact sparsity rollup next to the full telemetry block."""
    srv, engine, cfg, params = server
    prompt = np.random.RandomState(7).randint(0, cfg.vocab_size, 8).tolist()
    _post(srv, "/v1/completions", {"prompt": prompt, "max_tokens": 3})
    text = urllib.request.urlopen(_url(srv, "/metrics"),
                                  timeout=10).read().decode()
    assert 'serving_ffn_sparsity{layer="0"}' in text
    assert f'serving_ffn_sparsity{{layer="{cfg.num_layers - 1}"}}' in text
    assert "# TYPE serving_effective_flops_total counter" in text
    assert "# TYPE serving_tile_occupancy_ratio histogram" in text
    stats = json.load(urllib.request.urlopen(_url(srv, "/v1/stats"),
                                             timeout=10))
    sp = stats["sparsity"]                       # compact rollup
    assert 0.0 <= sp["mean_ffn_sparsity"] <= 1.0
    assert sp["flops_reduction"] is not None
    full = stats["telemetry"]["sparsity"]        # full block
    assert len(full["per_layer_sparsity"]) == cfg.num_layers
    assert full["dense_flops_total"] >= full["effective_flops_total"] > 0
    assert full["tile_occupancy_hist"]["count"] > 0


def test_metrics_503_when_disabled():
    """An engine built without telemetry serves 503 on /metrics (and no
    telemetry block in /v1/stats) instead of crashing."""
    cfg = get_config("paper-0.5b").reduced()
    params = lm.init(jax.random.PRNGKey(2), cfg)
    engine = ServingEngine(params, cfg, block_size=4, max_batch=2,
                           max_seq_len=32)
    srv = ServingServer(engine, port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(_url(srv, "/metrics"), timeout=10)
        assert e.value.code == 503
        stats = json.load(urllib.request.urlopen(_url(srv, "/v1/stats"),
                                                 timeout=10))
        assert "telemetry" not in stats
        assert "sparsity" not in stats
    finally:
        srv.shutdown()


def test_shutdown_is_clean():
    """A dedicated server instance shuts down with both threads joined and
    the engine pool invariant-clean."""
    cfg = get_config("paper-0.5b").reduced()
    params = lm.init(jax.random.PRNGKey(1), cfg)
    engine = ServingEngine(params, cfg, block_size=4, max_batch=2,
                           max_seq_len=32)
    srv = ServingServer(engine, port=0).start()
    prompt = list(range(1, 7))
    _post(srv, "/v1/completions", {"prompt": prompt, "max_tokens": 2})
    srv.shutdown()
    for t in srv._threads:
        assert not t.is_alive()
    engine.kv.check_invariants()
    with pytest.raises(Exception):
        urllib.request.urlopen(_url(srv, "/healthz"), timeout=2)
