"""OpenAI-style HTTP front end over the serving engine (stdlib only).

One engine thread drives ``engine.step()`` whenever work exists; handler
threads (``ThreadingHTTPServer``) talk to it exclusively through the
thread-safe handle API — ``submit`` / ``RequestHandle`` / ``cancel`` — and
a per-step condition variable the engine loop notifies, so no handler ever
polls a hot loop.

Endpoints:

  POST /v1/completions     {"prompt": [token ids], "max_tokens", "stream",
                            "temperature", "top_k", "top_p", "seed",
                            "priority", "eos_token_id"}
                           Non-streaming: one JSON body when finished.
                           ``"stream": true``: Server-Sent Events — one
                           ``data: {...}`` chunk per engine step that
                           committed tokens, a final chunk carrying
                           ``finish_reason``, then ``data: [DONE]``.
                           A client disconnect mid-stream cancels the
                           request (its KV blocks free on the next step).
  POST /v1/cancel          {"id": "cmpl-<rid>"} -> {"cancelled": bool}
  GET  /healthz            liveness + queue depths; 503 until startup
                           warmup precompilation (when enabled) finishes
  GET  /v1/stats           engine counters (finished/cancelled/preempted,
                           KV-pool picture) + a telemetry rollup (phase
                           timing means, cache hit rate, spec acceptance,
                           compile counts) when the engine has telemetry;
                           behind ``--disagg`` a ``roles`` section adds the
                           per-role engine + transfer-buffer picture
  GET  /metrics            Prometheus text exposition of the engine's
                           metrics registry (step-phase histograms, KV
                           occupancy gauges, TTFT/ITL histograms, ...);
                           503 when the engine was built without telemetry

The repo has no tokenizer: prompts are token-id lists, and completions
return ``token_ids`` (an OpenAI-shaped envelope, not a drop-in clone).

With engine telemetry on, the front door times itself on each request's
trace track: ``http.receive`` from the handler's entry until ``submit``
returned, and one ``http.write`` per SSE chunk with its ``tokens`` and its
``lag_ms``, from the engine's commit of the chunk's earliest token to the
flush. Both are also ``jax.profiler`` annotations of the same names.
"""
from __future__ import annotations

import contextlib
import gc
import json
import select
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from jax.profiler import TraceAnnotation

from repro.serving.sampling import SamplingParams

HTTP_RECEIVE = "http.receive"
HTTP_WRITE = "http.write"
_UNTRACED = contextlib.nullcontext()


def _completion_chunk(rid: int, tokens, finish_reason: Optional[str]):
    return {"id": f"cmpl-{rid}", "object": "text_completion.chunk",
            "choices": [{"index": 0, "token_ids": list(tokens),
                         "finish_reason": finish_reason}]}


class ServingServer:
    """HTTP server + engine-stepping thread over one ``ServingEngine``."""

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 8000,
                 idle_wait_s: float = 0.05, warmup: bool = False):
        self.engine = engine
        self.idle_wait_s = idle_wait_s
        self._work = threading.Event()        # submissions wake the loop
        self._stepped = threading.Condition() # notified after every step
        self._step_seq = 0                    # steps completed (under cond)
        self._stop = threading.Event()
        # readiness gate: with warmup=True the engine thread precompiles the
        # whole bucket grid before serving, and /healthz answers 503 until
        # that finishes so load balancers don't route to a cold process
        self._warmup = bool(warmup)
        self._ready = threading.Event()
        self._frozen = False                  # set-up heap frozen (warmup)
        if not self._warmup:
            self._ready.set()
        engine.on_new_work = self._work.set
        self._telemetry = engine.telemetry
        server = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):   # quiet by default
                pass

            def _json(self, code: int, payload: dict) -> None:
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    h = server.health()
                    self._json(200 if h["ok"] else 503, h)
                elif self.path == "/v1/stats":
                    self._json(200, server.stats())
                elif self.path == "/metrics":
                    self._metrics()
                else:
                    self._json(404, {"error": f"no route {self.path}"})

            def _metrics(self):
                tm = server.engine.telemetry
                if tm is None:
                    self._json(503, {"error": "telemetry disabled: build "
                                              "the engine with "
                                              "telemetry=True (serve.py "
                                              "--http enables it unless "
                                              "--no-metrics)"})
                    return
                body = tm.registry.render_prometheus().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _body(self) -> Optional[dict]:
                """The request's JSON body; None after a 400 reply."""
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    return json.loads(self.rfile.read(n) or b"{}")
                except (ValueError, json.JSONDecodeError) as e:
                    self._json(400, {"error": f"bad JSON: {e}"})
                    return None

            def do_POST(self):
                if self.path == "/v1/completions":
                    t_receive = time.perf_counter()
                    with server.annotation(HTTP_RECEIVE):
                        body = self._body()
                        handle = None if body is None else \
                            self._submit(body)
                    if handle is None:
                        return
                    server.record(handle.rid, HTTP_RECEIVE, t_receive,
                                  time.perf_counter())
                    if body.get("stream"):
                        self._stream(handle)
                    else:
                        self._reply(handle)
                    return
                body = self._body()
                if body is None:
                    return
                if self.path == "/v1/cancel":
                    rid = str(body.get("id", "")).replace("cmpl-", "")
                    ok = rid.lstrip("-").isdigit() and \
                        server.engine.cancel(int(rid))
                    self._json(200, {"cancelled": bool(ok)})
                else:
                    self._json(404, {"error": f"no route {self.path}"})

            def _submit(self, body: dict):
                """Submit a completion request; its handle, or None after a
                400 reply."""
                prompt = body.get("prompt")
                if not isinstance(prompt, list) or not prompt or \
                        not all(isinstance(t, int) for t in prompt):
                    self._json(400, {"error": "prompt must be a non-empty "
                                              "list of token ids (the repo "
                                              "ships no tokenizer)"})
                    return None
                try:
                    seed = body.get("seed")
                    sp = SamplingParams(
                        temperature=float(body.get("temperature", 0.0)),
                        top_k=int(body.get("top_k", 0)),
                        top_p=float(body.get("top_p", 1.0)),
                        seed=None if seed is None else int(seed))
                    # handle-side event buffering (stream=True) is for
                    # callers that drain handle.events(); the SSE loop
                    # below reads new_tokens() deltas, so don't buffer
                    eos = body.get("eos_token_id")
                    return server.engine.submit(
                        prompt, sampling=sp,
                        max_tokens=int(body.get("max_tokens", 16)),
                        eos_token_id=None if eos is None else int(eos),
                        priority=int(body.get("priority", 0)))
                except (TypeError, ValueError) as e:
                    self._json(400, {"error": str(e)})
                    return None

            def _reply(self, handle) -> None:
                server.wait_finished(handle)
                if not handle.finished:          # shutdown raced the request
                    self._json(503, {"error": "server shutting down"})
                    return
                out = handle.result()
                self._json(200, {
                    "id": f"cmpl-{out.rid}", "object": "text_completion",
                    "choices": [{"index": 0,
                                 "token_ids": out.token_ids,
                                 "finish_reason": out.finish_reason}],
                    "usage": {"prompt_tokens": len(out.prompt),
                              "completion_tokens": len(out.token_ids)},
                    "num_preemptions": out.num_preemptions})

            def _client_gone(self) -> bool:
                """True when the peer closed its end. A failed write only
                surfaces after the kernel send buffer drains — far too late
                for a fast engine — so peek the socket for EOF instead."""
                try:
                    r, _, _ = select.select([self.connection], [], [], 0)
                    if not r:
                        return False
                    return self.connection.recv(1, socket.MSG_PEEK) == b""
                except OSError:
                    return True

            def _write(self, rid: int, data: bytes, tokens: int,
                       t_commit: float) -> None:
                """Write and flush one SSE chunk of ``tokens`` tokens, the
                earliest committed at ``t_commit``."""
                t0 = time.perf_counter()
                with server.annotation(HTTP_WRITE):
                    self.wfile.write(data)
                    self.wfile.flush()
                t1 = time.perf_counter()
                server.record(rid, HTTP_WRITE, t0, t1, tokens=tokens,
                              lag_ms=(t1 - t_commit) * 1e3)

            def _stream(self, handle) -> None:
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self.end_headers()
                try:
                    while True:
                        seen = server.step_token()   # before the state reads
                        if self._client_gone():
                            raise BrokenPipeError
                        # read `finished` BEFORE draining the delta: tokens
                        # commit before the terminal event publishes, so the
                        # opposite order could drop the final tokens when
                        # the engine finishes the request between the reads
                        done = handle.finished
                        delta, t_commit = handle.new_tokens_timed()
                        if delta or done:
                            chunk = _completion_chunk(
                                handle.rid, delta,
                                handle.finish_reason if done else None)
                            data = b"data: " + json.dumps(chunk).encode() \
                                + b"\n\n"
                            if done:
                                data += b"data: [DONE]\n\n"
                                if t_commit is None:   # only the close left
                                    t_commit = handle.result().finish_time
                            self._write(handle.rid, data, len(delta),
                                        t_commit)
                        if done:
                            return
                        if server._stop.is_set():
                            return       # shutdown: drop the stream mid-way
                        server.wait_step(seen, timeout=1.0)
                except OSError:          # BrokenPipe/ConnectionReset/EOF peek
                    # client went away mid-stream: abort the request so its
                    # KV blocks and batch slot go back to the pool
                    server.engine.cancel(handle)

        self.httpd = ThreadingHTTPServer((host, port), Handler)
        self.httpd.daemon_threads = True
        self.host, self.port = self.httpd.server_address[:2]
        self._threads = []

    # ---- front-door tracing ------------------------------------------------

    def annotation(self, name: str):
        """The ``name`` profiler annotation (a no-op without telemetry)."""
        return _UNTRACED if self._telemetry is None else TraceAnnotation(name)

    def record(self, rid: int, name: str, t0: float, t1: float,
               **args) -> None:
        """Keep a front-door span on request ``rid``'s trace track (when
        the engine traces)."""
        if self._telemetry is not None and self._telemetry.trace is not None:
            self._telemetry.trace.request_record(rid, name, t0, t1, **args)

    # ---- engine loop -------------------------------------------------------

    def _engine_loop(self) -> None:
        if self._warmup and not self._ready.is_set():
            self.engine.warmup()                # precompile the bucket grid
            # what set-up built (modules, traced and compiled programs, the
            # engine) lives as long as the server: park it outside the
            # collector, so a full collection while serving walks only what
            # serving allocated, not a heap that takes it hundreds of ms
            gc.collect()
            gc.freeze()
            self._frozen = True
            self._ready.set()
        while not self._stop.is_set():
            if self.engine.has_unfinished():
                self.engine.step()              # publishes handle state...
                with self._stepped:             # ...BEFORE the notify
                    self._step_seq += 1
                    self._stepped.notify_all()
            else:
                self._work.wait(self.idle_wait_s)
                self._work.clear()
        with self._stepped:                     # release any waiting handler
            self._stepped.notify_all()

    def step_token(self) -> int:
        """Current step sequence number; capture BEFORE reading handle
        state, then pass to ``wait_step`` — a step that lands between the
        read and the wait returns immediately instead of being missed."""
        with self._stepped:
            return self._step_seq

    def wait_step(self, seen: Optional[int] = None,
                  timeout: Optional[float] = None) -> None:
        """Block until a step newer than ``seen`` completes (or shutdown,
        or timeout). ``seen=None`` waits for the next step from now."""
        with self._stepped:
            if seen is None:
                seen = self._step_seq
            self._stepped.wait_for(
                lambda: self._step_seq != seen or self._stop.is_set(),
                timeout)

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        """Block until warmup precompilation finishes (immediately true when
        the server was built with ``warmup=False``)."""
        return self._ready.wait(timeout)

    def wait_finished(self, handle, timeout_per_step: float = 1.0) -> None:
        """Block until the handle is terminal (or shutdown). Missed-notify
        free: the terminal check and the wait share the condition lock the
        engine loop notifies under."""
        with self._stepped:
            while not handle.finished and not self._stop.is_set():
                self._stepped.wait(timeout_per_step)

    # ---- lifecycle ---------------------------------------------------------

    def start(self) -> "ServingServer":
        self._threads = [
            threading.Thread(target=self._engine_loop, name="engine-loop",
                             daemon=True),
            threading.Thread(target=self.httpd.serve_forever,
                             name="http-serve", daemon=True),
        ]
        for t in self._threads:
            t.start()
        return self

    def shutdown(self) -> None:
        """Stop accepting connections, stop the engine loop, join both
        threads. In-flight requests are dropped with the process (callers
        stream or poll; there is no persistence to flush)."""
        self._stop.set()
        self._work.set()
        self.httpd.shutdown()
        self.httpd.server_close()
        for t in self._threads:
            t.join(timeout=5.0)
        flush = getattr(self.engine, "flush", None)
        if flush is not None:
            flush()          # drain any pipelined in-flight step (no-op sync)
        if self._frozen:
            gc.unfreeze()    # the set-up heap is collectable again
            self._frozen = False

    # ---- introspection -----------------------------------------------------

    def health(self) -> dict:
        e = self.engine
        ready = self._ready.is_set()
        out = {"ok": ready,
               "running": len(e.running), "prefilling": len(e.prefilling),
               "waiting": len(e.scheduler), "steps": e._step_idx}
        if not ready:
            out["warming_up"] = True
        return out

    def stats(self) -> dict:
        e = self.engine
        out = {"steps": e._step_idx, "finished": e.finished_total,
               "cancelled": e.cancelled_total,
               "preempted": e.preempted_total,
               "running": len(e.running), "waiting": len(e.scheduler),
               "kv": {"num_blocks": e.kv.num_blocks,
                      "free": e.kv.num_free,
                      "evictable_cached": e.kv.num_evictable,
                      "reserved": e._reserved},
               "prefill_tokens_total": e.prefill_tokens_total,
               "cached_tokens_total": e.cached_tokens_total}
        role_stats = getattr(e, "role_stats", None)
        if role_stats is not None:
            # disaggregated front door: per-role engine + transfer-buffer view
            out["roles"] = role_stats()
        if e.telemetry is not None:
            out["telemetry"] = e.telemetry.summary()
            sp = out["telemetry"].get("sparsity")
            if sp is not None:
                # compact operator-facing rollup (the full per-layer detail
                # stays under telemetry.sparsity)
                out["sparsity"] = {
                    "mean_ffn_sparsity": sp["mean_ffn_sparsity"],
                    "flops_reduction": sp["flops_reduction"],
                }
        return out
