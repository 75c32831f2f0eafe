"""From one measured window to numbers: end-to-end metrics from the client's
records, the sample that the reference scores, the data per-layer readers
read, and the run's printed facts (sparsity, overflow, compiles, lateness).
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchlib import tracekit, work

WINDOW_SPAN = "bench.window"


def model_config(cfg, m: dict):
    """``cfg`` (the preset ``serve.model_config`` resolved) with the
    configuration file's model section stamped in and checked."""
    sp = dataclasses.replace(cfg.sparsity, activation=m["activation"],
                             twell_tile=m["twell_tile"], twell_c=m["twell_c"])
    out = dataclasses.replace(
        cfg, num_layers=m["num_layers"], d_model=m["d_model"],
        num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
        head_dim=m["head_dim"], d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        gated=m["gated"], norm=m["norm"], rope_theta=m["rope_theta"],
        tied_embeddings=m["tied_embeddings"], dtype=m["dtype"],
        param_dtype=m["dtype"], sparsity=sp)
    if out.resolved_head_dim * out.num_heads == 0 or \
            out.padded_vocab % 128:
        raise ValueError(f"configuration does not describe a model: {m}")
    return out


def peaks(device_kind: str, path: Path) -> dict:
    """The device's published peaks from the table at ``path``; a device
    not in the table is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"have {sorted(table)}")
    return table[device_kind]


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100), linear between order statistics."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.nan
    return float(np.percentile(v, q))


class CompileCounter:
    """Programs lowered and compiled (or fetched from the persistent cache)
    while armed, from JAX's own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.lowered = self.compiled = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if not self.armed:
            return
        if event == self.EVENTS[0]:
            self.lowered += 1
        elif event == self.EVENTS[1]:
            self.compiled += 1

    def _event(self, event: str, **_) -> None:
        if self.armed and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self) -> None:
        self.lowered = self.compiled = self.cache_hits = 0

    def snapshot(self) -> Dict[str, int]:
        return {"lowered": self.lowered, "compiled": self.compiled,
                "cache_hits": self.cache_hits}

    def arm(self) -> None:
        self.armed = True

    def disarm(self) -> None:
        self.armed = False


@dataclasses.dataclass
class Window:
    t0: float                  # perf_counter when the window opened
    seconds: float
    records: List[dict]        # the client's, one per request
    requests: List[Any]        # traffic.Request, same order
    stats: List[Any]           # the engine's StepStats inside the window
    compiles: Dict[str, int]   # programs lowered / compiled / cache hits
    grace: float = 0.0         # late first tokens still count this long
    traced: Optional[Tuple[float, float]] = None   # perf_counter on/off

    @property
    def t1(self) -> float:
        return self.t0 + self.seconds

    def due(self) -> List[Tuple[Any, dict]]:
        return [(r, rec) for r, rec in zip(self.requests, self.records)
                if self.t0 <= rec["t_due"] < self.t1]

    @property
    def attempted(self) -> int:
        return len(self.due())

    @property
    def failed(self) -> int:
        """Requests due in the window that ended in an error, or that had no
        first token by the end of the grace."""
        return sum(1 for _, rec in self.due()
                   if not rec["t_tokens"] or rec["status"] not in
                   ("ok", "open"))


def end_to_end(win: Window) -> Dict[str, float]:
    """TTFT from the due time (a request with no first token counts as
    waiting until the client's deadline), every inter-token gap whose later
    token arrived in the window, and the tokens delivered in the window."""
    ttft, gaps, tokens = [], [], 0
    for _, rec in win.due():
        times = rec["t_tokens"]
        ttft.append(times[0] - rec["t_due"] if times else
                    win.t1 + win.grace - rec["t_due"])
    for rec in win.records:
        times = rec["t_tokens"]
        tokens += sum(1 for t in times if win.t0 <= t < win.t1)
        gaps += [b - a for a, b in zip(times, times[1:])
                 if win.t0 <= b < win.t1]
    return {"ttft_p90_ms": percentile(ttft, 90) * 1e3,
            "itl_p95_ms": percentile(gaps, 95) * 1e3,
            "output_tokens_per_s": tokens / win.seconds,
            "ttft_p50_ms": percentile(ttft, 50) * 1e3,
            "itl_p50_ms": percentile(gaps, 50) * 1e3}


def backlog(win: Window) -> Dict[str, float]:
    """How far the server fell behind: requests due in the window that had
    not finished when it closed, and the last due request's TTFT."""
    open_at_close = sum(1 for _, rec in win.due()
                        if not rec["t_tokens"] or rec["t_tokens"][-1] >= win.t1)
    last = max(win.due(), key=lambda x: x[1]["t_due"])[1]
    last_ttft = (last["t_tokens"][0] - last["t_due"]) if last["t_tokens"] \
        else math.inf
    return {"unfinished_at_close": open_at_close,
            "last_request_ttft_ms": last_ttft * 1e3}


def lateness(win: Window) -> Dict[str, float]:
    late = [rec["t_sent"] - rec["t_due"] for rec in win.records
            if rec["t_sent"] is not None]
    return {"p50_ms": percentile(late, 50) * 1e3,
            "max_ms": max(late, default=math.nan) * 1e3}


def finished(win: Window) -> List[Tuple[Any, dict]]:
    return [(r, rec) for r, rec in win.due()
            if rec["status"] == "ok" and rec["finish_reason"] is not None]


def short_outputs(win: Window) -> int:
    """Finished requests whose stream does not hold exactly ``max_tokens``
    tokens (decoding runs to the limit; there is no EOS)."""
    return sum(1 for r, rec in finished(win)
               if len(rec["tokens"]) != r.max_tokens
               or rec["finish_reason"] != "length")


def sample_for_check(sizes: List[Tuple[int, int]], seed: int, tokens: int,
                     max_requests: int) -> List[int]:
    """A seeded sample of requests given as (prompt length, served tokens):
    the indices of the longest (prompt + output) first, then of others at
    random until ``tokens`` served tokens or ``max_requests`` requests."""
    if not sizes:
        return []
    longest = max(range(len(sizes)), key=lambda i: sum(sizes[i]))
    rng = np.random.default_rng([int(seed) % (1 << 64), 3])
    order = [longest] + [int(i) for i in rng.permutation(len(sizes))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(i)
        n += sizes[i][1]
    return out


def run_info(served, win: Window) -> Dict[str, Any]:
    """What every run prints before its result: FFN sparsity from the
    program's probe, the TwELL overflow bound, compiles in the window, and
    how late the generator ran."""
    summary = served.engine.telemetry.summary()
    sp = (summary.get("sparsity") or {}).get("mean_ffn_sparsity")
    live_max, slots = served.overflow
    return {
        "ffn_sparsity": sp,
        "twell_overflow_share": 0.0 if live_max <= slots else None,
        "twell_live_columns_per_tile_max": f"{live_max} of {slots} slots",
        "compiles_in_window": win.compiles,
        "generator_lateness": lateness(win),
        "requests": {"attempted": win.attempted, "failed": win.failed,
                     "finished": len(finished(win))},
        "steps_in_window": len(win.stats),
    }


# ---------------------------------------------------------------------------
# the traced run

def host_phase_spans(engine, win: Window, lo: int) -> List[tracekit.Event]:
    """The engine's own step-phase spans (host clock), moved onto the
    trace's clock through the window span that opens both."""
    t_on = win.traced[0]
    chrome = engine.telemetry.trace.to_chrome()
    t_rec = engine.telemetry.trace.t0
    out = []
    for ev in chrome["traceEvents"]:
        if ev.get("ph") != "X" or ev.get("tid") != 0:
            continue
        start = t_rec + ev["ts"] / 1e6
        if start + ev["dur"] / 1e6 < win.traced[0] or start > win.traced[1]:
            continue
        out.append(tracekit.Event(f"engine:{ev['name']}",
                                  lo + int((start - t_on) * 1e9),
                                  int(ev["dur"] * 1e3)))
    # "overlap" spans launch -> collect, the time device work was in flight
    return [e for e in out if e.name != "engine:overlap"] + \
        [e for e in out if e.name == "engine:overlap"]


def load_trace(trace_dir: str, win: Window, engine) -> tracekit.Trace:
    tr = tracekit.load(trace_dir, WINDOW_SPAN)
    tr.host_spans = host_phase_spans(engine, win, tr.lo)
    return tr


@dataclasses.dataclass
class RunData:
    """What a per-layer reader gets: the configuration, the window (client
    records, step stats), the reduced trace, the engine (for its telemetry)
    and the device's peaks."""
    conf: dict
    win: Window
    trace: tracekit.Trace
    engine: Any
    peaks: dict

    @property
    def dims(self) -> work.Dims:
        return work.Dims.from_model(self.conf["model"])

    def traced_tokens(self):
        """(decoded tokens, summed attention context of those tokens) whose
        arrival fell inside the traced span. A request's token ``j >= 1``
        came from a decode step whose query attended ``prompt + j`` keys."""
        lo, hi = self.win.traced
        n = ctx = 0
        for r, rec in zip(self.win.requests, self.win.records):
            plen = len(r.prompt)
            for j, t in enumerate(rec["t_tokens"]):
                if j >= 1 and lo <= t < hi:
                    n += 1
                    ctx += plen + j
        return n, ctx

    def traced_steps(self):
        """StepStats of steps launched inside the traced span (by the
        engine's own launch-phase spans)."""
        lo, hi = self.win.traced
        chrome = self.engine.telemetry.trace.to_chrome()
        t_rec = self.engine.telemetry.trace.t0
        steps = set()
        for ev in chrome["traceEvents"]:
            if ev.get("ph") == "X" and ev.get("tid") == 0 and \
                    ev.get("name") == "launch":
                t = t_rec + ev["ts"] / 1e6
                if lo <= t < hi:
                    steps.add(ev["args"]["step"])
        # the launch span of step k is recorded before StepStats k + 1
        return [s for s in self.win.stats if s.step - 1 in steps]

    def prompt_ctx_mean(self) -> float:
        """Mean keys a prompt token's query attends, over the prompts that
        finished prefill in the traced span: (P + 1) / 2 weighted by P."""
        lo, hi = self.win.traced
        num = den = 0
        for r, rec in zip(self.win.requests, self.win.records):
            if rec["t_tokens"] and lo <= rec["t_tokens"][0] < hi:
                p = len(r.prompt)
                num += p * (p + 1) / 2
                den += p
        return num / den if den else math.nan

    def prompts_finished(self) -> int:
        lo, hi = self.win.traced
        return sum(1 for rec in self.win.records
                   if rec["t_tokens"] and lo <= rec["t_tokens"][0] < hi)
