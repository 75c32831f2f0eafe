"""What the program names itself, for the per-layer readers: the spans its
trace recorder keeps (engine phases, request lifecycle, the HTTP front
door), and the step programs it names in the device trace
(``jit_decode_step``, ``jit_prefill_step``).

A program that lacks a span or a name gives nothing here, never an error,
so a reader returns None there.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple


def recorded(engine, name: str) -> List[Tuple[float, float, Dict]]:
    """(start, duration, args) of every complete span named ``name`` that
    the engine's trace recorder holds, start on its host clock
    (``perf_counter`` seconds), duration in seconds."""
    tm = getattr(engine, "telemetry", None)
    rec = getattr(tm, "trace", None)
    if rec is None:
        return []
    return [(rec.t0 + ev["ts"] / 1e6, ev["dur"] / 1e6, ev.get("args", {}))
            for ev in rec.to_chrome()["traceEvents"]
            if ev.get("ph") == "X" and ev.get("name") == name]


def started_in(spans, lo: float, hi: float):
    """The spans of ``recorded`` that start in ``[lo, hi)``."""
    return [s for s in spans if lo <= s[0] < hi]


def program_ms(trace, fn: str) -> List[float]:
    """Device milliseconds of each execution of the jitted function ``fn``
    (``XLA Modules`` events ``jit_<fn>(...)``) that lies wholly inside the
    traced span."""
    pat = re.compile(rf"jit_{re.escape(fn)}(\(|$)")
    return [p.dur / 1e6 for p in trace.programs
            if pat.match(p.name) and p.start >= trace.lo
            and p.start + p.dur <= trace.hi]
