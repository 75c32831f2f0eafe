"""Scheduler: 90th percentile of the time requests submitted in the window
spent queued before admission, from the engine's own request spans
(telemetry ``QUEUED`` spans, host clock). Moves ``ttft_p90_ms``."""
from benchlib.measure import percentile


def read(run):
    tr = run.engine.telemetry.trace
    waits = []
    for ev in tr.to_chrome()["traceEvents"]:
        if ev.get("name") != "QUEUED" or ev.get("ph") != "X":
            continue
        start = tr.t0 + ev["ts"] / 1e6
        if run.win.t0 <= start < run.win.t1:
            waits.append(ev["dur"] / 1e3)
    return percentile(waits, 90) if waits else None
