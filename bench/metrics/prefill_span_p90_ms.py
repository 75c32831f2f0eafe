"""Scheduler: 90th percentile of the time from a request's admission to
the commit of its first token (telemetry ``PREFILL`` spans, host clock) of
requests admitted in the window. Moves ``ttft_p90_ms``."""
from benchlib import spans
from benchlib.measure import percentile


def read(run):
    got = spans.started_in(spans.recorded(run.engine, "PREFILL"),
                           run.win.t0, run.win.t1)
    return percentile([d for _, d, _ in got], 90) * 1e3 if got else None
