"""Front door: 95th percentile, over the server's ``http.write`` records
(one per SSE chunk) that start in the window, of ``lag_ms``: the flush's
end minus the engine's commit of the chunk's earliest token (host clock).
Moves ``itl_p95_ms``."""
from benchlib import spans
from benchlib.measure import percentile


def read(run):
    got = spans.started_in(spans.recorded(run.engine, "http.write"),
                           run.win.t0, run.win.t1)
    return percentile([a["lag_ms"] for _, _, a in got], 95) if got else None
