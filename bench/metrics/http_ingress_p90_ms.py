"""Front door: 90th percentile of the server's ``http.receive`` spans that
start in the window, from the handler's entry until ``submit`` returned
(host clock). Moves ``ttft_p90_ms``."""
from benchlib import spans
from benchlib.measure import percentile


def read(run):
    got = spans.started_in(spans.recorded(run.engine, "http.receive"),
                           run.win.t0, run.win.t1)
    return percentile([d for _, d, _ in got], 90) * 1e3 if got else None
