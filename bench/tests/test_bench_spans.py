"""The readers of the program's own spans and named step programs, on
synthetic runs: a reduced device trace with ``jit_decode_step`` and
``jit_prefill_step`` executions, and an engine whose trace recorder exports
``PREFILL`` spans and ``http.*`` records. Each reader returns its number,
and None where the program has nothing for it to read."""
import types

import pytest

from benchlib import measure, tracekit
from benchlib.spec import BENCH_DIR, Spec

MS = 1_000_000                 # ns
LO, HI = 5 * MS, 10_005 * MS   # the traced span
T_REC = 50.0                   # the recorder's timebase (perf_counter s)
WIN_T0, WIN_S = 100.0, 10.0    # the window (perf_counter s)


def reader(name):
    return Spec({}, BENCH_DIR).reader(name)


def trace(programs):
    return tracekit.Trace(lo=LO, hi=HI, ops=[], programs=[
        tracekit.Program(n, s, d) for n, s, d in programs])


STEPS = trace([
    ("jit_decode_step(11)", 10 * MS, 80 * MS),
    ("jit_decode_step(11)", 100 * MS, 90 * MS),
    ("jit_decode_step(12)", 0, 80 * MS),           # starts before the span
    ("jit_decode_step(11)", 10_000 * MS, 80 * MS),  # ends after it
    ("jit_prefill_step(13)", 200 * MS, 200 * MS),
    ("jit_prefill_step(14)", 500 * MS, 300 * MS),
    ("jit_fn(15)", 900 * MS, 5 * MS),
    ("jit_decode_step_probe(16)", 950 * MS, 7 * MS),
])


def span(name, start_s, dur_ms, tid=1, **args):
    return {"ph": "X", "pid": 0, "tid": tid, "name": name,
            "ts": (start_s - T_REC) * 1e6, "dur": dur_ms * 1e3,
            "args": args}


def engine(events):
    rec = types.SimpleNamespace(
        t0=T_REC, to_chrome=lambda: {"traceEvents": events})
    return types.SimpleNamespace(telemetry=types.SimpleNamespace(trace=rec))


RECORDED = engine(
    [{"ph": "M", "pid": 0, "tid": 1, "name": "thread_name",
      "args": {"name": "request 0"}},
     span("launch", 101.0, 1.0, tid=0, step=3),
     span("QUEUED", 101.0, 0.5)]
    # admitted in the window: 100, 200, ..., 1000 ms; one before it
    + [span("PREFILL", 100.5 + i, 100.0 * (i + 1)) for i in range(10)]
    + [span("PREFILL", 99.0, 5000.0)]
    + [span("http.receive", 101.0 + i, float(i + 1)) for i in range(4)]
    + [span("http.receive", 110.0, 50.0)]           # after the window
    + [span("http.write", 100.0 + i / 4, 0.1, tokens=1,
            lag_ms=float(i + 1)) for i in range(20)]
    + [span("http.write", 99.9, 0.1, tokens=1, lag_ms=900.0)])


def run(trace_=STEPS, engine_=RECORDED):
    win = measure.Window(t0=WIN_T0, seconds=WIN_S, records=[], requests=[],
                         stats=[], compiles={})
    return measure.RunData(conf={}, win=win, trace=trace_, engine=engine_,
                           peaks={})


@pytest.mark.parametrize("name, expected", [
    ("decode_step_device_ms", 85.0),        # 80 and 90 lie inside the span
    ("prefill_step_device_ms", 250.0),
    ("prefill_span_p90_ms", 910.0),         # p90 of 100 ... 1000
    ("http_ingress_p90_ms", 3.7),           # p90 of 1, 2, 3, 4
    ("http_egress_p95_ms", 19.05),          # p95 of 1 ... 20
])
def test_reader_reads_its_spans(name, expected):
    assert reader(name).read(run()) == pytest.approx(expected)


@pytest.mark.parametrize("name", [
    "decode_step_device_ms", "prefill_step_device_ms"])
def test_step_readers_find_nothing_in_unnamed_programs(name):
    unnamed = trace([("jit_fn(1)", 10 * MS, 80 * MS),
                     ("jit_fn(2)", 200 * MS, 200 * MS)])
    assert reader(name).read(run(trace_=unnamed)) is None


@pytest.mark.parametrize("name", [
    "prefill_span_p90_ms", "http_ingress_p90_ms", "http_egress_p95_ms"])
@pytest.mark.parametrize("engine_", [
    engine([span("QUEUED", 101.0, 0.5)]),                # no such records
    engine([span("PREFILL", 120.0, 10.0),
            span("http.receive", 120.0, 1.0),
            span("http.write", 120.0, 0.1, tokens=1, lag_ms=1.0)]),  # late
    types.SimpleNamespace(telemetry=None),               # no telemetry
], ids=["absent", "outside_window", "no_telemetry"])
def test_span_readers_find_nothing(name, engine_):
    assert reader(name).read(run(engine_=engine_)) is None
