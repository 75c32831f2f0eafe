"""The trace reduction: kernel classification, busy/idle union, kernel and
program time, on a slice of a trace recorded on a TPU v5e and on a small
hand-written XSpace read from disk."""
import json
import os

import pytest

from benchlib import tracekit

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tpu_trace_slice.json")


@pytest.fixture(scope="module")
def recorded():
    d = json.load(open(DATA))
    lo, hi = d["window"]
    ops = [tracekit.Event(n, s, t) for n, s, t in d["ops"]]
    progs = [tracekit.Program(n, s, t) for n, s, t in d["modules"]]
    tracekit.classify(progs, ops)
    return tracekit.Trace(lo=lo, hi=hi, ops=ops, programs=progs)


def test_step_programs_classified_by_their_kernels(recorded):
    kinds = [(p.name.split("(")[0], p.kind) for p in recorded.programs]
    assert ("jit_fn", "prefill") in kinds and ("jit_fn", "decode") in kinds
    assert all(k == "other" for n, k in kinds if n != "jit_fn")


def test_kernels_land_in_their_programs(recorded):
    assert recorded.kernel_s("attn_decode", "decode") > 0
    assert recorded.kernel_s("attn_decode", "prefill") == 0
    assert recorded.kernel_s("attn_chunk", "prefill") > 0
    assert recorded.kernel_s("ffn", "decode") > 0
    assert recorded.kernel_s("ffn") == pytest.approx(
        recorded.kernel_s("ffn", "decode") +
        recorded.kernel_s("ffn", "prefill"))


def test_busy_is_a_union_within_the_window(recorded):
    busy = recorded.busy_s
    assert 0 < busy <= recorded.window_s
    # nested ops (a loop and its body) count once: busy <= summed durations
    assert busy < sum(o.dur for o in recorded.ops) / 1e9
    gaps = recorded.idle_gaps()
    assert gaps and all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in recorded.idle_gaps(n=10**6)) == \
        pytest.approx(recorded.window_s - busy)


def test_breakdown_names_ops_not_loops(recorded):
    top = recorded.top_ops()
    names = [n for n, _ in top]
    assert "while" not in names and len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)


def test_union_of_overlapping_intervals():
    covered, merged = tracekit.union_ns([(0, 10), (5, 20), (30, 40),
                                         (40, 41)])
    assert covered == 31 and merged == [[0, 20], [30, 41]]


XSPACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules"
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops"
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 20000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_fn(1)" } }
  event_metadata { key: 2 value { id: 2 name: "%paged_decode_attention_pallas.1 = bf16[1]" } }
  event_metadata { key: 3 value { id: 3 name: "%twell_gate_matmul_pallas.2 = bf16[1]" } }
  event_metadata { key: 4 value { id: 4 name: "jit_convert_element_type(2)" } }
  event_metadata { key: 5 value { id: 5 name: "%convert.3 = f32[1]" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 3 name: "python3"
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 30000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } } }
'''


def test_load_reads_an_xplane_file(tmp_path):
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    tr = tracekit.load(str(tmp_path), "bench.window")
    assert tr.window_s == pytest.approx(30e-6)
    assert [p.kind for p in tr.programs] == ["decode", "other"]
    assert tr.program_s("decode") == pytest.approx(4e-6)
    assert tr.kernel_s("attn_decode", "decode") == pytest.approx(3e-6)
    assert tr.kernel_s("ffn", "decode") == pytest.approx(1e-6)
    assert tr.busy_s == pytest.approx(5e-6)
    assert tr.idle_gaps(n=1)[0][1] == pytest.approx(14e-6)
