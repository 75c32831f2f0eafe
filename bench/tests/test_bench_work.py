"""Operation and byte counts against shapes worked out by hand, and the
roofline and MFU arithmetic of the per-layer readers."""
import math
import types

import pytest

from benchlib import spec, tracekit, work

PAPER = {"num_layers": 28, "d_model": 2048, "num_heads": 32,
         "num_kv_heads": 32, "head_dim": 64, "d_ff": 5632,
         "vocab_size": 49152, "gated": True, "dtype": "bfloat16"}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_paper_sizes():
    d = work.Dims.from_model(PAPER)
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert d.layer_matmul_params == per_layer
    assert d.matmul_params == 28 * per_layer + 49152 * 2048
    assert d.kv_bytes_per_token == 28 * 2 * 2048 * 2 == 229376   # 224 KiB


def test_decode_token_and_attention():
    d = work.Dims.from_model(PAPER)
    assert work.attn_flops(d, 100) == 4 * 100 * 2048 * 28
    assert work.decode_token_flops(d, 100) == \
        2 * d.matmul_params + 4 * 100 * 2048 * 28
    f, b = work.attn_decode_call(d, 600)
    assert f == 4 * 600 * 2048
    assert b == (2 * 600 * 2048 + 2 * 2048) * 2


def test_prefill_counts_head_once_per_prompt():
    d = work.Dims.from_model(PAPER)
    body = 2 * (d.matmul_params - 49152 * 2048) * 384
    attn = 4 * (384 * 385 / 2) * 2048 * 28
    assert work.prefill_flops(d, 384, 384 * 385 / 2, 1) == \
        pytest.approx(body + attn + 2 * 49152 * 2048)


def test_ffn_decode_call_eq3():
    d = work.Dims.from_model(PAPER)
    f, b = work.ffn_decode_call(d, 16, 44)
    assert f == 16 * (2 * 2048 * 5632 + 4 * 2048 * 44)
    assert b == (2048 * 5632 + 2 * 44 * 2048 + 2 * 16 * 2048) * 2
    nongated = work.Dims.from_model(dict(PAPER, gated=False))
    f, b = work.ffn_decode_call(nongated, 1, 10)
    assert f == 2 * 2048 * 5632 + 2 * 2048 * 10


def test_least_seconds_takes_the_binding_bound():
    assert work.least_seconds(197e12, 1.0, PEAK) == pytest.approx(1.0)
    assert work.least_seconds(1.0, 819e9, PEAK) == pytest.approx(1.0)


def _run(ops, programs, tokens, lo=0, hi=10**9):
    """A RunData stand-in: one request whose tokens arrive inside the
    traced span, over the paper's shapes."""
    trace = tracekit.Trace(lo=lo, hi=hi, ops=ops, programs=programs)
    tracekit.classify(programs, ops)
    req = types.SimpleNamespace(prompt=[0] * 100)
    rec = {"t_tokens": [0.5 + 0.001 * i for i in range(tokens)]}
    win = types.SimpleNamespace(requests=[req], records=[rec],
                                traced=(0.0, 10.0))
    run = types.SimpleNamespace(trace=trace, win=win, peaks=PEAK,
                                dims=work.Dims.from_model(PAPER))
    from benchlib.measure import RunData
    run.traced_tokens = types.MethodType(RunData.traced_tokens, run)
    return run


def test_attention_roofline_and_decode_mfu_readers():
    # one decode program of 1 ms holding the decode kernel for 0.5 ms
    ops = [tracekit.Event("%paged_decode_attention_pallas.3 = x", 100, 500_000),
           tracekit.Event("%fusion.1 = y", 600_000, 400_000)]
    progs = [tracekit.Program("jit_fn(1)", 0, 1_000_000)]
    run = _run(ops, progs, tokens=11)          # 10 decoded tokens
    bench = spec.Spec({"per_layer": []})
    n, ctx = run.traced_tokens()
    assert n == 10 and ctx == sum(100 + j for j in range(1, 11))
    roof = bench.reader("attn_decode_roofline").read(run)
    d = run.dims
    kv = (2 * ctx * d.kv_width + 2 * 10 * d.q_width) * 2 * d.layers
    assert roof == pytest.approx(100 * kv / 819e9 / 0.5e-3)
    mfu = bench.reader("mfu.decode").read(run)
    flops = 10 * 2 * d.matmul_params + 4 * ctx * 2048 * 28
    assert mfu == pytest.approx(100 * flops / (1e-3 * 197e12))


def test_readers_find_nothing_in_an_empty_trace():
    run = _run([], [], tokens=5)
    bench = spec.Spec({"per_layer": []})
    for name in ("attn_decode_roofline", "mfu.decode"):
        assert bench.reader(name).read(run) is None
    assert math.isclose(bench.reader("device_idle_share").read(run), 100.0)
