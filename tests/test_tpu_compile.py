"""The five serving kernels compile for a described TPU v5e at paper-0.5b
widths, the whole paper-1.5b decode program holds no copy of its KV
pools, and its FFN kernels read their layer's weights out of the stacked
arrays.

No chip is needed: the TPU compiler is installed, and it compiles for a
topology that is described rather than attached. That catches what Pallas
interpret mode cannot — block shapes the Mosaic tiling refuses, kernels over
the VMEM budget — at a decode-sized M (the engine's batch buckets) and a
prefill-sized M (prefill chunk 64 x batch 8). The whole decode program
shows what the compiler lays out around the kernels: the pools are donated
and carried through the layer scan, so its temporaries stay far below the
pools' 3.5 GiB.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file. The persistent compilation cache
is off around the compiles (an entry compiled for a described chip cannot
be read back without one).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import lm
from repro.kernels.paged_chunk_attention import paged_chunk_attention_pallas
from repro.kernels.paged_decode_attention import paged_decode_attention_pallas
from repro.kernels.sparse_ffn import (tile_skip_ffn_pallas,
                                      twell_down_proj_pallas,
                                      twell_fused_ffn_pallas)
from repro.kernels.twell_pack import twell_gate_matmul_pallas

CFG = get_config("paper-0.5b")
K, N = CFG.d_model, CFG.d_ff
T, C = CFG.sparsity.twell_tile, CFG.sparsity.twell_c
NT, TC = N // T, T // C
H, HKV, HD = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim
BLOCK, WIDTH = 16, 32                 # KV block; table width of 512 tokens
STACK = 4                             # FFN weights stacked over 4 layers
FFN_M = [1, 8, 512]                   # decode buckets 1 and 8; 64 x 8 prefill
ATTN_B = [1, 8]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:            # no TPU compiler to describe
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes):
    """Compile ``fn`` for the described chip; assert it holds a kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


BF16, I32 = jnp.bfloat16, jnp.int32


def _packed(m):
    return [((m, NT * TC), BF16), ((m, NT * TC), I32), ((m, NT), I32)]


# stacked (L, K, N) / (L, N, K) FFN weights and the layer to read
W_KN, W_NK, LAYER = ((STACK, K, N), BF16), ((STACK, N, K), BF16), ((), I32)


@pytest.mark.parametrize("m", FFN_M)
def test_twell_gate_matmul_compiles(one_chip, m):
    _compile(one_chip,
             lambda x, w, layer: twell_gate_matmul_pallas(
                 x, w, layer, T, C, "relu", interpret=False),
             ((m, K), BF16), W_KN, LAYER)


@pytest.mark.parametrize("m", FFN_M)
def test_twell_fused_ffn_compiles(one_chip, m):
    _compile(one_chip,
             lambda v, i, n, x, wu, wd, layer: twell_fused_ffn_pallas(
                 v, i, n, x, wu, wd, layer, T, interpret=False),
             *_packed(m), ((m, K), BF16), W_KN, W_NK, LAYER)


@pytest.mark.parametrize("m", FFN_M)
def test_twell_down_proj_compiles(one_chip, m):
    _compile(one_chip,
             lambda v, i, n, wd, layer: twell_down_proj_pallas(
                 v, i, n, wd, layer, T, interpret=False),
             *_packed(m), W_NK, LAYER)


@pytest.mark.parametrize("m", FFN_M)
def test_tile_skip_ffn_compiles(one_chip, m):
    _compile(one_chip,
             lambda x, wg, wu, wd, layer: tile_skip_ffn_pallas(
                 x, wg, wu, wd, layer, T, "relu", interpret=False),
             ((m, K), BF16), W_KN, W_KN, W_NK, LAYER)


def _pools(b):
    """The whole (L, N, bs, Hkv*hd) pools of every layer, as the layer
    scan carries them, a block table and seq_lens."""
    pool = ((CFG.num_layers, 1 + b * WIDTH, BLOCK, HKV * HD), BF16)
    return [pool, pool, ((b, WIDTH), I32), ((b,), I32)]


@pytest.mark.parametrize("b", ATTN_B)
def test_paged_decode_attention_compiles(one_chip, b):
    _compile(one_chip,
             lambda q, kp, vp, bt, sl, layer: paged_decode_attention_pallas(
                 q, kp, vp, bt, sl, layer, interpret=False),
             ((b, 1, H, HD), BF16), *_pools(b), ((), I32))


@pytest.mark.parametrize("b", ATTN_B)
def test_paged_chunk_attention_compiles(one_chip, b):
    _compile(one_chip,
             lambda q, kp, vp, bt, sl, nn, layer: paged_chunk_attention_pallas(
                 q, kp, vp, bt, sl, nn, layer, interpret=False),
             ((b, 64, H, HD), BF16), *_pools(b), ((b,), I32), ((), I32))


def _paper_1_5b_step(sharding, phase):
    """paper-1.5b's decode or prefill program compiled as its benchmark cell
    serves it: batch 16, 256 blocks of 64 (3.5 GiB of bf16 pools), a table
    of 1920 tokens, a 256-token prefill chunk, pools donated."""
    from repro.serving.backends import get_backend
    cfg = get_backend("gather").configure(
        dataclasses.replace(get_config("paper-1.5b"), attn_backend="pallas"),
        phase)
    b, blocks, block, width, chunk = 16, 256, 64, 1920 // 64, 256

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=sharding)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), cfg)))
    pools = jax.tree.map(on_chip, jax.eval_shape(
        lambda: lm.init_paged_cache(cfg, blocks, block)))
    if phase == "decode":
        step = jax.jit(lambda p, pl, bt, sl, t: lm.paged_decode_step(
            p, pl, bt, sl, t, cfg), donate_argnums=(1,))
        args = (i32(b, width), i32(b), i32(b, 1))
    else:
        step = jax.jit(lambda p, pl, bt, t, nn, st: lm.paged_prefill(
            p, pl, bt, t, nn, cfg, start_lens=st, last_only=True),
            donate_argnums=(1,))
        args = (i32(b, width), i32(b, chunk), i32(b), i32(b))
    compiled = step.lower(params, pools, *args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    return cfg, compiled


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_paper_1_5b_step_holds_no_pool_copy(one_chip, phase):
    """Taking the pools as scan inputs and stacking them back held 4.0 GiB
    of temporaries in the decode program, relaying the pools out for the
    kernel layer by layer. The prefill program at 16 x 256 is the largest
    bucket the engine's warmup compiles. The FFN takes the host's path
    here; the attention kernels are the chip's."""
    _, compiled = _paper_1_5b_step(one_chip, phase)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.5 * 2 ** 30, f"temporaries {temp / 2 ** 30:.2f} GiB"


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_paper_1_5b_step_reads_ffn_weights_in_place(one_chip, monkeypatch,
                                                    phase):
    """With the FFN on the chip's path (the TwELL kernels), no value in the
    program is one layer's FFN matrix: the kernels take the stacked
    (L, d, d_ff) / (L, d_ff, d) weights whole. Scanned as layer inputs, each
    layer's three matrices were copied out of the stack (into VMEM) before
    the opaque kernel calls, a ``dynamic-slice`` per matrix per layer."""
    monkeypatch.setattr(ops, "kernel_mode", lambda: "pallas")
    cfg, compiled = _paper_1_5b_step(one_chip, phase)
    text = compiled.as_text()
    for kernel in ("twell_gate_matmul", "twell_fused_ffn"):
        assert kernel in text
    d, f, layers = cfg.d_model, cfg.d_ff, cfg.num_layers
    stacked = [f"bf16[{layers},{d},{f}]", f"bf16[{layers},{f},{d}]"]
    assert all(s in text for s in stacked)
    # a value of one layer's shape, with or without its unit layer axis
    one_layer = re.compile(rf"= bf16\[(1,)?({d},{f}|{f},{d})\]")
    copies = [ln.strip()[:160] for ln in text.splitlines()
              if one_layer.search(ln)]
    assert not copies, copies
