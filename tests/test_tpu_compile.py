"""The five serving kernels compile for a described TPU v5e at paper-0.5b
widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
topology that is described rather than attached. That catches what Pallas
interpret mode cannot — block shapes the Mosaic tiling refuses, kernels over
the VMEM budget — at a decode-sized M (the engine's batch buckets) and a
prefill-sized M (prefill chunk 64 x batch 8).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file. The persistent compilation cache
is off around the compiles (an entry compiled for a described chip cannot
be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
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


@pytest.mark.parametrize("m", FFN_M)
def test_twell_gate_matmul_compiles(one_chip, m):
    _compile(one_chip,
             lambda x, w: twell_gate_matmul_pallas(x, w, T, C, "relu",
                                                   interpret=False),
             ((m, K), BF16), ((K, N), BF16))


@pytest.mark.parametrize("m", FFN_M)
def test_twell_fused_ffn_compiles(one_chip, m):
    _compile(one_chip,
             lambda v, i, n, x, wu, wd: twell_fused_ffn_pallas(
                 v, i, n, x, wu, wd, T, interpret=False),
             *_packed(m), ((m, K), BF16), ((K, N), BF16), ((N, K), BF16))


@pytest.mark.parametrize("m", FFN_M)
def test_twell_down_proj_compiles(one_chip, m):
    _compile(one_chip,
             lambda v, i, n, wd: twell_down_proj_pallas(v, i, n, wd, T,
                                                        interpret=False),
             *_packed(m), ((N, K), BF16))


@pytest.mark.parametrize("m", FFN_M)
def test_tile_skip_ffn_compiles(one_chip, m):
    _compile(one_chip,
             lambda x, wg, wu, wd: tile_skip_ffn_pallas(x, wg, wu, wd, T,
                                                        "relu",
                                                        interpret=False),
             ((m, K), BF16), ((K, N), BF16), ((K, N), BF16), ((N, K), BF16))


def _pools(b):
    pool = ((1 + b * WIDTH, BLOCK, HKV, HD), BF16)
    return [pool, pool, ((b, WIDTH), I32), ((b,), I32)]


@pytest.mark.parametrize("b", ATTN_B)
def test_paged_decode_attention_compiles(one_chip, b):
    _compile(one_chip,
             lambda q, kp, vp, bt, sl: paged_decode_attention_pallas(
                 q, kp, vp, bt, sl, interpret=False),
             ((b, 1, H, HD), BF16), *_pools(b))


@pytest.mark.parametrize("b", ATTN_B)
def test_paged_chunk_attention_compiles(one_chip, b):
    _compile(one_chip,
             lambda q, kp, vp, bt, sl, nn: paged_chunk_attention_pallas(
                 q, kp, vp, bt, sl, nn, interpret=False),
             ((b, 64, H, HD), BF16), *_pools(b), ((b,), I32))
