"""jit'd public wrappers for the Pallas kernels, dispatched by platform.

On a TPU the Pallas kernels run compiled. Elsewhere the execution path falls
back to the numerically-identical jnp references in ``kernels.ref``; the
tests pin the kernels to those references by calling them in Pallas
interpret mode (the paged-attention wrappers take an explicit ``mode``).

The FFN wrappers take the weights stacked over layers — W_g / W_u
``(L, K, N)``, W_d ``(L, N, K)`` — and an int32 ``layer``: the kernels read
that layer's tiles out of the stacked arrays, so the layer scan never
slices them. A caller holding one layer's matrices passes ``w[None]`` and
layer 0.

Under a tensor-parallel mesh the FFN kernels run shard-locally over the
``model`` axis (Pallas calls are opaque to GSPMD): each device packs and
projects its own d_ff slice, whose width the serving backends require to be
a multiple of the TwELL tile, and the partial outputs are summed across the
axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import twell
from repro.distributed.sharding import current_mesh, model_sharded, tp_size
from repro.kernels import ref


def kernel_mode() -> str:
    """``pallas`` on a TPU, ``ref`` everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _tp() -> int:
    mesh = current_mesh()
    return 1 if mesh is None else tp_size(mesh)


def _valid_slots(nnz, tc: int):
    """(M, NT) clipped counts -> (M, NT*tc) bool: slot holds a value."""
    slot = jnp.arange(nnz.shape[1] * tc, dtype=jnp.int32) % tc
    return slot[None, :] < jnp.repeat(nnz, tc, axis=-1)


def _twell_acts(vals, idx, nnz, tile: int, compression: int, n: int):
    tc = tile // compression
    return twell.TwellActs(vals, idx, jnp.minimum(nnz, tc),
                           jnp.any(nnz > tc), tile, compression, n)


# shard specs of the stacked weights: W_g / W_u split their d_ff columns,
# W_d its d_ff rows, over ``model``; the layer index is replicated
_COLS, _ROWS = P(None, None, "model"), P(None, "model", None)


def twell_ffn(x, wg, wu, wd, layer, tile: int, compression: int,
              act: str = "relu"):
    """Gated TwELL FFN (Algorithm 1 pack + Eq. 3 fused up/down) of layer
    ``layer`` of the stacked weights: x (M, K) -> (y (M, K) in x.dtype, the
    gate's TwellActs)."""
    if kernel_mode() == "ref":
        tw = ref.twell_gate_matmul(x, wg[layer], tile, compression, act)
        return ref.twell_fused_ffn(x, tw, wu[layer], wd[layer]), tw
    from repro.kernels.sparse_ffn import twell_fused_ffn_pallas
    from repro.kernels.twell_pack import twell_gate_matmul_pallas

    tp = _tp()

    def local(x, wg, wu, wd, layer):
        vals, idx, nnz = twell_gate_matmul_pallas(
            x, wg, layer, tile, compression, act, interpret=False)
        y = twell_fused_ffn_pallas(vals, idx, nnz, x, wu, wd, layer, tile,
                                   interpret=False)
        return _shard_outputs(tp, y, vals, idx, nnz, tile, compression)

    col = P(None, "model")
    y, vals, idx, nnz = model_sharded(
        local, (P(), _COLS, _COLS, _ROWS, P()), (P(), col, col, col))(
            x, wg, wu, wd, jnp.asarray(layer, jnp.int32))
    return y.astype(x.dtype), _twell_acts(vals, idx, nnz, tile, compression,
                                          wg.shape[2])


def twell_ffn_nongated(x, wu, wd, layer, tile: int, compression: int,
                       act: str = "relu"):
    """Non-gated TwELL FFN (App. C.2) of layer ``layer``: the up projection
    packs, the down projection consumes the pattern. -> (y, TwellActs)."""
    if kernel_mode() == "ref":
        tw = ref.twell_gate_matmul(x, wu[layer], tile, compression, act)
        return ref.twell_down_proj(tw, wd[layer]), tw
    from repro.kernels.sparse_ffn import twell_down_proj_pallas
    from repro.kernels.twell_pack import twell_gate_matmul_pallas

    tp = _tp()

    def local(x, wu, wd, layer):
        vals, idx, nnz = twell_gate_matmul_pallas(
            x, wu, layer, tile, compression, act, interpret=False)
        y = twell_down_proj_pallas(vals, idx, nnz, wd, layer, tile,
                                   interpret=False)
        return _shard_outputs(tp, y, vals, idx, nnz, tile, compression)

    col = P(None, "model")
    y, vals, idx, nnz = model_sharded(
        local, (P(), _COLS, _ROWS, P()), (P(), col, col, col))(
            x, wu, wd, jnp.asarray(layer, jnp.int32))
    return y.astype(wd.dtype), _twell_acts(vals, idx, nnz, tile, compression,
                                           wu.shape[2])


def _shard_outputs(tp: int, y, vals, idx, nnz, tile: int,
                   compression: int):
    """Shard-local TwELL FFN results -> (y summed over ``model``, vals,
    global column indices, counts). Invalid slots keep index 0, as in
    ``twell.pack``. The identity at tp == 1."""
    if tp <= 1:
        return y, vals, idx, nnz
    tc = tile // compression
    off = jax.lax.axis_index("model") * (nnz.shape[1] * tile)
    idx = jnp.where(_valid_slots(jnp.minimum(nnz, tc), tc), idx + off, 0)
    return jax.lax.psum(y, "model"), vals, idx, nnz


def tile_skip_ffn(x, wg, wu, wd, layer, tile: int, act: str = "relu",
                  threshold: float = 0.0):
    """Gated FFN of layer ``layer`` of the stacked weights with (row x
    hidden-tile) block skipping -> (y in x.dtype, dense h)."""
    # Thresholded (lossy) tile dropping runs through the reference math for
    # now: the Pallas harvest kernel's skip predicate is `tile all-zero`;
    # folding the |hg|<=threshold predicate into it is TPU follow-up work.
    if kernel_mode() == "ref" or threshold > 0.0:
        return ref.tile_skip_ffn(x, wg[layer], wu[layer], wd[layer], tile,
                                 act, threshold)
    from repro.kernels.sparse_ffn import tile_skip_ffn_pallas

    tp = _tp()

    def local(x, wg, wu, wd, layer):
        y, h = tile_skip_ffn_pallas(x, wg, wu, wd, layer, tile, act,
                                    interpret=False)
        return (jax.lax.psum(y, "model") if tp > 1 else y), h

    y, h = model_sharded(local, (P(), _COLS, _COLS, _ROWS, P()),
                         (P(), P(None, "model")))(
                             x, wg, wu, wd, jnp.asarray(layer, jnp.int32))
    return y.astype(x.dtype), h


def _layer_pool(pool, layer, hd: int):
    """Layer ``layer`` of (L, N, bs, Hkv*hd) pools as the oracles' (N, bs,
    Hkv, hd) page pool."""
    return pool[layer].reshape(*pool.shape[1:3], -1, hd)


def paged_attention_decode(q, kpool, vpool, block_tables, seq_lens, layer,
                           mode: str):
    """Paged decode attention read (scatter happens in the caller) of layer
    ``layer`` of the (L, N, bs, Hkv*hd) pools; ``mode`` is the attention
    backend's kernel mode (ref | pallas | interpret)."""
    if mode == "ref":
        hd = q.shape[-1]
        return ref.paged_attention_decode(
            q, _layer_pool(kpool, layer, hd), _layer_pool(vpool, layer, hd),
            block_tables, seq_lens)
    from repro.kernels.paged_decode_attention import (
        paged_decode_attention_pallas)
    return paged_decode_attention_pallas(
        q, kpool, vpool, block_tables, seq_lens, layer,
        interpret=(mode == "interpret"))


def paged_attention_extend(q, kpool, vpool, block_tables, seq_lens, num_new,
                           layer, mode: str):
    """Chunk-append attention read (prefill / chunked prefill / verify) of
    layer ``layer`` of the (L, N, bs, Hkv*hd) pools."""
    if mode == "ref":
        hd = q.shape[-1]
        return ref.paged_attention_extend(
            q, _layer_pool(kpool, layer, hd), _layer_pool(vpool, layer, hd),
            block_tables, seq_lens, num_new)
    from repro.kernels.paged_chunk_attention import (
        paged_chunk_attention_pallas)
    return paged_chunk_attention_pallas(
        q, kpool, vpool, block_tables, seq_lens, num_new, layer,
        interpret=(mode == "interpret"))
