"""Pallas-TPU kernel: tile-skip fused up+down projection from TwELL.

The TPU harvest of the paper's Eq. 3 (DESIGN.md §2): per-(row-block x
hidden-tile) activity comes free from the TwELL counts; dead tiles skip the
W_u / W_d MXU work entirely (@pl.when), and h_u is materialized only in VMEM
(never to HBM) exactly as the CUDA kernel keeps it in registers. The W DMAs
of dead tiles still run.

Grid: (M/bm, N/T); full-K blocks (VMEM budget: (bm + 2T) * K * bytes). The
packed operands arrive in the kernel layout of ``kernels/twell_pack.py``
(tile-major values/indices, a (bm, N/T) count row block); the jitted
wrappers take the public ``TwellActs`` layout and convert.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.twell_pack import column, to_tile_major


def _unpack_tile(vals_ref, idx_ref, nnz_ref, j, tile: int):
    """Scatter tile ``j`` of a packed (bm, tc) TwELL block back to a dense
    (bm, T) f32 block in VMEM, one slot at a time (a slot's column index
    broadcast against the tile's lane iota); slots at or past the row's count
    contribute zero."""
    count = column(nnz_ref, j)                             # (bm, 1) f32
    local = idx_ref[...] - j * tile                        # (bm, tc)
    slots = jax.lax.broadcasted_iota(jnp.int32, local.shape, 1)
    vals = jnp.where(slots.astype(jnp.float32) < count,
                     vals_ref[...].astype(jnp.float32), 0.0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (local.shape[0], tile), 1)
    dense = jnp.zeros((local.shape[0], tile), jnp.float32)
    for s in range(local.shape[1]):
        dense += jnp.where(cols == local[:, s:s + 1], vals[:, s:s + 1], 0.0)
    return dense


def _kernel(vals_ref, idx_ref, nnz_ref, x_ref, wu_ref, wd_ref, y_ref, *,
            tile: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(jnp.max(column(nnz_ref, j)) > 0)
    def _compute():
        g = _unpack_tile(vals_ref, idx_ref, nnz_ref, j, tile)   # (bm, T)
        hu = jnp.dot(x_ref[...], wu_ref[...],
                     preferred_element_type=jnp.float32)   # (bm, T)
        h = (hu * g).astype(x_ref.dtype)
        y_ref[...] += jnp.dot(h, wd_ref[...],
                              preferred_element_type=jnp.float32)


def _packed_specs(bm: int, tc: int, nt: int):
    """BlockSpecs of the kernel-layout TwELL operands: tile-major
    values/indices (NT, M, tc) and the (bm, NT) count row block."""
    return [pl.BlockSpec((pl.squeezed, bm, tc), lambda i, j: (j, i, 0)),
            pl.BlockSpec((pl.squeezed, bm, tc), lambda i, j: (j, i, 0)),
            pl.BlockSpec((bm, nt), lambda i, j: (i, 0))]


@functools.partial(jax.jit, static_argnames=("tile", "bm", "interpret"))
def twell_fused_ffn_pallas(vals, idx, nnz, x, wu, wd, tile: int = 256,
                           bm: int = 128, interpret: bool = True):
    """vals/idx: (M, N/C), nnz: (M, N/T), x: (M, K), wu: (K, N), wd: (N, K)
    -> y: (M, K) f32 (cast by the caller)."""
    m, kdim = x.shape
    n = wu.shape[1]
    nt = n // tile
    tc = vals.shape[1] // nt
    bm = min(bm, m)
    assert m % bm == 0
    kern = functools.partial(_kernel, tile=tile)
    return pl.pallas_call(
        kern,
        grid=(m // bm, nt),
        in_specs=_packed_specs(bm, tc, nt) + [
            pl.BlockSpec((bm, kdim), lambda i, j: (i, 0)),
            pl.BlockSpec((kdim, tile), lambda i, j: (0, j)),
            pl.BlockSpec((tile, kdim), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, kdim), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, kdim), jnp.float32),
        interpret=interpret,
        name="twell_fused_ffn",
    )(to_tile_major(vals, nt), to_tile_major(idx, nt), nnz, x, wu, wd)


def _down_kernel(vals_ref, idx_ref, nnz_ref, wd_ref, y_ref, *, tile: int):
    """Non-gated variant (paper App. C.2, Listing 3): y = unpack(h) @ W_d
    with tile skipping — the up projection produced the TwELL pattern."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(jnp.max(column(nnz_ref, j)) > 0)
    def _compute():
        h = _unpack_tile(vals_ref, idx_ref, nnz_ref, j, tile)
        y_ref[...] += jnp.dot(h.astype(wd_ref.dtype), wd_ref[...],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "bm", "interpret"))
def twell_down_proj_pallas(vals, idx, nnz, wd, tile: int = 256,
                           bm: int = 128, interpret: bool = True):
    m = vals.shape[0]
    n, kdim = wd.shape
    nt = n // tile
    tc = vals.shape[1] // nt
    bm = min(bm, m)
    assert m % bm == 0
    kern = functools.partial(_down_kernel, tile=tile)
    return pl.pallas_call(
        kern,
        grid=(m // bm, nt),
        in_specs=_packed_specs(bm, tc, nt) + [
            pl.BlockSpec((tile, kdim), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, kdim), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, kdim), jnp.float32),
        interpret=interpret,
        name="twell_down_proj",
    )(to_tile_major(vals, nt), to_tile_major(idx, nt), nnz, wd)


def _kernel_gated_dense_gate(x_ref, wg_ref, wu_ref, wd_ref, y_ref, h_ref, *,
                             act: str):
    """Single-kernel gated FFN with tile skipping decided *inside* (used when
    the gate matmul and the fused projections are fused end-to-end)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    g = jnp.dot(x_ref[...], wg_ref[...], preferred_element_type=jnp.float32)
    if act == "relu":
        g = jnp.maximum(g, 0)
    else:
        g = jnp.square(jnp.maximum(g, 0))
    active = jnp.any(g > 0)
    h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(active)
    def _compute():
        hu = jnp.dot(x_ref[...], wu_ref[...],
                     preferred_element_type=jnp.float32)
        h = hu * g
        y_ref[...] += jnp.dot(h.astype(x_ref.dtype), wd_ref[...],
                              preferred_element_type=jnp.float32)
        h_ref[...] = h.astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "act", "bm", "interpret"))
def tile_skip_ffn_pallas(x, wg, wu, wd, tile: int = 256, act: str = "relu",
                         bm: int = 128, interpret: bool = True):
    """End-to-end gated FFN with in-kernel tile skip; also emits dense h for
    the sparsity statistics path. x: (M, K) -> (y (M, K) f32, h (M, N))."""
    m, kdim = x.shape
    n = wu.shape[1]
    nt = n // tile
    bm = min(bm, m)
    assert m % bm == 0 and n % tile == 0
    kern = functools.partial(_kernel_gated_dense_gate, act=act)
    y, h = pl.pallas_call(
        kern,
        grid=(m // bm, nt),
        in_specs=[
            pl.BlockSpec((bm, kdim), lambda i, j: (i, 0)),
            pl.BlockSpec((kdim, tile), lambda i, j: (0, j)),
            pl.BlockSpec((kdim, tile), lambda i, j: (0, j)),
            pl.BlockSpec((tile, kdim), lambda i, j: (j, 0)),
        ],
        out_specs=[pl.BlockSpec((bm, kdim), lambda i, j: (i, 0)),
                   pl.BlockSpec((bm, tile), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((m, kdim), jnp.float32),
                   jax.ShapeDtypeStruct((m, n), x.dtype)],
        interpret=interpret,
        name="tile_skip_ffn",
    )(x, wg, wu, wd)
    return y, h
