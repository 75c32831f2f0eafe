"""Pallas-TPU kernels for the hybrid training format (paper Sec. 3.5).

TPU adaptation (DESIGN.md §2): the CUDA per-row CUDA-core SpMM becomes a
tile-loop kernel — for each (row-block, N-tile) the ELL entries landing in
the tile are scattered VMEM-locally (one-hot over the tile) and the tile's
contribution runs on the MXU; (row-block x tile) pairs containing no index
are skipped with @pl.when. The dense-backup rows take the plain MXU path in
the ops wrapper (the paper's Tensor-Core branch of Algorithm 3).

Kernels here cover the ELL side of:
- hybrid_to_dense:  y = h @ W        (forward down-proj, Eq. 4 grads)
- dense_to_hybrid:  vals = (x @ W)[pattern]   (pattern-only h_u / grad_h)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _h2d_kernel(vals_ref, idx_ref, nnz_ref, live_ref, w_ref, y_ref, *,
                tile: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    local = idx_ref[...] - j * tile                        # (bm, E)
    slots = jax.lax.broadcasted_iota(jnp.int32, local.shape, 1)
    valid = (slots < nnz_ref[...]) & live_ref[...] & \
        (local >= 0) & (local < tile)
    active = jnp.any(valid)

    @pl.when(active)
    def _compute():
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tile), 2)
        hit = (local[:, :, None] == cols) & valid[:, :, None]   # (bm, E, T)
        h_tile = jnp.sum(jnp.where(
            hit, vals_ref[...][:, :, None].astype(jnp.float32), 0.0), axis=1)
        y_ref[...] += jnp.dot(h_tile.astype(w_ref.dtype), w_ref[...],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "bm", "interpret"))
def hybrid_to_dense_pallas(ell_vals, ell_idx, row_nnz, is_sparse, w,
                           tile: int = 256, bm: int = 128,
                           interpret: bool = True):
    """ELL side of Algorithm 3. ell_vals/idx: (M, E); w: (N, K) -> (M, K) f32.
    is_sparse: (M,) bool — rows routed to the dense backup contribute 0."""
    m, e = ell_vals.shape
    n, kdim = w.shape
    assert n % tile == 0
    bm = min(bm, m)
    assert m % bm == 0
    kern = functools.partial(_h2d_kernel, tile=tile)
    y = pl.pallas_call(
        kern,
        grid=(m // bm, n // tile),
        in_specs=[
            pl.BlockSpec((bm, e), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, e), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tile, kdim), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, kdim), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, kdim), jnp.float32),
        interpret=interpret,
        name="hybrid_to_dense",
    )(ell_vals, ell_idx, row_nnz[:, None], is_sparse[:, None], w)
    return y


def _d2h_kernel(x_ref, w_ref, idx_ref, nnz_ref, live_ref, vals_ref, *,
                tile: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.zeros_like(vals_ref)

    local = idx_ref[...] - j * tile                        # (bm, E)
    slots = jax.lax.broadcasted_iota(jnp.int32, local.shape, 1)
    valid = (slots < nnz_ref[...]) & live_ref[...] & \
        (local >= 0) & (local < tile)
    active = jnp.any(valid)

    @pl.when(active)
    def _compute():
        hu = jnp.dot(x_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)   # (bm, T)
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tile), 2)
        hit = (local[:, :, None] == cols) & valid[:, :, None]
        picked = jnp.sum(jnp.where(hit, hu[:, None, :], 0.0), axis=2)
        vals_ref[...] += picked.astype(vals_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "bm", "interpret"))
def dense_to_hybrid_pallas(x, w, ell_idx, row_nnz, is_sparse,
                           tile: int = 256, bm: int = 128,
                           interpret: bool = True):
    """Listing 5 (ELL side): vals[m, e] = x[m, :] . w[:, idx[m, e]].
    x: (M, K), w: (K, N) -> (M, E) f32 on the given pattern."""
    m, kdim = x.shape
    n = w.shape[1]
    e = ell_idx.shape[1]
    assert n % tile == 0
    bm = min(bm, m)
    assert m % bm == 0
    kern = functools.partial(_d2h_kernel, tile=tile)
    vals = pl.pallas_call(
        kern,
        grid=(m // bm, n // tile),
        in_specs=[
            pl.BlockSpec((bm, kdim), lambda i, j: (i, 0)),
            pl.BlockSpec((kdim, tile), lambda i, j: (0, j)),
            pl.BlockSpec((bm, e), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, e), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, e), jnp.float32),
        interpret=interpret,
        name="dense_to_hybrid",
    )(x, w, ell_idx, row_nnz[:, None], is_sparse[:, None])
    return vals
