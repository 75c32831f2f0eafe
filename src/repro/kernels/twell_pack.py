"""Pallas-TPU kernel: fused gate matmul + activation + TwELL pack epilogue.

The paper's Algorithm 1 adapted to TPU (DESIGN.md §2): the matmul output
block lives in VMEM; the epilogue replaces the CUDA CTA-scoped atomic counter
with a per-row *prefix count over the lane axis* (a matmul against a
triangular ones matrix, exact in f32) and a per-slot masked lane reduction,
producing tile-locally packed values / global indices / counts in the same
kernel — no second pass over dense data, no extra kernel launch.

Grid: (M/bm, N/T, K/bk), K innermost (TPU sequential minor axis) with a VMEM
f32 scratch accumulator; the epilogue fires on the last K step.

Kernel layout: values/indices are tile-major ``(N/T, M, T/C)`` so every
block's last two dims are ``(bm, T/C)`` with ``T/C`` the whole last axis,
and the counts are one ``(bm, N/T)`` row block per row block, resident
across the tile axis (each tile writes its column). The jitted wrapper
converts to and from the public row-major ``TwellActs`` layout
``(M, N/C)`` / ``(M, N/T)`` (see ``to_tile_major`` / ``from_tile_major``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _act(name: str, x):
    if name == "relu":
        return jnp.maximum(x, 0)
    if name == "relu2":
        return jnp.square(jnp.maximum(x, 0))
    raise ValueError(name)


def to_tile_major(a, nt: int):
    """(M, NT*tc) row-major TwELL values/indices -> kernel (NT, M, tc)."""
    m = a.shape[0]
    return a.reshape(m, nt, -1).transpose(1, 0, 2)


def from_tile_major(a):
    """Kernel (NT, M, tc) -> public row-major (M, NT*tc)."""
    nt, m, tc = a.shape
    return a.transpose(1, 0, 2).reshape(m, nt * tc)


def column(ref, j):
    """Column ``j`` of a (rows, cols) ref as a (rows, 1) f32 value — a
    masked lane reduction, so ``j`` may be a traced grid index."""
    a = ref[...].astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    return jnp.sum(jnp.where(cols == j, a, 0.0), axis=1, keepdims=True)


def _kernel(x_ref, w_ref, vals_ref, idx_ref, nnz_ref, acc_ref, *,
            tile: int, tc: int, act: str):
    k = pl.program_id(2)
    nk = pl.num_programs(2)
    j = pl.program_id(1)        # read outside pl.when (interpret-mode req.)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        h = _act(act, acc_ref[...])                       # (bm, T) f32
        mask = h > 0
        # inclusive running count of non-zeros along the tile: mask @ U with
        # U[t', t] = (t' <= t); 0/1 operands are exact in bf16, sums in f32
        r = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
        tri = (r <= c).astype(jnp.bfloat16)
        pos = jnp.dot(mask.astype(jnp.bfloat16), tri,
                      preferred_element_type=jnp.float32) - 1.0   # (bm, T)
        cols = (jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
                + j * tile).astype(jnp.float32)
        slots = jax.lax.broadcasted_iota(jnp.int32, (h.shape[0], tc), 1)
        vals = jnp.zeros((h.shape[0], tc), jnp.float32)
        gidx = jnp.zeros((h.shape[0], tc), jnp.float32)
        for s in range(tc):                 # slot s holds the s-th non-zero
            hit = mask & (pos == s)
            v = jnp.sum(jnp.where(hit, h, 0.0), axis=1, keepdims=True)
            g = jnp.sum(jnp.where(hit, cols, 0.0), axis=1, keepdims=True)
            vals = jnp.where(slots == s, v, vals)
            gidx = jnp.where(slots == s, g, gidx)
        vals_ref[...] = vals.astype(vals_ref.dtype)
        idx_ref[...] = gidx.astype(jnp.int32)
        count = jnp.sum(mask.astype(jnp.float32), axis=1, keepdims=True)
        tiles = jax.lax.broadcasted_iota(jnp.int32, nnz_ref.shape, 1)
        nnz_ref[...] = jnp.where(tiles == j, count.astype(jnp.int32),
                                 nnz_ref[...])


@functools.partial(jax.jit, static_argnames=("tile", "compression", "act",
                                             "bm", "bk", "interpret"))
def twell_gate_matmul_pallas(x, w, tile: int = 256, compression: int = 8,
                             act: str = "relu", bm: int = 128, bk: int = 512,
                             interpret: bool = True):
    """x: (M, K), w: (K, N) -> (values (M, N/C), indices, nnz (M, N/T)).

    Note: counts are exact even when a tile overflows its T/C slots; values
    beyond the slot budget are dropped per the paper's overflow contract
    (App. B.2.1) — the caller compares nnz against T/C to raise the flag.
    """
    m, kdim = x.shape
    _, n = w.shape
    assert n % tile == 0 and tile % compression == 0
    bm = min(bm, m)
    bk = min(bk, kdim)
    assert m % bm == 0 and kdim % bk == 0
    tc = tile // compression
    nt = n // tile
    grid = (m // bm, nt, kdim // bk)
    kern = functools.partial(_kernel, tile=tile, tc=tc, act=act)
    vals, idx, nnz = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, tile), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((pl.squeezed, bm, tc), lambda i, j, k: (j, i, 0)),
            pl.BlockSpec((pl.squeezed, bm, tc), lambda i, j, k: (j, i, 0)),
            pl.BlockSpec((bm, nt), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nt, m, tc), x.dtype),
            jax.ShapeDtypeStruct((nt, m, tc), jnp.int32),
            jax.ShapeDtypeStruct((m, nt), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, tile), jnp.float32)],
        interpret=interpret,
        name="twell_gate_matmul",
    )(x, w)
    return from_tile_major(vals), from_tile_major(idx), nnz
