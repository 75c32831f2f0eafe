"""Pallas-TPU causal flash attention with block-level causal skipping.

Beyond-paper perf component: the jnp chunked attention used for CPU lowering
pays ~2x FLOPs on masked future chunks (see models/layers.py); this kernel
skips strictly-future KV blocks entirely (@pl.when on the block index), so
HLO FLOPs match the causal optimum. Online-softmax state (m, l) and the
output accumulator live in VMEM scratch across the KV grid axis.

Grid: (B*H, S/bq, S/bk), KV innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki <= qi)          # causal block skip: future blocks do nothing
    def _compute():
        q = q_ref[0]                                       # (bq, hd)
        k = k_ref[0]                                       # (bk, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
        s = jnp.where(rows >= cols, s, -1e30)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, bq: int = 512, bk: int = 512,
                           interpret: bool = True):
    """q, k, v: (B, S, H, hd) -> (B, S, H, hd), causal."""
    b, s, h, hd = q.shape
    scale = 1.0 / (hd ** 0.5)
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kern = functools.partial(_kernel, bq=bq, bk=bk, scale=scale)
    o = pl.pallas_call(
        kern,
        grid=(b * h, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return o.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
