"""Paged chunk-append attention Pallas kernel (prefill / chunked-prefill /
speculative-verify regime).

Each request appends ``num_new`` tokens at logical positions
``seq_len .. seq_len+num_new-1``; its chunk queries attend the full paged
history *plus* the causal prefix of the chunk itself (the chunk's K/V are
already scattered into the pools by the caller). The kernel takes the whole
(L, N, bs, Hkv*hd) pools that the layer scan carries; the layer index, the
block table and the per-request ``seq_lens``/``num_new`` are scalar-prefetch
operands, so the kernel walks only the pages of one layer that a row's live
span covers — no per-layer slice of the pools is materialised, and pages
past ``seq_len + num_new - 1`` are skipped via ``pl.when``.

Grid: ``(B, W)`` — pages innermost, one online-softmax pass per request
over its live pages. Each step fetches one whole page
``(1, 1, bs, Hkv*hd)`` at ``(layer, table[b, page])`` — every kv head at
once, so the block's last two dims are the pool's own — and loops over the
kv heads inside the kernel, head ``h`` being lanes ``[h*hd, (h+1)*hd)`` of
each token's row. GQA queries ride as a ``G*S`` row axis per kv head (row
j ↦ group j // S, chunk offset j % S), so no ``repeat_kv``
materialization. Rows in the padded tail (``j % S >= num_new``) produce
garbage the engine discards (zeros when the row has no live pages at all —
never NaN).

Numerics mirror ``kernels.ref.paged_attention_extend``: f32 logits/softmax,
-1e30 mask, 1/sqrt(hd) scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_kernel(layer_ref, bt_ref, sl_ref, nn_ref, q_ref, k_ref, v_ref, o_ref,
                  m_s, l_s, acc_s, *, bs, width, chunk, scale, hkv, hd):
    b = pl.program_id(0)
    page = pl.program_id(1)

    @pl.when(page == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -1e30)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    sl = sl_ref[b]
    nn = nn_ref[b]
    # last live logical position for this request; num_new == 0 (padded
    # batch row) makes it negative -> no live pages at all
    last = sl + nn - 1
    live = page * bs <= last

    @pl.when(live)
    def _compute():
        for h in range(hkv):                # one fetched page, every kv head
            q = q_ref[0, h].astype(jnp.float32)                # (G*S, hd)
            lanes = slice(h * hd, (h + 1) * hd)
            k = k_ref[0, 0, :, lanes].astype(jnp.float32)      # (bs, hd)
            v = v_ref[0, 0, :, lanes].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # (G*S, bs)
            # row j is chunk offset j % S of head-group j // S: query
            # position sl + j % S (full history + causal within the chunk)
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            qpos = sl + row % chunk
            kpos = page * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, -1e30)
            m_prev = m_s[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_s[h] = l_s[h] * corr + p.sum(axis=1, keepdims=True)
            acc_s[h] = acc_s[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_s[h] = m_new

    @pl.when(page == width - 1)
    def _finish():
        o_ref[0] = acc_s[...] / jnp.maximum(l_s[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_chunk_attention_pallas(q, kpool, vpool, block_tables, seq_lens,
                                 num_new, layer, *, interpret: bool = False):
    """q: (B, S, H, hd); pools: (L, N, bs, Hkv*hd); block_tables: (B, W);
    seq_lens/num_new: (B,); layer: int32 scalar, the pools' layer to read.
    Returns (B, S, H, hd) in q.dtype."""
    b, s, h, hd = q.shape
    _, _, bs, row = kpool.shape
    hkv = row // hd
    width = block_tables.shape[1]
    g = h // hkv
    scale = 1.0 / (hd ** 0.5)
    # (B, S, H, hd) -> (B, Hkv, G*S, hd): head h = hkv_idx * G + g_idx, and
    # row j = g_idx * S + chunk offset, matching repeat_kv's group broadcast
    qg = q.reshape(b, s, hkv, g, hd).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b, hkv, g * s, hd)
    kernel = functools.partial(_chunk_kernel, bs=bs, width=width,
                               chunk=s, scale=scale, hkv=hkv, hd=hd)

    def kv_map(bi, pi, layer_ref, bt_ref, sl_ref, nn_ref):
        return (layer_ref[0], bt_ref[bi, pi], 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, width),
            in_specs=[
                pl.BlockSpec((1, hkv, g * s, hd),
                             lambda bi, pi, *_: (bi, 0, 0, 0)),
                pl.BlockSpec((1, 1, bs, row), kv_map),
                pl.BlockSpec((1, 1, bs, row), kv_map),
            ],
            out_specs=pl.BlockSpec(
                (1, hkv, g * s, hd),
                lambda bi, pi, *_: (bi, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((hkv, g * s, 1), jnp.float32),
                            pltpu.VMEM((hkv, g * s, 1), jnp.float32),
                            pltpu.VMEM((hkv, g * s, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g * s, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="paged_chunk_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), block_tables, seq_lens,
      num_new, qg, kpool, vpool)

    out = out.reshape(b, hkv, g, s, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, s, h, hd).astype(q.dtype)
