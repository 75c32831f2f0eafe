"""Fused paged decode-attention Pallas kernel (flash-decoding style).

One query token per request attends its paged KV history *in place*: the
kernel takes the whole (L, N, bs, Hkv*hd) pools that the layer scan
carries, with the layer index and the block table as scalar-prefetch
operands, so each grid step DMAs exactly one live page of one layer out of
the pool — no per-layer slice of the pools, no (B, W*block_size, H, hd)
gather and no ``repeat_kv`` materialization (GQA is a head-group axis on
the query side).

Grid: ``(B, num_splits, pages_per_split)`` — pages innermost so the
online-softmax scratch carries across a split's pages; splits are merged in
plain jnp afterwards (second-stage reduce). Each step fetches one whole page
``(1, 1, bs, Hkv*hd)`` at ``(layer, table[b, page])`` — every kv head at
once, so the block's last two dims are the pool's own — and loops over the
kv heads inside the kernel, head ``h`` being lanes ``[h*hd, (h+1)*hd)`` of
each token's row. Pages at or past a request's live span
(``page * block_size > seq_len``) are skipped via ``pl.when``: compute per
step is proportional to the request's actual ``seq_len``, not the padded
table width.

Numerics mirror ``kernels.ref.paged_attention_decode``: f32 logits/softmax,
-1e30 mask, 1/sqrt(hd) scale. The null block (id 0) backs padded batch rows
and ``write_valid``-routed speculative writes; padded rows (seq_len 0, all
null table) read one page of the null block and produce garbage the engine
discards — never NaN, because page 0 is always live.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_kernel(layer_ref, bt_ref, sl_ref, q_ref, k_ref, v_ref,
                   o_ref, m_ref, l_ref,
                   m_s, l_s, acc_s, *, bs, width, pages, scale, hkv, hd):
    b = pl.program_id(0)
    pi = pl.program_id(2)
    page = pl.program_id(1) * pages + pi

    @pl.when(pi == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -1e30)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    sl = sl_ref[b]
    live = jnp.logical_and(page * bs <= sl, page < width)

    @pl.when(live)
    def _compute():
        for h in range(hkv):                # one fetched page, every kv head
            q = q_ref[0, h].astype(jnp.float32)                # (G, hd)
            lanes = slice(h * hd, (h + 1) * hd)
            k = k_ref[0, 0, :, lanes].astype(jnp.float32)      # (bs, hd)
            v = v_ref[0, 0, :, lanes].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # (G, bs)
            kpos = page * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= sl, s, -1e30)
            m_prev = m_s[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_s[h] = l_s[h] * corr + p.sum(axis=1, keepdims=True)
            acc_s[h] = acc_s[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_s[h] = m_new

    @pl.when(pi == pages - 1)
    def _finish():
        o_ref[0, 0] = acc_s[...]
        m_ref[0, 0] = m_s[...]
        l_ref[0, 0] = l_s[...]


def _pick_splits(width: int, num_splits: int) -> int:
    return max(1, min(num_splits, width))


@functools.partial(jax.jit, static_argnames=("num_splits", "interpret"))
def paged_decode_attention_pallas(q, kpool, vpool, block_tables, seq_lens,
                                  layer, *, num_splits: int = 4,
                                  interpret: bool = False):
    """q: (B, 1, H, hd); pools: (L, N, bs, Hkv*hd); block_tables: (B, W);
    seq_lens: (B,); layer: int32 scalar, the pools' layer to read. Returns
    (B, 1, H, hd) in q.dtype."""
    b, _, h, hd = q.shape
    _, _, bs, row = kpool.shape
    hkv = row // hd
    width = block_tables.shape[1]
    g = h // hkv
    ns = _pick_splits(width, num_splits)
    pages = -(-width // ns)
    scale = 1.0 / (hd ** 0.5)
    # head h = hkv_idx * G + g: reshape matches repeat_kv's group broadcast
    qg = q.reshape(b, hkv, g, hd)
    kernel = functools.partial(_decode_kernel, bs=bs, width=width,
                               pages=pages, scale=scale, hkv=hkv, hd=hd)

    def kv_map(bi, si, pi, layer_ref, bt_ref, sl_ref):
        page = jnp.minimum(si * pages + pi, width - 1)
        return (layer_ref[0], bt_ref[bi, page], 0, 0)

    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, ns, pages),
            in_specs=[
                pl.BlockSpec((1, hkv, g, hd),
                             lambda bi, si, pi, *_: (bi, 0, 0, 0)),
                pl.BlockSpec((1, 1, bs, row), kv_map),
                pl.BlockSpec((1, 1, bs, row), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hkv, g, hd),
                             lambda bi, si, pi, *_: (bi, si, 0, 0, 0)),
                pl.BlockSpec((1, 1, hkv, g, 1),
                             lambda bi, si, pi, *_: (bi, si, 0, 0, 0)),
                pl.BlockSpec((1, 1, hkv, g, 1),
                             lambda bi, si, pi, *_: (bi, si, 0, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((hkv, g, 1), jnp.float32),
                            pltpu.VMEM((hkv, g, 1), jnp.float32),
                            pltpu.VMEM((hkv, g, hd), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, ns, hkv, g, hd), jnp.float32),
                   jax.ShapeDtypeStruct((b, ns, hkv, g, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, ns, hkv, g, 1), jnp.float32)],
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), block_tables, seq_lens,
      qg, kpool, vpool)

    # second-stage reduce: merge per-split partial softmaxes. Dead splits
    # (every page skipped) carry m = -1e30, l = 0 and contribute exactly 0.
    m_max = m.max(axis=1, keepdims=True)
    alpha = jnp.exp(m - m_max)
    l_tot = (alpha * l).sum(axis=1)
    out = (alpha * o).sum(axis=1) / jnp.maximum(l_tot, 1e-30)
    return out.reshape(b, 1, h, hd).astype(q.dtype)
