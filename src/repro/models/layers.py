"""Model primitives: norms, RoPE, attention variants, embeddings.

Pure functional modules: ``*_init(key, ...) -> params`` and stateless apply
functions. All attention variants share one entry point so every architecture
family (full-causal / SWA / chunked-local / cross / decode) uses the same
code path, and so the dry-run lowers a single, auditable attention HLO.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act

INIT_STD = 0.02


def _norm_f32(fn):
    @functools.wraps(fn)
    def wrapped(x, *a, **k):
        return fn(x.astype(jnp.float32), *a, **k).astype(x.dtype)
    return wrapped


@_norm_f32
def rmsnorm(x, scale=None, eps: float = 1e-6):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y if scale is None else y * scale.astype(jnp.float32)


@_norm_f32
def layernorm(x, scale=None, bias=None, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y


def norm_init(kind: str, d: int, dtype) -> Dict[str, jax.Array]:
    if kind == "nonparametric_ln":           # olmo: no learned affine
        return {}
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    raise ValueError(kind)


def norm_apply(kind: str, params: Dict, x: jax.Array) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    if kind == "layernorm":
        return layernorm(x, params["scale"], params["bias"])
    if kind == "nonparametric_ln":
        return layernorm(x)
    raise ValueError(kind)


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * freqs          # (..., S, hd/2)
    ang = ang[..., None, :]                                         # head axis
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = xf1 * cos - xf2 * sin
    o2 = xf2 * cos + xf1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------------- #

def attn_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
              dtype) -> Dict[str, jax.Array]:
    ks = jax.random.split(key, 4)
    r = lambda k, s: (INIT_STD * jax.random.normal(k, s)).astype(dtype)
    return {
        "wq": r(ks[0], (d_model, n_heads * head_dim)),
        "wk": r(ks[1], (d_model, n_kv * head_dim)),
        "wv": r(ks[2], (d_model, n_kv * head_dim)),
        "wo": r(ks[3], (n_heads * head_dim, d_model)),
    }


def repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, Hkv, hd) -> (B, S, H, hd) by group broadcast."""
    b, s, hkv, hd = k.shape
    if hkv == n_heads:
        return k
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (b, s, hkv, n_heads // hkv, hd)).reshape(b, s, n_heads, hd)


def _sdpa(q, k, v, mask, scale):
    """Plain masked attention on (B, Sq, H, hd) x (B, Sk, H, hd)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _chunked_causal(q, k, v, scale, q_chunk: int, kv_chunk: int):
    """Flash-style online-softmax causal attention, O(q_chunk*kv_chunk) memory.

    Query chunks are scanned; for each, KV chunks are scanned with a causal
    mask. Chunk-pairs strictly in the future contribute nothing but are still
    computed (masked) — the FLOP waste is removed by the banded variants below
    and by the Pallas flash kernel on TPU (kernels/flash_attention.py).
    """
    b, s, h, hd = q.shape
    nq, nk = s // q_chunk, s // kv_chunk
    qc = q.reshape(b, nq, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    kc = k.reshape(b, nk, kv_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nk, kv_chunk, h, hd).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_q):
        qi, qq = qi_q

        def kv_step(carry, ki_kv):
            m, l, acc = carry
            ki, kk, vv = ki_kv
            logit = jnp.einsum("bqhd,bkhd->bhqk", qq, kk).astype(jnp.float32) * scale
            qpos = qi * q_chunk + jnp.arange(q_chunk)
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            mask = qpos[:, None] >= kpos[None, :]
            logit = jnp.where(mask[None, None], logit, -1e30)
            m_new = jnp.maximum(m, logit.max(-1))
            p = jnp.exp(logit - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qq.dtype), vv).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, h, q_chunk), -jnp.inf, jnp.float32),
                jnp.zeros((b, h, q_chunk), jnp.float32),
                jnp.zeros((b, h, q_chunk, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init, (jnp.arange(nk), kc, vc))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return None, out.transpose(0, 2, 1, 3)                   # (b, qc, h, hd)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qc))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


def _banded(q, k, v, scale, band_chunk: int, lookback: int,
            window: int = 0):
    """Exact banded causal attention: query chunk i attends KV chunks
    [i-lookback, i]. lookback=0 => chunked-local (llama4); lookback=1 with
    band_chunk=W and a window mask => sliding-window (mixtral).
    FLOPs O(S * (lookback+1)*C) — sub-quadratic.
    """
    b, s, h, hd = q.shape
    c = band_chunk
    nq = s // c
    qc = q.reshape(b, nq, c, h, hd)
    pad = lookback * c
    kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    span = (lookback + 1) * c
    # each query chunk's KV band: chunks [i-lookback, i] of the padded seq
    nb = kp.shape[1] // c
    kb = kp.reshape(b, nb, c, h, hd)
    idx = jnp.arange(nq)[:, None] + jnp.arange(lookback + 1)[None, :]
    kb = kb[:, idx].reshape(b, nq, span, h, hd)
    vb = vp.reshape(b, nb, c, h, hd)[:, idx].reshape(b, nq, span, h, hd)
    qpos = (jnp.arange(nq) * c)[:, None] + jnp.arange(c)[None, :]          # global q pos
    kpos = (jnp.arange(nq) * c)[:, None] + jnp.arange(span)[None, :] - pad  # global k pos
    logits = jnp.einsum("bnqhd,bnkhd->bnhqk", qc, kb).astype(jnp.float32) * scale
    mask = (qpos[:, :, None] >= kpos[:, None, :]) & (kpos[:, None, :] >= 0)
    if window:
        mask = mask & (qpos[:, :, None] - kpos[:, None, :] < window)
    logits = jnp.where(mask[:, None][None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", probs, vb)
    return out.reshape(b, s, h, hd)


def _paged_attention(q, k, v, cache, cfg, n_heads, scale):
    """Paged-KV attention (serving engine).

    cache = {"kpool", "vpool", "layer", "block_tables", "seq_lens"}:
      kpool/vpool:   (L, num_blocks, block_size, Hkv*hd) page pools of
                     EVERY layer, one row of every kv head's K (V) per
                     token — the layer scan carries them whole and this
                     call updates them in place
      layer:         int32 scalar, the layer this call reads and writes
                     (K/V land at ``[layer, blk, off]``)
      block_tables:  (B, W) int32 physical block ids (0 = reserved null block)
      seq_lens:      (B,) int32 tokens already cached per request

    q/k/v arrive roped with per-request absolute positions. Two regimes:
      decode (S == 1): scatter the new K/V at logical position ``seq_len``
        into the request's page, then read over kpos <= seq_len. Optional
        cache["write_valid"] (B,) bool routes a row's write to the null
        block (speculative draft steps past a request's budget draft
        nothing).
      chunk-append (S > 1, cache has "num_new"): the chunk *appends to
        existing history* — one path serves prefill (history empty),
        chunked/prefix-cached prefill (history = cached prefix), and
        speculative verify (history = committed tokens). Row positions are
        seq_len..seq_len+num_new-1 (num_new (B,) valid chunk lengths; the
        padded tail routes to the null block); K/V scatter there, then the
        read masks kpos <= seq_len + j (full history + causal within the
        chunk).
    Padded batch rows carry an all-null table, so their writes land in the
    null block and their outputs are garbage the engine discards.

    The scatter (including write_valid / padded-tail null-block routing) is
    shared plain JAX; ``cfg.attn_backend`` selects how the scattered pools
    are *read*. "ref" is the inline gather-pages SDPA below (the numerics
    reference); "pallas"/"interpret" dispatch to the fused paged-attention
    kernels through ``repro.serving.attention`` (layer index and block
    tables consumed in-kernel — only live pages of the layer are touched,
    no repeat_kv materialization). Neither reads a per-layer slice of the
    pools, so nothing pool-sized is copied.
    """
    kpool, vpool, layer = cache["kpool"], cache["vpool"], cache["layer"]
    bt, sl = cache["block_tables"], cache["seq_lens"]
    b, s, hkv, hd = k.shape
    bs_blk = kpool.shape[2]
    backend = getattr(cfg, "attn_backend", "ref")
    # tensor-parallel serving: per-head tensors split over the model axis,
    # matching the pool's kv-head sharding, so scatter/gather and the SDPA
    # run shard-local and only the wo projection all-reduces. No-ops (and
    # bit-identical) without a mesh or when heads don't divide.
    q = shard_act(q, None, None, "model", None)
    k = shard_act(k, None, None, "model", None)
    v = shard_act(v, None, None, "model", None)
    if s == 1:                                     # decode: one token per row
        blk = jnp.take_along_axis(bt, (sl // bs_blk)[:, None], axis=1)[:, 0]
        off = sl % bs_blk
        if "write_valid" in cache:
            wv = cache["write_valid"]
            blk = jnp.where(wv, blk, 0)
            off = jnp.where(wv, off, 0)
        kpool = kpool.at[layer, blk, off].set(k[:, 0].reshape(b, -1))
        vpool = vpool.at[layer, blk, off].set(v[:, 0].reshape(b, -1))
        if backend != "ref":
            out = _attn_backend(backend).forward_decode(
                q, kpool, vpool, bt, sl, layer)
        else:
            kf = shard_act(repeat_kv(kpool[layer, bt].reshape(b, -1, hkv, hd),
                                     n_heads), None, None, "model", None)
            vf = shard_act(repeat_kv(vpool[layer, bt].reshape(b, -1, hkv, hd),
                                     n_heads), None, None, "model", None)
            kpos = jnp.arange(kf.shape[1])
            mask = (kpos[None, :] <= sl[:, None])[:, None, None, :]
            out = _sdpa(q, kf, vf, mask, scale)
        out = shard_act(out, None, None, "model", None)
    else:                                          # chunk-append w/ history
        idx = jnp.arange(s)
        valid = idx[None, :] < cache["num_new"][:, None]           # (B, S)
        pos = sl[:, None] + idx[None, :]                           # (B, S)
        slot = jnp.clip(pos // bs_blk, 0, bt.shape[1] - 1)
        blk = jnp.where(valid, jnp.take_along_axis(bt, slot, axis=1), 0)
        off = jnp.where(valid, pos % bs_blk, 0)
        kpool = kpool.at[layer, blk.reshape(-1), off.reshape(-1)].set(
            k.reshape(b * s, -1))
        vpool = vpool.at[layer, blk.reshape(-1), off.reshape(-1)].set(
            v.reshape(b * s, -1))
        if backend != "ref":
            out = _attn_backend(backend).forward_extend(
                q, kpool, vpool, bt, sl, cache["num_new"], layer)
        else:
            kf = shard_act(repeat_kv(kpool[layer, bt].reshape(b, -1, hkv, hd),
                                     n_heads), None, None, "model", None)
            vf = shard_act(repeat_kv(vpool[layer, bt].reshape(b, -1, hkv, hd),
                                     n_heads), None, None, "model", None)
            kpos = jnp.arange(kf.shape[1])
            mask = (kpos[None, None, :] <= pos[:, :, None])[:, None]
            out = _sdpa(q, kf, vf, mask, scale)
        out = shard_act(out, None, None, "model", None)
    out_cache = dict(cache)
    out_cache.update(kpool=kpool, vpool=vpool)
    return out, out_cache


def _attn_backend(name: str):
    """Resolve a non-ref attention backend lazily: importing
    ``repro.serving`` at module scope would cycle back into this module
    (serving.engine -> models.lm -> models.layers), so the lookup happens
    at trace time, when both modules are fully loaded."""
    from repro.serving.attention import get_attn_backend
    return get_attn_backend(name)


def attention(params: Dict, x: jax.Array, cfg, *, positions: jax.Array,
              kind: str = "causal", kv_x: Optional[jax.Array] = None,
              cache: Optional[Dict] = None,
              q_chunk: int = 1024, kv_chunk: int = 1024
              ) -> Tuple[jax.Array, Optional[Dict]]:
    """Unified attention.

    kind: causal | swa | local_chunk | cross | bidir
    cache: decode mode — {"k","v","pos"}; x is (B, 1, D). Returns updated
    cache. A cache carrying "kpool" selects the paged serving path instead.
    """
    b, s, d = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    scale = 1.0 / (hd ** 0.5)
    q = (x @ params["wq"]).reshape(b, s, h, hd)
    if cache is not None and kind == "cross" and "xk" in cache:
        k = v = None                       # cross K/V live in the cache
    else:
        src = x if kv_x is None else kv_x
        sk = src.shape[1]
        k = (src @ params["wk"]).reshape(b, sk, hkv, hd)
        v = (src @ params["wv"]).reshape(b, sk, hkv, hd)
    if kind != "cross":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    # band degeneration: when the window / local chunk covers the whole
    # sequence, SWA and chunked-local ARE full causal attention — route to
    # the optimized causal paths (avoids the padded lookback chunk: -45%
    # banded logits bytes at S == window, §Perf extra)
    if cache is None:
        if kind == "swa" and cfg.window >= s:
            kind = "causal"
        if kind == "local_chunk" and cfg.attn_chunk >= s:
            kind = "causal"

    new_cache = None
    if cache is not None and "kpool" in cache:
        out, new_cache = _paged_attention(q, k, v, cache, cfg, h, scale)
    elif cache is not None and kind != "cross":
        # decode: append to (ring) cache. cache["k"]: (B, S_cache, Hkv, hd)
        pos = cache["pos"]                                        # scalar int
        s_cache = cache["k"].shape[1]
        slot = pos % s_cache if kind == "swa" else pos
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        new_cache = {"k": ck, "v": cv, "pos": pos + 1}
        kf = repeat_kv(ck, h)
        vf = repeat_kv(cv, h)
        kpos = jnp.arange(s_cache)
        if kind == "swa":
            valid = (kpos < pos + 1) & (kpos > pos - s_cache)     # ring validity
            # ring buffer stores absolute positions implicitly; mask invalid
            mask = valid[None, None, None, :]
        else:
            mask = (kpos <= pos)[None, None, None, :]
        out = _sdpa(q, kf, vf, mask, scale)
    elif kind == "cross":
        if cache is not None and "xk" in cache:
            # decode: cross K/V precomputed once into the cache
            out = _sdpa(q, repeat_kv(cache["xk"], h), repeat_kv(cache["xv"], h),
                        None, scale)
            new_cache = cache
        else:
            out = _sdpa(q, repeat_kv(k, h), repeat_kv(v, h), None, scale)
    elif kind == "bidir":
        out = _chunked_bidir(q, repeat_kv(k, h), repeat_kv(v, h), scale,
                             q_chunk, kv_chunk) if s > 2048 else \
            _sdpa(q, repeat_kv(k, h), repeat_kv(v, h), None, scale)
    elif kind == "local_chunk":
        out = _banded(q, repeat_kv(k, h), repeat_kv(v, h), scale,
                      band_chunk=min(cfg.attn_chunk, s), lookback=0)
    elif kind == "swa":
        w = min(cfg.window, s)
        out = _banded(q, repeat_kv(k, h), repeat_kv(v, h), scale,
                      band_chunk=w, lookback=1, window=w)
    else:  # full causal
        # <=2k: one masked SDPA. 2k-4k: unrolled exact-causal (query chunks
        # against growing KV prefixes — no masked-FLOP waste, ~40% fewer
        # logits bytes than chunked scans; §Perf C iters 1+3). >4k: the
        # O(S^2) buffers force the online-softmax chunked path (the Pallas
        # flash kernel replaces it on real TPU).
        if s > 4096:
            out = _chunked_causal(q, repeat_kv(k, h), repeat_kv(v, h), scale,
                                  min(q_chunk, s), min(kv_chunk, s))
        elif s > 2048:
            out = _causal_unrolled(q, repeat_kv(k, h), repeat_kv(v, h),
                                   scale, min(q_chunk, s))
        else:
            qpos = jnp.arange(s)
            mask = (qpos[:, None] >= qpos[None, :])[None, None]
            out = _sdpa(q, repeat_kv(k, h), repeat_kv(v, h), mask, scale)
    y = out.reshape(b, s, h * hd) @ params["wo"]
    return y, new_cache


def _causal_unrolled(q, k, v, scale, q_chunk: int):
    """Exact causal attention as a python-unrolled loop over query chunks,
    each attending its *static-length* KV prefix — causal-optimal FLOPs
    (no masked future work except the diagonal chunk's triangle)."""
    b, s, h, hd = q.shape
    nq = s // q_chunk
    outs = []
    for i in range(nq):
        qi = q[:, i * q_chunk:(i + 1) * q_chunk]
        klen = (i + 1) * q_chunk
        ki, vi = k[:, :klen], v[:, :klen]
        qpos = i * q_chunk + jnp.arange(q_chunk)
        mask = (qpos[:, None] >= jnp.arange(klen)[None, :])[None, None]
        outs.append(_sdpa(qi, ki, vi, mask, scale))
    return jnp.concatenate(outs, axis=1)


def _chunked_bidir(q, k, v, scale, q_chunk, kv_chunk):
    """Non-causal chunked attention (whisper encoder at 32k frames)."""
    b, s, hq, hd = q.shape
    nq = s // q_chunk
    qc = q.reshape(b, nq, q_chunk, hq, hd).transpose(1, 0, 2, 3, 4)

    def q_step(_, qq):
        sk = k.shape[1]
        nk = sk // kv_chunk
        kc = k.reshape(b, nk, kv_chunk, hq, hd).transpose(1, 0, 2, 3, 4)
        vc = v.reshape(b, nk, kv_chunk, hq, hd).transpose(1, 0, 2, 3, 4)

        def kv_step(carry, kv):
            m, l, acc = carry
            kk, vv = kv
            logit = jnp.einsum("bqhd,bkhd->bhqk", qq, kk).astype(jnp.float32) * scale
            m_new = jnp.maximum(m, logit.max(-1))
            p = jnp.exp(logit - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qq.dtype), vv).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, hq, q_chunk), -jnp.inf, jnp.float32),
                jnp.zeros((b, hq, q_chunk), jnp.float32),
                jnp.zeros((b, hq, q_chunk, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, (kc, vc))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return None, out.transpose(0, 2, 1, 3)

    _, outs = jax.lax.scan(q_step, None, qc)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, hq, hd)


# --------------------------------------------------------------------------- #
# embeddings
# --------------------------------------------------------------------------- #

def embed_init(key, vocab: int, d_model: int, dtype) -> jax.Array:
    return (INIT_STD * jax.random.normal(key, (vocab, d_model))).astype(dtype)


def embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def lm_logits(x: jax.Array, table: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,vd->bsv", x, table)
