"""LM assembly for all ten assigned architecture families.

Public API:
  init(key, cfg)                          -> params
  forward(params, batch, cfg)             -> (logits, aux)      [train/prefill]
  loss_fn(params, batch, cfg, l1_coeff)   -> (loss, metrics)
  init_cache(cfg, batch, cache_len)       -> cache pytree       [decode]
  decode_step(params, cache, tokens, cfg) -> (logits, cache)

Layer stacking uses lax.scan over stacked parameter pytrees (HLO size O(1) in
depth — required for the 80 dry-run compiles). Aux sparsity statistics stack
per FFN-bearing layer, feeding Eq. 2 and the Sec. 4.3 analyses.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import sparse_ffn
from repro.distributed import sharding
from repro.distributed.sharding import shard_act
from repro.models import mamba2, moe, rwkv6
from repro.models.layers import (attention, attn_init, embed_init,
                                 embed_lookup, lm_logits, norm_apply,
                                 norm_init)

AUX0 = ("l1", "nnz_mean", "nnz_max", "neuron_active", "ffn_present",
        "tile_frac")


def _dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


def _zero_aux(cfg) -> Dict[str, jax.Array]:
    return {"l1": jnp.float32(0), "nnz_mean": jnp.float32(0),
            "nnz_max": jnp.int32(0),
            "neuron_active": jnp.zeros((cfg.d_ff,), bool),
            "ffn_present": jnp.float32(0), "moe_balance": jnp.float32(0),
            "tile_frac": jnp.float32(0)}


def _mark(aux: Dict) -> Dict:
    out = dict(aux)
    out["ffn_present"] = jnp.float32(1)
    out.setdefault("moe_balance", jnp.float32(0))
    out.pop("moe_drop_frac", None)
    return out


def _dp():
    mesh = sharding.current_mesh()
    if mesh is None or not mesh.axis_names:
        return None, ()
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return (mesh if dp else None), dp


def _attn_kind(cfg) -> str:
    if cfg.window:
        return "swa"
    if cfg.attn_chunk:
        return "local_chunk"
    return "causal"


# --------------------------------------------------------------------------- #
# blocks
# --------------------------------------------------------------------------- #

def _block_init(key, cfg, dtype, use_moe: bool, cross: bool = False) -> Dict:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: Dict[str, Any] = {
        "ln1": norm_init(cfg.norm, d, dtype),
        "ln2": norm_init(cfg.norm, d, dtype),
    }
    p["attn"] = attn_init(ks[0], d, cfg.num_heads, cfg.num_kv_heads,
                          cfg.resolved_head_dim, dtype)
    if use_moe:
        p["moe"] = moe.moe_init(ks[1], d, cfg.d_ff, cfg.num_experts,
                                cfg.gated, dtype)
    else:
        p["ffn"] = sparse_ffn.init(ks[1], d, cfg.d_ff, cfg.gated, dtype)
    if cross:
        p["gate_attn"] = jnp.zeros((), dtype)
        p["gate_ffn"] = jnp.zeros((), dtype)
    return p


def _block_apply(p, x, cfg, positions, *, kind, use_moe, kv_x=None,
                 cache=None, ffn=None):
    """One block. ``ffn``: ``(weights stacked over layers, layer)`` for the
    FFN when ``p`` holds none of its own (the paged scan's blocks)."""
    mesh, dp = _dp()
    a, new_cache = attention(p["attn"], norm_apply(cfg.norm, p["ln1"], x), cfg,
                             positions=positions, kind=kind, kv_x=kv_x,
                             cache=cache)
    if "gate_attn" in p:                      # vlm gated cross-attn layer
        a = jnp.tanh(p["gate_attn"].astype(jnp.float32)).astype(a.dtype) * a
    x = x + a
    x = shard_act(x, ("pod", "data"), None, None)
    h = norm_apply(cfg.norm, p["ln2"], x)
    if use_moe:
        y, aux = moe.moe_apply(p["moe"], h, cfg, cfg.sparsity, cfg.gated,
                               mesh, dp)
    else:
        weights, layer = ffn if ffn is not None else (p["ffn"], None)
        y, aux = sparse_ffn.apply(weights, h, cfg.sparsity, cfg.gated, layer)
    if "gate_ffn" in p:
        y = jnp.tanh(p["gate_ffn"].astype(jnp.float32)).astype(y.dtype) * y
    x = x + y
    x = shard_act(x, ("pod", "data"), None, None)
    return x, _mark(aux), new_cache


def _maybe_remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _split_depth(l: int) -> Tuple[int, int]:
    """Pick (g_out, g_in) with g_out*g_in == l minimizing stored+transient."""
    best = (l, 1)
    for g_in in range(1, l + 1):
        if l % g_in == 0:
            g_out = l // g_in
            if g_out + g_in < best[0] + best[1]:
                best = (g_out, g_in)
    return best


def stacked_scan(body, x, xs_tree, cfg):
    """scan-over-layers with selectable remat.

    remat='2level' = sqrt-remat: layers regrouped (g_out, g_in); only g_out
    carries are stored for the backward pass, the inner group forward is
    recomputed (memory O(g_out + g_in) carries instead of O(L); one extra
    forward per layer). Required to fit the deepest assigned archs
    (llama3-405b: 126 x 268MB carries -> ~3GB) on 16GB v5e chips.
    """
    leaves = jax.tree.leaves(xs_tree)
    l = leaves[0].shape[0]
    if cfg.remat != "2level" or l < 4:
        return jax.lax.scan(_maybe_remat(body, cfg), x, xs_tree)
    g_out, g_in = _split_depth(l)
    grouped = jax.tree.map(lambda a: a.reshape(g_out, g_in, *a.shape[1:]),
                           xs_tree)

    def outer(xc, group):
        return jax.lax.scan(jax.checkpoint(body), xc, group)

    x, aux = jax.lax.scan(jax.checkpoint(outer), x, grouped)
    aux = jax.tree.map(lambda a: a.reshape(l, *a.shape[2:]), aux)
    return x, aux


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #

def init(key: jax.Array, cfg: ModelConfig) -> Dict:
    dtype = _dtype(cfg)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dtype),
        "final_ln": norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = embed_init(keys[1], cfg.padded_vocab, cfg.d_model,
                                       dtype)
    fam = cfg.family
    if fam in ("dense", "moe"):
        bk = jax.random.split(keys[2], cfg.num_layers)
        params["blocks"] = jax.vmap(
            lambda k: _block_init(k, cfg, dtype, use_moe=fam == "moe"))(bk)
    elif fam == "vlm":
        per = cfg.cross_every
        nb = cfg.num_layers // per
        def super_init(k):
            k1, k2 = jax.random.split(k)
            selfs = jax.vmap(lambda kk: _block_init(kk, cfg, dtype, False))(
                jax.random.split(k1, per - 1))
            cross = _block_init(k2, cfg, dtype, False, cross=True)
            return {"selfs": selfs, "cross": cross}
        params["blocks"] = jax.vmap(super_init)(jax.random.split(keys[2], nb))
    elif fam == "audio":
        ek = jax.random.split(keys[2], cfg.encoder_layers)
        dk = jax.random.split(keys[3], cfg.num_layers)
        params["enc_blocks"] = jax.vmap(
            lambda k: _block_init(k, cfg, dtype, False))(ek)
        def dec_init(k):
            k1, k2 = jax.random.split(k)
            p = _block_init(k1, cfg, dtype, False)
            p["lnx"] = norm_init(cfg.norm, cfg.d_model, dtype)
            p["xattn"] = attn_init(k2, cfg.d_model, cfg.num_heads,
                                   cfg.num_kv_heads, cfg.resolved_head_dim,
                                   dtype)
            return p
        params["dec_blocks"] = jax.vmap(dec_init)(dk)
        params["enc_ln"] = norm_init(cfg.norm, cfg.d_model, dtype)
        # stub frontend boundary: frames arrive as precomputed embeddings
        params["frontend_proj"] = (0.02 * jax.random.normal(
            keys[4], (cfg.d_model, cfg.d_model))).astype(dtype)
    elif fam == "hybrid":
        bk = jax.random.split(keys[2], cfg.num_layers)
        params["blocks"] = jax.vmap(
            lambda k: {"ln": norm_init(cfg.norm, cfg.d_model, dtype),
                       "mamba": mamba2.mamba2_init(k, cfg, dtype)})(bk)
        params["shared_attn"] = _block_init(keys[3], cfg, dtype, False)
    elif fam == "ssm":
        bk = jax.random.split(keys[2], cfg.num_layers)
        def rw_init(k):
            k1, k2 = jax.random.split(k)
            return {"ln1": norm_init(cfg.norm, cfg.d_model, dtype),
                    "ln2": norm_init(cfg.norm, cfg.d_model, dtype),
                    "tm": rwkv6.timemix_init(k1, cfg, dtype),
                    "cm": rwkv6.channelmix_init(k2, cfg, dtype)}
        params["blocks"] = jax.vmap(rw_init)(bk)
    else:
        raise ValueError(f"unknown family {fam}")
    return params


# --------------------------------------------------------------------------- #
# forward (train / prefill)
# --------------------------------------------------------------------------- #

def forward(params: Dict, batch: Dict, cfg: ModelConfig
            ) -> Tuple[jax.Array, Dict]:
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens)
    x = shard_act(x, ("pod", "data"), None, None)
    positions = jnp.arange(s)
    fam = cfg.family

    if fam in ("dense", "moe"):
        kind = _attn_kind(cfg)

        def body(xc, p):
            xc, aux, _ = _block_apply(p, xc, cfg, positions, kind=kind,
                                      use_moe=fam == "moe")
            return xc, aux
        x, aux = stacked_scan(body, x, params["blocks"], cfg)

    elif fam == "vlm":
        patches = batch["patches"].astype(x.dtype)        # (B, P, D) stub

        def super_body(xc, p):
            def self_body(xi, pi):
                xi, aux, _ = _block_apply(pi, xi, cfg, positions,
                                          kind="causal", use_moe=False)
                return xi, aux
            xc, aux_s = jax.lax.scan(_maybe_remat(self_body, cfg), xc,
                                     p["selfs"])
            xc, aux_c, _ = _block_apply(p["cross"], xc, cfg, positions,
                                        kind="cross", use_moe=False,
                                        kv_x=patches)
            aux = jax.tree.map(lambda a, c: jnp.concatenate(
                [a, c[None]]), aux_s, aux_c)
            return xc, aux
        x, aux = jax.lax.scan(super_body, x, params["blocks"])
        aux = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), aux)

    elif fam == "audio":
        frames = batch["frames"].astype(x.dtype)          # (B, S_a, D) stub
        enc_in = frames @ params["frontend_proj"]
        enc_pos = jnp.arange(enc_in.shape[1])

        def enc_body(xc, p):
            xc, aux, _ = _block_apply(p, xc, cfg, enc_pos, kind="bidir",
                                      use_moe=False)
            return xc, aux
        enc, aux_e = stacked_scan(enc_body, enc_in, params["enc_blocks"], cfg)
        enc = norm_apply(cfg.norm, params["enc_ln"], enc)

        def dec_body(xc, p):
            a, _ = attention(p["attn"], norm_apply(cfg.norm, p["ln1"], xc),
                             cfg, positions=positions, kind="causal")
            xc = xc + a
            xa, _ = attention(p["xattn"],
                              norm_apply(cfg.norm, p["lnx"], xc), cfg,
                              positions=positions, kind="cross", kv_x=enc)
            xc = xc + xa
            y, aux = sparse_ffn.apply(p["ffn"],
                                      norm_apply(cfg.norm, p["ln2"], xc),
                                      cfg.sparsity, cfg.gated)
            return xc + y, _mark(aux)
        x, aux_d = stacked_scan(dec_body, x, params["dec_blocks"], cfg)
        aux = jax.tree.map(lambda a, b2: jnp.concatenate([a, b2]), aux_e, aux_d)

    elif fam == "hybrid":
        every = cfg.shared_attn_every
        shared = params["shared_attn"]

        def body(xc, pi):
            i, p = pi
            xc = xc + mamba2.mamba2_apply(
                p["mamba"], norm_apply(cfg.norm, p["ln"], xc), cfg)

            def with_attn(xc):
                y, aux, _ = _block_apply(shared, xc, cfg, positions,
                                         kind="causal", use_moe=False)
                return y, aux

            def without(xc):
                return xc, _zero_aux(cfg)
            xc, aux = jax.lax.cond(i % every == every - 1, with_attn,
                                   without, xc)
            return xc, aux
        idx = jnp.arange(cfg.num_layers)
        x, aux = stacked_scan(body, x, (idx, params["blocks"]), cfg)

    elif fam == "ssm":
        def body(carry, p):
            xc = carry
            y, _ = rwkv6.timemix_apply(
                p["tm"], norm_apply(cfg.norm, p["ln1"], xc), cfg)
            xc = xc + y
            y, _, aux = rwkv6.channelmix_apply(
                p["cm"], norm_apply(cfg.norm, p["ln2"], xc), cfg, cfg.sparsity)
            xc = xc + y
            return xc, _mark(aux)
        x, aux = stacked_scan(body, x, params["blocks"], cfg)
    else:
        raise ValueError(fam)

    x = norm_apply(cfg.norm, params["final_ln"], x)
    head = params["embed"] if cfg.tied_embeddings else params["lm_head"]
    logits = lm_logits(x, head)
    logits = shard_act(logits, ("pod", "data"), None, "model")
    return logits, aux


def loss_fn(params, batch, cfg: ModelConfig, l1_coeff: Optional[float] = None,
            moe_balance_coeff: float = 0.01):
    """Cross-entropy + Eq. 2 L1 regularization (+ MoE balance loss)."""
    logits, aux = forward(params, batch, cfg)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    ce = (lse - gold).mean()
    mask = aux["ffn_present"]
    l1_mean = (aux["l1"] * mask).sum() / jnp.maximum(mask.sum(), 1)
    coeff = cfg.sparsity.l1_coeff if l1_coeff is None else l1_coeff
    loss = ce + coeff * l1_mean
    metrics = {"ce": ce, "l1": l1_mean,
               "nnz_mean": (aux["nnz_mean"] * mask).sum() / jnp.maximum(mask.sum(), 1),
               "nnz_max": aux["nnz_max"].max()}
    if "moe_balance" in aux:
        bal = (aux["moe_balance"] * mask).sum() / jnp.maximum(mask.sum(), 1)
        loss = loss + moe_balance_coeff * bal
        metrics["moe_balance"] = bal
    metrics["loss"] = loss
    return loss, (metrics, aux)


# --------------------------------------------------------------------------- #
# decode (serve_step)
# --------------------------------------------------------------------------- #

def encode_frames(params, frames: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Whisper encoder stack over stub frame embeddings -> (B, S_a, D)."""
    enc = frames.astype(_dtype(cfg)) @ params["frontend_proj"]
    enc_pos = jnp.arange(enc.shape[1])

    def enc_body(xc, p):
        xc, aux, _ = _block_apply(p, xc, cfg, enc_pos, kind="bidir",
                                  use_moe=False)
        return xc, aux
    enc, _ = stacked_scan(enc_body, enc, params["enc_blocks"], cfg)
    return norm_apply(cfg.norm, params["enc_ln"], enc)


def prefill_cross_cache(params: Dict, cache: Dict, batch: Dict,
                        cfg: ModelConfig) -> Dict:
    """Fill the cross-attention K/V caches once per request:
    whisper -> from encoder outputs; vlm -> from image patch embeddings."""
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    out = dict(cache)
    if cfg.family == "audio":
        enc = encode_frames(params, batch["frames"], cfg)
        wk = params["dec_blocks"]["xattn"]["wk"]          # (L, D, kv*hd)
        wv = params["dec_blocks"]["xattn"]["wv"]
        b, s, _ = enc.shape
        out["xk"] = jnp.einsum("bsd,ldh->lbsh", enc, wk).reshape(
            wk.shape[0], b, s, hkv, hd)
        out["xv"] = jnp.einsum("bsd,ldh->lbsh", enc, wv).reshape(
            wv.shape[0], b, s, hkv, hd)
    elif cfg.family == "vlm":
        patches = batch["patches"].astype(_dtype(cfg))
        wk = params["blocks"]["cross"]["attn"]["wk"]      # (nb, D, kv*hd)
        wv = params["blocks"]["cross"]["attn"]["wv"]
        b, p, _ = patches.shape
        out["xk"] = jnp.einsum("bpd,ldh->lbph", patches, wk).reshape(
            wk.shape[0], b, p, hkv, hd)
        out["xv"] = jnp.einsum("bpd,ldh->lbph", patches, wv).reshape(
            wv.shape[0], b, p, hkv, hd)
    return out

def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               enc_len: int = 0, num_patches: int = 0) -> Dict:
    """Zero cache pytree; ``cache_len`` is the KV capacity (== shape seq_len).
    SWA archs only keep a window-sized ring buffer (that *is* the mechanism
    that makes 500k decode feasible)."""
    dtype = _dtype(cfg)
    hkv, hd, L = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_layers
    fam = cfg.family
    pos = jnp.zeros((), jnp.int32)
    if fam in ("dense", "moe"):
        sc = min(cache_len, cfg.window) if cfg.window else cache_len
        if cfg.attn_chunk:
            sc = min(cache_len, cfg.attn_chunk)
        return {"k": jnp.zeros((L, batch, sc, hkv, hd), dtype),
                "v": jnp.zeros((L, batch, sc, hkv, hd), dtype), "pos": pos}
    if fam == "vlm":
        per = cfg.cross_every
        nb = L // per
        return {"k": jnp.zeros((L - nb, batch, cache_len, hkv, hd), dtype),
                "v": jnp.zeros((L - nb, batch, cache_len, hkv, hd), dtype),
                "xk": jnp.zeros((nb, batch, num_patches, hkv, hd), dtype),
                "xv": jnp.zeros((nb, batch, num_patches, hkv, hd), dtype),
                "pos": pos}
    if fam == "audio":
        return {"k": jnp.zeros((L, batch, cache_len, hkv, hd), dtype),
                "v": jnp.zeros((L, batch, cache_len, hkv, hd), dtype),
                "xk": jnp.zeros((L, batch, enc_len, hkv, hd), dtype),
                "xv": jnp.zeros((L, batch, enc_len, hkv, hd), dtype),
                "pos": pos}
    if fam == "hybrid":
        d_inner, n_heads, d_state = mamba2.mamba2_dims(cfg)
        napp = L // cfg.shared_attn_every
        return {"state": jnp.zeros((L, batch, n_heads, cfg.ssm_head_dim,
                                    d_state), jnp.float32),
                "conv": jnp.zeros((L, batch, cfg.ssm_conv_width - 1,
                                   d_inner + 2 * d_state), dtype),
                "k": jnp.zeros((napp, batch, cache_len, hkv, hd), dtype),
                "v": jnp.zeros((napp, batch, cache_len, hkv, hd), dtype),
                "pos": pos}
    if fam == "ssm":
        h, hdr = rwkv6.rwkv_dims(cfg)
        return {"wkv": jnp.zeros((L, batch, h, hdr, hdr), jnp.float32),
                "shift_tm": jnp.zeros((L, batch, cfg.d_model), dtype),
                "shift_cm": jnp.zeros((L, batch, cfg.d_model), dtype),
                "pos": pos}
    raise ValueError(fam)


def init_paged_cache(cfg: ModelConfig, num_blocks: int,
                     block_size: int) -> Dict[str, jax.Array]:
    """Block-paged KV pool for the serving engine: one shared pool of
    fixed-size blocks instead of a monolithic (L, B, S, ...) cache per call.
    Layout: (L, num_blocks, block_size, Hkv*hd) — each token's K (V) of
    every kv head is one head-major row; block 0 is the null block (scatter
    target for padding — see repro.serving.kv_cache).

    The row is what keeps the pools row-major on a TPU: with a minor dim of
    hd < 128 the device would lay a (..., Hkv, hd) pool out with the block
    axis minor-most, and every kernel read of a page would need a
    transposing copy of the pools first."""
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"paged KV serving supports dense/moe families, got {cfg.family}")
    if cfg.window or cfg.attn_chunk:
        raise NotImplementedError(
            "paged KV serving does not support windowed/chunked attention yet")
    dtype = _dtype(cfg)
    hkv, hd, L = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_layers
    shape = (L, num_blocks, block_size, hkv * hd)
    return {"kpool": jnp.zeros(shape, dtype), "vpool": jnp.zeros(shape, dtype)}


def _paged_scan(params, x, pools, cfg, positions, block_tables, seq_lens,
                num_new=None, write_valid=None, last_rows=None,
                collect_aux=False):
    fam = cfg.family
    num_layers = pools["kpool"].shape[0]

    # The whole (L, N, bs, Hkv*hd) pools ride in the carry and each layer
    # writes its K/V at [l, blk, off] in place. As scan xs / stacked ys
    # they would be sliced per layer and written into a fresh pool-sized
    # output, which donation cannot alias: a copy of the pools per program.
    # The FFN's stacked weights stay out of the xs too, closed over and
    # read-only: the TwELL kernels take them whole with the layer index and
    # read that layer's tiles in place. As xs, each layer's matrices would
    # first be copied out of the stack for the opaque kernel calls.
    blocks = dict(params["blocks"])
    ffn = blocks.pop("ffn", None)

    def body(carry, xs):
        xc, kp, vp = carry
        p, layer = xs
        lc = {"kpool": kp, "vpool": vp, "layer": layer,
              "block_tables": block_tables, "seq_lens": seq_lens}
        if num_new is not None:
            lc["num_new"] = num_new
        if write_valid is not None:
            lc["write_valid"] = write_valid
        xc, aux, nc = _block_apply(p, xc, cfg, positions, kind="causal",
                                   use_moe=fam == "moe", cache=lc,
                                   ffn=None if ffn is None else (ffn, layer))
        # two scalars per layer; cheap enough to ship every probed step
        ys = {"nnz_mean": aux["nnz_mean"], "tile_frac": aux["tile_frac"],
              "ffn_present": aux["ffn_present"]} if collect_aux else None
        return (xc, nc["kpool"], nc["vpool"]), ys

    (x, kps, vps), aux_stack = jax.lax.scan(
        body, (x, pools["kpool"], pools["vpool"]),
        (blocks, jnp.arange(num_layers, dtype=jnp.int32)))
    if last_rows is not None:
        # keep only each row's last valid hidden state before the O(V) head:
        # the engine samples one token per request, so materializing
        # (B, S, V) logits is pure TTFT/memory waste at large vocab
        x = jnp.take_along_axis(x, last_rows[:, None, None], axis=1)
    x = norm_apply(cfg.norm, params["final_ln"], x)
    head = params["embed"] if cfg.tied_embeddings else params["lm_head"]
    # vocab-sharded logits (head rows split over model); the engine's argmax
    # / sampler reduces them device-side — only the winning token row ever
    # crosses back to host
    logits = shard_act(lm_logits(x, head), None, None, "model")
    pools_out = {"kpool": kps, "vpool": vps}
    if collect_aux:
        return logits, aux_stack, pools_out
    return logits, pools_out


# Donation contract for the paged entrypoints below: the serving engine
# jits them with the ``pools`` argument donated, so the input pool buffers
# are CONSUMED by the call — after dispatch the only valid handle is the
# returned ``pools``, which callers must rebind (``PagedKVCache.swap_pools``)
# before the next dispatch. The layer scan carries the pools and updates
# them in place, so the returned pools alias the donated buffers and no
# step program holds a copy of them. Both logits and pools come back as
# unresolved device values; nothing here blocks, which is what lets the
# pipelined engine run host planning while the device step executes.

def paged_prefill(params: Dict, pools: Dict, block_tables: jax.Array,
                  tokens: jax.Array, num_new: jax.Array,
                  cfg: ModelConfig, start_lens: Optional[jax.Array] = None,
                  last_only: bool = False,
                  collect_aux: bool = False) -> Tuple[jax.Array, Dict]:
    """Prefill a prompt chunk into the paged pool, appending to any cached
    history (the same chunk-append-with-history regime ``paged_verify``
    uses — chunked prefill, prefix-cache reuse, and speculative verify are
    one attention path).

    tokens: (B, C) right-padded chunk tokens; num_new: (B,) valid chunk
    lengths; start_lens: (B,) tokens already cached per request (None = 0
    everywhere: a fresh full-prompt prefill, the original behavior);
    block_tables: (B, W). Writes roped K/V for chunk positions
    ``start + [0, num_new)`` into each request's pages with per-row RoPE
    offsets (padded tail -> null block); the chunk attends to the cached
    history plus itself causally.

    Returns (logits, pools): ``last_only=False`` gives the full (B, C, V)
    logits (rows past num_new are garbage) — the debug/verify escape hatch;
    ``last_only=True`` gathers each row's last valid hidden state *before*
    the vocab projection and returns (B, 1, V) — the serving path, which
    only ever samples the last position.

    ``collect_aux=True`` additionally returns a per-layer sparsity probe —
    ``(logits, {"nnz_mean": (L,), "tile_frac": (L,), "ffn_present": (L,)},
    pools)`` — for the serving telemetry's FLOPs accounting. The probe is
    extra scan outputs only; logits and pools are bit-identical either way.
    """
    x = embed_lookup(params["embed"], tokens)
    if start_lens is None:
        start_lens = jnp.zeros_like(num_new)
    positions = start_lens[:, None] + jnp.arange(tokens.shape[1])[None, :]
    last_rows = jnp.clip(num_new - 1, 0, tokens.shape[1] - 1) if last_only \
        else None
    return _paged_scan(params, x, pools, cfg, positions, block_tables,
                       start_lens, num_new=num_new, last_rows=last_rows,
                       collect_aux=collect_aux)


def paged_decode_step(params: Dict, pools: Dict, block_tables: jax.Array,
                      seq_lens: jax.Array, tokens: jax.Array,
                      cfg: ModelConfig,
                      write_valid: Optional[jax.Array] = None,
                      collect_aux: bool = False
                      ) -> Tuple[jax.Array, Dict]:
    """Continuous-batching decode: one token per running request against the
    shared paged pool. tokens: (B, 1); seq_lens: (B,) cached lengths (the new
    token is written at that position). ``write_valid`` (B,) bool routes a
    row's KV write to the null block when False (speculative draft steps
    past a request's token budget must leave the pool untouched). Returns
    (logits (B, 1, V), pools). Padded rows (all-null table, seq_len 0)
    produce garbage logits."""
    x = embed_lookup(params["embed"], tokens)
    positions = seq_lens[:, None]
    return _paged_scan(params, x, pools, cfg, positions, block_tables,
                       seq_lens, write_valid=write_valid,
                       collect_aux=collect_aux)


def paged_verify(params: Dict, pools: Dict, block_tables: jax.Array,
                 start_lens: jax.Array, num_new: jax.Array,
                 tokens: jax.Array, cfg: ModelConfig
                 ) -> Tuple[jax.Array, Dict]:
    """Speculative-verify forward: score a drafted chunk in one batched pass.

    tokens: (B, S) — per request, the last committed token followed by its
    drafted tokens (right-padded when a request drafted fewer than S-1);
    start_lens: (B,) tokens already cached (the chunk is written starting
    there, with per-request RoPE position offsets); num_new: (B,) valid chunk
    lengths (padded tail positions route their KV writes to the null block).

    Writes *exact* K/V for all valid chunk positions — overwriting whatever
    the approximate draft pass left there — and returns
    (logits (B, S, V), pools); logits row j scores the token following
    position start+j. Rows >= num_new are garbage the caller discards.

    This IS the chunk-append-with-history regime: delegate to
    ``paged_prefill`` so the verifier and the (chunked, prefix-cached)
    prefill path can never drift apart.
    """
    return paged_prefill(params, pools, block_tables, tokens, num_new, cfg,
                         start_lens=start_lens)


def decode_step(params: Dict, cache: Dict, tokens: jax.Array,
                cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """One new token per sequence. tokens: (B, 1) -> logits (B, 1, V)."""
    b = tokens.shape[0]
    x = embed_lookup(params["embed"], tokens)
    pos = cache["pos"]
    positions = pos[None]
    fam = cfg.family
    kind = _attn_kind(cfg)
    if kind == "local_chunk":
        kind = "swa"  # single-token decode against a chunk-local ring cache

    if fam in ("dense", "moe"):
        def body(xc, pk):
            p, ck, cv = pk
            layer_cache = {"k": ck, "v": cv, "pos": pos}
            xc, aux, nc = _block_apply(p, xc, cfg, positions, kind=kind,
                                       use_moe=fam == "moe",
                                       cache=layer_cache)
            return xc, (nc["k"], nc["v"])
        x, (ks, vs) = jax.lax.scan(body, x,
                                   (params["blocks"], cache["k"], cache["v"]))
        new_cache = {"k": ks, "v": vs, "pos": pos + 1}

    elif fam == "vlm":
        per = cfg.cross_every

        def super_body(xc, pk):
            p, ck, cv, xk, xv = pk

            def self_body(xi, pki):
                pi, cki, cvi = pki
                lc = {"k": cki, "v": cvi, "pos": pos}
                xi, _, nc = _block_apply(pi, xi, cfg, positions, kind=kind,
                                         use_moe=False, cache=lc)
                return xi, (nc["k"], nc["v"])
            xc, (ks, vs) = jax.lax.scan(self_body, xc, (p["selfs"], ck, cv))
            lc = {"xk": xk, "xv": xv}
            xc, _, _ = _block_apply(p["cross"], xc, cfg, positions,
                                    kind="cross", use_moe=False, cache=lc)
            return xc, (ks, vs)
        nb = cfg.num_layers // per
        ck = cache["k"].reshape(nb, per - 1, *cache["k"].shape[1:])
        cv = cache["v"].reshape(nb, per - 1, *cache["v"].shape[1:])
        x, (ks, vs) = jax.lax.scan(super_body, x,
                                   (params["blocks"], ck, cv,
                                    cache["xk"], cache["xv"]))
        new_cache = dict(cache)
        new_cache.update(k=ks.reshape(cache["k"].shape),
                         v=vs.reshape(cache["v"].shape), pos=pos + 1)

    elif fam == "audio":
        def body(xc, pk):
            p, ck, cv, xk, xv = pk
            lc = {"k": ck, "v": cv, "pos": pos}
            a, nc = attention(p["attn"], norm_apply(cfg.norm, p["ln1"], xc),
                              cfg, positions=positions, kind="causal",
                              cache=lc)
            xc = xc + a
            xa, _ = attention(p["xattn"], norm_apply(cfg.norm, p["lnx"], xc),
                              cfg, positions=positions, kind="cross",
                              cache={"xk": xk, "xv": xv})
            xc = xc + xa
            y, _ = sparse_ffn.apply(p["ffn"],
                                    norm_apply(cfg.norm, p["ln2"], xc),
                                    cfg.sparsity, cfg.gated)
            return xc + y, (nc["k"], nc["v"])
        x, (ks, vs) = jax.lax.scan(body, x,
                                   (params["dec_blocks"], cache["k"],
                                    cache["v"], cache["xk"], cache["xv"]))
        new_cache = dict(cache)
        new_cache.update(k=ks, v=vs, pos=pos + 1)

    elif fam == "hybrid":
        every = cfg.shared_attn_every
        shared = params["shared_attn"]

        def body(carry, pk):
            xc, attn_k, attn_v = carry
            i, p, st, cw = pk
            y, nst = mamba2.mamba2_decode(
                p["mamba"], norm_apply(cfg.norm, p["ln"], xc), cfg,
                {"state": st, "conv": cw})
            xc = xc + y

            def with_attn(args):
                xc, attn_k, attn_v = args
                app = i // every
                lk = jax.lax.dynamic_index_in_dim(attn_k, app, 0, False)
                lv = jax.lax.dynamic_index_in_dim(attn_v, app, 0, False)
                lc = {"k": lk, "v": lv, "pos": pos}
                y2, _, nc = _block_apply(shared, xc, cfg, positions,
                                         kind="causal", use_moe=False,
                                         cache=lc)
                attn_k = jax.lax.dynamic_update_index_in_dim(
                    attn_k, nc["k"], app, 0)
                attn_v = jax.lax.dynamic_update_index_in_dim(
                    attn_v, nc["v"], app, 0)
                return y2, attn_k, attn_v
            xc, attn_k, attn_v = jax.lax.cond(
                i % every == every - 1, with_attn, lambda a: a,
                (xc, attn_k, attn_v))
            return (xc, attn_k, attn_v), (nst["state"], nst["conv"])
        idx = jnp.arange(cfg.num_layers)
        (x, ks, vs), (sts, cws) = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (idx, params["blocks"], cache["state"], cache["conv"]))
        new_cache = {"state": sts, "conv": cws, "k": ks, "v": vs,
                     "pos": pos + 1}

    elif fam == "ssm":
        def body(xc, pk):
            p, wkv, stm, scm = pk
            y, ns_tm = rwkv6.timemix_apply(
                p["tm"], norm_apply(cfg.norm, p["ln1"], xc), cfg,
                state={"wkv": wkv, "shift": stm})
            xc = xc + y
            y, ns_cm, _ = rwkv6.channelmix_apply(
                p["cm"], norm_apply(cfg.norm, p["ln2"], xc), cfg,
                cfg.sparsity, state={"shift": scm})
            xc = xc + y
            return xc, (ns_tm["wkv"], ns_tm["shift"], ns_cm["shift"])
        x, (wkvs, stms, scms) = jax.lax.scan(
            body, x, (params["blocks"], cache["wkv"], cache["shift_tm"],
                      cache["shift_cm"]))
        new_cache = {"wkv": wkvs, "shift_tm": stms, "shift_cm": scms,
                     "pos": pos + 1}
    else:
        raise ValueError(fam)

    x = norm_apply(cfg.norm, params["final_ln"], x)
    head = params["embed"] if cfg.tied_embeddings else params["lm_head"]
    logits = lm_logits(x, head)
    return logits, new_cache
