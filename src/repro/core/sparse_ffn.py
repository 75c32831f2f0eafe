"""SparseFFN — the paper's contribution as a composable JAX module.

One module, four execution strategies (``SparsityConfig.ffn_impl``):

- ``dense``      paper-faithful math (Eq. 1 / Eq. 5) on the XLA dense path;
                 the baseline the paper compares against, and the semantics
                 every other impl must match bit-for-bit (up to dtype).
- ``tile_skip``  TPU-native TwELL harvest: Pallas kernel skips dead
                 (row-block × hidden-tile) blocks (DESIGN.md §2). CPU falls
                 back to dense math (numerically identical by construction).
- ``gather``     Eq. 3 fused up+down projection from packed TwELL gate
                 activations (GEMV/decode regime).
- ``hybrid``     training path (Sec. 3.4/3.5): ``jax.custom_vjp`` whose
                 residuals are the *packed* activations, with the Eq. 4
                 pattern-only backward and L1 gradient injection. This is the
                 peak-memory reduction of Table 1, natively in JAX.

The weights are stacked over layers (``wg`` / ``wu`` ``(L, d_model, d_ff)``,
``wd`` ``(L, d_ff, d_model)``, as the model's layer scan holds them) and an
int32 ``layer`` picks one: the kernel-backed impls hand the stacked arrays
and the layer to the kernels, which read that layer's tiles in place, so a
serving step never copies a layer's FFN weights out of the stack. ``apply``
called without a layer takes one layer's matrices and stacks them as a
stack of one.

All impls return ``(y, aux)`` with ``aux = {l1, nnz_mean, nnz_max,
neuron_active, tile_frac}`` feeding Eq. 2, the Sec. 4.3 instrumentation,
and the observability cost model (``repro.observability.accounting``).
``tile_frac`` is the fraction of (row x twell_tile) cells holding any
non-zero — the occupancy the tile-skip kernel and the analytic FLOPs
model consume.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import SparsityConfig
from repro.core import hybrid as hybrid_fmt
from repro.core import twell
from repro.core.sparsity import activation, activation_grad, l1_loss


def init(key: jax.Array, d_model: int, d_ff: int, gated: bool,
         dtype=jnp.float32, init_std: float = 0.02) -> Dict[str, jax.Array]:
    ks = jax.random.split(key, 3)
    params = {
        "wu": (init_std * jax.random.normal(ks[0], (d_model, d_ff))).astype(dtype),
        "wd": (init_std * jax.random.normal(ks[1], (d_ff, d_model))).astype(dtype),
    }
    if gated:
        params["wg"] = (init_std * jax.random.normal(ks[2], (d_model, d_ff))).astype(dtype)
    return params


def _tile_frac(mask_n: jax.Array, tile: int) -> jax.Array:
    """Fraction of (row x tile) cells with any active neuron. ``mask_n``:
    bool, last axis = d_ff; ragged d_ff pads with dead columns."""
    *lead, n = mask_n.shape
    tile = max(1, min(int(tile), n))
    nt = -(-n // tile)
    pad = nt * tile - n
    if pad:
        mask_n = jnp.pad(mask_n, [(0, 0)] * len(lead) + [(0, pad)])
    return mask_n.reshape(*lead, nt, tile).any(-1).mean().astype(jnp.float32)


def _aux_from_h(h: jax.Array, tile: int) -> Dict[str, jax.Array]:
    mask = h != 0
    nnz = mask.sum(axis=-1)
    return {
        "l1": l1_loss(h),
        "nnz_mean": nnz.mean().astype(jnp.float32),
        "nnz_max": nnz.max().astype(jnp.int32),
        "neuron_active": jnp.any(mask, axis=0),
        "tile_frac": _tile_frac(mask, tile),
    }


# --------------------------------------------------------------------------- #
# dense (paper-faithful math; also the tile_skip CPU path)
# --------------------------------------------------------------------------- #

def _layer(params, layer):
    """One layer's matrices of the stacked FFN weights."""
    return {k: w[layer] for k, w in params.items()}


def _dense_apply(params, x, scfg: SparsityConfig, gated: bool, layer):
    from repro.distributed.sharding import shard_act
    params = _layer(params, layer)
    act = activation(scfg.activation if scfg.enabled else "silu")
    if gated:
        h = (x @ params["wu"]) * act(x @ params["wg"])
    else:
        h = act(x @ params["wu"])
    # Megatron layout: the hidden dim splits over the model axis, matching
    # wu's column / wd's row sharding — shard-local up+down projections with
    # one all-reduce on y. No-op without a mesh (single-device serving/tests).
    h = shard_act(h, *([None] * (h.ndim - 1) + ["model"]))
    y = h @ params["wd"]
    return y, _aux_from_h(h, scfg.twell_tile)


# --------------------------------------------------------------------------- #
# TwELL gather (Eq. 3) — inference
# --------------------------------------------------------------------------- #

def _twell_apply(params, x, scfg: SparsityConfig, gated: bool, layer):
    from repro.kernels import ops as kops
    if gated:
        y, tw = kops.twell_ffn(x, params["wg"], params["wu"], params["wd"],
                               layer, scfg.twell_tile, scfg.twell_c,
                               scfg.activation)
        # Eq. 2's L1 is over h = h_u * h_g: recover |h| on the pattern via
        # the same gathered h_u elements the fused kernel computes (Eq. 3);
        # one gather of the pattern's W_u columns out of the stack
        tc = tw.slot_width
        slot = jnp.arange(tw.values.shape[1], dtype=jnp.int32) % tc
        valid = slot[None, :] < jnp.repeat(tw.nnz, tc, axis=-1)
        hu_p = jnp.einsum("mk,mck->mc", x, params["wu"][layer, :, tw.indices])
        h_abs = jnp.abs(jnp.where(valid, tw.values * hu_p, 0)
                        .astype(jnp.float32))
    else:
        y, tw = kops.twell_ffn_nongated(x, params["wu"], params["wd"], layer,
                                        scfg.twell_tile, scfg.twell_c,
                                        scfg.activation)
        h_abs = jnp.abs(tw.values.astype(jnp.float32))
    nnz_rows = tw.nnz.sum(-1)
    aux = {
        "l1": h_abs.sum() / (x.shape[0] * tw.n),
        "nnz_mean": nnz_rows.mean().astype(jnp.float32),
        "nnz_max": nnz_rows.max().astype(jnp.int32),
        "neuron_active": jnp.zeros((tw.n,), bool).at[
            tw.indices.reshape(-1)].max(tw.values.reshape(-1) != 0),
        # per-(row x tile) occupancy straight from the packed counts
        "tile_frac": (tw.nnz > 0).mean().astype(jnp.float32),
    }
    return y, aux


# --------------------------------------------------------------------------- #
# tile_skip — TPU block-sparse kernel, dense math on CPU
# --------------------------------------------------------------------------- #

def _tile_skip_apply(params, x, scfg: SparsityConfig, gated: bool, layer):
    from repro.kernels import ops as kops
    if not gated:
        return _dense_apply(params, x, scfg, gated, layer)
    y, h = kops.tile_skip_ffn(x, params["wg"], params["wu"], params["wd"],
                              layer, scfg.twell_tile, scfg.activation,
                              threshold=scfg.tile_skip_threshold)
    return y, _aux_from_h(h, scfg.twell_tile)


# --------------------------------------------------------------------------- #
# hybrid — training custom_vjp with packed residuals (Sec. 3.4/3.5, Eq. 4)
# --------------------------------------------------------------------------- #

def _scatter_wgrad(idx: jax.Array, gvals: jax.Array, x: jax.Array,
                   dense_gvals: jax.Array, dense_map: jax.Array,
                   n: int) -> jax.Array:
    """grad_W[k, n] = sum_m x[m, k] * g[m, n] with g in hybrid layout.

    Returns (K, N). ELL side scatters into (N, K) then transposes; the dense
    backup side is a plain matmul on gathered source rows (MXU path).
    """
    m, ell_w = idx.shape
    rows = jnp.repeat(jnp.arange(m, dtype=jnp.int32), ell_w)
    contrib = gvals.reshape(-1)[:, None].astype(jnp.float32) * \
        x[rows].astype(jnp.float32)                      # (M*E, K)
    wn = jnp.zeros((n, x.shape[1]), jnp.float32).at[idx.reshape(-1)].add(contrib)
    ok = dense_map >= 0
    src = jnp.where(ok, dense_map, 0)
    xd = jnp.where(ok[:, None], x[src], 0).astype(jnp.float32)   # (M_d, K)
    wn = wn + dense_gvals.astype(jnp.float32).T @ xd             # (N, K)
    return wn.T.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _hybrid_gated(x, wg, wu, wd, ell_width, num_dense_rows, act_name):
    y, l1, stats, _ = _hybrid_gated_fwd_impl(x, wg, wu, wd, ell_width,
                                             num_dense_rows, act_name)
    return y, l1, stats


def _packed_stats(h: hybrid_fmt.HybridActs):
    """(row_nnz, neuron_active) from the packed representation — no dense MxN.
    Returned as float32: integer/bool custom_vjp outputs get float0
    cotangents, which jax.checkpoint cannot reduce on older jax releases."""
    active = jnp.zeros((h.n,), bool).at[h.ell_indices.reshape(-1)].max(
        h.ell_values.reshape(-1) != 0)
    active = active | jnp.any(h.dense_rows != 0, axis=0)
    return h.row_nnz.astype(jnp.float32), active.astype(jnp.float32)


def _hybrid_gated_fwd_impl(x, wg, wu, wd, ell_width, num_dense_rows, act_name):
    act = activation(act_name)
    hg_dense = act(x @ wg)                      # dense gate matmul (TwELL kernel on TPU)
    hg = hybrid_fmt.pack(hg_dense, ell_width, num_dense_rows, mask=hg_dense > 0)
    hu = hybrid_fmt.dense_to_hybrid_matmul(x, wu, hg)            # pattern-only h_u
    h = hybrid_fmt.elementwise(hg, hu.ell_values, hu.dense_rows, jnp.multiply)
    h = h._replace(dense_rows=jnp.where(hg.dense_rows != 0, h.dense_rows, 0))
    y = hybrid_fmt.hybrid_to_dense_matmul(h, wd)
    m, n = hg_dense.shape
    l1 = (jnp.abs(h.ell_values.astype(jnp.float32)).sum() +
          jnp.abs(h.dense_rows.astype(jnp.float32)).sum()) / (m * n)
    return y, l1, _packed_stats(h), (hg, hu, h)


def _hybrid_gated_fwd(x, wg, wu, wd, ell_width, num_dense_rows, act_name):
    y, l1, stats, (hg, hu, h) = _hybrid_gated_fwd_impl(
        x, wg, wu, wd, ell_width, num_dense_rows, act_name)
    # Residuals: inputs + *packed* activations only — the Table-1 memory win.
    return (y, l1, stats), (x, wg, wu, wd, hg, hu, h)


def _hybrid_gated_bwd(ell_width, num_dense_rows, act_name, res, cts):
    x, wg, wu, wd, hg, hu, h = res
    gy, gl1 = cts[0], cts[1]        # stats outputs carry zero cotangents
    m, k = x.shape
    n = hg.n

    # grad_h = grad_y @ W_d^T on the stored pattern (dense-to-hybrid matmul)
    gh = hybrid_fmt.dense_to_hybrid_matmul(gy, wd.T, hg)
    # L1 injection: d|h|/dh = sign(h) on the pattern, scaled by 1/(M N)
    inj = gl1 / (m * n)
    gh = gh._replace(
        ell_values=gh.ell_values + inj * jnp.sign(h.ell_values),
        dense_rows=gh.dense_rows + inj * jnp.sign(h.dense_rows))

    # Eq. 4 elementwise splits on the pattern
    ghu_e, ghu_d = gh.ell_values * hg.ell_values, gh.dense_rows * hg.dense_rows
    ghg_e, ghg_d = gh.ell_values * hu.ell_values, gh.dense_rows * hu.dense_rows
    # through the gate non-linearity (exact on the pattern; see DESIGN.md)
    ghg_e = ghg_e * activation_grad(act_name, hg.ell_values)
    ghg_d = ghg_d * activation_grad(act_name, hg.dense_rows)

    ghu = hg._replace(ell_values=ghu_e, dense_rows=ghu_d)
    ghg = hg._replace(ell_values=ghg_e, dense_rows=ghg_d)

    # weight grads: scatter-add on the pattern (never dense MxN).
    # _scatter_wgrad returns (cols(gy), N); grad_wd[n, k] = sum_m h[m,n] gy[m,k]
    gwd = _scatter_wgrad(h.ell_indices, h.ell_values, gy,
                         h.dense_rows, h.dense_map, n).T
    gwu = _scatter_wgrad(hu.ell_indices, ghu.ell_values, x,
                         ghu.dense_rows, hu.dense_map, n)
    gwg = _scatter_wgrad(hg.ell_indices, ghg.ell_values, x,
                         ghg.dense_rows, hg.dense_map, n)

    # grad_x = grad_hu @ W_u^T + grad_g @ W_g^T (hybrid-to-dense matmuls)
    gx = hybrid_fmt.hybrid_to_dense_matmul(ghu, wu.T) + \
        hybrid_fmt.hybrid_to_dense_matmul(ghg, wg.T)
    return gx.astype(x.dtype), gwg.astype(wg.dtype), gwu.astype(wu.dtype), \
        gwd.astype(wd.dtype)


_hybrid_gated.defvjp(_hybrid_gated_fwd, _hybrid_gated_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _hybrid_nongated(x, wu, wd, ell_width, num_dense_rows, act_name):
    y, l1, stats, _ = _hybrid_nongated_fwd_impl(x, wu, wd, ell_width,
                                                num_dense_rows, act_name)
    return y, l1, stats


def _hybrid_nongated_fwd_impl(x, wu, wd, ell_width, num_dense_rows, act_name):
    act = activation(act_name)
    h_dense = act(x @ wu)
    h = hybrid_fmt.pack(h_dense, ell_width, num_dense_rows, mask=h_dense > 0)
    y = hybrid_fmt.hybrid_to_dense_matmul(h, wd)
    m, n = h_dense.shape
    l1 = (jnp.abs(h.ell_values.astype(jnp.float32)).sum() +
          jnp.abs(h.dense_rows.astype(jnp.float32)).sum()) / (m * n)
    return y, l1, _packed_stats(h), h


def _hybrid_nongated_fwd(x, wu, wd, ell_width, num_dense_rows, act_name):
    y, l1, stats, h = _hybrid_nongated_fwd_impl(x, wu, wd, ell_width,
                                                num_dense_rows, act_name)
    return (y, l1, stats), (x, wu, wd, h)


def _hybrid_nongated_bwd(ell_width, num_dense_rows, act_name, res, cts):
    x, wu, wd, h = res
    gy, gl1 = cts[0], cts[1]
    m, k = x.shape
    n = h.n
    gh = hybrid_fmt.dense_to_hybrid_matmul(gy, wd.T, h)
    inj = gl1 / (m * n)
    gh = gh._replace(ell_values=gh.ell_values + inj * jnp.sign(h.ell_values),
                     dense_rows=gh.dense_rows + inj * jnp.sign(h.dense_rows))
    gu_e = gh.ell_values * activation_grad(act_name, h.ell_values)
    gu_d = gh.dense_rows * activation_grad(act_name, h.dense_rows)
    gu = h._replace(ell_values=gu_e, dense_rows=gu_d)
    gwd = _scatter_wgrad(h.ell_indices, h.ell_values, gy,
                         h.dense_rows, h.dense_map, n).T
    gwu = _scatter_wgrad(h.ell_indices, gu.ell_values, x, gu.dense_rows,
                         h.dense_map, n)
    gx = hybrid_fmt.hybrid_to_dense_matmul(gu, wu.T)
    return gx.astype(x.dtype), gwu.astype(wu.dtype), gwd.astype(wd.dtype)


_hybrid_nongated.defvjp(_hybrid_nongated_fwd, _hybrid_nongated_bwd)


def _hybrid_apply(params, x, scfg: SparsityConfig, gated: bool, layer):
    params = _layer(params, layer)
    m = x.shape[0]
    md = max(1, int(m * scfg.dense_backup_frac))
    if gated:
        y, l1, (row_nnz, active) = _hybrid_gated(
            x, params["wg"], params["wu"], params["wd"],
            scfg.ell_width, md, scfg.activation)
    else:
        y, l1, (row_nnz, active) = _hybrid_nongated(
            x, params["wu"], params["wd"], scfg.ell_width, md,
            scfg.activation)
    aux = {
        "l1": l1,
        "nnz_mean": row_nnz.astype(jnp.float32).mean(),
        "nnz_max": row_nnz.max().astype(jnp.int32),
        "neuron_active": active > 0,
        # the packed stats are per-neuron, not per-(row x tile): report the
        # batch-level tile occupancy (an upper bound on per-row occupancy)
        # rather than widening the custom_vjp's residuals to recover it
        "tile_frac": _tile_frac((active > 0)[None, :], scfg.twell_tile),
    }
    return y, aux


_IMPLS = {
    "dense": _dense_apply,
    "tile_skip": _tile_skip_apply,
    "gather": _twell_apply,
    "hybrid": _hybrid_apply,
}


def apply(params: Dict[str, jax.Array], x: jax.Array, scfg: SparsityConfig,
          gated: bool, layer=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (..., d_model) -> (..., d_model), plus sparsity aux.

    ``params`` hold the weights stacked over layers and ``layer`` (an int32
    scalar) picks one; without a layer they are one layer's matrices, which
    go on as a stack of one at layer 0."""
    if layer is None:
        params, layer = jax.tree.map(lambda w: w[None], params), 0
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    impl = scfg.ffn_impl if scfg.enabled else "dense"
    y, aux = _IMPLS[impl](params, x2, scfg, gated, layer)
    return y.reshape(*lead, -1), aux
