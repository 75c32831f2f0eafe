"""SparseFFN: all execution strategies agree with the dense paper math, and
the hybrid custom_vjp gradients (Eq. 4 + L1 injection) match jax.grad of the
dense formulation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SparsityConfig
from repro.core import sparse_ffn

KEY = jax.random.PRNGKey(0)
M, K, N = 32, 48, 128


def _setup(gated=True, act="relu", keep_frac=0.25):
    params = sparse_ffn.init(KEY, K, N, gated, jnp.float32)
    # zero most columns of the pattern-bearing projection -> paper-like
    # per-token sparsity (~ keep_frac/2 active) without ELL/backup overflow
    tgt = "wg" if gated else "wu"
    col_mask = jax.random.uniform(jax.random.fold_in(KEY, 3), (N,)) < keep_frac
    params[tgt] = params[tgt] * col_mask[None, :]
    x = jax.random.normal(jax.random.fold_in(KEY, 7), (M, K))
    scfg = SparsityConfig(enabled=True, l1_coeff=1e-3, activation=act,
                          twell_tile=32, twell_c=4, ell_width=32,
                          dense_backup_frac=0.5)
    return params, x, scfg


def _dense_ref_loss(params, x, scfg, gated, l1c):
    y, aux = sparse_ffn.apply(params, x,
                              dataclasses.replace(scfg, ffn_impl="dense"),
                              gated)
    return (y ** 2).mean() + l1c * aux["l1"]


@pytest.mark.parametrize("impl", ["tile_skip", "gather", "hybrid"])
@pytest.mark.parametrize("gated", [True, False])
def test_impl_matches_dense(impl, gated):
    if impl == "tile_skip" and not gated:
        pytest.skip("tile_skip falls back to dense for non-gated")
    params, x, scfg = _setup(gated)
    # gather consumes packed TwELL: use compression=1 so no tile can
    # overflow its slot budget (exact equality regime; overflow dropping is
    # covered by the format tests)
    scfg_i = dataclasses.replace(scfg, ffn_impl=impl,
                                 twell_c=1 if impl == "gather" else scfg.twell_c)
    y_ref, aux_ref = sparse_ffn.apply(params, x,
                                      dataclasses.replace(scfg, ffn_impl="dense"),
                                      gated)
    y, aux = sparse_ffn.apply(params, x, scfg_i, gated)
    np.testing.assert_allclose(y, y_ref, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(aux["l1"], aux_ref["l1"], rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(aux["nnz_mean"], aux_ref["nnz_mean"], rtol=1e-4)


@pytest.mark.parametrize("gated,act", [(True, "relu"), (False, "relu"),
                                       (False, "relu2")])
def test_hybrid_vjp_matches_dense_grads(gated, act):
    """The pattern-only backward (Eq. 4) is exact for ReLU-family activations
    (zero-measure boundary aside) — including the L1 gradient injection."""
    params, x, scfg = _setup(gated, act)
    l1c = 3e-3
    scfg_h = dataclasses.replace(scfg, ffn_impl="hybrid")

    def loss_hybrid(params, x):
        y, aux = sparse_ffn.apply(params, x, scfg_h, gated)
        return (y ** 2).mean() + l1c * aux["l1"]

    g_ref = jax.grad(lambda p: _dense_ref_loss(p, x, scfg, gated, l1c))(params)
    g_hyb = jax.grad(lambda p: loss_hybrid(p, x))(params)
    for k in g_ref:
        np.testing.assert_allclose(g_hyb[k], g_ref[k], rtol=5e-3, atol=5e-4,
                                   err_msg=f"grad mismatch for {k}")
    gx_ref = jax.grad(lambda xx: _dense_ref_loss(params, xx, scfg, gated,
                                                 l1c))(x)
    gx_hyb = jax.grad(lambda xx: loss_hybrid(params, xx))(x)
    np.testing.assert_allclose(gx_hyb, gx_ref, rtol=5e-3, atol=5e-4)


def test_hybrid_residuals_are_packed():
    """The custom_vjp must not save dense (M, N) activations — the Table-1
    peak-memory claim. Inspect the residual shapes via jax.linearize on the
    underlying primitive function."""
    params, x, scfg = _setup(True)
    md = max(1, int(M * scfg.dense_backup_frac))
    _, f_vjp = jax.vjp(
        lambda x_, wg, wu, wd: sparse_ffn._hybrid_gated(
            x_, wg, wu, wd, scfg.ell_width, md, "relu")[0],
        x, params["wg"], params["wu"], params["wd"])
    # residual arrays live in f_vjp closure; largest saved tensor must be
    # the weights (K x N), not an (M, N) dense activation triple
    sizes = [v.size for v in jax.tree.leaves(f_vjp)]
    assert max(sizes) <= K * N, sizes


def test_silu_baseline_unsupported_in_hybrid():
    params, x, scfg = _setup(True, act="relu")
    scfg = dataclasses.replace(scfg, activation="silu", ffn_impl="hybrid")
    with pytest.raises(ValueError):
        jax.grad(lambda p: sparse_ffn.apply(p, x, scfg, True)[0].sum())(params)
