"""Per-kernel allclose sweeps: every Pallas kernel (interpret mode) against
its ref.py pure-jnp oracle across shapes and dtypes.

The TwELL FFN kernels take their weights stacked over layers with a layer
index, as the serving scan hands them over; each is checked on the first,
a middle and the last layer of a stack whose layers all differ, against the
oracle on that layer's matrices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hybrid as hyb
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.hybrid_matmul import (dense_to_hybrid_pallas,
                                         hybrid_to_dense_pallas)
from repro.kernels.sparse_ffn import (tile_skip_ffn_pallas,
                                      twell_down_proj_pallas,
                                      twell_fused_ffn_pallas)
from repro.kernels.twell_pack import twell_gate_matmul_pallas


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


def _inputs(m, k, n, dtype, seed=0, sparse_shift=0.0, keep=0.3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = (jax.random.normal(ks[0], (m, k)) * 0.5).astype(dtype)
    col = jax.random.uniform(ks[4], (n,)) < keep
    wg = (jax.random.normal(ks[1], (k, n)) * 0.08 * col[None]).astype(dtype)
    wu = (jax.random.normal(ks[2], (k, n)) * 0.08).astype(dtype)
    wd = (jax.random.normal(ks[3], (n, k)) * 0.08).astype(dtype)
    return x, wg, wu, wd


LAYERS = 3                  # a stack of three: first, middle, last layer


def _stacked(m, k, n, dtype, layer, **kw):
    """x and the stacked (LAYERS, ...) wg, wu, wd, every layer drawn from
    its own seed, and ``layer``'s own matrices for the oracle."""
    per = [_inputs(m, k, n, dtype, seed=i, **kw) for i in range(LAYERS)]
    x = per[layer][0]
    stacks = [jnp.stack([p[i] for p in per]) for i in (1, 2, 3)]
    return x, stacks, per[layer][1:]


SHAPES = [(64, 128, 256, 128, 4), (128, 256, 512, 256, 8),
          (64, 64, 1024, 256, 8), (256, 128, 256, 128, 8)]
# the benchmark cells' FFN geometry at a reduced K: 22 (paper-1.5b) and 32
# (olmo-1b) tiles of 256, at decode batches 1 and 8 and a 512-row prefill
CELL_SHAPES = [(m, 128, nt * 256, 256, 8) for nt in (22, 32)
               for m in (1, 8, 512)]
# (shape, share of live gate columns) of the projection sweeps: the cells'
# shapes at a share that keeps every tile within its slots, as the
# benchmark's weights do
PROJ_SHAPES = [(*s, 0.15) for s in SHAPES[:3]] + \
    [(*s, 0.05) for s in CELL_SHAPES]


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("m,k,n,tile,c", SHAPES + CELL_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_twell_pack_sweep(m, k, n, tile, c, dtype, layer):
    x, (wgs, _, _), (wg, _, _) = _stacked(m, k, n, dtype, layer)
    vals, idx, nnz = twell_gate_matmul_pallas(x, wgs, layer, tile, c, "relu",
                                              bm=64, bk=64)
    tw = ref.twell_gate_matmul(x, wg, tile, c, "relu")
    np.testing.assert_array_equal(np.minimum(np.asarray(nnz), tile // c),
                                  np.asarray(tw.nnz))
    np.testing.assert_allclose(np.asarray(vals, np.float32),
                               np.asarray(tw.values, np.float32),
                               **_tol(dtype))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(tw.indices))


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("m,k,n,tile,c,keep", PROJ_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_twell_fused_ffn_sweep(m, k, n, tile, c, keep, dtype, layer):
    x, (_, wus, wds), (wg, wu, wd) = _stacked(m, k, n, dtype, layer,
                                              keep=keep)
    tw = ref.twell_gate_matmul(x, wg, tile, c, "relu")
    if bool(tw.overflow):
        pytest.skip("overflowing geometry")
    y = twell_fused_ffn_pallas(tw.values, tw.indices, tw.nnz, x, wus, wds,
                               layer, tile, bm=64)
    y_ref = ref.twell_fused_ffn(x, tw, wu, wd)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("m,k,n,tile,c,keep", PROJ_SHAPES)
def test_twell_down_proj_sweep(m, k, n, tile, c, keep, layer):
    # the non-gated FFN's up projection packs: give it the live-column
    # matrix, as the non-gated benchmark weights gate W_u's columns
    x, (_, _, wds), (wu, _, wd) = _stacked(m, k, n, jnp.float32, layer,
                                           keep=keep)
    tw = ref.twell_gate_matmul(x, wu, tile, c, "relu")
    if bool(tw.overflow):
        pytest.skip("overflowing geometry")
    y = twell_down_proj_pallas(tw.values, tw.indices, tw.nnz, wds, layer,
                               tile, bm=64)
    np.testing.assert_allclose(y, ref.twell_down_proj(tw, wd),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("m,k,n,tile,c", SHAPES[:2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tile_skip_ffn_sweep(m, k, n, tile, c, dtype, layer):
    x, stacks, (wg, wu, wd) = _stacked(m, k, n, dtype, layer, keep=0.15)
    y, h = tile_skip_ffn_pallas(x, *stacks, layer, tile, "relu", bm=64)
    y_ref, h_ref = ref.tile_skip_ffn(x, wg, wu, wd, tile, "relu")
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(h_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("m,n,k,ew", [(64, 256, 128, 16), (128, 512, 64, 32)])
def test_hybrid_to_dense_sweep(m, n, k, ew):
    key = jax.random.PRNGKey(1)
    h = jax.nn.relu(jax.random.normal(key, (m, n)) - 1.8)
    hy = hyb.pack(h, ew, num_dense_rows=max(1, m // 8))
    w = jax.random.normal(jax.random.fold_in(key, 2), (n, k)) * 0.1
    y = hybrid_to_dense_pallas(hy.ell_values, hy.ell_indices, hy.row_nnz,
                               ~hy.is_dense, w, tile=128, bm=64)
    hy_ell_only = hy._replace(dense_rows=jnp.zeros_like(hy.dense_rows))
    np.testing.assert_allclose(y, ref.hybrid_to_dense(hy_ell_only, w),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m,n,k,ew", [(64, 256, 128, 16), (128, 512, 64, 32)])
def test_dense_to_hybrid_sweep(m, n, k, ew):
    key = jax.random.PRNGKey(2)
    h = jax.nn.relu(jax.random.normal(key, (m, n)) - 1.8)
    hy = hyb.pack(h, ew, num_dense_rows=max(1, m // 8))
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, k))
    w = jax.random.normal(jax.random.fold_in(key, 2), (k, n)) * 0.1
    vals = dense_to_hybrid_pallas(x, w, hy.ell_indices, hy.row_nnz,
                                  ~hy.is_dense, tile=128, bm=64)
    vref = ref.dense_to_hybrid(x, w, hy).ell_values
    np.testing.assert_allclose(vals, np.asarray(vref, np.float32),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("b,s,h,hd,bq,bk", [
    (2, 128, 2, 64, 64, 64), (1, 256, 4, 32, 64, 128), (2, 512, 1, 64, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, hd, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, h, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, h, hd)).astype(dtype)
    o = flash_attention_pallas(q, k, v, bq=bq, bk=bk)
    o_ref = ref.flash_attention(q, k, v)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **tol)
