"""Paged-attention Pallas kernels + pluggable attention backends.

Two layers of guarantees:

1. Kernel semantics (interpret mode, CPU): the fused decode and
   chunk-append kernels, handed the whole (L, N, bs, Hkv*hd) pools and a
   layer index, match the plain-jnp oracles in ``kernels.ref`` on that
   layer's (N, bs, Hkv, hd) pages to float tolerance over fuzzed layers, block tables, ragged
   seq_lens, GQA ratios, ``write_valid`` masks, and ``num_new`` padded
   tails.
2. Serving semantics: greedy tokens through the full ``ServingEngine`` are
   IDENTICAL between the ``ref`` backend (gather-pages SDPA, the numerics
   reference) and the kernel backend (interpret mode here; the compiled
   ``pallas`` backend is the same code TPU-side) across every regime —
   decode, chunked prefill + prefix-cache COW, speculative draft/verify,
   preempt/resume, pipeline on/off. tp=2 runs in test_tp_serving.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig
from repro.kernels import ops, ref
from repro.models import lm
from repro.serving import ServingEngine, SpecConfig
from repro.serving.attention import (ATTN_BACKENDS, AttentionBackend,
                                     get_attn_backend)

BS = 4


LAYERS = 3


def _random_paged(rng, b, hkv, g, hd, bs, width):
    """Random (LAYERS, N, bs, Hkv*hd) pools, a valid random block table
    (block 0 = null) and a random layer to read."""
    n = 1 + b * width
    kpool = jnp.asarray(rng.randn(LAYERS, n, bs, hkv * hd), jnp.float32)
    vpool = jnp.asarray(rng.randn(LAYERS, n, bs, hkv * hd), jnp.float32)
    perm = rng.permutation(np.arange(1, n))
    bt = jnp.asarray(perm[:b * width].reshape(b, width).astype(np.int32))
    return kpool, vpool, bt, jnp.int32(rng.randint(LAYERS))


def _pages(pool, layer, hd):
    """Layer ``layer`` of a pool as the oracles' (N, bs, Hkv, hd) pages."""
    return pool[layer].reshape(*pool.shape[1:3], -1, hd)


# --------------------------------------------------------------------------- #
# kernel vs oracle (interpret mode)
# --------------------------------------------------------------------------- #

def test_decode_kernel_fuzz_vs_ref():
    rng = np.random.RandomState(0)
    for _ in range(8):
        b = rng.randint(1, 5)
        hkv = int(rng.choice([1, 2, 4]))
        g = int(rng.choice([1, 2, 4]))          # GQA ratio H/Hkv
        hd = int(rng.choice([8, 16]))
        bs = int(rng.choice([2, 4, 8]))
        width = rng.randint(1, 7)
        kpool, vpool, bt, l = _random_paged(rng, b, hkv, g, hd, bs, width)
        sl = jnp.asarray(rng.randint(0, width * bs, size=b), jnp.int32)
        q = jnp.asarray(rng.randn(b, 1, hkv * g, hd), jnp.float32)
        o_ref = ref.paged_attention_decode(q, _pages(kpool, l, hd),
                                           _pages(vpool, l, hd), bt, sl)
        o_k = ops.paged_attention_decode(q, kpool, vpool, bt, sl, l,
                                         mode="interpret")
        assert float(jnp.abs(o_ref - o_k).max()) < 2e-5


def test_decode_kernel_ragged_and_boundary_seq_lens():
    """seq_len 0 (history empty, first decode after a 1-token prefill sits
    at position 0), exact page boundaries, and the last position of the
    table — the liveness predicate's edges."""
    rng = np.random.RandomState(1)
    b, hkv, g, hd, bs, width = 5, 2, 2, 16, 4, 4
    kpool, vpool, bt, l = _random_paged(rng, b, hkv, g, hd, bs, width)
    sl = jnp.asarray([0, bs - 1, bs, 2 * bs, width * bs - 1], jnp.int32)
    q = jnp.asarray(rng.randn(b, 1, hkv * g, hd), jnp.float32)
    o_ref = ref.paged_attention_decode(q, _pages(kpool, l, hd),
                                       _pages(vpool, l, hd), bt, sl)
    o_k = ops.paged_attention_decode(q, kpool, vpool, bt, sl, l,
                                     mode="interpret")
    assert float(jnp.abs(o_ref - o_k).max()) < 2e-5


def test_chunk_kernel_fuzz_vs_ref():
    rng = np.random.RandomState(2)
    for _ in range(8):
        b = rng.randint(1, 4)
        hkv = int(rng.choice([1, 2]))
        g = int(rng.choice([1, 2, 4]))
        hd = 16
        bs = int(rng.choice([2, 4]))
        s = int(rng.choice([2, 4, 8]))
        width = rng.randint(max(1, -(-s // bs)) + 1, 8)
        kpool, vpool, bt, l = _random_paged(rng, b, hkv, g, hd, bs, width)
        sl = jnp.asarray(rng.randint(0, width * bs - s, size=b), jnp.int32)
        nn = jnp.asarray(rng.randint(0, s + 1, size=b), jnp.int32)
        q = jnp.asarray(rng.randn(b, s, hkv * g, hd), jnp.float32)
        o_ref = ref.paged_attention_extend(q, _pages(kpool, l, hd),
                                           _pages(vpool, l, hd), bt, sl, nn)
        o_k = ops.paged_attention_extend(q, kpool, vpool, bt, sl, nn, l,
                                         mode="interpret")
        # rows at or past num_new are padding — garbage in both paths
        valid = (jnp.arange(s)[None, :] < nn[:, None])[:, :, None, None]
        assert float(jnp.abs((o_ref - o_k) * valid).max()) < 2e-5


def test_chunk_kernel_zero_num_new_row_is_finite():
    """A padded batch row (num_new == 0, all-null table) has no live pages:
    the kernel must emit zeros, never NaN (the engine discards the row)."""
    rng = np.random.RandomState(3)
    kpool, vpool, _, l = _random_paged(rng, 1, 2, 2, 16, 4, 3)
    bt = jnp.zeros((1, 3), jnp.int32)
    q = jnp.asarray(rng.randn(1, 4, 4, 16), jnp.float32)
    out = ops.paged_attention_extend(
        q, kpool, vpool, bt, jnp.zeros((1,), jnp.int32),
        jnp.zeros((1,), jnp.int32), l, mode="interpret")
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.abs(out).max()) == 0.0


def test_gqa_head_group_mapping_matches_repeat_kv():
    """Head h attends kv head h // G exactly as repeat_kv broadcasts — per
    head, not just in aggregate. Make each kv head's pages distinct and
    check the per-head outputs against a per-head dense reference."""
    rng = np.random.RandomState(4)
    b, hkv, g, hd, bs, width = 2, 4, 2, 8, 4, 3
    kpool, vpool, bt, l = _random_paged(rng, b, hkv, g, hd, bs, width)
    sl = jnp.asarray([5, 9], jnp.int32)
    q = jnp.asarray(rng.randn(b, 1, hkv * g, hd), jnp.float32)
    out = ops.paged_attention_decode(q, kpool, vpool, bt, sl, l,
                                     mode="interpret")
    kf = kpool[l, bt].reshape(b, -1, hkv, hd)
    vf = vpool[l, bt].reshape(b, -1, hkv, hd)
    kpos = jnp.arange(kf.shape[1])
    scale = 1.0 / (hd ** 0.5)
    for h in range(hkv * g):
        logits = jnp.einsum("bd,bkd->bk", q[:, 0, h],
                            kf[:, :, h // g]).astype(jnp.float32) * scale
        logits = jnp.where(kpos[None] <= sl[:, None], logits, -1e30)
        o = jnp.einsum("bk,bkd->bd", jax.nn.softmax(logits, -1),
                       vf[:, :, h // g])
        assert float(jnp.abs(out[:, 0, h] - o).max()) < 2e-5, f"head {h}"


def test_write_valid_routing_through_layers():
    """The decode regime with a write_valid mask (spec drafts past budget)
    produces identical pools and logits across backends — the masked row's
    write lands in the null block either way."""
    cfg = _tiny_cfg()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    pools = lm.init_paged_cache(cfg, num_blocks=9, block_size=BS)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    sl = jnp.asarray([3, 5], jnp.int32)
    toks = jnp.asarray([[7], [9]], jnp.int32)
    wv = jnp.asarray([True, False])
    outs = {}
    for be in ("ref", "interpret"):
        c = dataclasses.replace(cfg, attn_backend=be)
        p = jax.tree_util.tree_map(jnp.copy, pools)
        logits, p2 = lm.paged_decode_step(params, p, bt, sl, toks, c,
                                          write_valid=wv)
        outs[be] = (np.asarray(logits), jax.tree_util.tree_map(np.asarray, p2))
    assert np.abs(outs["ref"][0] - outs["interpret"][0]).max() < 1e-4
    # pools match to float tolerance (layer > 0 K/V inherit the attention
    # read's rounding), and the masked row's pages are BIT-identical: its
    # write went to the null block in both backends, so blocks 3/4 hold
    # only prior contents
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-4),
        outs["ref"][1], outs["interpret"][1])
    for pool in ("kpool", "vpool"):
        np.testing.assert_array_equal(outs["ref"][1][pool][:, 3:5],
                                      outs["interpret"][1][pool][:, 3:5])


# --------------------------------------------------------------------------- #
# backend registry / platform validation
# --------------------------------------------------------------------------- #

def test_backend_registry():
    assert set(ATTN_BACKENDS) == {"ref", "pallas", "interpret"}
    for name in ATTN_BACKENDS:
        be = get_attn_backend(name)
        assert isinstance(be, AttentionBackend) and be.name == name
        cfg = be.configure(_tiny_cfg())
        assert cfg.attn_backend == name
    # instances pass through; unknown names raise
    be = get_attn_backend("ref")
    assert get_attn_backend(be) is be
    with pytest.raises(ValueError, match="unknown attention backend"):
        get_attn_backend("flashinfer")


def test_pallas_backend_requires_tpu():
    get_attn_backend("pallas").validate_platform("tpu")
    with pytest.raises(ValueError, match="requires TPU"):
        get_attn_backend("pallas").validate_platform("cpu")
    get_attn_backend("interpret").validate_platform("cpu")
    get_attn_backend("ref").validate_platform("cpu")
    get_attn_backend("ref").validate_platform("tpu")
    with pytest.raises(ValueError, match="emulates the kernels off TPU"):
        get_attn_backend("interpret").validate_platform("tpu")
    if jax.default_backend() != "tpu":
        with pytest.raises(ValueError, match="requires TPU"):
            _engine(_tiny_model()[0], _tiny_cfg(), attn_backend="pallas")


def test_default_attn_backend_follows_platform(monkeypatch):
    """No backend named: the compiled kernels on a TPU, the reference
    elsewhere — the engine never quietly serves a TPU through ``ref``."""
    from repro.serving import attention
    eng = _engine(_tiny_model()[0], _tiny_cfg())
    assert eng.attn.name == attention.default_attn_backend() == "ref"
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert get_attn_backend(None).name == "pallas"


# --------------------------------------------------------------------------- #
# engine token identity across backends
# --------------------------------------------------------------------------- #

def _tiny_cfg():
    return ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                       num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                       head_dim=16, dtype="float32", param_dtype="float32",
                       remat="none", vocab_pad_multiple=8)


_MODEL = {}


def _tiny_model():
    if "m" not in _MODEL:
        cfg = _tiny_cfg()
        _MODEL["m"] = (lm.init(jax.random.PRNGKey(0), cfg), cfg)
    return _MODEL["m"]


def _engine(params, cfg, **kw):
    kw.setdefault("backend", "dense")
    kw.setdefault("block_size", BS)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("min_prefill_bucket", 4)
    return ServingEngine(params, cfg, **kw)


def _prompts(lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 250, size=n).tolist() for n in lens]


def _gen(attn, prompts, max_tokens=10, **kw):
    params, cfg = _tiny_model()
    eng = _engine(params, cfg, attn_backend=attn, **kw)
    outs = [o.token_ids for o in eng.generate(prompts, max_tokens=max_tokens)]
    return outs, eng


def test_engine_decode_prefill_identity():
    prompts = _prompts([5, 19, 33, 12])
    ref_outs, _ = _gen("ref", prompts)
    itp_outs, eng = _gen("interpret", prompts)
    assert ref_outs == itp_outs
    assert eng.cfg.attn_backend == "interpret"


def test_engine_prefix_cache_cow_identity():
    """Duplicate prompts share prefix blocks; decode then COWs them. Tokens
    and cache-hit accounting must match across backends."""
    rng = np.random.RandomState(7)
    system = rng.randint(1, 250, 3 * BS).tolist()
    prompts = [system + rng.randint(1, 250, 3).tolist() for _ in range(2)]
    prompts += [list(system)]
    res = {}
    for be in ("ref", "interpret"):
        params, cfg = _tiny_model()
        eng = _engine(params, cfg, attn_backend=be)
        outs = [o.token_ids for o in eng.generate([prompts[0]],
                                                  max_tokens=6)]
        outs += [o.token_ids for o in eng.generate(prompts[1:],
                                                   max_tokens=6)]
        assert eng.cached_tokens_total > 0
        res[be] = (outs, eng.cached_tokens_total)
    assert res["ref"] == res["interpret"]


def test_engine_spec_decode_identity():
    prompts = _prompts([6, 14], seed=11)
    ref_outs, _ = _gen("ref", prompts, spec=SpecConfig(k=2))
    itp_outs, _ = _gen("interpret", prompts, spec=SpecConfig(k=2))
    assert ref_outs == itp_outs


def test_engine_preempt_resume_identity():
    prompts = _prompts([8, 8], seed=21)

    def run(be):
        params, cfg = _tiny_model()
        eng = _engine(params, cfg, attn_backend=be, num_blocks=6,
                      max_batch=2, max_seq_len=16, scheduler="priority")
        lo = eng.submit(prompts[0], max_tokens=6, priority=0)
        for _ in range(4):
            eng.step()
        hi = eng.submit(prompts[1], max_tokens=4, priority=1)
        while eng.has_unfinished():
            eng.step()
        assert lo.result().num_preemptions >= 1, "preemption never happened"
        return lo.result().token_ids, hi.result().token_ids

    assert run("ref") == run("interpret")


def test_engine_pipeline_identity():
    prompts = _prompts([5, 19, 33, 12], seed=5)
    sync_outs, _ = _gen("interpret", prompts)
    pipe_outs, _ = _gen("interpret", prompts, pipeline=True)
    ref_outs, _ = _gen("ref", prompts, pipeline=True)
    assert sync_outs == pipe_outs == ref_outs


def test_decode_width_clamp_and_warmup_grid():
    """Decode jits at a bucketed table width <= ceil(max seq_len / bs)
    rounded to the grid — short contexts never trace the full padded table
    — and warmup precompiles every (batch, width) bucket so the clamp adds
    no steady-state compiles."""
    from repro.serving.pipeline import bucket, bucket_grid
    params, cfg = _tiny_model()
    eng = _engine(params, cfg, attn_backend="ref", max_seq_len=256)
    prompts = _prompts([5, 9], seed=9)
    eng.generate(prompts, max_tokens=6)
    widths = {w for (_, w, _) in eng._decode_fns}
    grid = set(bucket_grid(1, eng.table_width))
    assert widths <= grid
    # 9 + 6 tokens -> <= 4 blocks -> bucketed width 4, far below the
    # padded table width of 256 // BS = 64
    assert max(widths) <= bucket(4, 1, eng.table_width)
    assert max(widths) < eng.table_width

    eng2 = _engine(params, cfg, attn_backend="ref", telemetry=True,
                   warmup=True)
    before = eng2.telemetry.summary()["jit_compiles"]
    eng2.generate(prompts, max_tokens=6)
    after = eng2.telemetry.summary()["jit_compiles"]
    assert before == after, "width clamp caused steady-state compiles"
