"""Tensor-parallel serving tests.

Multi-device cases run on 8 fake CPU host devices in a subprocess (so the
main pytest process keeps its single-device view). The subprocess harness
is CPU-only by construction (it pins JAX_PLATFORMS=cpu and forces the host
platform's device count); the four-chip path is ``chip_smoke.py --tp 4``,
never these tests.

The acceptance bar: greedy outputs must be token-identical between the
unsharded engine and tp=2/4, for all three FFN backends, with speculative
decoding and the prefix cache enabled — i.e. every serving regime built in
PRs 1-3 survives the mesh unchanged.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, Mesh

from repro.configs import get_config
from repro.distributed.sharding import (cache_spec, current_mesh,
                                        make_paged_pool_shardings)
from repro.serving.backends import get_backend

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(script: str, timeout=560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"        # CPU-only: never the chip
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


# --------------------------------------------------------------------------- #
# host-side: spec rules, mesh resolution, validation
# --------------------------------------------------------------------------- #

def test_paged_pool_spec_shards_kv_heads_only():
    """kpool/vpool split ONLY the Hkv*hd row, into whole kv heads; the
    block axis (host-side free-list ids) and intra-block offset stay whole
    even with a data axis on the mesh (the generic batch-dim rule must not
    touch dim 1)."""
    cfg = get_config("paper-0.5b").reduced()
    shape = (cfg.num_layers, 16, 4,
             cfg.num_kv_heads * cfg.resolved_head_dim)
    mesh = AbstractMesh((2, 2), ("data", "model"))
    for name in ("kpool", "vpool"):
        spec = cache_spec(name, shape, cfg, mesh)
        assert tuple(spec) == (None, None, None, "model"), (name, spec)
    # non-divisible kv heads -> fully replicated, never a seq-dim fallback
    import dataclasses
    cfg3 = dataclasses.replace(cfg, num_kv_heads=3)
    spec = cache_spec("kpool", (2, 16, 4, 3 * 16), cfg3, mesh)
    assert "model" not in tuple(spec) and tuple(spec)[1] is None


def test_make_paged_pool_shardings_specs():
    cfg = get_config("paper-0.5b").reduced()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    sh = make_paged_pool_shardings(cfg, mesh, num_blocks=8, block_size=4)
    assert set(sh) == {"kpool", "vpool"}
    for s in sh.values():
        assert tuple(s.spec) == (None, None, None, "model")


def test_current_mesh_one_path_with_and_without_context():
    """The resolver sees a ``jax.set_mesh`` context (and returns None
    outside any context) — training and serving share this one path."""
    assert current_mesh() is None
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    with jax.set_mesh(mesh):
        got = current_mesh()
        assert got is not None and "model" in got.axis_names
    assert current_mesh() is None


@pytest.mark.parametrize("backend", ["dense", "gather", "tile_skip"])
def test_validate_mesh_rejects_nondivisible_heads(backend):
    cfg = get_config("paper-0.5b").reduced()   # 4 heads / 4 kv heads
    bad = AbstractMesh((3,), ("model",))
    with pytest.raises(ValueError, match="not divisible"):
        get_backend(backend).validate_mesh(cfg, bad)
    ok = AbstractMesh((2,), ("model",))
    get_backend(backend).validate_mesh(cfg, ok)   # no raise


@pytest.mark.parametrize("backend", ["gather", "tile_skip"])
def test_validate_mesh_refuses_local_dff_off_tile(backend, monkeypatch):
    """Where the FFN runs as shard-local TwELL kernels (a TPU), each
    device's d_ff slice must be whole tiles: paper-0.5b's d_ff 5632 at tp=4
    leaves 1408 per device, which tile 256 does not divide but tile 128
    does. The dense backend runs no TwELL kernel and is unaffected."""
    import dataclasses

    from repro.kernels import ops
    monkeypatch.setattr(ops, "kernel_mode", lambda: "pallas")
    cfg = get_config("paper-0.5b")
    tp4 = AbstractMesh((4,), ("model",))
    with pytest.raises(ValueError, match="twell_tile=256"):
        get_backend(backend).validate_mesh(cfg, tp4)
    get_backend("dense").validate_mesh(cfg, tp4)
    cfg128 = dataclasses.replace(cfg, sparsity=dataclasses.replace(
        cfg.sparsity, twell_tile=128))
    get_backend(backend).validate_mesh(cfg128, tp4)
    monkeypatch.setattr(ops, "kernel_mode", lambda: "ref")
    get_backend(backend).validate_mesh(cfg, tp4)   # GSPMD tiles globally


# --------------------------------------------------------------------------- #
# multi-device: token identity + sharded pool mechanics (subprocess)
# --------------------------------------------------------------------------- #

# Workload notes: prompt lens vs prefill_chunk=8 force chunked prefill; C ==
# A arriving after A finished exercises a fully-cached prompt (recompute of
# the last position inside a shared block -> device-side COW on the sharded
# pool); staggered arrivals exercise join-on-arrival under the mesh.
_IDENTITY_SCRIPT = """
import jax, numpy as np
from repro.configs import get_config
from repro.distributed.sharding import make_serving_mesh
from repro.models import lm
from repro.serving import (EVENT_TOKEN, ServingEngine, SpecConfig,
                           finished_outputs)

cfg = get_config('paper-0.5b').reduced()
params = lm.init(jax.random.PRNGKey(0), cfg)
rng = np.random.RandomState(7)
A = rng.randint(0, cfg.vocab_size, 20).tolist()
B = A[:16] + rng.randint(0, cfg.vocab_size, 4).tolist()
D = rng.randint(0, cfg.vocab_size, 9).tolist()
# C == A arrives at step 3: A has registered its prompt blocks (prefill done
# at step 2) but is still decoding, so the fully-cached duplicate must COW
# the live shared last block to recompute its final position
work = [(0, A, 10), (1, D, 6), (3, list(A), 8), (4, B, 8)]

def run(mesh, backend, tp_label):
    eng = ServingEngine(params, cfg, backend=backend, block_size=4,
                        max_batch=4, max_seq_len=48, prefill_chunk=8,
                        spec=SpecConfig(k=2, draft_backend='tile_skip',
                                        draft_threshold=0.05), mesh=mesh)
    # drive through the handle/event API: handles submitted staggered, token
    # deltas accumulated from TOKEN events and cross-checked vs the handle
    handles, streamed, pending, step = {{}}, {{}}, list(work), 0
    while pending or eng.has_unfinished():
        while pending and pending[0][0] <= step:
            _, p, mt = pending.pop(0)
            h = eng.submit(p, max_tokens=mt)
            handles[h.rid] = h
            streamed[h.rid] = []
        for ev in eng.step():
            if ev.kind == EVENT_TOKEN:
                streamed[ev.rid].extend(ev.tokens)
        step += 1
    eng.kv.check_invariants()
    outs = {{r: h.result() for r, h in handles.items()}}
    for r, h in handles.items():
        assert streamed[r] == outs[r].token_ids, 'events != terminal output'
    return {{r: o.token_ids for r, o in outs.items()}}, eng

for backend in {backends}:
    ref, _ = run(None, backend, 'tp1')
    for tp in {tps}:
        got, eng = run(make_serving_mesh(tp), backend, f'tp{{tp}}')
        assert got == ref, (backend, tp, ref, got)
        assert eng.kv.cow_count >= 1, 'fully-cached prompt never hit COW'
        assert any(s.spec_drafted for s in eng.stats), 'spec never ran'
        assert eng.cached_tokens_total > 0, 'prefix cache never hit'

# old generate() shim vs handle/event API under the mesh: same engine
# config, spec + prefix cache on — outputs must be token-identical
mesh = make_serving_mesh({tps}[0])
kw = dict(backend={backends}[0], block_size=4, max_batch=4, max_seq_len=48,
          prefill_chunk=8, spec=SpecConfig(k=2, draft_backend='tile_skip'))
shim = [o.token_ids for o in
        ServingEngine(params, cfg, mesh=mesh, **kw).generate([A, D],
                                                             max_tokens=6)]
eng = ServingEngine(params, cfg, mesh=mesh, **kw)
hs = [eng.submit(p, max_tokens=6) for p in (A, D)]
while eng.has_unfinished():
    eng.step()
assert [h.result().token_ids for h in hs] == shim, 'shim != handle API'
print('TP_IDENTITY_OK')
"""


@pytest.mark.parametrize("backend", ["dense", "gather", "tile_skip"])
def test_tp2_token_identity_spec_and_prefix_cache(backend):
    """Greedy outputs identical tp=1 vs tp=2 for one backend, with
    speculative decoding, chunked prefill, prefix-cache sharing, and COW
    all active in the same run."""
    out = _run(_IDENTITY_SCRIPT.format(backends=[backend], tps=[2]))
    assert "TP_IDENTITY_OK" in out


def test_tp4_token_identity_dense():
    out = _run(_IDENTITY_SCRIPT.format(backends=["dense"], tps=[4]))
    assert "TP_IDENTITY_OK" in out


def test_tp2_attention_backend_identity():
    """Greedy outputs identical across attention backends (ref gather-pages
    SDPA vs the paged Pallas kernels in interpret mode) under tp=2
    head-sharded pools, with spec decode + chunked prefill + prefix cache
    active — the kernel's shard_map split over the model axis must commute
    with every regime. Also pins tp=2 == tp=1 within the kernel backend."""
    out = _run("""
import jax, numpy as np
from repro.configs import get_config
from repro.distributed.sharding import make_serving_mesh
from repro.models import lm
from repro.serving import ServingEngine, SpecConfig

cfg = get_config('paper-0.5b').reduced()
params = lm.init(jax.random.PRNGKey(0), cfg)
rng = np.random.RandomState(7)
A = rng.randint(0, cfg.vocab_size, 20).tolist()
D = rng.randint(0, cfg.vocab_size, 9).tolist()

def run(mesh, attn):
    eng = ServingEngine(params, cfg, backend='dense', attn_backend=attn,
                        block_size=4, max_batch=4, max_seq_len=48,
                        prefill_chunk=8, spec=SpecConfig(k=2), mesh=mesh)
    outs = [o.token_ids for o in eng.generate([A], max_tokens=8)]
    outs += [o.token_ids for o in
             eng.generate([list(A), D], max_tokens=8)]
    assert eng.cached_tokens_total > 0, 'prefix cache never hit'
    assert any(s.spec_drafted for s in eng.stats), 'spec never ran'
    return outs

ref = run(None, 'ref')
assert run(make_serving_mesh(2), 'ref') == ref, 'ref tp2 != tp1'
assert run(make_serving_mesh(2), 'interpret') == ref, 'kernel tp2 != ref'
assert run(None, 'interpret') == ref, 'kernel tp1 != ref'
print('ATTN_TP_IDENTITY_OK')
""")
    assert "ATTN_TP_IDENTITY_OK" in out


def test_sharded_cow_copy_matches_unsharded():
    """ensure_writable on a tp=2-sharded pool copies exactly the same bytes
    as on an unsharded pool (per-shard local copy, no resharding), and the
    pool partition invariants hold throughout."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.distributed.sharding import make_serving_mesh
from repro.serving import PagedKVCache

cfg = get_config('paper-0.5b').reduced()
mesh = make_serving_mesh(2)
kvs = {'plain': PagedKVCache(cfg, 10, 4),
       'tp2': PagedKVCache(cfg, 10, 4, mesh=mesh)}
key = jax.random.PRNGKey(0)
content = {n: jax.random.normal(jax.random.fold_in(key, i),
                                kvs['plain'].pools[n].shape)
           for i, n in enumerate(('kpool', 'vpool'))}
kvs['plain'].pools = dict(content)
kvs['tp2'].pools = jax.device_put(dict(content), kvs['tp2'].pool_shardings)

toks = list(range(8))
for kv in kvs.values():
    kv.allocate_prefix(0, toks, 2)
    kv.register_prefix(0, toks)
    kv.allocate_prefix(1, toks, 2)          # shares both blocks (ref 2)
    kv.check_invariants()
    new = kv.ensure_writable(1, 1)          # COW the second shared block
    assert new is not None
    kv.check_invariants()
    kv.append_block(1)
    kv.truncate(1, 2)                       # host-side: sharding-oblivious
    kv.check_invariants()
    kv.free(0); kv.free(1)
    kv.check_invariants()
for n in ('kpool', 'vpool'):
    a = np.asarray(kvs['plain'].pools[n])
    b = np.asarray(kvs['tp2'].pools[n])
    np.testing.assert_array_equal(a, b)
sh = kvs['tp2'].pools['kpool'].sharding
assert tuple(sh.spec) == (None, None, None, 'model'), sh
print('COW_SHARDED_OK')
""")
    assert "COW_SHARDED_OK" in out
