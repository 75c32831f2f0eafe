"""Pipelined plan/launch/collect step engine.

The acceptance bar: ``ServingEngine(pipeline=True)`` is byte-identical to
the synchronous path in every serving regime — greedy, seeded-stochastic,
speculative decode, chunked prefill with prefix-cache COW, preempt/resume —
while cancels racing an in-flight launched step never touch launched block
tables before collect commits the launched token, ``flush()`` drains the
tail, and the startup warmup leaves zero JIT compiles for steady state.

tp=2 runs in a subprocess on fake CPU host devices, mirroring
tests/test_tp_serving.py.
"""
import dataclasses
import os
import subprocess
import sys
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import generate
from repro.models import lm
from repro.serving import (EVENT_CANCEL, EVENT_PREEMPT, SamplingParams,
                           ServingEngine, SpecConfig, Telemetry)
from repro.serving.pipeline import bucket, bucket_grid, sequence_hash

BS = 4
ROOT = os.path.join(os.path.dirname(__file__), "..")


def _cfg():
    base = get_config("paper-0.5b").reduced()
    return dataclasses.replace(base, sparsity=dataclasses.replace(
        base.sparsity, ffn_impl="dense"))


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, n).tolist() for n in lens]


def _static_ref(params, cfg, prompt, steps):
    import jax.numpy as jnp
    toks = generate(params, cfg, jnp.asarray([prompt], jnp.int32), steps,
                    cache_len=len(prompt) + steps + 1)
    return np.asarray(toks)[0, len(prompt):].tolist()


def _drain(engine):
    events = []
    while engine.has_unfinished():
        events.extend(engine.step())
    return events


def _assert_clean(engine):
    engine.kv.check_invariants()
    assert engine.kv.num_available == engine.kv.num_blocks - 1, \
        "KV blocks leaked"
    assert engine._reserved == 0, "reservation leaked"
    assert engine._inflight is None, "in-flight step survived the drain"


@pytest.fixture(scope="module")
def dense_model():
    cfg = _cfg()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    return params, cfg


# --------------------------------------------------------------------------- #
# bucketing helpers
# --------------------------------------------------------------------------- #

def test_bucket_and_grid():
    assert [bucket(n, 1, 4) for n in (1, 2, 3, 4, 5, 9)] == [1, 2, 4, 4, 4, 4]
    assert bucket(5, 4, 64) == 8 and bucket(17, 4, 64) == 32
    assert bucket_grid(1, 4) == [1, 2, 4]
    assert bucket_grid(4, 64) == [4, 8, 16, 32, 64]
    # every reachable padded size is in the grid — the warmup completeness
    # property the zero-steady-compile guarantee rests on
    for lo, hi in ((1, 4), (4, 64), (2, 5)):
        grid = set(bucket_grid(lo, hi))
        assert all(bucket(n, lo, hi) in grid for n in range(1, hi + 1))


# --------------------------------------------------------------------------- #
# pipeline-vs-sync token identity, regime by regime
# --------------------------------------------------------------------------- #

def _both_modes(params, cfg, prompts, *, sampling=None, max_tokens=6,
                **engine_kwargs):
    outs = {}
    for pipeline in (False, True):
        eng = ServingEngine(params, cfg, block_size=BS, pipeline=pipeline,
                            **engine_kwargs)
        res = eng.generate(prompts, sampling=sampling, max_tokens=max_tokens)
        _assert_clean(eng)
        outs[pipeline] = ([o.token_ids for o in res], eng)
    return outs


def test_pipeline_greedy_identity(dense_model):
    params, cfg = dense_model
    prompts = _prompts(cfg, [5, 9, 7, 12])
    outs = _both_modes(params, cfg, prompts, max_batch=4, max_seq_len=32)
    assert outs[True][0] == outs[False][0], \
        "pipelined greedy diverged from the synchronous engine"
    # the pipelined run actually overlapped: collect happened a step after
    # launch, so the recorded launch->collect span is non-zero
    assert any(s.overlap_ms > 0 for s in outs[True][1].stats)
    assert all(s.overlap_ms == 0 for s in outs[False][1].stats)


def test_pipeline_seeded_stochastic_identity(dense_model):
    """Per-request keys are (seed, output position)-determined, never
    schedule-determined — so the one-step launch lag cannot change draws."""
    params, cfg = dense_model
    prompts = _prompts(cfg, [5, 9, 7], seed=3)
    sp = SamplingParams(temperature=0.9, top_k=32, top_p=0.9, seed=77)
    outs = _both_modes(params, cfg, prompts, sampling=sp, max_batch=4,
                       max_seq_len=32, seed=11)
    assert outs[True][0] == outs[False][0], \
        "pipelined seeded-stochastic diverged"
    assert any(outs[True][0]), "no tokens sampled"


def test_pipeline_spec_identity(dense_model):
    """Speculative draft+verify under the pipeline (verify token block built
    on device, both dispatches in one launch) stays token-identical."""
    params, cfg = dense_model
    prompts = _prompts(cfg, [6, 9, 5], seed=7)
    outs = _both_modes(params, cfg, prompts, max_tokens=8, max_batch=4,
                       max_seq_len=32,
                       spec=SpecConfig(k=2, draft_backend="tile_skip",
                                       draft_threshold=0.3))
    assert outs[True][0] == outs[False][0], "pipelined spec decode diverged"
    assert sum(s.spec_drafted for s in outs[True][1].stats) > 0
    assert sum(s.spec_accepted for s in outs[True][1].stats) > 0


def test_pipeline_chunked_prefill_prefix_cow_identity(dense_model):
    """Chunked prefill + shared-prefix reuse + COW of the live shared last
    block — the launch/collect split must not reorder any of it."""
    params, cfg = dense_model
    rng = np.random.RandomState(17)
    system = rng.randint(0, cfg.vocab_size, 3 * BS).tolist()  # block-aligned
    first = system + rng.randint(0, cfg.vocab_size, 3).tolist()
    later = [system + rng.randint(0, cfg.vocab_size, 3).tolist()
             for _ in range(2)] + [list(system)]       # fully-cached dupe

    def run(pipeline):
        eng = ServingEngine(params, cfg, block_size=BS, max_batch=4,
                            max_seq_len=32, prefill_chunk=4,
                            min_prefill_bucket=4, pipeline=pipeline)
        # two waves: the first registers the system-prompt blocks, the
        # second admits against the now-populated prefix cache
        outs = [o.token_ids for o in eng.generate([first], max_tokens=4)]
        outs += [o.token_ids for o in eng.generate(later, max_tokens=4)]
        _assert_clean(eng)
        assert eng.cached_tokens_total > 0, "prefix cache never hit"
        assert eng.kv.cow_count >= 1, "COW never exercised"
        return outs, eng.cached_tokens_total

    sync_outs, sync_cached = run(False)
    pipe_outs, pipe_cached = run(True)
    assert pipe_outs == sync_outs, \
        "pipelined chunked-prefill/prefix-cache diverged"
    assert pipe_cached == sync_cached


def test_pipeline_preempt_resume_identity(dense_model):
    """Priority preemption under a tight pool: victims planned at plan time
    while a step is in flight are flushed at collect, and the resumed
    request's tokens are identical to the synchronous engine's."""
    params, cfg = dense_model
    lo_p, hi_p = _prompts(cfg, [8, 8], seed=21)

    def run(pipeline):
        eng = ServingEngine(params, cfg, block_size=BS, num_blocks=6,
                            max_batch=2, max_seq_len=16,
                            scheduler="priority", pipeline=pipeline)
        lo = eng.submit(lo_p, max_tokens=6, priority=0)
        for _ in range(4):
            eng.step()
        hi = eng.submit(hi_p, max_tokens=4, priority=1)
        events = _drain(eng)
        _assert_clean(eng)
        assert any(e.kind == EVENT_PREEMPT and e.rid == lo.rid
                   for e in events), "low-priority row not preempted"
        assert lo.result().num_preemptions >= 1
        return lo.result().token_ids, hi.result().token_ids

    assert run(False) == run(True), "pipelined preempt/resume diverged"


# --------------------------------------------------------------------------- #
# cancel racing an in-flight launched step
# --------------------------------------------------------------------------- #

def test_cancel_queued_request_pipelined(dense_model):
    params, cfg = dense_model
    p1, p2 = _prompts(cfg, [8, 6], seed=5)
    engine = ServingEngine(params, cfg, block_size=BS, num_blocks=4,
                           max_batch=2, max_seq_len=16, pipeline=True)
    ha = engine.submit(p1, max_tokens=4)
    hb = engine.submit(p2, max_tokens=4)
    engine.step()
    assert hb.status == "waiting"
    assert hb.cancel()
    evs = engine.step()          # queued cancels resolve at plan, same step
    assert [e.kind for e in evs if e.rid == hb.rid] == [EVENT_CANCEL]
    assert hb.result().token_ids == []
    _drain(engine)
    assert ha.result().finish_reason == "length"
    _assert_clean(engine)


def test_cancel_mid_chunked_prefill_pipelined(dense_model):
    params, cfg = dense_model
    long_p, other = _prompts(cfg, [20, 6], seed=9)
    ref = _static_ref(params, cfg, other, 4)
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=4,
                           max_seq_len=32, prefill_chunk=4,
                           min_prefill_bucket=4, pipeline=True)
    h = engine.submit(long_p, max_tokens=4)
    ho = engine.submit(other, max_tokens=4)
    engine.step()
    engine.step()
    assert h.status == "prefilling"      # 20-token prompt, 4-token chunks
    assert h.cancel()
    events = []
    while not h.finished:
        events.extend(engine.step())
    assert any(e.kind == EVENT_CANCEL and e.rid == h.rid for e in events)
    assert h.result().finish_reason == "cancelled"
    engine.kv.check_invariants()
    _drain(engine)
    assert ho.result().token_ids == ref, "cancel perturbed another request"
    _assert_clean(engine)


def test_cancel_mid_decode_pipelined_keeps_launched_token(dense_model):
    """The in-flight launched token commits BEFORE the deferred cancel: the
    stream never shortens vs the synchronous engine, and the partial output
    is still a prefix of the uninterrupted reference."""
    params, cfg = dense_model
    prompt = _prompts(cfg, [6], seed=11)[0]
    ref = _static_ref(params, cfg, prompt, 8)
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=32, pipeline=True)
    h = engine.submit(prompt, max_tokens=8)
    for _ in range(3):
        engine.step()
    assert h.status == "running" and len(h.tokens) >= 1
    assert engine._inflight is not None
    n_before = len(h.tokens)
    assert h.cancel()
    evs = engine.step()          # collect commits the launched token, then
    out = h.result()             # the deferred cancel goes terminal
    assert any(e.kind == EVENT_CANCEL and e.rid == h.rid for e in evs)
    assert out.finish_reason == "cancelled"
    assert len(out.token_ids) == n_before + 1
    assert out.token_ids == ref[:len(out.token_ids)]
    _assert_clean(engine)


def test_cancel_mid_spec_pipelined(dense_model):
    params, cfg = dense_model
    prompts = _prompts(cfg, [6, 9], seed=13)
    refs = [_static_ref(params, cfg, p, 16) for p in prompts]
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=32, pipeline=True,
                           spec=SpecConfig(k=3, draft_backend="tile_skip"))
    # a spec step commits up to k+1 tokens: budget large enough that the
    # deferred cancel lands before the length cap does
    ha = engine.submit(prompts[0], max_tokens=16)
    hb = engine.submit(prompts[1], max_tokens=16)
    for _ in range(3):
        engine.step()
    assert ha.spec_drafted > 0
    assert ha.cancel()
    events = []
    while not ha.finished:
        events.extend(engine.step())
    assert any(e.kind == EVENT_CANCEL and e.rid == ha.rid for e in events)
    assert ha.result().finish_reason == "cancelled"
    assert ha.result().token_ids == refs[0][:len(ha.result().token_ids)]
    engine.kv.check_invariants()
    _drain(engine)
    assert hb.result().token_ids == refs[1]
    _assert_clean(engine)


def test_cancel_inflight_never_touches_launched_tables(dense_model):
    """Regression: ``cancel()`` landing while a launched step is in flight
    must not mutate any launched block table (or free its blocks) before
    collect commits the launched token — a plan-phase free would hand the
    in-flight decode's pages to the next admission."""
    params, cfg = dense_model
    prompts = _prompts(cfg, [6, 7], seed=19)
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=32, pipeline=True)
    ha = engine.submit(prompts[0], max_tokens=8)
    hb = engine.submit(prompts[1], max_tokens=8)
    for _ in range(3):
        engine.step()
    assert engine._inflight is not None
    rids = [r.rid for r in engine.running]
    assert ha.rid in rids and hb.rid in rids
    fingerprint = sequence_hash(
        [engine.kv.block_table(r) for r in rids])
    free_before = engine.kv.num_free
    assert ha.cancel()
    # the cancel flag alone must not move the pool
    assert sequence_hash([engine.kv.block_table(r) for r in rids]) \
        == fingerprint
    assert engine.kv.num_free == free_before
    evs = engine.step()
    assert any(e.kind == EVENT_CANCEL and e.rid == ha.rid for e in evs)
    engine.kv.check_invariants()
    _drain(engine)
    assert hb.result().finish_reason == "length"
    _assert_clean(engine)


# --------------------------------------------------------------------------- #
# drain semantics
# --------------------------------------------------------------------------- #

def test_flush_drains_inflight(dense_model):
    params, cfg = dense_model
    prompt = _prompts(cfg, [6], seed=29)[0]
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=32, pipeline=True)
    assert engine.flush() == []          # nothing in flight: no-op
    h = engine.submit(prompt, max_tokens=6)
    engine.step()
    engine.step()
    assert engine._inflight is not None
    n = len(h.tokens)
    events = engine.flush()
    assert engine._inflight is None
    assert len(h.tokens) == n + 1, "flush did not commit the launched token"
    assert events, "flush returned no events for the committed token"
    _drain(engine)
    assert h.result().finish_reason == "length"
    _assert_clean(engine)


def test_has_unfinished_counts_inflight_tail(dense_model):
    """generate()/server drain loops terminate only after the in-flight
    tail commits — the last launched token is never dropped."""
    params, cfg = dense_model
    prompt = _prompts(cfg, [5], seed=31)[0]
    ref = _static_ref(params, cfg, prompt, 4)
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=16, pipeline=True)
    h = engine.submit(prompt, max_tokens=4)
    steps = 0
    while engine.has_unfinished():
        engine.step()
        steps += 1
        assert steps < 50
    assert h.result().token_ids == ref
    _assert_clean(engine)


# --------------------------------------------------------------------------- #
# warmup: precompile the whole steady-state shape space
# --------------------------------------------------------------------------- #

def test_warmup_zero_steady_state_compiles(dense_model):
    params, cfg = dense_model
    tm = Telemetry()
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=32, prefill_chunk=8,
                           min_prefill_bucket=4, pipeline=True,
                           telemetry=tm, warmup=True)
    assert engine.warmup_seconds > 0
    assert engine.warmup_report, "warmup compiled nothing"
    snap = dict(tm.summary()["jit_compiles"])
    # warmup compiles are themselves counted: exactly one per report row
    assert sum(snap.values()) == len(engine.warmup_report)
    assert tm.summary()["warmup_seconds"] == pytest.approx(
        engine.warmup_seconds)
    prompts = _prompts(cfg, [5, 9, 7], seed=37)
    engine.generate(prompts, max_tokens=6)
    assert dict(tm.summary()["jit_compiles"]) == snap, \
        "steady-state serving JIT-compiled after warmup"
    # lower_step inspects the very programs warmup built, adding none
    for entry, size in (("decode", engine.table_width), ("prefill", 8)):
        assert engine.lower_step(entry, 2, size).compile().as_text()
    assert dict(tm.summary()["jit_compiles"]) == snap
    _assert_clean(engine)


# --------------------------------------------------------------------------- #
# HTTP server readiness gating
# --------------------------------------------------------------------------- #

def test_server_warmup_gates_healthz(dense_model):
    from repro.serving.server import ServingServer
    params, cfg = dense_model
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=16, prefill_chunk=8,
                           min_prefill_bucket=4, pipeline=True)
    server = ServingServer(engine, port=0, warmup=True)
    try:
        h = server.health()
        assert h["ok"] is False and h["warming_up"] is True
        server.start()
        assert server.wait_ready(timeout=300)
        h = server.health()
        assert h["ok"] is True and "warming_up" not in h
        with urllib.request.urlopen(
                f"http://{server.host}:{server.port}/healthz",
                timeout=10) as resp:
            assert resp.status == 200
        assert engine.warmup_seconds > 0
    finally:
        server.shutdown()


def test_server_without_warmup_ready_immediately(dense_model):
    from repro.serving.server import ServingServer
    params, cfg = dense_model
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=16)
    server = ServingServer(engine, port=0)
    assert server.health()["ok"] is True     # ready from construction
    assert server.wait_ready(timeout=1)
    server.start()
    server.shutdown()


def test_server_warmup_freezes_setup_heap_until_shutdown(dense_model):
    """Once warm, what set-up built (the engine among it) is out of the
    collector's full passes; shutdown hands it back."""
    import gc
    from repro.serving.server import ServingServer
    params, cfg = dense_model
    engine = ServingEngine(params, cfg, block_size=BS, max_batch=2,
                           max_seq_len=16, prefill_chunk=8,
                           min_prefill_bucket=4, pipeline=True)
    server = ServingServer(engine, port=0, warmup=True)
    try:
        server.start()
        assert server.wait_ready(timeout=300)
        assert gc.get_freeze_count() > 0
        assert not any(o is engine for o in gc.get_objects())
    finally:
        server.shutdown()
    assert gc.get_freeze_count() == 0
    assert any(o is engine for o in gc.get_objects())


# --------------------------------------------------------------------------- #
# tp=2: pipelined == synchronous == unsharded (subprocess, fake devices)
# --------------------------------------------------------------------------- #

_TP_SCRIPT = """
import jax, numpy as np
from repro.configs import get_config
from repro.distributed.sharding import make_serving_mesh
from repro.models import lm
from repro.serving import ServingEngine, SpecConfig

cfg = get_config('paper-0.5b').reduced()
params = lm.init(jax.random.PRNGKey(0), cfg)
rng = np.random.RandomState(7)
prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n in (9, 14, 6)]

def run(mesh, pipeline):
    eng = ServingEngine(params, cfg, backend='dense', block_size=4,
                        max_batch=4, max_seq_len=48, prefill_chunk=8,
                        spec=SpecConfig(k=2, draft_backend='tile_skip',
                                        draft_threshold=0.05),
                        mesh=mesh, pipeline=pipeline)
    outs = eng.generate(prompts, max_tokens=8)
    eng.kv.check_invariants()
    assert eng._inflight is None
    return [o.token_ids for o in outs]

mesh = make_serving_mesh(2)
ref = run(None, False)
assert run(mesh, False) == ref, 'tp2 sync diverged from unsharded'
assert run(mesh, True) == ref, 'tp2 pipelined diverged'
print('TP_PIPELINE_OK')
"""


def test_tp2_pipeline_token_identity():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"        # CPU-only: never the chip
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", _TP_SCRIPT],
                       capture_output=True, text=True, timeout=560, env=env)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "TP_PIPELINE_OK" in r.stdout
