"""Serving benchmark: continuous batching + paged KV, dense vs TwELL.

Replays a mixed-length synthetic workload (varied prompt lengths and output
budgets, staggered arrivals) through the ``ServingEngine`` once per FFN
backend and reports throughput (tok/s), time-to-first-token (TTFT), and the
per-step decode-batch composition — the composition trace is the proof that
requests join and leave the batch mid-flight (continuous batching) rather
than running as one static batch. The admissible-blocks trace (free net of
reservations, plus the reservation itself) exposes admission stalls.

A second, shared-system-prompt workload runs with prefix caching on vs off:
it reports the cache hit rate and prefill-token savings and asserts greedy
outputs are token-identical either way (caching must be invisible except in
cost).

Timing is split so TP speedups are attributable: the warmup replay's wall
time is the compile cost, the measured replay is steady state, and within
steady state every engine step records wall vs device-sync milliseconds
(wall - sync = host-side scheduling overhead).

A pipeline section re-runs the main workload and the churn workload through
the overlapped plan/launch/collect step path with the bucket-grid warmup,
asserting greedy token identity with the synchronous engine and that the
jit-compile counters stay flat after warmup (zero steady-state compiles),
and reporting the residual sync_ms plus the measured plan/device overlap.

An attention-backend sweep decodes the same batches at growing context
lengths through the plain-JAX ``ref`` gather and the fused paged Pallas
kernel (interpret mode off-TPU), asserting greedy token identity per
bucket and reporting per-backend mean decode-step wall ms (the kernel's
scaling with context length); the speed advantage itself is asserted only
on a real TPU under the full profile.

With ``--tp N`` every engine runs under an N-way tensor-parallel mesh
(params + paged KV pools sharded over the model axis), and a third section
asserts greedy outputs are token-identical to the unsharded engine — with
speculative decoding and the prefix cache enabled — before reporting:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python benchmarks/bench_serving.py --reduced --tp 2
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.distributed.sharding import make_serving_mesh
from repro.models import lm
from repro.serving import (DisaggCoordinator, EngineSpec, EVENT_TOKEN,
                           SamplingParams, SpecConfig, Telemetry,
                           finished_outputs)

import common

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def write_bench_json(path: str, payload: dict) -> None:
    """Machine-readable benchmark record (BENCH_*.json at the repo root) so
    the perf trajectory is trackable across PRs."""
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}")


def make_workload(num_requests: int, vocab: int, seed: int):
    """Mixed-length requests with staggered arrivals.

    Returns [(arrival_step, prompt, max_tokens)] — prompt lengths cycle
    through short/medium/long buckets, output budgets vary, and a fresh
    request arrives every other engine step.
    """
    rng = np.random.RandomState(seed)
    prompt_lens = [8, 24, 48, 16, 32, 12]
    out_lens = [16, 8, 24, 12]
    work = []
    for i in range(num_requests):
        p = prompt_lens[i % len(prompt_lens)]
        work.append((i * 2,                       # arrival step
                     rng.randint(0, vocab, p).tolist(),
                     out_lens[i % len(out_lens)]))
    return work


def make_shared_prefix_workload(num_requests: int, vocab: int, seed: int,
                                prefix_len: int = 48, tail_len: int = 8):
    """Shared-system-prompt traffic: every request = one common prefix +
    a unique tail, staggered arrivals. The shape real fleets serve (system
    prompts, few-shot templates) and the one prefix caching exists for."""
    rng = np.random.RandomState(seed)
    system = rng.randint(0, vocab, prefix_len).tolist()
    work = []
    for i in range(num_requests):
        tail = rng.randint(0, vocab, tail_len).tolist()
        work.append((i * 2, system + tail, 8))
    return work


def make_churn_workload(num_requests: int, vocab: int, seed: int,
                        cancel_frac: float = 0.1, hi_frac: float = 0.3,
                        arrival_rate: float = 0.6):
    """Request churn the way real front ends see it: Poisson arrivals
    (exponential inter-arrival gaps, in engine steps), ~10% of clients
    cancel a few steps after submitting (disconnects), and traffic splits
    into two priority tiers (interactive hi=1 over batch lo=0).

    Returns [(arrival_step, prompt, max_tokens, priority, cancel_after)]
    where cancel_after is None (stays) or steps-after-arrival to cancel.
    """
    rng = np.random.RandomState(seed)
    work, t = [], 0.0
    for i in range(num_requests):
        t += rng.exponential(1.0 / arrival_rate)
        plen = int(rng.randint(6, 28))
        prompt = rng.randint(0, vocab, plen).tolist()
        max_tokens = int(rng.choice([8, 12, 16]))
        prio = 1 if rng.rand() < hi_frac else 0
        cancel_after = int(rng.randint(2, 8)) if rng.rand() < cancel_frac \
            else None
        work.append((int(t), prompt, max_tokens, prio, cancel_after))
    return work


def run_churn(params, cfg, work, *, backend: str, scheduler: str,
              block_size: int, max_batch: int, max_seq_len: int,
              num_blocks=None, prefill_chunk: int = 64, mesh=None,
              pipeline: bool = False, warmup: bool = False,
              telemetry: bool = False, trace_out=None,
              disagg: bool = False, transfer_ttl_steps: int = 64,
              stochastic: bool = False):
    """Replay a churn workload through one engine via the handle/event API,
    timing every TOKEN event for tail-latency stats. Asserts the KV pool
    drains invariant-clean with zero leaked blocks. With ``warmup`` the
    bucket grid precompiles first and the result records the jit-compile
    counters at the warmup/steady boundary, so callers can assert the whole
    churn replay (admissions, cancels, preemptions, every batch size)
    compiled nothing.

    ``disagg=True`` drives the same workload through the disaggregated
    prefill/decode front door (two engines, two KV pools, block migration)
    — same handle/event API, so this function is backend-agnostic; both
    pools are invariant-checked. ``stochastic`` samples with a
    deterministic per-submission seed (temperature + top-k) instead of
    greedy, so identical workloads must produce identical streams across
    engine architectures."""
    espec = EngineSpec(backend=backend, block_size=block_size,
                       num_blocks=num_blocks, max_batch=max_batch,
                       max_seq_len=max_seq_len, prefill_chunk=prefill_chunk,
                       scheduler=scheduler, mesh=mesh, pipeline=pipeline,
                       telemetry=Telemetry(trace=bool(trace_out))
                       if telemetry or trace_out else False)
    if disagg:
        engine = DisaggCoordinator(params, cfg, spec=espec,
                                   transfer_ttl_steps=transfer_ttl_steps)
    else:
        engine = espec.build(params, cfg)
    if warmup:
        engine.warmup()
    compiles_after_warmup = None
    if engine.telemetry is not None:
        compiles_after_warmup = dict(
            engine.telemetry.summary()["jit_compiles"])
    handles, token_times, cancel_at, outs = {}, {}, {}, {}
    pending = list(work)
    step = 0
    n_submitted = 0
    while pending or engine.has_unfinished():
        while pending and pending[0][0] <= step:
            _, prompt, max_tokens, prio, c_after = pending.pop(0)
            # stochastic: a per-submission seed, so the stream is a
            # function of the request alone — identical across engine
            # architectures, preemption patterns, and batch composition
            sp = SamplingParams(temperature=1.1, top_k=50,
                                seed=9000 + n_submitted) \
                if stochastic else SamplingParams()
            n_submitted += 1
            h = engine.submit(prompt, sampling=sp,
                              max_tokens=max_tokens, priority=prio)
            handles[h.rid] = h
            token_times[h.rid] = []
            if c_after is not None:
                cancel_at[h.rid] = step + c_after
        for rid, at in list(cancel_at.items()):
            if handles[rid].finished:
                del cancel_at[rid]           # finished before the disconnect
            elif at <= step:
                engine.cancel(rid)
                del cancel_at[rid]
        events = engine.step()
        now = time.perf_counter()
        for ev in events:
            if ev.kind == EVENT_TOKEN:
                token_times[ev.rid].extend([now] * len(ev.tokens))
            elif ev.terminal:
                outs[ev.rid] = ev.output
        step += 1
    pools = [("kv", engine.kv)] if not disagg else \
        [("prefill", engine.prefill_engine.kv),
         ("decode", engine.decode_engine.kv)]
    for tag, kv in pools:
        kv.check_invariants()
        leaked = (kv.num_blocks - 1) - kv.num_available
        assert leaked == 0, f"churn leaked {leaked} {tag} KV blocks"
    assert len(outs) == len(work), "some requests never reached terminal"
    if disagg:
        assert engine.decode_engine.prefill_tokens_total == 0, \
            "prefill chunks ran on the decode engine"

    def pct_ms(xs, q):
        if not len(xs):
            return None
        return float(np.percentile(np.asarray(xs), q)) * 1e3

    def tier_stats(prio):
        mine = [o for o in outs.values() if o.priority == prio]
        ttfts = [o.ttft for o in mine if o.token_ids]
        itls = []
        for o in mine:
            ts = token_times[o.rid]
            itls.extend(float(b - a) for a, b in zip(ts, ts[1:]))
        return {"requests": len(mine),
                "ttft_p50_ms": pct_ms(ttfts, 50),
                "ttft_p95_ms": pct_ms(ttfts, 95),
                "itl_p50_ms": pct_ms(itls, 50),
                "itl_p95_ms": pct_ms(itls, 95)}

    cancelled = [o for o in outs.values() if o.finish_reason == "cancelled"]
    compiles_total = None
    if engine.telemetry is not None:
        compiles_total = dict(engine.telemetry.summary()["jit_compiles"])
        if trace_out:
            engine.export_trace(trace_out)
            print(f"# churn chrome trace -> {trace_out}")
    result = {"scheduler": scheduler, "steps": step,
              "requests": len(work),
              "cancelled": len(cancelled),
              "preempted": engine.preempted_total,
              "pipeline": pipeline,
              "stochastic": stochastic,
              "warmup_shapes": len(engine.warmup_report),
              "jit_compiles_after_warmup": compiles_after_warmup,
              "jit_compiles_total": compiles_total,
              "tiers": {"hi": tier_stats(1), "lo": tier_stats(0)},
              "outputs": {rid: o.token_ids for rid, o in outs.items()
                          if o.finish_reason != "cancelled"}}
    if disagg:
        finished = [o for o in outs.values()
                    if o.finish_reason != "cancelled"]
        buf = engine.buffer
        result["disagg"] = {
            "migrated_blocks_total": engine.migrated_blocks_total,
            "decode_prefill_tokens": engine.decode_engine.
            prefill_tokens_total,
            "transfer_wait_ms_mean": float(np.mean(
                [o.transfer_wait_ms for o in finished])) if finished
            else None,
            "expired": engine.expired_total,
            "transfer": {"published": buf.published_total,
                         "claimed": buf.claimed_total,
                         "cancelled": buf.cancelled_total,
                         "expired": buf.expired_total,
                         "capacity": buf.max_entries,
                         "ttl_steps": buf.ttl_steps},
        }
    return result


def run_attention_sweep(params, cfg, *, backend: str, block_size: int,
                        max_batch: int, seq_lens, out_tokens: int,
                        prefill_chunk: int, seed: int, mesh=None,
                        assert_speed: bool = False):
    """Long-context decode sweep across attention backends.

    One engine per attention backend (the plain-JAX ``ref`` gather + the
    fused paged kernel — ``pallas`` on TPU, ``interpret`` elsewhere) decodes
    the same fixed-length batches at every seq_len bucket up to the table
    width. Greedy outputs must be token-identical per bucket; per-bucket
    mean decode-step wall ms is reported so the kernel's scaling with
    context length is trackable. The wall-clock advantage is asserted only
    under ``assert_speed`` (full profile on a real TPU): interpret mode on
    CPU exists for numerics, not speed.
    """
    kernel = "pallas" if jax.default_backend() == "tpu" else "interpret"
    attn_backends = ["ref", kernel]
    max_seq = max(seq_lens)
    rng = np.random.RandomState(seed)
    prompts = {L: [rng.randint(0, cfg.vocab_size, L - out_tokens).tolist()
                   for _ in range(max_batch)] for L in seq_lens}
    per = {a: {} for a in attn_backends}
    for attn in attn_backends:
        engine = EngineSpec(backend=backend, attn_backend=attn,
                            block_size=block_size, max_batch=max_batch,
                            max_seq_len=max_seq, prefix_cache=False,
                            prefill_chunk=prefill_chunk,
                            mesh=mesh).build(params, cfg)
        for L in seq_lens:
            batch = [list(p) for p in prompts[L]]
            engine.generate(batch, max_tokens=out_tokens)   # compile pass
            engine.stats.clear()
            outs = engine.generate(batch, max_tokens=out_tokens)
            decode_ms = [s.wall_ms for s in engine.stats
                         if s.decode_batch > 0 and s.prefill_tokens == 0]
            per[attn][L] = {"ms": float(np.mean(decode_ms)),
                            "outputs": [o.token_ids for o in outs]}
    rows = []
    for L in seq_lens:
        assert per["ref"][L]["outputs"] == per[kernel][L]["outputs"], (
            f"attention backend {kernel} diverged from ref at seq_len={L}")
        rows.append({"seq_len": L, "pages": -(-L // block_size),
                     "ref_step_wall_ms": per["ref"][L]["ms"],
                     "kernel_step_wall_ms": per[kernel][L]["ms"]})
    if assert_speed:
        last = rows[-1]
        assert last["kernel_step_wall_ms"] < last["ref_step_wall_ms"], (
            f"paged kernel slower than ref at seq_len={last['seq_len']}: "
            f"{last['kernel_step_wall_ms']:.2f} vs "
            f"{last['ref_step_wall_ms']:.2f} ms")
    return {"backends": attn_backends, "kernel": kernel,
            "outputs_identical": True, "out_tokens": out_tokens,
            "batch": max_batch, "sweep": rows}


def run_backend(params, cfg, backend: str, work, *, block_size: int,
                max_batch: int, max_seq_len: int, prefix_cache: bool = True,
                prefill_chunk: int = 64, mesh=None, spec=None,
                telemetry: bool = False, trace_out=None,
                pipeline: bool = False, warmup: bool = False):
    engine = EngineSpec(backend=backend,
                        block_size=block_size, max_batch=max_batch,
                        max_seq_len=max_seq_len,
                        prefix_cache=prefix_cache,
                        prefill_chunk=prefill_chunk, mesh=mesh, spec=spec,
                        pipeline=pipeline,
                        telemetry=Telemetry() if telemetry or trace_out
                        else False).build(params, cfg)
    if warmup:
        engine.warmup()    # before the compile-replay: its wall time is the
        # (exhaustive) compile cost, so compile_wall below stays ~0

    def reset_cache():
        # measured run starts from a cold cache so hit rates reflect sharing
        # WITHIN the workload, not leftovers from warmup
        engine.kv = type(engine.kv)(engine.kv.cfg, engine.kv.num_blocks,
                                    engine.kv.block_size, mesh=mesh)
        engine.prefill_tokens_total = 0
        engine.cached_tokens_total = 0
        engine.prompt_tokens_total = 0

    def replay():
        outs = {}
        pending = list(work)
        step = 0
        while pending or engine.has_unfinished():
            while pending and pending[0][0] <= step:
                _, prompt, max_tokens = pending.pop(0)
                engine.add_request(prompt, sampling=SamplingParams(),
                                   max_tokens=max_tokens)
            for o in finished_outputs(engine.step()):
                outs[o.rid] = o
            step += 1
        return outs

    # warmup: jit caches are per-engine, so compile every prefill/decode
    # bucket this workload hits by replaying it once on the SAME engine.
    # Its wall time is the compile cost (the steady-state replay re-hits
    # every cached executable), so the compile/steady split falls out free.
    t0 = time.perf_counter()
    replay()
    compile_wall = time.perf_counter() - t0
    engine.stats.clear()
    reset_cache()                 # device pool realloc stays OUTSIDE the timer
    t0 = time.perf_counter()
    outs = replay()
    wall = time.perf_counter() - t0
    total = sum(len(o.token_ids) for o in outs.values())
    ttfts = np.array([o.ttft for o in outs.values()])
    comp = [s.decode_batch for s in engine.stats]
    prompt_toks = engine.prompt_tokens_total
    step_wall = np.array([s.wall_ms for s in engine.stats])
    step_sync = np.array([s.sync_ms for s in engine.stats])
    step_overlap = np.array([s.overlap_ms for s in engine.stats])
    telemetry_summary = None
    if engine.telemetry is not None:
        # covers warmup + measured replays (jit compile counts only make
        # sense over both; the steady-state numbers live in step_* fields)
        telemetry_summary = engine.telemetry.summary()
        if trace_out:
            engine.export_trace(trace_out)
            print(f"# {backend} chrome trace -> {trace_out}")
    return {"backend": backend, "wall": wall, "tokens": total,
            "telemetry": telemetry_summary,
            "toks_per_s": total / wall, "ttft_mean_ms": ttfts.mean() * 1e3,
            "ttft_p90_ms": float(np.percentile(ttfts, 90)) * 1e3,
            "steps": len(engine.stats), "composition": comp,
            "free_trace": [s.free_blocks for s in engine.stats],
            "reserved_trace": [s.reserved_blocks for s in engine.stats],
            "step_wall_ms": step_wall.round(3).tolist(),
            "step_sync_ms": step_sync.round(3).tolist(),
            "compile_wall_s": compile_wall,
            "steady_wall_s": wall,
            "step_wall_ms_mean": float(step_wall.mean()),
            "step_wall_ms_p90": float(np.percentile(step_wall, 90)),
            "step_sync_ms_mean": float(step_sync.mean()),
            "sync_frac": float(step_sync.sum() / max(step_wall.sum(), 1e-9)),
            "pipeline": pipeline,
            "warmup_shapes": len(engine.warmup_report),
            "warmup_seconds": engine.warmup_seconds,
            "step_overlap_ms_mean": float(step_overlap.mean()),
            "prefix_cache": prefix_cache,
            "prompt_tokens": prompt_toks,
            "prefill_tokens": engine.prefill_tokens_total,
            "cached_tokens": engine.cached_tokens_total,
            "cache_hit_rate": engine.cached_tokens_total / max(prompt_toks, 1),
            "outputs": {rid: o.token_ids for rid, o in outs.items()}}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-0.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--num-requests", type=int, default=10)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backends", default="dense,gather")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: 2 requests, dense only")
    ap.add_argument("--json-out",
                    default=os.path.join(REPO_ROOT, "BENCH_serving.json"),
                    help="machine-readable results path ('' = skip)")
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--shared-prefix-requests", type=int, default=6,
                    help="requests in the shared-system-prompt workload")
    ap.add_argument("--churn-requests", type=int, default=12,
                    help="requests in the churn workload (Poisson arrivals, "
                         "cancellations, two priority tiers)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (shard params + paged KV "
                         "pools over a 1-D mesh; needs >= tp devices, e.g. "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="write a Chrome-trace JSON of the telemetry-on "
                         "replay (open in chrome://tracing); '' = skip")
    args = ap.parse_args(argv)
    if args.smoke:
        args.num_requests = 2
        args.backends = "dense"
        args.shared_prefix_requests = 3
        args.churn_requests = 8       # seed-0 draw includes 1 cancellation

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_serving_mesh(args.tp) if args.tp > 1 else None
    params = lm.init(jax.random.PRNGKey(args.seed), cfg)
    work = make_workload(args.num_requests, cfg.vocab_size, args.seed)
    max_seq_len = max(len(p) + m for _, p, m in work)
    max_seq_len = -(-max_seq_len // args.block_size) * args.block_size

    print(f"# bench_serving arch={cfg.name} reduced={args.reduced} "
          f"requests={args.num_requests} block_size={args.block_size} "
          f"max_batch={args.max_batch} tp={args.tp}")
    print("backend,tok_s,ttft_mean_ms,ttft_p90_ms,steps,total_tokens")
    results = []
    for backend in args.backends.split(","):
        r = run_backend(params, cfg, backend.strip(), work,
                        block_size=args.block_size,
                        max_batch=args.max_batch, max_seq_len=max_seq_len,
                        prefill_chunk=args.prefill_chunk, mesh=mesh)
        results.append(r)
        print(f"{r['backend']},{r['toks_per_s']:.1f},"
              f"{r['ttft_mean_ms']:.1f},{r['ttft_p90_ms']:.1f},"
              f"{r['steps']},{r['tokens']}", flush=True)
    for r in results:
        comp = r["composition"]
        print(f"# {r['backend']} decode-batch per step: {comp}")
        print(f"# {r['backend']} admissible/reserved blocks per step: "
              f"{list(zip(r['free_trace'], r['reserved_trace']))}")
        print(f"# {r['backend']} compile {r['compile_wall_s']:.2f}s, steady "
              f"{r['steady_wall_s']:.2f}s; step wall "
              f"{r['step_wall_ms_mean']:.2f}ms mean / "
              f"{r['step_wall_ms_p90']:.2f}ms p90, device-sync share "
              f"{r['sync_frac']:.1%}")
        assert len(set(comp)) > 1, \
            "batch composition never changed — not continuous batching"
    print("# composition varies across steps: continuous batching confirmed")

    # ---- telemetry: on-vs-off parity + overhead + phase breakdown ---------
    # same workload through the first backend with the full telemetry
    # subsystem on (metrics + request tracing): greedy outputs must be
    # token-identical to the telemetry-off run above. The off run IS the
    # pre-telemetry engine path (instrumentation is skipped entirely), so
    # the reported overhead is what turning the subsystem on costs; with
    # sub-ms steps on shared CPU it is noise-dominated — informative only.
    backend0 = args.backends.split(",")[0].strip()
    tm_run = run_backend(params, cfg, backend0, work,
                         block_size=args.block_size,
                         max_batch=args.max_batch, max_seq_len=max_seq_len,
                         prefill_chunk=args.prefill_chunk, mesh=mesh,
                         telemetry=True, trace_out=args.trace_out or None)
    base = results[0]
    assert tm_run["outputs"] == base["outputs"], \
        "telemetry changed greedy outputs"
    overhead = tm_run["step_wall_ms_mean"] / base["step_wall_ms_mean"] - 1
    tm = tm_run["telemetry"]
    print(f"# telemetry on-vs-off ({backend0}): outputs identical, step "
          f"wall {base['step_wall_ms_mean']:.2f} -> "
          f"{tm_run['step_wall_ms_mean']:.2f}ms mean "
          f"({overhead:+.1%} overhead)")
    print("# telemetry phase ms/step: " + ", ".join(
        f"{k}={v:.2f}" for k, v in sorted(tm["phases_ms_mean"].items())))
    for name in ("ttft_s", "itl_s"):
        for tier, snap in sorted(tm[name].items()):
            if snap["count"]:
                print(f"# telemetry {name} prio={tier}: n={snap['count']} "
                      f"mean={snap['sum'] / snap['count'] * 1e3:.1f}ms")
    print(f"# telemetry: {int(tm['tokens_generated'])} tokens over "
          f"{int(tm['steps'])} steps, {tm['trace_events']} trace events")

    # ---- pipelined step path: identity, overlap, warmup compile flatness --
    # the same workload through the plan/launch/collect pipeline with the
    # bucket grid precompiled at startup: greedy outputs must be token-
    # identical to the synchronous run, and NOTHING may JIT-compile after
    # warmup (every steady-state shape is a warmup shape). sync_ms in
    # pipelined mode is the residual blocking left after the async device→
    # host token copy overlaps with next-step planning; on CPU the drop vs
    # the synchronous path is noise-dominated, so it is reported, not gated.
    pipe_trace = None
    if args.trace_out:
        root, ext = os.path.splitext(args.trace_out)
        pipe_trace = root + ".pipeline" + (ext or ".json")
    pipe_run = run_backend(params, cfg, backend0, work,
                           block_size=args.block_size,
                           max_batch=args.max_batch, max_seq_len=max_seq_len,
                           prefill_chunk=args.prefill_chunk, mesh=mesh,
                           telemetry=True, pipeline=True, warmup=True,
                           trace_out=pipe_trace)
    assert pipe_run["outputs"] == base["outputs"], \
        "pipelined engine changed greedy outputs"
    pipe_compiles = sum(pipe_run["telemetry"]["jit_compiles"].values())
    steady_compiles = int(pipe_compiles) - pipe_run["warmup_shapes"]
    assert steady_compiles == 0, (
        f"{steady_compiles} JIT compiles AFTER warmup — the bucket grid "
        f"precompile missed a steady-state shape")
    sync_drop = 1 - pipe_run["step_sync_ms_mean"] / \
        max(base["step_sync_ms_mean"], 1e-9)
    print(f"# pipeline on-vs-off ({backend0}): outputs identical, "
          f"{pipe_run['warmup_shapes']} shapes warmed in "
          f"{pipe_run['warmup_seconds']:.1f}s, 0 steady-state compiles; "
          f"sync {base['step_sync_ms_mean']:.2f} -> "
          f"{pipe_run['step_sync_ms_mean']:.2f}ms mean ({sync_drop:+.1%}), "
          f"overlap {pipe_run['step_overlap_ms_mean']:.2f}ms/step")

    # ---- shared-system-prompt workload: prefix caching on vs off ----------
    shared = make_shared_prefix_workload(args.shared_prefix_requests,
                                         cfg.vocab_size, args.seed)
    shared_seq = max(len(p) + m for _, p, m in shared)
    shared_seq = -(-shared_seq // args.block_size) * args.block_size
    cache_runs = {}
    for on in (False, True):
        cache_runs[on] = run_backend(
            params, cfg, backend0, shared, block_size=args.block_size,
            max_batch=args.max_batch, max_seq_len=shared_seq,
            prefix_cache=on, prefill_chunk=args.prefill_chunk, mesh=mesh)
    hit, miss = cache_runs[True], cache_runs[False]
    assert hit["outputs"] == miss["outputs"], \
        "prefix caching changed greedy outputs"
    assert hit["prefill_tokens"] < miss["prefill_tokens"], (
        f"caching saved nothing: {hit['prefill_tokens']} vs "
        f"{miss['prefill_tokens']} prefill tokens")
    assert hit["cache_hit_rate"] > 0
    savings = 1 - hit["prefill_tokens"] / miss["prefill_tokens"]
    print(f"# shared-prefix workload ({args.shared_prefix_requests} reqs): "
          f"hit rate {hit['cache_hit_rate']:.1%}, prefill tokens "
          f"{miss['prefill_tokens']} -> {hit['prefill_tokens']} "
          f"({savings:.1%} saved), outputs identical")

    # ---- churn: Poisson arrivals, cancellations, two priority tiers -------
    churn_work = make_churn_workload(args.churn_requests, cfg.vocab_size,
                                     args.seed)
    churn_seq = max(len(p) + m for _, p, m, _, _ in churn_work)
    churn_seq = -(-churn_seq // args.block_size) * args.block_size
    worst = -(-churn_seq // args.block_size)
    # pool sized for ~2 worst-case requests: small enough that the priority
    # scheduler actually preempts low-tier decodes under the burst
    tight = 1 + 2 * worst
    churn = run_churn(params, cfg, churn_work, backend=backend0,
                      scheduler="priority", block_size=args.block_size,
                      max_batch=args.max_batch, max_seq_len=churn_seq,
                      num_blocks=tight, prefill_chunk=args.prefill_chunk,
                      mesh=mesh)
    print(f"# churn ({args.churn_requests} reqs, priority scheduler, "
          f"{tight} blocks): {churn['cancelled']} cancelled, "
          f"{churn['preempted']} preempted, {churn['steps']} steps, "
          f"pool drained clean")
    for tier in ("hi", "lo"):
        t = churn["tiers"][tier]
        if t["ttft_p50_ms"] is not None:
            print(f"#   {tier}: n={t['requests']} "
                  f"ttft p50/p95 {t['ttft_p50_ms']:.1f}/"
                  f"{t['ttft_p95_ms']:.1f}ms, "
                  f"itl p50/p95 {t['itl_p50_ms']:.1f}/"
                  f"{t['itl_p95_ms']:.1f}ms")

    # ---- pipelined churn: full lifecycle churn compiles nothing -----------
    # the hardest compile-flatness test: cancels, preemptions, resumes and
    # every batch size the tight pool forces, all through the pipelined
    # path — the jit counters must not move from their post-warmup values
    churn_trace = None
    if args.trace_out:
        root, ext = os.path.splitext(args.trace_out)
        churn_trace = root + ".churn.pipeline" + (ext or ".json")
    pipe_churn = run_churn(params, cfg, churn_work, backend=backend0,
                           scheduler="priority", block_size=args.block_size,
                           max_batch=args.max_batch, max_seq_len=churn_seq,
                           num_blocks=tight, prefill_chunk=args.prefill_chunk,
                           mesh=mesh, pipeline=True, warmup=True,
                           telemetry=True, trace_out=churn_trace)
    churn_compile_delta = {
        k: pipe_churn["jit_compiles_total"][k] -
        pipe_churn["jit_compiles_after_warmup"][k]
        for k in pipe_churn["jit_compiles_total"]}
    assert all(v == 0 for v in churn_compile_delta.values()), (
        f"pipelined churn JIT-compiled after warmup: {churn_compile_delta}")
    print(f"# pipelined churn: {pipe_churn['cancelled']} cancelled, "
          f"{pipe_churn['preempted']} preempted over "
          f"{pipe_churn['steps']} steps; jit counters flat after warmup "
          f"({pipe_churn['warmup_shapes']} shapes)")

    # ---- scheduler identity: FCFS == priority when nothing contends -------
    # same arrivals, no cancellations, ample pool/batch: policy must be
    # invisible in outputs (greedy token identity), only visible under load
    calm = [(t, p, m, prio, None) for t, p, m, prio, _ in churn_work]
    ident = {}
    for sched in ("fcfs", "priority"):
        ident[sched] = run_churn(params, cfg, calm, backend=backend0,
                                 scheduler=sched, block_size=args.block_size,
                                 max_batch=max(args.max_batch,
                                               len(calm)),
                                 max_seq_len=churn_seq,
                                 prefill_chunk=args.prefill_chunk, mesh=mesh)
    assert ident["fcfs"]["outputs"] == ident["priority"]["outputs"], \
        "scheduler policy changed greedy outputs on a no-contention workload"
    assert ident["fcfs"]["preempted"] == ident["priority"]["preempted"] == 0
    print("# scheduler identity: FCFS == priority token-identical "
          "(no contention)")

    # ---- disaggregated prefill/decode: identity under churn ---------------
    # the full churn workload (cancels, two tiers, tight pool pressure)
    # through the two-engine front door: every request that FINISHES in both
    # runs must be token-identical to the single unified engine, zero
    # prefill chunks may run on the decode engine, and both pools must
    # drain invariant-clean. Requests the driver cancels can straddle the
    # finish/cancel boundary differently across architectures (the disagg
    # path adds transfer steps), so only scheduled-cancel rids may differ.
    disagg_kw = dict(backend=backend0, scheduler="priority",
                     block_size=args.block_size, max_batch=args.max_batch,
                     max_seq_len=churn_seq, num_blocks=tight,
                     prefill_chunk=args.prefill_chunk, mesh=mesh)
    disagg_churn = run_churn(params, cfg, churn_work, disagg=True,
                             telemetry=True, **disagg_kw)
    cancel_rids = {i for i, (_, _, _, _, c) in enumerate(churn_work)
                   if c is not None}
    both = set(churn["outputs"]) & set(disagg_churn["outputs"])
    for rid in both:
        assert churn["outputs"][rid] == disagg_churn["outputs"][rid], (
            f"disagg diverged from the unified engine on rid {rid}")
    strays = set(churn["outputs"]) ^ set(disagg_churn["outputs"])
    assert strays <= cancel_rids, (
        f"non-cancelled requests differ in terminal state: "
        f"{sorted(strays - cancel_rids)}")
    dd = disagg_churn["disagg"]
    assert dd["decode_prefill_tokens"] == 0
    assert dd["migrated_blocks_total"] > 0
    assert dd["transfer"]["published"] == (dd["transfer"]["claimed"]
                                           + dd["transfer"]["cancelled"]
                                           + dd["transfer"]["expired"])
    print(f"# disagg churn: outputs token-identical to unified over "
          f"{len(both)} finished requests, "
          f"{dd['migrated_blocks_total']} blocks migrated "
          f"({dd['transfer']['claimed']} transfers claimed, "
          f"{dd['transfer']['cancelled']} cancelled, "
          f"{dd['transfer']['expired']} expired), 0 decode-side prefill "
          f"tokens, both pools drained clean")
    for tier in ("hi", "lo"):
        t = disagg_churn["tiers"][tier]
        if t["ttft_p50_ms"] is not None:
            print(f"#   disagg {tier}: n={t['requests']} "
                  f"ttft p50/p95 {t['ttft_p50_ms']:.1f}/"
                  f"{t['ttft_p95_ms']:.1f}ms, "
                  f"itl p50/p95 {t['itl_p50_ms']:.1f}/"
                  f"{t['itl_p95_ms']:.1f}ms")

    # seeded-stochastic identity on the no-cancel workload (tight pool, so
    # preemption composition still differs across architectures): streams
    # are a function of the request alone — STRICT full-set equality
    sto = {}
    for dis in (False, True):
        sto[dis] = run_churn(params, cfg, calm, disagg=dis, stochastic=True,
                             **disagg_kw)
    assert sto[False]["outputs"] == sto[True]["outputs"], \
        "disagg diverged from unified under seeded-stochastic sampling"
    assert sto[True]["disagg"]["decode_prefill_tokens"] == 0
    print(f"# disagg stochastic identity: all {len(calm)} seeded-sampling "
          f"streams identical to unified "
          f"(unified preempted {sto[False]['preempted']}, disagg "
          f"preempted {sto[True]['preempted']} — composition differs, "
          f"tokens cannot)")

    # ---- attention backends: long-context decode sweep --------------------
    # ref (gather-pages SDPA) vs the fused paged kernel at growing context
    # lengths: token identity is the gate everywhere; the wall-clock
    # advantage is asserted only on a real TPU under the full profile
    attn_seq_lens = [32, 64] if args.smoke else [32, 64, 128]
    attention = run_attention_sweep(
        params, cfg, backend=backend0, block_size=args.block_size,
        max_batch=2, seq_lens=attn_seq_lens, out_tokens=8,
        prefill_chunk=args.prefill_chunk, seed=args.seed, mesh=mesh,
        assert_speed=not args.smoke and jax.default_backend() == "tpu")
    print(f"# attention sweep (ref vs {attention['kernel']}, batch 2): "
          "outputs token-identical at every bucket")
    for row in attention["sweep"]:
        print(f"#   seq_len={row['seq_len']:4d} ({row['pages']} pages): "
              f"decode step {row['ref_step_wall_ms']:.2f}ms ref, "
              f"{row['kernel_step_wall_ms']:.2f}ms {attention['kernel']}")

    # ---- tp identity: sharded == unsharded, spec + prefix cache on --------
    tp_identity = None
    if mesh is not None:
        kwargs = dict(block_size=args.block_size, max_batch=args.max_batch,
                      max_seq_len=shared_seq, prefix_cache=True,
                      prefill_chunk=args.prefill_chunk,
                      spec=SpecConfig(k=2, draft_backend="tile_skip"))
        tp_run = run_backend(params, cfg, backend0, shared, mesh=mesh,
                             **kwargs)
        ref_run = run_backend(params, cfg, backend0, shared, mesh=None,
                              **kwargs)
        assert tp_run["outputs"] == ref_run["outputs"], \
            f"tp={args.tp} engine diverged from the unsharded engine"
        speedup = ref_run["steady_wall_s"] / tp_run["steady_wall_s"]
        tp_identity = {
            "tp": args.tp, "backend": backend0,
            "spec_k": 2, "prefix_cache": True,
            "outputs_identical": True,
            "steady_wall_s_tp": tp_run["steady_wall_s"],
            "steady_wall_s_tp1": ref_run["steady_wall_s"],
            "sync_frac_tp": tp_run["sync_frac"],
        }
        print(f"# tp={args.tp} identity: greedy outputs token-identical to "
              f"tp=1 (spec k=2 + prefix cache on, backend={backend0}); "
              f"steady wall {ref_run['steady_wall_s']:.2f}s -> "
              f"{tp_run['steady_wall_s']:.2f}s ({speedup:.2f}x on fake "
              f"host devices — expect >1 only on real accelerators)")

    def trim(r):
        return {k: v for k, v in r.items()
                if k not in ("composition", "outputs", "free_trace",
                             "reserved_trace", "step_wall_ms",
                             "step_sync_ms")}

    if args.json_out:
        write_bench_json(args.json_out, {
            "bench": "serving",
            "schema_version": common.BENCH_SCHEMA_VERSION,
            "meta": common.bench_meta(args.smoke),
            "arch": cfg.name, "reduced": args.reduced,
            "num_requests": args.num_requests,
            "block_size": args.block_size, "max_batch": args.max_batch,
            "prefill_chunk": args.prefill_chunk,
            "smoke": args.smoke,
            "tp": args.tp,
            "tp_identity": tp_identity,
            "attention": attention,
            "telemetry": {
                "backend": backend0,
                "outputs_identical": True,
                "step_wall_ms_mean_off": base["step_wall_ms_mean"],
                "step_wall_ms_mean_on": tm_run["step_wall_ms_mean"],
                "step_wall_overhead_frac": overhead,
                "summary": tm,
            },
            "results": [trim(r) for r in results],
            "pipeline": {
                "backend": backend0,
                "outputs_identical": True,
                "warmup_shapes": pipe_run["warmup_shapes"],
                "warmup_seconds": pipe_run["warmup_seconds"],
                "steady_compiles": steady_compiles,
                "step_wall_ms_mean_sync": base["step_wall_ms_mean"],
                "step_wall_ms_mean_pipeline": pipe_run["step_wall_ms_mean"],
                "step_sync_ms_mean_sync": base["step_sync_ms_mean"],
                "step_sync_ms_mean_pipeline": pipe_run["step_sync_ms_mean"],
                "sync_ms_drop_frac": sync_drop,
                "overlap_ms_mean": pipe_run["step_overlap_ms_mean"],
                "churn": {
                    "steps": pipe_churn["steps"],
                    "requests": pipe_churn["requests"],
                    "cancelled": pipe_churn["cancelled"],
                    "preempted": pipe_churn["preempted"],
                    "warmup_shapes": pipe_churn["warmup_shapes"],
                    "compiles_after_warmup_delta": churn_compile_delta,
                },
            },
            "churn": {k: v for k, v in churn.items() if k != "outputs"},
            "disagg": {
                "outputs_identical": True,
                "stochastic_outputs_identical": True,
                "finished_compared": len(both),
                "steps": disagg_churn["steps"],
                "requests": disagg_churn["requests"],
                "cancelled": disagg_churn["cancelled"],
                "preempted": disagg_churn["preempted"],
                "tiers": disagg_churn["tiers"],
                **disagg_churn["disagg"],
            },
            "scheduler_identity": {
                "workload": "churn arrivals, no cancellations, ample pool",
                "outputs_identical": True,
                "schedulers": ["fcfs", "priority"],
            },
            "shared_prefix": {
                "num_requests": args.shared_prefix_requests,
                "cache_hit_rate": hit["cache_hit_rate"],
                "prompt_tokens": hit["prompt_tokens"],
                "prefill_tokens_cached": hit["prefill_tokens"],
                "prefill_tokens_baseline": miss["prefill_tokens"],
                "prefill_tokens_saved_frac": savings,
                "outputs_identical": True,
            },
        })
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
