"""Speculative-decoding benchmark: tok/s + acceptance rate vs k / threshold.

Replays the same mixed-length staggered workload through the
``ServingEngine`` once without speculation (baseline) and once per
speculative configuration (draft-k x tile-skip draft threshold), reporting
throughput, tokens committed per engine step, and the draft acceptance rate
— the serving-side realization of the paper's claim that one set of weights
spans a spectrum of sparse execution paths: the >99%-sparsity tile-skip
path drafts, the exact path verifies, and greedy output is token-identical
to non-speculative decoding at any acceptance rate.

  PYTHONPATH=src python benchmarks/bench_spec_decode.py --reduced

Emits machine-readable ``BENCH_spec_decode.json`` at the repo root.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from bench_serving import REPO_ROOT, make_workload, write_bench_json

import common as bench_common
from repro.configs import get_config
from repro.models import lm
from repro.serving import (EngineSpec, SamplingParams, SpecConfig,
                           Telemetry, finished_outputs)


def run_mode(params, cfg, work, *, backend: str, spec, block_size: int,
             max_batch: int, max_seq_len: int, label: str):
    def build():
        # telemetry on for every mode (baseline included) so the
        # draft/verify/sample phase split and the per-step acceptance
        # histogram land in the bench record with uniform instrumentation
        espec = EngineSpec(backend=backend, block_size=block_size,
                           max_batch=max_batch, max_seq_len=max_seq_len,
                           spec=spec, telemetry=Telemetry(trace=False))
        return espec.build(params, cfg)

    def replay(engine):
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

    engine = build()
    replay(engine)                      # warmup: compile every bucket
    engine.stats.clear()
    t0 = time.perf_counter()
    outs = replay(engine)
    wall = time.perf_counter() - t0
    total = sum(len(o.token_ids) for o in outs.values())
    drafted = sum(o.spec_drafted for o in outs.values())
    accepted = sum(o.spec_accepted for o in outs.values())
    steps = len(engine.stats)
    tm = engine.telemetry.summary()
    return {
        "mode": label,
        "k": 0 if spec is None else spec.k,
        "draft_threshold": 0.0 if spec is None else spec.draft_threshold,
        "wall": wall, "tokens": total, "toks_per_s": total / wall,
        "steps": steps, "toks_per_step": total / max(steps, 1),
        "drafted": drafted, "accepted": accepted,
        "acceptance_rate": accepted / drafted if drafted else None,
        "phases_ms_mean": tm["phases_ms_mean"],
        "spec_acceptance_hist": tm["spec_acceptance_hist"],
        "jit_compiles": tm["jit_compiles"],
    }, outs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-0.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--num-requests", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="dense",
                    help="trusted (verify) backend: dense | gather")
    ap.add_argument("--draft-backend", default="tile_skip")
    ap.add_argument("--ks", default="2,4",
                    help="comma-separated draft lengths to sweep")
    ap.add_argument("--thresholds", default="0.0,0.3",
                    help="comma-separated tile-skip draft thresholds")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: 2 requests, k=2, one threshold")
    ap.add_argument("--json-out",
                    default=os.path.join(REPO_ROOT, "BENCH_spec_decode.json"),
                    help="machine-readable results path ('' = skip)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.num_requests = 2
        args.ks = "2"
        args.thresholds = "0.0"

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = lm.init(jax.random.PRNGKey(args.seed), cfg)
    work = make_workload(args.num_requests, cfg.vocab_size, args.seed)
    max_seq_len = max(len(p) + m for _, p, m in work)
    max_seq_len = -(-max_seq_len // args.block_size) * args.block_size
    common = dict(backend=args.backend, block_size=args.block_size,
                  max_batch=args.max_batch, max_seq_len=max_seq_len)

    print(f"# bench_spec_decode arch={cfg.name} reduced={args.reduced} "
          f"requests={args.num_requests} verify={args.backend} "
          f"draft={args.draft_backend}")
    print("mode,k,threshold,tok_s,tok_per_step,acceptance,steps,tokens")

    base, base_outs = run_mode(params, cfg, work, spec=None,
                               label="non-spec", **common)
    results = [base]
    rows = [(r, list(base_outs[r].token_ids)) for r in sorted(base_outs)]
    for r in results:
        print(f"{r['mode']},{r['k']},{r['draft_threshold']},"
              f"{r['toks_per_s']:.1f},{r['toks_per_step']:.2f},"
              f"-,{r['steps']},{r['tokens']}", flush=True)

    for k in [int(s) for s in args.ks.split(",")]:
        for thr in [float(s) for s in args.thresholds.split(",")]:
            spec = SpecConfig(k=k, draft_backend=args.draft_backend,
                              draft_threshold=thr)
            r, outs = run_mode(params, cfg, work, spec=spec,
                               label=f"spec-k{k}-t{thr}", **common)
            results.append(r)
            acc = r["acceptance_rate"]
            print(f"{r['mode']},{r['k']},{r['draft_threshold']},"
                  f"{r['toks_per_s']:.1f},{r['toks_per_step']:.2f},"
                  f"{acc:.3f},{r['steps']},{r['tokens']}", flush=True)
            # greedy spec decode must be token-identical to the baseline
            got = [(rid, list(outs[rid].token_ids)) for rid in sorted(outs)]
            assert got == rows, \
                f"spec-k{k}-t{thr} diverged from non-speculative greedy"
    print("# greedy spec output token-identical to non-spec: confirmed")
    for r in results:
        print(f"# {r['mode']} phase ms/step: " + ", ".join(
            f"{k}={v:.2f}" for k, v in sorted(r["phases_ms_mean"].items())))

    if args.json_out:
        write_bench_json(args.json_out, {
            "bench": "spec_decode",
            "schema_version": bench_common.BENCH_SCHEMA_VERSION,
            "meta": bench_common.bench_meta(args.smoke),
            "arch": cfg.name, "reduced": args.reduced,
            "num_requests": args.num_requests,
            "verify_backend": args.backend,
            "draft_backend": args.draft_backend,
            "block_size": args.block_size, "max_batch": args.max_batch,
            "smoke": args.smoke,
            "results": results,
        })
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
