"""Serving CLI: continuous-batching engine over the TwELL inference path.

The heavy lifting lives in ``repro.serving``: a continuous-batching engine
(``ServingEngine``) with a paged KV-cache pool, per-request sampling, and a
pluggable FFN backend (``--ffn-impl dense | gather | tile_skip``) so the
paper's sparse decode path (Algorithm 1/2, Eq. 3) and the dense baseline are
one flag apart. This module is a thin CLI plus the *static reference loop*
(``generate``) that the engine is regression-tested against.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch paper-0.5b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import lm


def generate(params, cfg, prompt: jax.Array, steps: int, cache_len: int,
             greedy: bool = True, extras=None,
             key: Optional[jax.Array] = None, top_k: int = 0,
             temperature: float = 1.0):
    """Static reference loop: prompt (B, P) -> tokens (B, P+steps).

    Fixed-shape batch, monolithic cache, prefill by teacher-forcing the
    prompt through decode (cache-exact). Kept as the numerically-trusted
    baseline the continuous-batching engine must reproduce token-for-token
    (greedy), and as the fallback for model families the paged engine does
    not cover yet. Stochastic sampling threads ``key`` through the loop —
    one fresh subkey per step (a constant per-step key would replay the
    same draw pattern every iteration).
    """
    b, p = prompt.shape
    cache = lm.init_cache(cfg, b, cache_len,
                          enc_len=extras["frames"].shape[1] if extras and
                          "frames" in extras else 0,
                          num_patches=cfg.num_image_tokens)
    if key is None:
        key = jax.random.PRNGKey(0)

    decode = jax.jit(lambda pr, c, t: lm.decode_step(pr, c, t, cfg),
                     donate_argnums=(1,))
    # prefill by teacher-forcing the prompt through decode (cache-exact)
    toks = prompt
    logits = None
    for i in range(p):
        logits, cache = decode(params, cache, toks[:, i:i + 1])
    out = [toks]
    for _ in range(steps):
        if greedy:
            nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            lg = logits[:, -1].astype(jnp.float32) / max(temperature, 1e-6)
            if top_k:
                tk = min(top_k, lg.shape[-1])    # top_k > vocab = no-op
                kth = -jnp.sort(-lg, axis=-1)[:, tk - 1, None]
                lg = jnp.where(lg >= kth, lg, -jnp.inf)
            nxt = jax.random.categorical(sub, lg).astype(jnp.int32)[:, None]
        out.append(nxt)
        logits, cache = decode(params, cache, nxt)
    return jnp.concatenate(out, axis=1)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--ffn-impl", default="gather",
                    help="dense | gather (TwELL fused path) | tile_skip")
    ap.add_argument("--attn-backend", default=None,
                    choices=("ref", "pallas", "interpret"),
                    help="paged-attention read path (default: pallas on a "
                         "TPU, ref elsewhere): ref (gather-pages SDPA, the "
                         "numerics reference), pallas (fused paged kernels, "
                         "TPU only), interpret (same kernels via Pallas "
                         "interpret mode — off-TPU only, slow). Validated "
                         "against the platform at startup")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV-cache block size (tokens)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="max prompt tokens prefilled per engine step "
                         "(long prompts interleave with decode)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix KV reuse")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="engine decode-batch cap (0 = --batch)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft tokens per step "
                         "(0 = off)")
    ap.add_argument("--draft-backend", default="tile_skip",
                    help="spec draft path: tile_skip | gather | dense")
    ap.add_argument("--draft-threshold", type=float, default=0.0,
                    help="tile-skip gate threshold for the draft pass "
                         "(higher = sparser/cheaper draft, lower acceptance)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard params + paged KV "
                         "pools over a 1-D device mesh (1 = unsharded; "
                         "CPU testing: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--mesh", action="store_true",
                    help="run the mesh-sharded engine path even at --tp 1 "
                         "(exercises the sharded code path on one device)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving: a prefill engine and a "
                         "decode engine with separate KV pools in one "
                         "process, bridged by a KV-block transfer buffer "
                         "(requests migrate after prefill and decode "
                         "without prefill interference; see "
                         "docs/serving.md)")
    ap.add_argument("--transfer-ttl", type=int, default=64,
                    help="--disagg: steps an unclaimed KV transfer survives "
                         "before it expires and the request re-queues")
    ap.add_argument("--scheduler", default="fcfs",
                    help="admission policy: fcfs | priority (priority "
                         "preempts lower-priority running requests under "
                         "pool pressure; they resume via the prefix cache)")
    ap.add_argument("--http", action="store_true",
                    help="serve an OpenAI-style HTTP API "
                         "(/v1/completions with SSE streaming; client "
                         "disconnect cancels the request) instead of "
                         "running the one-shot batch demo")
    ap.add_argument("--metrics", dest="metrics", action="store_true",
                    default=None,
                    help="enable the telemetry subsystem (metrics registry "
                         "+ request tracing); default: on with --http "
                         "(serving GET /metrics), off for the batch demo")
    ap.add_argument("--no-metrics", dest="metrics", action="store_false",
                    help="disable telemetry even with --http "
                         "(GET /metrics then returns 503)")
    ap.add_argument("--pipeline", dest="pipeline", action="store_true",
                    default=None,
                    help="overlapped plan/launch/collect step pipeline: "
                         "host scheduling for step N+1 runs while the "
                         "device executes step N (token-identical to the "
                         "synchronous path); default: on with --http, off "
                         "for the batch demo")
    ap.add_argument("--no-pipeline", dest="pipeline", action="store_false",
                    help="force the synchronous reference step path")
    ap.add_argument("--warmup", dest="warmup", action="store_true",
                    default=None,
                    help="precompile the full power-of-two bucket grid at "
                         "startup so steady-state serving never JIT-"
                         "compiles; with --http, /healthz answers 503 until "
                         "warmup finishes; default: on with --http, off for "
                         "the batch demo")
    ap.add_argument("--no-warmup", dest="warmup", action="store_false",
                    help="skip startup precompilation (shapes compile "
                         "lazily on first use)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of the run (engine step "
                         "phases + one track per request; open in "
                         "chrome://tracing or ui.perfetto.dev). Batch mode "
                         "exports after generation; --http exports at "
                         "shutdown. Implies --metrics.")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="also run jax.profiler over the generation / "
                         "serving window, writing an XLA-level device "
                         "trace to DIR (view with TensorBoard or Perfetto)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="HTTP port (0 = pick a free port; the chosen one "
                         "is printed)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--static", action="store_true",
                    help="use the fixed-shape reference loop instead of the "
                         "continuous-batching engine")
    ap.add_argument("--check-static", action="store_true",
                    help="greedy only: verify the engine reproduces the "
                         "static loop token-for-token (default when "
                         "--reduced)")
    return ap.parse_args(argv)


def model_config(args: argparse.Namespace):
    """The model the flags describe: ``--arch`` (``--reduced``) with the
    ``--ffn-impl`` stamped in. Weights are random, from ``--seed``."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    return dataclasses.replace(
        cfg, sparsity=dataclasses.replace(cfg.sparsity,
                                          ffn_impl=args.ffn_impl))


def engine_spec(args: argparse.Namespace, mesh=None):
    """The ``EngineSpec`` the flags describe — the one place that resolves
    the serving defaults: telemetry (with request tracing), the pipelined
    step loop and warmup are on with ``--http`` and off for the one-shot
    batch demo unless a flag says otherwise."""
    from repro.serving import EngineSpec, SpecConfig, Telemetry
    spec = None
    if args.spec_k:
        spec = SpecConfig(k=args.spec_k, draft_backend=args.draft_backend,
                          draft_threshold=args.draft_threshold)
    use_telemetry = args.http if args.metrics is None else args.metrics
    if args.trace_out:
        use_telemetry = True
    telemetry = Telemetry(trace=bool(args.trace_out) or args.http) \
        if use_telemetry else False
    use_pipeline = args.http if args.pipeline is None else args.pipeline
    if args.disagg:
        if args.pipeline:
            raise SystemExit("--disagg runs synchronous engines (KV "
                             "withdraw cannot race a launched step); drop "
                             "--pipeline")
        use_pipeline = False
        if mesh is not None:
            raise SystemExit("--disagg requires unsharded KV pools; drop "
                             "--tp/--mesh")
    return EngineSpec(
        backend=args.ffn_impl,
        attn_backend=args.attn_backend, block_size=args.block_size,
        max_batch=args.max_batch or args.batch,
        max_seq_len=args.prompt_len + args.gen, seed=args.seed, spec=spec,
        prefix_cache=not args.no_prefix_cache,
        prefill_chunk=args.prefill_chunk, scheduler=args.scheduler,
        telemetry=telemetry, mesh=mesh, pipeline=use_pipeline)


def uses_warmup(args: argparse.Namespace) -> bool:
    return args.http if args.warmup is None else args.warmup


def build_engine(params, cfg, espec, args: argparse.Namespace):
    from repro.serving import DisaggCoordinator
    if args.disagg:
        return DisaggCoordinator(params, cfg, spec=espec,
                                 transfer_ttl_steps=args.transfer_ttl)
    return espec.build(params, cfg)


def start_http_server(engine, args: argparse.Namespace):
    """Start ``serve.py --http``'s server over ``engine`` in this process;
    with warmup, returns once the bucket grid is compiled and /healthz is
    ready."""
    from repro.serving.server import ServingServer
    server = ServingServer(engine, host=args.host, port=args.port,
                           warmup=uses_warmup(args))
    server.start()
    if uses_warmup(args):
        server.wait_ready()
        for row in engine.warmup_report:
            print(f"[serve/warmup] {row['entry']:<8} {row['shape']} "
                  f"compiled in {row['seconds']:.2f}s", flush=True)
        print(f"[serve/warmup] {len(engine.warmup_report)} shapes in "
              f"{engine.warmup_seconds:.2f}s; steady-state serving "
              f"JIT-compiles nothing", flush=True)
    return server


def main(argv=None):
    args = parse_args(argv)
    cfg = model_config(args)
    key = jax.random.PRNGKey(args.seed)
    params = lm.init(key, cfg)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size, jnp.int32)

    use_engine = cfg.family in ("dense", "moe") and not cfg.window \
        and not cfg.attn_chunk and not args.static
    if (args.tp > 1 or args.mesh) and not use_engine:
        raise SystemExit("--tp/--mesh require the continuous-batching "
                         "engine (dense/moe family, no --static)")
    if args.http and not use_engine:
        raise SystemExit("--http requires the continuous-batching engine "
                         "(dense/moe family, no --static)")
    if not use_engine:
        t0 = time.time()
        toks = generate(params, cfg, prompt, args.gen,
                        cache_len=args.prompt_len + args.gen + 1,
                        greedy=args.temperature <= 0, key=key,
                        top_k=args.top_k,
                        temperature=args.temperature or 1.0)
        dt = time.time() - t0
        total_new = args.batch * args.gen
        print(f"[serve/static] generated {toks.shape} in {dt:.2f}s "
              f"({total_new / dt:.1f} tok/s, ffn_impl={args.ffn_impl})")
        print(np.asarray(toks[:, :16]))
        return toks

    from repro.distributed.sharding import make_serving_mesh
    from repro.serving import SamplingParams, jax_profiler
    mesh = None
    if args.tp > 1 or args.mesh:
        mesh = make_serving_mesh(args.tp)
        print(f"[serve/engine] tensor-parallel mesh: tp={args.tp} over "
              f"{[str(d) for d in mesh.devices.flat]}")
    from repro.serving.attention import get_attn_backend
    espec = engine_spec(args, mesh)
    engine = build_engine(params, cfg, espec, args)
    use_telemetry = engine.telemetry is not None
    spec = espec.spec
    attn = get_attn_backend(args.attn_backend).name

    if args.http:
        import signal

        server = start_http_server(engine, args)
        stop = {"flag": False}

        def _sig(signum, frame):
            stop["flag"] = True
        signal.signal(signal.SIGINT, _sig)
        signal.signal(signal.SIGTERM, _sig)
        print(f"[serve/http] listening on http://{server.host}:{server.port} "
              f"(backend={args.ffn_impl}, attn={attn}, "
              f"scheduler={args.scheduler}, "
              + ("disagg=prefill+decode, " if args.disagg else "") +
              f"tp={args.tp}; POST /v1/completions, GET /healthz"
              + (", GET /metrics" if use_telemetry else "") + ")",
              flush=True)
        with jax_profiler(args.jax_profile):
            try:
                while not stop["flag"]:
                    time.sleep(0.1)
            except KeyboardInterrupt:
                pass
            server.shutdown()
        if args.trace_out:
            engine.export_trace(args.trace_out)
            print(f"[serve/http] chrome trace -> {args.trace_out}",
                  flush=True)
        print("[serve/http] clean shutdown", flush=True)
        return None
    # no per-request seed: each request derives its own key from the engine
    # master key (identical prompts must not produce identical samples)
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p)
    if uses_warmup(args):
        engine.warmup()
        print(f"[serve/warmup] {len(engine.warmup_report)} shapes "
              f"precompiled in {engine.warmup_seconds:.2f}s")
    t0 = time.time()
    with jax_profiler(args.jax_profile):
        outs = engine.generate([np.asarray(prompt[i]).tolist()
                                for i in range(args.batch)],
                               sampling=sp, max_tokens=args.gen)
    dt = time.time() - t0
    total_new = sum(len(o.token_ids) for o in outs)
    ttft = [o.ttft for o in outs]
    gen_toks = np.stack([o.token_ids for o in outs])
    toks = np.concatenate([np.asarray(prompt), gen_toks], axis=1)
    print(f"[serve/engine] generated {toks.shape} in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s, backend={args.ffn_impl}, "
          f"attn={attn}, block_size={args.block_size}, "
          f"ttft mean {np.mean(ttft) * 1e3:.1f}ms)")
    if engine.prefix_cache and engine.cached_tokens_total:
        print(f"[serve/engine] prefix cache: "
              f"{engine.cached_tokens_total}/{engine.prompt_tokens_total} "
              f"prompt tokens served from cache")
    if spec is not None:
        drafted = sum(o.spec_drafted for o in outs)
        accepted = sum(o.spec_accepted for o in outs)
        steps = len(engine.stats)
        print(f"[serve/engine] spec k={spec.k} "
              f"draft={engine.draft_pair.describe()} "
              f"acceptance={accepted}/{drafted} "
              f"({accepted / max(drafted, 1):.1%}), "
              f"{total_new / max(steps, 1):.2f} tok/step over {steps} steps")
    if engine.telemetry is not None:
        phases = engine.telemetry.phase_ms_mean()
        if phases:
            print("[serve/engine] phase ms/step: " + ", ".join(
                f"{k}={v:.2f}" for k, v in sorted(phases.items())))
    if args.trace_out:
        engine.export_trace(args.trace_out)
        print(f"[serve/engine] chrome trace -> {args.trace_out}")
    print(toks[:, :16])

    if args.temperature <= 0 and (args.check_static or args.reduced):
        ref = np.asarray(generate(params, cfg, prompt, args.gen,
                                  cache_len=args.prompt_len + args.gen + 1))
        agree = (toks == ref).mean()
        print(f"[serve/engine] static-loop agreement: {agree:.2%}")
        assert agree == 1.0, \
            "continuous-batching engine diverged from the static loop"
    return toks


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
