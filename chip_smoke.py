"""Chip smoke run: the continuous-batching serving path of ``paper-0.5b`` at
its full published width on a TPU, end to end through its entry points.

  python3 chip_smoke.py             # one chip: kernels, HTTP serving, and
                                    # a pallas-vs-ref attention engine check
  python3 chip_smoke.py --tp 4      # four chips: tp=4 against tp=1, only

Weights and prompts are random, made from ``--seed``. Every line before the
last is a smoke reading, not a benchmark. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed;
any failed check or phase exits non-zero. Without a TPU it exits non-zero
before any phase runs.

Everything runs in this one process: a chip belongs to one process at a
time, so nothing here starts a child that would need it.

One-chip phases:

1. kernels — the five serving kernels at paper-0.5b widths, at a decode-
   and a prefill-sized M, each against its ``kernels/ref.py`` oracle.
2. serve   — the server ``serve.py --http`` builds (gather FFN, pallas
   attention, pipelined steps, warmup, telemetry), answering requests over
   HTTP from this process, one of them streamed.
3. engines — the same requests through a pallas- and a ref-attention engine:
   first-token logits within tolerance, greedy agreement reported.

``--tp 4``: the same requests through a tp=4 engine over
``make_serving_mesh(4)`` and a tp=1 engine on device 0, at TwELL tile 128
(d_ff 5632 / 4 = 1408 = 11 x 128; tile 256 does not divide a device's
slice, which the serving backends refuse).

Random weights put about half of the FFN hidden units on, far above the
T/C slots a TwELL tile keeps, so the gather path drops values past each
tile's slot budget: it is not the dense model, and the kernels are checked
against the oracle of the same semantics, never against dense. The overflow
share is printed. The engine comparisons (``engines``, ``--tp 4``) run on
the same weights with 31/32 of the gate columns zeroed, where no tile
overflows (``without_overflow`` says why).
"""
from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import os
import sys
import threading

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "paper-0.5b"
KERNEL_TOL = 2e-2        # max |kernel - oracle| / max |oracle|
LOGIT_TOL = 5e-2         # max |a - b| / max |b| over first-token logits
BLOCK_SIZE = 16          # serve.py's default KV block
MAX_BATCH = 4            # keeps warmup's bucket grid to 54 programs
PROMPT_LENS = (128, 448, 192, 384, 256, 320, 160, 416)
GEN_LENS = (32, 64, 48, 40, 64, 32, 56, 48)
SHARED_PREFIX = 96       # tokens every prompt starts with
STREAMED = 1             # index of the request sent with "stream": true
LIVE_GATE_FRACTION = 1 / 32  # of FFN units in the comparison weights
HTTP_TIMEOUT_S = 600.0
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'   # a Pallas kernel


def say(phase: str, msg: str) -> None:
    print(f"[smoke reading, not a benchmark] {phase}: {msg}", flush=True)


class Checks:
    """Named pass/fail checks; the run fails if any one failed."""

    def __init__(self):
        self.failed = []

    def check(self, phase: str, name: str, ok: bool, detail: str) -> None:
        say(phase, f"{name}: {detail} -> {'ok' if ok else 'FAIL'}")
        if not ok:
            self.failed.append(f"{phase}: {name}")


def rel_err(got, want) -> float:
    """max |got - want| / max |want| in f32 (inf when ``got`` is not
    finite)."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max()) / max(float(np.abs(want).max()),
                                                 1e-30)


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def mib(n: float) -> str:
    return f"{n / 2**20:.1f} MiB"


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    n = stats.get("peak_bytes_in_use")
    return "not reported" if n is None else mib(n)


# --------------------------------------------------------------------------- #
# phase 1: kernels against their oracles
# --------------------------------------------------------------------------- #

def ternary_ffn_inputs(key, m: int, k: int, n: int, dtype):
    """x in {-1, 0, 1}; weights +-1 at 1/16 density, 0 elsewhere. Every
    product and partial sum is a small integer, exact in f32 in any
    accumulation order, so the gate kernel cannot find another pattern than
    the oracle through a rounding flip near zero: counts and indices must
    match exactly, and values to the final bf16 rounding."""
    import jax
    import jax.numpy as jnp
    kx, kg, ku, kd = jax.random.split(key, 4)

    def weights(kk, shape):
        a, b = jax.random.split(kk)
        sign = jnp.where(jax.random.bernoulli(a, 0.5, shape), 1.0, -1.0)
        return (sign * jax.random.bernoulli(b, 1 / 16, shape)).astype(dtype)

    x = jax.random.randint(kx, (m, k), -1, 2).astype(dtype)
    return x, weights(kg, (k, n)), weights(ku, (k, n)), weights(kd, (n, k))


def check_ffn_kernels(cfg, key, checks: Checks) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.sparse_ffn import (tile_skip_ffn_pallas,
                                          twell_down_proj_pallas,
                                          twell_fused_ffn_pallas)
    from repro.kernels.twell_pack import twell_gate_matmul_pallas
    sp = cfg.sparsity
    tile, comp, act = sp.twell_tile, sp.twell_c, sp.activation
    tc = tile // comp
    dtype = jnp.dtype(cfg.dtype)
    # a decode batch bucket, and a prefill chunk (64) x batch bucket (8)
    for m in (8, 512):
        x, wg, wu, wd = ternary_ffn_inputs(jax.random.fold_in(key, m), m,
                                           cfg.d_model, cfg.d_ff, dtype)
        name = f"M={m} K={cfg.d_model} N={cfg.d_ff} T={tile} C={comp}"
        vals, idx, nnz = twell_gate_matmul_pallas(x, wg, tile, comp, act,
                                                  interpret=False)
        tw = ref.twell_gate_matmul(x, wg, tile, comp, act)
        exact = (np.array_equal(np.minimum(np.asarray(nnz), tc),
                                np.asarray(tw.nnz))
                 and np.array_equal(np.asarray(idx), np.asarray(tw.indices)))
        err = rel_err(vals, tw.values)
        checks.check("kernels", f"twell_gate_matmul {name}",
                     exact and err <= KERNEL_TOL,
                     f"counts and indices {'equal' if exact else 'DIFFER'}, "
                     f"value error {err:.3e} (tolerance {KERNEL_TOL})")
        y = twell_fused_ffn_pallas(tw.values, tw.indices, tw.nnz, x, wu, wd,
                                   tile, interpret=False)
        err = rel_err(y, ref.twell_fused_ffn(x, tw, wu, wd))
        checks.check("kernels", f"twell_fused_ffn {name}", err <= KERNEL_TOL,
                     f"error {err:.3e} (tolerance {KERNEL_TOL})")
        tu = ref.twell_gate_matmul(x, wu, tile, comp, act)   # non-gated
        y = twell_down_proj_pallas(tu.values, tu.indices, tu.nnz, wd, tile,
                                   interpret=False)
        err = rel_err(y, ref.twell_down_proj(tu, wd))
        checks.check("kernels", f"twell_down_proj {name}", err <= KERNEL_TOL,
                     f"error {err:.3e} (tolerance {KERNEL_TOL})")
        y, h = tile_skip_ffn_pallas(x, wg, wu, wd, tile, act, interpret=False)
        y_ref, h_ref = ref.tile_skip_ffn(x, wg, wu, wd, tile, act)
        err = max(rel_err(y, y_ref), rel_err(h, h_ref))
        checks.check("kernels", f"tile_skip_ffn {name}", err <= KERNEL_TOL,
                     f"error {err:.3e} (tolerance {KERNEL_TOL})")


def check_attention_kernels(cfg, key, checks: Checks) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.paged_chunk_attention import \
        paged_chunk_attention_pallas
    from repro.kernels.paged_decode_attention import \
        paged_decode_attention_pallas
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.dtype)
    b, width, chunk = MAX_BATCH * 2, 512 // BLOCK_SIZE, 64
    span = width * BLOCK_SIZE
    nb = 1 + b * width
    ks = jax.random.split(key, 8)
    # two layers of (L, N, bs, Hkv*hd) pools; the kernels read the second,
    # as a layer scan hands them the whole pools and the layer index, and
    # the oracles read that layer's (N, bs, Hkv, hd) pages
    layers, layer = 2, jnp.int32(1)
    kpool = jax.random.normal(
        ks[0], (layers, nb, BLOCK_SIZE, hkv * hd)).astype(dtype)
    vpool = jax.random.normal(
        ks[1], (layers, nb, BLOCK_SIZE, hkv * hd)).astype(dtype)
    kpages = kpool[layer].reshape(nb, BLOCK_SIZE, hkv, hd)
    vpages = vpool[layer].reshape(nb, BLOCK_SIZE, hkv, hd)
    tables = (1 + jax.random.permutation(ks[2], nb - 1)).reshape(b, width)
    tables = tables.astype(jnp.int32)
    shape = (f"pool ({layers}, {nb}, {BLOCK_SIZE}, {hkv * hd}), "
             f"table width {width}")

    q = jax.random.normal(ks[3], (b, 1, h, hd)).astype(dtype)
    lens = jax.random.randint(ks[4], (b,), 0, span, jnp.int32)
    got = paged_decode_attention_pallas(q, kpool, vpool, tables, lens,
                                        layer, interpret=False)
    err = rel_err(got, ref.paged_attention_decode(q, kpages, vpages,
                                                  tables, lens))
    checks.check("kernels", f"paged_decode_attention B={b} H={h} {shape}",
                 err <= KERNEL_TOL, f"error {err:.3e} (tolerance "
                                    f"{KERNEL_TOL})")

    rows = MAX_BATCH
    q = jax.random.normal(ks[5], (rows, chunk, h, hd)).astype(dtype)
    lens = jax.random.randint(ks[6], (rows,), 0, span - chunk, jnp.int32)
    num_new = jax.random.randint(ks[7], (rows,), 1, chunk + 1, jnp.int32)
    got = paged_chunk_attention_pallas(q, kpool, vpool, tables[:rows], lens,
                                       num_new, layer, interpret=False)
    want = ref.paged_attention_extend(q, kpages, vpages, tables[:rows],
                                      lens, num_new)
    # rows past a request's num_new are padding, garbage in both
    live = np.arange(chunk)[None, :] < np.asarray(num_new)[:, None]
    err = rel_err(np.asarray(got, np.float32)[live],
                  np.asarray(want, np.float32)[live])
    checks.check("kernels",
                 f"paged_chunk_attention B={rows} S={chunk} H={h} {shape}",
                 err <= KERNEL_TOL, f"error {err:.3e} (tolerance "
                                    f"{KERNEL_TOL})")


def overflow_reading(cfg, params, prompts, label: str) -> None:
    """TwELL overflow of ``params``: layer 0's gate through the pack kernel,
    its FFN input approximated by the normed prompt embeddings."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.twell_pack import twell_gate_matmul_pallas
    from repro.models.layers import embed_lookup, norm_apply
    sp = cfg.sparsity
    tc = sp.twell_tile // sp.twell_c
    toks = jnp.asarray(np.concatenate(prompts)[:512], jnp.int32)
    blk = params["blocks"]
    x = norm_apply(cfg.norm, {k: v[0] for k, v in blk["ln2"].items()},
                   embed_lookup(params["embed"], toks))
    _, _, nnz = twell_gate_matmul_pallas(x, blk["ffn"]["wg"][0],
                                         sp.twell_tile, sp.twell_c,
                                         sp.activation, interpret=False)
    nnz = np.asarray(nnz)
    over = float((nnz > tc).mean())
    dropped = float(np.maximum(nnz - tc, 0).sum() / max(nnz.sum(), 1))
    say("overflow", f"{label}, layer-0 gate over {len(toks)} prompt "
        f"tokens: FFN density {nnz.sum() / nnz.size / sp.twell_tile:.3f}, "
        f"{over:.3f} of (row, tile) pairs exceed the {tc} slots of a "
        f"{sp.twell_tile}-wide tile, {dropped:.3f} of non-zeros dropped")


# --------------------------------------------------------------------------- #
# requests
# --------------------------------------------------------------------------- #

def make_requests(vocab: int, seed: int):
    """Prompts sharing a ``SHARED_PREFIX``-token prefix, from ``seed``."""
    import numpy as np
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, SHARED_PREFIX).tolist()
    return [prefix + rng.integers(0, vocab, n - SHARED_PREFIX).tolist()
            for n in PROMPT_LENS]


def run_engine(engine, prompts):
    """Greedy outputs of ``prompts`` through ``engine``'s handle API."""
    handles = [engine.submit(p, max_tokens=g)
               for p, g in zip(prompts, GEN_LENS)]
    while engine.has_unfinished():
        engine.step()
    return [h.result() for h in handles]


def compare_outputs(phase: str, checks: Checks, label: str, got, want
                    ) -> None:
    """First-token logits within ``LOGIT_TOL``; greedy agreement reported
    (random weights leave near-ties that any rounding may flip)."""
    errs = [rel_err(a.logits[0], b.logits[0]) for a, b in zip(got, want)]
    checks.check(phase, f"first-token logits {label}",
                 max(errs) <= LOGIT_TOL,
                 f"worst error {max(errs):.3e} over {len(errs)} requests "
                 f"(tolerance {LOGIT_TOL})")
    same = sum(a.token_ids == b.token_ids for a, b in zip(got, want))
    pos = sum(x == y for a, b in zip(got, want)
              for x, y in zip(a.token_ids, b.token_ids))
    total = sum(len(b.token_ids) for b in want)
    say(phase, f"greedy agreement {label}: {same}/{len(want)} requests "
        f"identical, {pos}/{total} tokens equal by position")


# --------------------------------------------------------------------------- #
# phase 2: the HTTP server serve.py --http builds
# --------------------------------------------------------------------------- #

def complete(port: int, body: dict):
    """POST /v1/completions -> (status, token ids, finish reason); a
    streamed body is read as server-sent events up to ``[DONE]``."""
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=HTTP_TIMEOUT_S)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if not body.get("stream"):
            choice = json.loads(resp.read())["choices"][0]
            return resp.status, choice["token_ids"], choice["finish_reason"]
        tokens, finish = [], None
        for line in resp:
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):].strip()
            if data == b"[DONE]":
                break
            choice = json.loads(data)["choices"][0]
            tokens += choice["token_ids"]
            finish = choice["finish_reason"] or finish
        return resp.status, tokens, finish
    finally:
        conn.close()


def get_json(port: int, path: str) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=HTTP_TIMEOUT_S)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def serve_phase(args, cfg, params, prompts, checks: Checks, cache):
    """Serve ``prompts`` over HTTP; returns the server's ``EngineSpec``."""
    import jax

    from repro.launch import serve
    espec = serve.engine_spec(args)
    engine = serve.build_engine(params, cfg, espec, args)
    say("serve", f"{ARCH}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads} heads of {cfg.resolved_head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}; FFN "
        f"{engine.backend.describe()}, attention {engine.attn.name}, "
        f"pipeline {engine.pipeline}, telemetry "
        f"{engine.telemetry is not None}")
    say("serve", f"parameters {mib(tree_bytes(params))}, KV pools "
        f"{mib(tree_bytes(engine.kv.pools))} ({engine.kv.num_blocks} blocks "
        f"of {BLOCK_SIZE} tokens)")
    server = serve.start_http_server(engine, args)
    try:
        say("serve", f"warmup compiled {len(engine.warmup_report)} step "
            f"programs in {engine.warmup_seconds:.1f} s; compile cache "
            f"{cache.summary()}")
        checks.check("serve", "healthz after warmup",
                     get_json(server.port, "/healthz")["ok"], "ready")
        results = [None] * len(prompts)

        def send(i):
            try:
                results[i] = complete(server.port, {
                    "prompt": prompts[i], "max_tokens": GEN_LENS[i],
                    "stream": i == STREAMED})
            except OSError as e:
                results[i] = (None, [], repr(e))

        threads = [threading.Thread(target=send, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(HTTP_TIMEOUT_S)
        answered = sum(r is not None and r[0] == 200
                       and len(r[1]) == g and r[2] == "length"
                       for r, g in zip(results, GEN_LENS))
        checks.check("serve", "requests answered",
                     answered == len(prompts),
                     f"{answered}/{len(prompts)} with all their tokens "
                     f"(request {STREAMED} streamed)")
        stats = get_json(server.port, "/v1/stats")
    finally:
        server.shutdown()
    say("serve", f"{len(prompts)} requests: {sum(PROMPT_LENS)} prompt "
        f"tokens ({stats['cached_tokens_total']} from the prefix cache), "
        f"{sum(GEN_LENS)} completion tokens, {stats['steps']} engine steps")
    sparsity = stats.get("sparsity") or {}
    say("serve", f"mean FFN sparsity {sparsity.get('mean_ffn_sparsity')} "
        f"(from the TwELL-clipped counts), MFU estimate "
        f"{sparsity.get('mfu')}")
    for entry, size in (("decode", engine.table_width),
                        ("prefill", args.prefill_chunk)):
        text = engine.lower_step(entry, 1, size).compile().as_text()
        n = text.count(CUSTOM_CALL)
        checks.check("serve", f"Pallas kernels in the compiled {entry} step",
                     n > 0, f"{n} tpu_custom_call")
    dev = jax.devices()[0]
    say("serve", f"peak_bytes_in_use {peak_bytes(dev)} on {dev.device_kind}")
    return espec


def without_overflow(params, key):
    """``params`` with all but ``LIVE_GATE_FRACTION`` of every layer's gate
    columns zeroed. Those hidden units never fire, so a tile holds about
    T/64 non-zeros, far inside its T/C slots: no tile overflows, and the
    gather FFN is continuous in its input. At the served weights every tile
    overflows, and which values it keeps turns on the sign of
    pre-activations near zero, so two engines that round attention
    differently would differ by whole dropped values, not by rounding."""
    import jax
    import jax.numpy as jnp
    ffn = params["blocks"]["ffn"]
    wg = ffn["wg"]                                            # (L, K, N)
    live = jax.random.bernoulli(key, LIVE_GATE_FRACTION,
                                (wg.shape[0], 1, wg.shape[2]))
    ffn = dict(ffn, wg=jnp.where(live, wg, jnp.zeros_like(wg)))
    return {**params, "blocks": {**params["blocks"], "ffn": ffn}}


def engines_phase(cfg, params, espec, prompts, checks: Checks) -> None:
    outs = {}
    for attn in ("pallas", "ref"):
        engine = espec.replace(attn_backend=attn, record_logits=True,
                               telemetry=True, warmup=False).build(params, cfg)
        outs[attn] = run_engine(engine, prompts)
        del engine
    compare_outputs("engines", checks, "pallas vs ref attention",
                    outs["pallas"], outs["ref"])


# --------------------------------------------------------------------------- #
# --tp 4: tensor-parallel serving against one chip
# --------------------------------------------------------------------------- #

def tp_phase(args, tp: int, checks: Checks) -> None:
    import jax

    from repro.distributed import sharding
    from repro.launch import serve
    from repro.models import lm
    cfg = serve.model_config(args)
    cfg = dataclasses.replace(cfg, sparsity=dataclasses.replace(
        cfg.sparsity, twell_tile=128))
    prompts = make_requests(cfg.vocab_size, args.seed)
    key = jax.random.PRNGKey(args.seed)
    live_key = jax.random.fold_in(key, 3)
    mesh = sharding.make_serving_mesh(tp)
    shardings = sharding.named(mesh, sharding.make_param_specs(
        jax.eval_shape(lambda: lm.init(key, cfg)), cfg, mesh, fsdp=False))
    params = jax.jit(lambda k: without_overflow(lm.init(k, cfg), live_key),
                     out_shardings=shardings)(key)
    espec = serve.engine_spec(args, mesh).replace(record_logits=True)
    engine = espec.build(params, cfg)
    say("tp", f"{ARCH} tp={tp} over {[str(d) for d in mesh.devices.flat]}, "
        f"TwELL tile {cfg.sparsity.twell_tile}, FFN "
        f"{engine.backend.describe()}, attention {engine.attn.name}")
    out_tp = run_engine(engine, prompts)
    text = engine.lower_step("decode", 1, engine.table_width).compile(
        ).as_text()
    n = text.count(CUSTOM_CALL)
    checks.check("tp", f"Pallas kernels in the compiled tp={tp} decode step",
                 n > 0, f"{n} tpu_custom_call, {text.count('all-reduce(')} "
                        f"all-reduce")
    held = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves((engine.params, engine.kv.pools)):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    for d in mesh.devices.flat:
        say("tp", f"{d}: params + KV pools held {mib(held[d])}, "
            f"peak_bytes_in_use {peak_bytes(d)}")
    total = tree_bytes((engine.params, engine.kv.pools))
    checks.check("tp", "params and pools split over the mesh",
                 max(held.values()) < total / 2,
                 f"largest device share {mib(max(held.values()))} of "
                 f"{mib(total)}")
    del engine, params
    params1 = without_overflow(lm.init(key, cfg), live_key)
    overflow_reading(cfg, params1, prompts, "comparison weights")
    engine = espec.replace(mesh=None).build(params1, cfg)
    out_one = run_engine(engine, prompts)
    compare_outputs("tp", checks, f"tp={tp} vs tp=1", out_tp, out_one)


# --------------------------------------------------------------------------- #

class CacheCounter:
    """Persistent compilation cache hits and misses, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def summary(self) -> str:
        return f"{self.hits} hits, {self.misses} misses so far"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1, choices=(1, 4),
                    help="4: run only the tensor-parallel phase (4 chips)")
    opts = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    from repro.launch import serve
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    cache = CacheCounter()
    say("setup", f"{len(jax.devices())} x {dev.device_kind}, jax "
        f"{jax.__version__}, compile cache {cache_dir}")
    checks = Checks()
    flags = ["--arch", ARCH, "--ffn-impl", "gather", "--attn-backend",
             "pallas", "--block-size", str(BLOCK_SIZE), "--max-batch",
             str(MAX_BATCH), "--prompt-len", str(max(PROMPT_LENS)),
             "--gen", str(max(GEN_LENS)), "--seed", str(opts.seed)]
    if opts.tp > 1:
        tp_phase(serve.parse_args(flags + ["--tp", str(opts.tp)]), opts.tp,
                 checks)
    else:
        from repro.models import lm
        args = serve.parse_args(flags + ["--http", "--port", "0"])
        cfg = serve.model_config(args)
        key = jax.random.PRNGKey(opts.seed)
        params = lm.init(key, cfg)                 # as serve.py builds it
        check_ffn_kernels(cfg, jax.random.fold_in(key, 1), checks)
        check_attention_kernels(cfg, jax.random.fold_in(key, 2), checks)
        prompts = make_requests(cfg.vocab_size, opts.seed)
        overflow_reading(cfg, params, prompts, "served weights")
        espec = serve_phase(args, cfg, params, prompts, checks, cache)
        params = without_overflow(params, jax.random.fold_in(key, 3))
        overflow_reading(cfg, params, prompts, "comparison weights")
        engines_phase(cfg, params, espec, prompts, checks)
    say("setup", f"compile cache {cache.summary()}")
    if checks.failed:
        print("chip_smoke: FAILED: " + "; ".join(checks.failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
