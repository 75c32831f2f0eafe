#!/usr/bin/env python3
"""Runs one benchmark cell once: the served path exactly as ``serve.py --http``
builds it, under an open-loop HTTP client, on a TPU.

    python3 bench/run.py --workload paper-1.5b.chat --seed 7 --seconds 51 \
        --trace 0

Set-up: the weights are made on the device from ``--seed``; the engine,
its ``warmup()`` of the whole bucket grid and the HTTP server come from
``repro.launch.serve``; two short requests walk the HTTP path. Then a child
process that never imports JAX sends the cell's seeded schedule for
``--seconds`` and times every request from when it was due. With
``--trace 1`` the profiler traces the measured window and the cell's
per-layer metrics are read; otherwise its end-to-end metrics are. After the
window the program's state is freed and the plain reference scores a seeded
sample of the finished requests (``correct``). The last line of standard
output is the result as one JSON object.

Two further modes serve the builder of a cell, not the driver:
``--sweep R1,R2,...`` serves one window at each offered rate (requests per
second) after one set-up, to find the knee; ``--calibrate S1,S2,...`` reads,
in one process, the compared number of the program and of the float8 control
on each seed, to set the cell's limit.

It exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import measure, traffic  # noqa: E402
from benchlib.spec import Spec  # noqa: E402

GRACE_S = 10.0         # after the window: late first tokens still count
TRACE_S = 10.0         # the profiler traces this much of the window's middle
COMPARE_TOKENS = 1500  # served tokens the correctness sample aims for
COMPARE_MAX_REQUESTS = 12   # at most max_batch: the replay is one wave
MIN_COMPARED = 100     # fewer served tokens compared than this: not correct
REPLAY_TIMEOUT_S = 150
WATCHDOG_S = 1190      # a run that has not ended by then exits non-zero


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates (req/s): one window "
                         "each after one set-up; no correctness check")
    ap.add_argument("--calibrate", default=None,
                    help="comma-separated seeds: program and control "
                         "readings of the compared number, in one process")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory)")
    return ap.parse_args(argv)


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def devices_or_none(chips: int):
    """JAX's devices when they are TPUs and at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        say(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s); nothing run")
        return None
    return devs


def start_watchdog(seconds: float) -> None:
    """End the process, non-zero and with no result, if the run has not
    ended after ``seconds`` (a step that fails in the engine's thread would
    otherwise leave the harness waiting for ever)."""
    import threading

    def expire():
        say(f"run exceeded {seconds:.0f} s; exiting")
        os._exit(3)
    t = threading.Timer(seconds, expire)
    t.daemon = True
    t.start()


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unbounded, caching every program (so a second run compiles
    nothing)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# the system under test

class Served:
    """The engine and HTTP server of one cell, built as ``serve.py --http``
    builds them, over weights made from the seed."""

    def __init__(self, conf: dict, seed: int):
        import jax
        from benchlib import model
        from repro.launch import serve
        m, s, w = conf["model"], conf["serving"], conf["weights"]
        flags = ["--arch", conf["preset"], "--ffn-impl", s["ffn_impl"],
                 "--attn-backend", s["attn_backend"],
                 "--block-size", str(s["block_size"]),
                 "--prefill-chunk", str(s["prefill_chunk"]),
                 "--max-batch", str(s["max_batch"]),
                 "--prompt-len", str(s["max_seq_len"]), "--gen", "0",
                 "--http", "--port", "0", "--seed", str(seed % (1 << 31))]
        self.args = serve.parse_args(flags)
        self.cfg = measure.model_config(serve.model_config(self.args), m)
        espec = serve.engine_spec(self.args).replace(
            num_blocks=s["num_blocks"],
            min_prefill_bucket=s["min_prefill_bucket"])
        params, live = model.make_params(m, w, seed)
        jax.block_until_ready(params)
        self.overflow = model.overflow_bound(
            live, m["twell_tile"], m["twell_tile"] // m["twell_c"])
        self.engine = serve.build_engine(params, self.cfg, espec, self.args)
        del params
        with contextlib.redirect_stdout(sys.stderr):
            self.server = serve.start_http_server(self.engine, self.args)
        self.port = self.server.port
        self.compiles = measure.CompileCounter()

    def swap_weights(self, conf: dict, seed: int) -> None:
        """Serve the weights of another seed on the same engine (the
        calibration mode's way to read many seeds after one warmup)."""
        import jax
        from benchlib import model
        old = self.engine.params
        self.engine.params = None
        jax.tree.map(lambda a: a.delete(), old)
        params, live = model.make_params(conf["model"], conf["weights"], seed)
        self.engine.params = jax.block_until_ready(params)
        m = conf["model"]
        self.overflow = model.overflow_bound(
            live, m["twell_tile"], m["twell_tile"] // m["twell_c"])

    def close(self) -> None:
        """Stop the server and free every array the program holds."""
        import jax
        self.server.shutdown()
        self.server = self.engine = None
        gc.collect()
        for a in jax.live_arrays():
            a.delete()


# ---------------------------------------------------------------------------
# one measured window

def run_client(job: dict, workdir: str) -> list:
    """Run the load generator in a child process and return its records."""
    job_path = os.path.join(workdir, "job.json")
    out_path = os.path.join(workdir, "records.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    proc = subprocess.Popen(
        [sys.executable, str(BENCH / "benchlib" / "client.py"), job_path,
         out_path], stdin=subprocess.DEVNULL)
    try:
        rc = proc.wait(timeout=job["t0"] + job["deadline"] + 60
                       - time.perf_counter())
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        raise RuntimeError(f"load generator exited with {rc}")
    with open(out_path) as f:
        return json.load(f)


def job_for(served: Served, reqs, t0: float, deadline: float) -> dict:
    return {"host": "127.0.0.1", "port": served.port, "t0": t0,
            "deadline": deadline,
            "requests": [{"index": r.index, "t_due": r.t_due,
                          "prompt": r.prompt, "max_tokens": r.max_tokens}
                         for r in reqs]}


def last_step(engine) -> int:
    """The number of the engine's newest ``StepStats`` (0 before any)."""
    return engine.stats[-1].step if engine.stats else 0


def window(served: Served, reqs, seconds: float, workdir: str,
           trace_dir=None) -> measure.Window:
    """Send ``reqs`` open loop for ``seconds`` (plus the grace for late
    first tokens); with ``trace_dir`` the profiler traces ``TRACE_S`` of
    the window's middle."""
    import threading

    import jax
    engine = served.engine
    counter = served.compiles
    counter.reset()
    t0 = time.perf_counter() + 0.5
    job = job_for(served, reqs, t0, seconds + GRACE_S)
    box = {}
    th = threading.Thread(target=lambda: box.update(
        records=run_client(job, workdir)), name="bench-client")
    th.start()
    step0 = last_step(engine)
    time.sleep(max(t0 - time.perf_counter(), 0.0))
    counter.arm()
    traced = None
    if trace_dir is not None:
        span = min(TRACE_S, seconds)
        time.sleep(max(t0 + (seconds - span) / 2 - time.perf_counter(), 0.0))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # Python tracing slows the host
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(measure.WINDOW_SPAN):
            t_on = time.perf_counter()
            time.sleep(max(t_on + span - time.perf_counter(), 0.0))
            t_off = time.perf_counter()
        jax.profiler.stop_trace()
        traced = (t_on, t_off)
    time.sleep(max(t0 + seconds - time.perf_counter(), 0.0))
    step1 = last_step(engine)
    counter.disarm()
    compiles = counter.snapshot()
    th.join()
    if "records" not in box:
        raise RuntimeError("the load generator returned no records")
    stats = [s for s in engine.stats if step0 < s.step <= step1]
    return measure.Window(t0=t0, seconds=seconds, records=box["records"],
                          requests=reqs, stats=stats, compiles=compiles,
                          grace=GRACE_S, traced=traced)


# ---------------------------------------------------------------------------
# correctness

def replay(engine, items) -> list:
    """Serve ``(prompt, max_tokens)`` items again on the same engine, all
    submitted at once and greedy, with the engine recording the logits that
    produced each served token. Returns ``(tokens, logits (n, V) float32)``
    per item, or None for one that did not finish within the timeout."""
    from repro.serving.sampling import SamplingParams
    engine.record_logits = True
    try:
        handles = [engine.submit(p, sampling=SamplingParams(temperature=0.0),
                                 max_tokens=n) for p, n in items]
    finally:
        engine.record_logits = False
    deadline = time.perf_counter() + REPLAY_TIMEOUT_S
    while not all(h.finished for h in handles) and \
            time.perf_counter() < deadline:
        time.sleep(0.05)
    out = []
    for h in handles:
        if not h.finished:
            h.cancel()
            out.append(None)
            continue
        o = h.result()
        out.append((list(o.token_ids), np.stack(o.logits).astype(np.float32)))
    return out


def padded(rows: int, a) -> np.ndarray:
    """``a`` (tokens (n,) or logits (n, V)) zero-padded to ``rows`` rows."""
    a = np.asarray(a)
    out = np.zeros((rows,) + a.shape[1:], a.dtype)
    out[:len(a)] = a
    return out


def score(ref, params, prompt, tokens, logits=None, control=False):
    """Per served position of ``prompt`` + ``tokens``: the reference's
    ``model.score`` of ``logits`` (the program's; the control's with
    ``control``; the reference's own when neither), as host arrays cut to
    ``len(tokens)``."""
    from benchlib import model
    rows = ref.logits(params, prompt, tokens)
    other = rows if logits is None and not control else \
        ref.control_logits(params, prompt, tokens) if control else \
        padded(ref.rows, logits)
    n = len(tokens)
    s = model.score(rows, other, padded(ref.rows, np.asarray(tokens, np.int32)),
                    n)
    return {k: np.asarray(v)[:n] for k, v in s.items()}


def widest(parts) -> float:
    """The largest value over per-request arrays (inf with none)."""
    flat = np.concatenate(parts) if parts else np.zeros(0)
    return float(flat.max()) if len(flat) else math.inf


def argmax_mismatches(tokens, logits) -> int:
    """Served tokens that are not the argmax of the logits the program
    recorded for them (greedy decoding: exactly 0)."""
    return int(np.sum(np.asarray(tokens) != np.asarray(logits).argmax(-1)))


def check_sample(win: measure.Window, seed: int):
    """The seeded sample of the window's finished requests: (request,
    record) pairs."""
    done = measure.finished(win)
    pick = measure.sample_for_check(
        [(len(r.prompt), len(rec["tokens"])) for r, rec in done], seed,
        COMPARE_TOKENS, COMPARE_MAX_REQUESTS)
    return [done[i] for i in pick]


def compare(conf: dict, mix: dict, seed: int, win: measure.Window, sample,
            replayed, limits: dict):
    """Score the sample with the reference, over weights made again from
    the seed (the program's state is freed by then): the widest gap of the
    tokens the window served, and, from the same requests served again on
    the same engine, the logits' distance from the reference's and whether
    each served token is the argmax of its logits. Returns the checks: each
    number the cell file limits, and the counts that have fixed limits."""
    import jax
    from benchlib import model
    params, _ = model.make_params(conf["model"], conf["weights"], seed)
    ref = model.Reference(conf["model"], conf["serving"]["max_seq_len"],
                          mix["output"]["max"])
    gaps, rel, absd = [], [], []
    mismatches = unfinished = 0
    for (r, rec), rep in zip(sample, replayed):
        gaps.append(score(ref, params, r.prompt, rec["tokens"])["gap"])
        if rep is None:
            unfinished += 1
            continue
        toks, logits = rep
        s = score(ref, params, r.prompt, toks, logits)
        rel.append(s["rel_err"])
        absd.append(s["abs_err"])
        mismatches += argmax_mismatches(toks, logits)
    jax.tree.map(lambda a: a.delete(), params)
    nums = {"widest_gap": widest(gaps), "logit_rel_err": widest(rel),
            "logit_abs_err": widest(absd)}
    checks = {k: {"value": nums[k], "limit": v, "le": True}
              for k, v in limits.items()}
    fixed = {"short_outputs": (measure.short_outputs(win), 0, True),
             "replays_unfinished": (unfinished, 0, True),
             "argmax_mismatches": (mismatches, 0, True),
             "compared_tokens": (int(sum(len(g) for g in gaps)),
                                 MIN_COMPARED, False)}
    for k, (value, limit, le) in fixed.items():
        checks[k] = {"value": value, "limit": limit, "le": le}
    say(f"widest gap {nums['widest_gap']}, logit error relative "
        f"{nums['logit_rel_err']} absolute {nums['logit_abs_err']}, over "
        f"{checks['compared_tokens']['value']} served tokens")
    return checks


def checks_pass(checks: dict) -> bool:
    return all((c["value"] <= c["limit"]) if c["le"]
               else (c["value"] >= c["limit"]) for c in checks.values())


# ---------------------------------------------------------------------------
# modes

def main(argv=None, *, need_tpu: bool = True, root: Path = ROOT,
         bench_dir: Path = BENCH) -> int:
    """One run; ``need_tpu=False`` and another ``bench_dir`` let the tests
    drive a tiny cell on the CPU."""
    opts = parse_args(argv)
    spec = Spec.load(root, bench_dir)
    cell = spec.workload(opts.workload)
    if need_tpu and devices_or_none(cell["chips"]) is None:
        return 2
    if need_tpu:
        start_watchdog(3300 if opts.sweep or opts.calibrate else WATCHDOG_S)
    import jax
    use_compile_cache(root)
    conf = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    cell_file = spec.cell(cell["name"])
    seed = opts.seed
    served = Served(conf, seed)
    vocab = conf["model"]["vocab_size"]
    with tempfile.TemporaryDirectory(prefix="bench-") as workdir:
        for doc in traffic.documents(mix, seed, vocab):
            run_client(job_for(served, [traffic.Request(-1, 0.0, doc, 1)],
                               time.perf_counter() + 0.1, 300.0), workdir)
        run_client(job_for(served, traffic.warmup_requests(seed, vocab),
                           time.perf_counter() + 0.1, 300.0), workdir)
        say(f"memory_stats after set-up: {jax.devices()[0].memory_stats()}")
        if opts.sweep:
            return sweep(served, mix, opts, vocab, workdir)
        if opts.calibrate:
            return calibrate(served, conf, mix, cell_file, opts, vocab)
        reqs = traffic.schedule(mix, cell_file["rate_per_s"], opts.seconds,
                                seed, vocab)
        trace_dir = None
        if opts.trace:
            trace_dir = opts.trace_dir or os.path.join(workdir, "trace")
        win = window(served, reqs, opts.seconds, workdir, trace_dir)
        setup_s = win.t0 - T_START
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": int(
                      (dev.memory_stats() or {}).get("peak_bytes_in_use", 0))}
        info = measure.run_info(served, win)
        for k, v in info.items():
            say(f"{k}: {v}")
        per_layer = breakdown = None
        if opts.trace:
            tr = measure.load_trace(trace_dir, win, served.engine)
            device["busy_s"] = tr.busy_s
            device["window_s"] = tr.window_s
            run = measure.RunData(conf=conf, win=win, trace=tr,
                                  engine=served.engine,
                                  peaks=measure.peaks(dev.device_kind,
                                                 spec.dir / "peaks.json"))
            per_layer = {}
            for name, reader in spec.readers(cell["name"]).items():
                value = reader.read(run)
                if value is not None:
                    per_layer[name] = value
            breakdown = tr.breakdown()
        sample = check_sample(win, seed)
        replayed = replay(served.engine, [(r.prompt, len(rec["tokens"]))
                                          for r, rec in sample])
        served.close()
        checks = compare(conf, mix, seed, win, sample, replayed,
                         cell_file["limits"])
    units = {m["name"]: m["unit"] for kind in ("end_to_end", "per_layer")
             for m in spec.benchmark[kind]}
    if opts.trace:
        values = per_layer
    else:
        e2e = measure.end_to_end(win)
        e2e["setup_s"] = setup_s
        values = {m["name"]: e2e[m["name"]]
                  for m in spec.metrics("end_to_end", cell["name"])}
    out = {"correct": checks_pass(checks),
           "attempted": win.attempted, "failed": win.failed,
           "metrics": {k: {"value": v, "unit": units[k]}
                       for k, v in values.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    for k, c in checks.items():
        rel = "<=" if c["le"] else ">="
        say(f"check {k}: {c['value']} (must be {rel} {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


def sweep(served, mix, opts, vocab, workdir) -> int:
    for i, rate in enumerate(float(r) for r in opts.sweep.split(",")):
        reqs = traffic.schedule(mix, rate, opts.seconds, opts.seed + i, vocab)
        win = window(served, reqs, opts.seconds, workdir)
        row = {"rate_per_s": rate, **measure.end_to_end(win),
               **measure.backlog(win), "attempted": win.attempted,
               "failed": win.failed,
               "compiles_in_window": win.compiles}
        print(json.dumps(row), flush=True)
        # let the queue drain before the next rate
        while served.engine.has_unfinished():
            time.sleep(0.5)
    served.close()
    return 0


def calibrate(served, conf, mix, cell_file, opts, vocab) -> int:
    """Per seed, over the seeded sample of the cell's schedule served on
    one engine (as a run's check serves it again): the numbers compared, of
    the program; of the float8 control at the same positions (its logits,
    and the gap of the token it puts first); and of a token altered where
    it is produced (every seventh served token moved to the next id)."""
    from benchlib import model
    ref = model.Reference(conf["model"], conf["serving"]["max_seq_len"],
                          mix["output"]["max"])
    for seed in (int(s) for s in opts.calibrate.split(",")):
        served.swap_weights(conf, seed)
        reqs = traffic.schedule(mix, cell_file["rate_per_s"], opts.seconds,
                                seed, vocab)
        pick = measure.sample_for_check(
            [(len(r.prompt), r.max_tokens) for r in reqs], seed,
            COMPARE_TOKENS, COMPARE_MAX_REQUESTS)
        sample = [reqs[i] for i in pick]
        replayed = replay(served.engine, [(r.prompt, r.max_tokens)
                                          for r in sample])
        params = served.engine.params
        row = {"seed": seed, "requests": len(sample),
               "unfinished": sum(x is None for x in replayed)}
        parts = {}
        for r, rep in zip(sample, replayed):
            if rep is None:
                continue
            toks, logits = rep
            prog = score(ref, params, r.prompt, toks, logits)
            ctrl = score(ref, params, r.prompt, toks, control=True)
            moved = [(t + 1) % vocab if j % 7 == 3 else t
                     for j, t in enumerate(toks)]
            fault = score(ref, params, r.prompt, moved)["gap"]
            for who, s in (("program", prog), ("control", ctrl)):
                for k in ("gap", "pick_gap", "rel_err", "abs_err"):
                    parts.setdefault((who, k), []).append(s[k])
            parts.setdefault(("fault", "gap"), []).append(fault)
            row["argmax_mismatches"] = row.get("argmax_mismatches", 0) + \
                argmax_mismatches(toks, logits)
        row["tokens"] = int(sum(len(g) for g in parts.get(("fault", "gap"),
                                                          [])))
        name = {"gap": "widest_gap", "pick_gap": "widest_gap",
                "rel_err": "logit_rel_err", "abs_err": "logit_abs_err"}
        for (who, k), v in parts.items():
            if (who, k) in (("program", "pick_gap"), ("control", "gap")):
                continue       # the program's own pick is its token
            row.setdefault(who, {})[name[k]] = widest(v)
            if k == "rel_err":
                row[who]["logit_rel_err_mean"] = float(
                    np.concatenate(v).mean())
        print(json.dumps(row), flush=True)
    served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
