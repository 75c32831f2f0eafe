"""Production training launcher with fault tolerance.

Features exercised here (and tested in tests/test_train_loop.py):
- auto-resume from the latest checkpoint (params, optimizer, data-iterator
  state, PRNG) — elastic across mesh changes via sharded restore;
- SIGTERM/SIGINT -> final synchronous checkpoint, clean exit (preemption);
- async rotating checkpoints every N steps;
- step-time watchdog: logs a straggler warning when a step exceeds
  ``watchdog_factor`` x the trailing median (on real pods this feeds the
  controller that triggers hot-spare swaps);
- the paper's recipe: L1 schedule, per-layer sparsity stats, dead-neuron
  tracking + optional targeted reinitialization (Eq. 6) after every step.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch paper-0.5b \
      --steps 200 --batch 8 --seq 256 --reduced --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import signal
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import CheckpointManager
from repro.config import TrainConfig
from repro.configs import get_config
from repro.core.sparsity import targeted_reinit
from repro.data.pipeline import SyntheticLM, make_iterator
from repro.models import lm
from repro.observability import (PEAKS, RunLogger, SparsityReport,
                                  param_count)
from repro.optim import adamw
from repro import training


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-0.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--l1", type=float, default=None)
    ap.add_argument("--ffn-impl", default=None)
    ap.add_argument("--dead-reinit", action="store_true")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--watchdog-factor", type=float, default=3.0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--run-log", default=None,
                    help="append structured JSONL (meta/step/event records, "
                         "incl. per-layer nnz and FLOPs/MFU accounting) here")
    ap.add_argument("--halt-at", type=int, default=0,
                    help="simulate preemption: checkpoint+exit at this step "
                         "while keeping the --steps LR schedule")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(d_model=args.width, d_ff=args.width * 4,
                          num_layers=args.layers)
    sp = cfg.sparsity
    if args.l1 is not None:
        sp = dataclasses.replace(sp, l1_coeff=args.l1)
    if args.ffn_impl:
        sp = dataclasses.replace(sp, ffn_impl=args.ffn_impl)
    cfg = dataclasses.replace(cfg, sparsity=sp,
                              remat="none" if args.reduced else cfg.remat)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=min(50, args.steps // 10 + 1),
                       checkpoint_every=args.ckpt_every,
                       checkpoint_dir=args.ckpt_dir)

    key = jax.random.PRNGKey(tcfg.seed)
    params = lm.init(key, cfg)
    opt_state = adamw.init(params, jnp.dtype(cfg.opt_state_dtype))
    data = SyntheticLM(cfg.vocab_size, args.batch, args.seq, seed=tcfg.seed)
    ever_active = jnp.zeros((max(cfg.num_layers, 1), cfg.d_ff), bool)

    n_params = param_count(params)
    # MFU only where the device's peak is published (None elsewhere)
    peaks = PEAKS.get(jax.devices()[0].device_kind)
    peak_flops = peaks.flops if peaks is not None else None
    tokens_per_step = args.batch * args.seq
    runlog = None
    if args.run_log:
        runlog = RunLogger(args.run_log, console=True, meta={
            "arch": cfg.name, "reduced": args.reduced,
            "d_model": cfg.d_model, "d_ff": cfg.d_ff,
            "num_layers": cfg.num_layers, "ffn_impl": cfg.sparsity.ffn_impl,
            "l1_coeff": cfg.sparsity.l1_coeff, "steps": args.steps,
            "batch": args.batch, "seq": args.seq, "n_params": n_params,
            "jax_version": jax.__version__})

    def _event(event, message, **fields):
        # events flow through the run log when enabled (which echoes the
        # console line itself); bare print otherwise
        if runlog is not None:
            runlog.event(event, message=message, **fields)
        else:
            print(f"[train] {message}", flush=True)

    mgr = CheckpointManager(args.ckpt_dir, keep=tcfg.keep_checkpoints)
    start_step = 0
    resumed = mgr.restore_latest((params, opt_state, ever_active))
    if resumed is not None:
        start_step, (params, opt_state, ever_active), extra = resumed
        data = make_iterator(extra["data"])
        _event("resume", f"resumed from step {start_step}", step=start_step)

    step_fn = jax.jit(
        training.make_train_step(cfg, tcfg, layer_stats=runlog is not None),
        donate_argnums=(0, 1))
    reinit_fn = jax.jit(targeted_reinit)

    # --- preemption handling -------------------------------------------------
    stop = {"flag": False}

    def _sig(_s, _f):
        stop["flag"] = True
    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)

    times = []
    history = []
    rkey = jax.random.PRNGKey(1234)
    for step in range(start_step, args.steps):
        t0 = time.time()
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        # layer_stats adds (L,)-shaped trajectories; keep the returned
        # history scalar-only (tests and --metrics-out depend on it)
        arrays = {k: np.asarray(v) for k, v in metrics.items()
                  if getattr(v, "ndim", 0)}
        metrics = {k: float(v) for k, v in metrics.items()
                   if not getattr(v, "ndim", 0)}

        if args.dead_reinit and cfg.family == "dense":
            # Eq. 6: reinit gate columns that never fired this step
            _, (_, aux) = jax.jit(
                lambda p, b: lm.loss_fn(p, b, cfg))(params, batch)
            dead = ~aux["neuron_active"]
            blocks = params["blocks"]
            rkey, sub = jax.random.split(rkey)
            wg = blocks["ffn"].get("wg")
            if wg is not None:
                keys = jax.random.split(sub, wg.shape[0])
                blocks["ffn"]["wg"] = jax.vmap(
                    lambda k, w, d: reinit_fn(k, w, d))(keys, wg, dead)

        dt = time.time() - t0
        times.append(dt)
        if len(times) > 20:
            times.pop(0)
        med = statistics.median(times)
        if dt > args.watchdog_factor * med and len(times) > 5:
            msg = (f"step {step} took {dt:.2f}s "
                   f"(median {med:.2f}s) — straggler suspected")
            print(f"[watchdog] {msg}", file=sys.stderr)
            if runlog is not None:
                runlog.event("watchdog", step=step, step_time_s=dt,
                             median_s=med, factor=args.watchdog_factor,
                             detail=msg)

        if runlog is not None:
            report = SparsityReport.build(
                cfg, tokens_per_step, arrays["nnz_per_layer"],
                tile_frac_per_layer=arrays["tile_frac_per_layer"],
                dead_frac_per_layer=arrays["dead_frac_per_layer"],
                ffn_present=arrays["ffn_present_per_layer"],
                n_params=n_params, train=True)
            runlog.step(
                step, loss=metrics["loss"], ce=metrics["ce"],
                l1=metrics["l1"], l1_coeff=metrics["l1_coeff"],
                nnz_mean=metrics["nnz_mean"],
                nnz_per_layer=arrays["nnz_per_layer"],
                dead_frac_per_layer=arrays["dead_frac_per_layer"],
                tile_frac_per_layer=arrays["tile_frac_per_layer"],
                mean_sparsity=report.mean_sparsity,
                ffn_effective_flops=report.ffn_effective_flops,
                ffn_dense_flops=report.ffn_dense_flops,
                model_effective_flops=report.model_effective_flops,
                model_dense_flops=report.model_dense_flops,
                flops_reduction=report.flops_reduction(),
                step_time_s=dt,
                tokens_per_s=tokens_per_step / max(dt, 1e-9),
                mfu=report.mfu_estimate(dt, peak_flops))

        history.append({"step": step, **metrics})
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {metrics['loss']:.4f} "
                  f"ce {metrics['ce']:.4f} nnz {metrics['nnz_mean']:.1f} "
                  f"l1 {metrics['l1']:.5f} {dt*1000:.0f}ms", flush=True)

        if args.halt_at and step + 1 >= args.halt_at:
            stop["flag"] = True
        if (step + 1) % tcfg.checkpoint_every == 0 or stop["flag"]:
            mgr.save(step + 1, (params, opt_state, ever_active),
                     extra={"data": data.state(), "arch": cfg.name})
        if stop["flag"]:
            _event("sigterm",
                   f"SIGTERM: checkpointed at step {step + 1}, exiting",
                   step=step + 1)
            break

    mgr.save(args.steps if not stop["flag"] else step + 1,
             (params, opt_state, ever_active),
             extra={"data": data.state(), "arch": cfg.name})
    mgr.wait()
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f)
    _event("done", f"done; final loss {history[-1]['loss']:.4f}",
           step=history[-1]["step"], loss=history[-1]["loss"])
    if runlog is not None:
        runlog.close()
    return history


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
