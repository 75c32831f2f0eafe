"""Continuous-batching serving engine over the paged KV cache.

The front door is handle-and-event shaped: ``submit()`` returns a
``RequestHandle`` immediately (incremental ``new_tokens()`` deltas, status,
``cancel()``), and each ``step()`` returns the ``StepEvent`` list for that
iteration — TOKEN / FINISH / PREEMPT / CANCEL per affected row — so callers
stream tokens as they commit instead of polling for finished requests.
``generate()`` remains as a thin batch-synchronous shim over the same path.

One ``step()`` is one engine iteration:

  0. cancel — requests flagged by ``cancel()`` since the last step are
     aborted wherever they are (queued, mid-chunked-prefill, mid-decode,
     mid-speculation): KV blocks are freed/parked, growth reservations
     returned, and a CANCEL event carries the partial output.
  1. decode — every running request advances one token through a single
     jitted ``lm.paged_decode_step`` call (batch padded to a power-of-two
     bucket, so recompilation is bounded by ``log2(max_batch)``); sampling
     (greedy / temperature / top-k, per-request PRNG keys) runs inside the
     same jitted call. Requests hitting EOS or ``max_tokens`` are evicted
     and their KV blocks released (registered prefix blocks park in the
     cache's evictable LRU, everything else returns to the free list).
  2. admit — the ``Scheduler`` (policy: FCFS default, priority optional)
     names the next candidate; it joins once the batch has a slot and the
     KV pool can cover its worst case (prompt + max_tokens blocks:
     reservation-style admission control, so decode-time block growth can
     never fail). When the candidate does NOT fit, the scheduler may name a
     running victim to **preempt**: the victim's KV is freed (registered
     full prompt blocks park in the prefix cache, still matchable), its
     reservation returns to the pool, and it re-queues keeping its
     committed output tokens — resume re-prefills ``prompt + outputs``,
     re-sharing any still-cached prompt blocks nearly for free. With prefix
     caching on, admission first matches the longest cached block-aligned
     prefix and shares those blocks (refcounted, copy-on-write) — only
     suffix blocks are newly allocated, only suffix tokens computed.
  3. prefill — ALL in-flight prefills (just-admitted and partially done)
     advance together through ONE batched ``lm.paged_prefill`` call, at
     most ``prefill_chunk`` tokens each. Long prompts therefore prefill in
     fixed-size chunks interleaved with decode steps — bounded TTFT impact
     on running requests — and same-step admissions share a single
     dispatch. A request whose prefill completes samples its next token in
     the same call (from the last valid row's logits only: the O(V) head
     never materializes over the whole chunk) and joins the next
     iteration's decode batch ("join-on-arrival").

The FFN execution path per phase (dense | gather/TwELL | tile_skip) comes
from the ``ServingBackend``, so sparse-vs-dense serving is one constructor
flag.

Tensor parallelism is one more flag: ``ServingEngine(..., mesh=mesh)`` runs
every jitted entrypoint (decode, chunked prefill, the speculative drafter's
scan, the verifier) under a ``jax.sharding.Mesh`` with explicit
in/out_shardings — params and the paged KV pools split over the ``model``
axis (attention heads / FFN hidden / vocab / kv-head pool axis), while the
scheduler's state (block tables, seq lens, tokens, sampling knobs) stays
replicated. Scheduling, admission, prefix caching, cancellation,
preemption, and rollback are host-side and layout-agnostic, so the engine
is byte-for-byte the same code path sharded or not; the only per-step host
transfer either way is the sampled-token row.

Thread safety: ``submit`` and ``step`` serialize on one engine lock, so an
HTTP front end may submit from handler threads while a single engine thread
drives ``step()``. ``cancel`` is lock-free — it only flags the request
(atomic under the GIL; processed at the next step) — so it never waits out
a step's device work.

Observability: ``ServingEngine(..., telemetry=True)`` publishes per-phase
step timings, KV occupancy, prefix-cache and speculative counters, TTFT /
inter-token latency histograms, and JIT compile-event counts into a
``telemetry.MetricsRegistry`` (Prometheus text via ``GET /metrics`` on the
HTTP server), and records per-request lifecycle spans + a whole-engine
step timeline exportable as Chrome-trace JSON (``engine.export_trace``).
Each step phase is also a ``jax.profiler`` annotation (``engine.<phase>``
inside an ``engine.step`` step marker), and the step programs are named
``decode_step`` / ``prefill_step``, so a profiler trace shows host phases
and device programs on one clock. Telemetry off (the default) is a few
``is None`` checks per step — the token stream is identical either way. See
docs/observability.md.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.distributed import sharding
from repro.models import lm
from repro.observability import accounting
from repro.serving import sampling as sampling_mod
from repro.serving.attention import get_attn_backend
from repro.serving.backends import (DECODE, PREFILL, get_backend,
                                    make_draft_pair)
from repro.serving.kv_cache import PagedKVCache
from repro.serving.pipeline import (DecodeLaunch, InFlightStep, PrefillLaunch,
                                    SpecLaunch, bucket, bucket_grid,
                                    start_host_copy)
from repro.serving.request import (CANCELLED, EVENT_CANCEL, EVENT_FINISH,
                                   EVENT_PREEMPT, EVENT_TOKEN,
                                   FINISH_CANCELLED, FINISHED, PREEMPTED,
                                   PREFILLING, RUNNING, Request,
                                   RequestHandle, RequestOutput, StepEvent)
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import Scheduler, get_scheduler, plan_victims
from repro.serving.spec import (Drafter, SpecConfig, Verifier,
                                rollback_after_verify)
from repro.serving.telemetry import (PHASE_ADMISSION, PHASE_CANCEL,
                                     PHASE_COLLECT, PHASE_DECODE,
                                     PHASE_DRAFT, PHASE_LAUNCH, PHASE_PLAN,
                                     PHASE_PREFILL, PHASE_SAMPLE,
                                     PHASE_VERIFY, Telemetry)

_UNTIMED = contextlib.nullcontext()      # the span of an engine without
#                                          telemetry


@dataclasses.dataclass(frozen=True)
class StepStats:
    """Per-iteration batch composition (proof of continuous batching)."""

    step: int
    decode_batch: int        # live rows in this step's normal-decode call
    padded_batch: int        # bucketed batch the kernel actually ran
    prefills: int            # requests admitted this step
    finished: int            # FINISH events (EOS / length) this step
    running_after: int
    waiting_after: int
    free_blocks: int         # admissible capacity: free + evictable cached
    #                          blocks NET of outstanding growth reservations
    reserved_blocks: int = 0         # growth blocks promised to running reqs
    cached_blocks: int = 0           # evictable prefix-cache blocks (LRU)
    prefilling_after: int = 0        # requests mid-prefill after this step
    prefill_tokens: int = 0          # prompt tokens computed this step
    cached_prefix_tokens: int = 0    # prompt tokens served from cache (admits)
    cancelled: int = 0       # CANCEL events processed this step
    preempted: int = 0       # PREEMPT events (scheduler evictions) this step
    spec_batch: int = 0      # rows that ran draft->verify this step
    spec_drafted: int = 0    # draft tokens proposed this step
    spec_accepted: int = 0   # ... of which the verifier accepted
    wall_ms: float = 0.0     # host wall-clock for the whole step
    sync_ms: float = 0.0     # ... of which spent blocked on device results.
    #                          Synchronous mode: dispatch+compute sync (wall -
    #                          sync = host-side scheduling, so TP speedups are
    #                          attributable). Pipelined mode: RESIDUAL
    #                          blocking only — the tail of the previous
    #                          step's async sampled-token transfer that this
    #                          step's plan work did not hide.
    overlap_ms: float = 0.0  # pipelined mode only: wall time the previously
    #                          launched device step ran concurrently with
    #                          host-side work (its launch -> collect span);
    #                          0.0 in synchronous mode / nothing in flight
    migrated_blocks: int = 0  # KV blocks materialized into this engine's pool
    #                           from another engine this step (disaggregation)
    role: str = "unified"    # engine role that produced this step
    #                          (unified | prefill | decode)


# canonical power-of-two bucketing lives in pipeline.py (warmup walks the
# same grid the steps request); the old private name stays importable
_bucket = bucket


class ServingEngine:
    """Continuous-batching engine serving one model on one set of weights."""

    def __init__(self, params, cfg: ModelConfig, *, backend="dense",
                 attn_backend: Optional[str] = None,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 max_batch: int = 8, max_seq_len: int = 256,
                 min_prefill_bucket: int = 16, seed: int = 0,
                 record_logits: bool = False,
                 spec: Optional[SpecConfig] = None,
                 prefix_cache: bool = True, prefill_chunk: int = 64,
                 scheduler: Union[str, Scheduler] = "fcfs",
                 max_stats: Optional[int] = 4096, mesh=None,
                 telemetry: Union[bool, Telemetry, None] = False,
                 pipeline: bool = False, warmup: bool = False,
                 role: str = "unified"):
        self.role = role
        self.backend = get_backend(backend)
        # attention backend first (None = the platform default): configure()
        # stamps cfg.attn_backend, and every derived config below
        # (prefill/decode/draft/verify) must inherit it so all regimes read
        # the paged KV the same way
        self.attn = get_attn_backend(attn_backend)
        self.attn.validate_platform(jax.default_backend())
        cfg = self.attn.configure(cfg)
        self.cfg = cfg
        self.cfg_prefill = self.backend.configure(cfg, PREFILL)
        self.cfg_decode = self.backend.configure(cfg, DECODE)
        self.mesh = mesh
        self._param_shardings = None
        if mesh is not None:
            self.backend.validate_mesh(cfg, mesh)
            pspecs = sharding.make_param_specs(
                jax.eval_shape(lambda: params), cfg, mesh, fsdp=False)
            self._param_shardings = sharding.named(mesh, pspecs)
            params = jax.device_put(params, self._param_shardings)
        self.params = params
        self.spec = spec
        if spec is not None:
            spec.validate()
            self.draft_pair = make_draft_pair(self.backend, spec.draft_backend,
                                              spec.draft_threshold)
            cfg_draft = self.draft_pair.draft.configure(cfg, DECODE)
            self.drafter = Drafter(cfg_draft, spec.k)
            self.verifier = Verifier(self.cfg_decode, spec.k)
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_seq_len < 1:
            raise ValueError(f"max_seq_len must be >= 1, got {max_seq_len}")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self.min_prefill_bucket = min_prefill_bucket
        self.record_logits = record_logits
        self.prefix_cache = prefix_cache
        self.prefill_chunk = prefill_chunk
        if num_blocks is None:
            # enough for a full batch of worst-case requests, + null block
            num_blocks = 1 + max_batch * (-(-max_seq_len // block_size))
        self.kv = PagedKVCache(cfg, num_blocks, block_size, mesh=mesh)
        if mesh is not None and spec is not None:
            # drafter: (bt, sl0, tok0, draft_len, keys, temps, topks, topps)
            # -> (toks, logits, pools); verifier: (bt, start, num_new, toks)
            # -> (logits, pools)
            self.drafter.jit_shardings = sharding.serving_jit_shardings(
                mesh, self._param_shardings, self.kv.pool_shardings, 8, 2)
            self.verifier.jit_shardings = sharding.serving_jit_shardings(
                mesh, self._param_shardings, self.kv.pool_shardings, 4, 1)
        self.table_width = -(-max_seq_len // block_size)
        self.scheduler: Scheduler = get_scheduler(scheduler)
        # observability: metrics registry + span tracing (telemetry=True
        # builds a default Telemetry; pass an instance to share a registry
        # across engines; False/None = zero instrumentation on the hot path)
        if telemetry is True:
            telemetry = Telemetry()
        elif telemetry is False:
            telemetry = None
        self.telemetry: Optional[Telemetry] = telemetry
        if telemetry is not None:
            telemetry.metrics.build_info.set(
                1, backend=self.backend.name, attn_backend=self.attn.name,
                scheduler=self.scheduler.name,
                spec_k=str(0 if spec is None else spec.k),
                tp=str(1 if mesh is None else mesh.devices.size))
            if spec is not None:
                self.drafter.dispatch = self._dispatch
                self.verifier.dispatch = self._dispatch
            # arm the sparsity/compute cost model: the decode/prefill entry
            # points collect a per-layer (nnz, tile_frac) probe as extra
            # scan outputs (logits are bit-identical with or without it)
            telemetry.attach_compute(
                cfg, accounting.param_count(params),
                chips=1 if mesh is None else mesh.devices.size)
        self._probe = telemetry is not None
        self.prefilling: List[Request] = []
        self.running: List[Request] = []
        self.stats: List[StepStats] = []
        self.prefill_tokens_total = 0      # prompt tokens actually computed
        self.cached_tokens_total = 0       # prompt tokens served from cache
        self.prompt_tokens_total = 0       # prompt tokens admitted overall
        self.finished_total = 0            # requests finished (EOS / length)
        self.cancelled_total = 0           # requests aborted via cancel()
        self.preempted_total = 0           # scheduler evictions (resumes)
        self.migrated_blocks_total = 0     # KV blocks materialized into this
        #                                    pool from another engine (disagg)
        self._migrated_step = 0            # ... of which since the last step
        self.max_stats = max_stats         # keep only the newest N StepStats
        #                                    (bounded by default so long-lived
        #                                    engines cannot grow without
        #                                    limit; None = unbounded, for
        #                                    short diagnostic runs — totals
        #                                    above never truncate either way)
        self.on_new_work = None            # optional callable: submit/cancel
        #                                    wake-up hook for a server loop
        self.on_prefill_done = None        # optional callable(req, reason):
        #                                    fires when a request's prefill
        #                                    target completes, AFTER its first
        #                                    sampled token commits but BEFORE
        #                                    any terminal transition frees its
        #                                    KV — the disagg coordinator holds
        #                                    the blocks for transfer here
        self._master_key = jax.random.PRNGKey(seed)
        self._next_rid = 0
        self._step_idx = 0
        self._reserved = 0            # growth blocks promised to running reqs
        self._sync_s = 0.0            # device-sync seconds within this step
        self._lock = threading.RLock()
        self._requests: Dict[int, Request] = {}    # every non-terminal rid
        self._handles: Dict[int, RequestHandle] = {}
        self._decode_fns: Dict[int, callable] = {}
        self._prefill_fns: Dict[int, callable] = {}
        self._dispatched: set = set()    # step programs run at least once
        # pipelined step loop (plan/launch/collect; see pipeline.py):
        # pipeline=False keeps the original synchronous step as the
        # numerics/latency reference — token streams are identical either way
        self.pipeline = bool(pipeline)
        self._inflight: Optional[InFlightStep] = None
        self._preempt_pending: List[Request] = []  # victims planned while a
        #                                            step was in flight; they
        #                                            preempt at collect
        self.warmup_seconds = 0.0
        self.warmup_report: List[Dict] = []        # per-shape compile timings
        if warmup:
            self.warmup()

    @property
    def waiting(self) -> List[Request]:
        """Queued (waiting or preempted) requests, scheduler order opaque."""
        return list(self.scheduler)

    def _mesh_ctx(self):
        """Ambient-mesh context for tracing/dispatching jitted serving calls
        (``shard_act`` and the shard-local kernels resolve it through
        ``sharding.current_mesh``); a no-op unsharded."""
        return jax.set_mesh(self.mesh) if self.mesh is not None \
            else contextlib.nullcontext()

    def _jit_kwargs(self, n_host_args: int, n_rep_outs: int) -> Dict:
        """Explicit in/out_shardings for a serving entrypoint (empty dict
        when unsharded — plain jit, exactly the pre-mesh behavior)."""
        if self.mesh is None:
            return {}
        return sharding.serving_jit_shardings(
            self.mesh, self._param_shardings, self.kv.pool_shardings,
            n_host_args, n_rep_outs)

    def _sync(self, *outputs) -> None:
        """Block on device outputs, attributing the wait to this step's
        ``sync_ms`` (everything outside it is host-side scheduling)."""
        t0 = time.perf_counter()
        for o in outputs:
            jax.block_until_ready(o)
        self._sync_s += time.perf_counter() - t0

    def _wake(self) -> None:
        if self.on_new_work is not None:
            self.on_new_work()

    def _span(self, phase: str):
        """Context manager timing one phase of the current step (a no-op
        without telemetry)."""
        if self.telemetry is None:
            return _UNTIMED
        return self.telemetry.span(phase, self._step_idx)

    def _dispatch(self, entry: str, fn, *args):
        """Call the jitted step program ``fn``. Its first call compiles it:
        with telemetry that call is counted and runs inside the
        ``compile.<entry>`` span, so a step that recompiles shows."""
        if self.telemetry is None or fn in self._dispatched:
            return fn(*args)
        self._dispatched.add(fn)
        with self.telemetry.compiling(entry, self._step_idx):
            return fn(*args)

    # ------------------------------------------------------------------ API

    def submit(self, prompt: Sequence[int], *,
               sampling: Optional[SamplingParams] = None,
               max_tokens: int = 16,
               eos_token_id: Optional[int] = None,
               no_spec: bool = False,
               priority: int = 0,
               stream: bool = False,
               outputs: Sequence[int] = (),
               base_key: Optional[jax.Array] = None) -> RequestHandle:
        """Queue a request; returns its ``RequestHandle`` immediately.
        Admission happens in ``step()`` under the engine's scheduler policy.

        priority: larger = more urgent. The FCFS scheduler ignores it; the
        priority scheduler admits high tiers first and may preempt running
        lower-priority requests under pool pressure.
        stream: buffer this request's ``StepEvent``s on the handle
        (``handle.events()`` drains them); ``new_tokens()`` works either way.
        ``no_spec`` opts this request out of speculative decoding (it will
        run single-token decode even in a speculating engine).
        outputs / base_key are the disaggregation coordinator's resume
        interface: ``outputs`` pre-commits already-generated tokens (the
        request admits exactly like a preempt-resume, prefilling
        ``prompt + outputs``; ``max_tokens`` still counts TOTAL outputs and
        must exceed ``len(outputs)``), and ``base_key`` overrides the
        per-request PRNG base key so a cross-engine request samples with the
        key of the coordinator rid it belongs to, not this engine's local
        rid."""
        with self._lock:
            sp = sampling or SamplingParams()
            if outputs and max_tokens <= len(outputs):
                raise ValueError(
                    f"max_tokens ({max_tokens}) must exceed pre-committed "
                    f"outputs ({len(outputs)})")
            req = Request(rid=self._next_rid, prompt=list(map(int, prompt)),
                          max_tokens=max_tokens, sampling=sp,
                          eos_token_id=eos_token_id, no_spec=no_spec,
                          priority=priority,
                          output_tokens=list(map(int, outputs)))
            req.role = self.role
            if len(req.prompt) + max_tokens > self.max_seq_len:
                raise ValueError(
                    f"prompt ({len(req.prompt)}) + max_tokens ({max_tokens}) "
                    f"exceeds max_seq_len ({self.max_seq_len})")
            worst = self.kv.blocks_for(len(req.prompt) + max_tokens)
            if worst > self.kv.num_blocks - 1:
                raise ValueError(
                    f"request needs {worst} KV blocks but the pool only has "
                    f"{self.kv.num_blocks - 1}; it could never be admitted")
            req.base_key = base_key if base_key is not None else \
                sampling_mod.request_base_key(
                    self._master_key, req.rid, sp.seed)
            if self.record_logits:
                req.logits_trace = []
            self._next_rid += 1
            handle = RequestHandle(self, req, stream=stream)
            self._requests[req.rid] = req
            self._handles[req.rid] = handle
            if self.telemetry is not None:
                self.telemetry.on_submit(req)
            self.scheduler.add(req)
        self._wake()
        return handle

    def add_request(self, prompt: Sequence[int], *,
                    sampling: Optional[SamplingParams] = None,
                    max_tokens: int = 16,
                    eos_token_id: Optional[int] = None,
                    no_spec: bool = False) -> int:
        """Compat shim over ``submit()``: queue a request, return its id."""
        return self.submit(prompt, sampling=sampling, max_tokens=max_tokens,
                           eos_token_id=eos_token_id, no_spec=no_spec).rid

    def cancel(self, request: Union[RequestHandle, int]) -> bool:
        """Abort a request wherever it is in its lifecycle — queued,
        mid-chunked-prefill, mid-decode, or mid-speculation. Takes effect at
        the next ``step()``, which frees/parks its KV blocks, returns its
        growth reservation, and emits a CANCEL event carrying the partial
        output. Returns False when the request is unknown or already
        terminal (cancellation raced completion — the output stands)."""
        rid = request.rid if isinstance(request, RequestHandle) \
            else int(request)
        # deliberately lock-free: step() holds the engine lock across device
        # compute, and cancellation must not wait a whole step to be noted.
        # Safe because this only READS the registry and SETS a bool (both
        # atomic under the GIL); flagging a request that concurrently
        # reached a terminal state is a no-op (the flag is never read again).
        req = self._requests.get(rid)
        if req is None or req.done:
            return False
        req.cancel_requested = True
        self._wake()
        return True

    def admit_migrated(self, req: Request,
                       migrate_fn) -> Optional[RequestHandle]:
        """Admit a request whose KV arrives from ANOTHER engine's pool
        (disaggregated serving) — the decode-side half of a migration.

        ``req`` is a coordinator-owned ``Request`` carrying committed
        ``output_tokens``; its cache holds nothing yet. The method plans a
        prefix-cache-aware allocation for the ``seq_len - 1`` cached
        positions (matched full prompt blocks dedupe against this pool's
        content-hash index — their KV is bit-identical by construction, so
        the transfer skips them), claims the remaining blocks fresh, and
        calls ``migrate_fn(fresh_blocks, skip_blocks)`` to materialize their
        contents from the source pool. The request then joins the decode
        batch directly: ZERO prefill chunks run here, and the first decode
        writes position ``seq_len - 1`` — exactly where a preempt-resume
        would continue. Matched blocks are never written before the write
        position leaves them (the next write lands in a fresh or appended
        private block), so no copy-on-write is ever needed.

        Returns the engine-side ``RequestHandle``, or None when a batch slot
        or the worst-case block reservation is unavailable right now (the
        caller retries after capacity frees up)."""
        with self._lock:
            if req.rid in self._requests or req.rid in self.kv:
                raise ValueError(f"rid {req.rid} already live in this engine")
            cached = req.seq_len - 1
            plen = len(req.prompt)
            total = self.kv.blocks_for(plen + req.max_tokens)
            if plen + req.max_tokens > self.max_seq_len:
                raise ValueError(
                    f"prompt ({plen}) + max_tokens ({req.max_tokens}) "
                    f"exceeds max_seq_len ({self.max_seq_len})")
            if total > self.kv.num_blocks - 1:
                raise ValueError(
                    f"request needs {total} KV blocks but the pool only has "
                    f"{self.kv.num_blocks - 1}; it could never be admitted")
            n_blocks = self.kv.blocks_for(cached)
            if self.prefix_cache:
                matched, avail = self.kv.plan_admission(req.prompt)
            else:
                matched, avail = [], self.kv.num_available
            have_slot = len(self.running) + len(self.prefilling) \
                < self.max_batch
            if not have_slot or avail - self._reserved < total - len(matched):
                return None
            if self.prefix_cache:
                self.kv.commit_allocation(self.kv.plan_allocation(
                    req.rid, req.prompt, n_blocks, matched=matched))
            else:
                self.kv.allocate(req.rid, n_blocks)
            fresh = self.kv.block_table(req.rid)[len(matched):]
            if fresh:
                migrate_fn(fresh, len(matched))
            if self.prefix_cache:
                self.kv.register_prefix(req.rid, req.prompt)
            hit = len(matched) * self.kv.block_size
            req.cached_prefix_tokens = hit
            self.cached_tokens_total += hit
            self.prompt_tokens_total += plen
            req.migrated_blocks += len(fresh)
            self.migrated_blocks_total += len(fresh)
            self._migrated_step += len(fresh)
            req.reserved_blocks = total - n_blocks
            self._reserved += req.reserved_blocks
            req.cow_spare = 0
            req.status = RUNNING
            req.role = self.role
            handle = RequestHandle(self, req)
            self._requests[req.rid] = req
            self._handles[req.rid] = handle
            self.running.append(req)
            if self.telemetry is not None:
                self.telemetry.on_migrated(req, len(fresh))
        self._wake()
        return handle

    def withdraw(self, rid: int) -> Optional[Request]:
        """Remove a RUNNING request from this engine, freeing/parking its KV
        and returning the ``Request`` — committed outputs intact — to the
        caller instead of this engine's own queue. This is the disagg
        coordinator's cross-engine preemption primitive: the withdrawn
        request re-queues at the coordinator, re-prefills on the prefill
        engine, and re-migrates, exactly like an in-engine preempt-resume.
        Returns None when the rid is unknown or not currently running."""
        with self._lock:
            if self._inflight is not None:
                raise RuntimeError(
                    "cannot withdraw with a launched step in flight; "
                    "flush() first (disagg engines run pipeline=False)")
            req = self._requests.get(rid)
            if req is None or req.status != RUNNING:
                return None
            self.kv.free(rid)
            self._reserved -= req.reserved_blocks
            req.reserved_blocks = 0
            req.cow_spare = 0
            self.running = [r for r in self.running if r.rid != rid]
            self._requests.pop(rid, None)
            self._handles.pop(rid, None)
            req.status = PREEMPTED
            req.num_preemptions += 1
            self.preempted_total += 1
            if self.telemetry is not None:
                self.telemetry.on_preempt(req)
            return req

    def has_unfinished(self) -> bool:
        return bool(len(self.scheduler) or self.prefilling or self.running
                    or self._inflight is not None)

    def step(self) -> List[StepEvent]:
        """One engine iteration: process pending cancellations, advance the
        running batch (speculative draft->verify for eligible requests,
        single-token decode for the rest), admit waiting requests under the
        scheduler policy (prefix-cache-aware, possibly preempting), then
        advance every in-flight prefill by one chunk through a single
        batched call. Returns this iteration's StepEvents in commit order;
        they are also dispatched to each request's handle.

        With ``pipeline=True`` the same work is re-ordered into
        plan -> collect -> launch: host planning runs while the previously
        launched device step is still executing, its tokens commit at
        collect, and this step's device work is dispatched without blocking
        (resolved by the NEXT step, or by ``flush()``). Per-request token
        streams are identical in both modes."""
        with self._lock:
            step_span = _UNTIMED if self.telemetry is None else \
                self.telemetry.step_span(self._step_idx)
            with step_span:
                if self.pipeline:
                    return self._step_pipelined()
                return self._step_sync()

    def _step_sync(self) -> List[StepEvent]:
        """The original fully synchronous step: each phase launches AND
        collects before the next phase plans (the numerics/latency
        reference for the pipelined loop)."""
        t_step = time.perf_counter()
        self._sync_s = 0.0
        events: List[StepEvent] = []
        with self._span(PHASE_CANCEL):
            events += self._process_cancels()
        decode_batch = padded = 0
        spec_batch = drafted = accepted = 0
        if self.running:
            spec_rows = [r for r in self.running if self._can_spec(r)]
            normal_rows = [r for r in self.running if not self._can_spec(r)]
            if normal_rows:
                with self._span(PHASE_DECODE):
                    dl = self._launch_decode(normal_rows)
                    decode_batch, padded = dl.batch, dl.padded
                    events.extend(self._collect_decode(dl))
            if spec_rows:
                # draft / verify / sample sub-phases are timed inside
                sl = self._launch_spec(spec_rows, timed=True)
                spec_batch, drafted, accepted, evs = self._collect_spec(sl)
                events.extend(evs)
        with self._span(PHASE_ADMISSION):
            admitted, cached_toks, evs = self._admit()
        events.extend(evs)
        pf_tokens = 0
        if self.prefilling:
            with self._span(PHASE_PREFILL):
                pl = self._launch_prefill()
                pf_tokens = sum(pl.chunk_lens)
                events.extend(self._collect_prefill(pl))
        return self._finalize_step(
            events, t_step=t_step, decode_batch=decode_batch, padded=padded,
            admitted=admitted, cached_toks=cached_toks, pf_tokens=pf_tokens,
            spec_batch=spec_batch, drafted=drafted, accepted=accepted)

    def _step_pipelined(self) -> List[StepEvent]:
        """plan(N+1) concurrent with device(N): host planning first, then
        resolve the previously launched step, then dispatch new device work
        without blocking on it.

        The external contract (per-request event/token streams) matches the
        synchronous path. StepStats attribution shifts by construction:
        decode/prefill columns describe THIS call's launch, the spec
        columns describe the collected (previous) launch, and terminal /
        preempt counts describe events committed by this call.

        Safety invariant: while a launched step is in flight, every
        prefilling/running row is part of it, and plan-phase work only
        claims free or refcount-zero blocks — so cancels and preemptions of
        launched rows are DEFERRED and settle at collect, right after their
        in-flight tokens commit, and nothing the device is reading or
        writing is ever freed, COW-copied, or reallocated under it."""
        tm = self.telemetry
        t_step = time.perf_counter()
        self._sync_s = 0.0
        events: List[StepEvent] = []
        inflight = self._inflight
        # ---- plan: pure host work against committed state
        with self._span(PHASE_PLAN):
            with self._span(PHASE_CANCEL):
                events += self._process_cancels(
                    defer_inflight=inflight is not None)
            with self._span(PHASE_ADMISSION):
                admitted, cached_toks, evs = self._admit(
                    defer_preempt=inflight is not None)
        events.extend(evs)
        # ---- collect: resolve the previous launch, commit its tokens
        overlap_ms = 0.0
        spec_batch = drafted = accepted = 0
        if inflight is not None:
            self._inflight = None
            t_collect0 = time.perf_counter()
            overlap_ms = (t_collect0 - inflight.t_launched) * 1e3
            if tm is not None:
                tm.overlap(inflight.t_launched, t_collect0, self._step_idx)
            with self._span(PHASE_COLLECT):
                if inflight.decode is not None:
                    events.extend(self._collect_decode(inflight.decode))
                if inflight.spec is not None:
                    spec_batch, drafted, accepted, evs = self._collect_spec(
                        inflight.spec)
                    events.extend(evs)
                if inflight.prefill is not None:
                    events.extend(self._collect_prefill(inflight.prefill))
                events.extend(self._flush_pending_preempts())
        # ---- launch: dispatch on post-collect state; nothing blocks
        decode_batch = padded = pf_tokens = 0
        dl = sl = None
        with self._span(PHASE_LAUNCH):
            if self.running:
                spec_rows = [r for r in self.running if self._can_spec(r)]
                normal_rows = [r for r in self.running
                               if not self._can_spec(r)]
                if normal_rows:
                    dl = self._launch_decode(normal_rows)
                    decode_batch, padded = dl.batch, dl.padded
                if spec_rows:
                    sl = self._launch_spec(spec_rows, timed=False)
            pl = self._launch_prefill()
            if pl is not None:
                pf_tokens = sum(pl.chunk_lens)
            if dl is not None or sl is not None or pl is not None:
                self._inflight = InFlightStep(decode=dl, spec=sl, prefill=pl,
                                              t_launched=time.perf_counter())
        return self._finalize_step(
            events, t_step=t_step, decode_batch=decode_batch, padded=padded,
            admitted=admitted, cached_toks=cached_toks, pf_tokens=pf_tokens,
            spec_batch=spec_batch, drafted=drafted, accepted=accepted,
            overlap_ms=overlap_ms)

    def _finalize_step(self, events: List[StepEvent], *, t_step: float,
                       decode_batch: int, padded: int, admitted: int,
                       cached_toks: int, pf_tokens: int, spec_batch: int,
                       drafted: int, accepted: int,
                       overlap_ms: float = 0.0) -> List[StepEvent]:
        """Shared step epilogue: StepStats, telemetry rollup, handle
        dispatch. Identical between the synchronous and pipelined loops."""
        tm = self.telemetry
        self._step_idx += 1
        n_fin = sum(1 for e in events if e.kind == EVENT_FINISH)
        n_cancel = sum(1 for e in events if e.kind == EVENT_CANCEL)
        n_preempt = sum(1 for e in events if e.kind == EVENT_PREEMPT)
        self.stats.append(StepStats(
            step=self._step_idx, decode_batch=decode_batch,
            padded_batch=padded, prefills=admitted, finished=n_fin,
            running_after=len(self.running),
            waiting_after=len(self.scheduler),
            free_blocks=self.kv.num_available - self._reserved,
            reserved_blocks=self._reserved,
            cached_blocks=self.kv.num_evictable,
            prefilling_after=len(self.prefilling),
            prefill_tokens=pf_tokens, cached_prefix_tokens=cached_toks,
            cancelled=n_cancel, preempted=n_preempt,
            spec_batch=spec_batch,
            spec_drafted=drafted, spec_accepted=accepted,
            wall_ms=(time.perf_counter() - t_step) * 1e3,
            sync_ms=self._sync_s * 1e3,
            overlap_ms=overlap_ms,
            migrated_blocks=self._migrated_step,
            role=self.role))
        self._migrated_step = 0
        if self.max_stats is not None and len(self.stats) >= 2 * self.max_stats:
            del self.stats[:-self.max_stats]     # amortized O(1) trim
        if tm is not None:
            tm.on_step(kv=self.kv, reserved=self._reserved,
                       wall_s=time.perf_counter() - t_step,
                       sync_s=self._sync_s)
        self._dispatch_events(events)
        return events

    def _dispatch_events(self, events: List[StepEvent]) -> None:
        for ev in events:
            h = self._handles.get(ev.rid)
            if h is not None:
                h._on_event(ev)
                if ev.terminal:
                    self._handles.pop(ev.rid, None)

    def flush(self) -> List[StepEvent]:
        """Drain the pipelined tail: resolve the in-flight launched step (if
        any) WITHOUT launching new work, commit its tokens, dispatch its
        events to the handles, and return them. A no-op (empty list) in
        synchronous mode or when nothing is in flight. ``generate()`` and
        the engine loop drain via ``has_unfinished()`` + ``step()``, which
        subsumes this; the HTTP server calls it on shutdown so a launched
        step never leaks past the process's clean exit."""
        with self._lock:
            inflight = self._inflight
            if inflight is None:
                return []
            self._inflight = None
            self._sync_s = 0.0
            events: List[StepEvent] = []
            if inflight.decode is not None:
                events.extend(self._collect_decode(inflight.decode))
            if inflight.spec is not None:
                _, _, _, evs = self._collect_spec(inflight.spec)
                events.extend(evs)
            if inflight.prefill is not None:
                events.extend(self._collect_prefill(inflight.prefill))
            events.extend(self._flush_pending_preempts())
            self._dispatch_events(events)
            return events

    def generate(self, prompts: Sequence[Sequence[int]], *,
                 sampling: Optional[SamplingParams] = None,
                 max_tokens: int = 16,
                 eos_token_id: Optional[int] = None) -> List[RequestOutput]:
        """Batch-synchronous compat shim over the handle API: submit
        everything, drain the engine, return outputs in submission order."""
        handles = [self.submit(p, sampling=sampling, max_tokens=max_tokens,
                               eos_token_id=eos_token_id) for p in prompts]
        while self.has_unfinished():
            self.step()
        return [h.result() for h in handles]

    # ------------------------------------------------------------ internals

    def export_trace(self, path: str) -> None:
        """Write the Chrome-trace JSON timeline (requires telemetry with
        tracing on; open the file in chrome://tracing or ui.perfetto.dev)."""
        if self.telemetry is None or self.telemetry.trace is None:
            raise RuntimeError("engine was built without trace telemetry; "
                               "construct with ServingEngine(..., "
                               "telemetry=True)")
        with self._lock:
            live = list(self._requests.values())
        self.telemetry.trace.export(path, live_requests=live)

    def _jit_decode(self, padded_batch: int, width: int, greedy: bool):
        """``width`` is the bucketed block-table width the step runs at —
        decode gathers (ref) / walks (kernel) only ``width`` table columns
        instead of the full ``table_width``, so short-context steps stop
        paying for the padded span. It must be part of the cache key: jax
        would silently re-specialize on a new bt shape without going
        through here, bypassing the jit_compiles counter."""
        if (padded_batch, width, greedy) not in self._decode_fns:
            cfg = self.cfg_decode
            probe = self._probe

            # (bt, sl, toks, keys, temps, topks, topps) in;
            # (tok, last[, ffn_aux]) out — the probe rides as extra scan
            # outputs and never feeds back into the logits
            @functools.partial(jax.jit, donate_argnums=(1,),
                               **self._jit_kwargs(7, 3 if probe else 2))
            def decode_step(params, pools, bt, sl, toks, keys, temps, topks,
                            topps):
                out = lm.paged_decode_step(params, pools, bt, sl, toks, cfg,
                                           collect_aux=probe)
                logits, aux, pools = out if probe else (out[0], None, out[1])
                last = logits[:, -1]
                # all-greedy fast path: skip the O(V log V) top-k sort and
                # categorical draw entirely (the hot serving configuration)
                tok = jnp.argmax(last, -1).astype(jnp.int32) if greedy else \
                    sampling_mod.sample_tokens(last, keys, temps, topks,
                                               topps)
                return (tok, last, aux, pools) if probe else \
                    (tok, last, pools)
            self._decode_fns[(padded_batch, width, greedy)] = decode_step
        return self._decode_fns[(padded_batch, width, greedy)]

    def _jit_prefill(self, padded_batch: int, padded_chunk: int,
                     greedy: bool):
        key = (padded_batch, padded_chunk, greedy)
        if key not in self._prefill_fns:
            cfg = self.cfg_prefill
            probe = self._probe

            # (bt, toks, start, num_new, keys, temps, topks, topps) in;
            # (tok, last[, ffn_aux]) out
            @functools.partial(jax.jit, donate_argnums=(1,),
                               **self._jit_kwargs(8, 3 if probe else 2))
            def prefill_step(params, pools, bt, toks, start, num_new, keys,
                             temps, topks, topps):
                # last_only: the head runs on each row's final valid hidden
                # state only — never (B, C, V) over the whole chunk
                out = lm.paged_prefill(params, pools, bt, toks, num_new, cfg,
                                       start_lens=start, last_only=True,
                                       collect_aux=probe)
                logits, aux, pools = out if probe else (out[0], None, out[1])
                last = logits[:, 0]
                tok = jnp.argmax(last, -1).astype(jnp.int32) if greedy else \
                    sampling_mod.sample_tokens(last, keys, temps, topks,
                                               topps)
                return (tok, last, aux, pools) if probe else \
                    (tok, last, pools)
            self._prefill_fns[key] = prefill_step
        return self._prefill_fns[key]

    def _finish(self, req: Request, reason: str) -> RequestOutput:
        """Terminal transition (EOS / length / cancel), from ANY live state:
        queued requests hold no KV; admitted ones free/park their blocks and
        return their growth reservation."""
        if req.rid in self.kv:
            self.kv.free(req.rid)
        req.status = CANCELLED if reason == FINISH_CANCELLED else FINISHED
        req.finish_reason = reason
        req.finish_time = time.perf_counter()
        if self.telemetry is not None:
            # before RequestOutput.from_request so the FINISH/CANCEL instant
            # lands on the spans the output snapshots
            self.telemetry.on_terminal(req, reason,
                                       cancelled=reason == FINISH_CANCELLED)
        self._reserved -= req.reserved_blocks
        req.reserved_blocks = 0
        req.cow_spare = 0
        self.running = [r for r in self.running if r.rid != req.rid]
        self.prefilling = [r for r in self.prefilling if r.rid != req.rid]
        self._requests.pop(req.rid, None)
        return RequestOutput.from_request(req)

    def _terminal_event(self, req: Request, reason: str) -> StepEvent:
        out = self._finish(req, reason)
        kind = EVENT_CANCEL if reason == FINISH_CANCELLED else EVENT_FINISH
        if kind == EVENT_CANCEL:
            self.cancelled_total += 1
        else:
            self.finished_total += 1
        return StepEvent(kind=kind, rid=req.rid, step=self._step_idx,
                         output=out)

    def _process_cancels(self, defer_inflight: bool = False) \
            -> List[StepEvent]:
        """Abort every request flagged since the last step, wherever it is:
        queued (no KV to release), or admitted (prefilling/running/spec —
        blocks freed or parked, reservation returned).

        defer_inflight: plan-phase mode with a launched step still
        executing. Queued cancels process immediately (no KV, not part of
        any launch); prefilling/running rows are ALL part of the in-flight
        step — freeing their blocks now would mutate tables the device is
        still reading/writing — so their flag stays set and collect
        resolves it right after their launched tokens commit."""
        events: List[StepEvent] = []
        for req in [r for r in self.scheduler if r.cancel_requested]:
            self.scheduler.remove(req.rid)
            events.append(self._terminal_event(req, FINISH_CANCELLED))
        if defer_inflight:
            return events
        for req in [r for r in self.prefilling + self.running
                    if r.cancel_requested]:
            events.append(self._terminal_event(req, FINISH_CANCELLED))
        return events

    def _deferred_cancel(self, req: Request) -> Optional[StepEvent]:
        """Pipelined collect: resolve a cancel flagged while this row's
        step was in flight (its just-launched token has already committed —
        cancellation never shortens the stream vs the synchronous path).
        Always None in synchronous mode, whose cancel timing — flags
        processed at the NEXT step's cancel phase — must stay untouched."""
        if self.pipeline and req.cancel_requested:
            return self._terminal_event(req, FINISH_CANCELLED)
        return None

    def _flush_pending_preempts(self) -> List[StepEvent]:
        """Apply preemptions planned while a step was in flight. Runs at
        collect, after the victims' launched tokens committed; a victim
        that reached a terminal state in the meantime (finished naturally,
        or cancelled) has nothing left to preempt."""
        events: List[StepEvent] = []
        pending, self._preempt_pending = self._preempt_pending, []
        for req in pending:
            if not req.done and any(r.rid == req.rid for r in self.running):
                events.append(self._preempt(req))
        return events

    def _preempt(self, req: Request) -> StepEvent:
        """Evict a RUNNING request to relieve pool/slot pressure: free/park
        its KV (registered prompt blocks stay matchable in the prefix
        cache), return its reservation, and re-queue it. Committed output
        tokens are kept — resume re-prefills ``prompt + outputs`` and
        continues exactly where it left off (token-identical: per-token
        sampling keys depend only on committed-output length)."""
        self.kv.free(req.rid)
        self._reserved -= req.reserved_blocks
        req.reserved_blocks = 0
        self.running = [r for r in self.running if r.rid != req.rid]
        req.status = PREEMPTED
        req.num_preemptions += 1
        self.preempted_total += 1
        if self.telemetry is not None:
            self.telemetry.on_preempt(req)
        self.scheduler.add(req)
        return StepEvent(kind=EVENT_PREEMPT, rid=req.rid,
                         step=self._step_idx)

    def _can_spec(self, req: Request) -> bool:
        """Speculate when >= 2 tokens of budget remain (accepting even one
        draft must leave room for the verifier's correction/bonus token)."""
        return (self.spec is not None and not req.no_spec
                and req.max_tokens - len(req.output_tokens) >= 2)

    def _publish_ffn(self, ffn_aux, tokens: int, cfg_phase) -> None:
        """Hand a probed forward's per-layer (nnz, tile_frac) stack to the
        telemetry cost model. ``tokens`` is the REAL token count (padding
        rows contribute to the averaged stats but not to FLOPs credit)."""
        if ffn_aux is None or self.telemetry is None:
            return
        self.telemetry.on_ffn(
            tokens,
            np.asarray(ffn_aux["nnz_mean"], np.float64),
            tile_frac_per_layer=np.asarray(ffn_aux["tile_frac"], np.float64),
            ffn_present=np.asarray(ffn_aux["ffn_present"], np.float64),
            impl=cfg_phase.sparsity.ffn_impl)

    def _launch_decode(self, batch: List[Request]) -> DecodeLaunch:
        """Dispatch one batched decode call; no blocking readback. The
        device->host copy of the sampled row starts immediately so collect
        pays only the residual transfer tail."""
        b = len(batch)
        padded = _bucket(b, 1, self.max_batch)
        # The last sampled token is not in the cache yet: it is this step's
        # input, written at position seq_len - 1 (= cached token count).
        for r in batch:
            write_pos = r.seq_len - 1
            if write_pos // self.kv.block_size >= \
                    len(self.kv.block_table(r.rid)):
                self.kv.append_block(r.rid)
                r.reserved_blocks -= 1
                self._reserved -= 1
        # clamp the table to the batch's live page span (bucketed so the
        # shape grid stays warm): masked-out columns contribute exactly 0
        # to the softmax either way, so truncation is numerics-free, and
        # the gather/kernel cost tracks max(seq_lens) instead of the full
        # padded table width
        width = _bucket(max(len(self.kv.block_table(r.rid)) for r in batch),
                        1, self.table_width)
        bt = self.kv.table_array([r.rid for r in batch], padded, width)
        sl = np.zeros((padded,), np.int32)
        toks = np.zeros((padded, 1), np.int32)
        temps = np.zeros((padded,), np.float32)
        topks = np.zeros((padded,), np.int32)
        topps = np.ones((padded,), np.float32)
        for i, r in enumerate(batch):
            sl[i] = r.seq_len - 1
            toks[i, 0] = r.last_token
            temps[i] = r.sampling.temperature
            topks[i] = r.sampling.top_k
            topps[i] = r.sampling.top_p
        all_greedy = all(r.sampling.greedy for r in batch)
        keys = jnp.zeros((padded, 2), jnp.uint32)
        if not all_greedy:
            base = jnp.stack([r.base_key for r in batch])
            pos = jnp.asarray([len(r.output_tokens) for r in batch],
                              jnp.int32)
            keys = keys.at[:b].set(sampling_mod.batch_keys(base, pos))
        with self._mesh_ctx():
            fn = self._jit_decode(padded, width, all_greedy)
            out = self._dispatch(
                "decode", fn, self.params, self.kv.pools, jnp.asarray(bt),
                jnp.asarray(sl), jnp.asarray(toks), keys, jnp.asarray(temps),
                jnp.asarray(topks), jnp.asarray(topps))
            if self._probe:
                next_toks, logits, ffn_aux, pools = out
            else:
                (next_toks, logits, pools), ffn_aux = out, None
            self.kv.swap_pools(pools)
        start_host_copy(next_toks)
        return DecodeLaunch(rows=list(batch), batch=b, padded=padded,
                            next_toks=next_toks, logits=logits,
                            ffn_aux=ffn_aux)

    def _collect_decode(self, dl: DecodeLaunch) -> List[StepEvent]:
        """Resolve a launched decode: block on the sampled row (counted as
        sync), then commit one token per row and settle deferred cancels."""
        self._sync(dl.next_toks)
        next_toks = np.asarray(dl.next_toks)
        self._publish_ffn(dl.ffn_aux, dl.batch, self.cfg_decode)
        events: List[StepEvent] = []
        now = time.perf_counter()
        for i, r in enumerate(dl.rows):
            if r.logits_trace is not None:
                r.logits_trace.append(np.asarray(dl.logits[i], np.float32))
            reason = r.append(next_toks[i])
            if self.telemetry is not None:
                self.telemetry.on_tokens(r, 1, now)
            events.append(StepEvent(kind=EVENT_TOKEN, rid=r.rid,
                                    step=self._step_idx,
                                    tokens=(int(next_toks[i]),)))
            if reason:
                events.append(self._terminal_event(r, reason))
            else:
                cancel_ev = self._deferred_cancel(r)
                if cancel_ev is not None:
                    events.append(cancel_ev)
        return events

    def _launch_spec(self, rows: List[Request], *, timed: bool) -> SpecLaunch:
        """Dispatch draft -> verify for the speculating rows.

        Per step each row proposes ``k_eff = min(k, remaining - 1)`` tokens
        through the draft backend, then ONE batched trusted-backend pass
        scores all of them. The verify token block is concatenated ON
        DEVICE from the draft output, so both calls go out back-to-back
        with no host readback between them — in pipelined mode
        (``timed=False``) nothing here blocks at all; the synchronous path
        (``timed=True``) times the draft and verify phases by syncing each
        call's output inside its span."""
        b = len(rows)
        k = self.spec.k
        padded = _bucket(b, 1, self.max_batch)
        # cover every scratch position up front: draft+verify write positions
        # seq_len-1 .. seq_len+k_eff-1, all inside the admission reservation
        # (k_eff <= remaining - 1 implies seq_len + k_eff <= prompt+max_tokens)
        k_effs = []
        for r in rows:
            k_eff = min(k, r.max_tokens - len(r.output_tokens) - 1)
            k_effs.append(k_eff)
            need = self.kv.blocks_for(r.seq_len + k_eff)
            while len(self.kv.block_table(r.rid)) < need:
                self.kv.append_block(r.rid)
                r.reserved_blocks -= 1
                self._reserved -= 1
        bt = self.kv.table_array([r.rid for r in rows], padded,
                                 self.table_width)
        sl0 = np.zeros((padded,), np.int32)
        tok0 = np.zeros((padded, 1), np.int32)
        dlen = np.zeros((padded,), np.int32)
        temps = np.zeros((padded,), np.float32)
        topks = np.zeros((padded,), np.int32)
        topps = np.ones((padded,), np.float32)
        for i, r in enumerate(rows):
            sl0[i] = r.seq_len - 1
            tok0[i, 0] = r.last_token
            dlen[i] = k_effs[i]
            temps[i] = r.sampling.temperature
            topks[i] = r.sampling.top_k
            topps[i] = r.sampling.top_p
        all_greedy = all(r.sampling.greedy for r in rows)
        keys = jnp.zeros((k, padded, 2), jnp.uint32)
        if not all_greedy:
            base = jnp.stack([r.base_key for r in rows])
            pos = jnp.asarray([len(r.output_tokens) for r in rows], jnp.int32)
            keys = keys.at[:, :b].set(jnp.stack([
                sampling_mod.spec_batch_keys(base, pos + j,
                                             sampling_mod.STREAM_DRAFT)
                for j in range(k)]))
        with self._span(PHASE_DRAFT) if timed else _UNTIMED:
            with self._mesh_ctx():
                d_toks, d_logits, pools = self.drafter.draft(
                    self.params, self.kv.pools, jnp.asarray(bt),
                    jnp.asarray(sl0), jnp.asarray(tok0), jnp.asarray(dlen),
                    keys, jnp.asarray(temps), jnp.asarray(topks),
                    jnp.asarray(topps), greedy=all_greedy)
                self.kv.swap_pools(pools)
            if timed:
                self._sync(d_toks)
        num_new = dlen + (dlen > 0)            # k_eff + 1; 0 for padded rows
        with self._span(PHASE_VERIFY) if timed else _UNTIMED:
            with self._mesh_ctx():
                tok0_dev = jnp.asarray(tok0)
                if self.mesh is not None:
                    # commit the host column to the replicated layout d_toks
                    # already has, so the eager concat never guesses a
                    # sharding
                    tok0_dev = jax.device_put(
                        tok0_dev, sharding.replicated(self.mesh))
                verify_toks = jnp.concatenate([tok0_dev, d_toks], axis=1)
                t_logits, pools = self.verifier.verify(
                    self.params, self.kv.pools, jnp.asarray(bt),
                    jnp.asarray(sl0), jnp.asarray(num_new), verify_toks)
                self.kv.swap_pools(pools)
            if timed:
                self._sync(t_logits)
        start_host_copy(d_toks)
        start_host_copy(t_logits)
        if not all_greedy:
            start_host_copy(d_logits)
        return SpecLaunch(rows=list(rows), batch=b, padded=padded,
                          k_effs=k_effs, all_greedy=all_greedy,
                          d_toks=d_toks, d_logits=d_logits,
                          t_logits=t_logits)

    def _collect_spec(self, sl: SpecLaunch):
        """Resolve a launched draft+verify pair: accept on the host, commit
        the accepted prefix + correction/bonus token per row (>= 1 token
        guaranteed), roll the block-table tail covering rejected scratch
        positions back to the pool, and settle deferred cancels."""
        tm = self.telemetry
        self._sync(sl.d_toks, sl.t_logits)
        d_toks = np.asarray(sl.d_toks)
        t_logits = np.asarray(sl.t_logits)
        d_logits_np = None if sl.all_greedy else np.asarray(sl.d_logits)
        events: List[StepEvent] = []
        drafted_total = accepted_total = 0
        # host-side acceptance / rejection-sampling over the whole batch
        with self._span(PHASE_SAMPLE):
            for i, r in enumerate(sl.rows):
                k_eff = sl.k_effs[i]
                emitted, n_acc = self.verifier.accept(
                    r, k_eff, d_toks[i, :k_eff],
                    None if d_logits_np is None else d_logits_np[i, :k_eff],
                    t_logits[i, :k_eff + 1])
                r.spec_drafted += k_eff
                r.spec_accepted += n_acc
                drafted_total += k_eff
                accepted_total += n_acc
                reason = None
                committed = []
                for j, tok in enumerate(emitted):
                    if r.logits_trace is not None:
                        r.logits_trace.append(
                            t_logits[i, j].astype(np.float32))
                    committed.append(int(tok))
                    reason = r.append(int(tok))
                    if reason:
                        break
                if tm is not None:
                    tm.on_spec(r, k_eff, n_acc)
                    tm.on_tokens(r, len(committed))
                events.append(StepEvent(kind=EVENT_TOKEN, rid=r.rid,
                                        step=self._step_idx,
                                        tokens=tuple(committed)))
                if reason:
                    events.append(self._terminal_event(r, reason))
                else:
                    cancel_ev = self._deferred_cancel(r)
                    if cancel_ev is not None:
                        # _finish freed the whole table, scratch tail included
                        events.append(cancel_ev)
                        continue
                    # rollback: blocks past the committed length (seq_len - 1
                    # cached slots) return to the pool and the reservation
                    freed = rollback_after_verify(self.kv, r.rid,
                                                  r.seq_len - 1)
                    r.reserved_blocks += freed
                    self._reserved += freed
        return sl.batch, drafted_total, accepted_total, events

    def _admit(self, defer_preempt: bool = False):
        """Admit queued requests under the scheduler policy while a batch
        slot and (prefix-cache-aware) worst-case block capacity exist.
        Matched prefix blocks are shared instead of recomputed: only the
        suffix is allocated fresh and only suffix tokens will be prefilled.
        When the candidate does NOT fit, the scheduler may name a running
        victim to preempt — freeing its blocks (and slot) for the candidate
        and re-queueing it to resume later.

        defer_preempt: plan-phase mode with a launched step in flight.
        Victims must keep running until their launched tokens commit, so
        the planned set is parked in ``_preempt_pending`` (applied at
        collect) and the candidate re-tries on a later plan against the
        freed capacity. Block allocation itself is safe while in flight:
        ``plan_allocation``/``commit_allocation`` only claim free-list or
        refcount-zero LRU blocks, which no launched table references."""
        admitted = 0
        cached_tokens = 0
        events: List[StepEvent] = []
        while True:
            if self._preempt_pending:
                # a victim set is already planned but its blocks free only
                # at collect; admission state is stale until then
                break
            req = self.scheduler.peek()
            if req is None:
                break
            # a preempted request resumes by re-prefilling its prompt PLUS
            # its committed outputs (KV for both was dropped at preemption);
            # for a fresh request this is just the prompt
            target = req.prompt + req.output_tokens
            tlen = len(target)
            total = self.kv.blocks_for(len(req.prompt) + req.max_tokens)
            if self.prefix_cache:
                matched, avail = self.kv.plan_admission(target)
            else:
                matched, avail = [], self.kv.num_available
            # a fully cached target recomputes its last position inside a
            # matched block, which may need a copy-on-write block mid-step:
            # budget it here (and reserve it below) or ensure_writable could
            # steal a block promised to another request's decode growth
            spare = 1 if len(matched) * self.kv.block_size >= tlen else 0
            need = total - len(matched) + spare
            have_slot = len(self.running) + len(self.prefilling) \
                < self.max_batch
            if not have_slot or avail - self._reserved < need:
                # plan the full victim set BEFORE evicting anyone: if even
                # preempting every victim the policy would offer cannot fit
                # the candidate, defer without wasting their KV/progress
                # (plan_victims simulates the whole plan's refcounts and
                # mutates nothing)
                plan = plan_victims(
                    self.scheduler, req, self.running, self.kv,
                    reserved=self._reserved, avail=avail, need=need,
                    other_slots=len(self.prefilling),
                    max_batch=self.max_batch)
                if plan is None:
                    break              # defer: preemption cannot help
                if defer_preempt:
                    self._preempt_pending.extend(plan)
                    break              # victims free at collect; re-plan then
                for victim in plan:
                    events.append(self._preempt(victim))
                continue               # capacity changed: re-plan admission
            self.scheduler.take(req)
            target_blocks = self.kv.blocks_for(tlen)
            if self.prefix_cache:
                hit = self.kv.commit_allocation(self.kv.plan_allocation(
                    req.rid, target, target_blocks, matched=matched))
            else:
                self.kv.allocate(req.rid, target_blocks)
                hit = 0
            # a fully cached target still recomputes its last position: the
            # engine needs that position's logits to sample the next token
            start = min(hit, tlen - 1)
            req.prefill_pos = start
            req.prefill_target = target
            req.cached_prefix_tokens = start
            cached_tokens += start
            self.cached_tokens_total += start
            self.prompt_tokens_total += tlen
            if self.telemetry is not None:
                self.telemetry.on_admit(req, start, tlen - start)
            req.cow_spare = spare
            req.reserved_blocks = total - target_blocks + spare
            self._reserved += req.reserved_blocks
            req.status = PREFILLING
            self.prefilling.append(req)
            admitted += 1
        return admitted, cached_tokens, events

    def _launch_prefill(self) -> Optional[PrefillLaunch]:
        """Advance every in-flight prefill by one chunk in ONE batched call.

        Each row computes up to ``prefill_chunk`` tokens of its prefill
        target (prompt, plus committed outputs when resuming a preempted
        request) starting at its ``prefill_pos``, appended to whatever the
        cache already holds (cached prefix + earlier chunks) with per-row
        RoPE offsets. Rows whose target completes sample their next token
        from the same call and join the decode batch; the rest resume next
        step, interleaved with decode (bounded TTFT impact on running
        requests). Returns None when nothing is prefilling; otherwise the
        launched (unresolved) call — ``_collect_prefill`` commits it."""
        rows = list(self.prefilling)
        if not rows:
            return None
        b = len(rows)
        padded_b = _bucket(b, 1, self.max_batch)
        chunk_lens = [min(self.prefill_chunk,
                          len(r.prefill_target) - r.prefill_pos)
                      for r in rows]
        lo = min(self.min_prefill_bucket, self.prefill_chunk)
        padded_c = _bucket(max(chunk_lens), lo, self.prefill_chunk)
        toks = np.zeros((padded_b, padded_c), np.int32)
        start = np.zeros((padded_b,), np.int32)
        num_new = np.zeros((padded_b,), np.int32)
        temps = np.zeros((padded_b,), np.float32)
        topks = np.zeros((padded_b,), np.int32)
        topps = np.ones((padded_b,), np.float32)
        bs = self.kv.block_size
        for i, r in enumerate(rows):
            c = chunk_lens[i]
            s0 = r.prefill_pos
            # copy-on-write: a block this chunk writes into may be shared
            # with another live request (fully cached block-aligned prompt
            # recomputing its last position) — give this row a private copy
            for bi in range(s0 // bs, (s0 + c - 1) // bs + 1):
                self.kv.ensure_writable(r.rid, bi)
            if r.cow_spare:
                # the COW (or the certainty it is not needed) just resolved:
                # release the admission-time spare either way — if a copy
                # happened, the spare paid for the block it consumed
                r.reserved_blocks -= r.cow_spare
                self._reserved -= r.cow_spare
                r.cow_spare = 0
            toks[i, :c] = r.prefill_target[s0:s0 + c]
            start[i] = s0
            num_new[i] = c
            temps[i] = r.sampling.temperature
            topks[i] = r.sampling.top_k
            topps[i] = r.sampling.top_p
        # table_array AFTER ensure_writable: COW swaps table entries
        bt = self.kv.table_array([r.rid for r in rows], padded_b,
                                 self.table_width)
        all_greedy = all(r.sampling.greedy for r in rows)
        keys = jnp.zeros((padded_b, 2), jnp.uint32)
        if not all_greedy:
            base = jnp.stack([r.base_key for r in rows])
            # the token sampled at prefill completion is output position
            # len(output_tokens): 0 for a fresh request, the next committed
            # slot for a preempted-resumed one — same key either way, so
            # resume replays exactly what the uninterrupted run would draw
            pos = jnp.asarray([len(r.output_tokens) for r in rows],
                              jnp.int32)
            keys = keys.at[:b].set(sampling_mod.batch_keys(base, pos))
        with self._mesh_ctx():
            fn = self._jit_prefill(padded_b, padded_c, all_greedy)
            out = self._dispatch(
                "prefill", fn, self.params, self.kv.pools, jnp.asarray(bt),
                jnp.asarray(toks), jnp.asarray(start), jnp.asarray(num_new),
                keys, jnp.asarray(temps), jnp.asarray(topks),
                jnp.asarray(topps))
            if self._probe:
                tok, logits, ffn_aux, pools = out
            else:
                (tok, logits, pools), ffn_aux = out, None
            self.kv.swap_pools(pools)
        start_host_copy(tok)
        self.prefill_tokens_total += sum(chunk_lens)
        return PrefillLaunch(rows=rows, chunk_lens=chunk_lens, tok=tok,
                             logits=logits, ffn_aux=ffn_aux)

    def _collect_prefill(self, pl: PrefillLaunch) -> List[StepEvent]:
        """Resolve a launched prefill chunk: advance each row's position,
        settle deferred cancels, and for rows whose target completed commit
        the sampled token and move them to the decode batch (in pipelined
        mode that is THIS step's launch — join-on-arrival keeps its one-step
        cadence, just phase-shifted with everything else)."""
        self._sync(pl.tok)
        tok = np.asarray(pl.tok)
        self._publish_ffn(pl.ffn_aux, sum(pl.chunk_lens), self.cfg_prefill)
        events: List[StepEvent] = []
        for i, r in enumerate(pl.rows):
            r.prefill_pos += pl.chunk_lens[i]
            if r.prefill_pos < len(r.prefill_target):
                cancel_ev = self._deferred_cancel(r)
                if cancel_ev is not None:
                    events.append(cancel_ev)
                continue                              # more chunks to go
            if self.prefix_cache:
                self.kv.register_prefix(r.rid, r.prompt)
            if r.logits_trace is not None:
                r.logits_trace.append(np.asarray(pl.logits[i], np.float32))
            self.prefilling = [x for x in self.prefilling if x.rid != r.rid]
            r.status = RUNNING
            self.running.append(r)
            if self.telemetry is not None:
                self.telemetry.on_running(r)
            reason = r.append(int(tok[i]))
            if self.telemetry is not None:
                self.telemetry.on_tokens(r, 1)
            events.append(StepEvent(kind=EVENT_TOKEN, rid=r.rid,
                                    step=self._step_idx,
                                    tokens=(int(tok[i]),)))
            if self.on_prefill_done is not None:
                # disagg hook: the row's whole prefill target is cached and
                # its first token committed, but nothing is freed yet — the
                # coordinator can still hold the blocks for transfer
                self.on_prefill_done(r, reason)
            if reason:
                events.append(self._terminal_event(r, reason))
            else:
                cancel_ev = self._deferred_cancel(r)
                if cancel_ev is not None:
                    events.append(cancel_ev)
        return events

    # ---------------------------------------------------------------- warmup

    def warmup(self) -> List[Dict]:
        """Precompile the full bucketed shape grid so steady-state serving
        never pays a JIT compile: every decode batch bucket, every
        (batch, chunk) prefill bucket pair, and — with speculation on — the
        draft/verify shapes for the configured k, each in both the
        all-greedy and sampling variants. Dummy calls use all-null block
        tables with zero valid lengths, exactly the shape/trace every
        padded production row already exercises, so no allocator or request
        state is touched and the writes all land in the discarded null
        block. Records per-shape compile time in ``warmup_report``, the
        total in ``warmup_seconds`` (and the ``serving_warmup_seconds``
        gauge), and returns the report."""
        with self._lock:
            t_start = time.perf_counter()
            report: List[Dict] = []
            batches = bucket_grid(1, self.max_batch)
            lo = min(self.min_prefill_bucket, self.prefill_chunk)
            chunks = bucket_grid(lo, self.prefill_chunk)
            width = self.table_width

            def timed(entry, shape, call):
                t0 = time.perf_counter()
                out = call()
                jax.block_until_ready(out)
                report.append({"entry": entry, "shape": shape,
                               "seconds": time.perf_counter() - t0})
                return out

            with self._mesh_ctx():
                # decode runs at a clamped, bucketed table width (see
                # _launch_decode) — precompile every width bucket too
                grid = [("decode", padded, w) for padded in batches
                        for w in bucket_grid(1, width)]
                grid += [("prefill", padded, chunk) for padded in batches
                         for chunk in chunks]
                for entry, padded, size in grid:
                    args = self._null_step_args(entry, padded, size)
                    for greedy in (True, False):
                        fn = self._step_fn(entry, padded, size, greedy)
                        out = timed(entry, (padded, size, greedy),
                                    lambda: self._dispatch(
                                        entry, fn, self.params,
                                        self.kv.pools, *args))
                        self.kv.swap_pools(out[-1])
                if self.spec is not None:
                    k = self.spec.k
                    for padded in batches:
                        bt, sl0, tok0, _, temps, topks, topps = \
                            self._null_step_args("decode", padded, width)
                        dlen = jnp.zeros((padded,), jnp.int32)
                        dkeys = jnp.zeros((k, padded, 2), jnp.uint32)
                        for greedy in (True, False):
                            out = timed(
                                "draft", (padded, greedy),
                                lambda: self.drafter.draft(
                                    self.params, self.kv.pools, bt, sl0,
                                    tok0, dlen, dkeys, temps, topks, topps,
                                    greedy=greedy))
                            self.kv.swap_pools(out[-1])
                        vtoks = jnp.zeros((padded, k + 1), jnp.int32)
                        out = timed(
                            "verify", (padded,),
                            lambda: self.verifier.verify(
                                self.params, self.kv.pools, bt, sl0, dlen,
                                vtoks))
                        self.kv.swap_pools(out[-1])
            self.warmup_seconds = time.perf_counter() - t_start
            self.warmup_report = report
            if self.telemetry is not None:
                self.telemetry.on_warmup(self.warmup_seconds, len(report))
            return report

    def _step_fn(self, entry: str, padded: int, size: int, greedy: bool):
        """The jitted ``decode`` (``size`` = table width) or ``prefill``
        (``size`` = chunk) step program of one bucket."""
        if entry == "decode":
            return self._jit_decode(padded, size, greedy)
        return self._jit_prefill(padded, size, greedy)

    def _null_step_args(self, entry: str, padded: int, size: int) -> tuple:
        """Host args of one bucket's step (after params and pools) over
        all-null block tables with zero valid lengths — the shape/trace
        every padded production row already exercises, so no allocator or
        request state is touched and the writes all land in the discarded
        null block. temps=1/topp=1 keep the sampling variant's math
        well-defined even over the null block's garbage."""
        ints = functools.partial(jnp.zeros, dtype=jnp.int32)
        sampling = (jnp.zeros((padded, 2), jnp.uint32),
                    jnp.ones((padded,), jnp.float32), ints((padded,)),
                    jnp.ones((padded,), jnp.float32))
        if entry == "decode":      # (bt, sl, toks, keys, temps, topks, topps)
            return (ints((padded, size)), ints((padded,)),
                    ints((padded, 1))) + sampling
        # (bt, toks, start, num_new, keys, temps, topks, topps)
        return (ints((padded, self.table_width)), ints((padded, size)),
                ints((padded,)), ints((padded,))) + sampling

    def lower_step(self, entry: str, padded: int, size: int,
                   greedy: bool = True):
        """``jax.stages.Lowered`` of one bucket's ``decode``/``prefill``
        step program, as ``warmup`` compiles it — for inspecting what the
        device runs (``.compile().as_text()``)."""
        with self._lock, self._mesh_ctx():
            fn = self._step_fn(entry, padded, size, greedy)
            return fn.lower(self.params, self.kv.pools,
                            *self._null_step_args(entry, padded, size))
