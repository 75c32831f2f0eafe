"""k-token autoregressive draft loop through the cheap sparse path.

One jitted call drafts ``k`` tokens for the whole speculating batch: a
``lax.scan`` over ``k`` single-token ``lm.paged_decode_step`` calls run under
the *draft* config (e.g. thresholded tile-skip). Draft KV lands in scratch
positions — each request's pages past its committed length, which admission
already reserved (``k_eff <= remaining - 1`` keeps every write inside the
request's worst-case block reservation). Rows that can draft fewer than ``k``
tokens route their surplus writes to the null block (``write_valid``), so a
draft overshoot can never dirty the pool.

The draft's K/V values are approximate (they came through the lossy path);
the verifier's batched pass rewrites every drafted position with exact
values before anything is committed, so the approximation can only ever cost
acceptance rate, never correctness.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import lm
from repro.serving import sampling as sampling_mod


def call(entry: str, fn, *args):
    """Call the jitted program ``fn`` of entry point ``entry`` as it is."""
    return fn(*args)


class Drafter:
    """Runs the k-token draft loop under the draft backend's config."""

    def __init__(self, cfg_draft: ModelConfig, k: int):
        self.cfg = cfg_draft
        self.k = k
        # tensor-parallel serving: the engine installs explicit
        # in/out_shardings (params + pools sharded, host args replicated)
        # so the whole k-step scan compiles under the mesh
        self.jit_shardings: Dict = {}
        # dispatch(entry, fn, *args) calls a jitted program; the engine
        # installs its own, which times a program's first (compiling) call
        self.dispatch = call
        self._fns: Dict[Tuple[int, bool], callable] = {}

    def _jit(self, padded_batch: int, greedy: bool):
        if (padded_batch, greedy) not in self._fns:
            cfg, k = self.cfg, self.k

            @functools.partial(jax.jit, donate_argnums=(1,),
                               **self.jit_shardings)
            def draft_step(params, pools, bt, sl0, tok0, draft_len, keys,
                           temps, topks, topps):
                # keys: (k, B, 2) per-step per-request draft keys
                def step(carry, xs):
                    tok, pools = carry
                    j, step_keys = xs
                    sl = sl0 + j
                    logits, pools = lm.paged_decode_step(
                        params, pools, bt, sl, tok, cfg,
                        write_valid=j < draft_len)
                    last = logits[:, -1]
                    nxt = jnp.argmax(last, -1).astype(jnp.int32) if greedy \
                        else sampling_mod.sample_tokens(last, step_keys,
                                                        temps, topks, topps)
                    return (nxt[:, None], pools), (nxt, last)

                (_, pools), (toks, logits) = jax.lax.scan(
                    step, (tok0, pools), (jnp.arange(k), keys))
                # scan stacks along the step axis -> (B, k[, V])
                return (jnp.swapaxes(toks, 0, 1),
                        jnp.swapaxes(logits, 0, 1), pools)
            self._fns[(padded_batch, greedy)] = draft_step
        return self._fns[(padded_batch, greedy)]

    def draft(self, params, pools, bt, sl0, tok0, draft_len, keys, temps,
              topks, topps, *, greedy: bool):
        """Draft ``k`` tokens per row.

        bt: (B, W) block tables; sl0: (B,) committed cache lengths; tok0:
        (B, 1) last committed tokens; draft_len: (B,) per-row valid draft
        budget (writes for steps >= draft_len go to the null block); keys:
        (k, B, 2) draft PRNG keys (zeros for an all-greedy batch). Returns
        (draft_tokens (B, k), draft_logits (B, k, V), pools).
        """
        fn = self._jit(bt.shape[0], greedy)
        return self.dispatch("draft", fn, params, pools, bt, sl0, tok0,
                             draft_len, keys, temps, topks, topps)
