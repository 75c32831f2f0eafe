"""Batched verify pass + exact acceptance for speculative decoding.

``Verifier.verify`` scores all drafted tokens for the whole speculating
batch in ONE jitted multi-token forward through the *trusted* backend
(``lm.paged_verify``), which also overwrites the draft loop's approximate
K/V with exact values position by position — after verify, the cache is
bitwise what non-speculative decoding would have written.

``Verifier.accept`` is the host-side acceptance rule per request:

  greedy     — accept drafted d_j while it equals argmax(target_j); emit the
               corrected argmax at the first mismatch, or the bonus argmax
               when everything matched. Output is therefore always an argmax
               of trusted-path logits — token-identical to non-speculative
               greedy decoding.
  stochastic — exact rejection sampling (Leviathan et al. / vLLM): accept
               d_j with probability min(1, p_j(d_j) / q_j(d_j)); at the
               first rejection resample from norm(max(p_j - q_j, 0)); if all
               k drafts are accepted, draw the bonus token from p_k. Both p
               and q are built by ``sampling.filter_logits`` — the same
               temperature/top-k/top-p truncation the non-speculative
               sampler uses — so the output *distribution* is exactly that
               of non-speculative decoding for any acceptance rate.

Draws use per-(request, position, stream) keys derived from the request's
base key, so a seeded speculative request is reproducible and independent
of batch composition, like everything else in the engine.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import lm
from repro.serving import sampling as sampling_mod
from repro.serving.request import Request
from repro.serving.spec.drafter import call


class Verifier:
    """One batched trusted-path forward over drafted chunks + acceptance."""

    def __init__(self, cfg_verify: ModelConfig, k: int):
        self.cfg = cfg_verify
        self.k = k
        # tensor-parallel serving: the engine installs explicit
        # in/out_shardings so the batched verify compiles under the mesh
        self.jit_shardings: Dict = {}
        # dispatch(entry, fn, *args) calls a jitted program; the engine
        # installs its own, which times a program's first (compiling) call
        self.dispatch = call
        self._fns: Dict[int, callable] = {}

    # ------------------------------------------------------------ device side

    def _jit(self, padded_batch: int):
        if padded_batch not in self._fns:
            cfg = self.cfg

            @functools.partial(jax.jit, donate_argnums=(1,),
                               **self.jit_shardings)
            def verify_step(params, pools, bt, start, num_new, toks):
                logits, pools = lm.paged_verify(params, pools, bt, start,
                                                num_new, toks, cfg)
                return logits.astype(jnp.float32), pools
            self._fns[padded_batch] = verify_step
        return self._fns[padded_batch]

    def verify(self, params, pools, bt, start, num_new, toks):
        """toks: (B, k+1) = last committed token + k drafts per row;
        start: (B,) committed cache lengths; num_new: (B,) valid chunk
        lengths (k_eff + 1; 0 for padded rows). Returns
        (logits float32 (B, k+1, V), pools); logits row j scores the token
        following position start + j."""
        fn = self._jit(bt.shape[0])
        return self.dispatch("verify", fn, params, pools, bt, start, num_new,
                             toks)

    # -------------------------------------------------------------- host side

    def _dists(self, logits_rows: np.ndarray, sp) -> np.ndarray:
        """The request's sampling distributions for a stack of positions —
        the SAME truncation the non-speculative sampler applies, computed in
        one batched call (one dispatch per request, not per position)."""
        n = logits_rows.shape[0]
        masked = sampling_mod.filter_logits(
            jnp.asarray(logits_rows),
            jnp.full((n,), sp.temperature, jnp.float32),
            jnp.full((n,), sp.top_k, jnp.int32),
            jnp.full((n,), sp.top_p, jnp.float32))
        return np.asarray(jax.nn.softmax(masked, axis=-1), np.float64)

    def accept(self, req: Request, k_eff: int, draft_toks: np.ndarray,
               draft_logits: np.ndarray, target_logits: np.ndarray
               ) -> Tuple[List[int], int]:
        """Acceptance rule for one request.

        draft_toks: (k_eff,); draft_logits: (k_eff, V) draft-path logits
        that produced them; target_logits: (k_eff + 1, V) trusted-path
        logits. Returns (emitted_tokens, num_accepted): the accepted draft
        prefix plus exactly one trusted-path token (correction or bonus),
        so every speculative step emits >= 1 token and can never stall.
        """
        if req.sampling.greedy:
            tgt = np.argmax(target_logits, axis=-1)
            emitted: List[int] = []
            for j in range(k_eff):
                if int(draft_toks[j]) != int(tgt[j]):
                    emitted.append(int(tgt[j]))
                    return emitted, j
                emitted.append(int(draft_toks[j]))
            emitted.append(int(tgt[k_eff]))
            return emitted, k_eff

        sp = req.sampling
        pos0 = len(req.output_tokens)
        p_all = self._dists(target_logits, sp)         # (k_eff + 1, V)
        q_all = self._dists(draft_logits, sp)          # (k_eff, V)
        emitted = []
        for j in range(k_eff):
            d = int(draft_toks[j])
            p, q = p_all[j], q_all[j]
            u = float(jax.random.uniform(sampling_mod.spec_key(
                req.base_key, pos0 + j, sampling_mod.STREAM_ACCEPT)))
            # accept with prob min(1, p(d)/q(d)); q(d) > 0 since d ~ q
            if u * q[d] <= p[d]:
                emitted.append(d)
                continue
            residual = np.maximum(p - q, 0.0)
            total = residual.sum()
            dist = residual / total if total > 0 else p
            tok = int(jax.random.categorical(
                sampling_mod.spec_key(req.base_key, pos0 + j,
                                      sampling_mod.STREAM_RESAMPLE),
                jnp.log(jnp.asarray(np.maximum(dist, 1e-38)))))
            emitted.append(tok)
            return emitted, j
        tok = int(jax.random.categorical(
            sampling_mod.spec_key(req.base_key, pos0 + k_eff,
                                  sampling_mod.STREAM_RESAMPLE),
            jnp.log(jnp.asarray(np.maximum(p_all[k_eff], 1e-38)))))
        emitted.append(tok)
        return emitted, k_eff
