"""KV-block transfer between engine pools: buffer + transport.

Disaggregated serving moves a request's cached KV from the prefill engine's
paged pool into the decode engine's. Two pieces live here:

``TransferBuffer``
    A bounded, request-id-keyed map of published-but-unclaimed transfers.
    Publishing pins the source blocks via ``PagedKVCache.hold`` under a
    synthetic negative owner id, so the prefill engine can finish (and
    ``free``) the request without the block contents being reallocated out
    from under the pending transfer. Claiming (or cancelling) releases the
    hold; a TTL sweep expires entries no decode engine claimed in time, so
    a stalled or dead consumer can never leak prefill-pool blocks — the
    expired request simply re-queues and re-prefills (migration IS a
    resume, so nothing is lost but work).

``Transport``
    The copy mechanism, as an ABC so the in-process implementations can be
    swapped for a socket/RDMA transport later without touching the
    coordinator: ``transfer(src_kv, dst_kv, src_blocks, dst_blocks)`` moves
    whole blocks (every layer, both K and V pools) between pools.

      ``InProcessTransport``      one fused jitted gather/scatter per
                                  power-of-two block-count bucket (block
                                  ids padded with the null block, whose
                                  contents are never read — the same trick
                                  every padded engine step already uses).
      ``HostRoundtripTransport``  device -> host ``bytes`` -> device. The
                                  explicit bytes boundary is exactly the
                                  payload a socket transport would ship;
                                  it exists to prove the extension point
                                  (and is the reference the fused path is
                                  tested against).

Thread safety: the buffer has no lock of its own — every caller runs under
the coordinator's lock (publishes happen inside the prefill engine's
``step()``, which the coordinator drives).
"""
from __future__ import annotations

import abc
import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.kv_cache import NULL_BLOCK, PagedKVCache
from repro.serving.pipeline import bucket_grid


@dataclasses.dataclass(frozen=True)
class TransferEntry:
    """One published, not-yet-claimed KV migration."""

    rid: int                     # coordinator request id (the buffer key)
    hold_id: int                 # synthetic owner pinning the source blocks
    blocks: Tuple[int, ...]      # source block ids, table order
    cached_tokens: int           # KV positions the blocks hold (seq_len - 1)
    published_step: int          # coordinator step at publish (TTL base)
    published_t: float           # wall clock at publish (wait metrics)


class TransferBuffer:
    """Bounded rid-keyed buffer of pending KV transfers over one source
    pool. Holds (refcounts) the source blocks from publish until claim /
    cancel / TTL expiry."""

    def __init__(self, src_kv: PagedKVCache, *, max_entries: int = 8,
                 ttl_steps: Optional[int] = 64):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        if ttl_steps is not None and ttl_steps < 1:
            raise ValueError(f"ttl_steps must be >= 1, got {ttl_steps}")
        self.src_kv = src_kv
        self.max_entries = max_entries
        self.ttl_steps = ttl_steps
        self._entries: Dict[int, TransferEntry] = {}
        self.published_total = 0
        self.claimed_total = 0
        self.cancelled_total = 0
        self.expired_total = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, rid: int) -> bool:
        return rid in self._entries

    @property
    def full(self) -> bool:
        return len(self._entries) >= self.max_entries

    @property
    def blocks_pinned(self) -> int:
        """Source-pool blocks currently pinned by unclaimed entries."""
        return sum(len(e.blocks) for e in self._entries.values())

    def get(self, rid: int) -> Optional[TransferEntry]:
        return self._entries.get(rid)

    def entries(self) -> List[TransferEntry]:
        return list(self._entries.values())

    def publish(self, rid: int, blocks: Sequence[int], cached_tokens: int,
                step: int, now: Optional[float] = None) -> TransferEntry:
        """Pin ``blocks`` in the source pool and enter them under ``rid``.
        Must be called while the source request still owns its table (the
        engine's ``on_prefill_done`` hook guarantees that window)."""
        if self.full:
            raise RuntimeError(
                f"transfer buffer full ({self.max_entries} entries); the "
                "coordinator must gate prefill submissions on headroom")
        if rid in self._entries:
            raise ValueError(f"rid {rid} already has a pending transfer")
        hold_id = -(rid + 1)          # rids are >= 0, so never collides
        self.src_kv.hold(hold_id, blocks)
        entry = TransferEntry(
            rid=rid, hold_id=hold_id, blocks=tuple(int(b) for b in blocks),
            cached_tokens=int(cached_tokens), published_step=int(step),
            published_t=time.perf_counter() if now is None else now)
        self._entries[rid] = entry
        self.published_total += 1
        return entry

    def claim(self, rid: int) -> TransferEntry:
        """Remove ``rid``'s entry and release its hold. The caller must have
        already copied the block contents out (the coordinator runs the
        transport inside ``admit_migrated``, while the hold is live)."""
        entry = self._entries.pop(rid)
        self.src_kv.free(entry.hold_id)
        self.claimed_total += 1
        return entry

    def cancel(self, rid: int) -> bool:
        """Drop a pending transfer (request cancelled mid-transfer),
        releasing its hold. False when ``rid`` has no pending entry."""
        entry = self._entries.pop(rid, None)
        if entry is None:
            return False
        self.src_kv.free(entry.hold_id)
        self.cancelled_total += 1
        return True

    def expire(self, now_step: int) -> List[TransferEntry]:
        """Drop every entry unclaimed for ``ttl_steps`` coordinator steps,
        releasing the holds; returns the expired entries so the coordinator
        can re-queue their requests. No-op when TTL is disabled (None)."""
        if self.ttl_steps is None:
            return []
        expired = [e for e in self._entries.values()
                   if now_step - e.published_step >= self.ttl_steps]
        for e in expired:
            del self._entries[e.rid]
            self.src_kv.free(e.hold_id)
            self.expired_total += 1
        return expired


class Transport(abc.ABC):
    """Block-content copy between two paged pools. Implementations move
    whole blocks — every layer, K and V — for the given id lists (equal
    length, positionally paired). Pools must be unsharded (the disagg
    coordinator rejects meshes; a sharded transport is future work)."""

    @abc.abstractmethod
    def transfer(self, src_kv: PagedKVCache, dst_kv: PagedKVCache,
                 src_blocks: Sequence[int],
                 dst_blocks: Sequence[int]) -> None:
        """Copy ``src_blocks[i] -> dst_blocks[i]`` contents."""

    def warmup(self, src_kv: PagedKVCache, dst_kv: PagedKVCache,
               max_blocks: int) -> int:
        """Precompile whatever shape grid ``transfer`` uses, up to
        ``max_blocks`` per call; returns shapes compiled (0 by default)."""
        return 0


class InProcessTransport(Transport):
    """Fused on-device copy: one jitted gather/scatter moves all requested
    blocks across both pools in a single dispatch. Block-id vectors are
    padded to power-of-two buckets with the null block (src null contents
    land in the dst null block, which no live table references and whose
    positions attention masks out), so compile count is bounded by
    ``log2(max blocks per transfer)``."""

    def __init__(self):
        self._copy_fns: Dict[int, callable] = {}

    def _copy_fn(self, padded: int):
        if padded not in self._copy_fns:
            @functools.partial(jax.jit, donate_argnums=(1,))
            def copy(src_pools, dst_pools, src_ids, dst_ids):
                return {k: v.at[:, dst_ids].set(src_pools[k][:, src_ids])
                        for k, v in dst_pools.items()}
            self._copy_fns[padded] = copy
        return self._copy_fns[padded]

    def transfer(self, src_kv, dst_kv, src_blocks, dst_blocks) -> None:
        if len(src_blocks) != len(dst_blocks):
            raise ValueError(
                f"block count mismatch: {len(src_blocks)} src vs "
                f"{len(dst_blocks)} dst")
        n = len(src_blocks)
        if n == 0:
            return
        padded = 1 << (n - 1).bit_length()       # pow2 round-up, uncapped
        src_ids = np.full((padded,), NULL_BLOCK, np.int32)
        dst_ids = np.full((padded,), NULL_BLOCK, np.int32)
        src_ids[:n] = src_blocks
        dst_ids[:n] = dst_blocks
        fn = self._copy_fn(padded)
        dst_kv.swap_pools(fn(src_kv.pools, dst_kv.pools,
                             jnp.asarray(src_ids), jnp.asarray(dst_ids)))

    def warmup(self, src_kv, dst_kv, max_blocks: int) -> int:
        shapes = 0
        hi = 1 << max(0, max_blocks - 1).bit_length()
        for padded in bucket_grid(1, hi):
            ids = jnp.zeros((padded,), jnp.int32)       # all-null: no-op copy
            fn = self._copy_fn(padded)
            out = fn(src_kv.pools, dst_kv.pools, ids, ids)
            jax.block_until_ready(out)
            dst_kv.swap_pools(out)
            shapes += 1
        return shapes


class HostRoundtripTransport(Transport):
    """Copy via an explicit host ``bytes`` payload — the socket-transport
    stand-in. ``transfer`` serializes the source blocks exactly as a wire
    transport would (contiguous buffer + shape + dtype per pool), then
    deserializes into the destination. Slow by construction; exists to
    prove the ABC boundary carries everything a cross-process impl needs
    and as a reference for testing the fused path."""

    def transfer(self, src_kv, dst_kv, src_blocks, dst_blocks) -> None:
        if len(src_blocks) != len(dst_blocks):
            raise ValueError(
                f"block count mismatch: {len(src_blocks)} src vs "
                f"{len(dst_blocks)} dst")
        if not src_blocks:
            return
        src_ids = np.asarray(src_blocks, np.int32)
        payload = {}
        for k, pool in src_kv.pools.items():
            arr = np.asarray(pool[:, src_ids])       # (L, n, bs, Hkv*hd)
            payload[k] = (arr.tobytes(), arr.shape, str(arr.dtype))
        # -- everything below this line could run in another process --
        dst_ids = np.asarray(dst_blocks, np.int32)
        new_pools = {}
        for k, pool in dst_kv.pools.items():
            buf, shape, dtype = payload[k]
            arr = np.frombuffer(buf, dtype=dtype).reshape(shape)
            new_pools[k] = pool.at[:, dst_ids].set(jnp.asarray(arr))
        dst_kv.swap_pools(new_pools)
