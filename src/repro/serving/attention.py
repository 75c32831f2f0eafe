"""Pluggable paged-attention backends for the serving engine.

Sibling of the FFN ``ServingBackend`` split (``serving/backends.py``), same
sglang ``AttentionBackend`` shape: one object picks the *attention read*
implementation for both serving regimes — ``forward_decode`` (one query
token against the paged history) and ``forward_extend`` (a chunk appended
to history: prefill, chunked/prefix-cached prefill, speculative verify).
The page scatter, ``write_valid`` null-block routing, and ``num_new``
padded-tail routing stay shared plain-JAX in ``models.layers`` — backends
differ only in how the scattered pools are read. Every backend takes the
whole (L, N, bs, Hkv*hd) pools the layer scan carries plus the layer index
to read, so no per-layer slice of the pools is ever materialised:

  ref        gather every table page + repeat_kv + masked SDPA (plain JAX —
             the numerics reference every other backend is tested against)
  pallas     fused Pallas kernels (flash-decoding split-K decode kernel +
             chunk-append kernel); block tables consumed in-kernel so only
             live pages are touched. TPU only, and the TPU default.
  interpret  the same kernels through Pallas interpret mode — off-TPU only,
             used by CI to pin kernel semantics to the ref backend.

With no backend named, the platform decides: ``pallas`` on a TPU, ``ref``
elsewhere (``default_attn_backend``).

Under a tensor-parallel mesh the kernel backends run inside ``shard_map``
over the ``model`` axis (q/pools head-sharded, tables/lens/layer replicated),
matching the ref path's head sharding, so the per-device kernel sees local
head counts and only the downstream wo projection all-reduces.
"""
from __future__ import annotations

import dataclasses
from abc import ABC, abstractmethod
from typing import Dict, Type

from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.distributed.sharding import model_sharded
from repro.kernels import ops


class AttentionBackend(ABC):
    """Selects the paged-attention read path for each serving regime."""

    name: str = "abstract"
    kernel_mode: str = "ref"        # repro.kernels.ops dispatch mode

    def configure(self, cfg: ModelConfig) -> ModelConfig:
        """A config whose paged-attention path is this backend."""
        return dataclasses.replace(cfg, attn_backend=self.name)

    def validate_platform(self, platform: str) -> None:
        """Reject backend/platform pairs that cannot execute or that would
        hide the device (called once at engine startup)."""

    @abstractmethod
    def forward_decode(self, q, kpool, vpool, block_tables, seq_lens, layer):
        """(B, 1, H, hd) decode-attention read over layer ``layer``'s paged
        history."""

    @abstractmethod
    def forward_extend(self, q, kpool, vpool, block_tables, seq_lens,
                       num_new, layer):
        """(B, S, H, hd) chunk-append read: history + causal-within-chunk."""

    def describe(self) -> str:
        return f"{self.name}: kernel_mode={self.kernel_mode}"


class RefAttentionBackend(AttentionBackend):
    """Gather-pages SDPA in plain JAX — the numerics reference. The engine
    short-circuits this backend inside ``models.layers._paged_attention``
    (shared-sharding fast path); these methods exist so tests and tools can
    call every backend through one interface."""

    name = "ref"
    kernel_mode = "ref"

    def forward_decode(self, q, kpool, vpool, block_tables, seq_lens, layer):
        return ops.paged_attention_decode(q, kpool, vpool, block_tables,
                                          seq_lens, layer, mode="ref")

    def forward_extend(self, q, kpool, vpool, block_tables, seq_lens,
                       num_new, layer):
        return ops.paged_attention_extend(q, kpool, vpool, block_tables,
                                          seq_lens, num_new, layer,
                                          mode="ref")


def _shard_mapped(fn, q, kpool, vpool, *scalars):
    """Run a paged-attention kernel shard-local over the ``model`` axis: q
    split on its head axis and the pools on their Hkv*hd row (whole kv
    heads per device), block tables / seq_lens / num_new / the layer index
    replicated. ``validate_mesh`` has already refused head counts the axis
    does not divide."""
    heads = P(None, None, "model", None)
    pools = P(None, None, None, "model")
    return model_sharded(fn, (heads, pools, pools) + (P(),) * len(scalars),
                         heads)(q, kpool, vpool, *scalars)


class PallasAttentionBackend(AttentionBackend):
    """Fused paged-attention Pallas kernels (flash-decoding decode +
    chunk-append extend). Compiled TPU execution; refuse anything else at
    startup instead of failing deep inside the first jitted step."""

    name = "pallas"
    kernel_mode = "pallas"

    def validate_platform(self, platform: str) -> None:
        if platform != "tpu":
            raise ValueError(
                f"attention backend {self.name!r} requires TPU, got "
                f"platform {platform!r} — use attn_backend='interpret' "
                f"(same kernels, Pallas interpret mode) or 'ref' on CPU")

    def forward_decode(self, q, kpool, vpool, block_tables, seq_lens, layer):
        def call(q, kpool, vpool, bt, sl, layer):
            return ops.paged_attention_decode(q, kpool, vpool, bt, sl, layer,
                                              mode=self.kernel_mode)
        return _shard_mapped(call, q, kpool, vpool, block_tables, seq_lens,
                             layer)

    def forward_extend(self, q, kpool, vpool, block_tables, seq_lens,
                       num_new, layer):
        def call(q, kpool, vpool, bt, sl, nn, layer):
            return ops.paged_attention_extend(q, kpool, vpool, bt, sl, nn,
                                              layer, mode=self.kernel_mode)
        return _shard_mapped(call, q, kpool, vpool, block_tables, seq_lens,
                             num_new, layer)


class InterpretAttentionBackend(PallasAttentionBackend):
    """The Pallas kernels evaluated in interpret mode (lowered to plain lax
    ops), so CPU CI can pin kernel numerics and engine token identity
    against the ref backend without TPU hardware. Refused on a TPU, where
    it would stand in for the compiled kernels."""

    name = "interpret"
    kernel_mode = "interpret"

    def validate_platform(self, platform: str) -> None:
        if platform == "tpu":
            raise ValueError(
                f"attention backend {self.name!r} emulates the kernels off "
                f"TPU; on a TPU use 'pallas' (the default) or 'ref'")


_REGISTRY: Dict[str, Type[AttentionBackend]] = {}


def register(cls: Type[AttentionBackend]) -> Type[AttentionBackend]:
    _REGISTRY[cls.name] = cls
    return cls


for _cls in (RefAttentionBackend, PallasAttentionBackend,
             InterpretAttentionBackend):
    register(_cls)

ATTN_BACKENDS = tuple(sorted(_REGISTRY))


def default_attn_backend() -> str:
    """The platform's attention backend: the compiled kernels on a TPU, the
    plain-JAX reference elsewhere — the FFN kernels' rule."""
    return ops.kernel_mode()


def get_attn_backend(name_or_backend=None, **kwargs) -> AttentionBackend:
    """Resolve an attention backend by name (None = the platform default),
    or pass an instance through."""
    if isinstance(name_or_backend, AttentionBackend):
        return name_or_backend
    if name_or_backend is None:
        name_or_backend = default_attn_backend()
    try:
        return _REGISTRY[name_or_backend](**kwargs)
    except KeyError:
        raise ValueError(f"unknown attention backend {name_or_backend!r}; "
                         f"have {sorted(_REGISTRY)}") from None
