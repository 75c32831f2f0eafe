"""FFN execution backends for the serving engine.

The paper's serving story is one flag: the same weights decode either through
the dense XLA path or through the TwELL sparse path (pack-in-gate-matmul +
fused up/down projection, Algorithms 1-2 / Eq. 3). A ``ServingBackend``
(in the spirit of sglang's ``AttentionBackend`` ABC) selects the FFN
implementation per step kind, so dense-vs-sparse serving is
``ServingEngine(..., backend="gather")`` vs ``backend="dense"`` — nothing
else in the engine changes.

Every backend's jitted entrypoint follows the engine's donation contract:
KV pools go in donated and come back as fresh (unresolved) device buffers,
so a backend implementation must never stash or reuse a pool handle it was
called with — only the returned pools are alive (see
``repro.models.lm.paged_prefill`` and ``PagedKVCache.swap_pools``).
"""
from __future__ import annotations

import dataclasses
from abc import ABC, abstractmethod
from typing import Dict, Type

from repro.config import ModelConfig

PREFILL = "prefill"
DECODE = "decode"


class ServingBackend(ABC):
    """Selects the FFN execution path for each engine step."""

    name: str = "abstract"

    @abstractmethod
    def ffn_impl(self, mode: str) -> str:
        """The ``SparsityConfig.ffn_impl`` to run for ``mode``
        (``prefill`` | ``decode``)."""
        raise NotImplementedError

    def configure(self, cfg: ModelConfig, mode: str) -> ModelConfig:
        """A config whose FFN path is this backend's choice for ``mode``."""
        if mode not in (PREFILL, DECODE):
            raise ValueError(f"mode must be prefill|decode, got {mode!r}")
        return dataclasses.replace(
            cfg, sparsity=dataclasses.replace(cfg.sparsity,
                                              ffn_impl=self.ffn_impl(mode)))

    def describe(self) -> str:
        return (f"{self.name}: prefill={self.ffn_impl(PREFILL)} "
                f"decode={self.ffn_impl(DECODE)}")

    def _twell_kernels(self) -> bool:
        """Does this backend run TwELL-tiled FFN kernels on this platform?
        Each device then tiles its own d_ff slice."""
        from repro.kernels.ops import kernel_mode
        return kernel_mode() == "pallas" and any(
            self.ffn_impl(mode) != "dense" for mode in (PREFILL, DECODE))

    def validate_mesh(self, cfg: ModelConfig, mesh) -> None:
        """Reject model/mesh combinations tensor-parallel serving cannot
        shard. Every backend routes attention through the paged KV pool,
        whose only shardable axis is the kv-head dim, and projects through
        head-sharded wq/wo — so non-divisible head counts would silently
        replicate the very tensors TP exists to split. Fail loudly instead
        (the training path keeps its graceful fallbacks; serving opts into
        strictness because the operator asked for tp>1 on purpose). Where
        the FFN runs as shard-local TwELL kernels, every device's d_ff
        slice must also be whole tiles, so its tiles are the global ones."""
        from repro.distributed.sharding import tp_size
        tp = tp_size(mesh)
        if tp <= 1:
            return
        problems = []
        if cfg.num_kv_heads % tp:
            problems.append(f"num_kv_heads={cfg.num_kv_heads} (paged KV "
                            f"pool head axis) not divisible by it")
        if cfg.num_heads % tp:
            problems.append(f"num_heads={cfg.num_heads} (attention TP) not "
                            f"divisible by it")
        if cfg.d_ff % tp:
            problems.append(f"d_ff={cfg.d_ff} (FFN TP) not divisible by it")
        elif self._twell_kernels() and (cfg.d_ff // tp) % \
                cfg.sparsity.twell_tile:
            problems.append(
                f"local d_ff={cfg.d_ff // tp} not a multiple of "
                f"twell_tile={cfg.sparsity.twell_tile} (shard-local TwELL "
                f"kernels)")
        if cfg.padded_vocab % tp:
            problems.append(f"padded_vocab={cfg.padded_vocab} "
                            f"(vocab-sharded logits) not divisible by it")
        if problems:
            raise ValueError(
                f"backend {self.name!r} cannot serve under tp={tp}, the "
                f"model axis: " + "; ".join(problems))


class DenseBackend(ServingBackend):
    """Paper baseline: dense FFN math everywhere."""

    name = "dense"

    def ffn_impl(self, mode: str) -> str:
        return "dense"


class TwellGatherBackend(ServingBackend):
    """TwELL sparse path (Eq. 3 fused up+down from packed gate activations).

    Decode is the GEMV regime the format targets; prefill defaults to the
    same path so sparse serving is numerically one pipeline end to end, but
    ``prefill_impl="dense"`` gives the Polar-Sparsity-style split (dense
    prefill, sparse decode) when prefill is compute- rather than
    memory-bound.
    """

    name = "gather"

    def __init__(self, prefill_impl: str = "gather"):
        if prefill_impl not in ("gather", "dense"):
            raise ValueError(f"bad prefill_impl {prefill_impl!r}")
        self._prefill_impl = prefill_impl

    def ffn_impl(self, mode: str) -> str:
        return "gather" if mode == DECODE else self._prefill_impl


class TileSkipBackend(ServingBackend):
    """TPU block-skip harvest kernel (dense math on CPU).

    ``threshold > 0`` drops gate tiles whose max |activation| is below it —
    approximate but much sparser, which is exactly the cheap execution
    regime self-speculative decoding drafts with (the exact gather/TwELL
    path then verifies). ``threshold == 0`` skips only all-zero tiles and
    is numerically identical to dense math.
    """

    name = "tile_skip"

    def __init__(self, threshold: float = 0.0):
        if threshold < 0:
            raise ValueError(f"threshold must be >= 0, got {threshold}")
        self.threshold = threshold

    def ffn_impl(self, mode: str) -> str:
        return "tile_skip"

    def configure(self, cfg: ModelConfig, mode: str) -> ModelConfig:
        cfg = super().configure(cfg, mode)
        return dataclasses.replace(
            cfg, sparsity=dataclasses.replace(
                cfg.sparsity, tile_skip_threshold=self.threshold))

    def describe(self) -> str:
        return super().describe() + f" threshold={self.threshold}"


_REGISTRY: Dict[str, Type[ServingBackend]] = {}


def register(cls: Type[ServingBackend]) -> Type[ServingBackend]:
    _REGISTRY[cls.name] = cls
    return cls


for _cls in (DenseBackend, TwellGatherBackend, TileSkipBackend):
    register(_cls)


def get_backend(name_or_backend, **kwargs) -> ServingBackend:
    """Resolve a backend by name (or pass an instance through)."""
    if isinstance(name_or_backend, ServingBackend):
        return name_or_backend
    try:
        return _REGISTRY[name_or_backend](**kwargs)
    except KeyError:
        raise ValueError(f"unknown backend {name_or_backend!r}; "
                         f"have {sorted(_REGISTRY)}") from None


@dataclasses.dataclass(frozen=True)
class DraftPair:
    """A draft/verify execution pair over ONE set of weights.

    Self-speculative decoding needs two execution paths, not two models:
    ``draft`` is the cheap approximate path the k-token draft loop runs
    (typically thresholded tile-skip), ``verify`` the trusted path whose
    output distribution the engine must preserve (whatever backend the
    engine itself serves with — dense or gather/TwELL).
    """

    draft: ServingBackend
    verify: ServingBackend

    def describe(self) -> str:
        return (f"draft[{self.draft.describe()}] -> "
                f"verify[{self.verify.describe()}]")


def make_draft_pair(verify_backend, draft_backend,
                    draft_threshold: float = 0.0) -> DraftPair:
    """Resolve a draft/verify pair; the threshold only applies to tile_skip
    drafts. A nonzero threshold on any other draft backend is an error —
    the user explicitly set a lossy knob that would otherwise be silently
    ignored."""
    kwargs = {}
    if draft_threshold:
        if draft_backend != "tile_skip":
            raise ValueError(
                f"draft_threshold={draft_threshold} only applies to "
                f"tile_skip drafts; draft_backend={draft_backend!r} has no "
                f"lossy knob (set draft_threshold=0)")
        kwargs["threshold"] = draft_threshold
    return DraftPair(draft=get_backend(draft_backend, **kwargs),
                     verify=get_backend(verify_backend))
