"""EngineSpec: one declarative bundle of ServingEngine construction kwargs.

Every engine-building entry point — ``launch/serve.py``, the HTTP server
path, both bench drivers, and the disaggregation coordinator (which builds
TWO engines that must agree on everything except their role) — used to
assemble the same long kwarg list by hand, so a flag added in one place
could silently drift from the others. ``EngineSpec`` is that list as a
frozen dataclass: build an engine with ``spec.build(params, cfg)``, derive
a variant with ``spec.replace(role="prefill", telemetry=tm)``.

The field set mirrors ``ServingEngine.__init__`` keyword-for-keyword (a
test asserts they cannot drift); ``build`` forwards the fields verbatim, so
an ``EngineSpec`` never reinterprets a knob.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Union

from repro.serving.engine import ServingEngine
from repro.serving.spec import SpecConfig


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """ServingEngine construction kwargs as data (defaults match the engine
    ctor). ``scheduler`` should be a policy NAME when the spec builds more
    than one engine (the disagg coordinator does) — a shared ``Scheduler``
    instance would corrupt both engines' queues."""

    backend: str = "dense"
    attn_backend: Optional[str] = None     # None = the platform default
    block_size: int = 16
    num_blocks: Optional[int] = None
    max_batch: int = 8
    max_seq_len: int = 256
    min_prefill_bucket: int = 16
    seed: int = 0
    record_logits: bool = False
    spec: Optional[SpecConfig] = None
    prefix_cache: bool = True
    prefill_chunk: int = 64
    scheduler: Union[str, Any] = "fcfs"
    max_stats: Optional[int] = 4096
    mesh: Any = None
    telemetry: Any = False           # bool | Telemetry instance
    pipeline: bool = False
    warmup: bool = False
    role: str = "unified"

    def replace(self, **changes) -> "EngineSpec":
        return dataclasses.replace(self, **changes)

    def kwargs(self) -> dict:
        """The ctor kwargs, field-for-field (no asdict: nested dataclasses
        like SpecConfig must pass through as objects, not dicts)."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}

    def build(self, params, cfg) -> ServingEngine:
        return ServingEngine(params, cfg, **self.kwargs())
