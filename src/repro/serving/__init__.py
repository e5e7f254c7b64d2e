"""Serving layer: one engine protocol over a paged, prefix-shared KV cache.

``repro.serving.api`` is the single public surface — :class:`EngineCore`
(``submit``/``step``/``cancel``/``abort_all``), :class:`SamplingParams`,
:class:`RequestHandle` streaming, typed :class:`FinishReason`, and pluggable
:class:`AdmissionPolicy` queues. ``GenerationEngine`` is the lockstep
micro-batching baseline; ``ContinuousBatchingEngine`` is the production path
— continuous admission, chunked prefill interleaved with decode, and
copy-on-write prefix sharing (see ``docs/serving.md`` for the full design).
``repro.serving.fleet`` supervises N engine workers behind the bus —
probes, crash-replay recovery, autoscaling (paper §3.5 fused with the
serving arc). ``repro.serving.kv_tiers`` keeps prefix KV pages alive past
release — parked on device, spilled to host RAM, persisted to an
ArtifactStore — with async prefetch back on prefix hits.
``repro.serving.speculative`` breaks the one-token-per-dispatch decode
chain: an n-gram or draft-model proposer drafts k tokens and one fused
verify dispatch scores them all, streams staying byte-identical to
spec-off. ``repro.serving.ssm_engine`` serves the recurrent-state
families (Mamba2/Zamba2): the same engine protocol over a per-slot
recurrent-state bank instead of a page pool.
"""

from repro.serving.api import (
    AdmissionPolicy,
    DeadlineAdmission,
    EngineCore,
    FIFOAdmission,
    FinishReason,
    PriorityAdmission,
    Request,
    RequestHandle,
    Result,
    SamplingParams,
    StreamEvent,
    UnsupportedConfigError,
    request_from_message,
)
from repro.serving.engine import ContinuousBatchingEngine, GenerationEngine
from repro.serving.fleet import (
    EngineWorker,
    FleetConfig,
    FleetSupervisor,
    fleet_seed,
)
from repro.serving.kv_cache import PagedKVCache, PagePool
from repro.serving.kv_tiers import KVTierManager
from repro.serving.metrics import (
    FleetMetrics,
    format_latency,
    format_waits,
    latency_percentiles,
)
from repro.serving.speculative import (
    DraftModelProposer,
    NgramProposer,
    SpeculativeProposer,
    build_proposer,
)
from repro.serving.ssm_engine import SlotStateBank, SSMEngine

__all__ = [
    "AdmissionPolicy",
    "ContinuousBatchingEngine",
    "DeadlineAdmission",
    "DraftModelProposer",
    "EngineCore",
    "EngineWorker",
    "FIFOAdmission",
    "FinishReason",
    "FleetConfig",
    "FleetMetrics",
    "FleetSupervisor",
    "GenerationEngine",
    "KVTierManager",
    "NgramProposer",
    "PagedKVCache",
    "PagePool",
    "PriorityAdmission",
    "Request",
    "RequestHandle",
    "Result",
    "SSMEngine",
    "SamplingParams",
    "SlotStateBank",
    "SpeculativeProposer",
    "StreamEvent",
    "UnsupportedConfigError",
    "build_proposer",
    "fleet_seed",
    "format_latency",
    "format_waits",
    "latency_percentiles",
    "request_from_message",
]
