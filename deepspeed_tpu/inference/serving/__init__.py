"""Continuous-batching serving subsystem (docs/serving.md).

Three layers, composed by ``InferenceEngine.serving_engine()``:

  * :mod:`block_allocator` — paged KV-cache block pool bookkeeping
    (PagedAttention-style block tables, refcounted fork, leak checks);
  * :mod:`scheduler` — Orca-style iteration-level scheduling: FCFS
    admission, LIFO recompute preemption, completion draining;
  * :mod:`engine` — the compiled prefill / single-trace decode programs
    over ``ops/transformer/paged_decode_attention.py``, instrumented
    with the ``dstpu_serving_*`` observability metrics — now with
    in-program per-request sampling, token streaming, and an optional
    speculative-decoding draft lane;
  * :mod:`frontend` — the SLO-grade multi-tenant front-end
    (:class:`ServingFrontend`): weighted-fair admission / prefill /
    shed policies plus per-tenant metrics;
  * :mod:`fleet` — the resilient replica fleet (:class:`FleetRouter` +
    :class:`ReplicaHandle`): health-checked replicas, prefix-affinity
    placement, token-exact failover with exactly-once delivery, live
    drain/join.
"""
from ...observability.slo import SloAlert, SloMonitor  # noqa: F401
from ...runtime.resilience.errors import ServingError  # noqa: F401
from .block_allocator import (BlockPoolError, NULL_BLOCK,  # noqa: F401
                              PagedBlockAllocator, blocks_for_budget,
                              kv_block_bytes, latent_block_bytes)
from .engine import ServingEngine  # noqa: F401
from .fleet import (FleetAutoscaler, FleetRequest,  # noqa: F401
                    FleetRouter, ReplicaHandle, ReplicaState,
                    placement_score)
from .frontend import (ServingFrontend, StreamCollector,  # noqa: F401
                       StreamDeduper, TokenEvent, TenantRegistry,
                       TenantSpec)
from .host_cache import (BlockCodec, HostTierCache,  # noqa: F401
                         host_block_bytes, tiered_blocks_for_budget)
from .scheduler import (ContinuousBatchingScheduler, Request,  # noqa: F401
                        RequestState, RequestStatus)

__all__ = ["BlockCodec", "BlockPoolError", "NULL_BLOCK",
           "PagedBlockAllocator",
           "ContinuousBatchingScheduler", "FleetAutoscaler",
           "FleetRequest", "FleetRouter",
           "HostTierCache", "ReplicaHandle", "ReplicaState", "Request",
           "RequestState", "RequestStatus", "ServingEngine",
           "ServingError", "ServingFrontend", "SloAlert", "SloMonitor",
           "StreamCollector", "StreamDeduper", "TokenEvent",
           "TenantRegistry", "TenantSpec",
           "host_block_bytes", "kv_block_bytes", "latent_block_bytes",
           "blocks_for_budget",
           "placement_score", "tiered_blocks_for_budget"]
