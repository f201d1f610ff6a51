"""paddle_tpu.serving — TPU-native model serving.

The deployment story past the one-shot C-API machine: a saved inference
model becomes a production server loop with

- :class:`InferenceEngine` — bucketed, pre-warmed one-shot inference
  (zero compiles on the serving path after warmup), data-parallel across
  local devices via a ``parallel.make_mesh`` mesh;
- :class:`GenerationEngine` — Orca-style continuous batching for
  autoregressive decode over a slot-table KV cache (requests join and
  leave mid-flight; one compiled decode step in steady state);
- :class:`DynamicBatcher` — Clipper-style deadline batching with bounded
  admission and typed backpressure errors;
- :class:`Server` — the dispatch thread plus an in-process ``submit()``
  API and a stdlib JSON HTTP endpoint;
- :class:`MetricsRegistry` — QPS / queue depth / batch occupancy /
  latency quantiles / compile-cache hits as a plain dict snapshot,
  publishable into :mod:`paddle_tpu.profiler`;
- :class:`Fleet` — the layer above one server: N replicas (in-process
  or remote HTTP) behind a :class:`Router` with per-replica circuit
  breakers, deadline-propagating retries to a different replica,
  tail-latency hedging, typed load shedding, and zero-downtime rolling
  weight updates (``Fleet.update_weights``);
- :class:`ModelRegistry` / :class:`Tenant` / :class:`MultiTenantServer`
  — several resident models per replica behind ONE ``/v1`` surface,
  routed on the request's ``model``/``tenant`` field, with per-tenant
  sampling defaults, admission quotas, labeled SLO gauges, and
  tenant-scoped weight rolls (the other tenants serve through them);
- :class:`DisaggEngine` + :class:`PrefillPool`/:class:`DecodePool` —
  prefill/decode disaggregation: split engine pools with KV handoff by
  refcounted page migration (same-process) or serialized page ranges
  over ``POST /v1/adopt`` (:class:`RemoteDecodeLeg`) — never a prefill
  recompute;
- :class:`LineageStore` / :class:`LineageRecord` — work-preserving
  recovery: every admitted generation's prompt + pinned sampling policy
  + emitted-tokens-so-far, kept router-side so a replica that dies
  mid-stream triggers a RESUME on a healthy replica (``resume_tokens``
  chunk-prefill, token-exact by (request, seed) determinism) instead of
  a failure.

See demos/serving_lm.py and demos/serving_fleet.py for the end-to-end
walkthroughs.
"""
from .batcher import DynamicBatcher, Future, Request
from .disagg import (DecodePool, DisaggEngine, PrefillPool,
                     RemoteDecodeLeg)
from .engine import InferenceEngine, load_param_arrays, swap_scope_params
from .errors import (BadRequestError, CacheExhaustedError,
                     ConnectionDroppedError, EngineClosedError,
                     FleetOverloadedError, ModelNotFoundError,
                     QueueFullError, ReplicaUnavailableError,
                     RequestTimeoutError, ServingError)
from .fleet import Fleet, HttpReplica, LocalReplica, Replica
from .generation import (GenerationEngine, LMSpec, RequestTimeline,
                         spec_from_program_dict)
from .metrics import MetricsRegistry
from .paging import PagePool, PrefixIndex
from .recovery import LineageRecord, LineageStore
from .router import (CircuitBreaker, LeastLoadedPolicy, RoundRobinPolicy,
                     Router, SessionAffinityPolicy)
from .server import Server
from .tenancy import ModelRegistry, MultiTenantServer, Tenant

__all__ = [
    "DynamicBatcher", "Future", "Request",
    "InferenceEngine", "GenerationEngine",
    "LMSpec", "RequestTimeline", "spec_from_program_dict",
    "MetricsRegistry", "Server",
    "PagePool", "PrefixIndex",
    "Fleet", "Replica", "LocalReplica", "HttpReplica",
    "Router", "CircuitBreaker", "RoundRobinPolicy", "LeastLoadedPolicy",
    "SessionAffinityPolicy", "load_param_arrays", "swap_scope_params",
    "ModelRegistry", "Tenant", "MultiTenantServer",
    "DisaggEngine", "PrefillPool", "DecodePool", "RemoteDecodeLeg",
    "LineageStore", "LineageRecord",
    "ServingError", "QueueFullError", "RequestTimeoutError",
    "BadRequestError", "EngineClosedError", "ReplicaUnavailableError",
    "FleetOverloadedError", "CacheExhaustedError", "ModelNotFoundError",
    "ConnectionDroppedError",
]
