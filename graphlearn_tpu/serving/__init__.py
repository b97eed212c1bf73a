"""Online inference serving plane (ISSUE 9).

Turns the `DistServer`/`DistClient` runtime into an SLO-gated
inference tier: shape-bucketed warm fused sample+gather(+forward)
executables (`engine`), a bounded-queue admission controller with
typed load-shedding (`admission`), and a request coalescer + executor
loop (`frontend`).  Wire-up: build a `ServingEngine` over the served
`Dataset`, wrap it in a `ServingFrontend`, and
`DistServer.attach_serving(frontend)` — clients call
`DistClient.serve`.

Fleet resilience (ISSUE 13): `FleetRouter` spreads traffic over N
replicas with heartbeat-classified routing and exactly-once request
redrive on replica loss (`router`); `swap.hot_swap` swaps model
versions drain-free behind a parity check; `aot_cache` persists
bucket executables under ``GLT_AOT_CACHE_DIR`` so replacements warm
from disk instead of recompiling.

Closed-loop elasticity (ISSUE 19): `ElasticController` sizes the
fleet from the SLO-burn/queue/headroom signal plane (scale-out
admits only warm, verified replicas; scale-in drains and retires the
coldest), and `parallel.handoff` moves partition ownership planned —
fence then one-bump cutover, zero degraded window.

Knobs (one row each in KNOBS.md): ``GLT_SERVING_BUCKETS``,
``GLT_SERVING_MAX_WAIT_MS``, ``GLT_SERVING_QUEUE_DEPTH``,
``GLT_SERVING_DEADLINE_MS``, ``GLT_AOT_CACHE_DIR``,
``GLT_FLEET_HEARTBEAT_MS``, ``GLT_FLEET_OVERLOAD_RATIO``,
``GLT_SERVING_DRAIN_RETRY_MS``, ``GLT_SCALE_*``,
``GLT_FLEET_FLAP_WINDOW_S``.
"""
from .admission import (AdmissionController, AdmissionRejected,
                        ServingFuture)
from .aot_cache import AotExecutableCache
from .autoscaler import ElasticController, ScaleAbortedError
from .engine import ServingEngine, ServingResult, resolve_buckets
from .frontend import ServingFrontend
from .router import FleetRouter, LocalReplica, RemoteReplica, RouterFuture
from .swap import (SwapAbortedError, SwapParityError,
                   SwapValidationError, hot_swap)

__all__ = [
    'AdmissionController', 'AdmissionRejected', 'ServingFuture',
    'AotExecutableCache',
    'ElasticController', 'ScaleAbortedError',
    'ServingEngine', 'ServingResult', 'resolve_buckets',
    'ServingFrontend',
    'FleetRouter', 'LocalReplica', 'RemoteReplica', 'RouterFuture',
    'SwapAbortedError', 'SwapParityError', 'SwapValidationError',
    'hot_swap',
]
