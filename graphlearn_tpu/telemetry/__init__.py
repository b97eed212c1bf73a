"""Structured telemetry plane: flight recorder, mesh aggregation, spans.

What is on which clock:

  * device scopes (`utils.profiling.layer_scope`: ``glt.sample`` /
    ``gather`` / ``model`` / ``optimizer`` / ``exchange`` on every
    device op of the jitted programs), `span()` and `step_annotation`
    — the PROFILER's clock: they appear in a `jax.profiler` trace
    beside the device ops;
  * flight-recorder events (``span.begin`` / ``span.end`` with their
    fields and ``dur``) — the host's monotonic clock, and only while
    the recorder is on;
  * the request `Tracer` — the host's clock; it records finished
    spans and annotates nothing (serving only).

The by-layer picture of a running program: ``with capture(dir):``
around a few dispatches, open ``dir`` in xprof, filter ops by ``glt.``.

The reference has NO tracing/profiling subsystem (SURVEY §5: wall-clock
prints in benchmarks only); `utils/profiling.py` grew the first counters
and xprof hooks, and this package grows them into a real layer with
two pieces:

  * :mod:`~graphlearn_tpu.telemetry.recorder` — a bounded, thread-safe
    JSON-lines "flight recorder" (`EventRecorder` / the global
    :data:`recorder`).  Samplers, loaders, channels and the fused
    epochs emit structured events into it: per-hop frontier sizes and
    padding-fill ratios, slack-cap drops and `AdaptiveSlack` ladder
    transitions, compile-cache hits/misses with `_counted_jit` compile
    seconds, channel ring occupancy/stalls, and cold-tier hit/miss from
    tiered feature stores.  Recording is OFF by default (`emit` is a
    single attribute check); enable with
    ``recorder.enable('/path/flight.jsonl')`` or the
    ``GLT_TELEMETRY_JSONL`` env var.
  * :mod:`~graphlearn_tpu.telemetry.aggregate` —
    :func:`gather_metrics` allgathers per-host `Metrics` snapshots over
    the existing collective plane so the distributed engines report
    CLUSTER-wide padding-waste / drop-rate / throughput instead of
    host-0-only numbers (`DistNeighborSampler.cluster_exchange_stats`).

On top of the recorder sits the CAUSAL layer:

  * :mod:`~graphlearn_tpu.telemetry.spans` — ``span()`` context
    manager — the ONE host span primitive: a
    `jax.profiler.TraceAnnotation` of its name always, and, with the
    recorder on, paired ``span.begin``/``span.end`` events with
    ``trace_id``/``span_id``/``parent_id`` and monotonic-clock
    durations; the pipeline (channels, mesh samplers, loaders, the
    server/client runtime, fused epochs) opens sample → exchange →
    feature-lookup → stitch → dispatch child spans, and the context
    crosses process boundaries inside each `SampleMessage`.
  * :mod:`~graphlearn_tpu.telemetry.histogram` — fixed-bucket log2
    latency histograms per span kind, encoded as flat metric keys so
    :func:`gather_metrics` merges them across hosts for free.
  * :mod:`~graphlearn_tpu.telemetry.export` /
    :mod:`~graphlearn_tpu.telemetry.report` — recorder dump → Chrome
    trace-event JSON (Perfetto-loadable), and the
    ``python -m graphlearn_tpu.telemetry.report`` per-stage latency
    table / trace-diff CLI.
  * :mod:`~graphlearn_tpu.telemetry.schema` — the registry of event
    kinds and span names the static schema test enforces.

xprof integration: :func:`step_annotation` wraps
`jax.profiler.StepTraceAnnotation` so fused-epoch dispatches show up as
steps on the TensorBoard timeline.

The LIVE ops plane (ISSUE 12) sits beside the offline stack:

  * :mod:`~graphlearn_tpu.telemetry.live` — the declared live-metric
    registry (`LiveRegistry` / the global :data:`live`): counters and
    log2 histograms writing through the shared `Metrics` store (one
    vocabulary with `gather_metrics`), plus
    scrape-time gauges and health providers.
  * :mod:`~graphlearn_tpu.telemetry.opsserver` — the per-process HTTP
    ops endpoint (``/metrics`` Prometheus text, ``/varz`` JSON,
    ``/healthz``), bound via ``GLT_OPS_PORT`` (0 = disabled, default).
  * :mod:`~graphlearn_tpu.telemetry.slo` — serving SLO tracking:
    sliding-window percentiles and multi-window error-budget burn
    rate vs ``GLT_SERVING_SLO_P99_MS`` / ``GLT_SERVING_SLO_QPS``.
  * :mod:`~graphlearn_tpu.telemetry.postmortem` — the black box: on
    `MeshStallError` / irrecoverable peers / executor faults / fatal
    signals, one timestamped bundle (recorder ring + metrics snapshot
    + health + time-series rings) to ``GLT_POSTMORTEM_DIR``, rendered
    by ``report.py --postmortem``.

The fleet signal plane (ISSUE 16) completes the live stack:

  * :mod:`~graphlearn_tpu.telemetry.timeseries` — `TimeSeriesStore`:
    fixed-cadence samples of every live gauge/counter into bounded
    rings (counters become ``:rate`` series), served at
    ``/timeseries`` and attached to post-mortem bundles.
  * :mod:`~graphlearn_tpu.telemetry.federation` — `FleetScraper`:
    polls replica ops endpoints / in-process registries, re-labels
    each sample with ``replica=`` and merges ``glt_fleet_*``
    aggregates, served at ``/fleet``
    (``FleetRouter.make_scraper()`` wires a serving fleet up).

Request-scoped fleet tracing (ISSUE 17) rides the live stack:

  * :mod:`~graphlearn_tpu.telemetry.tracing` — the request
    `Tracer` (global :data:`tracer`): the router mints a trace
    context that rides the serve RPC, every hop records completed
    spans, and tail-based retention (slow / failed / 1-in-N) keeps
    the interesting traces in a bounded ring served at ``/traces``
    and ``/trace?trace_id=`` (``?format=chrome`` =
    Perfetto-loadable; `FleetScraper.fetch_trace` reassembles the
    cross-process tree first).  Live histograms attach the last
    trace id per bucket as an OpenMetrics EXEMPLAR on ``/metrics``.
  * :mod:`~graphlearn_tpu.telemetry.memaccount` — per-tier byte
    accounting (``memory.tier_bytes{tier=}`` + peaks over
    :data:`~graphlearn_tpu.telemetry.memaccount.TIERS`) and the
    `CapacityModel` EWMA cost model behind ``fleet.headroom_qps``.

The low-level counter/timer registry (`Metrics`, the global
:data:`metrics`), `capture` and `step_annotation` still live in
:mod:`graphlearn_tpu.utils.profiling` and are re-exported here.
"""
from __future__ import annotations

from ..utils.profiling import (Metrics, capture, metrics, start_trace,
                               step_annotation, stop_trace)
from .aggregate import exchange_summary, gather_metrics, per_hop_padding
from .federation import FleetScraper
from .histogram import Histogram, from_snapshot
from .live import (LiveRegistry, live, parse_prometheus_text,
                   split_exemplar)
from .memaccount import TIERS, CapacityModel, register_tier
from .opsserver import OpsServer, maybe_start_from_env
from .recorder import EventRecorder, recorder
from .slo import SloTracker
from .spans import SpanContext, span
from .timeseries import TimeSeriesStore
from .tracing import Tracer, child_ctx, spans_to_events, tracer

__all__ = [
    'CapacityModel', 'EventRecorder', 'FleetScraper', 'Histogram',
    'LiveRegistry', 'Metrics', 'OpsServer', 'SloTracker',
    'SpanContext', 'TIERS', 'TimeSeriesStore', 'Tracer', 'capture',
    'child_ctx', 'exchange_summary', 'from_snapshot', 'gather_metrics', 'live',
    'maybe_start_from_env', 'metrics', 'parse_prometheus_text',
    'per_hop_padding', 'recorder', 'register_tier', 'span',
    'spans_to_events', 'split_exemplar', 'start_trace',
    'step_annotation', 'stop_trace', 'tracer',
]
