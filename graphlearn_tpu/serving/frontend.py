"""Request coalescing + executor loop over warm bucket executables.

The `ServingFrontend` is the glue of the online tier: producers
(`DistServer.serve_infer` handler threads, or in-process callers)
``submit`` single-seed / few-seed requests through the
`AdmissionController`; ONE executor thread drains the bounded queue
in coalesced runs — FIFO requests packed until the largest bucket
fills or ``GLT_SERVING_MAX_WAIT_MS`` has passed since the run's first
arrival — dispatches each run through the engine's warm bucket
program, and de-multiplexes per-request slices back onto the waiting
futures.  Per-seed sampling determinism (`serving.engine`) is what
makes the slices byte-identical to serving each request alone.

Latency anatomy of one request (all spans/events in the flight
recorder): queue wait (bounded by max-wait + the in-flight dispatch),
``serving.infer`` span (the device dispatch + tiered host fill),
demux.  ``serving.request`` events carry the end-to-end
``latency_ms`` the report's percentile table is built from.

Coalescing is a LATENCY/THROUGHPUT dial, not a correctness one:
``GLT_SERVING_MAX_WAIT_MS=0`` degrades to serve-every-request-alone
(lowest added latency, one dispatch per request); large values
amortize dispatch overhead across deeper buckets under load.  Under
an arrival burst the wait never binds — the queue fills a bucket
immediately and the tier runs back-to-back dispatches.
"""
from __future__ import annotations

import os
import threading
import time
from typing import List, Optional

import numpy as np

from ..telemetry import postmortem
from ..telemetry.live import live
from ..telemetry.memaccount import CapacityModel
from ..telemetry.recorder import recorder
from ..telemetry.slo import SloTracker
from ..telemetry.spans import span
from ..telemetry.tracing import tracer
from .admission import AdmissionController, AdmissionRejected, Request
from .engine import ServingEngine, ServingResult

MAX_WAIT_ENV = 'GLT_SERVING_MAX_WAIT_MS'
DEFAULT_MAX_WAIT_MS = 2.0


def max_wait_ms_from_env() -> float:
  raw = os.environ.get(MAX_WAIT_ENV)
  if raw is None:
    return DEFAULT_MAX_WAIT_MS
  try:
    return max(float(raw), 0.0)
  except ValueError:
    return DEFAULT_MAX_WAIT_MS


class ServingFrontend:
  """Admission + coalescing + warm-executable execution.

  Args:
    engine: a `ServingEngine` (warmed by `start`, see below).
    max_wait_ms: coalescing window (else ``GLT_SERVING_MAX_WAIT_MS``).
    max_queue / default_deadline_ms: admission bounds (else the
      ``GLT_SERVING_QUEUE_DEPTH`` / ``GLT_SERVING_DEADLINE_MS``
      defaults).
    auto_start: start the executor thread (and run `engine.warmup`
      when not yet warm) immediately.  Tests pass ``False`` and pump
      deterministically with `pump_once`.
  """

  def __init__(self, engine: ServingEngine,
               max_wait_ms: Optional[float] = None,
               max_queue: Optional[int] = None,
               default_deadline_ms: Optional[float] = None,
               auto_start: bool = True, warmup: bool = True,
               name: str = ''):
    self.engine = engine
    #: fleet identity (set by `router.LocalReplica` when unset):
    #: rides the executor chaos seam so plans can target one replica
    self.name = name
    self.max_wait_s = (max_wait_ms if max_wait_ms is not None
                       else max_wait_ms_from_env()) / 1e3
    self.admission = AdmissionController(
        max_queue=max_queue, default_deadline_ms=default_deadline_ms,
        max_request_seeds=engine.max_request_seeds())
    self._closed = False
    #: crash-simulation hook (`serving.router.LocalReplica.kill` /
    #: chaos ``serving.replica:kill``): a frozen frontend stops COLD —
    #: taken runs are dropped unresolved (their futures freeze exactly
    #: like a killed process's would), nothing sheds typed.  The fleet
    #: router's redrive is what turns this into zero lost requests.
    self._frozen = False
    self._thread: Optional[threading.Thread] = None
    self._lock = threading.Lock()
    #: held by the executor across each coalesced run; `swap.hot_swap`
    #: acquires it to quiesce BETWEEN runs (the drain-free cutover
    #: point — no dispatch is ever interrupted, no queue is flushed)
    self._dispatch_gate = threading.Lock()
    #: serializes whole hot_swap attempts (two concurrent swaps on
    #: one tier must not interleave their drain windows or probes)
    self._swap_lock = threading.Lock()
    #: executor-side counters (heartbeat/stats; executor thread only
    #: writes, readers take the lock for a consistent snapshot —
    #: enforced by glint's guarded-by pass)
    self.in_flight = 0          # guarded-by: self._lock
    self.served_requests = 0    # guarded-by: self._lock
    self.served_seeds = 0       # guarded-by: self._lock
    self.dispatches = 0         # guarded-by: self._lock
    self.failed = 0             # guarded-by: self._lock
    # live ops plane (ISSUE 12): typed handles for the hot path
    # (registration is once, ticking is a dict increment), gauges
    # evaluated at scrape time, per-bucket latency histograms, and
    # the SLO tracker (targets from GLT_SERVING_SLO_P99_MS/_QPS).
    # "Latest frontend wins" for the gauges/health — the contract of
    # a process that restarts its serving tier.
    self._m_requests = live.counter('serving.requests_total')
    self._m_seeds = live.counter('serving.seeds_total')
    self._m_dispatches = live.counter('serving.dispatches_total')
    self._m_failed = live.counter('serving.failed_total')
    # fn-gauges retain self through their callbacks — tracked so
    # shutdown() can unregister them (fn-identity guarded: a newer
    # frontend's replacements survive a stale one's shutdown).  The
    # fill ratio is an fn-gauge over `_last_fill` rather than a
    # stored value for the same reason: a dead tier must not keep
    # exporting its final dispatch's fill as live state.
    self._last_fill: Optional[float] = None
    _depth_fn = self.admission.depth
    _in_flight_fn = self._in_flight_snapshot
    _fill_fn = self._fill_snapshot
    live.gauge('serving.queue_depth', fn=_depth_fn)
    live.gauge('serving.in_flight', fn=_in_flight_fn)
    live.gauge('serving.coalesce_fill_ratio', fn=_fill_fn)
    self._gauge_regs = [('serving.queue_depth', _depth_fn),
                        ('serving.in_flight', _in_flight_fn),
                        ('serving.coalesce_fill_ratio', _fill_fn)]
    self._lat_hists: dict = {}
    #: per-request admission→pickup wait (always on — the metrics
    #: plane is not the data plane; byte-identity concerns results
    #: and the exemplar-free /metrics text)
    self._m_queue_wait = live.histogram('serving.queue_wait')
    self.slo = SloTracker(registry=live)
    #: per-bucket EWMA serve-cost → fleet.headroom_qps (the ROADMAP
    #: item 3 admission signal; fed after every coalesced dispatch)
    self.capacity = CapacityModel(slo=self.slo, registry=live)
    # budget-burning sheds (queue_full/deadline — the tier failing
    # its callers) feed the SLO window as failures; INTENTIONAL sheds
    # (draining cutover, shutdown) are exempt by the admission
    # controller's feed contract — a replica mid-hot-swap must not
    # burn error budget or trip burn-rate alarms (ISSUE 13 satellite)
    self.admission.slo_feed = self._slo_shed_feed
    # bound method pinned once — unregister compares by identity
    self._health_fn = self._health
    live.register_health('serving', self._health_fn)
    if auto_start:
      self.start(warmup=warmup)

  # -- lifecycle ------------------------------------------------------------
  def start(self, warmup: bool = True) -> None:
    if self._thread is not None:
      return
    from ..telemetry import opsserver
    opsserver.maybe_start_from_env()
    if warmup and not all(self.engine.warm.values()):
      self.engine.warmup()
    self._thread = threading.Thread(target=self._loop, daemon=True,
                                    name='glt-serving-executor')
    self._thread.start()

  def shutdown(self, timeout: float = 10.0) -> None:
    """Stop the executor; every queued request resolves with a typed
    shutdown rejection (never silently lost)."""
    self._closed = True
    self.admission.close()
    t = self._thread
    if t is not None:
      t.join(timeout)
    self._thread = None
    self._unregister_observability()

  def _unregister_observability(self) -> None:
    """Drop this frontend's live-registry callbacks (health fn,
    gauges, SLO tracker) — the closure-pinning cleanup PR 12's gauge
    lifecycle established.  Shared by `shutdown` and the fleet
    kill-simulation path (`router.LocalReplica.kill`), which freezes
    the data plane WITHOUT resolving requests but must still release
    the registry (a killed process's exporters vanish too)."""
    live.unregister_health('serving', fn=self._health_fn)
    for gname, gfn in self._gauge_regs:
      live.unregister_gauge(gname, fn=gfn)
    self.capacity.close()
    self.slo.close()

  # -- producer side --------------------------------------------------------
  def submit(self, seeds, deadline_ms: Optional[float] = None,
             trace: Optional[dict] = None):
    """Admit one request; returns its `ServingFuture` (raises
    `AdmissionRejected` at the door when the queue is at bound, and
    `ValueError` for a MALFORMED request — empty, or seed ids outside
    ``[0, num_nodes)``; the engine's gathers CLAMP out-of-range ids,
    so without this check a bogus id would come back as a plausible
    answer for the wrong node instead of an error).  ``trace`` is the
    request-trace context minted by the router (or the RPC handler's
    child context) — it rides the queued request so the executor can
    attribute queue wait / dispatch slice / cold fill per request."""
    seeds = np.asarray(seeds, np.int64).reshape(-1)
    if seeds.size == 0:
      raise ValueError('a serving request needs at least one seed')
    if seeds.min() < 0 or seeds.max() >= self.engine.num_nodes:
      bad = seeds[(seeds < 0) | (seeds >= self.engine.num_nodes)]
      raise ValueError(
          f'seed id(s) {bad[:8].tolist()} outside [0, '
          f'{self.engine.num_nodes}) — refused (a clamped gather '
          'would silently answer for a different node)')
    return self.admission.submit(seeds, deadline_ms,
                                 trace=trace).future

  def infer(self, seeds, deadline_ms: Optional[float] = None,
            timeout: Optional[float] = None) -> ServingResult:
    """Blocking submit+wait convenience (the in-process client)."""
    dl = (deadline_ms if deadline_ms is not None
          else self.admission.default_deadline_ms)
    fut = self.submit(seeds, deadline_ms)
    # the wait outlives the deadline by a grace window: a request
    # PICKED before its deadline still completes (classic SLO
    # semantics — shed applies to queued requests only)
    return fut.result(timeout if timeout is not None
                      else dl / 1e3 + 30.0)

  # -- executor side --------------------------------------------------------
  def _loop(self) -> None:
    while not self._closed and not self._frozen:
      try:
        self.pump_once()
      except Exception:             # noqa: BLE001 — pump_once resolves
        # per-request errors onto futures; anything escaping here is a
        # harness bug, and dying silently would hang every later
        # caller — keep the loop alive
        if self._closed:
          return

  def pump_once(self, block: bool = True) -> int:
    """Drain ONE coalesced run end to end; returns requests served
    (0 = nothing to do / everything shed).  The executor loop calls
    this forever (``block=True``: wait for work); tests call it
    directly — ``block=False`` returns 0 immediately on an empty
    queue instead of waiting."""
    run = self.admission.take(self.engine.max_request_seeds(),
                              self.max_wait_s, block=block)
    if self._frozen:
      # simulated process death: the popped run is LOST unresolved —
      # the dead-replica shape the fleet redrive exists for
      return 0
    if not run:
      return 0
    with self._lock:
      self.in_flight = len(run)
    try:
      # the hot-swap quiesce point: a swap acquires this gate, so a
      # run never straddles a version change (and a swap never
      # interrupts a run)
      with self._dispatch_gate:
        return self._execute(run)
    finally:
      with self._lock:
        self.in_flight = 0

  def _execute(self, run: List[Request]) -> int:
    from ..testing import chaos
    sizes = [len(r.seeds) for r in run]
    total = sum(sizes)
    cap = self.engine.bucket_for(total)
    now = time.monotonic()
    recorder.emit('serving.coalesce', requests=len(run), seeds=total,
                  bucket=cap,
                  waited_ms=round(1e3 * (now - run[0].arrived), 3))
    for req in run:
      # admission enqueue → coalesce pickup, per request: the wait
      # the coalescing executor imposed (histogram always; a span
      # only when the request carries a trace context)
      wait_s = max(now - req.arrived, 0.0)
      self._m_queue_wait.observe(wait_s)
      if req.trace is not None:
        tracer.span('serving.queue_wait', req.trace, t0=req.arrived,
                    dur=wait_s)
    try:
      # chaos seam (executor flavor): a 'delay' here simulates a slow/
      # stuck dispatch — queued requests behind it expire and shed; a
      # 'drop' kills this dispatch with a typed error on every rider
      chaos.serving_request_check('dispatch', replica=self.name)
      with span('serving.infer', bucket=cap, requests=len(run),
                seeds=total):
        batch = self.engine.infer(
            np.concatenate([r.seeds for r in run]), cap=cap)
    except Exception as e:          # noqa: BLE001 — typed resolve,
      # never a silent drop: every rider of the failed dispatch gets
      # the error (an RPC handler re-raises it to its client)
      with self._lock:
        self.failed += len(run)
      self._m_failed.inc(len(run))
      for req in run:
        lat = req.waited_ms()
        if req.trace is not None:
          tracer.span('serving.dispatch_slice', req.trace, t0=now,
                      dur=time.monotonic() - now, bucket=cap,
                      requests=len(run),
                      error=f'{type(e).__name__}: {e}'[:160])
          tracer.resolve(req.trace, outcome='error', latency_ms=lat)
        req.future.set_error(e)
        self.slo.observe(lat, ok=False)
        recorder.emit('serving.request', seeds=len(req.seeds),
                      bucket=cap, coalesced=len(run), ok=False,
                      latency_ms=round(lat, 3),
                      error=f'{type(e).__name__}: {e}'[:160])
      if not isinstance(e, AdmissionRejected):
        # the black box: an executor fault is one of the fatal-ish
        # conditions an operator wants the last-N window for (typed
        # sheds are load signals, not faults — no bundle for those)
        postmortem.dump('serving.executor_fault', error=e,
                        extra={'bucket': cap, 'requests': len(run)})
      return 0
    off = 0
    self._last_fill = round(total / cap, 4) if cap else 0.0
    cold = getattr(self.engine, 'last_cold_fill', None)
    coll = getattr(self.engine, 'last_collect', None)
    hist = self._lat_hists.get(cap)
    if hist is None:
      hist = self._lat_hists[cap] = live.histogram(
          'serving.request_latency', labels={'bucket': cap})
    for req, k in zip(run, sizes):
      lat = req.waited_ms()
      if req.trace is not None:
        # record + resolve BEFORE the future fires: when a caller
        # (the RPC handler, the router) wakes, this request's spans
        # are already retained — /trace right after a serve returns
        # the complete tree, no eventual-consistency window
        end = time.monotonic()
        sid = tracer.span('serving.dispatch_slice', req.trace,
                          t0=now, dur=end - now, bucket=cap,
                          requests=len(run))
        if coll is not None and coll[0] >= now:
          # the engine's neighbor-sampling collect inside THIS
          # dispatch — with cold_fill below it splits the dispatch
          # into sampling cost vs feature-fill cost per trace
          tracer.span('serving.sample_collect', req.trace,
                      parent_id=sid, t0=coll[0], dur=coll[1])
        if cold is not None and cold[0] >= now:
          # the engine's tiered host fill inside THIS dispatch, one
          # view per traced rider (each tree stays self-contained)
          tracer.span('serving.cold_fill', req.trace, parent_id=sid,
                      t0=cold[0], dur=cold[1])
        tracer.resolve(req.trace, outcome='ok', latency_ms=lat)
      req.future.set_result(batch.slice(off, off + k))
      off += k
      # the trace_id lands as this bucket's OpenMetrics exemplar —
      # report.py jumps from the p99 bucket to the captured trace
      hist.observe(lat / 1e3,
                   exemplar=(req.trace['t'] if req.trace is not None
                             else None))
      self.slo.observe(lat, ok=True)
      recorder.emit('serving.request', seeds=k, bucket=cap,
                    coalesced=len(run), ok=True,
                    latency_ms=round(lat, 3))
    self.capacity.observe(cap, len(run), time.monotonic() - now)
    with self._lock:
      self.served_requests += len(run)
      self.served_seeds += total
      self.dispatches += 1
    self._m_requests.inc(len(run))
    self._m_seeds.inc(total)
    self._m_dispatches.inc()
    return len(run)

  # -- model lifecycle ------------------------------------------------------
  def swap_model(self, params, version: Optional[int] = None,
                 **kwargs) -> dict:
    """Drain-free hot model swap (see `serving.swap.hot_swap`):
    quiesce between coalesced runs, parity-check the candidate
    against the offline reference, commit-or-roll-back — zero dropped
    requests either way."""
    from .swap import hot_swap
    return hot_swap(self, params, version=version, **kwargs)

  def _slo_shed_feed(self, reason: str, waited_ms: float) -> None:
    self.slo.observe(waited_ms, ok=False)

  def quiesced(self) -> bool:
    """No queued work and no in-flight coalesced run — the drain
    point a planned retirement (elastic scale-in, ISSUE 19) waits for
    after flipping the admission door to draining: past it, shutdown
    resolves nothing but the already-empty queue."""
    return self.admission.depth() == 0 \
        and self._in_flight_snapshot() == 0

  # -- observability --------------------------------------------------------
  def _in_flight_snapshot(self) -> int:
    with self._lock:
      return self.in_flight

  def _fill_snapshot(self) -> Optional[float]:
    return self._last_fill

  def stats(self) -> dict:
    """The heartbeat serving block: queue depth, in-flight batch
    size, served/shed counters, per-bucket compile status, SLO
    window state."""
    with self._lock:
      out = {'in_flight': self.in_flight,
             'served_requests': self.served_requests,
             'served_seeds': self.served_seeds,
             'dispatches': self.dispatches,
             'failed': self.failed}
    out.update(self.admission.stats())
    out['closed'] = self._closed
    out['compile_status'] = self.engine.compile_status()
    out['model_version'] = self.engine.model_version
    out['max_wait_ms'] = round(self.max_wait_s * 1e3, 3)
    hr = self.capacity._headroom()
    if hr is not None:
      out['headroom_qps'] = hr     # the heartbeat copy of the gauge
    out['slo'] = self.slo.snapshot()
    return out

  def _health(self) -> dict:
    """The `/healthz` serving component: the heartbeat block plus a
    ``healthy`` verdict — unhealthy once closed, or if the executor
    thread was started and has since died (every queued caller would
    hang on its future but for the admission deadline)."""
    out = self.stats()
    executor_dead = (self._thread is not None
                     and not self._thread.is_alive())
    out['executor_alive'] = (self._thread is not None
                             and self._thread.is_alive())
    # a DRAINING tier is healthy: the hot-swap cutover sheds typed on
    # purpose and must not flip /healthz to 503 as if it were failing
    # (out['draining'] rides in from admission.stats() for routers)
    out['healthy'] = not self._closed and not executor_dead
    return out
