"""Seeded, declarative fault injection for the distributed runtime.

The resilience layer (`distributed/resilience.py`) claims a flaky
peer degrades into a retry, not a hung TPU step; this harness makes
that claim testable.  A *fault plan* is a list of :class:`Fault`
records naming a **site** (an injection seam the runtime calls into),
an **action**, and *when* to fire (the ``nth`` matching arrival at
that seam, counted per fault — deterministic under a fixed plan, no
wall clocks involved).  Sites and actions:

  ``rpc.request``
      Seam inside `RpcClient.request`, once per attempt.  Actions:
      ``drop`` (sever the connection after the request is sent — the
      server may have executed it, exercising the replay cache),
      ``delay`` (sleep ``secs`` before sending), ``corrupt`` (scramble
      the reply payload so the client misparses — exercising the
      reset-on-partial-read path).  ``op`` filters by handler name.
  ``producer.worker``
      Seam at the top of a sampling worker's per-batch loop.  Action
      ``kill`` ( ``os._exit(WORKER_KILL_EXIT)`` — a hard crash, no
      cleanup, like the OOM killer).  ``worker`` / ``epoch`` filter by
      worker rank and epoch.
  ``checkpoint.io``
      Seam inside `utils.checkpoint.Checkpointer.save`.  Actions:
      ``fail`` (the write dies before any byte lands), ``truncate``
      (a PARTIAL tmp write then death before the atomic publish — the
      kill-mid-write scenario; the previous snapshot must stay the
      durable latest).
  ``fused.dispatch``
      Seam around each fused-epoch chunk dispatch (`loader.fused`,
      `parallel.fused`).  Actions: ``delay`` (sleep ``secs`` INSIDE
      the watchdog-timed region, so a configured
      ``GLT_DISPATCH_DEADLINE`` converts it into `MeshStallError` —
      the hung-collective simulation), ``kill`` (raise
      :class:`ChaosKilledError` — the in-process stand-in for a
      preemption; the producer-worker site keeps the real
      ``os._exit`` arm).  ``epoch`` filters by epoch.
  ``feature.cold_service``
      Seam at the top of the host cold-tier gather (single-chip
      `data.feature.Feature` mixed path and the mesh cold overlay).
      Action ``fail`` raises :class:`InjectedFault` — a host feature
      tier that died mid-epoch; the snapshot/resume layer is what
      turns it into a finished epoch.
  ``serving.request``
      Two seams in the online serving plane, distinguished by ``op``:
      ``op='serve_infer'`` fires inside the `DistServer.serve_infer`
      RPC handler (before admission), ``op='dispatch'`` inside the
      serving executor just before a coalesced dispatch.  Actions:
      ``delay`` (sleep ``secs`` — a slow executor; queued requests
      behind it expire and SHED typed, the SLO-gating under test),
      ``drop`` (raise :class:`InjectedFault` — the request/dispatch
      dies server-side; the client sees a typed error, and a
      transport-level retry of the same RPC is answered by the replay
      cache, never re-executed).
  ``ops.scrape``
      Seam at the top of the ops-endpoint HTTP handler
      (`telemetry.opsserver`), ``op`` = route path (``/metrics`` /
      ``/varz`` / ``/healthz``).  Actions: ``delay`` (a stalled
      scraper — must never block the serving executor or a fused
      dispatch), ``drop`` (raise :class:`InjectedFault`; the handler
      answers HTTP 503).
  ``serving.replica``
      Seam inside a fleet replica handle (`serving.router`), fired on
      ``op='submit'`` and ``op='heartbeat'`` arrivals; ``replica``
      filters by replica name.  Actions: ``kill`` (the replica dies
      for good — its executor stops cold, queued requests freeze, and
      the `FleetRouter` must evict it and REDRIVE its in-flight
      requests to a survivor), ``delay`` (a slow replica — heartbeats
      and submits stall ``secs``; the router keeps it at reduced
      weight instead of evicting, the overloaded-vs-dead
      discriminator under test), ``flap`` (unreachable for ``secs``
      then back — a network partition; shorter than the router's
      eviction threshold it costs nothing, longer it costs one
      eviction + redrive and a later re-admission).
  ``aot.cache``
      Seam inside the persistent AOT executable cache
      (`serving.aot_cache`), ``op`` = ``'save'`` / ``'load'``.
      Actions: ``fail`` (the write/read dies — absorbed: a cache
      fault must cost a recompile, never an unserved bucket),
      ``corrupt`` (the payload lands scrambled on disk — a later
      load must detect the bad checksum and fall back to recompile,
      never deserialize garbage into a wrong executable).
  ``ingest.wal``
      Seam inside `streaming.wal.WriteAheadLog.append`.  Actions:
      ``fail`` (the append dies before any byte lands — the caller
      sees a typed error and the log is unchanged), ``truncate``
      (a PARTIAL record is written and the process "dies" mid-append
      — the kill-mid-write scenario; the next open must detect the
      torn tail by checksum, truncate back to the last whole record,
      and replay must land exactly the whole-record prefix).
  ``ingest.apply``
      Seam inside `streaming.ingest.IngestPipeline` BETWEEN the
      durable WAL append and the in-memory delta-CSR commit.
      Actions: ``kill`` (raise :class:`ChaosKilledError` — the
      process dies with the event logged but not applied; a restart
      must replay it from the WAL exactly once), ``delay`` (a slow
      apply — the ``ingest.lag_events`` gauge grows and, past
      ``GLT_INGEST_MAX_LAG``, flips the ingestion healthz component).
  ``ingest.compact``
      Seam inside `streaming.ingest.IngestPipeline.compact`, fired
      BEFORE the compacted-base snapshot publishes.  Action ``kill``
      (raise :class:`ChaosKilledError` mid-compaction — the previous
      snapshot + the full WAL stay the durable truth; a restart
      replays to the identical graph).
  ``scale.spawn``
      Seam inside the ElasticController's scale-out path
      (`serving.autoscaler`), fired once per spawn attempt BEFORE the
      replica factory runs.  Actions: ``delay`` (a slow provision —
      sleeps in place, the evaluation loop stalls but nothing is
      admitted half-built), ``fail`` (raise :class:`InjectedFault` —
      provisioning died), ``kill`` (raise :class:`ChaosKilledError` —
      the spawn died mid-flight).  Either raise must roll the decision
      back typed (no partial replica in rotation) and re-arm: the
      cooldown is NOT spent on a failed decision.
  ``handoff.transfer``
      Seam inside the planned partition handoff (`parallel.handoff`),
      fired once per phase with ``op`` = the seam name (``snapshot`` /
      ``transfer`` / ``fence`` / ``cutover`` / ``drain``) and
      ``partition`` = the moving range.  Actions: ``delay`` (sleeps in
      place — the source keeps serving throughout, that is the zero-
      degraded-window contract), ``fail`` (raise
      :class:`InjectedFault`), ``kill`` (raise
      :class:`ChaosKilledError`).  A raise at any seam BEFORE
      ``cutover`` unwinds to clean source retention (book untouched,
      staged shard dropped, typed `HandoffAbortedError`); at ``drain``
      the cutover has already published, so the destination owns the
      range — never two owners either way.

Plans install three ways: programmatically (:func:`install`), from the
``GLT_FAULT_PLAN`` env var (inherited by producer subprocesses and
sampling servers — how cross-process chaos reaches them), or not at
all — every seam is a single module-attribute check when no plan is
active, so the harness costs nothing in production.

Plan syntax — JSON::

    {"seed": 7, "faults": [
      {"site": "rpc.request", "action": "drop", "nth": 3,
       "op": "fetch_one_sampled_message"},
      {"site": "producer.worker", "action": "kill", "nth": 2,
       "worker": 0}]}

or the compact form (``;``-separated, ``site:action:nth[:key=val...]``)::

    rpc.request:drop:3:op=fetch_one_sampled_message;producer.worker:kill:2:worker=0

Every fired fault emits a ``fault.injected`` flight-recorder event, so
a chaos run's injected faults and the retries/restarts they caused
read out of ONE event stream.
"""
from __future__ import annotations

import json
import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

FAULT_PLAN_ENV = 'GLT_FAULT_PLAN'

#: exit code of a chaos-killed sampling worker (distinctive in
#: ``dead_worker_exitcodes`` so tests can tell injected kills from
#: real crashes).
WORKER_KILL_EXIT = 173

_SITES = ('rpc.request', 'producer.worker', 'checkpoint.io',
          'fused.dispatch', 'feature.cold_service', 'serving.request',
          'ops.scrape', 'serving.replica', 'aot.cache', 'ingest.wal',
          'ingest.apply', 'ingest.compact', 'partition.owner',
          'scale.spawn', 'handoff.transfer')
_ACTIONS = ('drop', 'delay', 'corrupt', 'kill', 'fail', 'truncate',
            'flap')


class InjectedFault(RuntimeError):
  """A chaos 'fail' action fired: the real-world analog (disk error,
  host OOM, cold-tier service death) raised mid-operation."""


class ChaosKilledError(RuntimeError):
  """A planned ``fused.dispatch:kill`` fired — the in-process stand-in
  for a preemption (SIGKILL would also kill the test runner; the
  producer-worker site keeps the real ``os._exit`` arm).  Everything a
  real kill loses is lost here too: the test must resume from the
  DURABLE snapshot in a fresh driver, not from live state."""


@dataclass
class Fault:
  """One planned fault: fire ``count`` times starting at the ``nth``
  matching arrival (1-based) at ``site``."""
  site: str
  action: str
  nth: int = 1
  count: int = 1
  op: Optional[str] = None        # rpc.request: handler-name filter
  worker: Optional[int] = None    # producer.worker: rank filter
  epoch: Optional[int] = None     # producer.worker: epoch filter
  replica: Optional[str] = None   # serving.replica: replica-name filter
  #: partition.owner: the VICTIM partition (a kill here classifies
  #: that owner dead at the next dispatch seam); also filters when the
  #: seam names one
  partition: Optional[int] = None
  #: producer.worker: restart-generation filter — ``0`` targets only
  #: the ORIGINAL worker incarnation, so a deterministic kill cannot
  #: re-fire inside the supervisor's replacement (whose fresh process
  #: restarts the arrival counters)
  generation: Optional[int] = None
  secs: float = 0.1               # delay duration
  _seen: int = field(default=0, repr=False, compare=False)

  def __post_init__(self):
    if self.site not in _SITES:
      raise ValueError(f'unknown fault site {self.site!r} '
                       f'(expected one of {_SITES})')
    if self.action not in _ACTIONS:
      raise ValueError(f'unknown fault action {self.action!r} '
                       f'(expected one of {_ACTIONS})')

  def _matches(self, ctx: Dict[str, Any]) -> bool:
    if self.op is not None and ctx.get('op') != self.op:
      return False
    if self.worker is not None and ctx.get('worker') != self.worker:
      return False
    if self.epoch is not None and ctx.get('epoch') != self.epoch:
      return False
    if self.generation is not None and \
        ctx.get('generation') != self.generation:
      return False
    if self.replica is not None and ctx.get('replica') != self.replica:
      return False
    if (self.partition is not None and 'partition' in ctx
        and ctx.get('partition') != self.partition):
      return False
    return True


class ChaosPlan:
  """A set of faults plus the seeded RNG probabilistic faults draw
  from.  Arrival counting is per fault, under a lock — deterministic
  for single-threaded seams (the chaos tests run prefetch depth 1 so
  RPC arrivals are totally ordered)."""

  def __init__(self, faults: List[Fault], seed: int = 0):
    self.faults = list(faults)
    self.seed = int(seed)
    self.rng = random.Random(self.seed)
    self._lock = threading.Lock()
    self.fired: List[Dict[str, Any]] = []

  def on(self, site: str, **ctx) -> List[Fault]:
    """Record one arrival at ``site``; return the faults that fire."""
    fired = []
    with self._lock:
      for f in self.faults:
        if f.site != site or not f._matches(ctx):
          continue
        f._seen += 1
        if f.nth <= f._seen < f.nth + f.count:
          fired.append(f)
          rec = {'site': site, 'action': f.action, 'arrival': f._seen}
          rec.update({k: v for k, v in ctx.items()
                      if isinstance(v, (str, int, float))})
          self.fired.append(rec)
    for f in fired:
      _emit_injected(f, site, ctx)
    return fired

  def exhausted(self) -> bool:
    """Every planned fault has fired its full count."""
    with self._lock:
      return all(f._seen >= f.nth + f.count - 1 for f in self.faults)


def _emit_injected(f: Fault, site: str, ctx: Dict[str, Any]) -> None:
  from ..telemetry.recorder import recorder
  recorder.emit('fault.injected', site=site, action=f.action,
                nth=f.nth, arrival=f._seen,
                op=ctx.get('op'), worker=ctx.get('worker'),
                epoch=ctx.get('epoch'),
                secs=(f.secs if f.action == 'delay' else None))


def parse_plan(spec) -> ChaosPlan:
  """Parse a plan from a dict / list / JSON string / compact string."""
  if isinstance(spec, ChaosPlan):
    return spec
  seed = 0
  if isinstance(spec, str):
    s = spec.strip()
    if s.startswith('{') or s.startswith('['):
      spec = json.loads(s)
    else:
      return ChaosPlan([_parse_compact(part)
                        for part in s.split(';') if part.strip()])
  if isinstance(spec, dict):
    seed = int(spec.get('seed', 0))
    spec = spec.get('faults', [])
  faults = [f if isinstance(f, Fault) else Fault(**f) for f in spec]
  return ChaosPlan(faults, seed=seed)


def _parse_compact(part: str) -> Fault:
  toks = part.strip().split(':')
  if len(toks) < 2:
    raise ValueError(f'bad compact fault {part!r}: need site:action')
  kw: Dict[str, Any] = {'site': toks[0], 'action': toks[1]}
  if len(toks) > 2 and toks[2]:
    kw['nth'] = int(toks[2])
  for tok in toks[3:]:
    if '=' not in tok:
      raise ValueError(f'bad compact fault field {tok!r} in {part!r}')
    k, v = tok.split('=', 1)
    if k in ('nth', 'count', 'worker', 'epoch', 'generation',
             'partition'):
      kw[k] = int(v)
    elif k == 'secs':
      kw[k] = float(v)
    else:
      kw[k] = v
  return Fault(**kw)


# -- process-global plan ----------------------------------------------------
_plan: Optional[ChaosPlan] = None
_env_checked = False
_install_lock = threading.Lock()


def install(spec) -> ChaosPlan:
  """Install ``spec`` as the process's active plan (replacing any)."""
  global _plan, _env_checked
  with _install_lock:
    _plan = parse_plan(spec)
    _env_checked = True
  return _plan


def uninstall() -> None:
  """Deactivate chaos for this process (the env var stays untouched —
  subprocesses spawned later still inherit it)."""
  global _plan, _env_checked
  with _install_lock:
    _plan = None
    _env_checked = True


def active() -> Optional[ChaosPlan]:
  """The process's plan, lazily initialized from ``GLT_FAULT_PLAN``
  (how producer subprocesses and server processes pick chaos up)."""
  global _plan, _env_checked
  if _plan is None and not _env_checked:
    with _install_lock:
      if _plan is None and not _env_checked:
        _env_checked = True
        spec = os.environ.get(FAULT_PLAN_ENV)
        if spec:
          _plan = parse_plan(spec)
  return _plan


# -- seams ------------------------------------------------------------------
def on(site: str, **ctx) -> List[Fault]:
  """The generic seam: no-op (one global read) without a plan."""
  p = active()
  return p.on(site, **ctx) if p is not None else []


def rpc_faults(op: str) -> List[Fault]:
  """`RpcClient.request` seam, called once per attempt.  The caller
  applies the returned actions (sleep for ``delay``, sever for
  ``drop``, scramble the reply for ``corrupt``)."""
  return on('rpc.request', op=op)


def maybe_delay(faults: List[Fault]) -> None:
  for f in faults:
    if f.action == 'delay':
      time.sleep(f.secs)


def corrupt_payload(payload: bytes) -> bytes:
  """Deterministically scramble a reply payload (bit-flip every 7th
  byte) — enough to break both pickle and tensor-map parsing."""
  buf = bytearray(payload)
  if not buf:
    return b'\xff\xff\xff\xff'
  buf[::7] = bytes((b ^ 0xFF) for b in buf[::7])
  return bytes(buf)


def worker_kill_check(rank: int, epoch: int, generation: int = 0,
                      flush=()) -> None:
  """Sampling-worker seam, called before each batch; a fired ``kill``
  hard-exits the process (no cleanup — a real crash).  ``generation``
  is the supervisor's restart count for this rank (0 = original).

  ``flush`` holds mp queues (the producer's progress-ack queue) whose
  feeder threads are joined BEFORE the exit.  The seam models a crash
  BETWEEN batches: every prior batch was already durably sent to the
  channel, and its ack merely sits in the mp.Queue feeder buffer — a
  plain ``os._exit`` raced that feeder, sometimes losing acks for
  batches the channel already holds, so the supervisor replayed the
  FULL assignment and the replacement re-fired the same deterministic
  ``nth`` kill until the restart budget died (the exact hazard
  `MpSamplingProducer._unacked` documents).  Joining the feeder keeps
  the simulation honest (a real crash that loses acks only replays
  already-delivered batches — harmless dedup — nondeterministically,
  not deterministically forever) and makes kill-fault replays exactly
  the unsent batches."""
  for f in on('producer.worker', worker=rank, epoch=epoch,
              generation=generation):
    if f.action == 'kill':
      for q in flush:
        try:
          q.close()
          q.join_thread()
        except Exception:           # noqa: BLE001 — best-effort flush
          pass
      os._exit(WORKER_KILL_EXIT)


def fused_dispatch_check(chunk: int = 0, epoch: int = 0,
                         phase: str = '') -> None:
  """Fused-chunk-dispatch seam (called INSIDE the watchdog-timed
  region): ``delay`` sleeps there so a configured dispatch deadline
  sees a hung collective; ``kill`` raises `ChaosKilledError` (the
  preemption stand-in)."""
  for f in on('fused.dispatch', chunk=chunk, epoch=epoch, op=phase or
              None):
    if f.action == 'delay':
      time.sleep(f.secs)
    elif f.action == 'kill':
      raise ChaosKilledError(
          f'injected fused.dispatch kill (epoch {epoch}, chunk '
          f'{chunk})')


def cold_service_check(scope: str = '') -> None:
  """Host cold-tier gather seam; ``fail`` raises `InjectedFault`."""
  for f in on('feature.cold_service', op=scope or None):
    if f.action == 'fail':
      raise InjectedFault(
          f'injected cold-tier service failure (scope {scope!r})')


def ops_scrape_check(path: str = '') -> None:
  """Ops-endpoint seam (`telemetry.opsserver`), once per HTTP request
  with ``op=<route path>``: ``delay`` stalls the scrape handler thread
  in place (the isolation under test — a wedged scraper must never
  block the serving executor or a fused dispatch), ``drop`` raises
  `InjectedFault` (the handler answers 503; the scraper's problem,
  nobody else's)."""
  for f in on('ops.scrape', op=path or None):
    if f.action == 'delay':
      time.sleep(f.secs)
    elif f.action == 'drop':
      raise InjectedFault(f'injected ops scrape drop (path {path!r})')


def partition_owner_check(step: int = 0) -> None:
  """Partition-owner seam (ISSUE 15), one arrival per mesh dispatch
  (called BEFORE the sampler's key stream advances, so a recovered
  dispatch replays byte-identically).  ``delay`` models a slow-but-
  alive owner (sleeps in place — the epoch slows, nothing is
  reclassified: the PR 13 overloaded-vs-dead discriminator); ``kill``
  classifies the fault's ``partition`` dead and raises the typed
  `PartitionLostError` the recovery ladder consumes (adopt →
  degraded → typed)."""
  fired = on('partition.owner', step=step)
  maybe_delay(fired)
  for f in fired:
    if f.action == 'kill':
      from ..parallel.failover import PartitionLostError
      p = int(f.partition or 0)
      raise PartitionLostError(
          f'injected partition.owner kill: partition {p} classified '
          f'dead at dispatch step {step}', partition=p)


def replica_faults(replica: str, op: str) -> List[Fault]:
  """Fleet-replica seam (`serving.router` handles), one arrival per
  ``submit`` / ``heartbeat``.  ``delay`` sleeps in place here (a slow
  replica — heartbeats stall, the router must classify it overloaded,
  not dead); ``kill`` and ``flap`` are returned for the HANDLE to
  apply (it owns the dead/flapping state the router then observes)."""
  fired = on('serving.replica', replica=replica, op=op)
  maybe_delay(fired)
  return fired


def aot_cache_faults(op: str) -> List[str]:
  """AOT-executable-cache seam (`serving.aot_cache`), ``op`` =
  ``'save'`` / ``'load'``.  ``fail`` raises `InjectedFault` (the
  caller absorbs it into a recompile); ``corrupt`` is returned so the
  writer scrambles the payload it is about to publish (the durable
  bad-entry scenario the checksum must catch on a later load)."""
  actions = [f.action for f in on('aot.cache', op=op)]
  if 'fail' in actions:
    raise InjectedFault(f'injected aot cache failure (op {op!r})')
  return actions


def ingest_wal_faults(op: str = 'append') -> List[str]:
  """WAL seam (`streaming.wal`), one arrival per append.  ``fail``
  raises `InjectedFault` BEFORE any byte is written (the log is
  unchanged — the caller's retry appends cleanly); ``truncate`` is
  returned so the WRITER lands a partial record and then raises (the
  kill-mid-append scenario the torn-tail recovery must absorb)."""
  actions = [f.action for f in on('ingest.wal', op=op)]
  if 'fail' in actions:
    raise InjectedFault(f'injected WAL append failure (op {op!r})')
  return actions


def ingest_apply_check(seqno: int = 0) -> None:
  """Delta-apply seam (`streaming.ingest`), fired between the durable
  WAL append and the in-memory commit: ``kill`` raises
  `ChaosKilledError` (the logged-but-unapplied crash the replay must
  make exactly-once), ``delay`` sleeps in place (lag grows)."""
  for f in on('ingest.apply', seqno=int(seqno)):
    if f.action == 'delay':
      time.sleep(f.secs)
    elif f.action == 'kill':
      raise ChaosKilledError(
          f'injected ingest apply kill (seqno {seqno})')


def ingest_compact_check(seqno: int = 0) -> None:
  """Compaction seam (`streaming.ingest.IngestPipeline.compact`),
  fired BEFORE the compacted-base snapshot publishes: ``kill`` raises
  `ChaosKilledError` mid-compaction — the previous snapshot plus the
  full WAL stay the durable truth."""
  for f in on('ingest.compact', seqno=int(seqno)):
    if f.action == 'kill':
      raise ChaosKilledError(
          f'injected ingest compaction kill (seqno {seqno})')


def scale_spawn_check(replica: str = '') -> None:
  """Elastic scale-out seam (`serving.autoscaler`), fired once per
  spawn attempt before the replica factory runs: ``delay`` sleeps in
  place (a slow provision), ``fail`` raises `InjectedFault`, ``kill``
  raises `ChaosKilledError` — both raises must surface as a typed
  rolled-back `scale.decision` that leaves the fleet unchanged and
  the cooldown unspent."""
  fired = on('scale.spawn', replica=replica or None)
  maybe_delay(fired)
  for f in fired:
    if f.action == 'fail':
      raise InjectedFault(
          f'injected scale.spawn provisioning failure '
          f'(replica {replica!r})')
    if f.action == 'kill':
      raise ChaosKilledError(
          f'injected scale.spawn kill (replica {replica!r})')


def handoff_transfer_check(seam: str, partition: int = 0) -> None:
  """Planned-handoff seam (`parallel.handoff`), fired once per phase
  with ``op`` = the seam name (snapshot/transfer/fence/cutover/drain)
  and ``partition`` = the moving range: ``delay`` sleeps in place (the
  source keeps serving — the handoff just takes longer), ``fail``
  raises `InjectedFault`, ``kill`` raises `ChaosKilledError`.  The
  caller's rollback ladder turns a pre-cutover raise into clean
  source retention and absorbs a post-cutover (drain) raise as a
  completed move — the single-owner invariant either way."""
  fired = on('handoff.transfer', op=seam, partition=int(partition))
  maybe_delay(fired)
  for f in fired:
    if f.action == 'fail':
      raise InjectedFault(
          f'injected handoff {seam} failure (partition {partition})')
    if f.action == 'kill':
      raise ChaosKilledError(
          f'injected handoff {seam} kill (partition {partition})')


def serving_request_check(op: str = '', replica: str = '') -> None:
  """Serving-plane seam (RPC handler: ``op='serve_infer'``; executor
  dispatch: ``op='dispatch'``): ``delay`` sleeps in place (driving
  deadline sheds behind it), ``drop`` raises `InjectedFault` (a typed
  server-side request loss — the replay cache still answers any
  transport retry of the same request id verbatim).  ``replica``
  carries the frontend's fleet name (when it has one), so a plan can
  stall ONE replica's dispatches — how a fleet test backs its
  victim up with real in-flight requests before killing it."""
  for f in on('serving.request', op=op or None,
              replica=replica or None):
    if f.action == 'delay':
      time.sleep(f.secs)
    elif f.action == 'drop':
      raise InjectedFault(
          f'injected serving request drop (op {op!r})')
