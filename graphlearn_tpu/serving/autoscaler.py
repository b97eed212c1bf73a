"""Closed-loop elastic autoscaling: scale the fleet from SLO burn.

ISSUE 19 tentpole (ROADMAP item 3's last step from "survives faults"
to "operates itself").  The `ElasticController` closes the loop the
earlier PRs opened one side at a time: PR 12 exports per-replica SLO
burn rates, PR 13 makes replica death survivable and AOT warm-start
nearly free, PR 17 exports ``fleet.headroom_qps`` — and until now a
human read all of it and changed nothing.  The controller runs a
periodic evaluation over the router's heartbeat signal feed
(`FleetRouter.heartbeats`: short/long-window burn, admission queue
depth, headroom) and:

  * **scales out** when the worst short- or long-window burn crosses
    ``out_burn`` or any queue is near its bound: spawn a replica
    (the caller's factory — expected to AOT-warm-restore from the
    shared ``GLT_AOT_CACHE_DIR``), verify it (healthy heartbeat, not
    draining/closed, and the ``compile_count()==0`` warm pin — a
    cold replica would answer its first requests at compile latency,
    the exact spike the scale-out is trying to absorb), and only
    then `FleetRouter.add_replica` it;
  * **scales in** when every window's burn is under ``in_burn`` and
    queues are idle: pick the COLDEST replica (lowest short-window
    qps), flip its admission door to draining (the PR 13 hot-swap
    drain machinery — queued work finishes, new arrivals shed typed
    with the retry hint), wait for quiesce, then retire it
    (`remove_replica` + `close`, which unregisters its
    observability).

**Hysteresis** keeps the loop stable: ``out_burn`` and ``in_burn``
are separated (a fleet that just scaled out reads burn between the
thresholds and does nothing), each direction has its own cooldown
(``GLT_SCALE_COOLDOWN_S`` = ``"out,in"`` — burn spikes scale out
fast, scale-in never flaps), and min/max replica bounds are hard
stops.  Every considered decision emits a ``scale.decision`` event
carrying the signal snapshot that justified it and lands in the
in-memory decision ledger (`decisions()`).  A decision that fails
mid-flight (chaos ``scale.spawn`` fault, warmup fault, quiesce
timeout) rolls back typed — the partial replica is closed, a drained
victim is un-drained, a postmortem bundle is dumped — and RE-ARMS:
the failed direction's cooldown is not spent, so the next evaluation
retries immediately.

Knobs (one row each in KNOBS.md): ``GLT_SCALE_EVAL_S``,
``GLT_SCALE_COOLDOWN_S``,
``GLT_SCALE_MIN`` / ``GLT_SCALE_MAX``, ``GLT_SCALE_OUT_BURN`` /
``GLT_SCALE_IN_BURN``.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..telemetry import postmortem
from ..telemetry.live import live
from ..telemetry.recorder import recorder

EVAL_ENV = 'GLT_SCALE_EVAL_S'
COOLDOWN_ENV = 'GLT_SCALE_COOLDOWN_S'
MIN_ENV = 'GLT_SCALE_MIN'
MAX_ENV = 'GLT_SCALE_MAX'
OUT_BURN_ENV = 'GLT_SCALE_OUT_BURN'
IN_BURN_ENV = 'GLT_SCALE_IN_BURN'

DEFAULT_EVAL_S = 1.0
#: (out, in) cooldowns: out short (a burn spike must add capacity
#: fast), in long (retiring capacity is never urgent)
DEFAULT_COOLDOWN_S = (3.0, 15.0)
DEFAULT_MIN_REPLICAS = 1
DEFAULT_MAX_REPLICAS = 8
#: scale-out above this worst-window burn (1.0 = spending the budget)
DEFAULT_OUT_BURN = 1.0
#: scale-in only below this on EVERY window — the hysteresis gap
#: between in_burn and out_burn is what keeps the loop from flapping
DEFAULT_IN_BURN = 0.1
#: queue_depth/max_queue at/above which scale-out triggers even
#: without burn (the queue is the leading indicator; burn lags a
#: window behind)
DEFAULT_QUEUE_RATIO = 0.7


def _env_float(name: str, default: float) -> float:
  try:
    return float(os.environ.get(name, default))
  except ValueError:
    return default


def _env_int(name: str, default: int) -> int:
  try:
    return int(os.environ.get(name, default))
  except ValueError:
    return default


def cooldowns_from_env() -> Tuple[float, float]:
  """``GLT_SCALE_COOLDOWN_S`` as ``"out,in"`` (one value = both)."""
  raw = os.environ.get(COOLDOWN_ENV)
  if not raw:
    return DEFAULT_COOLDOWN_S
  try:
    parts = [float(p) for p in raw.split(',')]
  except ValueError:
    return DEFAULT_COOLDOWN_S
  if len(parts) == 1:
    return (parts[0], parts[0])
  return (parts[0], parts[1])


class ScaleAbortedError(RuntimeError):
  """A scale decision failed mid-flight and was rolled back typed
  (spawn fault, warm-pin failure, quiesce timeout).  ``stage`` names
  where it died."""

  def __init__(self, msg: str, stage: Optional[str] = None):
    super().__init__(msg)
    self.stage = stage


class ElasticController:
  """The closed-loop fleet sizer (see module doc).

  Args:
    router: the `FleetRouter` whose fleet is managed.
    spawn_fn: zero-arg replica factory for scale-out — builds engine
      + frontend (AOT warm restore from the shared cache) and returns
      an UNREGISTERED handle (`LocalReplica` / `RemoteReplica`); the
      controller verifies it and admits it, or closes it on fault.
    min_replicas / max_replicas: hard fleet-size bounds (else
      ``GLT_SCALE_MIN`` / ``GLT_SCALE_MAX``).
    eval_s: evaluation cadence (else ``GLT_SCALE_EVAL_S``).
    cooldown_s: (out, in) seconds (else ``GLT_SCALE_COOLDOWN_S``).
    out_burn / in_burn: hysteresis thresholds on the worst-window
      burn (else ``GLT_SCALE_OUT_BURN`` / ``GLT_SCALE_IN_BURN``).
    queue_ratio: queue-fullness fraction that triggers scale-out on
      its own (the leading indicator).
    warm_pin: require ``engine.compile_count() == 0`` on a spawned
      replica (skipped for handles without an engine, e.g. remotes).
    quiesce_timeout_s: drain budget for scale-in before rollback.
    clock: injectable monotonic source (tests drive decisions
      deterministically).
    auto_start: run the evaluation thread.
  """

  def __init__(self, router, spawn_fn: Callable[[], object],
               min_replicas: Optional[int] = None,
               max_replicas: Optional[int] = None,
               eval_s: Optional[float] = None,
               cooldown_s: Optional[Tuple[float, float]] = None,
               out_burn: Optional[float] = None,
               in_burn: Optional[float] = None,
               queue_ratio: float = DEFAULT_QUEUE_RATIO,
               warm_pin: bool = True,
               quiesce_timeout_s: float = 10.0,
               clock=time.monotonic, auto_start: bool = True):
    self._router = router
    self._spawn_fn = spawn_fn
    self.min_replicas = (min_replicas if min_replicas is not None
                         else _env_int(MIN_ENV, DEFAULT_MIN_REPLICAS))
    self.max_replicas = (max_replicas if max_replicas is not None
                         else _env_int(MAX_ENV, DEFAULT_MAX_REPLICAS))
    self.eval_s = (eval_s if eval_s is not None
                   else _env_float(EVAL_ENV, DEFAULT_EVAL_S))
    cd = cooldown_s if cooldown_s is not None else cooldowns_from_env()
    self.cooldown_out_s, self.cooldown_in_s = float(cd[0]), float(cd[1])
    self.out_burn = (out_burn if out_burn is not None
                     else _env_float(OUT_BURN_ENV, DEFAULT_OUT_BURN))
    self.in_burn = (in_burn if in_burn is not None
                    else _env_float(IN_BURN_ENV, DEFAULT_IN_BURN))
    self.queue_ratio = float(queue_ratio)
    self.warm_pin = bool(warm_pin)
    self.quiesce_timeout_s = float(quiesce_timeout_s)
    self._clock = clock
    self._lock = threading.Lock()
    #: the decision ledger: every considered decision, in order, with
    #: its signal snapshot and outcome (`decisions()` copies it out)
    self._decisions: List[Dict] = []  # guarded-by: self._lock
    self._last_out = -1e18           # guarded-by: self._lock
    self._last_in = -1e18            # guarded-by: self._lock
    self._closed = False
    self._thread: Optional[threading.Thread] = None
    self._m_scale = {
        d: live.counter('scale.replicas', labels={'dir': d})
        for d in ('out', 'in')}
    if auto_start:
      self.start()

  # -- lifecycle ------------------------------------------------------------
  def start(self) -> None:
    if self._thread is not None:
      return
    self._thread = threading.Thread(target=self._loop, daemon=True,
                                    name='glt-elastic-controller')
    self._thread.start()

  def close(self) -> None:
    self._closed = True
    t = self._thread
    if t is not None:
      t.join(self.eval_s + 5.0)
    self._thread = None

  def _loop(self) -> None:
    while not self._closed:
      try:
        self.evaluate()
      except Exception:             # noqa: BLE001 — the loop must
        # outlive any single bad evaluation (a dead controller scales
        # nothing ever again)
        pass
      time.sleep(self.eval_s)

  # -- signals --------------------------------------------------------------
  def signals(self) -> Dict:
    """Aggregate the router's heartbeat feed into the decision
    signals: worst short/long-window burn across live replicas, worst
    queue-fullness fraction, summed headroom, live-replica count.
    Replicas without a heartbeat yet contribute burn/queue 0 — a
    freshly admitted replica's empty SLO window reads burn 0 by the
    `SloTracker` idle contract, so the first post-scale-out
    evaluation cannot immediately re-trigger."""
    short_burn = long_burn = queue_frac = 0.0
    headroom = 0.0
    have_headroom = False
    replicas = 0
    for name, ent in self._router.heartbeats().items():
      if ent['state'] in ('dead', 'quarantined'):
        continue
      replicas += 1
      serving = ent['serving'] or {}
      windows = (serving.get('slo') or {}).get('windows') or []
      if windows:
        short_burn = max(short_burn,
                         float(windows[0].get('burn_rate') or 0.0))
        long_burn = max(long_burn,
                        float(windows[-1].get('burn_rate') or 0.0))
      depth, max_q = serving.get('queue_depth'), serving.get('max_queue')
      if depth is not None and max_q:
        queue_frac = max(queue_frac, float(depth) / float(max_q))
      hr = serving.get('headroom_qps')
      if hr is not None:
        headroom += float(hr)
        have_headroom = True
    return {'replicas': replicas,
            'short_burn': round(short_burn, 4),
            'long_burn': round(long_burn, 4),
            'queue_frac': round(queue_frac, 4),
            'headroom_qps': (round(headroom, 3) if have_headroom
                             else None)}

  # -- the evaluation loop --------------------------------------------------
  def evaluate(self, now: Optional[float] = None) -> Optional[Dict]:
    """One closed-loop pass: read signals, decide, act.  Returns the
    ledger record of the decision considered (None = steady state —
    no event, no record: an idle fleet must not flood the flight
    recorder at the evaluation cadence)."""
    now = self._clock() if now is None else now
    sig = self.signals()
    n = sig['replicas']
    if n == 0:
      return None                    # nothing alive to read signals
      # from — replica survival is the router's job, not ours
    want_out = (sig['short_burn'] > self.out_burn
                or sig['long_burn'] > self.out_burn
                or sig['queue_frac'] >= self.queue_ratio)
    want_in = (not want_out
               and sig['short_burn'] < self.in_burn
               and sig['long_burn'] < self.in_burn
               and sig['queue_frac'] < self.queue_ratio / 2)
    if want_out:
      if n >= self.max_replicas:
        return self._record('out', sig, 'held:bounds', now)
      with self._lock:
        cooling = now - self._last_out < self.cooldown_out_s
      if cooling:
        return self._record('out', sig, 'held:cooldown', now)
      return self._scale_out(sig, now)
    if want_in:
      if n <= self.min_replicas:
        return self._record('in', sig, 'held:bounds', now)
      with self._lock:
        cooling = now - self._last_in < self.cooldown_in_s
      if cooling:
        return self._record('in', sig, 'held:cooldown', now)
      return self._scale_in(sig, now)
    return None                      # between thresholds: hysteresis

  def decisions(self) -> List[Dict]:
    with self._lock:
      return [dict(d) for d in self._decisions]

  def _record(self, direction: str, sig: Dict, outcome: str,
              now: float, replica: Optional[str] = None,
              error: Optional[str] = None) -> Dict:
    rec = {'dir': direction, 'outcome': outcome, 'replica': replica,
           'at': now, 'error': error, **sig}
    with self._lock:
      self._decisions.append(rec)
    recorder.emit('scale.decision', dir=direction, outcome=outcome,
                  replica=replica, error=error, **sig)
    return rec

  # -- scale-out ------------------------------------------------------------
  def _verify_replica(self, handle) -> None:
    """The admission bar for a freshly spawned replica: a healthy
    heartbeat (serving, not draining, not closed) and — when the
    handle exposes its engine — the ``compile_count()==0`` warm pin:
    every bucket restored from the shared AOT cache, so the replica's
    first request is served at warm latency, not compile latency."""
    hb = handle.heartbeat()
    serving = (hb or {}).get('serving')
    if not serving:
      raise ScaleAbortedError(
          f'spawned replica {handle.name!r} answered no heartbeat',
          stage='verify')
    if serving.get('closed') or serving.get('draining'):
      raise ScaleAbortedError(
          f'spawned replica {handle.name!r} is '
          f'{"closed" if serving.get("closed") else "draining"} at '
          'admission time', stage='verify')
    engine = getattr(getattr(handle, 'frontend', None), 'engine', None)
    if self.warm_pin and engine is not None:
      compiles = engine.compile_count()
      if compiles != 0:
        raise ScaleAbortedError(
            f'warm-restore pin failed on {handle.name!r}: '
            f'compile_count()=={compiles} after warmup — the shared '
            'GLT_AOT_CACHE_DIR did not cover every bucket; admitting '
            'it would serve first requests at compile latency',
            stage='verify')

  def _scale_out(self, sig: Dict, now: float) -> Dict:
    from ..testing import chaos
    handle = None
    try:
      chaos.scale_spawn_check()
      handle = self._spawn_fn()
      if handle is None:
        raise ScaleAbortedError('spawn_fn returned no replica',
                                stage='spawn')
      self._verify_replica(handle)
      self._router.add_replica(handle)
    except Exception as e:          # noqa: BLE001 — every spawn fault
      # rolls back typed and re-arms (cooldown NOT spent)
      if handle is not None:
        try:
          handle.close()
        except Exception:           # noqa: BLE001 — best-effort
          pass
      postmortem.dump('autoscale.scale_out_fault', error=e,
                      extra={'signals': sig})
      return self._record('out', sig, 'rolled_back', now,
                          replica=getattr(handle, 'name', None),
                          error=f'{type(e).__name__}: {e}')
    with self._lock:
      self._last_out = now
    self._m_scale['out'].inc()
    return self._record('out', sig, 'ok', now, replica=handle.name)

  # -- scale-in -------------------------------------------------------------
  def _pick_coldest(self) -> Optional[str]:
    """The scale-in victim: the healthy replica with the lowest
    short-window qps (ties broken by name for determinism)."""
    best = None
    for name, ent in sorted(self._router.heartbeats().items()):
      if ent['state'] != 'healthy':
        continue
      windows = ((ent['serving'] or {}).get('slo') or {}) \
          .get('windows') or []
      qps = float(windows[0].get('qps') or 0.0) if windows else 0.0
      if best is None or qps < best[1]:
        best = (name, qps)
    return best[0] if best else None

  def _scale_in(self, sig: Dict, now: float) -> Dict:
    victim = self._pick_coldest()
    if victim is None:
      return self._record('in', sig, 'held:no_victim', now)
    handle = self._router.get_replica(victim)
    frontend = getattr(handle, 'frontend', None)
    if handle is None or frontend is None:
      return self._record('in', sig, 'held:no_victim', now,
                          replica=victim)
    draining = False
    try:
      # the PR 13 drain machinery: flip the door, let queued work
      # finish, shed new arrivals typed with the retry hint —
      # clients that honor retry_after_ms land on survivors
      frontend.admission.set_draining(True)
      draining = True
      deadline = time.monotonic() + self.quiesce_timeout_s
      while not frontend.quiesced():
        if time.monotonic() > deadline:
          raise ScaleAbortedError(
              f'replica {victim!r} did not quiesce within '
              f'{self.quiesce_timeout_s:g}s of draining — '
              'un-draining and keeping it', stage='quiesce')
        time.sleep(0.005)
    except Exception as e:          # noqa: BLE001 — rollback: the
      # victim goes straight back into rotation, no capacity change
      if draining:
        try:
          frontend.admission.set_draining(False)
        except Exception:           # noqa: BLE001 — best-effort
          pass
      postmortem.dump('autoscale.scale_in_fault', error=e,
                      extra={'signals': sig, 'replica': victim})
      return self._record('in', sig, 'rolled_back', now,
                          replica=victim,
                          error=f'{type(e).__name__}: {e}')
    # quiesced: retire — out of rotation first (nothing new routes
    # there), then close (shutdown unregisters its observability)
    self._router.remove_replica(victim)
    try:
      handle.close()
    except Exception:               # noqa: BLE001 — best-effort; the
      # replica is already out of rotation either way
      pass
    with self._lock:
      self._last_in = now
    self._m_scale['in'].inc()
    return self._record('in', sig, 'ok', now, replica=victim)
