"""Closed-loop elastic autoscaling (ISSUE 19): the `ElasticController`
decision machine under an injected clock.

The contract stack: scale-out on a burn spike admits only a verified
warm replica; the per-direction cooldowns suppress re-fires and a
rolled-back decision does NOT spend them (re-arm is the point of a
typed rollback); scale-in drains the coldest replica and retires it
only after quiesce — a quiesce timeout un-drains and keeps it;
min/max bounds are hard stops; the hysteresis band between in_burn
and out_burn decides nothing.  Plus the `SloTracker` idle contract
the controller's first post-scale-out evaluation depends on (empty /
idle / zero-budget windows read burn 0.0, never NaN or stale).
"""
import pytest

from graphlearn_tpu.serving.autoscaler import (ElasticController,
                                               ScaleAbortedError)
from graphlearn_tpu.telemetry.live import LiveRegistry
from graphlearn_tpu.telemetry.slo import SloTracker
from graphlearn_tpu.testing import chaos



# -- scripted fleet ---------------------------------------------------------

def _hb(short_burn=0.0, long_burn=0.0, qps=1.0, depth=0, max_q=64,
        state='healthy'):
  return {'state': state, 'serving': {
      'queue_depth': depth, 'max_queue': max_q,
      'slo': {'windows': [
          {'window_secs': 1.0, 'burn_rate': short_burn, 'qps': qps},
          {'window_secs': 3.0, 'burn_rate': long_burn, 'qps': qps}]}}}


class FakeAdmission:
  def __init__(self):
    self.draining = False

  def set_draining(self, flag):
    self.draining = bool(flag)


class FakeEngine:
  def __init__(self, compiles=0):
    self._compiles = compiles

  def compile_count(self):
    return self._compiles


class FakeFrontend:
  def __init__(self, compiles=0, quiesces=True):
    self.engine = FakeEngine(compiles)
    self.admission = FakeAdmission()
    self._quiesces = quiesces

  def quiesced(self):
    return self._quiesces and self.admission.draining


class FakeReplica:
  def __init__(self, name, compiles=0, quiesces=True):
    self.name = name
    self.frontend = FakeFrontend(compiles, quiesces)
    self.closed = False

  def heartbeat(self):
    return {'serving': {'closed': False, 'draining': False}}

  def close(self):
    self.closed = True


class FakeRouter:
  def __init__(self, hb):
    self.hb = dict(hb)
    self.replicas = {}
    self.removed = []

  def heartbeats(self):
    return {k: dict(v) for k, v in self.hb.items()}

  def add_replica(self, handle):
    self.replicas[handle.name] = handle

  def remove_replica(self, name):
    self.removed.append(name)
    return self.replicas.pop(name, None)

  def get_replica(self, name):
    return self.replicas.get(name)


def _controller(router, spawn, **kw):
  kw.setdefault('min_replicas', 1)
  kw.setdefault('max_replicas', 3)
  kw.setdefault('cooldown_s', (3.0, 15.0))
  kw.setdefault('out_burn', 1.0)
  kw.setdefault('in_burn', 0.1)
  kw.setdefault('auto_start', False)
  return ElasticController(router, spawn, **kw)


# -- scale-out --------------------------------------------------------------

def test_scale_out_on_burn_spike_admits_warm_replica():
  router = FakeRouter({'r0': _hb(short_burn=2.0)})
  spawned = []

  def spawn():
    h = FakeReplica(f'spawn-{len(spawned)}')
    spawned.append(h)
    return h

  ctl = _controller(router, spawn)
  rec = ctl.evaluate(now=10.0)
  assert rec['dir'] == 'out' and rec['outcome'] == 'ok'
  assert rec['replica'] == 'spawn-0' and rec['short_burn'] == 2.0
  assert 'spawn-0' in router.replicas and not spawned[0].closed


def test_queue_is_a_leading_indicator():
  # no burn at all, but the queue near its bound scales out anyway
  router = FakeRouter({'r0': _hb(depth=60, max_q=64)})
  ctl = _controller(router, lambda: FakeReplica('s'), queue_ratio=0.7)
  rec = ctl.evaluate(now=0.0)
  assert rec['dir'] == 'out' and rec['outcome'] == 'ok'


def test_cooldown_suppresses_then_rearms():
  router = FakeRouter({'r0': _hb(short_burn=2.0)})
  ctl = _controller(router, lambda: FakeReplica('s0'))
  assert ctl.evaluate(now=10.0)['outcome'] == 'ok'
  held = ctl.evaluate(now=10.5)
  assert held['dir'] == 'out' and held['outcome'] == 'held:cooldown'
  # past the out-cooldown the same signal fires again
  router.replicas.clear()
  assert ctl.evaluate(now=13.5)['outcome'] == 'ok'


def test_bounds_are_hard_stops():
  router = FakeRouter({'r0': _hb(short_burn=2.0)})
  ctl = _controller(router, lambda: FakeReplica('s'), max_replicas=1)
  assert ctl.evaluate(now=0.0)['outcome'] == 'held:bounds'
  router = FakeRouter({'r0': _hb()})
  ctl = _controller(router, lambda: FakeReplica('s'), min_replicas=1)
  rec = ctl.evaluate(now=0.0)
  assert rec['dir'] == 'in' and rec['outcome'] == 'held:bounds'


def test_hysteresis_band_decides_nothing():
  # burn between in_burn and out_burn: steady state, no record at all
  router = FakeRouter({'r0': _hb(short_burn=0.5)})
  ctl = _controller(router, lambda: FakeReplica('s'))
  assert ctl.evaluate(now=0.0) is None
  assert ctl.decisions() == []


def test_spawn_chaos_fault_rolls_back_and_rearms():
  """The mid-flight fault contract: a chaos scale.spawn failure rolls
  back typed (fleet unchanged, postmortem dumped) and does NOT spend
  the out-cooldown — the very next evaluation retries."""
  router = FakeRouter({'r0': _hb(short_burn=2.0)})
  ctl = _controller(router, lambda: FakeReplica('s0'))
  chaos.install('scale.spawn:fail:1')
  try:
    rec = ctl.evaluate(now=10.0)
  finally:
    chaos.uninstall()
  assert rec['outcome'] == 'rolled_back'
  assert 'InjectedFault' in rec['error']
  assert router.replicas == {}              # fleet unchanged
  # cooldown NOT spent: an immediate retry succeeds
  rec2 = ctl.evaluate(now=10.1)
  assert rec2['outcome'] == 'ok' and 's0' in router.replicas


def test_cold_replica_refused_at_admission():
  # the warm pin: compile_count()>0 after warmup means the shared AOT
  # cache did not cover every bucket — the replica is closed, never
  # admitted, and the rollback re-arms
  router = FakeRouter({'r0': _hb(short_burn=2.0)})
  cold = FakeReplica('cold', compiles=2)
  ctl = _controller(router, lambda: cold)
  rec = ctl.evaluate(now=0.0)
  assert rec['outcome'] == 'rolled_back'
  assert 'warm-restore pin' in rec['error']
  assert cold.closed and router.replicas == {}


# -- scale-in ---------------------------------------------------------------

def test_scale_in_drains_coldest_then_retires():
  router = FakeRouter({'hot': _hb(qps=5.0), 'cold': _hb(qps=1.0)})
  victim = FakeReplica('cold')
  router.replicas = {'hot': FakeReplica('hot'), 'cold': victim}
  ctl = _controller(router, lambda: None)
  rec = ctl.evaluate(now=100.0)
  assert rec['dir'] == 'in' and rec['outcome'] == 'ok'
  assert rec['replica'] == 'cold'           # lowest short-window qps
  assert router.removed == ['cold'] and victim.closed
  assert victim.frontend.admission.draining  # drained before retire
  # the in-cooldown holds the next retirement (the heartbeat feed
  # still reads two entries — the fleet is above min bounds)
  assert ctl.evaluate(now=101.0)['outcome'] == 'held:cooldown'


def test_quiesce_timeout_undrains_and_keeps_victim():
  router = FakeRouter({'hot': _hb(qps=5.0), 'wedged': _hb(qps=1.0)})
  victim = FakeReplica('wedged', quiesces=False)
  router.replicas = {'hot': FakeReplica('hot'), 'wedged': victim}
  ctl = _controller(router, lambda: None, quiesce_timeout_s=0.05)
  rec = ctl.evaluate(now=100.0)
  assert rec['outcome'] == 'rolled_back'
  assert 'quiesce' in rec['error']
  assert not victim.frontend.admission.draining  # back in rotation
  assert not victim.closed and 'wedged' in router.replicas
  # rollback re-arms: the in-cooldown was not spent
  rec2 = ctl.evaluate(now=100.2)
  assert rec2['outcome'] == 'rolled_back'   # still wedged, still typed


def test_dead_and_quarantined_replicas_feed_no_signals():
  router = FakeRouter({'r0': _hb(short_burn=0.0),
                       'gone': _hb(short_burn=9.0, state='dead'),
                       'flap': _hb(short_burn=9.0,
                                   state='quarantined')})
  ctl = _controller(router, lambda: None)
  sig = ctl.signals()
  assert sig['replicas'] == 1 and sig['short_burn'] == 0.0


# -- the SloTracker idle contract -------------------------------------------

def _tracker(now, **kw):
  kw.setdefault('p99_target_ms', 100.0)
  kw.setdefault('qps_target', 0.0)
  kw.setdefault('windows', (1.0, 3.0))
  kw.setdefault('budget', 0.1)
  return SloTracker(registry=LiveRegistry(),
                    clock=lambda: now[0], **kw)


def test_fresh_tracker_reads_burn_zero():
  now = [1000.0]
  t = _tracker(now)
  try:
    for w in t.windows:
      st = t.window_stats(w)
      assert st['count'] == 0 and st['burn_rate'] == 0.0
    assert all(w['burn_rate'] == 0.0
               for w in t.snapshot()['windows'])
  finally:
    t.close()


def test_idle_window_reads_burn_zero_not_stale():
  """Violations that age out of the window leave burn 0.0 — an idle
  replica must not keep reporting the spike it absorbed minutes ago
  (the ElasticController would never scale it in)."""
  now = [1000.0]
  t = _tracker(now)
  try:
    for _ in range(5):
      t.observe(500.0, ok=True)     # 5/5 violating: burn = 10
    assert t.window_stats(1.0)['burn_rate'] == pytest.approx(10.0)
    now[0] += 60.0                  # both windows age to empty
    st = t.window_stats(1.0)
    assert st['count'] == 0 and st['burn_rate'] == 0.0
    assert st['burn_rate'] == st['burn_rate']   # not NaN
  finally:
    t.close()


def test_zero_budget_and_zero_target_read_burn_zero():
  now = [1000.0]
  for kw in ({'budget': 0.0}, {'p99_target_ms': 0.0}):
    t = _tracker(now, **kw)
    try:
      t.observe(500.0, ok=False)
      assert t.window_stats(1.0)['burn_rate'] == 0.0
    finally:
      t.close()
