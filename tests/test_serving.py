"""Online serving plane (ISSUE 9): engine byte-identity across bucket
boundaries, admission control (typed shedding, bounded queue),
coalescing frontend, and the zero-recompile-after-warmup pin.
"""
import time

import jax
import numpy as np
import pytest

from graphlearn_tpu.data import Dataset
from graphlearn_tpu.models.tree import TreeSAGE
from graphlearn_tpu.serving import (AdmissionRejected, ServingEngine,
                                    ServingFrontend, resolve_buckets)
from graphlearn_tpu.serving.admission import AdmissionController
from graphlearn_tpu.telemetry import recorder
from graphlearn_tpu.testing import chaos

N, D = 64, 6
FANOUTS = [3, 2]
BUCKETS = (1, 2, 4)


@pytest.fixture(autouse=True)
def _clean():
  chaos.uninstall()
  recorder.enable(None)
  recorder.clear()
  yield
  chaos.uninstall()
  recorder.clear()
  recorder.disable()


def _dataset(split_ratio=1.0, cold_cache_rows='auto'):
  rng = np.random.default_rng(0)
  rows = np.repeat(np.arange(N), 4)
  cols = rng.integers(0, N, rows.shape[0])
  # row r of the table = [r, r, ...]: a gathered feature row names its
  # node id, so identity assertions read directly off x
  feats = (np.arange(N, dtype=np.float32)[:, None]
           * np.ones((1, D), np.float32))
  ds = Dataset().init_graph((rows, cols), layout='COO', num_nodes=N)
  if split_ratio < 1.0:
    from graphlearn_tpu.data.feature import Feature
    ds.node_features = Feature(feats, split_ratio=split_ratio,
                               cold_cache_rows=cold_cache_rows)
  else:
    ds.init_node_features(feats)
  return ds


@pytest.fixture(scope='module')
def engine():
  eng = ServingEngine(_dataset(), FANOUTS, seed=7, buckets=BUCKETS)
  eng.warmup()
  return eng


@pytest.fixture(scope='module')
def model_engine():
  model = TreeSAGE(hidden_features=8, out_features=5,
                   num_layers=len(FANOUTS))
  eng = ServingEngine(_dataset(), FANOUTS, model=model, seed=7,
                      buckets=BUCKETS)
  eng.init_params(jax.random.key(0))
  eng.warmup()
  return eng


# -- bucket ladder ----------------------------------------------------------
def test_resolve_buckets(monkeypatch):
  assert resolve_buckets((8, 2, 2, 4)) == (2, 4, 8)
  monkeypatch.setenv('GLT_SERVING_BUCKETS', '1, 4,16')
  assert resolve_buckets() == (1, 4, 16)
  monkeypatch.setenv('GLT_SERVING_BUCKETS', 'garbage')
  assert resolve_buckets() == (1, 2, 4, 8, 16)   # degrade to default


def test_bucket_for(engine):
  assert engine.bucket_for(1) == 1
  assert engine.bucket_for(3) == 4
  with pytest.raises(ValueError):
    engine.bucket_for(5)


# -- byte-identity (the coalescing contract) --------------------------------
def test_coalesced_byte_identity_across_buckets(engine):
  """A request's nodes/x are byte-identical whether it was served
  alone (bucket 1) or coalesced with strangers into a deeper bucket —
  the per-seed key schedule at work."""
  seeds = np.array([5, 9, 33])
  co = engine.infer(seeds)                 # bucket 4, one dispatch
  off = engine.offline_reference(seeds)    # bucket 1, one per seed
  np.testing.assert_array_equal(co.nodes, off.nodes)
  np.testing.assert_array_equal(co.x, off.x)
  # gathered rows really are the sampled nodes' rows (zero for pads)
  valid = co.nodes >= 0
  np.testing.assert_array_equal(
      co.x[..., 0], np.where(valid, co.nodes, 0).astype(np.float32))
  # mid-ladder bucket agrees too
  two = engine.infer(seeds[:2])            # bucket 2
  np.testing.assert_array_equal(two.nodes, off.nodes[:2])
  np.testing.assert_array_equal(two.x, off.x[:2])


def test_rider_independence(engine):
  """Same seed, different co-batched traffic, same bucket -> the same
  bytes (what makes demuxed results request-private)."""
  a = engine.infer(np.array([5, 9, 33]))
  b = engine.infer(np.array([5, 60, 61, 62]))
  np.testing.assert_array_equal(a.nodes[0], b.nodes[0])
  np.testing.assert_array_equal(a.x[0], b.x[0])


def test_repeat_determinism(engine):
  """Two identical requests (e.g. an RPC retry's re-execution) answer
  byte-identically."""
  a = engine.infer(np.array([17, 3]))
  b = engine.infer(np.array([17, 3]))
  np.testing.assert_array_equal(a.nodes, b.nodes)
  np.testing.assert_array_equal(a.x, b.x)


def test_model_logits_identity(model_engine):
  """Fused-forward logits: byte-identical within a bucket shape
  whatever the request rode with; across bucket shapes nodes stay
  byte-identical and logits agree to float tolerance (XLA retiles
  matmuls per shape — see the engine docstring's fine print)."""
  seeds = np.array([5, 9, 33])
  a = model_engine.infer(seeds)                     # cap 4
  b = model_engine.infer(np.array([5, 9, 33, 60]))  # cap 4, one rider
  np.testing.assert_array_equal(a.logits, b.logits[:3])
  off = model_engine.offline_reference(seeds)       # cap 1 each
  np.testing.assert_array_equal(a.nodes, off.nodes)
  np.testing.assert_allclose(a.logits, off.logits, atol=1e-5)
  # pinned-cap offline reference IS bitwise, logits included
  off4 = model_engine.offline_reference(seeds, cap=4)
  np.testing.assert_array_equal(a.logits, off4.logits)


def test_tiered_matches_hot(engine):
  """A tiered table (hot split + cold cache + host misses) serves the
  same bytes as the fully-HBM table — for any cache budget."""
  seeds = np.array([5, 9, 33, 60])
  ref = engine.infer(seeds)
  for cache_rows in (0, 4):
    eng_t = ServingEngine(_dataset(split_ratio=0.5,
                                   cold_cache_rows=cache_rows),
                          FANOUTS, seed=7, buckets=BUCKETS)
    got = eng_t.infer(seeds)
    np.testing.assert_array_equal(got.nodes, ref.nodes)
    np.testing.assert_array_equal(got.x, ref.x)
  # cold-cache telemetry lands under the serving scope
  if any(e.get('scope') == 'serving'
         for e in recorder.events('cache.miss')):
    assert all(e['scope'] in ('serving', 'feature', 'dist')
               for e in recorder.events('cache.miss'))


def test_warmup_zero_recompiles(engine):
  """THE serving acceptance pin: after warmup, the whole traffic
  envelope (every request size up to the top bucket, both arms) hits
  warm executables — the `_counted_jit` per-callable compile
  counters must not move."""
  assert all(engine.warm.values())
  before = engine.compile_count()
  for k in (1, 2, 3, 4, 1, 2, 3, 4):
    engine.infer(np.arange(k) + 1)
  assert engine.compile_count() == before, \
      'a traffic shape escaped the bucket ladder and recompiled'
  status = engine.compile_status()
  assert status['buckets'] == {'1': True, '2': True, '4': True}


def test_driver_compile_count_counters():
  """The `_counted_jit` per-callable counters behind the pin: a
  compile ticks, a warm executable hit does not, a new shape ticks
  again — and `driver_compile_count` sums them duck-typed (the same
  helper the mesh epoch drivers expose as `compile_count()`)."""
  import jax.numpy as jnp
  from graphlearn_tpu.loader.fused import (_counted_jit,
                                           driver_compile_count)

  class _D:
    pass

  d = _D()
  d._compiled = _counted_jit(lambda x: x * 2)
  d._compiled(jnp.ones((2,)))
  assert (d._compiled.calls, d._compiled.compiles) == (1, 1)
  d._compiled(jnp.ones((2,)))
  assert d._compiled.compiles == 1          # in-memory executable hit
  d._compiled(jnp.ones((3,)))
  assert d._compiled.compiles == 2          # new shape = new compile
  assert driver_compile_count(d) == 2


# -- admission control ------------------------------------------------------
def test_queue_bound_typed_rejection():
  ctl = AdmissionController(max_queue=2, default_deadline_ms=1000)
  ctl.submit([1])
  ctl.submit([2])
  with pytest.raises(AdmissionRejected) as ei:
    ctl.submit([3])
  assert ei.value.reason == 'queue_full'
  assert ei.value.queue_depth == 2 and ei.value.limit == 2
  assert ctl.stats()['shed']['queue_full'] == 1
  assert len(recorder.events('serving.admit')) == 2
  shed = recorder.events('serving.shed')
  assert shed and shed[-1]['reason'] == 'queue_full'


def test_deadline_shed_typed_never_silent():
  """A queued request whose deadline passes is resolved with a typed
  AdmissionRejected (reason='deadline', waited_ms diagnostics) — its
  caller learns immediately; nothing is dropped on the floor."""
  ctl = AdmissionController(max_queue=8, default_deadline_ms=1000)
  expired = ctl.submit([1], deadline_ms=1)
  alive = ctl.submit([2], deadline_ms=10_000)
  time.sleep(0.05)
  run = ctl.take(max_seeds=4, max_wait_s=0.0)
  assert [r is alive for r in run] == [True]
  assert expired.future.done()
  with pytest.raises(AdmissionRejected) as ei:
    expired.future.result(0)
  assert ei.value.reason == 'deadline'
  assert ei.value.waited_ms > 0
  assert ctl.stats()['shed']['deadline'] == 1
  assert any(e['reason'] == 'deadline'
             for e in recorder.events('serving.shed'))


def test_burst_respects_queue_bound():
  """Under a burst the queue never exceeds its bound: exactly
  max_queue admissions succeed, the rest are refused typed, and every
  admitted request is eventually answered."""
  ctl = AdmissionController(max_queue=4, default_deadline_ms=10_000)
  admitted, refused = [], 0
  for i in range(10):
    try:
      admitted.append(ctl.submit([i]))
    except AdmissionRejected as e:
      refused += 1
      assert e.reason == 'queue_full'
  assert len(admitted) == 4 and refused == 6
  assert ctl.depth() == 4
  served = []
  while ctl.depth():
    served += ctl.take(max_seeds=2, max_wait_s=0.0)
  assert len(served) == 4
  ctl.close()


def test_shutdown_resolves_queued_typed():
  ctl = AdmissionController(max_queue=8, default_deadline_ms=10_000)
  req = ctl.submit([1])
  ctl.close()
  with pytest.raises(AdmissionRejected) as ei:
    req.future.result(0)
  assert ei.value.reason == 'shutdown'
  with pytest.raises(AdmissionRejected):
    ctl.submit([2])                 # the closed door is typed too


# -- coalescing frontend ----------------------------------------------------
def test_frontend_coalesces_and_demuxes(engine):
  fe = ServingFrontend(engine, auto_start=False, max_wait_ms=0.0,
                       default_deadline_ms=10_000)
  seeds = [np.array([5]), np.array([9, 33]), np.array([60])]
  futs = [fe.submit(s) for s in seeds]
  assert fe.pump_once() == 3
  flat = np.concatenate(seeds)
  ref = engine.offline_reference(flat)
  got = np.concatenate([f.result(1.0).x for f in futs])
  np.testing.assert_array_equal(got, ref.x)
  ev = recorder.events('serving.coalesce')
  assert ev and ev[-1]['requests'] == 3 and ev[-1]['seeds'] == 4 \
      and ev[-1]['bucket'] == 4
  reqs = recorder.events('serving.request')
  assert len(reqs) == 3 and all(e['ok'] for e in reqs)
  assert all(e['latency_ms'] >= 0 for e in reqs)
  assert fe.stats()['served_requests'] == 3
  fe.shutdown()


def test_frontend_too_large_typed(engine):
  fe = ServingFrontend(engine, auto_start=False)
  with pytest.raises(AdmissionRejected) as ei:
    fe.submit(np.arange(5))         # top bucket is 4
  assert ei.value.reason == 'too_large'
  fe.shutdown()


def test_frontend_refuses_out_of_range_seeds(engine):
  """Malformed seed ids are REFUSED, not clamped: jax gathers clamp
  out-of-range indices, so without the door check a bogus id would
  come back as a plausible answer for a different node."""
  fe = ServingFrontend(engine, auto_start=False)
  with pytest.raises(ValueError, match='outside'):
    fe.submit([N + 100])
  with pytest.raises(ValueError, match='outside'):
    fe.submit([-5])
  with pytest.raises(ValueError):
    fe.submit([])
  fe.shutdown()


def test_pump_once_nonblocking_empty_queue(engine):
  fe = ServingFrontend(engine, auto_start=False)
  assert fe.pump_once(block=False) == 0   # returns, never waits
  fe.shutdown()


def test_model_without_params_typed():
  eng = ServingEngine(
      _dataset(), FANOUTS,
      model=TreeSAGE(hidden_features=8, out_features=5,
                     num_layers=len(FANOUTS)),
      seed=7, buckets=(1,))
  with pytest.raises(ValueError, match='init_params'):
    eng.infer(np.array([3]))


def test_frontend_executor_fault_resolves_every_future(engine):
  """A dispatch that dies (injected serving.request drop at the
  executor seam) resolves EVERY rider's future with the typed error —
  the no-lost-requests contract under faults."""
  chaos.install('serving.request:drop:1:op=dispatch')
  fe = ServingFrontend(engine, auto_start=False, max_wait_ms=0.0,
                       default_deadline_ms=10_000)
  futs = [fe.submit([s]) for s in (3, 7)]
  assert fe.pump_once() == 0
  for f in futs:
    with pytest.raises(chaos.InjectedFault):
      f.result(1.0)
  reqs = recorder.events('serving.request')
  assert len(reqs) == 2 and not any(e['ok'] for e in reqs)
  assert fe.stats()['failed'] == 2
  assert chaos.active().exhausted()
  chaos.uninstall()
  # the tier recovers: the next pump serves normally
  fut = fe.submit([5])
  assert fe.pump_once() == 1
  np.testing.assert_array_equal(fut.result(1.0).x,
                                engine.offline_reference([5]).x)
  fe.shutdown()


def test_frontend_threaded_end_to_end(engine):
  """The real executor thread: concurrent submitters, everything
  answered, byte-identical to the offline reference."""
  fe = ServingFrontend(engine, auto_start=True, warmup=False,
                       max_wait_ms=1.0, default_deadline_ms=10_000)
  seeds = np.array([3, 5, 9, 17, 33, 60, 2, 41])
  futs = [fe.submit([int(s)]) for s in seeds]
  got = np.concatenate([f.result(10.0).x for f in futs])
  np.testing.assert_array_equal(got,
                                engine.offline_reference(seeds).x)
  fe.shutdown()
  with pytest.raises(AdmissionRejected):
    fe.submit([1])
