"""Persistent AOT executable cache (ISSUE 13): warm-restore skips
recompilation (compile_count == 0, THE acceptance pin), corrupt/
stale entries skip to recompile (never a crash or a wrong
executable), write faults are absorbed, publishes are atomic.

A "second process" is simulated by a FRESH `ServingEngine` over the
same cache dir: every engine builds fresh `_counted_jit` wrappers
(empty in-memory executable caches), so a zero compile-count warmup
can only come from the disk restore.
"""
import os
import pickle

import jax
import numpy as np
import pytest

from graphlearn_tpu.data import Dataset
from graphlearn_tpu.models.tree import TreeSAGE
from graphlearn_tpu.serving import AotExecutableCache, ServingEngine
from graphlearn_tpu.serving import aot_cache as aot_mod
from graphlearn_tpu.telemetry import recorder
from graphlearn_tpu.testing import chaos

N, D = 48, 4
FANOUTS = [3, 2]
BUCKETS = (1, 2)


@pytest.fixture(autouse=True)
def _clean():
  chaos.uninstall()
  recorder.enable(None)
  recorder.clear()
  yield
  chaos.uninstall()
  recorder.clear()
  recorder.disable()


def _dataset():
  rng = np.random.default_rng(0)
  rows = np.repeat(np.arange(N), 3)
  cols = rng.integers(0, N, rows.shape[0])
  feats = (np.arange(N, dtype=np.float32)[:, None]
           * np.ones((1, D), np.float32))
  return (Dataset().init_graph((rows, cols), layout='COO', num_nodes=N)
          .init_node_features(feats))


def _engine(model=False, seed=7):
  m = (TreeSAGE(hidden_features=8, out_features=5,
                num_layers=len(FANOUTS)) if model else None)
  eng = ServingEngine(_dataset(), FANOUTS, model=m, seed=seed,
                      buckets=BUCKETS)
  if model:
    eng.init_params(jax.random.key(0))
  return eng


def test_warm_restore_skips_recompilation(tmp_path):
  """THE acceptance pin: a second process with a populated
  GLT_AOT_CACHE_DIR warms with compile_count == 0 and answers
  byte-identically to the compiling process."""
  cache = AotExecutableCache(tmp_path)
  e1 = _engine(model=True)
  w1 = e1.warmup(aot_cache=cache)
  assert w1['compiles'] == len(BUCKETS)   # forward program per bucket
  assert e1.compile_count() == len(BUCKETS)
  assert len(cache.entries()) == len(BUCKETS)
  ref = e1.infer([3, 5])

  recorder.clear()
  e2 = _engine(model=True)
  w2 = e2.warmup(aot_cache=cache)
  assert w2['compiles'] == 0
  assert e2.compile_count() == 0          # the warm-start pin
  assert w2['aot_restored'] == len(BUCKETS)
  got = e2.infer([3, 5])
  np.testing.assert_array_equal(ref.nodes, got.nodes)
  np.testing.assert_array_equal(np.asarray(ref.logits),
                                np.asarray(got.logits))
  hits = recorder.events('aot.cache_hit')
  assert len(hits) == len(BUCKETS)
  # traffic after warm restore stays at zero compiles across buckets
  for seeds in ([1], [2, 9]):
    e2.infer(seeds)
  assert e2.compile_count() == 0


def test_env_knob_routes_warmup_through_cache(tmp_path, monkeypatch):
  monkeypatch.setenv(aot_mod.AOT_CACHE_DIR_ENV, str(tmp_path))
  e1 = _engine()
  w1 = e1.warmup()
  assert w1['aot_restored'] == 0
  assert len(AotExecutableCache(tmp_path).entries()) == len(BUCKETS)
  # re-warm of the SAME engine: the stat counts THIS call's restores
  # (not a lifetime delta that would read 0 forever after a compile)
  w1b = e1.warmup()
  assert w1b['aot_restored'] == len(BUCKETS)
  e2 = _engine()
  w2 = e2.warmup()
  assert e2.compile_count() == 0
  assert w2['aot_restored'] == len(BUCKETS)


def test_corrupt_entry_falls_back_to_recompile(tmp_path):
  """A scrambled payload is caught by the checksum: the warmup
  recompiles (one aot.cache_miss reason=corrupt per bad entry) and
  the answers stay correct — never a crash, never a wrong
  executable."""
  cache = AotExecutableCache(tmp_path)
  e1 = _engine()
  e1.warmup(aot_cache=cache)
  ref = e1.infer([4])
  for name in cache.entries():
    p = tmp_path / name
    rec = pickle.loads(p.read_bytes())
    buf = bytearray(rec['payload'])
    buf[::5] = bytes((b ^ 0xAA) for b in buf[::5])
    rec['payload'] = bytes(buf)
    p.write_bytes(pickle.dumps(rec))
  recorder.clear()
  e2 = _engine()
  e2.warmup(aot_cache=cache)
  assert e2.compile_count() == len(BUCKETS)   # recompiled, no crash
  got = e2.infer([4])
  np.testing.assert_array_equal(ref.nodes, got.nodes)
  reasons = [e.get('reason') for e in recorder.events('aot.cache_miss')]
  assert reasons.count('corrupt') == len(BUCKETS)


def test_garbage_file_and_stale_fingerprint_skip(tmp_path):
  cache = AotExecutableCache(tmp_path)
  e1 = _engine()
  e1.warmup(aot_cache=cache)
  entries = cache.entries()
  # unpicklable garbage in one, fingerprint drift in another
  (tmp_path / entries[0]).write_bytes(b'not a pickle at all')
  p = tmp_path / entries[1]
  rec = pickle.loads(p.read_bytes())
  rec['fingerprint'] = dict(rec['fingerprint'], seed=999)
  p.write_bytes(pickle.dumps(rec))
  recorder.clear()
  e2 = _engine()
  e2.warmup(aot_cache=cache)
  assert e2.compile_count() == len(BUCKETS)
  reasons = sorted(e.get('reason')
                   for e in recorder.events('aot.cache_miss'))
  assert reasons == ['corrupt', 'stale']


def test_different_seed_is_a_different_program(tmp_path):
  """The serve key is a traced closure constant: an engine with a
  different seed must NOT restore another seed's executables (it
  would answer with the wrong sampling trees)."""
  cache = AotExecutableCache(tmp_path)
  _engine(seed=7).warmup(aot_cache=cache)
  e2 = _engine(seed=8)
  e2.warmup(aot_cache=cache)
  assert e2.compile_count() == len(BUCKETS)   # no cross-seed reuse
  assert len(cache.entries()) == 2 * len(BUCKETS)


def test_chaos_fail_write_absorbed(tmp_path):
  """aot.cache:fail on save — the warmup succeeds (this process pays
  nothing), the directory stays empty (the next one pays a compile)."""
  chaos.install('aot.cache:fail:1:op=save;aot.cache:fail:2:op=save')
  cache = AotExecutableCache(tmp_path)
  e1 = _engine()
  w = e1.warmup(aot_cache=cache)
  assert w['buckets'] == {1: True, 2: True}
  assert cache.entries() == []
  assert not list(tmp_path.glob('*.tmp.*'))   # no torn tmp carcass
  chaos.uninstall()
  e2 = _engine()
  e2.warmup(aot_cache=cache)
  assert e2.compile_count() == len(BUCKETS)   # cache was never fed


def test_chaos_corrupt_write_caught_on_later_load(tmp_path):
  """aot.cache:corrupt scrambles the payload on disk; the NEXT
  process's load must detect the checksum mismatch and recompile."""
  chaos.install({'faults': [{'site': 'aot.cache', 'action': 'corrupt',
                             'op': 'save', 'nth': 1, 'count': 99}]})
  cache = AotExecutableCache(tmp_path)
  e1 = _engine()
  e1.warmup(aot_cache=cache)
  assert len(cache.entries()) == len(BUCKETS)   # published, but bad
  chaos.uninstall()
  recorder.clear()
  e2 = _engine()
  e2.warmup(aot_cache=cache)
  assert e2.compile_count() == len(BUCKETS)
  reasons = [e.get('reason') for e in recorder.events('aot.cache_miss')]
  assert reasons.count('corrupt') == len(BUCKETS)
  np.testing.assert_array_equal(e2.infer([4]).nodes,
                                e1.infer([4]).nodes)


def test_atomic_publish_leaves_no_tmp(tmp_path):
  cache = AotExecutableCache(tmp_path)
  _engine().warmup(aot_cache=cache)
  names = os.listdir(tmp_path)
  assert names and all(n.endswith('.aotx') for n in names)


def test_static_toggle_bypasses_baked_executable(tmp_path,
                                                 monkeypatch):
  """GLT_PALLAS keeps its documented DISPATCH-time semantics: an AOT
  executable that baked the other value at warmup is bypassed (the
  jit path serves the call), not silently served stale — and the
  entry still serves once the toggle flips back."""
  cache = AotExecutableCache(tmp_path)
  e1 = _engine()
  e1.warmup(aot_cache=cache)         # bakes use_pallas=False
  ref = e1.infer([4])
  e2 = _engine()
  e2.warmup(aot_cache=cache)
  assert e2.compile_count() == 0
  monkeypatch.setenv('GLT_PALLAS', '1')
  got = e2.infer([4])                # statics mismatch -> jit path
  np.testing.assert_array_equal(ref.nodes, got.nodes)
  assert e2.compile_count() > 0      # the bypass paid a real compile
  monkeypatch.delenv('GLT_PALLAS')
  before = e2.compile_count()
  got2 = e2.infer([4])               # baked statics match again
  np.testing.assert_array_equal(ref.nodes, got2.nodes)
  assert e2.compile_count() == before   # served by the AOT entry


def test_mutated_graph_skips_stale_executable(tmp_path):
  """ISSUE 14 satellite: `_aot_fingerprint` includes the graph shape
  AND the ingest graph_version, so a replica warming against a
  MUTATED graph pays a fresh compile instead of restoring an
  executable fingerprinted against the pre-ingest graph — and a
  replica at the SAME version still warm-restores."""
  from graphlearn_tpu.streaming import StreamingGraph
  cache = AotExecutableCache(tmp_path)
  rng = np.random.default_rng(0)
  rows = np.repeat(np.arange(N), 3)
  cols = rng.integers(0, N, rows.shape[0])
  feats = (np.arange(N, dtype=np.float32)[:, None]
           * np.ones((1, D), np.float32))
  sg = StreamingGraph.from_coo(rows, cols, num_nodes=N,
                               reserve_edges=4 * len(rows))

  def make():
    ds = Dataset().init_node_features(feats).attach_stream(sg)
    return ServingEngine(ds, FANOUTS, seed=7, buckets=BUCKETS)

  e1 = make()
  e1.warmup(aot_cache=cache)
  assert e1.compile_count() == len(BUCKETS)
  n_before = len(cache.entries())
  # same graph version: a replacement replica warm-restores
  e2 = make()
  e2.warmup(aot_cache=cache)
  assert e2.compile_count() == 0
  assert e2.graph_version == e1.graph_version
  # mutate the graph (same padded shape — reserve_edges holds), bump
  # the version: the old entries must NOT serve the new graph's warmup
  sg.apply_events(rng.integers(0, N, 10), rng.integers(0, N, 10))
  recorder.clear()
  e3 = make()
  e3.warmup(aot_cache=cache)
  assert e3.graph_version == sg.version
  assert e3.compile_count() == len(BUCKETS)   # recompiled, not stale
  assert len(cache.entries()) == n_before + len(BUCKETS)
  reasons = [e.get('reason') for e in recorder.events('aot.cache_miss')]
  assert reasons.count('absent') == len(BUCKETS)
  # and a fourth replica AT the new version warm-restores again
  e4 = make()
  e4.warmup(aot_cache=cache)
  assert e4.compile_count() == 0


def test_runtime_failure_of_restored_exec_recompiles(tmp_path):
  """skip-to-recompile extends to CALL time: a restored executable
  that raises is dropped and the dispatch falls back to the compile
  path, still answering correctly."""
  cache = AotExecutableCache(tmp_path)
  e1 = _engine()
  e1.warmup(aot_cache=cache)
  ref = e1.infer([4])
  e2 = _engine()
  e2.warmup(aot_cache=cache)
  assert e2.compile_count() == 0

  def boom(*a, **k):
    raise RuntimeError('deserialized executable rejected the call')
  for key in list(e2._aot):
    e2._aot[key] = (boom, e2._aot[key][1])
  got = e2.infer([4])                  # falls back, recompiles
  np.testing.assert_array_equal(ref.nodes, got.nodes)
  assert e2.compile_count() > 0
  assert ('gather', 1) not in e2._aot  # the bad exec it hit is dropped
