"""Prefetching iterator: equivalence, overlap, failure propagation.

The cold-tier gather + device_put ran inside the
batch critical path.  `prefetch=N` moves the next batch's host work
onto a worker thread; these tests pin the contract — identical batch
streams, real wall-clock overlap, exceptions surfacing at the
consumer, and clean early abandonment.
"""
import time

import numpy as np
import pytest

jax = pytest.importorskip('jax')

from graphlearn_tpu.data import Dataset
from graphlearn_tpu.loader import NeighborLoader, PrefetchIterator

N = 256


def _dataset(split_ratio):
  rng = np.random.default_rng(0)
  rows = np.repeat(np.arange(N), 4)
  cols = rng.integers(0, N, N * 4)
  feats = np.tile(np.arange(N, dtype=np.float32)[:, None], (1, 8))
  return (Dataset()
          .init_graph((rows, cols), layout='COO', num_nodes=N)
          .init_node_features(feats, split_ratio=split_ratio)
          .init_node_labels(np.arange(N) % 4))


@pytest.mark.parametrize('split_ratio', [1.0, 0.5])
def test_prefetch_yields_identical_batches(split_ratio):
  ds = _dataset(split_ratio)
  plain = NeighborLoader(ds, [3, 2], np.arange(N), batch_size=32,
                         shuffle=True, seed=7)
  pre = NeighborLoader(ds, [3, 2], np.arange(N), batch_size=32,
                       shuffle=True, seed=7, prefetch=2)
  got_a = list(plain)
  got_b = list(pre)
  assert len(got_a) == len(got_b) == len(plain)
  for a, b in zip(got_a, got_b):
    np.testing.assert_array_equal(np.asarray(a.batch), np.asarray(b.batch))
    np.testing.assert_array_equal(np.asarray(a.node), np.asarray(b.node))
    np.testing.assert_allclose(np.asarray(a.x), np.asarray(b.x))


def test_prefetch_overlaps_producer_with_consumer():
  """With depth 2, producer (d seconds/item) and consumer (d seconds/
  item) pipeline: total ~= n*d, not n*2d."""
  d = 0.05
  n = 10

  def slow_producer():
    for i in range(n):
      time.sleep(d)
      yield i

  t0 = time.perf_counter()
  got = []
  for item in PrefetchIterator(slow_producer(), depth=2):
    time.sleep(d)            # consumer work
    got.append(item)
  elapsed = time.perf_counter() - t0
  assert got == list(range(n))
  # serial would be >= n*2*d = 1.0s; overlapped ~ n*d + d.  Require
  # >= 60% of the producer time hidden (loose for CI noise).
  assert elapsed < n * 2 * d * 0.8, elapsed


def test_prefetch_propagates_exceptions():
  def boom():
    yield 1
    raise RuntimeError('producer failed')

  it = PrefetchIterator(boom(), depth=2)
  assert next(it) == 1
  with pytest.raises(RuntimeError, match='producer failed'):
    next(it)


def test_abandoned_prefetch_epoch_cannot_steal_next_epoch():
  """Breaking out of a prefetch epoch must not cost the NEXT epoch any
  batches (regression: an orphaned worker shared the seed iterator and
  consumed the new epoch's seeds into its dead queue)."""
  ds = _dataset(1.0)
  loader = NeighborLoader(ds, [3], np.arange(N), batch_size=8,
                          shuffle=True, seed=1, prefetch=2)
  it = iter(loader)
  next(it)                       # abandon mid-epoch
  abandoned_thread = it._thread
  seen = sum(1 for _ in loader)  # fresh epoch
  assert seen == len(loader) == N // 8
  # and the abandoned epoch's worker was closed by the new epoch
  abandoned_thread.join(timeout=10)
  assert not abandoned_thread.is_alive()


def test_prefetch_early_abandonment_stops_worker():
  def endless():
    i = 0
    while True:
      yield i
      i += 1

  it = PrefetchIterator(endless(), depth=2)
  assert next(it) == 0
  thread = it._thread
  it.close()
  thread.join(timeout=5)
  assert not thread.is_alive()


@pytest.mark.slow
def test_mesh_loader_prefetch_matches_sync():
  """prefetch=2 on the mesh loaders yields the SAME batches as the
  synchronous path (same seed stream), overlapped on a worker thread."""
  import jax
  from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                       make_mesh)
  n = 64
  rows = np.concatenate([np.arange(n), np.arange(n)])
  cols = np.concatenate([(np.arange(n) + 1) % n, (np.arange(n) + 2) % n])
  feats = np.arange(n, dtype=np.float32)[:, None] * np.ones((1, 3),
                                                            np.float32)
  ds = DistDataset.from_full_graph(4, rows, cols, node_feat=feats,
                                   num_nodes=n, split_ratio=0.5)
  outs = []
  for pf in (0, 2):
    loader = DistNeighborLoader(ds, [2, 2], np.arange(n), batch_size=4,
                                shuffle=True, mesh=make_mesh(4), seed=3,
                                prefetch=pf)
    acc = []
    for _ in range(2):                     # two epochs: worker reuse
      for b in loader:
        acc.append((np.asarray(b.node), np.asarray(b.x)))
    outs.append(acc)
  assert len(outs[0]) == len(outs[1])
  for (n0, x0), (n1, x1) in zip(outs[0], outs[1]):
    np.testing.assert_array_equal(n0, n1)
    np.testing.assert_allclose(x0, x1)
