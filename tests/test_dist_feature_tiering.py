"""Tiered distributed feature store: HBM hot shards + host-DRAM cold
tier.

The scale claim under test: the mesh engine must serve feature tables
LARGER than the per-device HBM shard budget.  On the virtual CPU mesh
that is asserted structurally — the device shard array holds only
``ceil(split_ratio * rows)`` rows per partition — while provenance
features (row value == original node id) prove every cold row is
served correctly through the host overlay, and the telemetry reports
the hit rate.  Mirrors the reference's beyond-HBM contract
(`data/feature.py:174-206`, `csrc/cuda/unified_tensor.cu:202+`).
"""
import numpy as np
import jax
import pytest

from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                     DistNeighborSampler, make_mesh)
from graphlearn_tpu.parallel.dist_sampler import (DistLinkNeighborLoader,
                                                  DistSubGraphLoader)

N = 64
P = 4


def _ring_dataset(split_ratio, num_parts=P):
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  feats = (np.arange(N, dtype=np.float32)[:, None]
           * np.ones((1, 4), np.float32))          # feat[v] == v
  labels = (np.arange(N) % 5).astype(np.int32)
  node_pb = (np.arange(N) % num_parts).astype(np.int32)
  return DistDataset.from_full_graph(
      num_parts, rows, cols, node_feat=feats, node_label=labels,
      num_nodes=N, node_pb=node_pb, split_ratio=split_ratio)


def _assert_provenance(ds, out):
  nodes = np.asarray(out['node'])
  x = np.asarray(out['x'])
  y = np.asarray(out['y'])
  for p in range(ds.num_partitions):
    m = nodes[p] >= 0
    old = ds.new2old[nodes[p][m]]
    np.testing.assert_allclose(x[p][m][:, 0], old.astype(np.float32))
    np.testing.assert_array_equal(y[p][m], old % 5)


def test_tiered_layout_smaller_hbm_shards():
  ds = _ring_dataset(split_ratio=0.5)
  nf = ds.node_features
  assert nf.is_tiered
  # each partition owns 16 rows; the HBM shard holds only 8 of them.
  assert nf.shards.shape == (P, 8, 4)
  np.testing.assert_array_equal(nf.hot_counts, [8, 8, 8, 8])
  assert nf.cold_host.shape == (N, 4)
  # hotness relabel: within each partition, hot rows (the first half of
  # the ownership range) have in-degree >= the cold rows' (ring: all
  # equal, so just check the id map round-trips).
  np.testing.assert_array_equal(np.sort(ds.new2old), np.arange(N))


@pytest.mark.parametrize('split_ratio', [0.0, 0.25, 0.75])
def test_tiered_feature_provenance(split_ratio):
  ds = _ring_dataset(split_ratio)
  sampler = DistNeighborSampler(ds, [2, 2], mesh=make_mesh(P), seed=0)
  assert sampler.tiered
  # seeds span the whole id range so cold rows (the coldest tail of
  # every partition) are guaranteed to appear in the neighborhoods
  seeds = ds.old2new[np.arange(0, N, 2).reshape(P, 8)]
  out = sampler.sample_from_nodes(seeds)
  _assert_provenance(ds, out)
  stats = sampler.exchange_stats()
  # new r10 vocabulary: lookups = all valid feature lookups,
  # cold_lookups = lookups past the hot tier (the cache denominator)
  assert stats['dist.feature.cold_lookups'] > 0
  assert (stats['dist.feature.cold_lookups']
          <= stats['dist.feature.lookups'])
  assert (0 < stats['dist.feature.cold_misses']
          <= stats['dist.feature.cold_lookups'])
  if split_ratio == 0.0:
    # everything is cold: no lookup is hot-served
    assert (stats['dist.feature.cold_lookups']
            == stats['dist.feature.lookups'])
    assert stats['dist.feature.hot_hit_rate'] == 0.0
  else:
    assert (stats['dist.feature.cold_lookups']
            < stats['dist.feature.lookups'])
    assert 0.0 < stats['dist.feature.hot_hit_rate'] < 1.0
  assert 0.0 <= stats['dist.feature.cache_hit_rate'] <= 1.0
  assert (stats['dist.feature.cold_hit_rate']
          == stats['dist.feature.cache_hit_rate'])


def test_tiered_matches_untiered():
  """Tiering must not perturb sampled topology: with fanout >= max
  degree the hop is exact (no RNG influence), so the edge SET in old-id
  space must be identical between the tiered and fully-HBM stores
  (relabels differ — hotness order — so sets, not arrays)."""
  ds_full = _ring_dataset(1.0)
  ds_tier = _ring_dataset(0.4)
  s_full = DistNeighborSampler(ds_full, [2], mesh=make_mesh(P), seed=7)
  s_tier = DistNeighborSampler(ds_tier, [2], mesh=make_mesh(P), seed=7)
  edge_sets = []
  for s, ds in ((s_full, ds_full), (s_tier, ds_tier)):
    out = s.sample_from_nodes(ds.old2new[np.arange(16).reshape(P, 4)])
    _assert_provenance(ds, out)
    nodes = np.asarray(out['node'])
    rows = np.asarray(out['row'])
    cols = np.asarray(out['col'])
    es = set()
    for p in range(P):
      m = rows[p] >= 0
      r_old = ds.new2old[nodes[p][rows[p][m]]]
      c_old = ds.new2old[nodes[p][cols[p][m]]]
      es.update(zip(r_old.tolist(), c_old.tolist()))
    edge_sets.append(es)
  assert edge_sets[0] == edge_sets[1]


def test_tiered_loader_epoch_and_training():
  """Full mesh-loader epoch over a table deliberately larger than the
  HBM shard budget (split_ratio=0.3): every batch trains."""
  import jax.numpy as jnp
  ds = _ring_dataset(0.3)
  loader = DistNeighborLoader(ds, [2, 2], np.arange(N), batch_size=4,
                              shuffle=True, mesh=make_mesh(P), seed=0)
  seen = 0
  for batch in loader:
    x = np.asarray(batch.x)
    nodes = np.asarray(batch.node)
    for p in range(P):
      m = nodes[p] >= 0
      np.testing.assert_allclose(
          x[p][m][:, 0], ds.new2old[nodes[p][m]].astype(np.float32))
    # a model consumes the batch: masked mean must be finite
    total = jnp.where(batch.node_mask[..., None], batch.x, 0).sum()
    assert np.isfinite(float(total))
    seen += 1
  assert seen == len(loader)
  stats = loader.sampler.exchange_stats()
  assert stats['dist.feature.cold_misses'] > 0


@pytest.mark.slow
def test_tiered_link_and_subgraph():
  ds = _ring_dataset(0.5)
  link = DistLinkNeighborLoader(
      ds, [2], edge_label_index=(np.arange(16), (np.arange(16) + 1) % N),
      neg_sampling='binary', batch_size=4, mesh=make_mesh(P), seed=0)
  b = next(iter(link))
  nodes = np.asarray(b.node)
  x = np.asarray(b.x)
  for p in range(P):
    m = nodes[p] >= 0
    np.testing.assert_allclose(
        x[p][m][:, 0], ds.new2old[nodes[p][m]].astype(np.float32))
  sub = DistSubGraphLoader(ds, [2], np.arange(8), batch_size=2,
                           mesh=make_mesh(P), seed=0)
  b = next(iter(sub))
  nodes = np.asarray(b.node)
  x = np.asarray(b.x)
  for p in range(P):
    m = nodes[p] >= 0
    np.testing.assert_allclose(
        x[p][m][:, 0], ds.new2old[nodes[p][m]].astype(np.float32))
