"""Host-local partition loading (`from_partition_dir(host_parts=...)`):
this process materializes only its partitions' tensors and the sampler
assembles the global sharded arrays shard-by-shard
(`make_array_from_single_device_arrays`) — the multi-host RAM story.
Single-process equivalence here (host_parts = every partition must
reproduce the full load bit-for-bit); the REAL 2-process arm runs in
tests/test_multihost.py.
"""
import numpy as np
import pytest

from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                     make_mesh)
from graphlearn_tpu.partition import RandomPartitioner

P, N = 8, 128


def _write(root):
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  feats = np.arange(N, dtype=np.float32)[:, None] * np.ones((1, 3),
                                                            np.float32)
  labels = (np.arange(N) % 5).astype(np.int32)
  RandomPartitioner(root, P, N, (rows, cols), node_feat=feats,
                    node_label=labels, seed=0).partition()


def test_host_local_equals_full_load(tmp_path):
  _write(tmp_path)
  full = DistDataset.from_partition_dir(tmp_path)
  local = DistDataset.from_partition_dir(tmp_path,
                                         host_parts=np.arange(P))
  np.testing.assert_array_equal(full.graph.bounds, local.graph.bounds)
  np.testing.assert_array_equal(full.old2new, local.old2new)
  np.testing.assert_array_equal(full.graph.indptr, local.graph.indptr)
  # CSR column ORDER within a row may differ (independent sorts);
  # compare per-row sets via a canonical sort
  for p in range(P):
    for r in range(full.graph.max_local_nodes):
      a = np.sort(full.graph.indices[p][full.graph.indptr[p][r]:
                                        full.graph.indptr[p][r + 1]])
      b = np.sort(local.graph.indices[p][local.graph.indptr[p][r]:
                                         local.graph.indptr[p][r + 1]])
      np.testing.assert_array_equal(a, b)
  np.testing.assert_array_equal(full.node_features.shards,
                                local.node_features.shards)
  np.testing.assert_array_equal(full.node_labels, local.node_labels)


def test_host_local_loader_epoch(tmp_path):
  _write(tmp_path)
  ds = DistDataset.from_partition_dir(tmp_path,
                                      host_parts=np.arange(P))
  loader = DistNeighborLoader(ds, [2, 2], np.arange(N), batch_size=4,
                              shuffle=True, mesh=make_mesh(P), seed=0)
  nb = 0
  for b in loader:
    nodes = np.asarray(b.node)
    x = np.asarray(b.x)
    y = np.asarray(b.y)
    for p in range(P):
      m = nodes[p] >= 0
      old = ds.new2old[nodes[p][m]]
      np.testing.assert_allclose(x[p][m][:, 0], old.astype(np.float32))
      np.testing.assert_array_equal(y[p][m], old % 5)
    nb += 1
  assert nb == len(loader)


def test_host_local_put_guard(tmp_path):
  _write(tmp_path)
  ds = DistDataset.from_partition_dir(tmp_path, host_parts=[0, 1])
  loader = DistNeighborLoader(ds, [2], np.arange(N), batch_size=4,
                              shuffle=True, mesh=make_mesh(P), seed=0)
  # single process owns ALL 8 mesh positions but only loaded 2 shards:
  # the put must refuse, not silently mis-place
  with pytest.raises(ValueError, match='host_parts'):
    next(iter(loader))


def _write_rich(root, split_feats: bool = True):
  """Layout with every optional payload: provenance features
  (col 0 = old id + 1), labels, edge features encoding (eid, src,
  dst), and an offline cache plan."""
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  e = len(rows)
  feats = np.tile((np.arange(N, dtype=np.float32) + 1)[:, None], (1, 3))
  labels = (np.arange(N) % 5).astype(np.int32)
  efeat = np.stack([np.arange(e), rows, cols], 1).astype(np.float32)
  RandomPartitioner(root, P, N, (rows, cols),
                    node_feat=feats if split_feats else None,
                    node_label=labels, edge_feat=efeat,
                    cache_ratio=0.1, seed=0).partition()
  return rows, cols, efeat


def test_host_local_tiered_equals_full(tmp_path):
  """Tiered host-local load (the IGBH-large enabler):
  hot shards, hot counts, cache plan, and edge features must all
  match a single-controller load of the same (layout, split_ratio);
  the cold stack must hold exactly this host's partitions' rows of
  the full cold table."""
  _write_rich(tmp_path)
  full = DistDataset.from_partition_dir(tmp_path, split_ratio=0.4)
  local = DistDataset.from_partition_dir(tmp_path, split_ratio=0.4,
                                         host_parts=np.arange(P))
  np.testing.assert_array_equal(full.old2new, local.old2new)
  nf_f, nf_l = full.node_features, local.node_features
  np.testing.assert_array_equal(nf_f.hot_counts, nf_l.hot_counts)
  np.testing.assert_array_equal(nf_f.shards, nf_l.shards)
  # cache plan honored (was: ignored with a warning in v1)
  assert nf_l.cache_ids is not None and nf_l.has_cache
  np.testing.assert_array_equal(nf_f.cache_ids, nf_l.cache_ids)
  np.testing.assert_array_equal(nf_f.cache_rows, nf_l.cache_rows)
  # cold provenance: local stack row r of partition p == global cold
  # table row bounds[p] + r
  assert nf_l.cold_local is not None and nf_l.cold_host is None
  bounds = full.graph.bounds
  counts = np.diff(bounds)
  for j, p in enumerate(range(P)):
    np.testing.assert_array_equal(
        nf_l.cold_local[j, :counts[p]],
        nf_f.cold_host[bounds[p]:bounds[p + 1]])
  # edge features (was: NotImplementedError in v1)
  assert local.edge_features is not None
  np.testing.assert_array_equal(full.edge_features.shards,
                                local.edge_features.shards)


def test_host_local_tiered_loader_epoch(tmp_path):
  """The composed path end-to-end on the virtual mesh: tiered store +
  cache plan + edge features + host-local layout, one loader epoch
  with per-row provenance (cold rows included — a failed owner-served
  overlay would leave zeros where col 0 must read old id + 1)."""
  rows, cols, _ = _write_rich(tmp_path)
  ds = DistDataset.from_partition_dir(tmp_path, split_ratio=0.3,
                                      host_parts=np.arange(P))
  loader = DistNeighborLoader(ds, [2, 2], np.arange(N), batch_size=4,
                              shuffle=True, with_edge=True,
                              mesh=make_mesh(P), seed=0)
  nb = 0
  for b in loader:
    nodes = np.asarray(b.node)
    x = np.asarray(b.x)
    y = np.asarray(b.y)
    ea = np.asarray(b.edge_attr)
    eid = np.asarray(b.edge)
    em = np.asarray(b.edge_mask)
    for p in range(P):
      m = nodes[p] >= 0
      old = ds.new2old[nodes[p][m]]
      np.testing.assert_allclose(x[p][m][:, 0],
                                 old.astype(np.float32) + 1)
      np.testing.assert_array_equal(y[p][m], old % 5)
      me = em[p]
      np.testing.assert_allclose(ea[p][me][:, 0], eid[p][me])
      np.testing.assert_allclose(ea[p][me][:, 1], rows[eid[p][me]])
      np.testing.assert_allclose(ea[p][me][:, 2], cols[eid[p][me]])
    nb += 1
  assert nb == len(loader)
  st = loader.sampler.exchange_stats(tick_metrics=False)
  assert st['dist.feature.cold_misses'] > 0
  assert 0.0 <= st['dist.feature.cache_hit_rate'] <= 1.0
  assert 0.0 < st['dist.feature.hot_hit_rate'] < 1.0


def test_host_local_by_dst_layout(tmp_path):
  """by_dst layouts re-bucket by src owner under host-local loading
  (was: NotImplementedError in v1) and must reproduce the
  single-controller CSR per-row edge sets."""
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  feats = np.tile((np.arange(N, dtype=np.float32) + 1)[:, None], (1, 2))
  RandomPartitioner(tmp_path, P, N, (rows, cols), node_feat=feats,
                    seed=0, edge_assign='by_dst').partition()
  full = DistDataset.from_partition_dir(tmp_path)
  local = DistDataset.from_partition_dir(tmp_path,
                                         host_parts=np.arange(P))
  np.testing.assert_array_equal(full.graph.bounds, local.graph.bounds)
  np.testing.assert_array_equal(full.graph.indptr, local.graph.indptr)
  for p in range(P):
    for r in range(full.graph.max_local_nodes):
      a = np.sort(full.graph.indices[p][full.graph.indptr[p][r]:
                                        full.graph.indptr[p][r + 1]])
      b = np.sort(local.graph.indices[p][local.graph.indptr[p][r]:
                                         local.graph.indptr[p][r + 1]])
      np.testing.assert_array_equal(a, b)
  np.testing.assert_array_equal(full.node_features.shards,
                                local.node_features.shards)


def test_hetero_host_local_equals_full(tmp_path):
  """Hetero host-local loading (host_parts = all) must match the full
  load's id spaces and serve provenance-correct batches."""
  from graphlearn_tpu.parallel import (DistHeteroDataset,
                                       DistHeteroNeighborLoader)
  U, I = 'u', 'i'
  ET = (U, 'to', I)
  REV = (I, 'rev_to', U)
  nu, ni = 48, 24
  urow = np.repeat(np.arange(nu), 2)
  icol = np.stack([np.arange(nu) % ni, (np.arange(nu) + 1) % ni],
                  1).reshape(-1)
  ufeat = np.tile(np.arange(nu, dtype=np.float32)[:, None], (1, 3))
  ifeat = np.tile(np.arange(ni, dtype=np.float32)[:, None], (1, 3))
  RandomPartitioner(tmp_path, P,
                    num_nodes={U: nu, I: ni},
                    edge_index={ET: (urow, icol), REV: (icol, urow)},
                    node_feat={U: ufeat, I: ifeat},
                    node_label={U: (np.arange(nu) % 4).astype(np.int32)},
                    seed=0).partition()
  full = DistHeteroDataset.from_partition_dir(tmp_path)
  local = DistHeteroDataset.from_partition_dir(
      tmp_path, host_parts=np.arange(P))
  for nt in (U, I):
    np.testing.assert_array_equal(full.bounds[nt], local.bounds[nt])
    np.testing.assert_array_equal(full.old2new[nt], local.old2new[nt])
    np.testing.assert_array_equal(full.node_features[nt].shards,
                                  local.node_features[nt].shards)
  np.testing.assert_array_equal(np.asarray(full.node_labels[U]),
                                local.node_labels[U])
  loader = DistHeteroNeighborLoader(local, [2, 2], (U, np.arange(nu)),
                                    batch_size=2, shuffle=True,
                                    mesh=make_mesh(P), seed=0)
  nb = 0
  for b in loader:
    for nt in (U, I):
      nodes = np.asarray(b.node_dict[nt])
      x = np.asarray(b.x_dict[nt])
      for p in range(P):
        m = nodes[p] >= 0
        np.testing.assert_allclose(
            x[p][m][:, 0],
            local.new2old[nt][nodes[p][m]].astype(np.float32))
    nb += 1
  assert nb == len(loader)


def test_hetero_host_local_csr_and_guard(tmp_path):
  """Hetero arm of the homo checks: per-etype CSR equality against the
  full load, and the sampler's put refusing a host_parts/mesh
  mismatch."""
  from graphlearn_tpu.parallel import (DistHeteroDataset,
                                       DistHeteroNeighborLoader)
  U, I = 'u', 'i'
  ET = (U, 'to', I)
  REV = (I, 'rev_to', U)
  nu, ni = 48, 24
  urow = np.repeat(np.arange(nu), 2)
  icol = np.stack([np.arange(nu) % ni, (np.arange(nu) + 1) % ni],
                  1).reshape(-1)
  RandomPartitioner(tmp_path, P,
                    num_nodes={U: nu, I: ni},
                    edge_index={ET: (urow, icol), REV: (icol, urow)},
                    node_feat={U: np.ones((nu, 2), np.float32)},
                    seed=0).partition()
  full = DistHeteroDataset.from_partition_dir(tmp_path)
  local = DistHeteroDataset.from_partition_dir(
      tmp_path, host_parts=np.arange(P))
  for et in (ET, REV):
    gf, gl = full.graphs[et], local.graphs[et]
    np.testing.assert_array_equal(gf.indptr, gl.indptr)
    for p in range(P):
      for r in range(gf.max_local_nodes):
        a = np.sort(gf.indices[p][gf.indptr[p][r]:gf.indptr[p][r + 1]])
        b = np.sort(gl.indices[p][gl.indptr[p][r]:gl.indptr[p][r + 1]])
        np.testing.assert_array_equal(a, b)
  bad = DistHeteroDataset.from_partition_dir(tmp_path,
                                             host_parts=[0, 1])
  loader = DistHeteroNeighborLoader(bad, [2], (U, np.arange(nu)),
                                    batch_size=2, shuffle=True,
                                    mesh=make_mesh(P), seed=0)
  with pytest.raises(ValueError, match='host_parts'):
    next(iter(loader))


def test_hetero_host_local_tiered_composition(tmp_path):
  """Hetero arm of the composed host-local path: per-type tiered
  stores (owner-served cold), per-etype edge features — host-local
  load must match single-controller and serve provenance-correct
  batches end to end."""
  from graphlearn_tpu.parallel import (DistHeteroDataset,
                                       DistHeteroNeighborLoader)
  U, I = 'u', 'i'
  ET = (U, 'to', I)
  REV = (I, 'rev_to', U)
  nu, ni = 48, 24
  urow = np.repeat(np.arange(nu), 2)
  icol = np.stack([np.arange(nu) % ni, (np.arange(nu) + 1) % ni],
                  1).reshape(-1)
  ufeat = np.tile((np.arange(nu, dtype=np.float32) + 1)[:, None],
                  (1, 3))
  ifeat = np.tile((np.arange(ni, dtype=np.float32) + 1)[:, None],
                  (1, 3))
  ef_fwd = np.stack([np.arange(len(urow)), urow, icol],
                    1).astype(np.float32)
  ef_rev = np.stack([np.arange(len(urow)), icol, urow],
                    1).astype(np.float32)
  RandomPartitioner(tmp_path, P,
                    num_nodes={U: nu, I: ni},
                    edge_index={ET: (urow, icol), REV: (icol, urow)},
                    node_feat={U: ufeat, I: ifeat},
                    node_label={U: (np.arange(nu) % 4).astype(np.int32)},
                    edge_feat={ET: ef_fwd, REV: ef_rev},
                    seed=0).partition()
  full = DistHeteroDataset.from_partition_dir(tmp_path, split_ratio=0.4)
  local = DistHeteroDataset.from_partition_dir(
      tmp_path, split_ratio=0.4, host_parts=np.arange(P))
  for nt in (U, I):
    np.testing.assert_array_equal(full.old2new[nt], local.old2new[nt])
    nf_f, nf_l = full.node_features[nt], local.node_features[nt]
    np.testing.assert_array_equal(nf_f.hot_counts, nf_l.hot_counts)
    np.testing.assert_array_equal(nf_f.shards, nf_l.shards)
    assert nf_l.cold_local is not None and nf_l.cold_host is None
    counts = np.diff(full.bounds[nt])
    for j, p in enumerate(range(P)):
      np.testing.assert_array_equal(
          nf_l.cold_local[j, :counts[p]],
          nf_f.cold_host[full.bounds[nt][p]:full.bounds[nt][p + 1]])
  for et in (ET, REV):
    np.testing.assert_array_equal(full.edge_features[et].shards,
                                  local.edge_features[et].shards)
  loader = DistHeteroNeighborLoader(local, [2, 2], (U, np.arange(nu)),
                                    batch_size=2, shuffle=True,
                                    with_edge=True, mesh=make_mesh(P),
                                    seed=0)
  nb = 0
  for b in loader:
    for nt in (U, I):
      nodes = np.asarray(b.node_dict[nt])
      x = np.asarray(b.x_dict[nt])
      for p in range(P):
        m = nodes[p] >= 0
        np.testing.assert_allclose(
            x[p][m][:, 0],
            local.new2old[nt][nodes[p][m]].astype(np.float32) + 1)
    for et, ea in b.edge_attr_dict.items():
      ea = np.asarray(ea)
      eid = np.asarray(b.metadata['edge_dict'][et])
      em = np.asarray(b.edge_mask_dict[et])
      for p in range(P):
        np.testing.assert_allclose(ea[p][em[p]][:, 0], eid[p][em[p]])
    nb += 1
  assert nb == len(loader)
  st = loader.sampler.exchange_stats(tick_metrics=False)
  assert st['dist.feature.cold_misses'] > 0


def test_multihost_global_max():
  from graphlearn_tpu.parallel import multihost
  mesh = make_mesh(P)
  assert multihost.global_max(7, mesh) == 7
