"""Distributed edge-feature collection — both engines + offline layout.

The reference serves edge features through the
same distributed fan-out as node features
(`distributed/dist_feature.py:39-48,122-269`, collation at
`dist_neighbor_sampler.py:600-673`, separate ``edge_feat_pb`` at
`dist_dataset.py:183-193`).  Here: the mesh engine gathers rows by
global eid through `dist_gather_multi` against even range-sharded
tables; the host runtime collates ``efeats`` in the producers.
Provenance trick: edge-feature rows ENCODE the edge id + endpoints, so
every gathered row is checkable arithmetically.
"""
import numpy as np
import pytest

jax = pytest.importorskip('jax')

from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                     make_mesh)

N = 64


def _ring():
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  e = len(rows)
  # row i encodes (eid, src, dst) — exact provenance
  efeat = np.stack([np.arange(e), rows, cols], 1).astype(np.float32)
  return rows, cols, efeat


def _check_batch_edge_attr(ea, eid, em, rows, cols, stacked=True):
  ps = range(ea.shape[0]) if stacked else [None]
  for p in ps:
    e, i, m = (ea[p], eid[p], em[p]) if stacked else (ea, eid, em)
    assert m.any()
    np.testing.assert_allclose(e[m][:, 0], i[m])
    np.testing.assert_allclose(e[m][:, 1], rows[i[m]])
    np.testing.assert_allclose(e[m][:, 2], cols[i[m]])
    assert (e[~m] == 0).all()


def test_mesh_node_loader_edge_features():
  rows, cols, efeat = _ring()
  feats = np.tile(np.arange(N, dtype=np.float32)[:, None], (1, 4))
  ds = DistDataset.from_full_graph(8, rows, cols, node_feat=feats,
                                   num_nodes=N, edge_feat=efeat)
  loader = DistNeighborLoader(ds, [2, 2], np.arange(N), batch_size=4,
                              shuffle=True, mesh=make_mesh(8),
                              with_edge=True, seed=0)
  n_checked = 0
  for batch in loader:
    _check_batch_edge_attr(np.asarray(batch.edge_attr),
                           np.asarray(batch.edge),
                           np.asarray(batch.edge_mask), rows, cols)
    n_checked += 1
  assert n_checked == len(loader)


def test_mesh_link_loader_edge_features():
  from graphlearn_tpu.parallel import DistLinkNeighborLoader
  rows, cols, efeat = _ring()
  ds = DistDataset.from_full_graph(8, rows, cols, num_nodes=N,
                                   edge_feat=efeat)
  loader = DistLinkNeighborLoader(
      ds, [2], (rows[:32], cols[:32]), neg_sampling='binary',
      batch_size=4, shuffle=True, mesh=make_mesh(8), with_edge=True,
      seed=1)
  batch = next(iter(loader))
  _check_batch_edge_attr(np.asarray(batch.edge_attr),
                         np.asarray(batch.edge),
                         np.asarray(batch.edge_mask), rows, cols)


def test_mesh_hetero_edge_features():
  """Per-etype gathered rows must encode (eid, src, dst) for every
  valid sampled edge of that type, on both sampled edge types."""
  from graphlearn_tpu.parallel import DistHeteroNeighborLoader
  from graphlearn_tpu.parallel.dist_hetero import DistHeteroDataset
  from graphlearn_tpu.typing import reverse_edge_type
  rng = np.random.default_rng(0)
  nu, ni = 24, 16
  et1, et2 = ('u', 'to', 'i'), ('i', 'by', 'u')
  r1 = rng.integers(0, nu, 96)
  c1 = rng.integers(0, ni, 96)
  r2 = rng.integers(0, ni, 80)
  c2 = rng.integers(0, nu, 80)
  ef1 = np.stack([np.arange(96), r1, c1], 1).astype(np.float32)
  ef2 = np.stack([np.arange(80), r2, c2], 1).astype(np.float32)
  ds = DistHeteroDataset.from_full_graph(
      8, {et1: (r1, c1), et2: (r2, c2)},
      num_nodes_dict={'u': nu, 'i': ni},
      edge_feat_dict={et1: ef1, et2: ef2})
  loader = DistHeteroNeighborLoader(
      ds, [2, 2], ('u', np.arange(nu)), batch_size=3, shuffle=True,
      mesh=make_mesh(8), with_edge=True, seed=2)
  ends = {reverse_edge_type(et1): (r1, c1),
          reverse_edge_type(et2): (r2, c2)}
  seen = set()
  for batch in loader:
    for rev, (rr, cc) in ends.items():
      if rev not in batch.edge_attr_dict:
        continue
      ea = np.asarray(batch.edge_attr_dict[rev])
      eid = np.asarray(batch.metadata['edge_dict'][rev])
      em = np.asarray(batch.edge_mask_dict[rev])
      if em.any():
        seen.add(rev)
      _check_batch_edge_attr(ea, eid, em, rr, cc)
  assert seen == set(ends)


def test_mesh_hetero_edge_features_unselected_etype():
  """Edge features for an etype the fanout dict EXCLUDES must be
  ignored, not crash the step (regression: the gather loop indexed
  eids_acc by every dataset efeat etype)."""
  from graphlearn_tpu.parallel import DistHeteroNeighborLoader
  from graphlearn_tpu.parallel.dist_hetero import DistHeteroDataset
  from graphlearn_tpu.typing import reverse_edge_type
  rng = np.random.default_rng(3)
  nu, ni = 24, 16
  et1, et2 = ('u', 'r1', 'i'), ('u', 'r2', 'i')
  r1 = rng.integers(0, nu, 64)
  c1 = rng.integers(0, ni, 64)
  r2 = rng.integers(0, nu, 48)
  c2 = rng.integers(0, ni, 48)
  ds = DistHeteroDataset.from_full_graph(
      8, {et1: (r1, c1), et2: (r2, c2)},
      num_nodes_dict={'u': nu, 'i': ni},
      edge_feat_dict={et1: np.stack([np.arange(64), r1, c1], 1)
                      .astype(np.float32),
                      et2: np.zeros((48, 2), np.float32)})
  loader = DistHeteroNeighborLoader(
      ds, {et1: [2]}, ('u', np.arange(nu)), batch_size=3,
      mesh=make_mesh(8), with_edge=True, seed=4)
  batch = next(iter(loader))
  rev1 = reverse_edge_type(et1)
  assert reverse_edge_type(et2) not in batch.edge_attr_dict
  ea = np.asarray(batch.edge_attr_dict[rev1])
  eid = np.asarray(batch.metadata['edge_dict'][rev1])
  em = np.asarray(batch.edge_mask_dict[rev1])
  _check_batch_edge_attr(ea, eid, em, r1, c1)


def test_partition_roundtrip_edge_features(tmp_path):
  """Offline layout carries edge features; DistDataset + host dataset
  reload them aligned to the ORIGINAL global edge ids."""
  from graphlearn_tpu.partition import RandomPartitioner, load_partition
  from graphlearn_tpu.distributed import HostDataset
  rows, cols, efeat = _ring()
  part = RandomPartitioner(tmp_path, 4, N, (rows, cols),
                           edge_feat=efeat, seed=0)
  part.partition()
  p0 = load_partition(tmp_path, 0)
  assert p0['edge_feat'] is not None
  np.testing.assert_allclose(p0['edge_feat'].feats[:, 0],
                             p0['edge_feat'].ids)
  ds = DistDataset.from_partition_dir(tmp_path)
  assert ds.edge_features is not None
  loader = DistNeighborLoader(ds, [2], np.arange(N), batch_size=4,
                              shuffle=True, mesh=make_mesh(4),
                              with_edge=True, seed=3)
  batch = next(iter(loader))
  _check_batch_edge_attr(np.asarray(batch.edge_attr),
                         np.asarray(batch.edge),
                         np.asarray(batch.edge_mask), rows, cols)
  hds = HostDataset.from_partition_dir(tmp_path, 0)
  assert hds.edge_features is not None
  assert hds.edge_features.shape[0] == len(rows)
  # rows owned by this partition carry their encoded eid; others zero
  owned = p0['edge_feat'].ids
  np.testing.assert_allclose(hds.edge_features[owned][:, 0], owned)


def test_host_runtime_edge_features():
  """Host producers collate efeats; collocated + mp modes, homo."""
  from graphlearn_tpu import native
  if not native.available():
    pytest.skip('native lib unavailable')
  from graphlearn_tpu.distributed import (DistNeighborLoader as HostLoader,
                                          HostDataset,
                                          MpDistSamplingWorkerOptions)
  rows, cols, efeat = _ring()
  ds = HostDataset.from_coo(rows, cols, N, edge_features=efeat)
  for opts in (None, MpDistSamplingWorkerOptions(num_workers=2)):
    loader = HostLoader(ds, [2, 2], np.arange(N), batch_size=8,
                        with_edge=True, to_device=False,
                        worker_options=opts)
    try:
      n = 0
      for batch in loader:
        _check_batch_edge_attr(np.asarray(batch.edge_attr),
                               np.asarray(batch.edge),
                               np.asarray(batch.edge_mask), rows, cols,
                               stacked=False)
        n += 1
      assert n == len(loader)
    finally:
      loader.shutdown()


def test_host_runtime_hetero_edge_features():
  from graphlearn_tpu import native
  if not native.available():
    pytest.skip('native lib unavailable')
  from graphlearn_tpu.distributed import (DistNeighborLoader as HostLoader,
                                          HostHeteroDataset)
  from graphlearn_tpu.typing import reverse_edge_type
  rng = np.random.default_rng(1)
  nu, ni = 24, 16
  et = ('u', 'to', 'i')
  r1 = rng.integers(0, nu, 96)
  c1 = rng.integers(0, ni, 96)
  ef1 = np.stack([np.arange(96), r1, c1], 1).astype(np.float32)
  ds = HostHeteroDataset.from_coo({et: (r1, c1)},
                                  num_nodes_dict={'u': nu, 'i': ni},
                                  edge_features={et: ef1})
  loader = HostLoader(ds, [2], ('u', np.arange(nu)), batch_size=6,
                      with_edge=True, to_device=False)
  rev = reverse_edge_type(et)
  n = 0
  for batch in loader:
    ea = np.asarray(batch.edge_attr_dict[rev])
    eid = np.asarray(batch.metadata['edge_dict'][rev])
    em = np.asarray(batch.edge_mask_dict[rev])
    _check_batch_edge_attr(ea, eid, em, r1, c1, stacked=False)
    n += 1
  assert n == len(loader)
