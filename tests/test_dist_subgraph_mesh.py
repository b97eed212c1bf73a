"""Mesh-engine induced subgraph (SEAL on the ICI path).

The reference samples induced subgraphs ACROSS
partitions (`distributed/dist_neighbor_sampler.py:456-516`); round 1
only had the host-runtime arm.  The mesh step = collective closure +
full-window hop + local membership/relabel; exactness is asserted
against a brute-force edge filter, per device.
"""
import numpy as np
import pytest

jax = pytest.importorskip('jax')

from graphlearn_tpu.parallel import (DistDataset, DistSubGraphLoader,
                                     make_mesh)

N = 48


def _graph():
  rng = np.random.default_rng(7)
  rows = np.concatenate([np.arange(N), np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N,
                         (np.arange(N) + 5) % N,
                         rng.integers(0, N, N)])
  return rows, cols


def test_mesh_subgraph_matches_bruteforce():
  rows, cols = _graph()
  edge_set = set(zip(rows.tolist(), cols.tolist()))
  feats = np.tile(np.arange(N, dtype=np.float32)[:, None], (1, 3))
  ds = DistDataset.from_full_graph(8, rows, cols, node_feat=feats,
                                   num_nodes=N)
  loader = DistSubGraphLoader(ds, [3, 3], np.arange(N), batch_size=2,
                              shuffle=True, mesh=make_mesh(8),
                              with_edge=True, seed=0)
  new2old = ds.new2old
  batches = 0
  for batch in loader:
    node = np.asarray(batch.node)
    nm = np.asarray(batch.node_mask)
    ei = np.asarray(batch.edge_index)
    em = np.asarray(batch.edge_mask)
    eid = np.asarray(batch.edge)
    x = np.asarray(batch.x)
    for p in range(8):
      kept_old = set(new2old[node[p][nm[p]]].tolist())
      got = set()
      for i in np.nonzero(em[p])[0]:
        u = int(new2old[node[p, ei[p, 0, i]]])
        v = int(new2old[node[p, ei[p, 1, i]]])
        got.add((u, v))
        # eid provenance: the emitted global edge id maps back to the
        # original COO slot for this (u, v)
        e = int(eid[p, i])
        assert rows[e] == u and cols[e] == v
      expect = {(u, v) for u, v in edge_set
                if u in kept_old and v in kept_old}
      assert got == expect, (p, got ^ expect)
      # features present for every kept node, encoding its id
      np.testing.assert_allclose(x[p][nm[p]][:, 0],
                                 new2old[node[p][nm[p]]])
    # mapping locates the seeds (the SEAL contract)
    mapping = np.asarray(batch.metadata['mapping'])
    seeds = np.asarray(batch.batch)
    for p in range(8):
      for j, s in enumerate(seeds[p]):
        if s >= 0:
          assert node[p, mapping[p, j]] == s
    batches += 1
  assert batches == len(loader)


def test_mesh_subgraph_truncated_window_counts_drops():
  """max_degree below the true max truncates windows — results are a
  subset of the true induced edges, never wrong edges."""
  rows, cols = _graph()
  edge_set = set(zip(rows.tolist(), cols.tolist()))
  ds = DistDataset.from_full_graph(8, rows, cols, num_nodes=N)
  loader = DistSubGraphLoader(ds, [3], np.arange(N), batch_size=2,
                              mesh=make_mesh(8), max_degree=2,
                              collect_features=False, seed=1)
  new2old = ds.new2old
  batch = next(iter(loader))
  node = np.asarray(batch.node)
  ei = np.asarray(batch.edge_index)
  em = np.asarray(batch.edge_mask)
  for p in range(8):
    for i in np.nonzero(em[p])[0]:
      u = int(new2old[node[p, ei[p, 0, i]]])
      v = int(new2old[node[p, ei[p, 1, i]]])
      assert (u, v) in edge_set


@pytest.mark.slow
def test_mesh_subgraph_hop_chunk_exact():
  """Chunked full-window hops (the SEAL-at-scale bound, hop_chunk)
  must produce the SAME subgraphs as one node_cap-wide exchange — the
  window is exact either way, only the exchange width changes."""
  rows, cols = _graph()
  feats = np.tile(np.arange(N, dtype=np.float32)[:, None], (1, 3))
  ds = DistDataset.from_full_graph(8, rows, cols, node_feat=feats,
                                   num_nodes=N)
  results = []
  for chunk in (None, 8):
    loader = DistSubGraphLoader(ds, [3, 3], np.arange(16), batch_size=2,
                                shuffle=False, mesh=make_mesh(8),
                                with_edge=True, seed=0, hop_chunk=chunk)
    edges = []
    for batch in loader:
      node = np.asarray(batch.node)
      ei = np.asarray(batch.edge_index)
      em = np.asarray(batch.edge_mask)
      for p in range(8):
        es = {(int(ds.new2old[node[p, ei[p, 0, i]]]),
               int(ds.new2old[node[p, ei[p, 1, i]]]))
              for i in np.nonzero(em[p])[0]}
        edges.append(es)
    results.append(edges)
  assert results[0] == results[1]


def test_hop_chunk_auto_resolution():
  """'auto' keeps one wide exchange below the window budget and
  bounds the chunk above it."""
  from graphlearn_tpu.parallel.dist_sampler import (
      SUBGRAPH_WINDOW_BUDGET, resolve_hop_chunk)
  assert resolve_hop_chunk(None, 10**9, 64) is None
  assert resolve_hop_chunk(512, 10**9, 64) == 512
  assert resolve_hop_chunk('auto', 1000, 64) is None
  big_cap = SUBGRAPH_WINDOW_BUDGET // 64 + 1000
  chunk = resolve_hop_chunk('auto', big_cap, 64)
  assert chunk is not None and chunk * 64 <= SUBGRAPH_WINDOW_BUDGET
  with pytest.raises(ValueError, match='hop_chunk'):
    resolve_hop_chunk('bogus', 10, 10)


def test_hop_chunk_auto_respects_budget_any_degree():
  from graphlearn_tpu.parallel.dist_sampler import (
      MIN_EXCHANGE_CAP, SUBGRAPH_WINDOW_BUDGET, resolve_hop_chunk)
  for md in (7, 64, 1000, 4097):
    chunk = resolve_hop_chunk('auto', 10**9, md)
    assert chunk is not None
    assert (chunk * md <= SUBGRAPH_WINDOW_BUDGET
            or chunk == MIN_EXCHANGE_CAP)
