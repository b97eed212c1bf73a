"""NeighborSampler tests.

Mirrors reference `test/python/test_neighbor_sampler.py` plus the
deterministic circular-graph provenance checks of
`test/python/dist_test_utils.py:26-50` (node v's out-neighbors are
{v+1, v+2} mod N, so every sampled edge is arithmetically checkable).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from graphlearn_tpu.data import CSRTopo, Graph
from graphlearn_tpu.sampler import (EdgeSamplerInput, NegativeSampling,
                                    NeighborSampler, NodeSamplerInput,
                                    RandomNegativeSampler)


def circular_graph(n=40):
  rows = np.repeat(np.arange(n), 2)
  cols = np.stack([(np.arange(n) + 1) % n, (np.arange(n) + 2) % n],
                  axis=1).reshape(-1)
  return CSRTopo((rows, cols), layout='COO', num_nodes=n)


@pytest.fixture(scope='module')
def graph():
  return Graph(circular_graph(40), mode='device')


def _check_edges(out, n=40):
  node = np.asarray(out.node)
  row = np.asarray(out.row)
  col = np.asarray(out.col)
  mask = np.asarray(out.edge_mask)
  assert mask.sum() > 0
  for r, c in zip(row[mask], col[mask]):
    src, dst = node[c], node[r]
    assert dst in ((src + 1) % n, (src + 2) % n)


def test_sample_from_nodes_basic(graph):
  sampler = NeighborSampler(graph, [2, 2], seed=7)
  seeds = np.array([0, 5, 10, 15], np.int32)
  out = sampler.sample_from_nodes(NodeSamplerInput(node=seeds))
  node = np.asarray(out.node)
  # seeds occupy the first local slots in order
  np.testing.assert_array_equal(node[:4], seeds)
  assert int(out.node_count) <= node.shape[0]
  # every valid node id is a real node, padding is INVALID
  cnt = int(out.node_count)
  assert (node[:cnt] >= 0).all() and (node[:cnt] < 40).all()
  assert (node[cnt:] == -1).all()
  _check_edges(out)
  # per-hop accounting
  nsn = np.asarray(out.num_sampled_nodes)
  assert nsn.sum() == cnt
  assert nsn[0] == 4


def test_full_fanout_exact(graph):
  # fanout >= degree: every neighbor must appear exactly once.
  sampler = NeighborSampler(graph, [2], seed=0, with_edge=True)
  seeds = np.array([3, 9], np.int32)
  out = sampler.sample_from_nodes(NodeSamplerInput(node=seeds))
  node = np.asarray(out.node)
  row, col = np.asarray(out.row), np.asarray(out.col)
  mask = np.asarray(out.edge_mask)
  got = {(node[c], node[r]) for r, c in zip(row[mask], col[mask])}
  want = {(3, 4), (3, 5), (9, 10), (9, 11)}
  assert got == want
  # edge ids are the global CSR positions
  eids = np.asarray(out.edge)[mask]
  assert set(eids.tolist()) == {6, 7, 18, 19}


def test_duplicate_seeds_deduped(graph):
  sampler = NeighborSampler(graph, [2], seed=1)
  seeds = np.array([7, 7, 8, 7], np.int32)
  out = sampler.sample_from_nodes(NodeSamplerInput(node=seeds))
  node = np.asarray(out.node)
  assert node[0] == 7 and node[1] == 8
  cnt = int(out.node_count)
  vals = node[:cnt]
  assert len(set(vals.tolist())) == cnt  # all unique


def test_determinism(graph):
  s1 = NeighborSampler(graph, [2, 2], seed=42)
  s2 = NeighborSampler(graph, [2, 2], seed=42)
  seeds = np.arange(8, dtype=np.int32)
  o1 = s1.sample_from_nodes(NodeSamplerInput(node=seeds))
  o2 = s2.sample_from_nodes(NodeSamplerInput(node=seeds))
  np.testing.assert_array_equal(np.asarray(o1.node), np.asarray(o2.node))
  np.testing.assert_array_equal(np.asarray(o1.row), np.asarray(o2.row))


def test_padded_seeds(graph):
  sampler = NeighborSampler(graph, [2], seed=3)
  seeds = np.array([1, 2, -1, -1], np.int32)  # INVALID-padded tail
  out = sampler.sample_from_nodes(NodeSamplerInput(node=seeds))
  node = np.asarray(out.node)
  assert node[0] == 1 and node[1] == 2
  _check_edges(out)


def test_sample_from_edges_binary(graph):
  sampler = NeighborSampler(graph, [2], seed=11, with_neg=True)
  row = np.array([0, 1, 2, 3], np.int32)
  col = np.array([1, 2, 3, 4], np.int32)
  out = sampler.sample_from_edges(
      EdgeSamplerInput(row=row, col=col),
      neg_sampling=NegativeSampling('binary', 1))
  eli = np.asarray(out.metadata['edge_label_index'])
  lab = np.asarray(out.metadata['edge_label'])
  assert eli.shape == (2, 8)
  np.testing.assert_array_equal(lab, [1, 1, 1, 1, 0, 0, 0, 0])
  node = np.asarray(out.node)
  # positive pairs resolve back to the original global edges
  for i in range(4):
    assert node[eli[0, i]] == row[i]
    assert node[eli[1, i]] == col[i]
  # negatives are non-edges (strict, modulo padding): dst not in {src+1, src+2}
  neg_src = node[eli[0, 4:]]
  neg_dst = node[eli[1, 4:]]
  for s, d in zip(neg_src, neg_dst):
    assert d not in ((s + 1) % 40, (s + 2) % 40)


def test_sample_from_edges_triplet(graph):
  sampler = NeighborSampler(graph, [2], seed=13, with_neg=True)
  row = np.array([0, 10], np.int32)
  col = np.array([1, 11], np.int32)
  out = sampler.sample_from_edges(
      EdgeSamplerInput(row=row, col=col),
      neg_sampling=NegativeSampling('triplet', 2))
  md = out.metadata
  node = np.asarray(out.node)
  assert np.asarray(md['src_index']).shape == (2,)
  assert np.asarray(md['dst_pos_index']).shape == (2,)
  assert np.asarray(md['dst_neg_index']).shape == (2, 2)
  np.testing.assert_array_equal(node[np.asarray(md['src_index'])], row)
  np.testing.assert_array_equal(node[np.asarray(md['dst_pos_index'])], col)
  neg = node[np.asarray(md['dst_neg_index'])]
  for i, s in enumerate(row):
    for d in neg[i]:
      assert d not in ((s + 1) % 40, (s + 2) % 40)


def test_subgraph(graph):
  sampler = NeighborSampler(graph, [2], seed=17)
  seeds = np.array([0, 1, 2], np.int32)
  out = sampler.subgraph(NodeSamplerInput(node=seeds))
  node = np.asarray(out.node)
  cnt = int(out.node_count)
  nodeset = set(node[:cnt].tolist())
  row, col, mask = (np.asarray(out.row), np.asarray(out.col),
                    np.asarray(out.edge_mask))
  # subgraph outputs are in natural src->dst orientation (unlike the
  # transposed hop edges), matching the reference SubGraphOp.
  got = {(node[r], node[c]) for r, c in zip(row[mask], col[mask])}
  # expected: all circular edges among the collected closure
  want = {(u, v) for u in nodeset for v in ((u + 1) % 40, (u + 2) % 40)
          if v in nodeset}
  assert got == want
  # mapping points seeds at their local slots
  np.testing.assert_array_equal(np.asarray(out.metadata['mapping'])[:3],
                                [0, 1, 2])


def test_negative_sampler_class(graph):
  ns = RandomNegativeSampler(graph, seed=5)
  ei = np.asarray(ns.sample(16))
  assert ei.shape == (2, 16)
  for s, d in zip(ei[0], ei[1]):
    assert d not in ((s + 1) % 40, (s + 2) % 40)


def test_sample_prob(graph):
  sampler = NeighborSampler(graph, [2, 2], seed=0)
  prob = np.asarray(sampler.sample_prob(np.array([0], np.int32), 40))
  assert prob.shape == (40,)
  assert prob[0] == 1.0
  # nodes 1..4 are reachable within 2 hops of node 0; far nodes are not
  assert (prob[1:5] > 0).all()
  assert (prob[10:30] == 0).all()


def _skewed_graph(n, seed=0):
  """Half of all edges point at eight hubs, so dedup packs most of a
  hop's draws into a few slots (later hops land in earlier prefixes)."""
  rng = np.random.default_rng(seed)
  e = n * 8
  rows = rng.integers(0, n, e)
  cols = rng.integers(0, n, e)
  cols[:e // 2] = rng.integers(0, 8, e // 2)
  return Graph(CSRTopo((rows, cols), layout='COO', num_nodes=n),
               mode='device')


@pytest.mark.parametrize('n,fanouts,batch,clamps', [
    (400, [4, 4], 16, False),
    (400, [5, 4, 3], 32, True),     # the last hop clamps: 32 + 400
    (24, [5, 4, 3], 16, True),      # every hop clamps: 16 + 24
])
def test_hop_prefix_layout(n, fanouts, batch, clamps):
  """The static layout `models.BasicGNN` trims by: hop ``h``'s nodes
  end inside ``[0, C_h)``, its edges are block ``h`` with targets below
  ``C_h`` and sources below ``C_{h+1}``, and every in-edge of a node
  sits in the block of the hop that discovered it."""
  from graphlearn_tpu.sampler.neighbor_sampler import hop_capacities
  sampler = NeighborSampler(_skewed_graph(n), fanouts, seed=3)
  rng = np.random.default_rng(1)
  clamped = packed = False
  for trial in range(4):
    seeds = rng.choice(n, batch, replace=False).astype(np.int32)
    if trial == 3:
      seeds[batch // 2:] = -1           # a short last batch
    out = sampler.sample_from_nodes(NodeSamplerInput(node=seeds))
    node_caps, edge_caps = out.metadata['hop_capacities']
    assert (node_caps, edge_caps) == hop_capacities(
        batch, fanouts, sampler.node_capacity(batch))
    # the stated capacities are the arrays' shapes
    assert node_caps[-1] == out.node.shape[0]
    assert edge_caps[-1] == out.row.shape[0] == out.col.shape[0]
    assert len(node_caps) == len(fanouts) + 1
    assert len(edge_caps) == len(fanouts)
    clamped |= node_caps[-1] < batch + sum(
        batch * int(np.prod(fanouts[:i + 1])) for i in range(len(fanouts)))
    counts = np.cumsum(np.asarray(out.num_sampled_nodes))
    assert (counts <= np.asarray(node_caps)).all()
    packed |= bool((counts[1:-1] < np.asarray(node_caps[1:-1])).any())
    # the hop that discovered each local slot
    found_at = np.searchsorted(counts, np.arange(counts[-1]), 'right')
    row, col = np.asarray(out.row), np.asarray(out.col)
    mask = np.asarray(out.edge_mask)
    starts = (0,) + edge_caps[:-1]
    for h, (lo, hi) in enumerate(zip(starts, edge_caps)):
      r, c = row[lo:hi][mask[lo:hi]], col[lo:hi][mask[lo:hi]]
      assert (c < node_caps[h]).all() and (r < node_caps[h + 1]).all()
      # targets of block h are exactly the nodes found at hop h
      assert (found_at[c] == h).all()
  assert packed                         # the case the prefixes must survive
  assert clamped == clamps
