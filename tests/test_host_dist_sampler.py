"""Cross-server host runtime: 2 partition services on localhost, real
socket RPC, partition-encoded provenance.

The SURVEY §4 pattern: a deterministic synthetic 2-partition dataset
whose features encode node ids, every role a local process/thread, no
mocks — the real RPC + native-op stack runs.  Correctness is asserted
against the FULL graph: with fanout >= max degree the sampled
neighborhood equals the exact one, so a shard-fed sampler that failed
to fan out per hop would visibly under-sample.
"""
import numpy as np
import pytest

from graphlearn_tpu.distributed import (HostDataset,
                                        HostDistNeighborSampler,
                                        HostNeighborSampler,
                                        PartitionService, connect_peers)
from graphlearn_tpu.partition import RandomPartitioner

N = 40
E = 2 * N  # ring: v -> v+1, v -> v+2


def _write_partitions(root, num_parts=2, with_efeat=True):
  rows = np.concatenate([np.arange(N), np.arange(N)]).astype(np.int64)
  cols = np.concatenate([(np.arange(N) + 1) % N,
                         (np.arange(N) + 2) % N]).astype(np.int64)
  feats = np.arange(N, dtype=np.float32)[:, None] * np.ones(
      (1, 4), np.float32)                      # feat[v] == v
  labels = (np.arange(N) % 3).astype(np.int32)
  efeats = (np.arange(E, dtype=np.float32)[:, None] * np.ones(
      (1, 2), np.float32) if with_efeat else None)   # efeat[e] == e
  RandomPartitioner(root, num_parts, N, (rows, cols), node_feat=feats,
                    node_label=labels, edge_feat=efeats,
                    seed=0).partition()
  return rows, cols, feats, labels, efeats


@pytest.fixture
def deployment(tmp_path):
  """2 shards served on localhost + a sampler on each shard."""
  _write_partitions(tmp_path)
  shards = [HostDataset.from_partition_dir(tmp_path, i) for i in range(2)]
  services = [PartitionService(s, host='127.0.0.1') for s in shards]
  addrs = [('127.0.0.1', sv.port) for sv in services]
  yield shards, services, addrs
  for sv in services:
    sv.shutdown()


def test_guard_refuses_shard(deployment):
  shards, _, _ = deployment
  with pytest.raises(ValueError, match='partition shard'):
    HostNeighborSampler(shards[0], [2])


def test_cross_server_node_sampling_exact(deployment):
  """fanout >= degree: neighborhoods must equal the full-graph exact
  ones — impossible without per-hop remote fan-out (each shard owns
  only half the rows)."""
  shards, _, addrs = deployment
  for part in range(2):
    sampler = HostDistNeighborSampler(
        shards[part], [2, 2], connect_peers(addrs, part),
        with_edge=True, seed=7)
    seeds = np.arange(0, N, 5, dtype=np.int64)
    msg = sampler.sample_from_nodes(seeds)
    ids, rows, cols = msg['ids'], msg['rows'], msg['cols']
    # exact 2-hop closure of the ring: {s, s+1, s+2, s+3, s+4}
    expect = set()
    for s in seeds:
      expect.update(((s + d) % N) for d in range(5))
    assert set(ids.tolist()) == expect
    # every edge is a real ring edge (emitted transposed for PyG
    # message passing: graph edge is col -> row)
    d = (ids[rows] - ids[cols]) % N
    assert np.isin(d, [1, 2]).all()
    # both hops sampled everything: 2 edges per frontier node per hop
    hop1 = len(seeds) * 2
    assert len(rows) >= hop1
    # provenance: features/labels encode ORIGINAL node ids — remote
    # rows included (zero-filled shard features would fail here)
    np.testing.assert_allclose(msg['nfeats'][:, 0],
                               ids.astype(np.float32))
    np.testing.assert_array_equal(msg['nlabels'], ids % 3)
    # edge features encode global eids (collected on the owning server)
    np.testing.assert_allclose(msg['efeats'][:, 0],
                               msg['eids'].astype(np.float32))


def test_cross_server_feature_only_lookup(deployment):
  """Feature fan-out alone (seeds on one shard, features everywhere)."""
  shards, _, addrs = deployment
  sampler = HostDistNeighborSampler(shards[0], [2],
                                    connect_peers(addrs, 0), seed=1)
  feats = sampler._gather_node_features(np.arange(N, dtype=np.int64))
  np.testing.assert_allclose(feats[:, 0], np.arange(N, dtype=np.float32))
  labels = sampler._gather_node_labels(np.arange(N, dtype=np.int64))
  np.testing.assert_array_equal(labels, np.arange(N) % 3)


def test_cross_server_link_sampling(deployment):
  shards, _, addrs = deployment
  sampler = HostDistNeighborSampler(shards[0], [2],
                                    connect_peers(addrs, 0),
                                    with_edge=True, seed=3)
  src = np.arange(8, dtype=np.int64)
  dst = (src + 1) % N
  msg = sampler.sample_from_edges(src, dst, neg_mode='binary')
  ids = msg['ids']
  np.testing.assert_allclose(msg['nfeats'][:, 0], ids.astype(np.float32))
  eli = msg['#META.edge_label_index']
  elab = msg['#META.edge_label']
  emask = msg['#META.edge_label_mask']
  # positive pairs map to the seed endpoints
  np.testing.assert_array_equal(ids[eli[0, :8]], src)
  np.testing.assert_array_equal(ids[eli[1, :8]], dst)
  assert elab[:8].all() and emask[:8].all()
  # negatives marked ok must not be ring edges
  edge_set = {( int(a), int((a + 1) % N)) for a in range(N)} | \
             {( int(a), int((a + 2) % N)) for a in range(N)}
  neg_r = ids[eli[0, 8:]][emask[8:]]
  neg_c = ids[eli[1, 8:]][emask[8:]]
  for a, b in zip(neg_r.tolist(), neg_c.tolist()):
    assert (a, b) not in edge_set


def test_cross_server_subgraph(deployment):
  """Induced subgraph over the 2-hop closure: edges among closure
  nodes must match the brute-force count over the FULL ring."""
  shards, _, addrs = deployment
  sampler = HostDistNeighborSampler(shards[1], [2, 2],
                                    connect_peers(addrs, 1),
                                    with_edge=True, seed=5)
  seeds = np.array([0, 20], dtype=np.int64)
  msg = sampler.sample_subgraph(seeds)
  ids, rows, cols = msg['ids'], msg['rows'], msg['cols']
  closure = set(ids.tolist())
  # brute force: every ring edge with both ends in the closure
  expect = {(u, (u + d) % N) for u in range(N) for d in (1, 2)
            if u in closure and (u + d) % N in closure}
  got = {(int(ids[r]), int(ids[c])) for r, c in zip(rows, cols)}
  assert got == expect
  # edge features for every induced edge, by global eid
  np.testing.assert_allclose(msg['efeats'][:, 0],
                             msg['eids'].astype(np.float32))
  np.testing.assert_allclose(msg['nfeats'][:, 0], ids.astype(np.float32))


def test_missing_peer_raises(deployment):
  shards, _, addrs = deployment
  with pytest.raises(ValueError, match='no peer client'):
    HostDistNeighborSampler(shards[0], [2], {})


def test_dead_peer_raises_not_hangs(deployment, monkeypatch):
  """A peer that dies mid-epoch must surface a typed error once the
  retry deadline expires (a peer that came BACK inside the deadline
  would heal the hop transparently — distributed/resilience.py),
  never a silent under-sample or an indefinite hang — the
  host-runtime arm of the failure-handling story.  The deadline is
  shortened so 'prompt' stays prompt on the test clock."""
  from graphlearn_tpu.distributed.resilience import (
      RetryExhausted, reset_default_policy)
  monkeypatch.setenv('GLT_RPC_DEADLINE', '2.0')
  monkeypatch.setenv('GLT_RPC_BACKOFF_CAP', '0.2')
  reset_default_policy()
  try:
    shards, services, addrs = deployment
    sampler = HostDistNeighborSampler(shards[0], [2],
                                      connect_peers(addrs, 0), seed=0)
    # first batch works
    sampler.sample_from_nodes(np.arange(4, dtype=np.int64))
    services[1].shutdown()
    with pytest.raises((RetryExhausted, ConnectionError, OSError)):
      # remote-owned seeds force RPC to the dead peer
      for _ in range(4):
        sampler.sample_from_nodes(np.arange(N, dtype=np.int64))
  finally:
    reset_default_policy()         # don't leak the short deadline
