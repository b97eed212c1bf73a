"""Distributed sampling tests on the virtual 8-device CPU mesh.

The TPU translation of the reference's all-local distributed tests
(`test/python/test_dist_neighbor_loader.py` + `dist_test_utils.py`):
a deterministic ring graph partitioned across devices, features that
encode node ids, correctness asserted arithmetically — the real
collective stack runs, no mocks.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                     DistNeighborSampler, make_mesh)

N = 64  # ring: v -> v+1, v -> v+2 (mod N)


def _ring_dist_dataset(num_parts=4, contiguous=False, with_feats=True):
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  feats = (np.arange(N, dtype=np.float32)[:, None]
           * np.ones((1, 4), np.float32)) if with_feats else None
  labels = (np.arange(N) % 5).astype(np.int32)
  if contiguous:
    node_pb = (np.arange(N) * num_parts // N).astype(np.int32)
  else:
    node_pb = (np.arange(N) % num_parts).astype(np.int32)  # interleaved
  return DistDataset.from_full_graph(
      num_parts, rows, cols, node_feat=feats, node_label=labels,
      num_nodes=N, node_pb=node_pb)


def test_dist_graph_layout():
  ds = _ring_dist_dataset(4)
  g = ds.graph
  assert g.num_partitions == 4
  assert g.num_nodes == N
  np.testing.assert_array_equal(g.bounds, [0, 16, 32, 48, 64])
  # each node has out-degree 2 in its owner's local CSR.
  for p in range(4):
    deg = np.diff(g.indptr[p])[:16]
    np.testing.assert_array_equal(deg, 2)


def test_dist_one_hop_edges_correct():
  ds = _ring_dist_dataset(4)
  sampler = DistNeighborSampler(ds, [2], mesh=make_mesh(4), seed=0)
  # each device seeds 4 of its own... seeds can be ANY nodes; use a
  # spread so every device requests remote partitions.
  seeds = ds.old2new[np.arange(16).reshape(4, 4)]
  out = sampler.sample_from_nodes(seeds)
  nodes = np.asarray(out['node'])       # [P, cap] relabeled ids
  rows = np.asarray(out['row'])
  cols = np.asarray(out['col'])
  new2old = ds.new2old
  for p in range(4):
    m = rows[p] >= 0
    assert m.any()
    r_old = new2old[nodes[p][rows[p][m]]]
    c_old = new2old[nodes[p][cols[p][m]]]
    # ring invariant: neighbor = seed + 1 or + 2 (mod N).
    d = (r_old - c_old) % N
    assert np.isin(d, [1, 2]).all(), d


def test_dist_feature_and_label_provenance():
  ds = _ring_dist_dataset(4)
  sampler = DistNeighborSampler(ds, [2, 2], mesh=make_mesh(4), seed=0)
  seeds = ds.old2new[np.arange(32).reshape(4, 8)]
  out = sampler.sample_from_nodes(seeds)
  nodes = np.asarray(out['node'])
  x = np.asarray(out['x'])
  y = np.asarray(out['y'])
  for p in range(4):
    m = nodes[p] >= 0
    old_ids = ds.new2old[nodes[p][m]]
    # feature rows encode the ORIGINAL node id — remote gathers
    # included (the dist_test_utils provenance trick).
    np.testing.assert_allclose(x[p][m][:, 0], old_ids)
    np.testing.assert_allclose(x[p][~m], 0)
    np.testing.assert_array_equal(y[p][m], old_ids % 5)


def test_dist_sampling_matches_single_chip_statistics():
  # every sampled edge must be a real edge; seeds keep slots 0..B-1.
  ds = _ring_dist_dataset(8)
  sampler = DistNeighborSampler(ds, [2], mesh=make_mesh(8), seed=0)
  seeds = ds.old2new[np.arange(64).reshape(8, 8)]
  out = sampler.sample_from_nodes(seeds)
  sl = np.asarray(out['seed_local'])
  for p in range(8):
    np.testing.assert_array_equal(sl[p], np.arange(8))


def test_dist_loader_epoch_and_training():
  import optax
  from graphlearn_tpu.models import GraphSAGE, create_train_state
  from graphlearn_tpu.parallel import make_dp_supervised_step, replicate
  from graphlearn_tpu.parallel.dp import make_mesh as mm

  num_parts = 4
  mesh = make_mesh(num_parts)
  ds = _ring_dist_dataset(num_parts)
  bs = 4
  loader = DistNeighborLoader(ds, [2, 2], np.arange(N), batch_size=bs,
                              shuffle=True, mesh=mesh, seed=0)
  batches = list(loader)
  assert len(batches) == len(loader) == N // (bs * num_parts)
  b0 = batches[0]
  assert b0.x.shape[0] == num_parts
  assert b0.edge_index.shape[1] == 2

  model = GraphSAGE(hidden_features=8, out_features=5, num_layers=2)
  tx = optax.adam(1e-2)
  single = jax.tree_util.tree_map(lambda v: v[0], b0)
  state, _ = create_train_state(model, jax.random.key(0), single, tx)
  step = make_dp_supervised_step(model.apply, tx, bs, mesh)
  state = replicate(state, mesh)
  losses = []
  for _ in range(3):
    for batch in loader:
      state, loss, _ = step(state, batch)
      losses.append(float(loss))
  assert np.isfinite(losses).all()
  assert losses[-1] < losses[0]


def test_cache_overlay_exact():
  """Cache hits overlay the exchanged rows; results match the uncached
  gather bit-exactly (cache rows mirror the table)."""
  from graphlearn_tpu.parallel.dist_sampler import (cache_overlay,
                                                    dist_gather)
  from graphlearn_tpu.parallel.dist_data import CACHE_PAD_ID
  from graphlearn_tpu.parallel.shard_map_compat import shard_map
  from jax.sharding import PartitionSpec as P

  num_parts = 4
  mesh = make_mesh(num_parts)
  rows_max = N // num_parts
  bounds = np.arange(num_parts + 1) * rows_max
  shards = (np.arange(N, dtype=np.float32).reshape(num_parts, rows_max, 1)
            * np.ones((1, 1, 4), np.float32))
  # each device caches 3 rows of the NEXT partition
  cids = np.full((num_parts, 3), CACHE_PAD_ID, np.int32)
  crows = np.zeros((num_parts, 3, 4), np.float32)
  for p in range(num_parts):
    ids = (bounds[(p + 1) % num_parts] + np.arange(3)).astype(np.int32)
    cids[p] = np.sort(ids)
    crows[p] = ids[:, None].astype(np.float32)
  ids_req = np.stack([np.arange(p, p + 8, dtype=np.int32) * 7 % N
                      for p in range(num_parts)])

  def run(shards_s, bounds_r, ids_s, cids_s, crows_s):
    ref = dist_gather(shards_s[0], bounds_r, ids_s[0], 'data', num_parts)
    out = cache_overlay(ref, ids_s[0], cids_s[0], crows_s[0])
    return out[None], ref[None]

  sh = P('data')
  f = jax.jit(shard_map(run, mesh=mesh,
                        in_specs=(sh, P(), sh, sh, sh),
                        out_specs=(sh, sh)))
  out, ref = f(shards, bounds, ids_req, cids, crows)
  np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
  # value correctness: row value == id
  np.testing.assert_array_equal(np.asarray(out)[..., 0],
                                ids_req.astype(np.float32))


def test_partition_dir_cache_roundtrip(tmp_path):
  """cache_ratio partitions -> DistDataset with a live cache -> loader
  features still exact (the cat_feature_cache flow, end to end)."""
  from graphlearn_tpu.partition import RandomPartitioner
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  feats = np.arange(N, dtype=np.float32)[:, None] * np.ones((1, 4),
                                                            np.float32)
  labels = (np.arange(N) % 5).astype(np.int32)
  RandomPartitioner(tmp_path, 4, N, (rows, cols), feats, labels,
                    cache_ratio=0.2, seed=0).partition()
  ds = DistDataset.from_partition_dir(tmp_path)
  assert ds.node_features.has_cache
  mesh = make_mesh(4)
  loader = DistNeighborLoader(ds, [2], np.arange(N), batch_size=4,
                              mesh=mesh, seed=0)
  for batch in loader:
    nodes = np.asarray(batch.node)
    x = np.asarray(batch.x)
    new2old = ds.new2old
    for p in range(4):
      valid = nodes[p] >= 0
      np.testing.assert_array_equal(
          x[p][valid][:, 0], new2old[nodes[p][valid]].astype(np.float32))


def test_exchange_capacity_lossless_with_slack():
  """With balanced buckets and 2x slack the capped exchange returns
  exactly the uncapped results (bytes shrink, nothing drops)."""
  ds = _ring_dist_dataset(4)
  mesh = make_mesh(4)
  a = DistNeighborSampler(ds, [2, 2], mesh=mesh, seed=0)
  b = DistNeighborSampler(ds, [2, 2], mesh=mesh, seed=0,
                          exchange_slack=2.0)
  seeds = ds.old2new[np.arange(16).reshape(4, 4)]
  oa = a.sample_from_nodes(seeds)
  ob = b.sample_from_nodes(seeds)
  for k in ('node', 'row', 'col', 'x', 'y'):
    np.testing.assert_array_equal(np.asarray(oa[k]), np.asarray(ob[k]))


def test_bucket_capacity_drops_overflow_not_valid_ids():
  """Direct bucket_by_owner contract under a cap smaller than one
  owner's load: exactly `cap` ids of the hot owner survive, invalid
  ids never consume slots, and dropped ids get slot_j == -1."""
  from functools import partial
  from graphlearn_tpu.parallel.dist_sampler import bucket_by_owner
  from graphlearn_tpu.parallel.shard_map_compat import shard_map
  from jax.sharding import PartitionSpec as P

  num_parts = 2
  mesh = make_mesh(num_parts)
  # device row: 5 ids for owner 1, one invalid FIRST, 2 for owner 0
  ids = np.tile(np.array([-1, 10, 11, 12, 13, 14, 2, 3], np.int32),
                (num_parts, 1))
  owner = np.tile(np.array([0, 1, 1, 1, 1, 1, 0, 0], np.int32),
                  (num_parts, 1))

  def run(ids_s, owner_s):
    send, slot_p, slot_j = bucket_by_owner(
        ids_s[0], owner_s[0], num_parts,
        jax.lax.axis_index('data'), capacity=3)
    return send[None], slot_p[None], slot_j[None]

  sh = P('data')
  f = jax.jit(shard_map(run, mesh=mesh, in_specs=(sh, sh),
                        out_specs=(sh, sh, sh)))
  send, slot_p, slot_j = (np.asarray(v) for v in f(ids, owner))
  d = 0
  # owner 0 had 2 valid ids (+1 invalid that must NOT take a slot)
  assert set(send[d, 0][send[d, 0] >= 0]) == {2, 3}
  # owner 1 had 5 ids, cap 3: exactly the first 3 survive
  np.testing.assert_array_equal(send[d, 1], [10, 11, 12])
  # dropped: ids 13, 14 and the invalid id -> slot_j -1
  dropped = slot_j[d] < 0
  np.testing.assert_array_equal(ids[0][dropped], [-1, 13, 14])
  # surviving slots point at their id
  for i in np.nonzero(~dropped)[0]:
    assert send[d, slot_p[d, i], slot_j[d, i]] == ids[0, i]


def _bucket_contract(ids, owner, num_parts, cap):
  """`bucket_by_owner`'s contract as a loop over the ids in request
  order: an owner keeps its first ``cap`` valid ids, in order; an
  invalid id takes no slot and reads ``(0, -1)``; a valid id past its
  owner's ``cap`` reads ``(owner, -1)``."""
  send = np.full((num_parts, cap), -1, ids.dtype)
  slot_p = np.zeros(len(ids), np.int32)
  slot_j = np.full(len(ids), -1, np.int32)
  taken = np.zeros(num_parts, np.int64)
  for i, (v, o) in enumerate(zip(ids, owner)):
    if v < 0:
      continue
    slot_p[i] = o
    if taken[o] < cap:
      send[o, taken[o]] = v
      slot_j[i] = taken[o]
    taken[o] += 1
  return send, slot_p, slot_j


@pytest.mark.parametrize('invalid', ['first', 'last'])
@pytest.mark.parametrize('owners', ['uniform', 'skewed'])
@pytest.mark.parametrize('cap', ['below', 'above'])
@pytest.mark.parametrize('num_parts', [2, 4, 8])
def test_bucket_by_owner_keeps_its_contract(num_parts, cap, owners,
                                            invalid):
  """The pack (sorts and slices) against a NumPy loop of what it
  promises, over caps below and above the busiest owner's load, owners
  drawn uniformly or piled on a few, and a block of invalid ids at
  either end; the payload of `bucket_with_payload` rides its id."""
  from graphlearn_tpu.parallel.dist_sampler import (bucket_by_owner,
                                                    bucket_with_payload)
  rng = np.random.default_rng(num_parts)
  f, bad = 240, 60
  if owners == 'uniform':
    owner = rng.integers(0, num_parts, f)
  else:
    owner = np.minimum(rng.geometric(0.5, f) - 1, num_parts - 1)
  owner = owner.astype(np.int32)
  ids = rng.permutation(10_000)[:f].astype(np.int32)
  ids[:bad] = -1
  if invalid == 'last':
    ids = np.roll(ids, -bad)
  load = np.bincount(owner[ids >= 0], minlength=num_parts).max()
  c = load // 2 if cap == 'below' else None
  want = _bucket_contract(ids, owner, num_parts, f if c is None else c)
  if cap == 'below':
    assert (want[2][ids >= 0] < 0).any()      # the cap does drop ids
  got = jax.jit(lambda i, o: bucket_by_owner(i, o, num_parts, None, c))(
      ids, owner)
  for w, g in zip(want, got):
    assert g.dtype == w.dtype
    np.testing.assert_array_equal(np.asarray(g), w)
  payload = (ids.astype(np.float32) + 0.5) * (ids >= 0)
  send, send_pl, slot_p, slot_j = jax.jit(
      lambda i, pl, o: bucket_with_payload(i, pl, o, num_parts, None, c))(
          ids, payload, owner)
  np.testing.assert_array_equal(np.asarray(send), want[0])
  np.testing.assert_array_equal(
      np.asarray(send_pl), np.where(want[0] >= 0, want[0] + 0.5, -1))
  np.testing.assert_array_equal(np.asarray(slot_p), want[1])
  np.testing.assert_array_equal(np.asarray(slot_j), want[2])


@pytest.mark.parametrize('payload', [False, True])
@pytest.mark.parametrize('f,cap', [(937_984, 468_992), (153_600, 76_800)])
def test_bucket_by_owner_lowers_to_sorts_and_slices(f, cap, payload):
  """At the four-chip cell's feature (937,984 ids) and widest frontier
  (153,600) exchanges, P = 4 and slack 2: the compiled pack holds no
  scatter and no gather (the ops the TPU prices worst), and at most
  two sorts."""
  import re
  from graphlearn_tpu.parallel.dist_sampler import (bucket_by_owner,
                                                    bucket_with_payload)
  i32 = jax.ShapeDtypeStruct((f,), jnp.int32)
  if payload:
    fn = lambda i, o, pl: bucket_with_payload(i, pl, o, 4, None, cap)
    args = (i32, i32, i32)
  else:
    fn = lambda i, o: bucket_by_owner(i, o, 4, None, cap)
    args = (i32, i32)
  lowered = jax.jit(fn).lower(*args)
  text = lowered.as_text()
  assert not re.search(r'stablehlo\.(gather|scatter)\b', text)
  assert len(re.findall(r'stablehlo\.sort\b', text)) <= 2
  hlo = lowered.compile().as_text()
  ops = re.findall(r'\b(gather|scatter|sort)\(', hlo)
  assert 'gather' not in ops and 'scatter' not in ops
  assert ops.count('sort') <= 2


def test_exchange_capacity_drops_are_masked():
  """A skewed workload (every seed targets partition 0's range) with a
  small slack: real drops happen, survivors stay correct.  The ring is
  sized so the skewed bucket exceeds the MIN_EXCHANGE_CAP floor (tiny
  exchanges are deliberately exact)."""
  n = 1024
  rows = np.concatenate([np.arange(n), np.arange(n)])
  cols = np.concatenate([(np.arange(n) + 1) % n, (np.arange(n) + 2) % n])
  node_pb = (np.arange(n) * 4 // n).astype(np.int32)
  ds = DistDataset.from_full_graph(4, rows, cols, num_nodes=n,
                                   node_pb=node_pb)
  mesh = make_mesh(4)
  s = DistNeighborSampler(ds, [2], mesh=mesh, seed=0,
                          exchange_slack=0.5)
  # 256 seeds per device, ALL in partition 0's range [0, 256): buckets
  # are maximally skewed, the cap max(256/4*0.5, 64) = 64 binds hard
  seeds = ds.old2new[np.tile(np.arange(256), (4, 1))]
  out = s.sample_from_nodes(seeds)
  rows_l = np.asarray(out['row'])
  cols_l = np.asarray(out['col'])
  nodes = np.asarray(out['node'])
  new2old = ds.new2old
  survived = 0
  for p in range(4):
    m = rows_l[p] >= 0
    for r, c in zip(rows_l[p][m], cols_l[p][m]):
      u = new2old[nodes[p, c]]
      v = new2old[nodes[p, r]]
      assert (v - u) % n in (1, 2)     # still a real ring edge
      survived += 1
  # the uncapped run yields 2 edges/seed; drops must actually occur
  uncapped = DistNeighborSampler(ds, [2], mesh=mesh, seed=0)
  out_u = uncapped.sample_from_nodes(seeds)
  full = int((np.asarray(out_u['row']) >= 0).sum())
  assert 0 < survived < full
  # each dropped frontier id loses exactly min(deg, k) = 2 edges
  st = s.exchange_stats(tick_metrics=False)
  assert st['dist.frontier.dropped'] * 2 == full - survived
