"""Shards built on the mesh (`DistDataset.from_device_coo`) against the
host partitioner (`from_full_graph`): the same bytes for the same
partition book, the same shapes for every graph of a stated capacity,
one compiled mesh epoch across them, and an error — never a drop or a
resize — for a graph over the capacity.  On the virtual CPU mesh."""
import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from graphlearn_tpu.models import TreeSAGE
from graphlearn_tpu.parallel import (DistDataset, DistNeighborSampler,
                                     FusedDistTreeEpoch, make_mesh)
from graphlearn_tpu.telemetry.recorder import recorder

P = 4
DIM, CLASSES = 12, 5


def graph(seed, n=4000, deg=6):
  """The products recipe in small: uniform sources, hub targets."""
  rng = np.random.default_rng(seed)
  e = n * deg
  rows = rng.integers(0, n, e)
  u = rng.random(e)
  cols = np.where(rng.random(e) < 0.3, u * u * n, u * n).astype(np.int64)
  feats = rng.random((n, DIM), np.float32)
  labels = rng.integers(0, CLASSES, n).astype(np.int32)
  return rows, cols, feats, labels


@pytest.fixture(scope='module')
def mesh():
  return make_mesh(P)


def on_device(a, dtype=jnp.int32):
  return jnp.asarray(a, dtype)


def placed(ds, mesh, **kw):
  """What a sampler puts on the devices, as host bytes."""
  arrs = DistNeighborSampler(ds, [2], mesh=mesh, **kw)._arrays()
  return {k: np.asarray(v) for k, v in arrs.items()}


@pytest.mark.parametrize('n', [4000, 4003])
@pytest.mark.parametrize('source', ['table', 'callable'])
def test_device_built_shards_equal_the_host_paths_byte_for_byte(
    mesh, source, n):
  """`indptr`, `indices`, bounds, feature and label shards as a sampler
  places them, and `old2new` — with equal partitions (4000 nodes) and
  unequal ones (4003: padded rows, repeated terminal `indptr`)."""
  rows, cols, feats, labels = graph(1, n=n)
  cap = 6600
  host = DistDataset.from_full_graph(
      P, rows, cols, node_feat=feats, node_label=labels, num_nodes=n,
      seed=3, edge_capacity=cap)
  if source == 'table':
    nf, nl = on_device(feats, jnp.float32), on_device(labels)
  else:
    ftab, ltab = on_device(feats, jnp.float32), on_device(labels)
    take = lambda ids, table: table[ids]
    nf, nl = (take, (ftab,)), (take, (ltab,))
  dev = DistDataset.from_device_coo(
      P, on_device(rows), on_device(cols), num_nodes=n, edge_capacity=cap,
      node_feat=nf, node_label=nl, mesh=mesh, seed=3)
  assert np.array_equal(dev.old2new, host.old2new)
  assert np.array_equal(dev.new2old, host.new2old)
  assert np.array_equal(dev.graph.bounds, host.graph.bounds)
  assert dev.graph.edge_ids is None and dev.partitioner == 'range'
  # the stacks themselves: values of the host path's
  assert np.array_equal(np.asarray(dev.graph.indptr), host.graph.indptr)
  assert np.array_equal(np.asarray(dev.graph.indices), host.graph.indices)
  assert np.array_equal(np.asarray(dev.node_features.shards),
                        host.node_features.shards)
  assert np.array_equal(np.asarray(dev.node_labels), host.node_labels)
  # and what reaches the devices: bytes, shapes, dtypes
  a, b = placed(host, mesh), placed(dev, mesh)
  assert set(a) == set(b)
  for k in a:
    assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape, k
    assert a[k].tobytes() == b[k].tobytes(), k
  assert a['indices'].shape == (P, cap)
  built = dev.shard_build
  assert built['edge_capacity'] == cap and built['secs'] > 0
  assert built['nodes'] == list(np.diff(host.graph.bounds))
  assert built['edges'] == [int(r[-1]) for r in host.graph.indptr]


def test_without_the_capacity_the_host_path_is_what_it_was(mesh):
  """`edge_capacity` left out: the width follows the graph's largest
  partition, edge ids are built, and the stacks are the same arrays
  with or without the argument up to the padding."""
  rows, cols, feats, labels = graph(2)
  plain = DistDataset.from_full_graph(P, rows, cols, node_feat=feats,
                                      node_label=labels, num_nodes=4000)
  most = int(plain.graph.indptr[:, -1].max())
  assert plain.graph.indices.shape == (P, most)
  assert plain.graph.edge_ids.shape == (P, most)
  stated = DistDataset.from_full_graph(
      P, rows, cols, node_feat=feats, node_label=labels, num_nodes=4000,
      edge_capacity=most + 40)
  assert np.array_equal(stated.graph.indices[:, :most],
                        plain.graph.indices)
  assert (stated.graph.indices[:, most:] == -1).all()
  assert np.array_equal(stated.graph.indptr, plain.graph.indptr)


def test_shards_already_on_the_mesh_pass_through_untouched(mesh):
  rows, cols, feats, labels = graph(3)
  dev = DistDataset.from_device_coo(
      P, on_device(rows), on_device(cols), num_nodes=4000,
      edge_capacity=6600, node_feat=feats, node_label=labels, mesh=mesh)
  arrs = DistNeighborSampler(dev, [2], mesh=mesh)._arrays()
  assert arrs['indptr'] is dev.graph.indptr
  assert arrs['indices'] is dev.graph.indices
  assert arrs['fshards'] is dev.node_features.shards
  assert arrs['lshards'] is dev.node_labels


def test_edge_ids_reach_the_devices_only_where_a_step_reads_them(mesh):
  rows, cols, feats, labels = graph(3)
  host = DistDataset.from_full_graph(P, rows, cols, node_feat=feats,
                                     node_label=labels, num_nodes=4000)
  width = host.graph.indices.shape[1]
  assert placed(host, mesh)['eids'].shape == (P, 1)
  with_ids = placed(host, mesh, with_edge=True)['eids']
  assert with_ids.shape == (P, width)
  assert np.array_equal(with_ids, host.graph.edge_ids)
  dev = DistDataset.from_device_coo(
      P, on_device(rows), on_device(cols), num_nodes=4000,
      edge_capacity=6600, mesh=mesh)
  with pytest.raises(ValueError, match='without them'):
    DistNeighborSampler(dev, [2], mesh=mesh, with_edge=True)


@pytest.mark.parametrize('path', ['device', 'host'])
def test_two_seeds_give_identical_shard_shapes_at_a_stated_capacity(
    mesh, path):
  shapes = []
  for seed in (11, 12):
    rows, cols, feats, labels = graph(seed)
    if path == 'host':
      ds = DistDataset.from_full_graph(
          P, rows, cols, node_feat=feats, node_label=labels,
          num_nodes=4000, seed=seed, edge_capacity=6600)
    else:
      ds = DistDataset.from_device_coo(
          P, on_device(rows), on_device(cols), num_nodes=4000,
          edge_capacity=6600, node_feat=feats, node_label=labels,
          mesh=mesh, seed=seed)
    shapes.append({k: (v.shape, v.dtype)
                   for k, v in placed(ds, mesh).items()})
  assert shapes[0] == shapes[1]
  # which the default does not give: the width follows the draw
  widths = {DistDataset.from_full_graph(
      P, *graph(seed)[:2], num_nodes=4000,
      seed=seed).graph.indices.shape[1] for seed in (11, 12)}
  assert len(widths) == 2


@pytest.mark.parametrize('over', ['edge_capacity', 'exchange width',
                                  'host'])
def test_a_draw_over_the_capacity_raises(mesh, over):
  rows, cols, _, _ = graph(5)
  host = DistDataset.from_full_graph(P, rows, cols, num_nodes=4000, seed=7)
  most = int(host.graph.indptr[:, -1].max())
  if over == 'host':
    with pytest.raises(ValueError, match='over the stated edge_capacity'):
      DistDataset.from_full_graph(P, rows, cols, num_nodes=4000, seed=7,
                                  edge_capacity=most - 1)
    return
  build = lambda rows, cols, cap: DistDataset.from_device_coo(
      P, on_device(rows), on_device(cols), num_nodes=4000, mesh=mesh,
      seed=7, edge_capacity=cap)
  # every block the same edges: each holds a P-th of every owner's, so
  # the exchange has room whenever the partitions have
  rows, cols = (np.tile(a[:len(a) // P], P) for a in (rows, cols))
  most = int(DistDataset.from_full_graph(
      P, rows, cols, num_nodes=4000, seed=7).graph.indptr[:, -1].max())
  if over == 'edge_capacity':
    with pytest.raises(ValueError, match='over the stated edge_capacity'):
      build(rows, cols, most - 1)
  else:
    # no partition is over its capacity, but the COO comes sorted by
    # owner: device 0's block holds one owner's edges alone, P times
    # what the exchange gives one device for one owner
    by_owner = np.argsort(host.old2new[rows], kind='stable')
    with pytest.raises(ValueError, match='over the exchange width'):
      build(rows[by_owner], cols[by_owner], most)
  # exactly at the capacity, blocks mixed, is no error
  build(rows, cols, most)


def _epoch(ds, mesh, seed):
  ids = np.random.default_rng(seed).permutation(4000)[:2 * P * 8]
  return FusedDistTreeEpoch(
      ds, [3, 2], ids, TreeSAGE(hidden_features=8, out_features=CLASSES,
                                num_layers=2),
      optax.adam(1e-2), batch_size=8, mesh=mesh, seed=seed)


def test_one_compiled_mesh_epoch_serves_every_seed(mesh):
  """Two graphs, one stated capacity: the epoch compiled for the first
  runs the second's shards with no new executable, and the second's
  own epoch lowers to the same program text (what the persistent cache
  keys on)."""
  eps = []
  for seed in (21, 22):
    rows, cols, feats, labels = graph(seed)
    ds = DistDataset.from_device_coo(
        P, on_device(rows), on_device(cols), num_nodes=4000,
        edge_capacity=6600, node_feat=feats, node_label=labels,
        mesh=mesh, seed=seed)
    eps.append(_epoch(ds, mesh, seed))
  a, b = eps
  state = a.init_state(jax.random.key(0))
  state, stats = a.run(state)
  assert np.isfinite(np.asarray(stats.losses)).all()
  assert a.compile_count() == 1
  seeds = a._put_batches(np.stack(list(b._batcher)).reshape(-1, P, 8))
  args = lambda ep: (ep.init_state(jax.random.key(0)), seeds,
                     jax.random.key(1), ep._chunk_arrs())
  out = a._compiled(*args(b))            # seed 22's shards, seed 21's program
  assert np.isfinite(np.asarray(out[1])).all()
  assert a.compile_count() == 1
  text = lambda ep: ep._compiled.jitted.lower(*args(ep)).as_text()
  assert text(a) == text(b)


def test_the_build_and_the_exchange_plan_are_on_record(mesh):
  """`dist.shard_build` carries the per-device counts and the stated
  capacities; `exchange.plan` says, once per compiled mesh program,
  which layout was chosen, its slack and the slots per hop."""
  rows, cols, feats, labels = graph(6)
  recorder.enable()
  try:
    ds = DistDataset.from_device_coo(
        P, on_device(rows), on_device(cols), num_nodes=4000,
        edge_capacity=6600, node_feat=feats, node_label=labels, mesh=mesh)
    ep = _epoch(ds, mesh, 6)
    ep.run(ep.init_state(jax.random.key(0)))
    ends = [e for e in recorder.events('span.end')
            if e['name'] == 'dist.shard_build']
    plans = recorder.events('exchange.plan')
  finally:
    recorder.disable()
  assert len(ends) == 1
  end = ends[0]
  assert end['edge_capacity'] == 6600 and end['exchange_capacity'] == 1650
  assert end['nodes'] == [1000] * P and sum(end['edges']) == 24000
  assert end['edges'] == ds.shard_build['edges']
  assert len(plans) == 1
  plan = plans[0]
  assert plan['scope'] == 'FusedDistTreeEpoch' and plan['layout'] == 'dense'
  assert plan['num_parts'] == P and plan['batch'] == 8
  assert plan['slack'] == ep.sampler.exchange_slack
  assert plan['frontier_ids'] == [8, 24]
  assert plan['feature_ids'] == 8 + 24 + 48
  for ids, slots in zip(plan['frontier_ids'] + [plan['feature_ids']],
                        plan['frontier_slots'] + [plan['feature_slots']]):
    assert slots >= ids and slots % P == 0
