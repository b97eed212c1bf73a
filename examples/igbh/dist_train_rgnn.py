"""Distributed RGNN (RGAT/RSAGE) on an IGBH-style hetero graph.

TPU counterpart of reference `examples/igbh/dist_train_rgnn.py` — THE
BASELINE scaling workload: every node type range-sharded over the
device mesh, per-edge-type neighbor exchange on ICI collectives
(`parallel.DistHeteroNeighborLoader`), and a data-parallel hetero
train step with psum-averaged gradients.

Runs on a real TPU slice, or anywhere via the virtual CPU mesh::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/igbh/dist_train_rgnn.py --num-parts 8 --model rgat
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

import numpy as np

from examples.igbh.train_rgnn import ETYPES, P as PAPER, synthetic


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--model', choices=['rgat', 'rsage'], default='rsage')
  ap.add_argument('--partition-dir', type=str, default=None,
                  help='hetero partition layout from RandomPartitioner')
  ap.add_argument('--igbh-root', type=str, default=None,
                  help='REAL IGBH directory (the reference npy layout, '
                       'examples/igbh/dataset.py) — loaded via '
                       'graphlearn_tpu.data.load_igbh_dir')
  ap.add_argument('--igbh-size', default='tiny',
                  choices=['tiny', 'small', 'medium', 'large', 'full'])
  ap.add_argument('--num-parts', type=int, default=None)
  ap.add_argument('--epochs', type=int, default=3)
  ap.add_argument('--batch-size', type=int, default=64,
                  help='per-device paper seeds')
  ap.add_argument('--fanout', type=int, nargs='+', default=[4, 4])
  ap.add_argument('--hidden', type=int, default=64)
  ap.add_argument('--heads', type=int, default=2)
  ap.add_argument('--split-ratio', type=float, default=1.0,
                  help='fraction of each node type\'s feature rows in '
                       'HBM; < 1 tiers the rest to host DRAM — the '
                       'IGBH-large "features exceed aggregate HBM" '
                       'lever (cold misses overlaid per batch, '
                       'hit rate in exchange_stats)')
  ap.add_argument('--host-local', action='store_true',
                  help='with --partition-dir on a multi-host pod: each '
                       'process materializes only ITS partitions '
                       '(per-host RAM = 1/num_hosts of the dataset)')
  args = ap.parse_args()

  import jax
  import jax.numpy as jnp
  import flax.linen as nn
  import optax
  from jax.sharding import NamedSharding, PartitionSpec
  from graphlearn_tpu.models import RGAT, SAGEConv
  from graphlearn_tpu.parallel import (DistHeteroDataset,
                                       DistHeteroNeighborLoader, make_mesh,
                                       replicate)
  from graphlearn_tpu.parallel.shard_map_compat import shard_map

  num_parts = args.num_parts or len(jax.devices())
  mesh = make_mesh(num_parts)

  if args.partition_dir:
    import json
    with open(Path(args.partition_dir) / 'META.json') as f:
      disk_parts = json.load(f)['num_parts']
    assert disk_parts == num_parts, (
        f'partition layout has {disk_parts} parts but the mesh has '
        f'{num_parts} devices — repartition or set --num-parts')
    from graphlearn_tpu.parallel import multihost
    ds = DistHeteroDataset.from_partition_dir(
        args.partition_dir, num_parts, split_ratio=args.split_ratio,
        host_parts=(multihost.host_partition_ids(mesh)
                    if args.host_local else None))
    assert PAPER in ds.node_labels, 'training needs paper labels'
    npaper = ds.num_nodes_dict()[PAPER]
    # host-local shards see only local labels: the class count (and so
    # the model width) must agree GLOBALLY across processes
    classes = multihost.global_max(
        int(np.max(ds.node_labels[PAPER])), mesh) + 1
    train_idx = np.arange(npaper)
  elif args.igbh_root:
    from graphlearn_tpu.data import load_igbh_dir
    # default mmap: tables stay on disk until the shard build slices
    # them (at large/full, partition offline with
    # `graphlearn_tpu.data.partition_igbh` + --partition-dir instead
    # of this in-memory path)
    d = load_igbh_dir(args.igbh_root, args.igbh_size)
    npaper = d['num_nodes_dict'][PAPER]
    classes = int(d['paper_labels'].max()) + 1
    ds = DistHeteroDataset.from_full_graph(
        num_parts, d['edge_index_dict'],
        node_feat_dict=d['node_feat_dict'],
        node_label_dict={PAPER: d['paper_labels'].astype(np.int32)},
        num_nodes_dict=d['num_nodes_dict'],
        split_ratio=args.split_ratio)
    train_idx = d['train_idx']          # reference 60% convention
  else:
    edges, feats, nnodes, topic = synthetic()
    npaper, classes = len(topic), int(topic.max()) + 1
    ds = DistHeteroDataset.from_full_graph(
        num_parts, edges, node_feat_dict=feats,
        node_label_dict={PAPER: topic}, num_nodes_dict=nnodes,
        split_ratio=args.split_ratio)
    train_idx = np.arange(npaper)

  bs = args.batch_size
  loader = DistHeteroNeighborLoader(
      ds, args.fanout, (PAPER, train_idx), batch_size=bs,
      shuffle=True, mesh=mesh, seed=0)

  batch0 = next(iter(loader))
  etypes = tuple(batch0.edge_index_dict.keys())

  class RSAGE(RGAT):
    """`RGAT`'s stack with a per-relation `SAGEConv`."""

    @nn.nowrap
    def make_conv(self):
      return SAGEConv(self.hidden_features)

  # the mesh loader's batches state no hop layout: whole tables
  model = (RGAT if args.model == 'rgat' else RSAGE)(
      etypes=etypes, hidden_features=args.hidden, out_features=classes,
      num_layers=2, heads=args.heads, target_ntype=PAPER)
  tx = optax.adam(1e-3)
  single = jax.tree_util.tree_map(lambda v: v[0], batch0)
  params = model.init(jax.random.key(0), single.x_dict,
                      single.edge_index_dict, single.edge_mask_dict)
  opt = tx.init(params)

  def device_step(params, opt, batch):
    batch = jax.tree_util.tree_map(lambda v: v[0], batch)

    def loss_fn(p):
      logits = model.apply(p, batch.x_dict, batch.edge_index_dict,
                           batch.edge_mask_dict)
      y = batch.y_dict[PAPER][:bs]
      valid = (batch.batch_dict[PAPER].reshape(-1) >= 0).astype(
          logits.dtype)
      ce = optax.softmax_cross_entropy_with_integer_labels(logits[:bs], y)
      return (ce * valid).sum() / jnp.maximum(valid.sum(), 1.0)

    loss, g = jax.value_and_grad(loss_fn)(params)
    g = jax.lax.pmean(g, 'data')             # DP gradient sync
    loss = jax.lax.pmean(loss, 'data')
    upd, opt = tx.update(g, opt, params)
    return optax.apply_updates(params, upd), opt, loss[None]

  pspec = PartitionSpec('data')
  step = jax.jit(shard_map(
      device_step, mesh=mesh,
      in_specs=(PartitionSpec(), PartitionSpec(), pspec),
      out_specs=(PartitionSpec(), PartitionSpec(), pspec)))

  for epoch in range(args.epochs):
    t0 = time.perf_counter()
    tot = cnt = 0
    for batch in loader:
      params, opt, loss = step(params, opt, batch)
      tot += float(np.asarray(loss)[0])
      cnt += 1
    print(f'epoch {epoch}: loss {tot / max(cnt, 1):.4f} '
          f'({time.perf_counter() - t0:.2f}s, {cnt} steps x '
          f'{num_parts} devices, {args.model})')


if __name__ == '__main__':
  main()
