"""Distributed UNSUPERVISED GraphSAGE over the device mesh.

The distributed twin of `examples/unsup_sage_ppi.py` (reference
`examples/graph_sage_unsup_ppi.py`), built on the mesh link engine:
seed edges split across devices, strict negatives drawn collectively
(`dist_edge_exists` over the sharded CSR), endpoint neighborhoods
expanded with all_to_all exchanges, and the binary link loss trained
data-parallel with pmean gradients.

Run on the 8-device virtual CPU mesh::

    JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/distributed/dist_unsup_sage.py
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

import numpy as np


def synthetic(n=2000, clusters=8, deg=6, d=32, seed=0):
  """Clustered graph: edges mostly intra-cluster, features noisy."""
  rng = np.random.default_rng(seed)
  cl = np.arange(n) % clusters
  rows = np.repeat(np.arange(n), deg)
  same = np.where(rng.random(n * deg) < 0.85,
                  (rows + clusters * rng.integers(1, n // clusters,
                                                  n * deg)) % n,
                  rng.integers(0, n, n * deg))
  # faint cluster direction in noisy features (the structural signal
  # alone is weak for a dot-product objective on random features)
  proto = rng.normal(0, 1, (clusters, d)).astype(np.float32)
  feats = (0.3 * proto[cl]
           + rng.standard_normal((n, d)).astype(np.float32))
  return rows, same, feats, cl


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--epochs', type=int, default=4)
  ap.add_argument('--batch-size', type=int, default=32)
  args = ap.parse_args()

  import jax
  import optax
  from graphlearn_tpu.models import GraphSAGE
  from graphlearn_tpu.models.train import TrainState
  from graphlearn_tpu.parallel import (DistDataset, DistLinkNeighborLoader,
                                       make_dp_unsupervised_step,
                                       make_mesh, replicate)

  n_dev = len(jax.devices())
  mesh = make_mesh(n_dev)
  rows, cols, feats, cl = synthetic()
  n = len(cl)
  dds = DistDataset.from_full_graph(n_dev, rows, cols, node_feat=feats,
                                    num_nodes=n)
  loader = DistLinkNeighborLoader(
      dds, [5, 5], (rows, cols), neg_sampling='binary',
      batch_size=args.batch_size, shuffle=True, mesh=mesh, seed=0)

  model = GraphSAGE(hidden_features=64, out_features=32, num_layers=2)
  tx = optax.adam(1e-3)
  batch0 = next(iter(loader))
  single = jax.tree_util.tree_map(lambda v: v[0], batch0)
  params = model.init(jax.random.key(0), single.x, single.edge_index,
                      single.edge_mask)
  state = replicate(TrainState(params, tx.init(params), 0), mesh)
  step = make_dp_unsupervised_step(model.apply, tx, mesh)

  for epoch in range(args.epochs):
    t0 = time.monotonic()
    tot = cnt = 0
    for batch in loader:
      state, loss = step(state, batch)
      tot += float(loss)
      cnt += 1
    print(f'epoch {epoch}: link loss {tot / max(cnt, 1):.4f} '
          f'({time.monotonic() - t0:.2f}s, {cnt} steps x {n_dev} devices)')

  # embedding quality probe: intra-cluster pairs should score higher
  # than random pairs under the trained dot-product model
  # embed every node through a full-neighborhood batch per device slice
  from graphlearn_tpu.parallel import DistNeighborLoader
  nl = DistNeighborLoader(dds, [5, 5], np.arange(n),
                          batch_size=64, mesh=mesh)
  emb = np.zeros((n, 32), np.float32)
  new2old = dds.new2old
  for batch in nl:
    out = jax.vmap(
        lambda x, ei, em: model.apply(state.params, x, ei, em))(
        batch.x, batch.edge_index, batch.edge_mask)
    seeds = np.asarray(batch.batch)
    for p in range(seeds.shape[0]):
      v = seeds[p] >= 0
      emb[new2old[seeds[p][v]]] = np.asarray(out[p][:seeds.shape[1]])[v]
  rng = np.random.default_rng(1)
  a = rng.integers(0, n, 2000)
  b = rng.integers(0, n, 2000)
  same_cl = (cl[a] == cl[b])
  score = (emb[a] * emb[b]).sum(1)
  pos, neg = score[same_cl], score[~same_cl]
  auc = (pos[:, None] > neg[None, :]).mean()
  print(f'intra-vs-inter cluster AUC: {auc:.4f}')


if __name__ == '__main__':
  main()
