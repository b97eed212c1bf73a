"""End-to-end loader throughput: batches/s and sampled-edges/s
including collation (features + labels + batch assembly).

Reference counterpart: `benchmarks/api/bench_dist_neighbor_loader.py`'s
single-node half — the number the training loop actually sees.

Usage::

    python benchmarks/bench_loader.py [--cpu] [--quick]

CAVEAT: this sweep times dispatch loops that end in
`block_until_ready` and has no analytic floor to check its walls
against.  Its numbers compare configs within one run; they are not a
benchmark of record (ROADMAP S0 replaces the harness).
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks.common import Timer, build_graph, emit


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--cpu', action='store_true')
  ap.add_argument('--quick', action='store_true')
  ap.add_argument('--dim', type=int, default=128)
  args = ap.parse_args()

  import jax
  if args.cpu:
    jax.config.update('jax_platforms', 'cpu')
  from graphlearn_tpu.data import Dataset
  from graphlearn_tpu.loader import NeighborLoader

  n = 200_000 if args.quick else 1_000_000
  rows, cols = build_graph(n)
  feats = np.random.default_rng(0).standard_normal(
      (n, args.dim)).astype(np.float32)
  labels = (np.arange(n) % 47).astype(np.int32)
  ds = (Dataset()
        .init_graph((rows, cols), layout='COO', num_nodes=n)
        .init_node_features(feats, split_ratio=1.0)
        .init_node_labels(labels))

  seeds = np.random.default_rng(1).permutation(n)[:20_000 if args.quick
                                                  else 100_000]
  for batch_size in (512, 1024):
    loader = NeighborLoader(ds, [15, 10, 5], seeds, batch_size=batch_size,
                            shuffle=True, seed=0)
    import jax.numpy as jnp
    b = next(iter(loader))          # compile
    b.x.block_until_ready()
    batches = 0
    # device-side accumulator: no per-batch host sync (which would
    # deflate throughput) and no batch retention (which would grow
    # device memory across the epoch)
    edges_dev = jnp.zeros((), jnp.int32)  # ~100k-seed epochs: <2^31 edges
    with Timer() as t:
      last = None
      for b in loader:
        last = b
        batches += 1
        edges_dev = edges_dev + b.edge_mask.sum()
      last.x.block_until_ready()
      edges_dev.block_until_ready()
    edges = int(edges_dev)
    emit('loader_batches_per_sec', batches / t.dt, 'batches/s',
         batch=batch_size, platform=jax.devices()[0].platform)
    emit('loader_edges_per_sec', edges / t.dt / 1e6, 'M edges/s',
         batch=batch_size, platform=jax.devices()[0].platform)


if __name__ == '__main__':
  main()
