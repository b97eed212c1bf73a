"""Measure the Pallas aligned-overfetch CSR window gather against the
XLA window gather on the chip (turn the "XLA beats Pallas for
sampling" design assertion into a measurement).

Method: device-resident inputs, vary seeds with fold_in-free host
rotation staged up front, dispatch N async then block once, best of 3
windows.

Usage (one process per chip)::

    python benchmarks/bench_pallas_window.py [--quick]
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks.common import build_graph_csr, emit


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--quick', action='store_true')
  ap.add_argument('--batch', type=int, default=8192)
  ap.add_argument('--window', type=int, default=128)
  ap.add_argument('--iters', type=int, default=30)
  args = ap.parse_args()

  import jax
  import jax.numpy as jnp
  from graphlearn_tpu.ops.pallas_window import (csr_window_gather,
                                                prepare_window_table,
                                                xla_window_gather)
  from graphlearn_tpu.ops.neighbor import sample_one_hop

  n = 500_000 if args.quick else 2_449_029
  indptr, indices, _ = build_graph_csr(n)
  indices = jnp.asarray(indices.astype(np.int32))
  indptr_d = jnp.asarray(indptr.astype(np.int32))
  rng = np.random.default_rng(0)
  iters = args.iters
  b, w = args.batch, args.window
  seed_sets = [jnp.asarray(rng.integers(0, n, b).astype(np.int32))
               for _ in range(iters)]
  start_sets = [indptr_d[s] for s in seed_sets]
  jax.block_until_ready(start_sets)
  bytes_per = b * w * 4

  def timeit(fn, inputs):
    fn(inputs[0]).block_until_ready()          # compile
    best = float('inf')
    for _ in range(3):
      t0 = time.perf_counter()
      outs = [fn(x) for x in inputs]
      outs[-1].block_until_ready()
      best = min(best, time.perf_counter() - t0)
    return best

  dt_x = timeit(lambda s: xla_window_gather(indices, s, w), start_sets)
  # repack ONCE outside the timing loop: the O(E) table build must not
  # masquerade as kernel time
  table = prepare_window_table(indices)
  jax.block_until_ready(table[0])
  dt_p, best_tile = float('inf'), None
  for tile in (8, 16, 32, 64):
    dt = timeit(lambda s: csr_window_gather(indices, s, w, tile=tile,
                                            interpret=False,
                                            table=table),
                start_sets)
    if dt < dt_p:
      dt_p, best_tile = dt, tile
  # context: the full sampler step (window + gumbel top-k + mask)
  key = jax.random.key(0)
  dt_full = timeit(
      lambda s: sample_one_hop(indptr_d, indices, s, 15, key).nbrs,
      seed_sets)

  emit('csr_window_gather_xla', iters * bytes_per / dt_x / 1e9, 'GB/s',
       batch=b, window=w, num_nodes=n,
       platform=jax.devices()[0].platform)
  emit('csr_window_gather_pallas_dma', iters * bytes_per / dt_p / 1e9,
       'GB/s', batch=b, window=w, best_tile=best_tile,
       overfetch_bytes_per_seed=2 * 4096,
       speedup_vs_xla=round(dt_x / dt_p, 3))
  emit('sample_one_hop_full', iters * b / dt_full / 1e6, 'M seeds/s',
       k=15, note='window gather + gumbel topk + mask, for context')


if __name__ == '__main__':
  main()
