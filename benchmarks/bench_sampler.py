"""Neighbor-sampling throughput across batch sizes and fanouts.

Reference counterpart: `benchmarks/api/bench_sampler.py` — metric
"Sampled Edges per secs (M)".  The root `bench.py` runs the single
flagship config; this sweeps the grid the reference's scale-up plot
covers.

Usage::

    python benchmarks/bench_sampler.py [--cpu] [--quick]
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks.common import NUM_NODES, Timer, build_graph, emit


CONFIGS = [((15, 10, 5), 512), ((15, 10, 5), 1024), ((15, 10, 5), 4096),
           ((10, 10), 512), ((10, 10), 1024), ((10, 10), 4096),
           ((25, 10), 512), ((25, 10), 1024), ((25, 10), 4096)]


def run_one(fanout, batch, quick: bool, cpu: bool):
  import jax
  if cpu:
    jax.config.update('jax_platforms', 'cpu')
  from graphlearn_tpu.data import Dataset
  from graphlearn_tpu.sampler import NeighborSampler

  import jax.numpy as jnp
  from benchmarks.common import make_sample_burst, sample_window_bytes

  n = 200_000 if quick else None
  iters = 5 if quick else 20
  rows, cols = (build_graph(n) if n else build_graph())
  n = n or int(max(rows.max(), cols.max())) + 1
  ds = Dataset().init_graph((rows, cols), layout='COO', num_nodes=n)
  g = ds.get_graph()
  g.lazy_init()
  rng = np.random.default_rng(1)
  sampler = NeighborSampler(g, list(fanout), seed=0)
  node_cap = sampler.node_capacity(batch)
  seeds_all = jnp.asarray(
      rng.integers(0, n, (iters, batch)).astype(np.int32))

  # pull protocol (see bench.py): the whole burst is ONE scan program
  # — a per-batch dispatch loop would time host dispatch, not the
  # sampler — and each wall ends in a value pull.  The first execution
  # of a fresh executable carries its program load, so time two: keep
  # the second if it clears the analytic window-bytes floor, else fall
  # back to the first (overstated by the load cost, flagged).
  burst = make_sample_burst(fanout, node_cap, iters)
  comp = jax.jit(burst).lower(g.indptr, g.indices, seeds_all,
                              jax.random.key(5)).compile()
  with Timer() as t1:
    edges = int(comp(g.indptr, g.indices, seeds_all,
                     jax.random.key(6)))
  with Timer() as t2:
    edges = int(comp(g.indptr, g.indices, seeds_all,
                     jax.random.key(7)))
  platform = jax.devices()[0].platform
  floor = (iters * sample_window_bytes(batch, fanout) / 819e9
           if platform == 'tpu' else 0.0)
  suspect = t2.dt < floor
  dt = t1.dt if suspect else t2.dt
  emit('sampler_edges_per_sec', edges / dt / 1e6, 'M edges/s',
       fanout=list(fanout), batch=batch,
       first_exec_secs=round(t1.dt, 4), steady_secs=round(t2.dt, 4),
       floor_secs=round(floor, 4), suspect_elision=bool(suspect),
       platform=platform)


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--cpu', action='store_true')
  ap.add_argument('--quick', action='store_true',
                  help='small graph, fewer iters')
  ap.add_argument('--one', type=str, default=None,
                  help='internal: "15,10,5:1024" runs one config inline')
  args = ap.parse_args()

  if args.one:
    fan, batch = args.one.split(':')
    run_one(tuple(int(k) for k in fan.split(',')), int(batch),
            args.quick, args.cpu)
    return

  from benchmarks.common import run_in_fresh_process
  build_graph(200_000 if args.quick else NUM_NODES)   # warm the cache
  failed = 0
  for fanout, batch in CONFIGS:
    extra = (['--quick'] if args.quick else []) + \
            (['--cpu'] if args.cpu else [])
    ok = run_in_fresh_process(
        __file__, ['--one', ','.join(map(str, fanout)) + f':{batch}']
        + extra)
    failed += not ok
  if failed:
    print(f'{failed}/{len(CONFIGS)} configs failed', file=sys.stderr)
    sys.exit(1)


if __name__ == '__main__':
  main()
