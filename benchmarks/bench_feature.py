"""Feature-lookup throughput (GB/s) across hot/cold split ratios.

Reference counterpart: `benchmarks/api/bench_feature.py:27-62` — gather
the features of each sampled batch's node set, timed alone, reported
as GB/s.  Sweeps ``split_ratio`` (1.0 = all HBM, like the reference's
DMA mode; lower = two-tier with host gathers) and the Pallas DMA
kernel vs the XLA gather on the hot tier.

Usage::

    python benchmarks/bench_feature.py [--cpu] [--quick]

CAVEAT: this sweep times dispatch loops that end in
`block_until_ready` and has no analytic floor to check its walls
against.  Its numbers compare configs within one run; they are not a
benchmark of record (ROADMAP S0 replaces the harness).
"""
import argparse
import os
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
  ap.add_argument('--overlap-only', action='store_true',
                  help='skip the lookup sweep; run only the prefetch '
                       'overlap measurement')
  args = ap.parse_args()

  import jax
  if args.cpu:
    jax.config.update('jax_platforms', 'cpu')
  from graphlearn_tpu.data import Dataset, sort_by_in_degree
  from graphlearn_tpu.sampler import NeighborSampler, NodeSamplerInput

  n = 200_000 if args.quick else 1_000_000
  iters = 5 if args.quick else 20
  rows, cols = build_graph(n)
  feats = np.random.default_rng(0).standard_normal(
      (n, args.dim)).astype(np.float32)
  rng = np.random.default_rng(1)

  if not args.overlap_only:
    # sampled node sets at the flagship config drive the lookups
    ds0 = Dataset().init_graph((rows, cols), layout='COO', num_nodes=n)
    sampler = NeighborSampler(ds0.get_graph(), [15, 10, 5], seed=0)
    node_sets = []
    for _ in range(iters):
      seeds = rng.integers(0, n, 1024).astype(np.int32)
      out = sampler.sample_from_nodes(NodeSamplerInput(node=seeds))
      node_sets.append(np.asarray(out.node))

  # legacy lookup sweep runs cache-OFF so its rows stay comparable
  # across bench rounds (the r10 cache sweep below measures budgets)
  os.environ['GLT_COLD_CACHE_ROWS'] = '0'
  for split_ratio in (() if args.overlap_only else (1.0, 0.5, 0.2)):
    for pallas in ((True, False) if split_ratio == 1.0 else (False,)):
      os.environ['GLT_PALLAS'] = '1' if pallas else '0'
      ds = Dataset().init_graph((rows, cols), layout='COO', num_nodes=n)
      ds.init_node_features(
          feats,
          sort_func=sort_by_in_degree if split_ratio < 1.0 else None,
          split_ratio=split_ratio)
      feat = ds.get_node_feature()
      # warm every node set once: the two-tier path buckets its compact
      # cold buffer by power-of-two size, so different sets may hit
      # different compiled variants — compiles must not land in the timer
      for ns in node_sets:
        feat[ns].block_until_ready()
      nbytes = 0
      with Timer() as t:
        res = None
        for ns in node_sets:
          res = feat[ns]
          nbytes += res.size * res.dtype.itemsize
        res.block_until_ready()
      emit('feature_lookup_gbps', nbytes / t.dt / 1e9, 'GB/s',
           split_ratio=split_ratio,
           impl=('pallas' if pallas else 'xla'),
           platform=jax.devices()[0].platform)
  os.environ.pop('GLT_PALLAS', None)

  # -- cold-cache budget sweep (r10): hit rate vs HBM spend --------------
  # The same sampled node sets against the split_ratio=0.2 store, with
  # the HBM victim cache (`data.cold_cache`) at 0 / 5% / 15% of the
  # cold rows — the BENCH_ARTIFACT row behind the "how much cache buys
  # how many hits" tradeoff (benchmarks/README "Cold-tier cache").
  # Timed pass runs WARM (cache populated by the warmup pass), so the
  # hit rate is the steady-state epoch>=2 number; stats reset between.
  if not args.overlap_only:
    split = 0.2
    cold_rows = n - int(round(n * split))
    for frac in (0.0, 0.05, 0.15):
      budget = int(cold_rows * frac)
      os.environ['GLT_COLD_CACHE_ROWS'] = str(budget)
      ds = Dataset().init_graph((rows, cols), layout='COO', num_nodes=n)
      ds.init_node_features(feats, sort_func=sort_by_in_degree,
                            split_ratio=split)
      feat = ds.get_node_feature()
      for ns in node_sets:
        feat[ns].block_until_ready()
      cache = feat._cold_cache
      if cache is not None:
        cache.stats.__init__()                    # steady-state window
      feat.cold_stats['lookups'] = 0
      feat.cold_stats['cold_lookups'] = 0
      nbytes = 0
      with Timer() as t:
        res = None
        for ns in node_sets:
          res = feat[ns]
          nbytes += res.size * res.dtype.itemsize
        res.block_until_ready()
      cold = max(feat.cold_stats['cold_lookups'], 1)
      hits = cache.stats.hits if cache is not None else 0
      emit('feature_cold_cache_gbps', nbytes / t.dt / 1e9, 'GB/s',
           split_ratio=split, cache_rows=budget,
           budget_frac=frac,
           cache_hit_rate=round(hits / cold, 4),
           cold_lookups=feat.cold_stats['cold_lookups'],
           admits=cache.stats.admits if cache is not None else 0,
           evicts=cache.stats.evicts if cache is not None else 0,
           platform=jax.devices()[0].platform)
    os.environ.pop('GLT_COLD_CACHE_ROWS', None)
  else:
    os.environ.pop('GLT_COLD_CACHE_ROWS', None)

  # -- cold-path overlap: prefetch=2 vs synchronous loader ---------------
  # The batch loop alternates a device compute step with the loader's
  # cold gather + transfer; double buffering should hide most of the
  # loader's host time behind the compute (the UVA-overlap parity gap,
  # `csrc/cuda/unified_tensor.cu:202+`).
  from graphlearn_tpu.loader import NeighborLoader
  import jax.numpy as jnp

  @jax.jit
  def compute(x):
    for _ in range(8):
      x = jnp.tanh(x @ x.T) @ x
    return x

  ds = Dataset().init_graph((rows, cols), layout='COO', num_nodes=n)
  ds.init_node_features(feats, sort_func=sort_by_in_degree,
                        split_ratio=0.2)
  ds.init_node_labels((np.arange(n) % 4).astype(np.int32))
  seeds = rng.integers(0, n, 1024 * (4 if args.quick else 16))
  # every timed pass below covers the SAME n_timed batches (the first
  # batch of each epoch is consumed untimed as warmup/compile)
  n_timed = len(seeds) // 1024 - 1

  # loader-only pass: the host+transfer time prefetch should hide —
  # measured FIRST and directly (deriving it from a subtraction is not
  # robust to run-to-run variance between passes)
  loader = NeighborLoader(ds, [15, 10], seeds, batch_size=1024,
                          shuffle=True, seed=0)
  it = iter(loader)
  b0 = next(it)
  b0.x.block_until_ready()
  with Timer() as t:
    b = None
    for b in it:
      b.x.block_until_ready()
  loader_time = t.dt

  # calibrate device compute to ~the per-batch loader time, so the
  # pipeline has comparable stages and the overlap claim is testable
  x0 = b0.x[:512]
  compute(x0).block_until_ready()
  with Timer() as t:
    compute(x0).block_until_ready()
  reps = max(1, int(loader_time / n_timed / max(t.dt, 1e-6)))

  def step(x):
    for _ in range(reps):
      x = compute(x)
    return x

  with Timer() as t:
    out = None
    for _ in range(n_timed):
      out = step(x0)
    out.block_until_ready()
  compute_time = t.dt

  times = {}
  for depth in (0, 2):
    loader = NeighborLoader(ds, [15, 10], seeds, batch_size=1024,
                            shuffle=True, seed=0, prefetch=depth)
    it = iter(loader)
    b = next(it)
    step(b.x[:512]).block_until_ready()
    with Timer() as t:
      out = None
      for b in it:
        out = step(b.x[:512])
      out.block_until_ready()
    times[depth] = t.dt
  # perfect overlap drives total from L + C to max(L, C): the
  # hideable span is min(L, C)
  hideable = min(loader_time, compute_time)
  hidden = (times[0] - times[2]) / max(hideable, 1e-9)
  emit('feature_prefetch_overlap', min(hidden, 1.0) * 100,
       '% hideable time hidden',
       sync_s=round(times[0], 4), prefetch_s=round(times[2], 4),
       loader_s=round(loader_time, 4),
       compute_s=round(compute_time, 4),
       platform=jax.devices()[0].platform)


if __name__ == '__main__':
  main()
