"""Compile-time accounting for the mesh programs.

A pod-scale program whose compile takes tens of minutes per
(shape, P) config is a real deployment cost: this tool measures the
wall of `jit(...).lower(...).compile()` for the three big mesh
programs — the per-batch distributed step, the DP train step, and the
whole-epoch `FusedDistEpoch` scan (with/without remat) — across batch
sizes, printing one JSON line per config so the numbers are
machine-comparable across rounds.  The root `bench.py` tracks the
same quantities in the artifact (`compile_secs`,
`fused_compile_secs`, dist `compile_secs`); this is the standalone
sweep for locating the knee.

Usage::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/bench_compile.py [--batches 128,512] [--steps 2]
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks.common import build_graph

NODES = 200_000
DIM = 64
CLASSES = 47
FANOUT = [15, 10, 5]


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--batches', default='128,512')
  ap.add_argument('--steps', type=int, default=2,
                  help='scan length for the fused epoch (compile time '
                       'must not depend on it — a scan compiles its '
                       'body once)')
  ap.add_argument('--skip-fused', action='store_true')
  args = ap.parse_args()

  import jax
  import optax
  from graphlearn_tpu.models import GraphSAGE, create_train_state
  from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                       FusedDistEpoch, local_batch_piece,
                                       make_mesh,
                                       make_dp_supervised_step,
                                       replicate)

  num_parts = len(jax.devices())
  mesh = make_mesh(num_parts)
  platform = jax.devices()[0].platform
  rows, cols = build_graph(NODES)
  rng = np.random.default_rng(0)
  feats = rng.random((NODES, DIM), dtype=np.float32)
  labels = rng.integers(0, CLASSES, NODES).astype(np.int32)
  ds = DistDataset.from_full_graph(num_parts, rows, cols,
                                   node_feat=feats, node_label=labels,
                                   num_nodes=NODES)
  model = GraphSAGE(hidden_features=256, out_features=CLASSES,
                    num_layers=3)
  tx = optax.adam(3e-3)

  def rec(kind, batch, secs, **extra):
    print(json.dumps({'metric': 'compile_secs', 'kind': kind,
                      'batch': batch, 'num_parts': num_parts,
                      'fanout': FANOUT, 'platform': platform,
                      'value': round(secs, 1), **extra}), flush=True)

  for batch in [int(b) for b in args.batches.split(',')]:
    seeds = rng.permutation(NODES)[:batch * num_parts * args.steps]
    loader = DistNeighborLoader(ds, FANOUT, seeds, batch_size=batch,
                                shuffle=True, mesh=mesh, seed=0)
    # per-batch dist step (sampler + collection, ONE SPMD program)
    t0 = time.perf_counter()
    b0 = next(iter(loader))
    b0.x.block_until_ready()
    rec('dist_step', batch, time.perf_counter() - t0)
    # DP train step
    b0_local = local_batch_piece(b0, num_parts)
    # same init key across loop variants BY DESIGN: compile timing
    # must compare identical programs  # glint: disable=rng-discipline
    state, apply_fn = create_train_state(model, jax.random.key(0),
                                         b0_local, tx)
    step = make_dp_supervised_step(apply_fn, tx, batch, mesh)
    state_r = replicate(state, mesh)
    t0 = time.perf_counter()
    state_r, _, _ = step(state_r, b0)
    jax.tree_util.tree_leaves(state_r.params)[0].block_until_ready()
    rec('dp_step', batch, time.perf_counter() - t0)
    if args.skip_fused:
      continue
    for remat, fastc in ((False, False), (True, False), (True, True)):
      fused = FusedDistEpoch(ds, FANOUT, seeds, apply_fn, tx,
                             batch_size=batch, mesh=mesh, shuffle=True,
                             seed=0, remat=remat, fast_compile=fastc)
      # glint: disable=rng-discipline — same rationale as above
      st, _ = create_train_state(model, jax.random.key(1), b0_local, tx)
      st = replicate(st, mesh)
      t0 = time.perf_counter()
      st, _ = fused.run(st)
      jax.tree_util.tree_leaves(st.params)[0].block_until_ready()
      rec('fused_dist_epoch', batch, time.perf_counter() - t0,
          steps=len(fused), remat=remat, fast_compile=fastc)


if __name__ == '__main__':
  main()
