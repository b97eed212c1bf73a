"""Distributed loader throughput + exchange-capacity validation.

Reference counterpart: `benchmarks/api/bench_dist_neighbor_loader.py`
(2 nodes x 2 GPUs, RPC sampling) — here the mesh-collective engine:
graph sharded over N devices, per-device seed shards, cross-partition
neighbor exchange on ICI (or the virtual CPU mesh).

Usage::

    # virtual 8-device mesh anywhere:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/bench_dist_loader.py --quick

    # capacity sweep: P in {8,16,32} x {exact, slack 2.0} at the
    # reference workload (batch 1024, fanout [15,10,5]); each config
    # in its own subprocess with its own virtual mesh size, printing
    # padding-waste %% and drop-rate %% from the exchange telemetry:
    python benchmarks/bench_dist_loader.py --capacity-sweep
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks.common import (Timer, build_graph, cpu_mesh_env, emit,
                               run_in_fresh_process)


def capacity_worker(num_parts: int, slack, batch: int, fanout,
                    num_nodes: int):
  """One capacity config on a ``num_parts``-device virtual mesh —
  measures the frontier-capacity math: hop-3
  frontier = batch * 15 * 10 ids/device exchanged under a 2x-balanced
  cap vs exact."""
  import jax
  from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                       make_mesh)
  assert len(jax.devices()) == num_parts, (
      f'mesh env failed: {len(jax.devices())} devices != {num_parts}')
  rows, cols = build_graph(num_nodes)
  ds = DistDataset.from_full_graph(num_parts, rows, cols,
                                   num_nodes=num_nodes)
  seeds = np.random.default_rng(1).integers(
      0, num_nodes, batch * num_parts * 3)
  loader = DistNeighborLoader(ds, fanout, seeds, batch_size=batch,
                              shuffle=True, mesh=make_mesh(num_parts),
                              collect_features=False, seed=0,
                              exchange_slack=slack)
  it = iter(loader)
  b = next(it)                    # compile + warm
  b.node.block_until_ready()
  with Timer() as t:
    n_batches = 0
    last = None
    for b in it:
      last = b
      n_batches += 1
    last.node.block_until_ready()
  st = loader.sampler.exchange_stats(tick_metrics=False)
  sent = st['dist.frontier.offered'] - st['dist.frontier.dropped']
  waste = 100.0 * (1 - sent / max(st['dist.frontier.slots'], 1))
  drop = 100.0 * st['dist.frontier.dropped'] / max(
      st['dist.frontier.offered'], 1)
  emit('dist_exchange_capacity',
       n_batches * batch * num_parts / t.dt / 1e3, 'K seeds/s',
       num_parts=num_parts,
       slack=('exact' if slack is None else slack), batch=batch,
       fanout=list(fanout), padding_waste_pct=round(waste, 2),
       drop_rate_pct=round(drop, 3),
       frontier_offered=st['dist.frontier.offered'],
       frontier_dropped=st['dist.frontier.dropped'])


def subgraph_worker(num_parts: int, hop_chunk, batch: int,
                    num_nodes: int):
  """SEAL-at-scale envelope: induced-subgraph
  loader with the full-window hop CHUNKED, so the widest all_to_all is
  ``[P, chunk, max_degree]`` regardless of closure size — the config
  that aborted at P>=16 when the window spanned the whole node table."""
  import jax
  from graphlearn_tpu.parallel import (DistDataset, DistSubGraphLoader,
                                       make_mesh)
  assert len(jax.devices()) == num_parts
  rows, cols = build_graph(num_nodes)
  ds = DistDataset.from_full_graph(num_parts, rows, cols,
                                   num_nodes=num_nodes)
  seeds = np.random.default_rng(1).integers(0, num_nodes,
                                            batch * num_parts * 3)
  max_degree = int(np.diff(ds.graph.indptr, axis=1).max())
  loader = DistSubGraphLoader(ds, [5, 5], seeds, batch_size=batch,
                              shuffle=True, mesh=make_mesh(num_parts),
                              collect_features=False, seed=0,
                              hop_chunk=hop_chunk)
  node_cap = loader.sampler.node_capacity(batch)
  it = iter(loader)
  b = next(it)
  b.node.block_until_ready()
  with Timer() as t:
    n_batches = 0
    last = None
    for b in it:
      last = b
      n_batches += 1
    last.node.block_until_ready()
  chunk = hop_chunk or node_cap
  emit('dist_subgraph_capacity',
       n_batches * batch * num_parts / t.dt, 'seeds/s',
       num_parts=num_parts,
       hop_chunk=('none' if hop_chunk is None else hop_chunk),
       node_cap=node_cap, max_degree=max_degree, batch=batch,
       window_exchange_width=num_parts * min(chunk, node_cap)
       * max_degree)


#: IGBH-large shapes for the memory envelope (PUBLIC IGB paper
#: figures, approximate — exact counts come from the npy headers when
#: the dataset is on disk; every type carries 1024-dim f32 features,
#: `reference examples/igbh/download_igbh_large.sh`).
IGBH_LARGE_SHAPES = {
    'nodes': {'paper': 100e6, 'author': 100e6, 'fos': 0.7e6,
              'institute': 0.03e6, 'journal': 0.05e6,
              'conference': 0.005e6},
    'feat_dim': 1024,
    'edges': 2.2e9,           # directed, pre-reverse; x2 with reverse
}


def memory_envelope(num_parts: int = 128, hbm_gb: float = 95.0,
                    split_ratio: float = 0.25, feat_bytes: int = 4):
  """BASELINE north-star check: does IGBH-large fit a
  v5p-128 pod under the host-local tiered layout?  Array-residency
  bytes per chip, analytic from `IGBH_LARGE_SHAPES`:

    * features: ``split_ratio`` of each type's rows in HBM (hotness
      prefix), the rest in that host's DRAM (`DistFeature.cold_local`);
    * topology: CSR int32 indices + per-part indptr, by-src sharded,
      x2 for the reverse-edge types the RGNN recipes add;
    * labels/books: int32 paper labels + O(P) range books (negligible).

  Exchange/activation peaks ride on top but are capacity-bounded
  (``exchange_slack`` x balanced share; the [P, C] buffers at batch
  1024, fanout [15,10,5] are tens of MB — `capacity_sweep` measures
  them).  Returns the per-chip table; `--memory-envelope` prints it.
  """
  n_total = sum(IGBH_LARGE_SHAPES['nodes'].values())
  d = IGBH_LARGE_SHAPES['feat_dim']
  e = IGBH_LARGE_SHAPES['edges'] * 2          # with reverse etypes
  feat_total = n_total * d * feat_bytes
  feat_hbm_chip = feat_total * split_ratio / num_parts
  feat_host_chip = feat_total * (1 - split_ratio) / num_parts
  topo_chip = (e * 4) / num_parts + n_total * 4 / num_parts
  labels_chip = IGBH_LARGE_SHAPES['nodes']['paper'] * 4 / num_parts
  hbm_chip = feat_hbm_chip + topo_chip + labels_chip
  return {
      'config': f'IGBH-large on v5p-{num_parts} '
                f'(split_ratio={split_ratio}, f32 feats)',
      'nodes_M': round(n_total / 1e6, 1),
      'feat_total_GB': round(feat_total / 1e9, 1),
      'per_chip_feat_hbm_GB': round(feat_hbm_chip / 1e9, 2),
      'per_chip_topo_GB': round(topo_chip / 1e9, 2),
      'per_chip_hbm_GB': round(hbm_chip / 1e9, 2),
      'per_chip_hbm_frac_of_v5p': round(hbm_chip / (hbm_gb * 1e9), 3),
      'per_host_cold_dram_GB': round(feat_host_chip * 4 / 1e9, 1),
      'fits': bool(hbm_chip < 0.7 * hbm_gb * 1e9),
      'note': ('fully-HBM (split_ratio=1.0) also fits: '
               f'{round((feat_total + e * 4) / num_parts / 1e9, 1)} '
               f'GB/chip vs {hbm_gb} GB v5p HBM; the tiered layout is '
               'for bf16-less full-dim features plus headroom, and '
               'IGBH-full (~5.5x)'),
  }


def _epoch_exchange_rows(loader, epochs: int, batch: int,
                         num_parts: int):
  """Run ``epochs`` epochs, returning (n_seeds, per-epoch
  (waste_pct, drop_pct) rows) from the frontier exchange deltas."""
  rows = []
  n_seeds = 0
  b = None
  for _ in range(epochs):
    prev = loader.sampler.exchange_stats(tick_metrics=False)
    for b in loader:
      n_seeds += batch * num_parts
    st = loader.sampler.exchange_stats(tick_metrics=False)
    offered = (st['dist.frontier.offered']
               - prev['dist.frontier.offered'])
    dropped = (st['dist.frontier.dropped']
               - prev['dist.frontier.dropped'])
    slots = st['dist.frontier.slots'] - prev['dist.frontier.slots']
    rows.append((round(100.0 * (1 - (offered - dropped)
                                / max(slots, 1)), 2),
                 round(100.0 * dropped / max(offered, 1), 3)))
  if b is not None:
    import jax
    jax.block_until_ready(b)
  return n_seeds, rows


def _locality_comparison(num_parts: int, rows, cols, num_nodes: int,
                         batch: int, mesh, rng, epochs: int = 4,
                         dim: int = 256):
  """Locality-aware partitioning x exchange co-design probe (ISSUE 20).

  The envelope's headline homo run is featureless (frontier exchange
  only), so it cannot see the feature plane the locality work targets.
  This sub-run re-runs the same graph FEATURED (``collect_features=
  True`` — the feature attribution matrix ticks) under two arms that
  differ ONLY in the partitioner:

    * ``range``    — the historical seeded round-robin placement;
    * ``locality`` — the streaming edge-cut minimizer plus the full
      co-design: replica cache (hot remote rows served locally) and
      EWMA capacity retune at the epoch seam.

  Per-arm ``cross_partition_bytes_frac`` / ``seeds_per_sec`` are what
  the ``dist.locality.*`` regression guards read (headline = final
  epoch, after the EWMA retune recompile has settled).  The
  ``rename_equivalent`` bool replays the locality arm's relabel as an
  explicit-``node_pb`` build in the renamed id space and checks one
  epoch of batches byte-identical — the pure-rename contract.
  """
  import os
  import time
  import jax
  from graphlearn_tpu.parallel import DistDataset, DistNeighborLoader
  feats = np.random.default_rng(2).standard_normal(
      (num_nodes, dim)).astype(np.float32)
  seeds = rng.integers(0, num_nodes, batch * num_parts * 8)
  res = {}
  ds_loc = None
  for arm in ('range', 'locality'):
    saved = {k: os.environ.pop(k, None)
             for k in ('GLT_EXCHANGE_EWMA', 'GLT_PARTITIONER',
                       'GLT_LOCALITY_REPLICA_FRAC')}
    os.environ['GLT_EXCHANGE_EWMA'] = '1'   # both arms: same config
    try:
      ds = DistDataset.from_full_graph(
          num_parts, rows, cols, node_feat=feats, num_nodes=num_nodes,
          partitioner=arm,
          replica_frac=(0.35 if arm == 'locality' else None))
      loader = DistNeighborLoader(ds, [5, 5], seeds, batch_size=batch,
                                  shuffle=True, mesh=mesh,
                                  collect_features=True, seed=0,
                                  exchange_slack=1.25)
      if arm == 'locality':
        ds_loc = ds
      rates = []
      last = None
      nb = 0
      for ep in range(epochs):
        t0 = time.perf_counter()
        nb = 0
        for b in loader:
          last = b
          nb += 1
        jax.block_until_ready(last)
        rates.append(round(nb * batch * num_parts
                           / (time.perf_counter() - t0), 1))
      # headline rate: one re-timed window over the FINAL capacity
      # program (the early epochs pay compiles + the EWMA retune
      # recompiles; per-epoch batch counts are small enough that a
      # single epoch is noisy)
      t0 = time.perf_counter()
      for _ in range(2):
        for b in loader:
          last = b
      jax.block_until_ready(last)
      steady = round(2 * nb * batch * num_parts
                     / (time.perf_counter() - t0), 1)
      att = loader.sampler.attribution_stats(tick_metrics=False)
      st = loader.sampler.exchange_stats(tick_metrics=False)
      res[arm] = {
          'partitioner': getattr(ds, 'partitioner', arm),
          'cross_partition_bytes_frac':
              att['cross_partition_bytes_frac'],
          'cross_partition_ids_frac': att['cross_partition_ids_frac'],
          'locally_served_ids': att.get('locally_served_ids', 0),
          'seeds_per_sec': steady,
          'seeds_per_sec_by_epoch': rates,
          'drop_rate_pct': round(
              100.0 * st['dist.frontier.dropped']
              / max(st['dist.frontier.offered'], 1), 3),
          'feature_drop_rate_pct': round(
              100.0 * st['dist.feature.dropped']
              / max(st['dist.feature.offered'], 1), 3),
      }
    finally:
      for k, v in saved.items():
        if v is None:
          os.environ.pop(k, None)
        else:
          os.environ[k] = v
  res['locality_over_range_speedup'] = round(
      res['locality']['seeds_per_sec']
      / max(res['range']['seeds_per_sec'], 1e-9), 3)
  # pure-rename contract: rebuild the locality arm's placement as an
  # explicit node_pb over the ALREADY-relabeled edge list — the
  # relabel must come out the identity and one epoch byte-identical
  o2n, n2o = ds_loc.old2new, ds_loc.new2old
  pb_new = (np.searchsorted(ds_loc.graph.bounds, np.arange(num_nodes),
                            'right') - 1).astype(np.int32)
  # the twin must carry the SAME replica cache (hotness = in-degree,
  # expressed in its own id space): the masked gather changes which
  # ids compete for exchange slots, so a cache-less twin can drop
  # rows the replica arm serves locally
  ds_ren = DistDataset.from_full_graph(
      num_parts, o2n[rows], o2n[cols], node_feat=feats[n2o],
      num_nodes=num_nodes, node_pb=pb_new, replica_frac=0.35,
      hotness=np.bincount(o2n[cols], minlength=num_nodes))
  la = DistNeighborLoader(ds_loc, [5, 5], seeds, batch_size=batch,
                          shuffle=True, mesh=mesh,
                          collect_features=True, seed=0,
                          exchange_slack=1.25)
  lb = DistNeighborLoader(ds_ren, [5, 5], o2n[seeds], batch_size=batch,
                          shuffle=True, mesh=mesh,
                          collect_features=True, seed=0,
                          exchange_slack=1.25)
  equivalent = bool(np.array_equal(ds_ren.old2new,
                                   np.arange(num_nodes)))
  for ba, bb in zip(la, lb):
    for f in ('node', 'x', 'edge_index', 'batch'):
      if not np.array_equal(np.asarray(jax.device_get(getattr(ba, f))),
                            np.asarray(jax.device_get(getattr(bb, f)))):
        equivalent = False
    if not equivalent:
      break
  res['rename_equivalent'] = equivalent
  return res


def envelope_worker(num_parts: int, mode: str, batch: int,
                    num_nodes: int, epochs: int = 5):
  """Scale-envelope probe at ``num_parts`` VIRTUAL devices (past
  P=32): a deliberately tiny workload — the point is the
  PER-P exchange behavior (padding waste, drops, adaptive-slack
  convergence), not throughput, since 64-128 virtual devices
  oversubscribe this box's cores ~10x.  ``mode``: 'homo' (adaptive
  slack, several epochs so the controller can walk), 'hetero'
  (per-type exchanges, adaptive), 'seal' (chunked full-window
  subgraph hop).  Prints ONE JSON line.

  The headline ``padding_waste_pct`` / ``drop_rate_pct`` are the
  FINAL epoch's (the adaptive ladder's converged state — the steady
  state an IGBH-scale run lives in, and the same convention as the
  main dist row's ``waste_by_epoch[-1]``); the full trajectory and
  the run-cumulative figures ride alongside.  ``mode='homo'`` also
  re-runs one epoch per exchange layout (dense / compact / hier, all
  at the same static slack) so the artifact captures the layout
  comparison at this P.
  """
  import json
  import time
  import jax
  from graphlearn_tpu.parallel import make_mesh, resolve_layout
  assert len(jax.devices()) == num_parts, len(jax.devices())
  rows, cols = build_graph(num_nodes)
  rng = np.random.default_rng(1)
  mesh = make_mesh(num_parts)
  out = {'metric': 'dist_scale_envelope', 'num_parts': num_parts,
         'mode': mode, 'batch': batch, 'num_nodes': num_nodes}

  def make_homo_loader(layout=None, slack='adaptive'):
    from graphlearn_tpu.parallel import DistDataset, DistNeighborLoader
    ds = DistDataset.from_full_graph(num_parts, rows, cols,
                                     num_nodes=num_nodes)
    seeds = rng.integers(0, num_nodes, batch * num_parts * 2)
    return DistNeighborLoader(ds, [5, 5], seeds, batch_size=batch,
                              shuffle=True, mesh=mesh,
                              collect_features=False, seed=0,
                              exchange_slack=slack,
                              exchange_layout=layout)

  if mode == 'seal':
    from graphlearn_tpu.parallel import DistDataset, DistSubGraphLoader
    ds = DistDataset.from_full_graph(num_parts, rows, cols,
                                     num_nodes=num_nodes)
    seeds = rng.integers(0, num_nodes, batch * num_parts * 2)
    loader = DistSubGraphLoader(ds, [5, 5], seeds, batch_size=batch,
                                shuffle=True, mesh=mesh,
                                collect_features=False, seed=0,
                                hop_chunk=256)
    epochs = 1
  elif mode == 'hetero':
    from graphlearn_tpu.parallel import DistHeteroNeighborLoader
    from graphlearn_tpu.parallel.dist_hetero import DistHeteroDataset
    nu = num_nodes
    ni = num_nodes // 2
    ds = DistHeteroDataset.from_full_graph(
        num_parts,
        {('u', 'to', 'i'): (rows % nu, cols % ni),
         ('i', 'rev_to', 'u'): (cols % ni, rows % nu)},
        num_nodes_dict={'u': nu, 'i': ni})
    seeds = rng.integers(0, nu, batch * num_parts * 2)
    loader = DistHeteroNeighborLoader(ds, [5, 5], ('u', seeds),
                                      batch_size=batch, shuffle=True,
                                      mesh=mesh,
                                      collect_features=False, seed=0,
                                      exchange_slack='adaptive')
  else:
    loader = make_homo_loader()
  t0 = time.perf_counter()
  b = next(iter(loader))
  jax.block_until_ready(b)
  out['compile_secs'] = round(time.perf_counter() - t0, 1)
  t0 = time.perf_counter()
  n_seeds, ep_rows = _epoch_exchange_rows(loader, epochs, batch,
                                          num_parts)
  dt = time.perf_counter() - t0
  st = loader.sampler.exchange_stats(tick_metrics=False)
  sent = st['dist.frontier.offered'] - st['dist.frontier.dropped']
  out.update(
      # the active partitioner rides on every envelope row so regress
      # baselines are never compared across a partitioner change
      # (ISSUE 20; the `same:` opt on the dist.locality.* guards)
      partitioner=getattr(getattr(loader, 'ds', None), 'partitioner',
                          None),
      seeds_per_sec=round(n_seeds / dt, 1),
      # headline = converged (final-epoch) exchange state; the
      # trajectory + run-cumulative figures follow
      padding_waste_pct=ep_rows[-1][0],
      drop_rate_pct=ep_rows[-1][1],
      padding_waste_pct_by_epoch=[r[0] for r in ep_rows],
      drop_rate_pct_by_epoch=[r[1] for r in ep_rows],
      padding_waste_pct_cum=round(
          100.0 * (1 - sent / max(st['dist.frontier.slots'], 1)), 2),
      drop_rate_pct_cum=round(100.0 * st['dist.frontier.dropped']
                              / max(st['dist.frontier.offered'], 1),
                              3),
      slack_final=getattr(loader.sampler, 'exchange_slack', None),
      exchange_layout=resolve_layout(
          getattr(loader.sampler, 'exchange_layout', None), num_parts))
  if mode == 'homo':
    # per-partition traffic attribution (ISSUE 16): the P×P exchange
    # byte matrix + hot-range table from the run above — the envelope
    # is where locality regressions are cheapest to catch, and the
    # regress gate guards the P=16 row's headline fractions
    try:
      out['attribution'] = loader.sampler.attribution_stats(
          tick_metrics=False)
    except Exception as e:          # never sink the envelope row
      out['attribution_error'] = f'{type(e).__name__}: {e}'
    # dense-vs-compacted-vs-hierarchical at the same static slack:
    # one epoch each, fresh loader (fresh compile) per layout
    comparison = {}
    for layout in ('dense', 'compact', 'hier'):
      ll = make_homo_loader(layout=layout, slack=1.25)
      _, lrows = _epoch_exchange_rows(ll, 1, batch, num_parts)
      lst = ll.sampler.exchange_stats(tick_metrics=False)
      comparison[layout] = {
          'padding_waste_pct': lrows[-1][0],
          'drop_rate_pct': lrows[-1][1],
          'frontier_slots': lst['dist.frontier.slots'],
          'frontier_offered': lst['dist.frontier.offered'],
      }
    out['layouts'] = comparison
    # locality-aware partitioning x exchange co-design (ISSUE 20):
    # range-vs-locality on the SAME graph, featured so the feature
    # attribution plane ticks — feeds the dist.locality.* guards
    try:
      out['locality'] = _locality_comparison(num_parts, rows, cols,
                                             num_nodes, batch, mesh,
                                             rng)
    except Exception as e:          # never sink the envelope row
      out['locality_error'] = f'{type(e).__name__}: {e}'
  # the BASELINE north-star memory check rides along on every
  # envelope row
  out['memory_envelope_v5p128'] = memory_envelope(128)
  print(json.dumps(out), flush=True)
  from benchmarks.common import tee_record
  tee_record(out)


def _chaos_server_proc(port_q, num_nodes, dim, jsonl, worker_plan):
  """Sampling-server process for the chaos smoke (spawn-started so it
  inherits THIS env assignment — its producer workers read the kill
  plan from GLT_FAULT_PLAN)."""
  import os
  if worker_plan:
    os.environ['GLT_FAULT_PLAN'] = worker_plan
  os.environ['GLT_TELEMETRY_JSONL'] = jsonl
  import numpy as np
  from graphlearn_tpu.distributed import (HostDataset, init_server,
                                          wait_and_shutdown_server)
  from graphlearn_tpu.telemetry import recorder
  recorder.enable(jsonl)
  rows, cols = build_graph(num_nodes)
  feats = np.random.default_rng(0).standard_normal(
      (num_nodes, dim)).astype(np.float32)
  ds = HostDataset.from_coo(rows, cols, num_nodes, node_features=feats)
  srv = init_server(num_servers=1, num_clients=1, rank=0, dataset=ds,
                    host='127.0.0.1', port=0)
  port_q.put(srv.port)
  wait_and_shutdown_server(timeout=600)


def chaos_smoke(batch: int = 64, num_nodes: int = 5000, dim: int = 32,
                epochs: int = 3):
  """Resilience smoke on the HOST server->client path (ISSUE 4): time
  fault-free epochs WITH the retry/idempotency layer on (the
  ``dist.chaos.fault_free_seeds_per_sec`` regression guard — the
  resilience layer must not tax the hot path), then run one chaos
  epoch (worker kill + connection drop + delayed fetch) and assert
  exact batch accounting.  Prints ONE JSON row."""
  import json
  import multiprocessing as mp
  import os
  import tempfile
  import time
  import numpy as np
  from graphlearn_tpu import native
  if not native.available():
    row = {'metric': 'dist_chaos_smoke', 'skipped': True,
           'reason': 'native lib unavailable'}
    print(json.dumps(row), flush=True)
    return
  from graphlearn_tpu.distributed import (
      DistNeighborLoader, RemoteDistSamplingWorkerOptions, init_client,
      shutdown_client)
  from graphlearn_tpu.distributed.dist_loader import DistLoader
  from graphlearn_tpu.telemetry import recorder
  from graphlearn_tpu.testing import chaos

  n_seeds = batch * 32
  n_batches = n_seeds // batch
  chaos_epoch = epochs             # epochs 0..epochs-1 fault-free
  jsonl = os.path.join(tempfile.mkdtemp(prefix='glt_chaos_'),
                       'server.jsonl')
  # the kill fires only in the chaos epoch (epoch filter) and only in
  # the ORIGINAL worker incarnation (generation filter), so the timed
  # fault-free epochs run untouched and the supervisor's replacement
  # worker survives to finish the replay
  worker_plan = (f'producer.worker:kill:2:worker=0:'
                 f'epoch={chaos_epoch}:generation=0')
  ctx = mp.get_context('spawn')
  port_q = ctx.Queue()
  proc = ctx.Process(target=_chaos_server_proc,
                     args=(port_q, num_nodes, dim, jsonl, worker_plan),
                     daemon=False)
  proc.start()
  port = port_q.get(timeout=300)
  init_client([('127.0.0.1', port)], rank=0, num_clients=1)
  recorder.enable(None)            # ring: rpc.retry/peer.lost capture
  DistLoader.RECV_POLL_SECS = 2.0
  seeds = np.arange(n_seeds) % num_nodes
  loader = DistNeighborLoader(
      None, [10, 5], seeds, batch_size=batch, shuffle=True,
      worker_options=RemoteDistSamplingWorkerOptions(
          server_rank=0, num_workers=2, prefetch_size=2),
      to_device=False, seed=0)

  # -- fault-free phase (epoch 0 warms the pipeline, rest are timed) --
  for b in loader:
    pass
  t0 = time.perf_counter()
  timed_batches = 0
  for _ in range(epochs - 1):
    for b in loader:
      timed_batches += 1
  dt = time.perf_counter() - t0
  fault_free_rate = timed_batches * batch / max(dt, 1e-9)
  base_retries = len(recorder.events('rpc.retry'))

  # -- chaos epoch ----------------------------------------------------
  chaos.install('rpc.request:drop:2:op=fetch_one_sampled_message;'
                'rpc.request:delay:4:op=fetch_one_sampled_message:'
                'secs=0.5')
  got = 0
  seen = set()
  for b in loader:
    got += 1
  ch = loader.channel
  seen = set(getattr(ch, '_seen_seqs', ()))
  dup = getattr(ch, 'duplicates_discarded', 0)
  retries = len(recorder.events('rpc.retry')) - base_retries
  chaos.uninstall()
  loader.shutdown()
  shutdown_client()
  proc.join(timeout=60)
  server_events = ''
  try:
    with open(jsonl) as f:
      server_events = f.read()
  except OSError:
    pass
  row = {
      'metric': 'dist_chaos_smoke',
      'batch': batch, 'num_nodes': num_nodes,
      'epochs_fault_free': epochs,
      'fault_free_seeds_per_sec': round(fault_free_rate, 1),
      'chaos_epoch': {
          'expected_batches': n_batches,
          'received_batches': got,
          'unique_seqs': len(seen),
          'duplicates_discarded': int(dup),
          'rpc_retries': retries,
          'producer_restart_logged':
              '"kind": "producer.restart"' in server_events,
          'fault_injected_logged':
              '"kind": "fault.injected"' in server_events,
      },
      'ok': bool(got == n_batches and len(seen) == n_batches
                 and retries >= 1),
  }
  print(json.dumps(row), flush=True)
  from benchmarks.common import tee_record
  tee_record(row)
  return row


def resume_smoke(batch: int = 64, num_nodes: int = 2048):
  """Preemption-resume smoke (ISSUE 6): time a snapshotting epoch
  against the no-snapshot line on the host mp producer path, then run
  the kill→restore→finish loop and report ``restore_secs`` (durable
  snapshot load + data-plane rewind) and ``replayed_batches`` (the
  re-produced prefix the consumer discards) — the two regression-
  guarded ``dist.resume.*`` metrics.  Prints ONE JSON row.

  The mesh ``dist.tiered`` line is snapshot-free by construction
  (snapshots are opt-in per driver via ``attach_snapshots`` /
  ``GLT_SNAPSHOT_DIR``), so the snapshot-overhead comparison is
  measured here on the path that DOES snapshot: the row's
  ``snap_over_nosnap_ratio`` (snapshotting / no-snapshot throughput,
  ~1.0 when overhead is in the noise) is what the
  ``dist.resume.snap_over_nosnap_ratio`` regression guard holds the
  line on (the raw signed ``snapshot_overhead_pct`` is reported for
  humans but is ratio-unsafe as a guard: its healthy baseline
  straddles zero)."""
  import json
  import shutil
  import tempfile
  import time as _time
  import numpy as np
  from graphlearn_tpu import native
  if not native.available():
    row = {'metric': 'dist_resume_smoke', 'skipped': True,
           'reason': 'native lib unavailable'}
    print(json.dumps(row), flush=True)
    return
  from graphlearn_tpu.distributed import (DistNeighborLoader,
                                          HostDataset,
                                          MpDistSamplingWorkerOptions)
  from graphlearn_tpu.utils.checkpoint import SnapshotManager

  n = num_nodes
  rows = np.repeat(np.arange(n), 2)
  cols = np.stack([(np.arange(n) + 1) % n,
                   (np.arange(n) + 2) % n], 1).reshape(-1)
  feats = np.tile(np.arange(n, dtype=np.float32)[:, None], (1, 16))
  ds = HostDataset.from_coo(rows, cols, n, node_features=feats,
                            node_labels=np.arange(n) % 4)

  def make_loader():
    return DistNeighborLoader(
        ds, [5, 5], np.arange(n), batch_size=batch, shuffle=True,
        worker_options=MpDistSamplingWorkerOptions(
            num_workers=2, mp_start_method='spawn'),
        to_device=False, seed=7)

  n_batches = (n + batch - 1) // batch
  snap_root = tempfile.mkdtemp(prefix='glt_resume_')
  try:
    # -- epoch timing: no-snapshot line vs snapshot-every-batch ------
    loader = make_loader()
    for b in loader:                       # warm the producer pool
      pass
    # the 5% criterion reads this comparison.  On the fused tiered
    # path a snapshot boundary is a GLT_FUSED_COLD_CHUNK (64-step)
    # chunk; the host loader's boundary is a single batch, so
    # GLT_SNAPSHOT_EVERY here defaults to 8 batches as the
    # chunk-equivalent cadence (a per-batch fsync is not the deployed
    # regime on any path).  Min over 3 epochs per arm: the mp producer
    # wall is noisy (worker scheduling), the floor is the signal.
    from graphlearn_tpu.utils.checkpoint import snapshot_every_from_env
    every = snapshot_every_from_env(default=8)
    snap = SnapshotManager(snap_root + '/overhead', every=every)
    nosnap_secs = snap_secs = float('inf')
    for _ in range(3):
      t0 = _time.perf_counter()
      for b in loader:
        pass
      nosnap_secs = min(nosnap_secs, _time.perf_counter() - t0)
      t0 = _time.perf_counter()
      seen = 0
      for b in loader:
        seen += 1
        if snap.due():
          snap.save(loader.state_dict(),
                    {'epoch': 2, 'next_chunk': seen})
      snap_secs = min(snap_secs, _time.perf_counter() - t0)
    rate_nosnap = n / max(nosnap_secs, 1e-9)
    rate_snap = n / max(snap_secs, 1e-9)
    overhead_pct = 100.0 * (snap_secs - nosnap_secs) / max(nosnap_secs,
                                                           1e-9)

    # -- kill -> restore -> finish -----------------------------------
    consumed = n_batches // 2
    it = iter(loader)
    for _ in range(consumed):
      next(it)
    resume_snap = SnapshotManager(snap_root + '/resume', every=1)
    resume_snap.save(loader.state_dict(),
                     {'epoch': 3, 'next_chunk': consumed})
    loader.shutdown()                      # the preemption

    resumed = make_loader()
    t0 = _time.perf_counter()
    payload = SnapshotManager(snap_root + '/resume').restore_latest()
    resumed.load_state_dict(payload['plane'])
    restore_secs = _time.perf_counter() - t0
    rest = sum(1 for _ in resumed.resume_epoch())
    replayed = int(getattr(resumed, 'replayed_discarded', 0))
    resumed.shutdown()
  finally:
    shutil.rmtree(snap_root, ignore_errors=True)

  row = {
      'metric': 'dist_resume_smoke',
      'batch': batch, 'num_nodes': n,
      'restore_secs': round(restore_secs, 4),
      'replayed_batches': replayed,
      'resumed_batches': rest,
      'consumed_before_kill': consumed,
      'seeds_per_sec_nosnap': round(rate_nosnap, 1),
      'seeds_per_sec_snap': round(rate_snap, 1),
      'snapshot_overhead_pct': round(overhead_pct, 2),
      'snap_over_nosnap_ratio': round(
          rate_snap / max(rate_nosnap, 1e-9), 4),
      'ok': bool(consumed + rest == n_batches
                 and replayed >= consumed),
  }
  print(json.dumps(row), flush=True)
  from benchmarks.common import tee_record
  tee_record(row)
  return row


def failover_smoke(batch: int = 64, num_nodes: int = 20_000,
                   dim: int = 32):
  """Elastic-failover smoke (ISSUE 15): one partition owner killed
  mid-epoch on the virtual mesh with a durable shard present under
  ``GLT_SHARD_DIR`` — a survivor adopts the orphaned shard and the
  epoch must finish with the EXACT-completion contract: the full
  expected batch count (``completed_ratio`` 1.0), batches
  byte-identical to the fault-free run, exactly ONE adoption, and
  ``recovery_secs`` (classification -> first served batch) gauged —
  the two ``dist.failover.*`` regression-guarded metrics.  Prints ONE
  JSON row; the caller exits nonzero unless ``ok``."""
  import json
  import os
  import shutil
  import tempfile
  import time
  import jax
  from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                       make_mesh)
  from graphlearn_tpu.telemetry import recorder
  from graphlearn_tpu.testing import chaos

  num_parts = len(jax.devices())
  mesh = make_mesh(num_parts)
  rows, cols = build_graph(num_nodes)
  feats = np.random.default_rng(0).standard_normal(
      (num_nodes, dim)).astype(np.float32)
  labels = (np.arange(num_nodes) % 7).astype(np.int32)

  def make_loader():
    ds = DistDataset.from_full_graph(num_parts, rows, cols,
                                     node_feat=feats, node_label=labels,
                                     num_nodes=num_nodes)
    seeds = np.random.default_rng(1).permutation(
        num_nodes)[:batch * num_parts * 10]
    return ds, DistNeighborLoader(ds, [10, 5], seeds, batch_size=batch,
                                  shuffle=True, mesh=mesh, seed=0)

  def grab(b):
    return tuple(np.asarray(jax.device_get(x))
                 for x in (b.node, b.x, b.y, b.edge_index))

  # -- fault-free reference: epoch 1 is the byte-identity reference
  # (the shuffle permutation advances per epoch, and the failover run
  # below is ITS loader's epoch 1 too); epoch 2 is the post-compile
  # timed line
  _, ref_loader = make_loader()
  ref = [grab(b) for b in ref_loader]
  t0 = time.perf_counter()
  for b in ref_loader:
    pass
  fault_free_secs = time.perf_counter() - t0
  n_batches = len(ref)
  kill_step = max(2, n_batches // 2)

  # -- failover epoch: durable shards on, one owner killed mid-epoch --
  shard_dir = tempfile.mkdtemp(prefix='glt_failover_')
  saved = {k: os.environ.pop(k, None)
           for k in ('GLT_SHARD_DIR', 'GLT_DEGRADED_OK')}
  os.environ['GLT_SHARD_DIR'] = shard_dir
  victim = num_parts // 2
  recorder.enable(None)
  chaos.install(f'partition.owner:kill:{kill_step}:partition={victim}')
  try:
    ds, loader = make_loader()
    t0 = time.perf_counter()
    got = [grab(b) for b in loader]
    failover_secs = time.perf_counter() - t0
    adopts = recorder.events('partition.adopt')
  finally:
    chaos.uninstall()
    recorder.disable()
    for k, v in saved.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v
    shutil.rmtree(shard_dir, ignore_errors=True)

  executed = [e for e in adopts if e.get('phase') is None]
  recovered = [e for e in adopts if e.get('phase') == 'recovered']
  byte_identical = len(got) == n_batches and all(
      all(np.array_equal(a, b) for a, b in zip(r, g))
      for r, g in zip(ref, got))
  completed_ratio = round(len(got) / max(n_batches, 1), 4)
  recovery_secs = recovered[0]['secs'] if recovered else None
  row = {
      'metric': 'dist_failover_smoke',
      'batch': batch, 'num_nodes': num_nodes, 'num_parts': num_parts,
      'expected_batches': n_batches,
      'received_batches': len(got),
      'completed_ratio': completed_ratio,
      'byte_identical': bool(byte_identical),
      'adoptions_total': len(executed),
      'book_version': int(ds.partition_book.version),
      'killed_partition': victim,
      'kill_step': kill_step,
      'recovery_secs': (round(recovery_secs, 4)
                        if recovery_secs is not None else None),
      'fault_free_epoch_secs': round(fault_free_secs, 3),
      'failover_epoch_secs': round(failover_secs, 3),
      'ok': bool(byte_identical and completed_ratio == 1.0
                 and len(executed) == 1
                 and ds.partition_book.version == 1
                 and recovery_secs is not None and recovery_secs > 0),
  }
  print(json.dumps(row), flush=True)
  from benchmarks.common import tee_record
  tee_record(row)
  return row


def capacity_sweep(quick: bool):
  import json
  fanout = [15, 10, 5]
  batch = 1024
  n = 100_000 if quick else 500_000
  script = str(Path(__file__).resolve())
  for p in (8, 16, 32):
    for slack in ('exact', 2.0):
      if slack == 'exact' and p > 8:
        # exact exchange at P>=16 with batch-1024 frontiers means
        # ~[P, 154k] all_to_all buffers per hop — beyond the virtual
        # CPU mesh's in-process collectives (rendezvous aborts on the
        # single-core CI box), and exactly the configuration the
        # capacity cap exists to avoid.  Recorded explicitly: no
        # silent truncation of the sweep.
        print(json.dumps(
            {'metric': 'dist_exchange_capacity', 'skipped': True,
             'num_parts': p, 'slack': 'exact',
             'reason': 'exact exchange buffers exceed virtual-mesh '
                       'capacity; use slack'}), flush=True)
        continue
      run_in_fresh_process(
          script,
          ['--capacity-worker', '--num-parts', p, '--slack', slack,
           '--batch', batch, '--nodes', n,
           '--fanout', ','.join(map(str, fanout))],
          env=cpu_mesh_env(p))
  # SEAL envelope: chunked full-window hops keep the exact subgraph
  # scan bounded where the unchunked window aborted at P>=16
  sg_n = 50_000 if quick else 100_000
  for p, chunk in ((8, 'none'), (8, 512), (16, 512)):
    run_in_fresh_process(
        script,
        ['--subgraph-worker', '--num-parts', p, '--hop-chunk', chunk,
         '--batch', 32, '--nodes', sg_n],
        env=cpu_mesh_env(p))
  # scale envelope past P=32: P=64/128 homo with
  # adaptive slack, hetero and chunked-SEAL at P=64 — tiny shapes (the
  # virtual devices oversubscribe the cores; the exchange accounting,
  # not throughput, is the deliverable)
  env_n = 20_000 if quick else 50_000
  for p, mode, batch in ((64, 'homo', 64), (128, 'homo', 32),
                         (64, 'hetero', 32), (64, 'seal', 8)):
    run_in_fresh_process(
        script,
        ['--envelope-worker', '--num-parts', p, '--mode', mode,
         '--batch', batch, '--nodes', env_n],
        env=cpu_mesh_env(p))


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--quick', action='store_true')
  ap.add_argument('--num-parts', type=int, default=None)
  ap.add_argument('--dim', type=int, default=64)
  ap.add_argument('--capacity-sweep', action='store_true')
  ap.add_argument('--capacity-worker', action='store_true')
  ap.add_argument('--subgraph-worker', action='store_true')
  ap.add_argument('--envelope-worker', action='store_true')
  ap.add_argument('--memory-envelope', action='store_true',
                  help='print the IGBH-large-on-v5p-128 per-chip '
                       'memory table')
  ap.add_argument('--chaos', action='store_true',
                  help='resilience smoke: fault-free host '
                       'server->client throughput with the retry '
                       'layer on, then one chaos epoch (worker kill '
                       '+ connection drop + delayed fetch) with '
                       'exact-accounting checks')
  ap.add_argument('--resume', action='store_true',
                  help='preemption-resume smoke: snapshot-overhead '
                       'epoch timing vs the no-snapshot line, then '
                       'kill -> durable restore -> finish with exact '
                       'accounting (dist.resume.* metrics)')
  ap.add_argument('--failover', action='store_true',
                  help='elastic-failover smoke (ISSUE 15): kill one '
                       'partition owner mid-epoch with a durable '
                       'shard under GLT_SHARD_DIR — exits nonzero '
                       'unless the epoch completes EXACTLY '
                       '(completed_ratio 1.0, batches byte-identical '
                       'to the fault-free run) with ONE adoption; '
                       'reports the guarded dist.failover.* metrics')
  ap.add_argument('--mode', default='homo')
  ap.add_argument('--epochs', type=int, default=5,
                  help='envelope-worker epochs (the adaptive ladder '
                       'walks one rung per drop-free epoch)')
  ap.add_argument('--slack', default='exact')
  ap.add_argument('--hop-chunk', default='none')
  ap.add_argument('--batch', type=int, default=1024)
  ap.add_argument('--nodes', type=int, default=500_000)
  ap.add_argument('--fanout', default='15,10,5')
  ap.add_argument('--fused', action='store_true',
                  help='also time parallel.FusedDistEpoch (whole '
                       'epoch = one SPMD scan program, WITH the DP '
                       'train step) against the per-batch loader + '
                       'DP-step loop — ~17 s of CPU-mesh compile at '
                       'the default shape (r4 measurement); the '
                       'multi-minute regime is the big-model shape, '
                       'see benchmarks/bench_compile.py')
  args = ap.parse_args()

  # live ops plane (r13): honor GLT_OPS_PORT so a long-running dist
  # bench is scrapeable mid-run (no-op at the 0/unset default)
  from graphlearn_tpu.telemetry import maybe_start_from_env
  maybe_start_from_env()

  if args.chaos:
    chaos_smoke(batch=args.batch if args.batch != 1024 else 64,
                num_nodes=min(args.nodes, 5000))
    return
  if args.resume:
    resume_smoke(batch=args.batch if args.batch != 1024 else 64,
                 num_nodes=min(args.nodes, 2048))
    return
  if args.failover:
    row = failover_smoke(batch=args.batch if args.batch != 1024 else 64,
                         num_nodes=min(args.nodes, 20_000))
    if not row.get('ok'):
      raise SystemExit(1)
    return
  if args.capacity_sweep:
    capacity_sweep(args.quick)
    return
  if args.capacity_worker:
    slack = None if args.slack == 'exact' else float(args.slack)
    capacity_worker(args.num_parts, slack, args.batch,
                    [int(k) for k in args.fanout.split(',')], args.nodes)
    return
  if args.subgraph_worker:
    chunk = None if args.hop_chunk == 'none' else int(args.hop_chunk)
    subgraph_worker(args.num_parts, chunk, args.batch, args.nodes)
    return
  if args.memory_envelope:
    import json
    print(json.dumps(memory_envelope(args.num_parts or 128)),
          flush=True)
    return
  if args.envelope_worker:
    envelope_worker(args.num_parts, args.mode, args.batch, args.nodes,
                    epochs=args.epochs)
    return

  import jax
  from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                       make_mesh)

  num_parts = args.num_parts or len(jax.devices())
  mesh = make_mesh(num_parts)
  n = 100_000 if args.quick else 500_000
  rows, cols = build_graph(n)
  feats = np.random.default_rng(0).standard_normal(
      (n, args.dim)).astype(np.float32)
  labels = (np.arange(n) % 47).astype(np.int32)
  ds = DistDataset.from_full_graph(num_parts, rows, cols,
                                   node_feat=feats, node_label=labels,
                                   num_nodes=n)

  seeds = np.random.default_rng(1).permutation(n)[:8192 if args.quick
                                                  else 65536]
  for batch_size in (256, 512):
    loader = DistNeighborLoader(ds, [10, 5], seeds,
                                batch_size=batch_size, shuffle=True,
                                mesh=mesh, seed=0)
    b = next(iter(loader))          # compile
    b.x.block_until_ready()
    batches = 0
    with Timer() as t:
      last = None
      for b in loader:
        last = b
        batches += 1
      last.x.block_until_ready()
    global_batch = batch_size * num_parts
    emit('dist_loader_seeds_per_sec',
         batches * global_batch / t.dt / 1e3, 'K seeds/s',
         batch=batch_size, num_parts=num_parts,
         platform=jax.devices()[0].platform)

  # -- tiered rows (r10): static split vs cache + cold pipeline ----------
  # The same workload against a split_ratio=0.3 store, twice: the r5
  # static-split configuration (no cache, synchronous overlay) and the
  # r10 default (HBM victim cache + double-buffered cold overlay).
  # Both rows land in BENCH_ARTIFACT.jsonl; the bench.py twin of this
  # measurement feeds the guarded `dist.tiered.seeds_per_sec` /
  # `dist.feature.cache_hit_rate` regression keys.
  import os
  ds_t = DistDataset.from_full_graph(num_parts, rows, cols,
                                     node_feat=feats, node_label=labels,
                                     num_nodes=n, split_ratio=0.3)
  # third row (r11): GNS-on vs GNS-off tiered comparison — the same
  # cache + pipeline with the sampler-side bias added (GLT_GNS=1
  # exercises the env-knob path the way a deployment would set it)
  for mode, env in (('static_split', {'GLT_COLD_CACHE_ROWS': '0',
                                      'GLT_COLD_PREFETCH': '0'}),
                    ('cached_pipelined', {}),
                    ('gns_cached_pipelined', {'GLT_GNS': '1'})):
    saved = {k: os.environ.pop(k, None)
             for k in ('GLT_COLD_CACHE_ROWS', 'GLT_COLD_PREFETCH',
                       'GLT_GNS')}
    os.environ.update(env)
    try:
      lt = DistNeighborLoader(ds_t, [10, 5], seeds, batch_size=512,
                              shuffle=True, mesh=mesh, seed=0,
                              prefetch=2)
      it = iter(lt)
      b = next(it)
      b.x.block_until_ready()
      nt = 0
      with Timer() as t:
        for b in it:
          b.x.block_until_ready()
          nt += 1
      st = lt.sampler.exchange_stats(tick_metrics=False)
      emit('dist_tiered_seeds_per_sec',
           nt * 512 * num_parts / t.dt / 1e3, 'K seeds/s',
           mode=mode, split_ratio=0.3, batch=512, num_parts=num_parts,
           gns=bool(lt.sampler.gns),
           cold_cache_rows=(lt.sampler._cold_cache.capacity
                            if lt.sampler._cold_cache else 0),
           cold_lookups=st['dist.feature.cold_lookups'],
           cold_misses=st['dist.feature.cold_misses'],
           hot_hit_rate=round(st['dist.feature.hot_hit_rate'], 4),
           cache_hit_rate=round(st['dist.feature.cache_hit_rate'], 4),
           platform=jax.devices()[0].platform)
    finally:
      for k, v in saved.items():
        if v is None:
          os.environ.pop(k, None)
        else:
          os.environ[k] = v

  if args.fused:
    # fused whole-epoch vs per-batch loader + DP step, same workload
    # (the dispatch-overhead measurement, mesh edition)
    import optax
    from graphlearn_tpu.models import GraphSAGE, create_train_state
    from graphlearn_tpu.parallel import (FusedDistEpoch,
                                         make_dp_supervised_step,
                                         replicate)
    bs = 256 if args.quick else 512
    fanout = [10, 5]   # matches the loader phase above (NOT --fanout,
                       # which parameterizes the capacity workers)
    model = GraphSAGE(hidden_features=64, out_features=47, num_layers=2)
    tx = optax.adam(3e-3)
    it = iter(DistNeighborLoader(ds, fanout, seeds, batch_size=bs,
                                 shuffle=True, mesh=mesh, seed=0))
    b0 = next(it)
    b0_local = jax.tree_util.tree_map(lambda x: x[0], b0)
    state, apply_fn = create_train_state(model, jax.random.key(0),
                                         b0_local, tx)
    step = make_dp_supervised_step(apply_fn, tx, bs, mesh)
    state = replicate(state, mesh)
    state, _, _ = step(state, b0)               # compile + warm
    jax.tree_util.tree_leaves(state.params)[0].block_until_ready()
    nb = 0
    with Timer() as t:
      for b in it:
        state, _, _ = step(state, b)
        nb += 1
      jax.tree_util.tree_leaves(state.params)[0].block_until_ready()
    emit('dist_train_seeds_per_sec', nb * bs * num_parts / t.dt / 1e3,
         'K seeds/s', mode='per-batch', batch=bs, fanout=fanout,
         num_parts=num_parts, platform=jax.devices()[0].platform)

    fused = FusedDistEpoch(ds, fanout, seeds, apply_fn, tx,
                           batch_size=bs, mesh=mesh, shuffle=True,
                           seed=0)
    for _ in range(2):                  # compile + donated recompile
      state, _ = fused.run(state)
    jax.tree_util.tree_leaves(state.params)[0].block_until_ready()
    with Timer() as t:
      state, _ = fused.run(state)
      jax.tree_util.tree_leaves(state.params)[0].block_until_ready()
    emit('dist_train_seeds_per_sec',
         len(fused) * bs * num_parts / t.dt / 1e3, 'K seeds/s',
         mode='fused', batch=bs, fanout=fanout, num_parts=num_parts,
         platform=jax.devices()[0].platform)


if __name__ == '__main__':
  main()
