"""Shared benchmark scaffolding.

Mirrors the reference's harness conventions (`benchmarks/api/
bench_sampler.py:46-54`, `bench_feature.py:50-62`): wall-clock around
the op under test, device-synchronized, metric printed as one JSON
line per config so the results are machine-comparable across rounds.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

NUM_NODES = 2_449_029          # ogbn-products node count
AVG_DEG = 25


#: bump when the construction below changes — part of the cache key so
#: stale /tmp graphs can never masquerade as the current generator.
GRAPH_VERSION = 1


def build_graph(num_nodes=NUM_NODES, avg_deg=AVG_DEG, seed=0,
                cache: bool = True):
  """Synthetic power-law-ish graph at ogbn-products scale (same
  construction as the root `bench.py`).  Cached to /tmp so the
  per-config subprocesses of the sweep benchmarks (see
  `run_in_fresh_process`) skip the ~1 min regeneration."""
  import os
  path = (f'/tmp/.glt_bench_graph_v{GRAPH_VERSION}'
          f'_{num_nodes}_{avg_deg}_{seed}.npz')
  if cache and os.path.exists(path):
    d = np.load(path)
    return d['rows'].astype(np.int64), d['cols'].astype(np.int64)
  rng = np.random.default_rng(seed)
  n = num_nodes
  e = n * avg_deg
  rows = rng.integers(0, n, e, dtype=np.int64)
  hubs = (rng.random(e) < 0.3)
  cols = np.where(hubs,
                  (rng.random(e) ** 2 * n).astype(np.int64),
                  rng.integers(0, n, e, dtype=np.int64))
  cols = cols.astype(np.int64)
  if cache:
    # pid-unique temp + atomic replace (concurrent cold-cache writers
    # must not interleave); int32 storage halves the /tmp footprint
    tmp = f'{path}.{os.getpid()}.tmp.npz'
    np.savez(tmp[:-4], rows=rows.astype(np.int32),
             cols=cols.astype(np.int32))       # savez appends .npz
    os.replace(tmp, path)
  return rows, cols


def build_graph_csr(num_nodes=NUM_NODES, avg_deg=AVG_DEG, seed=0):
  """CSR form of `build_graph`, cached: the COO->CSR sort costs ~60s
  at products scale on this box and dominated the per-session cost of
  the multi-session bench harness.  Returns ``(indptr, indices,
  edge_ids)`` for ``Dataset.init_graph(layout='CSR')``."""
  import os
  path = (f'/tmp/.glt_bench_csr_v{GRAPH_VERSION}'
          f'_{num_nodes}_{avg_deg}_{seed}.npz')
  if os.path.exists(path):
    d = np.load(path)
    return (d['indptr'].astype(np.int64), d['indices'].astype(np.int64),
            d['eids'].astype(np.int64))
  rows, cols = build_graph(num_nodes, avg_deg, seed)
  order = np.argsort(rows, kind='stable')
  indices = cols[order]
  indptr = np.zeros(num_nodes + 1, np.int64)
  np.cumsum(np.bincount(rows, minlength=num_nodes), out=indptr[1:])
  tmp = f'{path}.{os.getpid()}.tmp.npz'
  np.savez(tmp[:-4], indptr=indptr, indices=indices.astype(np.int32),
           eids=order.astype(np.int32))
  os.replace(tmp, path)
  return indptr, indices.astype(np.int64), order.astype(np.int64)


def build_graph_csr_device(num_nodes=NUM_NODES, avg_deg=AVG_DEG, seed=0):
  """Device-side twin of `build_graph_csr`: the same power-law-ish
  edge recipe (0.3 hub mixture, squared-uniform hub targets) generated
  and CSR-sorted entirely on the accelerator.  Zero host↔device
  transfer, no host-side sort, and no dependence on a graph cached
  under /tmp by an earlier run.  The graph is statistically identical to the
  host generator's but NOT bit-identical (different RNG); same-seed
  calls are deterministic across sessions, which is what
  cross-session comparability needs.

  Returns device ``(indptr, indices, edge_ids)`` for
  ``Dataset.init_graph(layout='CSR')``'s device-native path.
  """
  import jax
  import jax.numpy as jnp

  @jax.jit
  def build(key):
    e = num_nodes * avg_deg
    k1, k2, k3 = jax.random.split(key, 3)
    rows = jax.random.randint(k1, (e,), 0, num_nodes, jnp.int32)
    hub = jax.random.uniform(k2, (e,)) < 0.3
    u = jax.random.uniform(k3, (e,))
    hub_cols = (u * u * num_nodes).astype(jnp.int32)
    unif_cols = (u * num_nodes).astype(jnp.int32)
    cols = jnp.where(hub, hub_cols, unif_cols)
    # canonical sorted-CSR (cols ascending within each row) via
    # two-pass stable lexsort — a fused int64 key would truncate to
    # int32 without jax_enable_x64; the strict-negative sampler's
    # `edge_in_csr` binary search requires the sorted form
    by_col = jnp.argsort(cols, stable=True)
    order = by_col[jnp.argsort(rows[by_col], stable=True)]
    indices = cols[order]
    rows_sorted = rows[order]
    indptr = jnp.searchsorted(
        rows_sorted, jnp.arange(num_nodes + 1, dtype=jnp.int32),
        side='left').astype(jnp.int32)
    return indptr, indices, order.astype(jnp.int32)

  return build(jax.random.key(seed))


def build_bipartite_csr_device(n_src: int, n_dst: int, avg_deg: int,
                               seed: int = 0, hub_frac: float = 0.3):
  """Device-built sorted-CSR for one (src -> dst) edge type — the
  hetero sibling of `build_graph_csr_device` (same hub mixture,
  zero host↔device transfer, deterministic per seed)."""
  import jax
  import jax.numpy as jnp

  @jax.jit
  def build(key):
    e = n_src * avg_deg
    k1, k2, k3 = jax.random.split(key, 3)
    rows = jax.random.randint(k1, (e,), 0, n_src, jnp.int32)
    hub = jax.random.uniform(k2, (e,)) < hub_frac
    u = jax.random.uniform(k3, (e,))
    cols = jnp.where(hub, (u * u * n_dst).astype(jnp.int32),
                     (u * n_dst).astype(jnp.int32))
    by_col = jnp.argsort(cols, stable=True)
    order = by_col[jnp.argsort(rows[by_col], stable=True)]
    indices = cols[order]
    rows_sorted = rows[order]
    indptr = jnp.searchsorted(
        rows_sorted, jnp.arange(n_src + 1, dtype=jnp.int32),
        side='left').astype(jnp.int32)
    return indptr, indices
  return build(jax.random.key(seed))


def sample_window_bytes(batch: int, fanouts) -> int:
  """Analytic upper bound on HBM bytes one multihop sample's window
  gathers move (`ops/neighbor.py` exact-without-replacement path) —
  the analytic floor the sampling walls are cross-checked against."""
  from graphlearn_tpu.ops.neighbor import default_window
  frontier, total = batch, 0
  for k in fanouts:
    total += frontier * default_window(k) * 4
    frontier *= k
  return total


def make_sample_burst(fanouts, node_cap: int, iters: int):
  """The sampling-throughput program, ONE definition for
  `bench.py` and `bench_sampler.py`: a scan over ``[iters, B]`` seed
  batches whose body is the fused multihop sampler, returning the
  accepted-edge total (the value pull that forces real execution).
  Named unpacking so a `_multihop_sample` signature change fails
  loudly instead of summing the wrong array."""
  import jax
  import jax.numpy as jnp
  from jax import lax
  from graphlearn_tpu.sampler.neighbor_sampler import _multihop_sample

  def burst(indptr, indices, seeds_all, key):
    def body(acc, xs):
      i, seeds = xs
      (_nodes, _count, _row, _col, _edge, emask, _seed_local, _nsn,
       _nse) = _multihop_sample(
           indptr, indices, None, seeds, jax.random.fold_in(key, i),
           fanouts=tuple(fanouts), node_cap=node_cap, with_edge=False,
           sort_locality=True)
      return acc + jnp.sum(emask, dtype=jnp.int32), None
    total, _ = lax.scan(body, jnp.int32(0), (
        jnp.arange(iters, dtype=jnp.int32), seeds_all))
    return total

  return burst


def emit(metric: str, value: float, unit: str, baseline: float = None,
         **extra):
  rec = {'metric': metric, 'value': round(float(value), 3), 'unit': unit}
  if baseline:
    rec['vs_baseline'] = round(float(value) / baseline, 4)
  rec.update(extra)
  print(json.dumps(rec), flush=True)
  tee_record(rec)


def run_id() -> str:
  """Stable identifier for THIS sweep run, minted once by the first
  process to ask and inherited by its fresh per-config subprocesses
  through the environment — the sidecar appends across runs, so every
  record needs a key consumers can group/dedupe by."""
  rid = os.environ.get('GLT_BENCH_RUN_ID')
  if not rid:
    rid = time.strftime('%Y%m%dT%H%M%S') + f'-{os.getpid()}'
    os.environ['GLT_BENCH_RUN_ID'] = rid
  return rid


def tee_record(rec: dict) -> None:
  """File-artifact tee for sweep records: every emitted config line
  also appends to the JSONL sidecar (`telemetry.sink.append_record`,
  `GLT_BENCH_RECORDS` overrides the path, default
  ``BENCH_ARTIFACT.jsonl``) — line-atomic across the sweeps' fresh
  subprocesses, so a truncated stdout capture no longer loses
  measurements.  Records carry a ``run`` id (`run_id`) so re-runs in
  one directory stay distinguishable.  Best-effort: a sink failure
  never kills a bench."""
  try:
    from graphlearn_tpu.telemetry import sink
    sink.append_record(dict(rec, run=run_id()))
  except Exception:               # noqa: BLE001 — telemetry is optional
    pass


class Timer:
  """Wall-clock over N iters; call ``sync`` on a device array first."""

  def __enter__(self):
    self.t0 = time.perf_counter()
    return self

  def __exit__(self, *exc):
    self.dt = time.perf_counter() - self.t0


def cpu_mesh_env(num_devices: int) -> dict:
  """Subprocess env forcing an ``num_devices``-device virtual CPU mesh
  (``XLA_FLAGS`` is parsed once, at the child's backend init)."""
  run_id()      # mint the sweep's run id HERE, in the parent, so the
                # env snapshot below hands every worker the same one
  env = dict(os.environ)
  env['JAX_PLATFORMS'] = 'cpu'
  flags = env.get('XLA_FLAGS', '')
  flags = ' '.join(f for f in flags.split()
                   if '--xla_force_host_platform_device_count' not in f)
  env['XLA_FLAGS'] = (
      f'{flags} --xla_force_host_platform_device_count={num_devices}'
      .strip())
  return env


def run_in_fresh_process(script: str, args, env=None) -> bool:
  """Re-exec one benchmark config in a clean interpreter and stream
  its output; returns False (and keeps going) if the config failed,
  so one bad configuration never aborts the rest of a sweep.

  What the isolation buys: every configuration starts from the same
  process state (no warm jit caches, allocator pools or cold-cache
  rings inherited from the previous configuration), and an
  out-of-memory or crash in one costs only that one.  The PARENT must
  stay off JAX: a chip belongs to one process at a time, so a parent
  that had initialised a backend would leave its children without
  one.  Children run strictly one after another.
  """
  import subprocess
  import sys
  # every config must record the SAME run id: mint it in the parent
  # and plant it into the child env even when the caller snapshotted
  # that env before the id existed (env=None inherits os.environ,
  # which run_id() just stamped)
  rid = run_id()
  if env is not None and 'GLT_BENCH_RUN_ID' not in env:
    env = dict(env, GLT_BENCH_RUN_ID=rid)
  cmd = [sys.executable, script] + [str(a) for a in args]
  rc = subprocess.run(cmd, env=env).returncode
  if rc != 0:
    print(json.dumps({'metric': 'config_failed', 'args': list(map(str, args)),
                      'returncode': rc}), flush=True)
  return rc == 0
