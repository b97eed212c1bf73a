"""Does the system still start on the chip?  One process, one pass.

Drives the main path once through the entry points a user calls, at
the published widths of the flagship model (GraphSAGE, 3 layers,
hidden 256, fanout [15, 10, 5], batch 1024) on the products-scale
synthetic (2,449,029 nodes, ~61 M edges, 100-dim f32 features, 47
classes) generated on device from a seed:

  trainer   `Dataset` -> `FusedTreeEpoch` + `TreeSAGE`: one dispatch
            that compiles, one steady dispatch that must not, then
            `evaluate()` on a slice;
  loader    the same dataset through `NeighborLoader` -> `GraphSAGE`
            -> `create_train_state` / `make_supervised_step`;
  server    `ServingEngine` behind `ServingFrontend`: warm-up, a few
            requests of 1-16 seeds, each answer checked against the
            engine's per-seed offline reference, the CSR itself, and a
            float64 NumPy forward of the same weights;
  mesh      (only with >= 4 devices) the same graph sharded over the
            first four: `DistNeighborLoader` + `make_dp_supervised_step`,
            `FusedDistTreeEpoch`, `dryrun_multichip(4)`.

Every phase raises on failure.  `main()` fixes the full sizes and
demands a TPU; the phase functions take sizes as arguments so
`tests/test_chip_smoke.py` runs the same code tiny on the CPU.  The
seconds printed per phase are set-up facts (is the compile cache
warm?), not measurements.  The last line of stdout is one JSON object
naming the device, printed only when every phase passed.

    python chip_smoke.py          # on a machine with a TPU
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

NUM_NODES = 2_449_029
AVG_DEG = 25
DIM = 100
CLASSES = 47
FANOUT = (15, 10, 5)
HIDDEN = 256
BATCH = 1024
#: steps per trainer dispatch / per-batch loader steps / mesh steps
STEPS = 4
REQUEST_SIZES = (1, 3, 8, 16, 2, 5)


class CacheCounter:
  """Counts JAX persistent-compilation-cache hits and writes, so a
  second process against the same cache directory is visibly warm."""

  def __init__(self):
    import jax
    self.hits = self.writes = 0
    jax.monitoring.register_event_listener(self._on_event)

  def _on_event(self, event, **_kw):
    if event == '/jax/compilation_cache/cache_hits':
      self.hits += 1
    elif event == '/jax/compilation_cache/cache_misses':
      self.writes += 1

  def take(self) -> str:
    out = f'persistent cache hits={self.hits} writes={self.writes}'
    self.hits = self.writes = 0
    return out


def _finite(name: str, x) -> np.ndarray:
  x = np.asarray(x)
  if not np.all(np.isfinite(x)):
    raise AssertionError(f'{name}: non-finite values')
  return x


def _close(name: str, got, want) -> float:
  """Logits agree to 3% of the largest reference logit.  On a TPU an
  f32 matmul runs as bf16 passes by default (~2^-8 per layer, three
  layers), and XLA tiles each bucket shape differently, so neither
  the per-seed twin nor a float64 forward matches bit for bit."""
  err = float(np.max(np.abs(np.asarray(got) - want)))
  if err > 0.03 * float(np.max(np.abs(want))):
    raise AssertionError(f'{name}: max abs error {err} on logits of '
                         f'magnitude {np.max(np.abs(want))}')
  return err


def build_graph(num_nodes=NUM_NODES, avg_deg=AVG_DEG, seed=0):
  """The products recipe on the host, as COO ``(rows, cols)``:
  uniform sources, 30 % of the targets squared-uniform (hubs), the
  rest uniform.  The mesh phase partitions it."""
  rng = np.random.default_rng(seed)
  n = num_nodes
  e = n * avg_deg
  rows = rng.integers(0, n, e, dtype=np.int64)
  hubs = (rng.random(e) < 0.3)
  cols = np.where(hubs,
                  (rng.random(e) ** 2 * n).astype(np.int64),
                  rng.integers(0, n, e, dtype=np.int64))
  return rows, cols


def build_graph_csr_device(num_nodes=NUM_NODES, avg_deg=AVG_DEG, seed=0):
  """Device-side twin of `build_graph`: the same recipe (0.3 hub
  mixture, squared-uniform hub targets) generated and CSR-sorted
  entirely on the accelerator.  Zero host<->device transfer and no
  host-side sort.  Statistically the host generator's graph, NOT bit
  for bit (different RNG); same-seed calls are deterministic across
  sessions.

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


def build_dataset(num_nodes: int, avg_deg: int, dim: int, classes: int):
  """The products-recipe synthetic, generated and CSR-sorted on device
  (`build_graph_csr_device`): nothing is uploaded and nothing is read
  from an earlier run."""
  import jax
  import jax.numpy as jnp
  from graphlearn_tpu.data import Dataset
  indptr, indices, _ = build_graph_csr_device(num_nodes, avg_deg)
  kf, kl = jax.random.split(jax.random.key(7))
  feats = jax.random.uniform(kf, (num_nodes, dim), jnp.float32)
  labels = jax.random.randint(kl, (num_nodes,), 0, classes, jnp.int32)
  ds = (Dataset()
        .init_graph((indptr, indices), layout='CSR', num_nodes=num_nodes)
        .init_node_features(feats)
        .init_node_labels(labels))
  if int(indptr[-1]) != int(indices.shape[0]):
    raise AssertionError('CSR indptr does not close over indices')
  return ds


def trainer_phase(ds, *, fanout, hidden, classes, batch, steps,
                  eval_seeds) -> dict:
  """Flagship: fused tree epochs.  Two `run()`s of one ``steps``-step
  dispatch each — the first compiles, the second must not — then
  `evaluate()`.  Returns the trained params for the server phase."""
  import jax
  import optax
  from graphlearn_tpu.loader import FusedTreeEpoch
  from graphlearn_tpu.models import TreeSAGE
  n = ds.get_graph().num_nodes
  ids = np.random.default_rng(0).permutation(n)
  model = TreeSAGE(hidden_features=hidden, out_features=classes,
                   num_layers=len(fanout))
  fused = FusedTreeEpoch(ds, list(fanout), ids[:steps * batch], model,
                         optax.adam(3e-3), batch_size=batch,
                         shuffle=True, seed=0,
                         max_steps_per_program=steps)
  state = fused.init_state(jax.random.key(0))
  t0 = time.perf_counter()
  state, stats = fused.run(state)
  first = _finite('trainer first-dispatch losses', stats.losses)
  compile_secs = time.perf_counter() - t0
  compiles = fused.compile_count()
  if compiles < 1:
    raise AssertionError('first dispatch reported no compile')
  t0 = time.perf_counter()
  state, stats = fused.run(state)
  steady = _finite('trainer steady-dispatch losses', stats.losses)
  run_secs = time.perf_counter() - t0
  if fused.compile_count() != compiles:
    raise AssertionError(
        f'steady dispatch compiled: {compiles} -> '
        f'{fused.compile_count()}')
  if first.shape != (steps,) or steady.shape != (steps,):
    raise AssertionError(f'loss shapes {first.shape} {steady.shape}')
  if stats.seeds != steps * batch:
    raise AssertionError(f'{stats.seeds} seeds trained, expected '
                         f'{steps * batch}')
  t0 = time.perf_counter()
  acc = fused.evaluate(state.params,
                       ids[steps * batch:steps * batch + eval_seeds])
  eval_secs = time.perf_counter() - t0
  if not 0.0 <= acc <= 1.0:
    raise AssertionError(f'eval accuracy {acc}')
  for leaf in jax.tree_util.tree_leaves(state.params):
    _finite('trained params', leaf)
  return dict(params=state.params, compile_secs=compile_secs,
              run_secs=run_secs, eval_secs=eval_secs,
              loss_first=float(first.mean()),
              loss_steady=float(steady.mean()), eval_acc=float(acc))


def loader_phase(ds, *, fanout, hidden, classes, batch, steps) -> dict:
  """Per-batch API: `NeighborLoader` -> `GraphSAGE` -> supervised
  step, ``steps`` batches."""
  import jax
  import optax
  from graphlearn_tpu.loader import NeighborLoader
  from graphlearn_tpu.models import (GraphSAGE, create_train_state,
                                     make_supervised_step)
  n = ds.get_graph().num_nodes
  ids = np.random.default_rng(1).permutation(n)[:steps * batch]
  loader = NeighborLoader(ds, list(fanout), ids, batch_size=batch,
                          shuffle=True, seed=0)
  if len(loader) != steps:
    raise AssertionError(f'{len(loader)} batches, expected {steps}')
  model = GraphSAGE(hidden_features=hidden, out_features=classes,
                    num_layers=len(fanout))
  tx = optax.adam(3e-3)
  it = iter(loader)
  t0 = time.perf_counter()
  batch0 = next(it)
  state, apply_fn = create_train_state(model, jax.random.key(0),
                                       batch0, tx)
  step = make_supervised_step(apply_fn, tx, batch)
  state, loss, correct = step(state, batch0)
  losses = [float(loss)]
  compile_secs = time.perf_counter() - t0
  dim = ds.node_features.feature_dim
  if batch0.x.ndim != 2 or batch0.x.shape[1] != dim:
    raise AssertionError(f'batch.x shape {batch0.x.shape}')
  t0 = time.perf_counter()
  for b in it:
    state, loss, correct = step(state, b)
    losses.append(float(loss))
    if not 0 <= int(correct) <= batch:
      raise AssertionError(f'correct={int(correct)}')
  run_secs = time.perf_counter() - t0
  _finite('per-batch losses', losses)
  if len(losses) != steps:
    raise AssertionError(f'{len(losses)} steps ran, expected {steps}')
  return dict(compile_secs=compile_secs, run_secs=run_secs,
              loss_first=losses[0], loss_last=losses[-1])


def tree_sage_reference(params, xs, masks) -> np.ndarray:
  """float64 NumPy forward of `models.tree.TreeSAGE`'s layer
  equations (masked mean over each parent's child window, self +
  neighbour weights, relu between layers)."""
  p = params['params']
  num_layers = len(xs) - 1
  hs = [np.asarray(x, np.float64) * m[:, None] for x, m in zip(xs, masks)]
  for layer in range(num_layers):
    w_self = np.asarray(p[f'layer{layer}_self']['kernel'], np.float64)
    b_self = np.asarray(p[f'layer{layer}_self']['bias'], np.float64)
    w_nbr = np.asarray(p[f'layer{layer}_neigh']['kernel'], np.float64)
    nxt = []
    for t in range(num_layers - layer):
      parent, child = hs[t], hs[t + 1]
      k = child.shape[0] // parent.shape[0]
      cm = masks[t + 1].reshape(parent.shape[0], k)
      cd = child.reshape(parent.shape[0], k, child.shape[1])
      mean = ((cd * cm[..., None]).sum(1)
              / np.maximum(cm.sum(1), 1.0)[:, None])
      h = parent @ w_self + b_self + mean @ w_nbr
      nxt.append(np.maximum(h, 0.0) if layer < num_layers - 1 else h)
    hs = nxt
  return hs[0]


def _check_answers(engine, ds, reqs, answers, classes, params):
  """Served answers against the repo's references; returns the
  largest |logit error| seen against the per-seed twin and against
  the float64 forward."""
  import jax
  import jax.numpy as jnp
  from graphlearn_tpu.ops.negative import edge_in_csr
  widths = engine.level_widths
  off = np.cumsum((0,) + widths)
  twin = 0.0
  for seeds, res in zip(reqs, answers):
    k = len(seeds)
    if res.logits.shape != (k, classes) or \
        res.nodes.shape != (k, engine.tree_width):
      raise AssertionError(
          f'answer shapes {res.logits.shape} {res.nodes.shape}')
    _finite('served logits', res.logits)
    if not np.array_equal(res.nodes[:, 0], seeds):
      raise AssertionError('served tree roots differ from the seeds')
    # the per-seed offline twin: same trees, same logits
    ref = engine.offline_reference(seeds)
    if not np.array_equal(ref.nodes, res.nodes):
      raise AssertionError('coalesced trees differ from per-seed trees')
    twin = max(twin, _close('coalesced vs per-seed logits',
                            res.logits, ref.logits))
  nodes = np.concatenate([r.nodes for r in answers])      # [S, W]
  logits = np.concatenate([r.logits for r in answers])
  # every sampled child is a CSR neighbour of its parent (one device
  # call over all (parent, child) pairs)
  par, kid = [], []
  for t in range(len(widths) - 1):
    parents = nodes[:, off[t]:off[t + 1]]
    kids = nodes[:, off[t + 1]:off[t + 2]].reshape(
        len(nodes), parents.shape[1], -1)
    par.append(np.broadcast_to(parents[..., None], kids.shape).ravel())
    kid.append(kids.ravel())
  par, kid = np.concatenate(par), np.concatenate(kid)
  live = kid >= 0
  if np.any(live & (par < 0)):
    raise AssertionError('sampled a child of a masked parent')
  g = ds.get_graph()
  is_edge = np.asarray(jax.jit(edge_in_csr)(
      g.indptr, g.indices, jnp.asarray(np.where(live, par, -1), jnp.int32),
      jnp.asarray(np.where(live, kid, 0), jnp.int32)))
  if not np.all(is_edge[live]):
    raise AssertionError(
        f'{int((~is_edge[live]).sum())} sampled pairs are not edges')
  # float64 forward of the same weights over the same trees
  x = np.asarray(ds.node_features[jnp.asarray(nodes.ravel(), jnp.int32)])
  x = x.reshape(nodes.shape + (x.shape[-1],))
  worst = 0.0
  for i in range(len(nodes)):
    lv = [nodes[i, off[t]:off[t + 1]] for t in range(len(widths))]
    xs = [x[i, off[t]:off[t + 1]] for t in range(len(widths))]
    want = tree_sage_reference(params, xs, [v >= 0 for v in lv])[0]
    worst = max(worst, _close(f'seed {nodes[i, 0]} vs float64 forward',
                              logits[i], want))
  return twin, worst


def server_phase(ds, *, fanout, hidden, classes, params, buckets,
                 request_sizes) -> dict:
  """Serving: warm every bucket, answer a few requests through
  `submit`/`infer`, check each answer, and pin zero compiles after
  warm-up."""
  import jax
  from graphlearn_tpu.models import TreeSAGE
  from graphlearn_tpu.serving import ServingEngine, ServingFrontend
  n = ds.get_graph().num_nodes
  model = TreeSAGE(hidden_features=hidden, out_features=classes,
                   num_layers=len(fanout))
  engine = ServingEngine(ds, list(fanout), model=model, params=params,
                         seed=0, buckets=buckets)
  frontend = ServingFrontend(engine, auto_start=False)
  host_params = jax.tree_util.tree_map(np.asarray, params)
  try:
    t0 = time.perf_counter()
    frontend.start()                  # warm-up compiles every bucket
    compile_secs = time.perf_counter() - t0
    if not all(engine.warm.values()):
      raise AssertionError(f'buckets not warm: {engine.warm}')
    compiles = engine.compile_count()
    rng = np.random.default_rng(2)
    reqs = [rng.integers(0, n, k) for k in request_sizes]
    t0 = time.perf_counter()
    # all but the last ride the queue together (coalescing), the last
    # takes the blocking call; the deadline is far out because this
    # checks answers, not latency
    futs = [frontend.submit(s, deadline_ms=60e3) for s in reqs[:-1]]
    answers = [f.result(120.0) for f in futs]
    answers.append(frontend.infer(reqs[-1], deadline_ms=60e3))
    run_secs = time.perf_counter() - t0
    twin_err, ref_err = _check_answers(engine, ds, reqs, answers,
                                       classes, host_params)
    if engine.compile_count() != compiles:
      raise AssertionError(
          f'compiled after warm-up: {compiles} -> '
          f'{engine.compile_count()}')
    stats = frontend.stats()
  finally:
    frontend.shutdown()
  if stats['served_requests'] != len(reqs):
    raise AssertionError(f'served {stats["served_requests"]} of '
                         f'{len(reqs)} requests')
  return dict(compile_secs=compile_secs, run_secs=run_secs,
              buckets=tuple(engine.buckets), requests=len(reqs),
              logit_checksum=float(sum(np.abs(a.logits).sum()
                                       for a in answers)),
              max_twin_err=twin_err, max_ref_err=ref_err)


def _placement(arrays: dict) -> list:
  """``name: shape dtype -> device of each shard`` for every stacked
  array; raises unless each sharded array spreads over distinct
  devices."""
  lines = []
  for name, a in sorted(arrays.items()):
    devs = [s.device.id for s in a.addressable_shards]
    lines.append(f'{name}: {tuple(a.shape)} {a.dtype} '
                 f'{a.sharding.spec} -> devices {devs}')
    if tuple(a.sharding.spec) and len(set(devs)) != len(devs):
      raise AssertionError(f'{name}: shards share a device: {devs}')
  return lines


def mesh_phase(num_devices: int, *, num_nodes, avg_deg, dim, classes,
               fanout, hidden, batch, steps) -> dict:
  """The same graph sharded over ``num_devices``: per-batch DP steps,
  fused tree steps, then the repo's multi-chip dry run."""
  import jax
  import optax
  from graphlearn_tpu.models import (GraphSAGE, TreeSAGE,
                                     create_train_state)
  from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                       FusedDistTreeEpoch,
                                       local_batch_piece,
                                       make_dp_supervised_step,
                                       make_mesh, replicate)
  from graphlearn_tpu.parallel.exchange import resolve_layout
  import __graft_entry__
  rows, cols = build_graph(num_nodes, avg_deg)
  rng = np.random.default_rng(7)
  feats = rng.random((num_nodes, dim), np.float32)
  labels = rng.integers(0, classes, num_nodes).astype(np.int32)
  dds = DistDataset.from_full_graph(
      num_devices, rows, cols, node_feat=feats, node_label=labels,
      num_nodes=num_nodes)
  del rows, cols, feats
  mesh = make_mesh(num_devices)
  layout = resolve_layout(None, num_devices)
  ids = np.random.default_rng(3).permutation(num_nodes)[
      :steps * batch * num_devices]
  tx = optax.adam(3e-3)

  t0 = time.perf_counter()
  loader = DistNeighborLoader(dds, list(fanout), ids, batch_size=batch,
                              shuffle=True, mesh=mesh, seed=0)
  model = GraphSAGE(hidden_features=hidden, out_features=classes,
                    num_layers=len(fanout))
  step = make_dp_supervised_step(model.apply, tx, batch, mesh)
  state, losses = None, []
  for b in loader:
    if state is None:
      state, _ = create_train_state(
          model, jax.random.key(0), local_batch_piece(b, num_devices), tx)
      state = replicate(state, mesh)
    state, loss, _ = step(state, b)
    losses.append(float(loss))
  loader_secs = time.perf_counter() - t0
  _finite('mesh per-batch losses', losses)
  if len(losses) != steps:
    raise AssertionError(f'{len(losses)} mesh steps, expected {steps}')
  placement = _placement(loader.sampler._arrays())
  st = loader.sampler.exchange_stats(tick_metrics=False)
  if st['dist.frontier.dropped'] or st['dist.feature.dropped']:
    raise AssertionError(f'exchange dropped ids: {st}')

  t0 = time.perf_counter()
  tmodel = TreeSAGE(hidden_features=hidden, out_features=classes,
                    num_layers=len(fanout))
  fused = FusedDistTreeEpoch(dds, list(fanout), ids, tmodel, tx,
                             batch_size=batch, mesh=mesh, shuffle=True,
                             seed=0)
  tstate = fused.init_state(jax.random.key(1))
  tstate, tstats = fused.run(tstate)
  tloss = float(_finite('mesh fused tree losses', tstats.losses).mean())
  fused_secs = time.perf_counter() - t0
  fst = fused.sampler.exchange_stats(tick_metrics=False)
  if fst['dist.frontier.dropped']:
    raise AssertionError(f'fused tree epoch dropped frontier ids: {fst}')
  del dds, loader, fused, state, tstate

  t0 = time.perf_counter()
  __graft_entry__.dryrun_multichip(num_devices)
  return dict(layout=layout, placement=placement,
              loader_secs=loader_secs, fused_secs=fused_secs,
              dryrun_secs=time.perf_counter() - t0,
              loss_per_batch=losses[-1], loss_fused_tree=tloss)


def require_tpu() -> dict:
  """Print what JAX found; exit non-zero unless it is a TPU.  JAX
  falls back to the CPU with only a warning when libtpu cannot start,
  and `JAX_PLATFORMS=cpu` selects it outright — both are failures
  here, not slow passes."""
  import jax
  from graphlearn_tpu.utils.compile_cache import enable_compile_cache
  dev = jax.devices()[0]
  info = dict(platform=dev.platform, kind=dev.device_kind,
              count=len(jax.devices()))
  print(f'jax {jax.__version__} platform={info["platform"]} '
        f'device_kind={info["kind"]} devices={info["count"]}')
  print(f'compile cache: {enable_compile_cache()}', flush=True)
  if info['platform'] != 'tpu':
    raise SystemExit(f'chip_smoke: needs a TPU, JAX found '
                     f'{info["platform"]!r}')
  return info


def _report(name: str, out: dict, cache: CacheCounter) -> None:
  facts = ' '.join(
      f'{k}={v:.7g}' if isinstance(v, float) else f'{k}={v}'
      for k, v in out.items() if k not in ('params', 'placement'))
  print(f'{name}: ok {facts} [{cache.take()}]', flush=True)


def main() -> None:
  t_start = time.perf_counter()
  info = require_tpu()
  from graphlearn_tpu.serving.engine import DEFAULT_BUCKETS
  cache = CacheCounter()
  sizes = dict(fanout=FANOUT, hidden=HIDDEN, classes=CLASSES)
  t0 = time.perf_counter()
  ds = build_dataset(NUM_NODES, AVG_DEG, DIM, CLASSES)
  print(f'dataset: ok nodes={NUM_NODES} '
        f'edges={ds.get_graph().indices.shape[0]} dim={DIM} '
        f'build_secs={time.perf_counter() - t0:.4g} [{cache.take()}]',
        flush=True)
  trained = trainer_phase(ds, batch=BATCH, steps=STEPS,
                          eval_seeds=2 * BATCH, **sizes)
  _report('trainer', trained, cache)
  _report('loader', loader_phase(ds, batch=BATCH, steps=STEPS, **sizes),
          cache)
  _report('server', server_phase(ds, params=trained['params'],
                                 buckets=DEFAULT_BUCKETS,
                                 request_sizes=REQUEST_SIZES, **sizes),
          cache)
  del ds, trained
  if info['count'] >= 4:
    out = mesh_phase(4, num_nodes=NUM_NODES, avg_deg=AVG_DEG, dim=DIM,
                     batch=BATCH, steps=STEPS, **sizes)
    for line in out['placement']:
      print(f'mesh placement: {line}')
    _report('mesh', out, cache)
  else:
    print(f'mesh phase: not run ({info["count"]} device)')
  print(f'all phases ok wall_secs={time.perf_counter() - t_start:.4g}')
  print(json.dumps({'ok': True, 'device': info}), flush=True)


if __name__ == '__main__':
  main()
