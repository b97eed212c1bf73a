"""FusedDistEpoch: the one-program distributed epoch must train, keep
its telemetry, match the per-batch mesh step's numbers, and refuse the
configurations its design excludes."""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from graphlearn_tpu.loader import NeighborLoader
from graphlearn_tpu.data import Dataset
from graphlearn_tpu.models import GraphSAGE, create_train_state
from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                     FusedDistEpoch, make_mesh, replicate)

#: CPU-mesh scan-compile heavy (multi-minute): excluded from the
#: default run, selected by `pytest -m slow` (see pyproject.toml)
pytestmark = pytest.mark.slow

N = 256
CLASSES = 4
P_PARTS = 4


def _dist_dataset(split_ratio=None):
  rng = np.random.default_rng(0)
  labels = (np.arange(N) % CLASSES).astype(np.int32)
  rows, cols = [], []
  for v in range(N):
    for _ in range(5):
      if rng.random() < 0.8:
        u = int(rng.choice(np.nonzero(labels == labels[v])[0]))
      else:
        u = int(rng.integers(0, N))
      rows.append(v)
      cols.append(u)
  feats = np.eye(CLASSES, 8, dtype=np.float32)[labels]
  feats += rng.normal(0, 0.3, feats.shape).astype(np.float32)
  kw = {} if split_ratio is None else {'split_ratio': split_ratio}
  return DistDataset.from_full_graph(
      P_PARTS, np.asarray(rows), np.asarray(cols), node_feat=feats,
      node_label=labels, num_nodes=N, **kw)


def _init_state(tx, bs=16):
  """Params from a single-chip loader batch over an equivalent graph
  (shapes only matter via feature dim / classes)."""
  rng = np.random.default_rng(0)
  ds = (Dataset()
        .init_graph((np.arange(32), (np.arange(32) + 1) % 32),
                    layout='COO', num_nodes=32)
        .init_node_features(rng.random((32, 8), np.float32).astype(
            np.float32))
        .init_node_labels((np.arange(32) % CLASSES).astype(np.int32)))
  loader = NeighborLoader(ds, [3, 2], np.arange(32), batch_size=bs)
  model = GraphSAGE(hidden_features=16, out_features=CLASSES,
                    num_layers=2)
  return create_train_state(model, jax.random.key(0),
                            next(iter(loader)), tx)


def test_fused_dist_epoch_trains():
  ds = _dist_dataset()
  mesh = make_mesh(P_PARTS)
  tx = optax.adam(1e-2)
  state, apply_fn = _init_state(tx)
  fused = FusedDistEpoch(ds, [3, 2], np.arange(N), apply_fn, tx,
                         batch_size=16, mesh=mesh, shuffle=True, seed=0)
  assert len(fused) == N // (16 * P_PARTS)
  state = replicate(state, mesh)
  state, first = fused.run(state)
  for _ in range(12):
    state, stats = fused.run(state)
  assert stats['seeds'] == N
  assert stats['loss'] < first['loss']
  assert stats['accuracy'] > 0.6
  # telemetry flowed out of the fused program
  st = fused.sampler.exchange_stats(tick_metrics=False)
  assert st['dist.frontier.offered'] > 0
  # evaluate(): one SPMD scan program, same graph as the train split's
  # accuracy (dist fused eval without leaving the
  # fused path).  Params are replicated; pass the replicated leaf tree.
  acc = fused.evaluate(state.params, np.arange(N))
  assert acc > 0.6
  assert abs(acc - stats['accuracy']) < 0.25


def test_fused_dist_matches_per_batch_engine():
  """Same seeds, same slack: fused scan step 0 must equal the
  per-batch mesh sampler + DP step (identical key schedules are not
  promised — compare the TRAINING SIGNAL by loss magnitude and the
  telemetry's offered counts over one epoch)."""
  ds = _dist_dataset()
  mesh = make_mesh(P_PARTS)
  tx = optax.adam(1e-2)
  state, apply_fn = _init_state(tx)

  fused = FusedDistEpoch(ds, [3, 2], np.arange(N), apply_fn, tx,
                         batch_size=16, mesh=mesh, shuffle=False,
                         seed=0, input_space='old')
  s1 = replicate(jax.tree_util.tree_map(jnp.copy, state), mesh)
  s1, stats = fused.run(s1)
  offered_fused = fused.sampler.exchange_stats(
      tick_metrics=False)['dist.frontier.offered']

  from graphlearn_tpu.parallel import make_dp_supervised_step
  loader = DistNeighborLoader(ds, [3, 2], np.arange(N), batch_size=16,
                              mesh=mesh, shuffle=False, seed=0)
  step = make_dp_supervised_step(apply_fn, tx, 16, mesh)
  s2 = replicate(jax.tree_util.tree_map(jnp.copy, state), mesh)
  losses = []
  for batch in loader:
    s2, loss, _ = step(s2, batch)
    losses.append(float(loss))
  st_loader = loader.sampler.exchange_stats(tick_metrics=False)
  # identical exchange GEOMETRY: same static slot budget per epoch
  # (offered counts differ by RNG schedule — compare only coarsely)
  st_fused = fused.sampler.exchange_stats(tick_metrics=False)
  assert st_fused['dist.frontier.slots'] == st_loader[
      'dist.frontier.slots']
  assert 0 < offered_fused
  ratio = offered_fused / max(st_loader['dist.frontier.offered'], 1)
  assert 0.7 < ratio < 1.4, ratio
  assert len(losses) == len(np.asarray(stats['losses']))
  assert abs(stats['loss'] - np.mean(losses)) < 0.3


def test_fused_dist_link_epoch_trains():
  """Binary-mode fused mesh link training: loss decreases below ln(2)
  (positives separated from collective strict negatives) and the
  exchange telemetry flows out of the scan."""
  from graphlearn_tpu.parallel import FusedDistLinkEpoch
  ds = _dist_dataset()
  mesh = make_mesh(P_PARTS)
  tx = optax.adam(1e-2)
  state, apply_fn = _init_embed_state(tx)
  # seed edges = existing edges (positives), OLD id space
  rows = np.repeat(np.arange(N), 5)[:512]
  cols = np.asarray(
      [int(c) for r in range(N) for c in _neighbors_of(ds, r)])[:512]
  fused = FusedDistLinkEpoch(ds, [3, 2], (rows[:512], cols[:512]),
                             apply_fn, tx, batch_size=16, mesh=mesh,
                             neg_sampling='binary', shuffle=True,
                             seed=0)
  state = replicate(state, mesh)
  state, first = fused.run(state)
  for _ in range(15):
    state, stats = fused.run(state)
  assert stats['seeds'] == 512
  assert stats['loss'] < first['loss']
  assert stats['loss'] < 0.67
  st = fused.sampler.exchange_stats(tick_metrics=False)
  assert st['dist.frontier.offered'] > 0
  # evaluate(): held-out link AUC as one SPMD scan program — trained
  # positives must rank above fresh strict negatives
  auc = fused.evaluate(state.params, (rows[:128], cols[:128]))
  assert 0.6 < auc <= 1.0


def _neighbors_of(ds, r):
  """Old-space out-neighbors of old node r (via the shard CSR)."""
  new = int(ds.old2new[r])
  bounds = np.asarray(ds.graph.bounds)
  p = int(np.searchsorted(bounds, new, side='right')) - 1
  local = new - bounds[p]
  indptr = np.asarray(ds.graph.indptr[p])
  indices = np.asarray(ds.graph.indices[p])
  nbrs = indices[indptr[local]:indptr[local + 1]]
  return ds.new2old[nbrs]


def _init_embed_state(tx, bs=16):
  """Embedding model (out = 16-dim embeddings) for the link tests."""
  model = GraphSAGE(hidden_features=16, out_features=16, num_layers=2)
  rng = np.random.default_rng(0)
  ds0 = (Dataset()
         .init_graph((np.arange(32), (np.arange(32) + 1) % 32),
                     layout='COO', num_nodes=32)
         .init_node_features(rng.random((32, 8)).astype(np.float32)))
  loader = NeighborLoader(ds0, [3, 2], np.arange(32), batch_size=bs)
  b0 = next(iter(loader))
  params = model.init(jax.random.key(0), b0.x, b0.edge_index,
                      b0.edge_mask)
  from graphlearn_tpu.models.train import TrainState
  state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
  return state, model.apply


def test_fused_dist_link_tiered_trains():
  """The mesh link driver's tiered path: chunked collect → cold
  service → train/AUC-consume scans run end-to-end."""
  from graphlearn_tpu.parallel import FusedDistLinkEpoch
  ds = _dist_dataset(split_ratio=0.5)
  mesh = make_mesh(P_PARTS)
  tx = optax.adam(1e-2)
  state, apply_fn = _init_embed_state(tx)
  rows = np.repeat(np.arange(N), 5)[:256]
  cols = np.asarray(
      [int(c) for r in range(N) for c in _neighbors_of(ds, r)])[:256]
  fused = FusedDistLinkEpoch(ds, [3, 2], (rows, cols), apply_fn, tx,
                             batch_size=16, mesh=mesh,
                             neg_sampling='binary', shuffle=True,
                             seed=0)
  assert fused._tiered
  state = replicate(state, mesh)
  state, first = fused.run(state)
  for _ in range(4):
    state, stats = fused.run(state)
  assert stats['seeds'] == 256
  assert np.isfinite(float(stats['loss']))
  st = fused.sampler.exchange_stats(tick_metrics=False)
  assert st['dist.feature.cold_lookups'] > 0
  auc = fused.evaluate(state.params, (rows[:64], cols[:64]))
  assert 0.0 <= auc <= 1.0


def test_fused_dist_link_refuses_adaptive():
  from graphlearn_tpu.parallel import FusedDistLinkEpoch
  ds = _dist_dataset()
  tx = optax.adam(1e-2)
  state, apply_fn = _init_embed_state(tx)
  with pytest.raises(ValueError, match='adaptive'):
    FusedDistLinkEpoch(ds, [3, 2], (np.arange(16), np.arange(16)),
                       apply_fn, tx, batch_size=8,
                       mesh=make_mesh(P_PARTS),
                       exchange_slack='adaptive')


def test_fused_dist_tiered_epoch_matches_per_batch():
  """ISSUE 5 acceptance: FusedDistEpoch runs end-to-end with a
  ``split_ratio < 1`` store, and its chunked collect + cold-service
  batches are IDENTICAL to the per-batch tiered sampler driven with
  the same keys."""
  from graphlearn_tpu.parallel import DistNeighborSampler
  ds = _dist_dataset(split_ratio=0.4)
  mesh = make_mesh(P_PARTS)
  tx = optax.adam(1e-2)
  state, apply_fn = _init_state(tx)
  fused = FusedDistEpoch(ds, [3, 2], np.arange(N), apply_fn, tx,
                         batch_size=16, mesh=mesh, shuffle=False,
                         seed=0)
  assert fused._tiered
  # -- batch identity vs the per-batch engine, same keys ------------
  seeds = np.stack(list(fused._batcher)).reshape(-1, P_PARTS, 16)
  key = jax.random.fold_in(fused._base_key, 1)    # epoch 1's key
  keys = fused._chunk_key_stack(key, 0, seeds.shape[0])
  batches, _stats = fused._compiled_collect(
      fused._put_batches(seeds), keys, fused.sampler._arrays())
  batches = fused._overlay_chunk(batches)
  ref = DistNeighborSampler(ds, [3, 2], mesh=mesh, seed=0)
  for i in range(seeds.shape[0]):
    out = ref.sample_from_nodes(seeds[i], key=keys[i])
    np.testing.assert_array_equal(np.asarray(batches.node[i]),
                                  np.asarray(out['node']))
    np.testing.assert_array_equal(np.asarray(batches.x[i]),
                                  np.asarray(out['x']))
    np.testing.assert_array_equal(np.asarray(batches.y[i]),
                                  np.asarray(out['y']))
  # the store really is tiered and the cold tier was exercised
  st = fused.sampler.exchange_stats(tick_metrics=False)
  assert st['dist.feature.cold_lookups'] > 0
  # -- end-to-end: run() + evaluate() through the tiered path -------
  state = replicate(state, mesh)
  state, first = fused.run(state)
  for _ in range(8):
    state, stats = fused.run(state)
  assert stats['seeds'] == N
  assert stats['loss'] < first['loss']
  acc = fused.evaluate(state.params, np.arange(N))
  assert 0.0 <= acc <= 1.0


def test_fused_dist_tiered_tail_chunk_padded():
  """S % chunk != 0: the tail chunk pads with INVALID_ID steps so
  every chunk reuses ONE compiled shape, and losses/valid counts are
  identical to the unchunked epoch (padded steps contribute nothing)."""
  import os
  ds = _dist_dataset(split_ratio=0.4)
  mesh = make_mesh(P_PARTS)
  tx = optax.adam(1e-2)
  state, apply_fn = _init_state(tx)
  state = replicate(state, mesh)

  def epoch_losses(chunk_env):
    os.environ['GLT_FUSED_COLD_CHUNK'] = chunk_env
    try:
      fused = FusedDistEpoch(ds, [3, 2], np.arange(N), apply_fn, tx,
                             batch_size=16, mesh=mesh, shuffle=False,
                             seed=0)
      assert fused._tiered
      _, stats = fused.run(jax.tree_util.tree_map(jnp.copy, state))
      acc = fused.evaluate(state.params, np.arange(N))
      return np.asarray(stats.losses), int(stats['seeds']), acc
    finally:
      del os.environ['GLT_FUSED_COLD_CHUNK']

  # 4 steps per epoch: chunk=3 → chunks of 3 + a 1-step tail padded
  # to 3; chunk=4 → one exact chunk (the reference)
  ls_tail, seeds_tail, acc_tail = epoch_losses('3')
  ls_ref, seeds_ref, acc_ref = epoch_losses('4')
  assert ls_tail.shape == ls_ref.shape          # padded steps sliced
  np.testing.assert_allclose(ls_tail, ls_ref, rtol=1e-6)
  assert seeds_tail == seeds_ref == N
  assert acc_tail == acc_ref


def test_fused_dist_refuses_adaptive_slack():
  ds = _dist_dataset()
  tx = optax.adam(1e-2)
  _, apply_fn = _init_state(tx)
  with pytest.raises(ValueError, match='adaptive'):
    FusedDistEpoch(ds, [3, 2], np.arange(N), apply_fn, tx,
                   batch_size=16, mesh=make_mesh(P_PARTS),
                   exchange_slack='adaptive')


def test_fused_dist_tree_epoch_trains():
  """The mesh tree path: sharded-graph tree expansion + one fused
  feature/label exchange + pmean DP updates learn the planted
  communities, evaluate() agrees, and telemetry flows."""
  from graphlearn_tpu.models import TreeSAGE
  from graphlearn_tpu.parallel import FusedDistTreeEpoch
  ds = _dist_dataset()
  mesh = make_mesh(P_PARTS)
  tx = optax.adam(1e-2)
  model = TreeSAGE(hidden_features=16, out_features=CLASSES,
                   num_layers=2)
  fused = FusedDistTreeEpoch(ds, [4, 3], np.arange(N), model, tx,
                             batch_size=16, mesh=mesh, shuffle=True,
                             seed=0)
  assert len(fused) == N // (16 * P_PARTS)
  state = fused.init_state(jax.random.key(0))
  state, first = fused.run(state)
  for _ in range(14):
    state, stats = fused.run(state)
  assert stats['seeds'] == N
  assert stats['loss'] < first['loss']
  assert stats['accuracy'] > 0.6, stats['accuracy']
  acc = fused.evaluate(state.params, np.arange(N))
  assert acc > 0.6, acc
  st = fused.sampler.exchange_stats(tick_metrics=False)
  assert st['dist.frontier.offered'] > 0
  assert st['dist.feature.offered'] > 0


def test_fused_dist_tree_tiered_trains():
  """The tree driver's tiered path: chunked collect (concatenated
  level layout) → cold service → consume scans train end-to-end."""
  from graphlearn_tpu.models import TreeSAGE
  from graphlearn_tpu.parallel import FusedDistTreeEpoch
  ds = _dist_dataset(split_ratio=0.5)
  mesh = make_mesh(P_PARTS)
  tx = optax.adam(1e-2)
  model = TreeSAGE(hidden_features=16, out_features=CLASSES,
                   num_layers=2)
  fused = FusedDistTreeEpoch(ds, [4, 3], np.arange(N), model, tx,
                             batch_size=16, mesh=mesh, shuffle=True,
                             seed=0)
  assert fused._tiered
  state = fused.init_state(jax.random.key(0))
  state, first = fused.run(state)
  for _ in range(6):
    state, stats = fused.run(state)
  assert stats['seeds'] == N
  assert np.isfinite(float(stats['loss']))
  assert stats['loss'] < first['loss']
  st = fused.sampler.exchange_stats(tick_metrics=False)
  assert st['dist.feature.cold_lookups'] > 0
  acc = fused.evaluate(state.params, np.arange(N))
  assert 0.0 <= acc <= 1.0


def test_fused_dist_tree_refuses_adaptive():
  from graphlearn_tpu.models import TreeSAGE
  from graphlearn_tpu.parallel import FusedDistTreeEpoch
  model = TreeSAGE(hidden_features=8, out_features=CLASSES,
                   num_layers=2)
  tx = optax.adam(1e-2)
  with pytest.raises(ValueError, match='adaptive'):
    FusedDistTreeEpoch(_dist_dataset(), [3, 2], np.arange(N), model,
                       tx, batch_size=16, mesh=make_mesh(P_PARTS),
                       exchange_slack='adaptive')
