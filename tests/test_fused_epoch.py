"""FusedEpoch: the whole-epoch lax.scan program must train like the
per-batch path, be deterministic under its seed, and refuse datasets
its constraints exclude."""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from graphlearn_tpu.data import Dataset
from graphlearn_tpu.loader import FusedEpoch, NeighborLoader
from graphlearn_tpu.models import (GraphSAGE, create_train_state,
                                   make_supervised_step)
from graphlearn_tpu.sampler.neighbor_sampler import _multihop_sample


def _cluster_dataset(n=90, d=8, classes=3, seed=0, split_ratio=1.0):
  rng = np.random.default_rng(seed)
  labels = (np.arange(n) % classes).astype(np.int32)
  rows, cols = [], []
  for v in range(n):
    for _ in range(6):
      if rng.random() < 0.85:
        u = rng.choice(np.nonzero(labels == labels[v])[0])
      else:
        u = rng.integers(0, n)
      rows.append(v)
      cols.append(int(u))
  feats = np.eye(classes, d, dtype=np.float32)[labels]
  feats += rng.normal(0, 0.3, feats.shape).astype(np.float32)
  ds = (Dataset()
        .init_graph((np.array(rows), np.array(cols)), layout='COO',
                    num_nodes=n)
        .init_node_features(feats, split_ratio=split_ratio)
        .init_node_labels(labels))
  return ds, labels


def _setup(ds, batch_size=32, seed=0):
  model = GraphSAGE(hidden_features=16, out_features=3, num_layers=2)
  tx = optax.adam(1e-2)
  loader = NeighborLoader(ds, [4, 3], np.arange(90), batch_size=batch_size)
  state, apply_fn = create_train_state(
      model, jax.random.key(seed), next(iter(loader)), tx)
  return state, apply_fn, tx


def test_fused_epoch_trains():
  ds, _ = _cluster_dataset()
  state, apply_fn, tx = _setup(ds)
  fused = FusedEpoch(ds, [4, 3], np.arange(90), apply_fn, tx,
                     batch_size=32, shuffle=True, seed=0)
  assert len(fused) == 3                      # 90 seeds / 32 -> padded tail
  state, first = fused.run(state)             # run() donates its input state
  for _ in range(15):
    state, stats = fused.run(state)
  assert stats['seeds'] == 90                 # padded slots not counted
  assert stats['loss'] < first['loss']
  assert stats['accuracy'] > 0.8
  assert int(state.step) == 16 * len(fused)   # every scan step stepped optax


def test_fused_epoch_deterministic():
  ds, _ = _cluster_dataset()
  state, apply_fn, tx = _setup(ds)
  runs = []
  for _ in range(2):
    fused = FusedEpoch(ds, [4, 3], np.arange(90), apply_fn, tx,
                       batch_size=32, shuffle=True, seed=7)
    s, stats = fused.run(jax.tree_util.tree_map(jnp.copy, state))
    runs.append((np.asarray(stats['losses']),
                 np.asarray(jax.tree_util.tree_leaves(s.params)[0])))
  np.testing.assert_array_equal(runs[0][0], runs[1][0])
  np.testing.assert_array_equal(runs[0][1], runs[1][1])


def test_fused_step_matches_manual_batch():
  """One-batch epoch parity: re-derive the scan body's sample with the
  fused key schedule (epoch=1, i=0), collate it by hand, push it
  through `make_supervised_step` — the fused loss must match exactly."""
  from graphlearn_tpu.loader.transform import Batch, _gather_labels
  ds, _ = _cluster_dataset()
  state, apply_fn, tx = _setup(ds, batch_size=90)
  fused = FusedEpoch(ds, [4, 3], np.arange(90), apply_fn, tx,
                     batch_size=90, shuffle=False, seed=3)
  seeds = np.stack(list(fused._batcher))
  assert seeds.shape == (1, 90)
  key = jax.random.fold_in(fused._base_key, 1)
  g = ds.get_graph()
  (nodes, count, row, col, _e, emask, seed_local, _nsn,
   _nse) = _multihop_sample(
       g.indptr, g.indices, None, jnp.asarray(seeds[0]),
       jax.random.fold_in(key, 0), fanouts=(4, 3),
       node_cap=fused._node_cap, with_edge=False)
  assert int(count) <= fused._node_cap
  batch = Batch(
      x=ds.node_features._device_get(nodes),
      y=_gather_labels(ds.get_node_label_device(), nodes),
      edge_index=jnp.stack([row, col]),
      node=nodes, node_mask=nodes >= 0, edge_mask=emask,
      batch=jnp.asarray(seeds[0]), batch_size=90,
      metadata={'seed_local': seed_local})
  step = make_supervised_step(apply_fn, tx, 90)
  state_copy = jax.tree_util.tree_map(jnp.copy, state)
  _, loss_manual, correct_manual = step(state_copy, batch)
  _, stats = fused.run(state)
  np.testing.assert_allclose(np.asarray(stats['losses'][0]),
                             np.asarray(loss_manual), rtol=1e-6)
  assert stats['correct'] == int(correct_manual)


def test_fused_epoch_remat_trains_same_task():
  """remat=True must only change memory behavior, not learning: the
  rematerialized epoch trains to the same quality."""
  ds, _ = _cluster_dataset()
  state, apply_fn, tx = _setup(ds)
  fused = FusedEpoch(ds, [4, 3], np.arange(90), apply_fn, tx,
                     batch_size=32, shuffle=True, seed=0, remat=True)
  state, first = fused.run(state)
  for _ in range(15):
    state, stats = fused.run(state)
  assert stats['loss'] < first['loss']
  assert stats['accuracy'] > 0.8


def test_fused_epoch_tiered_matches_untiered():
  """Tiered Features (split_ratio < 1) now run as tiered fused epochs
  (r10): chunked collect scans + the cache-aware cold service between
  dispatches + train scans.  Same seed, same feature VALUES, so the
  per-step losses must match the fully-HBM single-program epoch."""
  ds_full, _ = _cluster_dataset()
  ds_tier, _ = _cluster_dataset(split_ratio=0.4)
  state_f, apply_fn, tx = _setup(ds_full)
  state_t = jax.tree_util.tree_map(jnp.copy, state_f)
  fused_f = FusedEpoch(ds_full, [4, 3], np.arange(90), apply_fn, tx,
                       batch_size=32, shuffle=True, seed=0)
  fused_t = FusedEpoch(ds_tier, [4, 3], np.arange(90), apply_fn, tx,
                       batch_size=32, shuffle=True, seed=0)
  assert fused_t._tiered and not fused_f._tiered
  state_f, stats_f = fused_f.run(state_f)
  state_t, stats_t = fused_t.run(state_t)
  np.testing.assert_allclose(np.asarray(stats_t['losses']),
                             np.asarray(stats_f['losses']), rtol=1e-5)
  assert stats_t['seeds'] == stats_f['seeds'] == 90
  # the cold tier actually served rows (this is not a vacuous run)
  assert fused_t._feat.cold_stats['cold_lookups'] > 0
  # and evaluate() takes the chunked path end-to-end
  acc = fused_t.evaluate(state_t.params, np.arange(90))
  assert 0.0 <= acc <= 1.0


def test_fused_epoch_refuses_missing_labels():
  ds, _ = _cluster_dataset()
  ds2 = (Dataset()
         .init_graph((ds.get_graph().indptr, ds.get_graph().indices),
                     layout='CSR', num_nodes=90)
         .init_node_features(np.ones((90, 4), np.float32)))
  _, apply_fn, tx = _setup(ds)
  with pytest.raises(ValueError, match='labels'):
    FusedEpoch(ds2, [4, 3], np.arange(90), apply_fn, tx, batch_size=32)


@pytest.mark.slow
def test_fused_evaluate_matches_eval_loop():
  """fused.evaluate == a make_eval_step loop over the same split
  (different sampling keys; on a well-separated task both sides must
  land at high accuracy)."""
  from graphlearn_tpu.models import make_eval_step
  ds, _ = _cluster_dataset()
  state, apply_fn, tx = _setup(ds)
  fused = FusedEpoch(ds, [4, 3], np.arange(90), apply_fn, tx,
                     batch_size=32, shuffle=True, seed=0)
  for _ in range(15):
    state, _ = fused.run(state)
  acc_fused = fused.evaluate(state.params, np.arange(90))
  eval_step = make_eval_step(apply_fn, 32)
  loader = NeighborLoader(ds, [4, 3], np.arange(90), batch_size=32)
  correct = total = 0
  for batch in loader:
    c, t = eval_step(state.params, batch)
    correct += int(c)
    total += int(t)
  assert total == 90
  assert acc_fused > 0.8
  assert abs(acc_fused - correct / total) < 0.15


@pytest.mark.slow
def test_fused_link_epoch_trains():
  """Binary-mode fused link training: loss decreases and positive
  pairs end up scoring above sampled negatives."""
  from graphlearn_tpu.loader import FusedLinkEpoch
  ds, labels = _cluster_dataset()
  g = ds.get_graph()
  # seed edges = existing edges (positives)
  rows = np.repeat(np.arange(90), np.diff(np.asarray(g.indptr)))
  cols = np.asarray(g.indices)
  sel = np.random.default_rng(0).permutation(len(rows))[:128]
  model = GraphSAGE(hidden_features=16, out_features=8, num_layers=2)
  import optax as _optax
  tx = _optax.adam(1e-2)
  loader = NeighborLoader(ds, [4, 3], np.arange(90), batch_size=32)
  state, apply_fn = create_train_state(
      model, jax.random.key(0), next(iter(loader)), tx)
  fused = FusedLinkEpoch(ds, [4, 3], (rows[sel], cols[sel]), apply_fn,
                         tx, batch_size=32, neg_sampling='binary',
                         shuffle=True, seed=0)
  assert len(fused) == 4
  state, first = fused.run(state)
  for _ in range(20):
    state, stats = fused.run(state)
  assert stats['seeds'] == 128
  assert stats['loss'] < first['loss']
  assert stats['loss'] < 0.62       # below ln(2): pos/neg separated


@pytest.mark.slow
def test_fused_link_triplet_trains():
  from graphlearn_tpu.loader import FusedLinkEpoch
  from graphlearn_tpu.sampler import NegativeSampling
  ds, _ = _cluster_dataset()
  g = ds.get_graph()
  rows = np.repeat(np.arange(90), np.diff(np.asarray(g.indptr)))
  cols = np.asarray(g.indices)
  sel = np.random.default_rng(1).permutation(len(rows))[:64]
  model = GraphSAGE(hidden_features=16, out_features=8, num_layers=2)
  import optax as _optax
  tx = _optax.adam(1e-2)
  loader = NeighborLoader(ds, [4, 3], np.arange(90), batch_size=32)
  state, apply_fn = create_train_state(
      model, jax.random.key(0), next(iter(loader)), tx)
  fused = FusedLinkEpoch(ds, [4, 3], (rows[sel], cols[sel]), apply_fn,
                         tx, batch_size=32,
                         neg_sampling=NegativeSampling('triplet', 2),
                         shuffle=True, seed=0)
  state, first = fused.run(state)
  for _ in range(20):
    state, stats = fused.run(state)
  assert stats['loss'] < first['loss']


def test_fused_link_tiered_matches_untiered():
  """FusedLinkEpoch over a tiered Feature (r10): the sample-only
  collect scans + the cache-aware cold service must reproduce the
  fully-HBM single-program epoch's losses under the same seed."""
  from graphlearn_tpu.loader import FusedLinkEpoch
  import optax as _optax
  ds_full, _ = _cluster_dataset()
  ds_tier, _ = _cluster_dataset(split_ratio=0.4)
  g = ds_full.get_graph()
  rows = np.repeat(np.arange(90), np.diff(np.asarray(g.indptr)))
  cols = np.asarray(g.indices)
  sel = np.arange(64)
  model = GraphSAGE(hidden_features=16, out_features=8, num_layers=2)
  tx = _optax.adam(1e-2)
  loader = NeighborLoader(ds_full, [4, 3], np.arange(90), batch_size=32)
  state, apply_fn = create_train_state(
      model, jax.random.key(0), next(iter(loader)), tx)
  state_t = jax.tree_util.tree_map(jnp.copy, state)
  fused_f = FusedLinkEpoch(ds_full, [4, 3], (rows[sel], cols[sel]),
                           apply_fn, tx, batch_size=32,
                           neg_sampling='binary', shuffle=False, seed=3)
  fused_t = FusedLinkEpoch(ds_tier, [4, 3], (rows[sel], cols[sel]),
                           apply_fn, tx, batch_size=32,
                           neg_sampling='binary', shuffle=False, seed=3)
  assert fused_t._tiered and not fused_f._tiered
  state, stats_f = fused_f.run(state)
  state_t, stats_t = fused_t.run(state_t)
  np.testing.assert_allclose(np.asarray(stats_t['losses']),
                             np.asarray(stats_f['losses']), rtol=1e-5)
  assert fused_t._feat.cold_stats['cold_lookups'] > 0
  # tiered evaluate() takes the chunked collect + AUC-consume path
  auc = fused_t.evaluate(state_t.params, (rows[sel][:32],
                                          cols[sel][:32]))
  assert 0.0 <= auc <= 1.0


@pytest.mark.slow
def test_fused_link_step_matches_manual_batch():
  """Parity pin for the duplicated seed/metadata assembly: one-batch
  fused link epoch == manual sample_negative + _multihop_sample +
  metadata + link step with the fused key schedule."""
  from graphlearn_tpu.loader import FusedLinkEpoch
  from graphlearn_tpu.loader.transform import Batch
  from graphlearn_tpu.models.train import link_loss_from_metadata
  from graphlearn_tpu.ops.negative import sample_negative
  import optax as _optax
  ds, _ = _cluster_dataset()
  g = ds.get_graph()
  rows = np.repeat(np.arange(90), np.diff(np.asarray(g.indptr)))
  cols = np.asarray(g.indices)
  b = 32
  sel = np.arange(b)
  model = GraphSAGE(hidden_features=16, out_features=8, num_layers=2)
  tx = _optax.adam(1e-2)
  loader = NeighborLoader(ds, [4, 3], np.arange(90), batch_size=b)
  state, apply_fn = create_train_state(
      model, jax.random.key(0), next(iter(loader)), tx)
  fused = FusedLinkEpoch(ds, [4, 3], (rows[sel], cols[sel]), apply_fn,
                         tx, batch_size=b, neg_sampling='binary',
                         shuffle=False, seed=5)
  # re-derive step 0's batch with the fused key schedule
  key = jax.random.fold_in(jax.random.fold_in(fused._base_key, 1), 0)
  src = jnp.asarray(rows[sel].astype(np.int32))
  dst = jnp.asarray(cols[sel].astype(np.int32))
  batch = fused._link_batch(src, dst, jnp.ones((b,), jnp.int32), key,
                            fused._dev, False)

  def loss_fn(params):
    emb = apply_fn(params, batch.x, batch.edge_index, batch.edge_mask)
    return link_loss_from_metadata(emb, batch.metadata)

  loss_manual = float(loss_fn(state.params))
  state2 = jax.tree_util.tree_map(jnp.copy, state)
  _, stats = fused.run(state2)
  np.testing.assert_allclose(float(np.asarray(stats['losses'])[0]),
                             loss_manual, rtol=1e-5)


@pytest.mark.slow
def test_fused_matches_per_batch_loss_scale():
  """Fused and per-batch paths train to comparable losses on the same
  task (not bit-identical: the key schedules differ by design)."""
  ds, _ = _cluster_dataset()
  state, apply_fn, tx = _setup(ds)
  step = make_supervised_step(apply_fn, tx, 32)
  loader = NeighborLoader(ds, [4, 3], np.arange(90), batch_size=32,
                          shuffle=True, seed=0)
  s_loop = state
  for _ in range(10):
    for batch in loader:
      s_loop, loss_loop, _ = step(s_loop, batch)
  fused = FusedEpoch(ds, [4, 3], np.arange(90), apply_fn, tx,
                     batch_size=32, shuffle=True, seed=0)
  s_fused = state
  for _ in range(10):
    s_fused, stats = fused.run(s_fused)
  assert abs(float(loss_loop) - stats['loss']) < 0.5


def test_fused_link_evaluate_auc():
  """`FusedLinkEpoch.evaluate`: held-out link AUC as one scan
  program.  Untrained embeddings must score near chance; after
  training on the clustered graph, held-out WITHIN-cluster edges
  must rank above strict random negatives (mostly cross-cluster)."""
  from graphlearn_tpu.loader import FusedLinkEpoch
  ds, labels = _cluster_dataset()
  g = ds.get_graph()
  rows = np.repeat(np.arange(90), np.diff(np.asarray(g.indptr)))
  cols = np.asarray(g.indices)
  perm = np.random.default_rng(1).permutation(len(rows))
  train_sel, eval_sel = perm[:256], perm[256:352]
  model = GraphSAGE(hidden_features=16, out_features=8, num_layers=2)
  import optax as _optax
  tx = _optax.adam(1e-2)
  loader = NeighborLoader(ds, [4, 3], np.arange(90), batch_size=32)
  state, apply_fn = create_train_state(
      model, jax.random.key(0), next(iter(loader)), tx)
  fused = FusedLinkEpoch(ds, [4, 3], (rows[train_sel], cols[train_sel]),
                         apply_fn, tx, batch_size=32,
                         neg_sampling='binary', shuffle=True, seed=0)
  eval_edges = (rows[eval_sel], cols[eval_sel])
  auc0 = fused.evaluate(state.params, eval_edges)
  assert 0.2 < auc0 < 0.8, f'untrained AUC {auc0} not near chance'
  for _ in range(20):
    state, _ = fused.run(state)
  auc1 = fused.evaluate(state.params, eval_edges)
  assert auc1 > 0.8, f'trained AUC {auc1} <= 0.8'
  assert auc1 > auc0
  # triplet mode refuses: precision@rank is its metric, not this AUC
  tri = FusedLinkEpoch(ds, [4, 3], eval_edges, apply_fn, tx,
                       batch_size=32, neg_sampling=('triplet', 1),
                       seed=0)
  with pytest.raises(ValueError, match='binary'):
    tri.evaluate(state.params, eval_edges)
