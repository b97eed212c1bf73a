"""Planted-structure learnability: the stack must LEARN, not just run.

Every synthetic bench uses random labels, so a
decreasing loss proves plumbing, not learning; the real-data accuracy
harnesses (`examples/acc_ogbn_products.py` etc.) SKIP on this
zero-egress box.  This is the offline analog of the reference's 0.787
ogbn-products bar (`examples/train_sage_ogbn_products.py:16`): a task
whose labels are derivable ONLY from neighborhood features —

  * every node gets a random color z(v); its feature is a noisy
    one-hot of z(v);
  * its LABEL is the majority color among its out-neighbors.

A node's own feature says nothing about its label (colors are i.i.d.),
so chance is 1/C for any feature-only model; one round of neighbor
aggregation reads the histogram and solves it.  Training through each
data path must therefore reach accuracy >> chance — proving sampling,
collation, masking, and the step wiring preserve the neighborhood
signal end to end:

  (a) NeighborLoader + per-batch supervised step,
  (b) FusedEpoch (whole-epoch scan program) + fused evaluate,
  (c) DistNeighborLoader + DP step on the 8-device virtual mesh.
"""
import numpy as np
import optax
import pytest

jax = pytest.importorskip('jax')

from graphlearn_tpu.data import Dataset
from graphlearn_tpu.loader import FusedEpoch, NeighborLoader
from graphlearn_tpu.models import (GraphSAGE, create_train_state,
                                   make_eval_step, make_supervised_step)

N, C, DEG, NOISE = 2000, 5, 10, 0.1
CHANCE = 1.0 / C
BAR = 0.75                      # >> chance (0.2); hop 1 covers the
                                # full out-neighborhood (fanout >= DEG)


def _planted(seed=0):
  rng = np.random.default_rng(seed)
  z = rng.integers(0, C, N)
  rows = np.repeat(np.arange(N), DEG)
  cols = rng.integers(0, N, N * DEG)
  hist = np.zeros((N, C), np.int64)
  np.add.at(hist, rows, np.eye(C, dtype=np.int64)[z[cols]])
  y = hist.argmax(1).astype(np.int32)
  x = (np.eye(C, dtype=np.float32)[z]
       + NOISE * rng.standard_normal((N, C)).astype(np.float32))
  return rows, cols, x, y


def _splits(seed=1):
  rng = np.random.default_rng(seed)
  perm = rng.permutation(N)
  return perm[:1500], perm[1500:]


def _model_tx():
  return (GraphSAGE(hidden_features=32, out_features=C, num_layers=2),
          optax.adam(1e-2))


@pytest.mark.slow
def test_learns_through_per_batch_loader():
  rows, cols, x, y = _planted()
  train_idx, test_idx = _splits()
  ds = (Dataset().init_graph((rows, cols), num_nodes=N)
        .init_node_features(x).init_node_labels(y))
  loader = NeighborLoader(ds, [10, 5], train_idx, batch_size=256,
                          shuffle=True, seed=0)
  model, tx = _model_tx()
  state, apply_fn = create_train_state(model, jax.random.key(0),
                                       next(iter(loader)), tx)
  step = make_supervised_step(apply_fn, tx, 256)
  for _ in range(12):
    for batch in loader:
      state, loss, _ = step(state, batch)
  ev = make_eval_step(apply_fn, 256)
  test_loader = NeighborLoader(ds, [10, 5], test_idx, batch_size=256,
                               shuffle=False, seed=0)
  correct = total = 0
  for batch in test_loader:
    c, t = ev(state.params, batch)
    correct += int(c)
    total += int(t)
  acc = correct / max(total, 1)
  assert acc > BAR, f'per-batch path accuracy {acc:.3f} <= {BAR}'


@pytest.mark.slow
def test_learns_through_fused_epoch():
  rows, cols, x, y = _planted()
  train_idx, test_idx = _splits()
  ds = (Dataset().init_graph((rows, cols), num_nodes=N)
        .init_node_features(x, split_ratio=1.0).init_node_labels(y))
  loader = NeighborLoader(ds, [10, 5], train_idx, batch_size=256,
                          shuffle=True, seed=0)
  model, tx = _model_tx()
  state, apply_fn = create_train_state(model, jax.random.key(0),
                                       next(iter(loader)), tx)
  fused = FusedEpoch(ds, [10, 5], train_idx, apply_fn, tx,
                     batch_size=256, shuffle=True, seed=0)
  first_loss = last = None
  for _ in range(12):
    state, stats = fused.run(state)
    if first_loss is None:
      first_loss = stats.loss
    last = stats
  assert last.loss < first_loss
  acc = fused.evaluate(state.params, test_idx)
  assert acc > BAR, f'fused path accuracy {acc:.3f} <= {BAR}'


@pytest.mark.slow
def test_learns_through_dist_loader():
  from graphlearn_tpu.parallel import (DistNeighborLoader,
                                       local_batch_piece,
                                       make_dp_supervised_step,
                                       make_mesh, replicate)
  num_parts = 8
  rows, cols, x, y = _planted()
  train_idx, test_idx = _splits()
  from graphlearn_tpu.parallel import DistDataset
  dds = DistDataset.from_full_graph(num_parts, rows, cols, node_feat=x,
                                    node_label=y, num_nodes=N)
  mesh = make_mesh(num_parts)
  bs = 32
  loader = DistNeighborLoader(dds, [10, 5], train_idx, batch_size=bs,
                              shuffle=True, mesh=mesh, seed=0)
  model, tx = _model_tx()
  first = next(iter(loader))
  local_piece = local_batch_piece(first, num_parts)
  state, apply_fn = create_train_state(model, jax.random.key(0),
                                       local_piece, tx)
  state = replicate(state, mesh)
  step = make_dp_supervised_step(model.apply, tx, bs, mesh)
  for _ in range(12):
    for batch in loader:
      state, loss, correct = step(state, batch)
  # params are mesh-replicated: pull one copy and evaluate through the
  # single-device path on the SAME relabeled graph
  params = jax.tree_util.tree_map(
      lambda v: np.asarray(v.addressable_shards[0].data), state.params)
  ds_eval = (Dataset()
             .init_graph((dds.old2new[rows], dds.old2new[cols]),
                         num_nodes=N)
             .init_node_features(x[dds.new2old])
             .init_node_labels(y[dds.new2old]))
  ev = make_eval_step(apply_fn, 256)
  test_loader = NeighborLoader(ds_eval, [10, 5], dds.old2new[test_idx],
                               batch_size=256, shuffle=False, seed=0)
  correct = total = 0
  for batch in test_loader:
    c, t = ev(params, batch)
    correct += int(c)
    total += int(t)
  acc = correct / max(total, 1)
  assert acc > BAR, f'dist path accuracy {acc:.3f} <= {BAR}'
