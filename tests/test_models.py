"""Model-family tests: correctness of masked aggregation and that a
few steps of training reduce loss on a learnable synthetic task."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from graphlearn_tpu.data import Dataset
from graphlearn_tpu.loader import NeighborLoader
from graphlearn_tpu.models import (GAT, GCN, GraphSAGE, SAGEConv,
                                   create_train_state, make_eval_step,
                                   make_supervised_step, segment_mean)


def test_segment_mean_masks_invalid():
  data = jnp.ones((4, 2))
  seg = jnp.array([0, 0, 1, -1])
  mask = jnp.array([True, True, True, False])
  out = segment_mean(data, seg, 3, mask)
  np.testing.assert_allclose(np.asarray(out[0]), 1.0)
  np.testing.assert_allclose(np.asarray(out[1]), 1.0)
  np.testing.assert_allclose(np.asarray(out[2]), 0.0)


def test_sageconv_matches_manual():
  # 3 nodes, edges 1->0, 2->0 (+ one masked junk edge).
  x = jnp.array([[1., 0.], [0., 1.], [2., 2.]])
  ei = jnp.array([[1, 2, -1], [0, 0, -1]])
  em = jnp.array([True, True, False])
  conv = SAGEConv(4)
  params = conv.init(jax.random.key(0), x, ei, em)
  out = conv.apply(params, x, ei, em)
  w_self = params['params']['lin_self']['kernel']
  b_self = params['params']['lin_self']['bias']
  w_neigh = params['params']['lin_neigh']['kernel']
  agg0 = (np.asarray(x[1]) + np.asarray(x[2])) / 2
  expect0 = np.asarray(x[0]) @ w_self + b_self + agg0 @ w_neigh
  np.testing.assert_allclose(np.asarray(out[0]), expect0, rtol=1e-5)
  # node 1 has no incoming edges -> only self term.
  expect1 = np.asarray(x[1]) @ w_self + b_self
  np.testing.assert_allclose(np.asarray(out[1]), expect1, rtol=1e-5)


def _cluster_dataset(n=60, d=8, classes=3, seed=0):
  """Learnable task: label = cluster id; edges mostly intra-cluster;
  features = noisy one-hot of cluster."""
  rng = np.random.default_rng(seed)
  labels = np.arange(n) % classes
  rows, cols = [], []
  for v in range(n):
    same = np.nonzero(labels == labels[v])[0]
    rows += [v] * 4
    cols += list(rng.choice(same, 3)) + [rng.integers(0, n)]
  feats = np.eye(classes, dtype=np.float32)[labels]
  feats = np.concatenate(
      [feats, rng.normal(0, 0.1, (n, d - classes)).astype(np.float32)], 1)
  feats += rng.normal(0, 0.05, feats.shape).astype(np.float32)
  return (Dataset()
          .init_graph((np.array(rows), np.array(cols)), layout='COO',
                      num_nodes=n)
          .init_node_features(feats, split_ratio=1.0)
          .init_node_labels(labels.astype(np.int32)))


def test_graphsage_trains():
  ds = _cluster_dataset()
  bs = 16
  loader = NeighborLoader(ds, [4, 4], np.arange(60), batch_size=bs,
                          shuffle=True, seed=0)
  model = GraphSAGE(hidden_features=16, out_features=3, num_layers=2)
  tx = optax.adam(1e-2)
  batch0 = next(iter(loader))
  state, apply_fn = create_train_state(model, jax.random.key(0), batch0, tx)
  step = make_supervised_step(apply_fn, tx, bs)
  losses = []
  for epoch in range(10):
    for batch in loader:
      state, loss, _ = step(state, batch)
      losses.append(float(loss))
  assert np.mean(losses[-4:]) < 0.5 * np.mean(losses[:4]), losses[:8]

  ev = make_eval_step(apply_fn, bs)
  correct = total = 0
  for batch in loader:
    c, t = ev(state.params, batch)
    correct += int(c)
    total += int(t)
  assert correct / total > 0.8


def test_gcn_gat_forward_shapes():
  ds = _cluster_dataset()
  loader = NeighborLoader(ds, [3, 3], np.arange(30), batch_size=8)
  batch = next(iter(loader))
  for model in (GCN(hidden_features=8, out_features=3, num_layers=2),
                GAT(hidden_features=8, out_features=3, num_layers=2,
                    heads=2)):
    params = model.init(jax.random.key(0), batch.x, batch.edge_index,
                        batch.edge_mask)
    out = model.apply(params, batch.x, batch.edge_index, batch.edge_mask)
    assert out.shape == (batch.x.shape[0], 3)
    assert np.isfinite(np.asarray(out)).all()


def test_bf16_compute_dtype():
  """dtype=bfloat16 computes on half-width MXU lanes but keeps params
  and outputs f32, and still learns."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  import optax
  from graphlearn_tpu.models import GraphSAGE

  rng = np.random.default_rng(0)
  n, d, classes = 64, 16, 4
  x = rng.standard_normal((n, d)).astype(np.float32)
  y = (np.arange(n) % classes).astype(np.int32)
  ei = jnp.asarray(
      np.stack([rng.integers(0, n, 128), rng.integers(0, n, 128)]))
  em = ei[0] >= 0
  x, y = jnp.asarray(x), jnp.asarray(y)
  model = GraphSAGE(hidden_features=32, out_features=classes,
                    num_layers=2, dtype=jnp.bfloat16)
  params = model.init(jax.random.key(0), x, ei, em)
  out = model.apply(params, x, ei, em)
  assert out.dtype == jnp.float32
  assert all(p.dtype == jnp.float32
             for p in jax.tree_util.tree_leaves(params))
  tx = optax.adam(1e-2)
  opt = tx.init(params)

  @jax.jit
  def step(params, opt):
    def loss_fn(p):
      logits = model.apply(p, x, ei, em)
      return optax.softmax_cross_entropy_with_integer_labels(
          logits, y).mean()
    loss, g = jax.value_and_grad(loss_fn)(params)
    upd, opt = tx.update(g, opt, params)
    return optax.apply_updates(params, upd), opt, loss

  first = None
  for _ in range(30):
    params, opt, loss = step(params, opt)
    first = float(loss) if first is None else first
  assert float(loss) < first * 0.7


def test_bf16_hub_degree_counts_not_saturated():
  """Edge counts/degrees accumulate in f32 even under bf16 compute:
  a 400-degree hub's mean aggregation must match the f32 model
  closely (bf16 scatter-add of ones saturates near 256)."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  from graphlearn_tpu.models import GraphSAGE

  n, deg = 512, 400
  rng = np.random.default_rng(0)
  x = jnp.asarray(rng.standard_normal((n, 8)).astype(np.float32))
  # every edge points at node 0 (the hub)
  src = jnp.asarray(rng.integers(1, n, deg).astype(np.int32))
  ei = jnp.stack([src, jnp.zeros((deg,), jnp.int32)])
  em = jnp.ones((deg,), bool)
  kw = dict(hidden_features=16, out_features=4, num_layers=1)
  m32 = GraphSAGE(**kw)
  m16 = GraphSAGE(**kw, dtype=jnp.bfloat16)
  params = m32.init(jax.random.key(0), x, ei, em)
  o32 = m32.apply(params, x, ei, em)
  o16 = m16.apply(params, x, ei, em)
  # hub row would be off by ~deg/256 (≈1.6x) if counts saturated
  rel = float(jnp.abs(o16[0] - o32[0]).max()
              / jnp.maximum(jnp.abs(o32[0]).max(), 1e-6))
  assert rel < 0.05, rel


@pytest.mark.slow
def test_dgcnn_learns_graph_label():
  """DGCNN separates graphs by structure: dense cliques vs sparse
  rings (graph-level task, static sort-pool)."""
  from graphlearn_tpu.models import DGCNN

  rng = np.random.default_rng(0)
  n = 20

  def clique():
    src, dst = np.meshgrid(np.arange(n), np.arange(n))
    m = src != dst
    return np.stack([src[m], dst[m]])

  def ring():
    return np.stack([np.arange(n), (np.arange(n) + 1) % n])

  graphs = []
  for i in range(24):
    ei = clique() if i % 2 == 0 else ring()
    cap = n * n
    pad = np.full((2, cap), -1)
    pad[:, :ei.shape[1]] = ei
    x = rng.standard_normal((n, 4)).astype(np.float32)
    graphs.append((jnp.asarray(x), jnp.asarray(pad),
                   jnp.asarray(pad[0] >= 0),
                   jnp.ones((n,), bool), i % 2))

  model = DGCNN(hidden_features=16, out_features=2, num_layers=2, k=8)
  params = model.init(jax.random.key(0), *graphs[0][:4])
  tx = optax.adam(1e-2)
  opt = tx.init(params)

  @jax.jit
  def step(params, opt, x, ei, em, nm, y):
    def loss_fn(p):
      logit = model.apply(p, x, ei, em, nm)
      return optax.softmax_cross_entropy_with_integer_labels(logit, y)
    loss, g = jax.value_and_grad(loss_fn)(params)
    upd, opt = tx.update(g, opt, params)
    return optax.apply_updates(params, upd), opt, loss

  for _ in range(20):
    for x, ei, em, nm, y in graphs[:16]:
      params, opt, loss = step(params, opt, x, ei, em, nm,
                               jnp.asarray(y))

  @jax.jit
  def predict(params, x, ei, em, nm):
    return jnp.argmax(model.apply(params, x, ei, em, nm))

  correct = sum(int(predict(params, x, ei, em, nm)) == y
                for x, ei, em, nm, y in graphs[16:])
  assert correct >= 7, correct


@pytest.mark.slow
def test_gin_and_gatv2_convs_mask_and_learn():
  """New zoo members (r3): masked padded edges contribute nothing, and
  an L-layer stack learns the clustered-graph task."""
  import jax
  import jax.numpy as jnp
  import optax
  from graphlearn_tpu.models import (GATv2Conv, GIN, GINConv,
                                     create_train_state,
                                     make_eval_step,
                                     make_supervised_step)
  rng = np.random.default_rng(0)
  n, e = 12, 30
  x = jnp.asarray(rng.normal(size=(n, 6)).astype(np.float32))
  src = rng.integers(0, n, e).astype(np.int32)
  dst = rng.integers(0, n, e).astype(np.int32)
  for cls, kw in ((GINConv, dict(out_features=5)),
                  (GATv2Conv, dict(out_features=5, heads=2))):
    conv = cls(**kw)
    ei_full = jnp.asarray(np.stack([src, dst]))
    mask = jnp.asarray(np.ones(e, bool))
    params = conv.init(jax.random.key(0), x, ei_full, mask)
    out_full = conv.apply(params, x, ei_full, mask)
    # append PADDED edges: outputs must be identical
    pad_src = np.concatenate([src, rng.integers(0, n, 7)]).astype(np.int32)
    pad_dst = np.concatenate([dst, np.full(7, -1)]).astype(np.int32)
    pad_mask = jnp.asarray(np.concatenate([np.ones(e, bool),
                                           np.zeros(7, bool)]))
    out_pad = conv.apply(params, x, jnp.asarray(np.stack([pad_src,
                                                          pad_dst])),
                         pad_mask)
    np.testing.assert_allclose(np.asarray(out_full),
                               np.asarray(out_pad), atol=1e-5)

  # GIN stack learns the clustered graph end-to-end
  import sys
  from pathlib import Path
  sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
  from examples._synthetic import clustered_graph
  from graphlearn_tpu.data import Dataset
  from graphlearn_tpu.loader import NeighborLoader
  rows, cols, feats, labels = clustered_graph(n=400, deg=8, classes=4,
                                              d=12, seed=1)
  ds = (Dataset().init_graph((rows, cols), layout='COO', num_nodes=400)
        .init_node_features(feats).init_node_labels(labels))
  loader = NeighborLoader(ds, [5, 5], np.arange(300), batch_size=64,
                          shuffle=True, seed=0)
  test_loader = NeighborLoader(ds, [5, 5], np.arange(300, 400),
                               batch_size=64)
  model = GIN(hidden_features=32, out_features=4, num_layers=2)
  tx = optax.adam(5e-3)
  state, apply_fn = create_train_state(model, jax.random.key(0),
                                       next(iter(loader)), tx)
  step = make_supervised_step(apply_fn, tx, 64)
  eval_step = make_eval_step(apply_fn, 64)
  for _ in range(5):
    for batch in loader:
      state, _, _ = step(state, batch)
  correct = total = 0
  for batch in test_loader:
    c, t = eval_step(state.params, batch)
    correct += int(c)
    total += int(t)
  assert correct / total > 0.8, correct / total


# -- hop-prefix trimming (BasicGNN.hop_capacities) ---------------------------

def _skewed_dataset(n=400, d=12, classes=5, seed=0):
  """Half of all edges point at eight hubs: dedup packs most of a hop's
  draws into few slots, so nodes of later hops sit inside the earlier
  hops' static prefixes."""
  rng = np.random.default_rng(seed)
  e = n * 8
  rows = rng.integers(0, n, e)
  cols = rng.integers(0, n, e)
  cols[:e // 2] = rng.integers(0, 8, e // 2)
  return (Dataset()
          .init_graph((rows, cols), layout='COO', num_nodes=n)
          .init_node_features(
              rng.normal(size=(n, d)).astype(np.float32), split_ratio=1.0)
          .init_node_labels(rng.integers(0, classes, n).astype(np.int32)))


def _without_capacities(batch):
  """The same batch as a loader that states no layout would hand it."""
  from graphlearn_tpu.loader.transform import Batch
  md = {k: v for k, v in batch.metadata.items()
        if k not in ('hop_capacities', 'hop_windows')}
  return Batch(batch.x, batch.y, batch.edge_index, batch.edge_attr,
               batch.node, batch.node_mask, batch.edge_mask, batch.edge,
               batch.batch, batch.batch_size, batch.num_sampled_nodes,
               batch.num_sampled_edges, md)


_TRIM_CASES = {
    # name: (nodes, fanouts, layers, aggr, batch, which batch, weights)
    'f44-mean': (400, [4, 4], 2, 'mean', 16, 'first', False),
    'f44-sum': (400, [4, 4], 2, 'sum', 16, 'first', False),
    'f44-max': (400, [4, 4], 2, 'max', 16, 'first', False),
    'f543-mean': (400, [5, 4, 3], 3, 'mean', 32, 'first', False),
    'f543-sum': (400, [5, 4, 3], 3, 'sum', 32, 'first', False),
    'f543-max': (400, [5, 4, 3], 3, 'max', 32, 'first', False),
    # 16 + 24 < 16 + 80 + 320 + 960: node_capacity clamps every hop
    'clamped-table': (24, [5, 4, 3], 3, 'mean', 16, 'first', False),
    # 100 seeds in batches of 32: the last has 4 seeds and 28 pads
    'short-last-batch': (400, [5, 4, 3], 3, 'mean', 32, 'last', False),
    'gns-edge-weight-mean': (400, [5, 4, 3], 3, 'mean', 32, 'first', True),
    'gns-edge-weight-sum': (400, [4, 4], 2, 'sum', 16, 'first', True),
    # a stack shallower / deeper than the sampler
    'two-layers-three-hops': (400, [5, 4, 3], 2, 'mean', 32, 'first', False),
    'three-layers-two-hops': (400, [4, 4], 3, 'mean', 16, 'first', False),
}


@pytest.mark.parametrize('case', sorted(_TRIM_CASES))
def test_trimmed_sage_matches_untrimmed(case):
  """Each layer computed only over the hops it feeds gives the seeds
  the logits, the loss and the gradients of the whole-table stack."""
  from graphlearn_tpu.models.train import (apply_to_batch,
                                           supervised_loss)
  n, fanouts, layers, aggr, bs, which, weighted = _TRIM_CASES[case]
  loader = NeighborLoader(_skewed_dataset(n), fanouts, np.arange(min(n, 100)),
                          batch_size=bs, shuffle=True, seed=1)
  batches = list(loader)
  batch = batches[0] if which == 'first' else batches[-1]
  valid = np.asarray(batch.batch) >= 0
  assert valid.all() == (which == 'first')
  node_caps, edge_caps = batch.metadata['hop_capacities']
  counts = np.cumsum(np.asarray(batch.num_sampled_nodes))
  # later hops did land inside earlier prefixes (what trimming must
  # survive), and the small graph did clamp
  assert (counts[1:-1] < np.asarray(node_caps[1:-1])).any()
  if case == 'clamped-table':
    assert node_caps[1] == node_caps[-1] == batch.x.shape[0]
  if weighted:
    rng = np.random.default_rng(5)
    batch.metadata['edge_weight'] = jnp.asarray(
        rng.uniform(0.5, 2.0, batch.edge_index.shape[1]), jnp.float32)
  full = _without_capacities(batch)
  model = GraphSAGE(hidden_features=16, out_features=5, num_layers=layers,
                    aggr=aggr)
  params = model.init(jax.random.key(0), batch.x, batch.edge_index,
                      batch.edge_mask)

  def loss_fn(p, b):
    logits = apply_to_batch(model.apply, p, b)
    return supervised_loss(logits, b.y, b.batch, bs), logits

  (loss_t, logits_t), grads_t = jax.value_and_grad(
      loss_fn, has_aux=True)(params, batch)
  (loss_f, logits_f), grads_f = jax.value_and_grad(
      loss_fn, has_aux=True)(params, full)
  assert logits_f.shape == (batch.x.shape[0], 5)
  assert logits_t.shape == (node_caps[0], 5) == (bs, 5)
  # by window against the whole-table segment path: float32 round-off
  np.testing.assert_allclose(np.asarray(logits_t)[valid],
                             np.asarray(logits_f)[:bs][valid],
                             rtol=1e-5, atol=1e-6)
  np.testing.assert_allclose(float(loss_t), float(loss_f), rtol=1e-5)
  flat_t = jax.tree_util.tree_leaves_with_path(grads_t)
  flat_f = jax.tree_util.tree_leaves_with_path(grads_f)
  assert [p for p, _ in flat_t] == [p for p, _ in flat_f]
  for (_, gt), (_, gf) in zip(flat_t, flat_f):
    scale = max(float(jnp.abs(gf).max()), 1e-6)
    np.testing.assert_allclose(np.asarray(gt), np.asarray(gf),
                               atol=1e-5 * scale)


def test_trimmed_step_one_compile_same_losses():
  """An epoch through `make_supervised_step`: the static capacities are
  the same on every batch (the short last one too), so one program;
  and it trains as the whole-table step does."""
  bs = 32
  loader = NeighborLoader(_skewed_dataset(), [5, 4, 3], np.arange(100),
                          batch_size=bs, shuffle=True, seed=2)
  model = GraphSAGE(hidden_features=16, out_features=5, num_layers=3)
  tx = optax.adam(1e-2)
  batches = list(loader)
  assert len(batches) == 4
  state_t, apply_fn = create_train_state(model, jax.random.key(0),
                                         batches[0], tx)
  # committed like the loader's batches, so the state a step returns
  # has the avals of the state it was given
  state_t = state_f = jax.device_put(state_t, jax.devices()[0])
  step_t = make_supervised_step(apply_fn, tx, bs)
  step_f = make_supervised_step(apply_fn, tx, bs)
  for batch in batches:
    state_t, loss_t, correct_t = step_t(state_t, batch)
    state_f, loss_f, correct_f = step_f(state_f, _without_capacities(batch))
    np.testing.assert_allclose(float(loss_t), float(loss_f), rtol=1e-5)
    assert int(correct_t) == int(correct_f)
  assert step_t._cache_size() == 1 and step_f._cache_size() == 1
  jax.tree_util.tree_map(
      lambda a, b: np.testing.assert_allclose(
          np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
      state_t.params, state_f.params)
  eval_step = make_eval_step(apply_fn, bs)
  assert ([int(v) for v in eval_step(state_t.params, batches[-1])]
          == [int(v) for v in eval_step(
              state_t.params, _without_capacities(batches[-1]))])


def _trim_events(fn, *args, **kwargs):
  """`model.trim` events of tracing ``fn`` (nothing is computed)."""
  from graphlearn_tpu.telemetry.recorder import recorder
  recorder.enable()
  recorder.clear()
  try:
    jax.eval_shape(fn, *args, **kwargs)
    return recorder.events('model.trim')
  finally:
    recorder.disable()
    recorder.clear()


def _flagship_shapes():
  from graphlearn_tpu.sampler.neighbor_sampler import hop_capacities
  caps = hop_capacities(1024, (15, 10, 5), 937984)
  f32, i32 = jnp.float32, jnp.int32
  return caps, (jax.ShapeDtypeStruct((caps[0][-1], 100), f32),
                jax.ShapeDtypeStruct((2, caps[1][-1]), i32),
                jax.ShapeDtypeStruct((caps[1][-1],), jnp.bool_))


def test_trim_record_names_rows_and_slots_per_layer():
  """The trace-time record of the flagship shape (batch 1024, fanout
  [15, 10, 5]): what each layer computes over, beside the table."""
  caps, (x, ei, em) = _flagship_shapes()
  assert caps == ((1024, 16384, 169984, 937984), (15360, 168960, 936960))
  model = GraphSAGE(hidden_features=256, out_features=47, num_layers=3)
  params = jax.eval_shape(model.init, jax.random.key(0), x, ei, em)
  from graphlearn_tpu.sampler.neighbor_sampler import hop_windows
  windows = hop_windows(1024, (15, 10, 5))
  assert windows == ((1024, 15), (15360, 10), (153600, 5))
  events = _trim_events(
      lambda p, *a: model.apply(p, *a, hop_capacities=caps,
                                hop_windows=windows), params, x, ei, em)
  assert len(events) == 1
  ev = events[0]
  assert ev['layers'] == 3
  assert ev['rows_in'] == [937984, 169984, 16384]
  assert ev['rows_out'] == [169984, 16384, 1024]
  assert ev['edge_slots'] == [936960, 168960, 15360]
  assert (ev['table_rows'], ev['table_slots']) == (937984, 936960)
  # every layer's slots aggregated by fanout window, none scattered
  assert ev['windowed_slots'] == [936960, 168960, 15360]
  assert ev['scattered_slots'] == [0, 0, 0]
  # capacities alone: the trimmed stack on the segment path
  (ev,) = _trim_events(
      lambda p, *a: model.apply(p, *a, hop_capacities=caps), params,
      x, ei, em)
  assert ev['windowed_slots'] == [0, 0, 0]
  assert ev['scattered_slots'] == ev['edge_slots'] == [936960, 168960,
                                                       15360]


@pytest.mark.parametrize('who', ['sage-no-capacities', 'gcn-declines',
                                 'init-is-not-a-step'])
def test_trim_record_absent_on_an_untrimmed_trace(who):
  caps, (x, ei, em) = _flagship_shapes()
  cls = GCN if who == 'gcn-declines' else GraphSAGE
  model = cls(hidden_features=256, out_features=47, num_layers=3)
  kwargs = {} if who == 'sage-no-capacities' else {'hop_capacities': caps}
  if who == 'init-is-not-a-step':
    assert not _trim_events(
        lambda *a: model.init(jax.random.key(0), *a, **kwargs), x, ei, em)
    return
  params = jax.eval_shape(model.init, jax.random.key(0), x, ei, em)
  out = jax.eval_shape(lambda p, *a: model.apply(p, *a, **kwargs),
                       params, x, ei, em)
  assert out.shape == (937984, 47)
  assert not _trim_events(lambda p, *a: model.apply(p, *a, **kwargs),
                          params, x, ei, em)


def test_gcn_declines_the_capacities():
  """GCN's normalisation counts a source's out-edges over the whole
  subgraph: it is handed the capacities by the seam and computes what
  it computes without them, over the whole table."""
  from graphlearn_tpu.models.train import apply_to_batch
  loader = NeighborLoader(_skewed_dataset(), [5, 4, 3], np.arange(100),
                          batch_size=32, shuffle=True, seed=1)
  batch = next(iter(loader))
  assert 'hop_capacities' in batch.metadata
  model = GCN(hidden_features=16, out_features=5, num_layers=3)
  params = model.init(jax.random.key(0), batch.x, batch.edge_index,
                      batch.edge_mask)
  stated = apply_to_batch(model.apply, params, batch)
  plain = model.apply(params, batch.x, batch.edge_index, batch.edge_mask)
  assert stated.shape == (batch.x.shape[0], 5)
  np.testing.assert_array_equal(np.asarray(stated), np.asarray(plain))


@pytest.mark.parametrize('source', ['hand-built', 'dist-loader',
                                    'subgraph-loader', 'plain-apply-fn'])
def test_batches_without_capacities_run_the_whole_table(source):
  """Only `NeighborSampler.sample_from_nodes` (and the link batches
  built on it, `tests/test_link_layout.py`) states the layout; every
  other batch — hand-built, the mesh loader's, an induced subgraph's —,
  and every ``apply_fn`` that is not a model's own ``apply``, gets the
  whole-table stack."""
  from graphlearn_tpu.loader.transform import Batch
  from graphlearn_tpu.models.train import apply_to_batch
  model = GraphSAGE(hidden_features=8, out_features=5, num_layers=2)
  apply_fn = model.apply
  if source == 'hand-built':
    rng = np.random.default_rng(0)
    ei = jnp.asarray(rng.integers(0, 20, (2, 50)), jnp.int32)
    batch = Batch(x=jnp.asarray(rng.normal(size=(20, 4)), jnp.float32),
                  edge_index=ei, edge_mask=jnp.ones((50,), bool),
                  batch=jnp.arange(4), batch_size=4)
  elif source == 'dist-loader':
    from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                         make_mesh)
    n = 64
    rows = np.concatenate([np.arange(n), np.arange(n)])
    cols = np.concatenate([(np.arange(n) + 1) % n, (np.arange(n) + 2) % n])
    ds = DistDataset.from_full_graph(
        4, rows, cols, node_feat=np.ones((n, 4), np.float32),
        node_label=(np.arange(n) % 5).astype(np.int32), num_nodes=n)
    stacked = next(iter(DistNeighborLoader(
        ds, [2, 2], np.arange(n), batch_size=4, mesh=make_mesh(4), seed=0)))
    batch = jax.tree_util.tree_map(lambda v: v[0], stacked)
  elif source == 'subgraph-loader':
    from graphlearn_tpu.loader import SubGraphLoader
    batch = next(iter(SubGraphLoader(_skewed_dataset(), [4, 4],
                                     np.arange(64), batch_size=16)))
  else:
    batch = next(iter(NeighborLoader(_skewed_dataset(), [4, 4],
                                     np.arange(64), batch_size=16)))
    assert 'hop_capacities' in batch.metadata
    apply_fn = lambda p, *a, **kw: model.apply(p, *a, **kw)  # noqa: E731
  if source != 'plain-apply-fn':
    assert 'hop_capacities' not in batch.metadata
  params = model.init(jax.random.key(0), batch.x, batch.edge_index,
                      batch.edge_mask)
  out = apply_to_batch(apply_fn, params, batch)
  assert out.shape == (batch.x.shape[0], 5)
  np.testing.assert_array_equal(
      np.asarray(out),
      np.asarray(model.apply(params, batch.x, batch.edge_index,
                             batch.edge_mask)))


def test_init_with_capacities_yields_the_same_parameter_tree():
  batch = next(iter(NeighborLoader(_skewed_dataset(), [5, 4, 3],
                                   np.arange(64), batch_size=16)))
  model = GraphSAGE(hidden_features=16, out_features=5, num_layers=3)
  args = (jax.random.key(0), batch.x, batch.edge_index, batch.edge_mask)
  plain = model.init(*args)
  stated = model.init(*args,
                      hop_capacities=batch.metadata['hop_capacities'])
  assert (jax.tree_util.tree_structure(plain)
          == jax.tree_util.tree_structure(stated))
  assert sorted(plain['params']) == ['conv0', 'conv1', 'conv2']
  assert sorted(plain['params']['conv0']) == ['lin_neigh', 'lin_self']
  jax.tree_util.tree_map(
      lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                 np.asarray(b)),
      plain, stated)
