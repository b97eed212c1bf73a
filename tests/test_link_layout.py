"""Link batches state their hop layout (ISSUE 38): `FusedLinkEpoch` and
`NeighborSampler.sample_from_edges` put ``hop_capacities`` /
``hop_windows`` for the seed width beside the link keys, the link step
applies the model through `apply_to_batch` (each layer over the hops
it feeds, aggregated by fanout window), and the link loss then equals
the whole-table segment path's to float32 round-off."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from graphlearn_tpu.data import Dataset
from graphlearn_tpu.loader import FusedLinkEpoch, LinkNeighborLoader
from graphlearn_tpu.models import GraphSAGE
from graphlearn_tpu.models.train import (TrainState, apply_to_batch,
                                         link_loss_from_metadata,
                                         make_unsupervised_step)
from graphlearn_tpu.sampler import NegativeSampling
from graphlearn_tpu.sampler.neighbor_sampler import (hop_capacities,
                                                     hop_windows, link_plan)
from graphlearn_tpu.telemetry.recorder import recorder

FANOUT = (4, 3, 2)
MODES = {'binary': NegativeSampling('binary', 1.0),
         'triplet': NegativeSampling('triplet', 2)}


def _graph(n=120, deg=4, d=6, seed=0):
  rng = np.random.default_rng(seed)
  rows = np.repeat(np.arange(n), deg)
  cols = rng.integers(0, n, n * deg)
  feats = rng.normal(size=(n, d)).astype(np.float32)
  ds = (Dataset()
        .init_graph((rows, cols), layout='COO', num_nodes=n)
        .init_node_features(feats, split_ratio=1.0))
  return ds, rows, cols


def _model():
  return GraphSAGE(hidden_features=8, out_features=8, num_layers=3)


def _params(model, ds):
  x = jnp.zeros((4, ds.node_features.feature_dim), jnp.float32)
  ei = jnp.zeros((2, 2), jnp.int32)
  return model.init(jax.random.key(1), x, ei, jnp.ones((2,), bool))


def _fused(ds, rows, cols, mode, b=16, **kw):
  model = _model()
  return model, FusedLinkEpoch(
      ds, list(FANOUT), (rows[:3 * b], cols[:3 * b]), model.apply,
      optax.adam(1e-2), batch_size=b, neg_sampling=MODES[mode],
      shuffle=False, seed=7, **kw)


def _fused_batch(epoch, rows, cols, b=16):
  src = jnp.asarray(rows[:b].astype(np.int32))
  dst = jnp.asarray(cols[:b].astype(np.int32))
  return epoch._link_batch(src, dst, None, jax.random.key(3), epoch._dev,
                           False)


def _loader_batch(ds, rows, cols, mode, b=16):
  loader = LinkNeighborLoader(ds, list(FANOUT), (rows[:b], cols[:b]),
                              neg_sampling=MODES[mode], batch_size=b,
                              seed=2)
  return next(iter(loader))


def _batch(path, mode):
  ds, rows, cols = _graph()
  if path == 'fused':
    _, epoch = _fused(ds, rows, cols, mode)
    return ds, rows, cols, _fused_batch(epoch, rows, cols)
  return ds, rows, cols, _loader_batch(ds, rows, cols, mode)


@pytest.mark.parametrize('path', ['fused', 'per_batch'])
@pytest.mark.parametrize('mode', ['binary', 'triplet'])
def test_link_batch_states_its_hop_layout(path, mode):
  ds, _, _, batch = _batch(path, mode)
  _, _, _, width = link_plan(MODES[mode], 16)
  assert batch.batch.shape == (width,)
  cap = batch.node.shape[0]
  assert batch.metadata['hop_capacities'] == hop_capacities(width, FANOUT,
                                                            cap)
  assert batch.metadata['hop_windows'] == hop_windows(width, FANOUT)
  # the seed-local rows of every endpoint lie below C_0, the rows a
  # trimmed model returns
  sl = np.asarray(batch.metadata['seed_local'])
  assert sl.max() < batch.metadata['hop_capacities'][0][0]
  if path == 'fused':
    assert batch.y is None        # the link loss reads no label


@pytest.mark.parametrize('path', ['fused', 'per_batch'])
@pytest.mark.parametrize('mode', ['binary', 'triplet'])
def test_trimmed_link_loss_equals_the_whole_table_path(path, mode):
  ds, _, _, batch = _batch(path, mode)
  model = _model()
  params = _params(model, ds)

  def trimmed(p):
    return link_loss_from_metadata(
        apply_to_batch(model.apply, p, batch), batch.metadata)

  def whole(p):
    return link_loss_from_metadata(
        model.apply(p, batch.x, batch.edge_index, batch.edge_mask),
        batch.metadata)

  assert apply_to_batch(model.apply, params, batch).shape[0] == (
      batch.metadata['hop_capacities'][0][0])
  lt, gt = jax.value_and_grad(trimmed)(params)
  lw, gw = jax.value_and_grad(whole)(params)
  np.testing.assert_allclose(float(lt), float(lw), rtol=2e-6)
  for a, b in zip(jax.tree_util.tree_leaves(gt),
                  jax.tree_util.tree_leaves(gw)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-6)


@pytest.mark.parametrize('path', ['fused', 'per_batch'])
@pytest.mark.parametrize('mode', ['binary', 'triplet'])
def test_strict_negatives_are_non_edges(path, mode):
  _, rows, cols, batch = _batch(path, mode)
  edges = set(zip(rows.tolist(), cols.tolist()))
  node = np.asarray(batch.node)
  md = batch.metadata
  if mode == 'binary':
    eli = np.asarray(md['edge_label_index'])
    lab = np.asarray(md['edge_label'])
    ok = np.asarray(md['edge_label_mask']) & (eli >= 0).all(0)
    pairs = zip(node[eli[0]][ok], node[eli[1]][ok], lab[ok])
    got = [(int(u), int(v), l) for u, v, l in pairs]
    assert sum(1 for *_, l in got if l == 0) == 16
    for u, v, l in got:
      assert ((u, v) in edges) == (l > 0), (u, v, l)
  else:
    src = node[np.asarray(md['src_index'])]
    neg = node[np.asarray(md['dst_neg_index'])]
    for u, vs in zip(src, neg):
      for v in vs:
        assert (int(u), int(v)) not in edges


def test_the_link_events_carry_their_fields():
  ds, rows, cols = _graph()
  recorder.enable()
  recorder.clear()
  try:
    # a batch size no other test asks for: a compiled program records
    # its events when it is traced, once
    _loader_batch(ds, rows, cols, 'binary', b=11)
    model, epoch = _fused(ds, rows, cols, 'triplet')
    batch = jax.eval_shape(lambda: _fused_batch(epoch, rows, cols))
    neg = recorder.events('sample.negative')
    link = recorder.events('link.batch')
  finally:
    recorder.disable()
    recorder.clear()
  assert [(e['mode'], e['req_num'], e['seed_width']) for e in neg] == [
      ('binary', 11, 44), ('triplet', 32, 64)]
  for e in neg:
    assert (e['trials'], e['strict'], e['padding']) == (5, True, True)
  assert [(e['mode'], e['batch'], e['negative_endpoints']) for e in link
          ] == [('binary', 11, 22), ('triplet', 16, 32)]
  caps = batch.metadata['hop_capacities']
  as_lists = lambda t: [list(c) for c in t]
  assert as_lists(link[1]['hop_capacities']) == as_lists(caps)
  assert as_lists(link[1]['hop_windows']) == as_lists(hop_windows(64,
                                                                  FANOUT))


@pytest.mark.parametrize('mode', ['binary', 'triplet'])
def test_the_link_step_trims_and_scatters_nothing(mode):
  """`make_unsupervised_step` hands the batch's layout to the model:
  one `model.trim` event, every layer trimmed and windowed."""
  ds, rows, cols, batch = _batch('fused', mode)
  model = _model()
  params = _params(model, ds)
  tx = optax.adam(1e-2)
  state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
  recorder.enable()
  recorder.clear()
  try:
    jax.eval_shape(make_unsupervised_step(model.apply, tx), state, batch)
    trim = recorder.events('model.trim')
  finally:
    recorder.disable()
    recorder.clear()
  assert len(trim) == 1
  (nodes, slots), t = batch.metadata['hop_capacities'], trim[0]
  assert t['rows_in'] == [nodes[3], nodes[2], nodes[1]]
  assert t['rows_out'] == [nodes[2], nodes[1], nodes[0]]
  assert t['scattered_slots'] == [0, 0, 0]
  assert t['windowed_slots'] == [slots[2], slots[1], slots[0]]


def test_fused_link_epoch_counts_its_batches_fill():
  ds, rows, cols = _graph()
  model, epoch = _fused(ds, rows, cols, 'binary',
                        max_steps_per_program=4)
  params = _params(model, ds)
  tx = optax.adam(1e-2)
  state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
  assert epoch.batch_fill() == dict(rows_valid=0, rows=0, edges_valid=0,
                                    edge_slots=0)
  state, stats = epoch.run(state)
  assert np.isfinite(np.asarray(stats.losses)).all()
  fill = epoch.batch_fill()
  cap = epoch._node_cap
  slots = epoch._layout[0][1][-1]
  # three batches in a four-step program: the padded step runs too
  assert fill['rows'] == 4 * cap and fill['edge_slots'] == 4 * slots
  assert 0 < fill['rows_valid'] < fill['rows']
  assert 0 < fill['edges_valid'] < fill['edge_slots']
  # the epoch's own sample-only scan draws what the dispatch trained on
  key = epoch.epoch_key(1)
  sp = jnp.asarray(rows[:16].astype(np.int32))[None]
  dp = jnp.asarray(cols[:16].astype(np.int32))[None]
  drawn = epoch._compiled_collect(sp, dp, jnp.ones_like(sp), key,
                                  epoch._dev, collect_x=True)
  again = _fused_batch(epoch, rows, cols)
  assert drawn.x.shape == (1,) + again.x.shape
  assert epoch.batch_fill()['rows'] == 4 * cap   # collect adds nothing


def test_remat_link_step_keeps_the_loss():
  ds, rows, cols, batch = _batch('fused', 'binary')
  model = _model()
  params = _params(model, ds)
  tx = optax.adam(1e-2)
  state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
  _, plain = make_unsupervised_step(model.apply, tx)(state, batch)
  _, remat = make_unsupervised_step(model.apply, tx, remat=True)(state,
                                                                 batch)
  np.testing.assert_allclose(float(plain), float(remat), rtol=1e-6)
