"""Target-side aggregation by fanout window (ISSUE 33): a batch that
states its sampler's fanout windows has `SAGEConv` / `GATConv` reduce
over each window of ``k`` consecutive edge slots and place the rows,
where the `segment_*` path scatters every edge slot into the target
rows.

  * `test_*_windows_hold_of_the_batch`: the samplers' contract — slot
    ``(i, j)`` of block ``h`` targets ``start_h + i`` or is masked;
  * `test_windowed_*`: the windowed convs and stacks against the
    segment path (the oracle) on real sampler batches — duplicates,
    short neighbourhoods, a hop that finds nothing, clamped tables, an
    empty block — in values, parameter gradients and input gradients,
    trimmed and untrimmed;
  * `test_flagship_step_*`, `test_batch_without_windows_*`: what the
    lowered programs hold — no scatter with one update per edge slot on
    the target side where the windows are stated, the segment path's
    scatters where they are not.
"""
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from graphlearn_tpu.data import CSRTopo, Dataset, Graph
from graphlearn_tpu.loader import NeighborLoader
from graphlearn_tpu.loader.transform import Batch
from graphlearn_tpu.models import (GAT, GATConv, GraphSAGE, HeteroConv,
                                   RGAT, SAGEConv, TrainState,
                                   apply_to_batch, make_supervised_step,
                                   supervised_loss)
from graphlearn_tpu.models.conv import _Windows, window_aggregate
from graphlearn_tpu.sampler import (HeteroNeighborSampler, NeighborSampler,
                                    NodeSamplerInput)
from graphlearn_tpu.sampler.hetero_neighbor_sampler import (
    _plan, typed_hop_windows)
from graphlearn_tpu.sampler.neighbor_sampler import (hop_capacities,
                                                     hop_windows)

P, A, I, F = 'paper', 'author', 'institute', 'fos'


# -- homogeneous batches ------------------------------------------------------

def _coo(kind, n, seed=0):
  rng = np.random.default_rng(seed)
  if kind == 'skewed':
    # half of all edges point at eight hubs: a hop's draws hold many
    # duplicates, so later hops' nodes sit inside earlier prefixes
    e = n * 8
    rows, cols = rng.integers(0, n, e), rng.integers(0, n, e)
    cols[:e // 2] = rng.integers(0, 8, e // 2)
    return rows, cols
  if kind == 'short':
    # out-degree 0..2 under fanouts of 3..5: short neighbourhoods and
    # nodes without any
    deg = rng.integers(0, 3, n)
    rows = np.repeat(np.arange(n), deg)
    return rows, rng.integers(0, n, rows.shape[0])
  # 'dead-end': seeds 0..15 reach nodes 16..63, which reach nothing —
  # the last hop's block is wholly masked
  assert kind == 'dead-end'
  rows = np.repeat(np.arange(16), 4)
  return rows, rng.integers(16, n, rows.shape[0])


_HOMO = {
    # name: (graph, nodes, fanouts, batch size, which batch)
    'skewed': ('skewed', 400, [5, 4, 3], 32, 0),
    'clamped-table': ('skewed', 24, [5, 4, 3], 16, 0),
    'short-neighbourhoods': ('short', 300, [5, 4, 3], 32, 0),
    'dead-end-hop': ('dead-end', 64, [3, 3, 2], 16, 0),
    'short-last-batch': ('skewed', 400, [4, 4], 32, -1),
}
_BATCHES = {}


def _homo_batch(name):
  if name not in _BATCHES:
    kind, n, fanouts, bs, which = _HOMO[name]
    rng = np.random.default_rng(1)
    ds = (Dataset()
          .init_graph(_coo(kind, n), layout='COO', num_nodes=n)
          .init_node_features(rng.normal(size=(n, 12)).astype(np.float32),
                              split_ratio=1.0)
          .init_node_labels(rng.integers(0, 5, n).astype(np.int32)))
    seeds = np.arange(16) if kind == 'dead-end' else np.arange(min(n, 100))
    batches = list(NeighborLoader(ds, fanouts, seeds, batch_size=bs,
                                  shuffle=kind != 'dead-end', seed=1))
    _BATCHES[name] = batches[which]
  return _BATCHES[name]


def _stating(batch, *keep):
  """The same batch stating only ``keep`` of its sampler's layout."""
  md = {k: v for k, v in batch.metadata.items()
        if k not in ('hop_capacities', 'hop_windows') or k in keep}
  leaves, tree = jax.tree_util.tree_flatten(batch)
  out = jax.tree_util.tree_unflatten(tree, leaves)
  out.metadata = md
  return out


def _close(got, want, what=''):
  """Float32 round-off: a sum of at most ``k`` terms in another order."""
  got, want = np.asarray(got), np.asarray(want)
  assert got.shape == want.shape, what
  scale = max(float(np.abs(want).max(initial=0.0)), 1e-6)
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6 * scale,
                             err_msg=what)


def _close_trees(got, want, what=''):
  got = jax.tree_util.tree_leaves_with_path(got)
  want = jax.tree_util.tree_leaves_with_path(want)
  assert [p for p, _ in got] == [p for p, _ in want]
  for (path, g), (_, w) in zip(got, want):
    _close(g, w, f'{what} {jax.tree_util.keystr(path)}')


# -- the contract -------------------------------------------------------------

def _holds(col, mask, windows, starts, counts):
  """Slot ``(i, j)`` of block ``h`` holds ``starts[h] + i`` or -1, is
  masked exactly where it holds -1, and the windows past the frontier's
  ``counts[h]`` valid nodes are wholly masked."""
  col, mask = np.asarray(col), np.asarray(mask)
  at = 0
  for (f, k), start, count in zip(windows, starts, counts):
    blk = col[at:at + f * k].reshape(f, k)
    want = start + np.arange(f)[:, None]
    assert ((blk == want) | (blk == -1)).all()
    np.testing.assert_array_equal(mask[at:at + f * k].reshape(f, k),
                                  blk >= 0)
    assert (blk[count:] == -1).all()
    at += f * k
  assert at == col.shape[0]


@pytest.mark.parametrize('name', sorted(_HOMO))
def test_homogeneous_windows_hold_of_the_batch(name):
  batch = _homo_batch(name)
  _, _, fanouts, bs, _ = _HOMO[name]
  windows = batch.metadata['hop_windows']
  assert windows == hop_windows(bs, fanouts)
  hash(windows)      # static pytree aux data
  node_caps, edge_caps = batch.metadata['hop_capacities']
  assert list(np.cumsum([f * k for f, k in windows])) == list(edge_caps)
  assert windows[0][0] == bs
  assert all(windows[h + 1][0] == f * k
             for h, (f, k) in enumerate(windows[:-1]))
  new = np.asarray(batch.num_sampled_nodes)
  cum = np.concatenate([[0], np.cumsum(new)])
  # hop h samples the nodes hop h - 1 appended: [cum[h], cum[h + 1])
  _holds(batch.edge_index[1], batch.edge_mask, windows, cum[:-2],
         new[:-1])
  assert all(cum[h] <= sum(f for f, _ in windows[:h])
             for h in range(len(windows)))
  # and the convs read the same offsets off the edge list itself
  win = _Windows(batch.edge_index[1], windows, batch.x.shape[0])
  assert [(f, k) for _, f, k in win.blocks] == list(windows)
  for h, ((first, f, k), start) in enumerate(zip(win.blocks, win.starts)):
    assert first == (edge_caps[h - 1] if h else 0)
    if np.asarray(batch.edge_mask[first:first + f * k]).any():
      assert int(start) == cum[h]
  if name == 'dead-end-hop':
    assert not np.asarray(batch.edge_mask)[edge_caps[-2]:].any()
    assert np.asarray(batch.edge_mask)[:edge_caps[-2]].any()
  if name == 'clamped-table':
    assert node_caps[1] == node_caps[-1] == batch.x.shape[0]
    assert win.rows > batch.x.shape[0]


_TYPED_DEGREES = {(P, 'cites', P): 3, (P, 'written_by', A): 2,
                  (A, 'affiliated_to', I): 1, (P, 'topic', F): 2,
                  (A, 'rev_written_by', P): 2,
                  (I, 'rev_affiliated_to', A): 3, (F, 'rev_topic', P): 3}
_TYPED_SIZES = {P: 300, A: 200, I: 9, F: 30}
_TYPED_FANOUT = [3, 2, 2]
_TB, _TD = 8, 12
_TYPED = {}


def _typed_world():
  """A typed toy graph of IGBH's shape — four node types, seven
  relations, `institute` so small that its table clamps — its sampler,
  and one batch of its loader."""
  if not _TYPED:
    rng = np.random.default_rng(4)
    coo = {}
    for i, ((s, rel, d), deg) in enumerate(sorted(_TYPED_DEGREES.items())):
      rows = np.repeat(np.arange(_TYPED_SIZES[s]), deg)
      coo[(s, rel, d)] = (rows, rng.integers(0, _TYPED_SIZES[d],
                                             rows.shape[0]))
    ds = (Dataset()
          .init_graph(coo, layout='COO',
                      num_nodes={et: _TYPED_SIZES[et[0]] for et in coo})
          .init_node_features(
              {t: rng.normal(size=(n, _TD)).astype(np.float32)
               for t, n in _TYPED_SIZES.items()}, split_ratio=1.0)
          .init_node_labels(
              {P: rng.integers(0, 5, _TYPED_SIZES[P]).astype(np.int32)}))
    loader = NeighborLoader(ds, _TYPED_FANOUT, (P, np.arange(64)),
                            batch_size=_TB, shuffle=True, seed=3)
    _TYPED.update(loader=loader, batch=next(iter(loader)))
  return _TYPED


def test_typed_windows_hold_of_the_batch():
  world = _typed_world()
  batch, s = world['batch'], world['loader'].sampler
  plan = _plan(s.etypes, s.fanouts, {P: _TB}, s.num_hops, s._num_nodes)
  stated = batch.metadata['hop_windows']
  assert stated == typed_hop_windows(s.etypes, s.fanouts, plan)
  hash(stated)
  windows, edge = dict(stated), dict(batch.metadata['hop_capacities'][1])
  node = dict(batch.metadata['hop_capacities'][0])
  assert set(windows) == set(edge) == set(batch.edge_index_dict)
  frontier_caps = plan[2]
  for rel, w in windows.items():
    a, _, b = rel
    assert list(np.cumsum([f * k for f, k in w])) == list(edge[rel])
    # block h is the frontier of the TARGET type at hop h by the fanout
    assert [f for f, _ in w] == [frontier_caps[h].get(b, 0)
                                 for h in range(s.num_hops)]
    new = _typed_new(batch, b)
    cum = np.concatenate([[0], np.cumsum(new)])
    _holds(batch.edge_index_dict[rel][1], batch.edge_mask_dict[rel], w,
           cum[:-2], new[:-1])
    assert all(cum[h] <= sum(f for f, _ in w[:h]) for h in range(len(w)))
  # papers are the only seeds: no relation into another type is sampled
  # at the first hop (an empty block); the frontiers of `institute` and
  # `fos` clamp at the types' node counts, under what the hop can find
  assert windows[(A, 'affiliated_to', I)][:2] == ((0, 0), (0, 0))
  assert windows[(P, 'cites', P)][0] == (_TB, _TYPED_FANOUT[0])
  assert windows[(A, 'affiliated_to', I)][2] == (_TYPED_SIZES[I], 2)
  assert windows[(P, 'topic', F)][2] == (_TYPED_SIZES[F], 2)
  assert node[F][-1] < node[F][1] + 2 * windows[(P, 'written_by', A)][1][0]


def _typed_new(batch, ntype):
  """Nodes of ``ntype`` found per hop (entry 0: the seeds), read off
  the batch: a table is in first-occurrence order and every node that is
  no seed is the source of an edge of the hop that found it."""
  edge = dict(batch.metadata['hop_capacities'][1])
  hops = len(next(iter(edge.values())))
  count = [int((np.asarray(batch.batch_dict[P]) >= 0).sum())
           if ntype == P else 0]
  for h in range(hops):
    top = count[-1]
    for rel, ends in edge.items():
      if rel[0] != ntype:
        continue
      blk = slice(ends[h - 1] if h else 0, ends[h])
      src = np.asarray(batch.edge_index_dict[rel][0])[blk]
      ok = np.asarray(batch.edge_mask_dict[rel])[blk]
      top = max(top, int(src[ok].max(initial=-1)) + 1)
    count.append(top)
  assert count[-1] == int((np.asarray(batch.node_dict[ntype]) >= 0).sum())
  return np.diff(np.concatenate([[0], count]))


# -- the convs against the segment path ---------------------------------------

def _conv_case(batch, hop):
  """``(x, edge_index, mask, windows, num_dst)`` of the layer that keeps
  edge blocks ``0..hop``; ``hop=None``: the whole table."""
  windows = batch.metadata['hop_windows']
  if hop is None:
    return batch.x, batch.edge_index, batch.edge_mask, windows, None
  node_caps, edge_caps = batch.metadata['hop_capacities']
  return (batch.x[:node_caps[hop + 1]], batch.edge_index[:, :edge_caps[hop]],
          batch.edge_mask[:edge_caps[hop]], windows[:hop + 1],
          node_caps[hop])


def _against_segment(conv, x, ei, mask, windows, num_dst, **kwargs):
  """Values, parameter gradients and input gradients of ``conv`` with
  ``windows`` against the segment path."""
  params = conv.init(jax.random.key(0), x, ei, mask)
  rows = x.shape[0] if num_dst is None else num_dst

  def run(p, x, w):
    return conv.apply(p, x, ei, mask, num_dst=num_dst, windows=w,
                      **kwargs)

  got, want = run(params, x, windows), run(params, x, None)
  assert got.shape == want.shape and got.shape[0] == rows
  _close(got, want, 'values')
  ct = jnp.asarray(np.random.default_rng(2).normal(size=want.shape),
                   jnp.float32)
  grad = lambda w: jax.grad(lambda p, x: (run(p, x, w) * ct).sum(),
                            argnums=(0, 1))(params, x)
  _close_trees(grad(windows), grad(None), 'gradient')
  # and the windowed form holds no scatter of its own: what is left in
  # the gradient's program is the source side's (`x[src]` transposed)
  fwd = jax.jit(lambda p, x: run(p, x, windows)).lower(params, x).as_text()
  assert 'stablehlo.scatter' not in fwd
  assert 'stablehlo.scatter' in jax.jit(
      lambda p, x: run(p, x, None)).lower(params, x).as_text()


@pytest.mark.parametrize('aggr,weighted', [
    ('mean', False), ('mean', True), ('sum', False), ('sum', True),
    ('max', False)], ids=['mean', 'mean-edge-weight', 'sum',
                          'sum-edge-weight', 'max'])
@pytest.mark.parametrize('hop', [None, 0, 1, 2],
                         ids=['whole', 'hop0', 'hop1', 'hop2'])
@pytest.mark.parametrize('name', ['skewed', 'clamped-table',
                                  'short-neighbourhoods', 'dead-end-hop'])
def test_windowed_sage_conv_equals_segment_path(name, hop, aggr, weighted):
  batch = _homo_batch(name)
  x, ei, mask, windows, num_dst = _conv_case(batch, hop)
  kwargs = {}
  if weighted:
    kwargs['edge_weight'] = jnp.asarray(np.random.default_rng(5).uniform(
        0.5, 2.0, ei.shape[1]), jnp.float32)
  _against_segment(SAGEConv(7, aggr=aggr), x, ei, mask, windows, num_dst,
                   **kwargs)


@pytest.mark.parametrize('concat', [True, False], ids=['concat', 'mean-heads'])
@pytest.mark.parametrize('hop', [None, 0, 2], ids=['whole', 'hop0', 'hop2'])
@pytest.mark.parametrize('name', ['skewed', 'clamped-table',
                                  'short-neighbourhoods', 'dead-end-hop'])
def test_windowed_gat_conv_equals_segment_path(name, hop, concat):
  batch = _homo_batch(name)
  _against_segment(GATConv(4, heads=3, concat=concat),
                   *_conv_case(batch, hop))


def test_windowed_conv_without_a_mask_reads_the_targets():
  """``edge_mask=None``: a slot is valid where its target is."""
  batch = _homo_batch('short-neighbourhoods')
  x, ei, _, windows, _ = _conv_case(batch, None)
  for conv in (SAGEConv(7), GATConv(4, heads=2)):
    params = conv.init(jax.random.key(0), x, ei)
    _close(conv.apply(params, x, ei, windows=windows),
           conv.apply(params, x, ei), type(conv).__name__)


def test_window_aggregate_refuses_windows_of_another_edge_list():
  batch = _homo_batch('skewed')
  with pytest.raises(ValueError, match='cover'):
    window_aggregate(batch.x, batch.edge_index[0], batch.edge_index[1],
                     batch.x.shape[0], batch.metadata['hop_windows'][:-1])
  with pytest.raises(ValueError, match='edge_weight'):
    SAGEConv(7, aggr='max').init(
        jax.random.key(0), batch.x, batch.edge_index, batch.edge_mask,
        edge_weight=jnp.ones((batch.edge_index.shape[1],)),
        windows=batch.metadata['hop_windows'])


# -- the stacks through the seam ----------------------------------------------

_STACKS = {
    'sage-mean': lambda: GraphSAGE(hidden_features=16, out_features=5,
                                   num_layers=3),
    'sage-max': lambda: GraphSAGE(hidden_features=16, out_features=5,
                                  num_layers=3, aggr='max'),
    'sage-two-layers': lambda: GraphSAGE(hidden_features=16, out_features=5,
                                         num_layers=2),
    'gat': lambda: GAT(hidden_features=16, out_features=5, num_layers=3,
                       heads=2),
}


@pytest.mark.parametrize('oracle', ['trimmed-segment', 'whole-segment',
                                    'whole-windowed'])
@pytest.mark.parametrize('stack', sorted(_STACKS))
@pytest.mark.parametrize('name', sorted(_HOMO))
def test_windowed_stack_equals_segment_path(name, stack, oracle):
  """`apply_to_batch` on the batch as the loader made it (trimmed, by
  window) against the same batch stating less: the seeds' logits, the
  loss and every gradient leaf."""
  batch = _homo_batch(name)
  bs = batch.batch_size
  keep = {'trimmed-segment': ('hop_capacities',), 'whole-segment': (),
          'whole-windowed': ('hop_windows',)}[oracle]
  other = _stating(batch, *keep)
  model = _STACKS[stack]()
  params = model.init(jax.random.key(0), batch.x, batch.edge_index,
                      batch.edge_mask)

  def loss_fn(p, b):
    logits = apply_to_batch(model.apply, p, b)
    return supervised_loss(logits, b.y, b.batch, bs), logits

  (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(
      params, batch)
  (loss_o, logits_o), grads_o = jax.value_and_grad(loss_fn, has_aux=True)(
      params, other)
  valid = np.asarray(batch.batch) >= 0
  assert logits.shape == (bs, 5)
  assert logits_o.shape[0] == (bs if 'hop_capacities' in keep
                               else batch.x.shape[0])
  _close(np.asarray(logits)[valid], np.asarray(logits_o)[:bs][valid],
         'logits')
  assert float(loss) == pytest.approx(float(loss_o), rel=1e-5)
  _close_trees(grads, grads_o, 'gradient')


def _trim_events(fn, *args):
  from graphlearn_tpu.telemetry.recorder import recorder
  recorder.enable()
  recorder.clear()
  try:
    jax.eval_shape(fn, *args)
    return recorder.events('model.trim')
  finally:
    recorder.disable()
    recorder.clear()


@pytest.mark.parametrize('stack', ['sage-mean', 'gat'])
def test_trim_event_says_how_each_layer_aggregated(stack):
  batch = _homo_batch('skewed')
  model = _STACKS[stack]()
  params = model.init(jax.random.key(0), batch.x, batch.edge_index,
                      batch.edge_mask)
  slots = list(batch.metadata['hop_capacities'][1][::-1])
  for b, windowed in ((batch, True),
                      (_stating(batch, 'hop_capacities'), False)):
    (ev,) = _trim_events(lambda p, b: apply_to_batch(model.apply, p, b),
                         params, b)
    assert ev['edge_slots'] == slots
    assert ev['windowed_slots'] == (slots if windowed else [0, 0, 0])
    assert ev['scattered_slots'] == ([0, 0, 0] if windowed else slots)


# -- typed batches ------------------------------------------------------------

def _typed_loss(model, params, batch):
  logits = apply_to_batch(model.apply, params, batch)[:_TB]
  ce = optax.softmax_cross_entropy_with_integer_labels(
      logits, batch.y_dict[P][:_TB])
  return ce.mean(), logits


class _RSAGE(RGAT):
  """The sibling stack of `examples/igbh`: `SAGEConv` per relation."""

  @nn.nowrap
  def make_conv(self):
    return SAGEConv(self.hidden_features)


@pytest.mark.parametrize('oracle', ['trimmed-segment', 'whole-segment',
                                    'whole-windowed'])
@pytest.mark.parametrize('stack', ['rgat', 'rsage'])
def test_windowed_typed_stack_equals_segment_path(stack, oracle):
  batch = _typed_world()['batch']
  keep = {'trimmed-segment': ('hop_capacities',), 'whole-segment': (),
          'whole-windowed': ('hop_windows',)}[oracle]
  other = _stating(batch, *keep)
  cls = RGAT if stack == 'rgat' else _RSAGE
  model = cls(etypes=tuple(sorted(batch.edge_index_dict)),
              hidden_features=16, out_features=5, num_layers=3, heads=4,
              target_ntype=P)
  params = model.init(jax.random.key(0), batch.x_dict,
                      batch.edge_index_dict, batch.edge_mask_dict)
  with jax.default_matmul_precision('highest'):
    (loss, logits), grads = jax.jit(jax.value_and_grad(
        lambda p: _typed_loss(model, p, batch), has_aux=True))(params)
    (loss_o, logits_o), grads_o = jax.jit(jax.value_and_grad(
        lambda p: _typed_loss(model, p, other), has_aux=True))(params)
  _close(logits, logits_o, 'logits')
  assert float(loss) == pytest.approx(float(loss_o), rel=1e-5)
  _close_trees(grads, grads_o, 'gradient')


@pytest.mark.parametrize('conv', ['gat', 'sage'])
def test_windowed_hetero_conv_equals_segment_path(conv):
  """`HeteroConv` over whole tables with ``windows_dict``: every
  relation's conv by window, the empty first blocks included."""
  batch = _typed_world()['batch']
  make = (lambda: GATConv(4, heads=4)) if conv == 'gat' else (
      lambda: SAGEConv(16))
  layer = HeteroConv(tuple(sorted(batch.edge_index_dict)), 16,
                     make_conv=make)
  args = (batch.x_dict, batch.edge_index_dict, batch.edge_mask_dict)
  params = layer.init(jax.random.key(0), *args)
  windows = dict(batch.metadata['hop_windows'])
  got = layer.apply(params, *args, windows_dict=windows)
  want = layer.apply(params, *args)
  _close_trees(got, want, 'values')
  text = lambda **kw: jax.jit(lambda p: layer.apply(
      p, *args, **kw)).lower(params).as_text()
  assert 'stablehlo.scatter' not in text(windows_dict=windows)
  assert 'stablehlo.scatter' in text()
  # the default mode (R-GCN's per-relation linear message) is not
  # given the windowed form: it keeps `segment_mean`
  plain = HeteroConv(tuple(sorted(batch.edge_index_dict)), 16)
  pparams = plain.init(jax.random.key(0), *args)
  assert (jax.jit(lambda p: plain.apply(p, *args, windows_dict=windows))
          .lower(pparams).as_text()
          == jax.jit(lambda p: plain.apply(p, *args))
          .lower(pparams).as_text())


# -- what the lowered programs hold -------------------------------------------

def _scatters(text):
  """``[(operand rows, updates)]`` of every scatter of a lowered text."""
  out = []
  for m in re.finditer(r'"stablehlo\.scatter"', text):
    sig = re.search(r'\}\)\s*:\s*\(tensor<(\d+)[x>][^)]*?,\s*tensor<[^>]*>,'
                    r'\s*tensor<(\d+)[x>]', text[m.start():])
    out.append((int(sig.group(1)), int(sig.group(2))))
  return sorted(out)


#: the loss's own two (the seeds' label pick and its transpose)
_LOSS = [(1024, 1024)] * 2


def _flagship_step_text(*stated):
  """The per-batch flagship step (batch 1024, fanout [15, 10, 5],
  3 x 256, 937,984 table rows) lowered on shapes alone."""
  caps = hop_capacities(1024, (15, 10, 5), 937984)
  md = dict(hop_capacities=caps, hop_windows=hop_windows(1024, (15, 10, 5)))
  n, e = caps[0][-1], caps[1][-1]
  sd = jax.ShapeDtypeStruct
  batch = Batch(x=sd((n, 100), jnp.float32), y=sd((n,), jnp.int32),
                edge_index=sd((2, e), jnp.int32), node=sd((n,), jnp.int32),
                node_mask=sd((n,), jnp.bool_), edge_mask=sd((e,), jnp.bool_),
                batch=sd((1024,), jnp.int32), batch_size=1024,
                num_sampled_nodes=sd((4,), jnp.int32),
                num_sampled_edges=sd((3,), jnp.int32),
                metadata={k: md[k] for k in stated})
  model = GraphSAGE(hidden_features=256, out_features=47, num_layers=3)
  tx = optax.adam(3e-3)
  params = jax.eval_shape(model.init, jax.random.key(0), batch.x,
                          batch.edge_index, batch.edge_mask)
  state = TrainState(params, jax.eval_shape(tx.init, params),
                     sd((), jnp.int32))
  return make_supervised_step(model.apply, tx, 1024).lower(
      state, batch).as_text()


def test_flagship_step_holds_no_scatter_over_the_edge_slots_of_a_target():
  """By window: what is left are the source side's two backward
  scatters (``x[src]`` transposed in layers 1 and 2; layer 0's input
  takes no gradient) — none with 936,960 updates, none with 168,960
  into layer 1's 16,384 target rows."""
  got = _scatters(_flagship_step_text('hop_capacities', 'hop_windows'))
  assert got == _LOSS + [(16384, 15360), (169984, 168960)]


def test_batch_without_windows_keeps_the_segment_path():
  """A batch that states capacities alone lowers to the scatters the
  trimmed step held before (two per `segment_mean`, forward; layers 1
  and 2 add the source side's backward one), a batch that states
  nothing to the whole-table ones."""
  trimmed = _scatters(_flagship_step_text('hop_capacities'))
  assert trimmed == sorted(
      _LOSS + [(169984, 936960)] * 2 + [(16384, 168960)] * 2
      + [(1024, 15360)] * 2 + [(169984, 168960), (16384, 15360)])
  whole = _scatters(_flagship_step_text())
  assert whole == sorted(_LOSS + [(937984, 936960)] * 8)


def test_sampler_states_windows_only_where_it_lays_them_out():
  """`sample_from_nodes` of both samplers states the windows; the
  induced-subgraph and link paths, whose edge lists are not laid out by
  target, state none."""
  rows, cols = _coo('skewed', 200)
  g = Graph(CSRTopo((rows, cols), num_nodes=200), mode='DEVICE')
  s = NeighborSampler(g, [3, 2], seed=0)
  out = s.sample_from_nodes(NodeSamplerInput(np.arange(8, dtype=np.int32)))
  assert out.metadata['hop_windows'] == ((8, 3), (24, 2))
  sub = s.subgraph(NodeSamplerInput(np.arange(8, dtype=np.int32)))
  assert 'hop_windows' not in (sub.metadata or {})
  hs = HeteroNeighborSampler({('u', 'to', 'v'): g, ('v', 'back', 'u'): g},
                             [3, 2], seed=0)
  out = hs.sample_from_nodes(NodeSamplerInput(
      np.arange(8, dtype=np.int32), input_type='u'))
  assert dict(out.metadata['hop_windows']) == {
      ('v', 'rev_to', 'u'): ((8, 3), (0, 0)),
      ('u', 'rev_back', 'v'): ((0, 0), (24, 2))}
