"""The typed graph's model path (ISSUEs 29 and 30): `models.RGAT`, `GATConv`'s
bipartite form, the typed sampler's stated hop layout and the stack
trimmed to it, and the scopes on the typed programs' device ops.

(a) the package R-GAT against the plain float32 reference of the
    benchmark (`chipbench/builders/igbh_reference.py`, which imports
    nothing of the package) on a typed toy graph: logits, loss, every
    gradient leaf;
(b) trimmed against whole-table: seed logits, every gradient leaf, the
    `model.trim` event's extents;
(c) `GATConv` (and `SAGEConv`) over separate source and target tables
    against the concatenated form;
(d) the sampler's stated capacities against `_plan_capacities` and
    against the batch itself, and a batch without them;
(e) at least 95 % of the typed programs' device ops carry a ``glt.``
    token.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
  sys.path.insert(0, REPO)

from chipbench import load_file
from graphlearn_tpu.data import Dataset
from graphlearn_tpu.loader import NeighborLoader
from graphlearn_tpu.models import (GATConv, RGAT, SAGEConv, TrainState,
                                   apply_to_batch, make_supervised_step,
                                   typed_layer_extent)
from graphlearn_tpu.sampler.hetero_neighbor_sampler import (
    _plan, _plan_capacities, typed_hop_capacities)
from graphlearn_tpu.typing import as_str, reverse_edge_type

igbh = load_file(os.path.join(REPO, 'chipbench', 'builders', 'igbh.py'))
ref, build = igbh.ref, igbh.build

P, A, I, F = 'paper', 'author', 'institute', 'fos'
SIZES = {P: 300, A: 200, I: 9, F: 30}
FANOUT = [3, 2, 2]
B, D, HIDDEN, HEADS, CLASSES = 8, 12, 16, 4, 5
CFG = dict(
    num_nodes=SIZES, feature_dim=D, hidden=HIDDEN, heads=HEADS,
    classes=CLASSES, num_layers=3, target=P,
    precision=dict(table='float32'),
    relations=[dict(type=[P, 'cites', P], avg_degree=3),
               dict(type=[P, 'written_by', A], avg_degree=2),
               dict(type=[A, 'affiliated_to', I], avg_degree=1),
               dict(type=[P, 'topic', F], avg_degree=2)])


@pytest.fixture(scope='module')
def world():
  """A typed toy graph of IGBH's shape, one batch of its loader, the
  model and the seed's weights in both forms."""
  data = build.tables(CFG, 11)
  ds = (Dataset()
        .init_graph(data['graphs'], layout='CSR', num_nodes=SIZES)
        .init_node_features(data['feats'], split_ratio=1.0)
        .init_node_labels({P: data['labels']}))
  loader = NeighborLoader(ds, FANOUT, (P, np.arange(64)), batch_size=B,
                          shuffle=True, seed=3)
  batch = next(iter(loader))
  weights = build.weights(CFG, 11)
  model = RGAT(etypes=tuple(sorted(batch.edge_index_dict)),
               hidden_features=HIDDEN, out_features=CLASSES, num_layers=3,
               heads=HEADS, target_ntype=P)
  return dict(data=data, ds=ds, loader=loader, batch=batch, model=model,
              weights=weights, params=igbh.program_params(weights))


@pytest.fixture(scope='module')
def trimmed(world):
  """``((loss, seed logits), gradients)`` of the model through the step
  builders' seam on the batch as the loader made it: the trimmed
  stack."""
  with jax.default_matmul_precision('highest'):
    return jax.jit(jax.value_and_grad(
        lambda p: _loss(world['model'], p, world['batch']),
        has_aux=True))(world['params'])


def _without_capacities(batch):
  md = {k: v for k, v in batch.metadata.items()
        if k not in ('hop_capacities', 'hop_windows')}
  leaves, tree = jax.tree_util.tree_flatten(batch)
  out = jax.tree_util.tree_unflatten(tree, leaves)
  out.metadata = md
  return out


def _loss(model, params, batch):
  logits = apply_to_batch(model.apply, params, batch)[:B]
  ce = optax.softmax_cross_entropy_with_integer_labels(
      logits, batch.y_dict[P][:B])
  return ce.mean(), logits


# -- (a) the package model against the plain reference -----------------------

def test_rgat_agrees_with_the_plain_reference(world, trimmed):
  batch = world['batch']
  step = dict(seeds=batch.batch_dict[P], node=dict(batch.node_dict),
              edges={rel: (ei[0], ei[1], batch.edge_mask_dict[rel])
                     for rel, ei in batch.edge_index_dict.items()})
  (loss, logits), grads = trimmed
  x = {t: ref.reference.take_rows(world['data']['feats'][t], ids)
       for t, ids in batch.node_dict.items()}
  want_logits = jax.jit(
      lambda w, x: ref.logits_of(w, x, step['edges'], P, B))(
          world['weights'], x)
  want_loss, want_grads = ref.loss_and_grad(
      world['weights'], step, world['data']['feats'],
      world['data']['labels'], target=P)
  np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                             rtol=1e-5, atol=1e-6)
  assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
  got = ref.leaves(igbh.weights_of(world['weights'], grads))
  want = ref.leaves(want_grads)
  scale = np.median([np.abs(w).max() for w in want])
  assert len(got) == len(want) == 3 * 7 * 3 + 2
  for g, w in zip(got, want):
    np.testing.assert_allclose(g, w, atol=1e-5 * max(scale,
                                                     np.abs(w).max()))
  # the seed rows depend on the relations into papers alone in the last
  # layer: the others' gradients are exactly zero on both sides
  assert sum(not w.any() for w in want) >= 12
  assert all(not g.any() for g, w in zip(got, want) if not w.any())


def test_program_params_round_trip(world):
  back = igbh.weights_of(world['weights'], world['params'])
  for a, b in zip(ref.leaves(back), ref.leaves(world['weights'])):
    np.testing.assert_array_equal(a, b)
  init = jax.eval_shape(
      world['model'].init, jax.random.key(0), world['batch'].x_dict,
      world['batch'].edge_index_dict, world['batch'].edge_mask_dict)
  assert (jax.tree_util.tree_structure(init)
          == jax.tree_util.tree_structure(world['params']))


# -- (b) trimmed against whole tables ----------------------------------------

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


def test_trimmed_stack_agrees_with_whole_tables(world, trimmed):
  batch, model, params = world['batch'], world['model'], world['params']
  plain = _without_capacities(batch)
  assert 'hop_capacities' not in plain.metadata
  (lt, trimmed), gt = trimmed
  (lw, whole), gw = jax.jit(jax.value_and_grad(
      lambda p: _loss(model, p, plain), has_aux=True))(params)
  assert trimmed.shape == (B, CLASSES)
  assert jax.eval_shape(lambda p: apply_to_batch(model.apply, p, plain),
                        params).shape == (batch.x_dict[P].shape[0],
                                          CLASSES)
  np.testing.assert_allclose(np.asarray(trimmed), np.asarray(whole),
                             rtol=0, atol=1e-6)
  assert float(lt) == pytest.approx(float(lw), abs=1e-6)
  for a, b in zip(jax.tree_util.tree_leaves(gt),
                  jax.tree_util.tree_leaves(gw)):
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=0,
        atol=1e-5 * max(float(jnp.abs(b).max()), 1e-3))


def test_trim_event_names_rows_and_slots_per_type_and_relation(world):
  batch, model, params = world['batch'], world['model'], world['params']
  caps = batch.metadata['hop_capacities']
  events = _trim_events(lambda p, b: apply_to_batch(model.apply, p, b),
                        params, batch)
  assert len(events) == 1
  ev = events[0]
  node, edge = dict(caps[0]), dict(caps[1])
  assert ev['layers'] == 3
  for t, c in node.items():
    # layer l reads C_{3-l} rows of a type and writes C_{2-l}
    assert ev['rows_in'][t] == [c[3], c[2], c[1]]
    assert ev['rows_out'][t] == [c[2], c[1], c[0]]
    assert ev['table_rows'][t] == c[3] == batch.x_dict[t].shape[0]
  s = world['loader'].sampler
  frontier_caps, edge_caps = _plan(s.etypes, s.fanouts, {P: B}, s.num_hops,
                                   s._num_nodes)[2:4]
  assert set(dict(batch.metadata['hop_windows'])) == set(edge)
  for rel, e in edge.items():
    assert ev['edge_slots'][as_str(rel)] == [e[2], e[1], e[0]]
    assert ev['table_slots'][as_str(rel)] == e[2]
    # all of them by fanout window — the plan's frontier capacity of
    # the target type times the fanout, summed over the blocks kept
    stored = reverse_edge_type(rel)
    by_hop = np.cumsum([frontier_caps[h].get(rel[2], 0) * FANOUT[h]
                        if stored in edge_caps[h] else 0
                        for h in range(3)])
    assert ev['windowed_slots'][as_str(rel)] == list(by_hop[::-1])
    assert ev['scattered_slots'][as_str(rel)] == [0, 0, 0]
  # capacities alone: trimmed, every slot on the segment path
  md = dict(batch.metadata)
  del md['hop_windows']
  leaves, tree = jax.tree_util.tree_flatten(batch)
  unstated = jax.tree_util.tree_unflatten(tree, leaves)
  unstated.metadata = md
  (seg,) = _trim_events(lambda p, b: apply_to_batch(model.apply, p, b),
                        params, unstated)
  assert seg['scattered_slots'] == seg['edge_slots'] == ev['edge_slots']
  assert not any(sum(v) for v in seg['windowed_slots'].values())
  assert ev['rows_out'][P][-1] == B and ev['rows_out'][A][-1] == 0
  assert not _trim_events(
      lambda p, b: apply_to_batch(model.apply, p, b), params,
      _without_capacities(batch))
  # the extents themselves, and a stack deeper than the sampler
  assert typed_layer_extent(caps, 0) == (
      {t: c[1] for t, c in node.items()},
      {t: c[0] for t, c in node.items()},
      {rel: e[0] for rel, e in edge.items()})
  assert typed_layer_extent(caps, 5) == (
      {t: c[3] for t, c in node.items()},
      {t: c[3] for t, c in node.items()},
      {rel: e[2] for rel, e in edge.items()})


def test_rgat_trains_through_the_step_builder(world):
  """typed `NeighborLoader` -> `Feature.get` -> `RGAT` ->
  `make_supervised_step`: nothing selects the trimmed path, and the
  loss falls."""
  model, tx = world['model'], optax.adam(1e-2)
  state = TrainState(world['params'], tx.init(world['params']),
                     jnp.zeros((), jnp.int32))
  step = make_supervised_step(model.apply, tx, B, target_ntype=P)
  batch = world['batch']
  losses = []
  for _ in range(12):
    state, loss, _ = step(state, batch)
    losses.append(float(loss))
  assert losses[-1] < 0.5 * losses[0]


# -- (c) the bipartite form --------------------------------------------------

@pytest.mark.parametrize('conv', ['gat', 'sage'])
def test_bipartite_form_agrees_with_the_concatenation(conv):
  rng = np.random.default_rng(0)
  na, nb, e, d = 40, 17, 120, 6
  xa = jnp.asarray(rng.normal(size=(na, d)), jnp.float32)
  xb = jnp.asarray(rng.normal(size=(nb, d)), jnp.float32)
  src = jnp.asarray(rng.integers(0, na, e), jnp.int32)
  dst = jnp.asarray(rng.integers(0, nb, e), jnp.int32)
  mask = jnp.asarray(rng.random(e) < 0.8)
  make = (lambda: GATConv(4, heads=3)) if conv == 'gat' else (
      lambda: SAGEConv(12))
  module = make()
  ei = jnp.stack([src, dst])
  params = module.init(jax.random.key(1), (xa, xb), ei, mask)

  def pair(p, xa, xb):
    return module.apply(p, (xa, xb), ei, mask)

  def concatenated(p, xa, xb):
    return module.apply(p, jnp.concatenate([xb, xa]),
                        jnp.stack([src + nb, dst]), mask)[:nb]

  out = pair(params, xa, xb)
  assert out.shape == (nb, 12)
  np.testing.assert_allclose(np.asarray(out),
                             np.asarray(concatenated(params, xa, xb)),
                             rtol=1e-5, atol=1e-6)
  w = jnp.asarray(rng.normal(size=(nb, 12)), jnp.float32)
  grad = lambda f: jax.grad(lambda p, a, b: (f(p, a, b) * w).sum(),
                            argnums=(0, 1, 2))(params, xa, xb)
  for a, b in zip(jax.tree_util.tree_leaves(grad(pair)),
                  jax.tree_util.tree_leaves(grad(concatenated))):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-5)
  # one table, targets its first rows: the square form's rows
  square = module.apply(params, xa, jnp.stack([src, dst]), mask)
  first = module.apply(params, xa, jnp.stack([src, dst]), mask,
                       num_dst=nb)
  np.testing.assert_allclose(np.asarray(first), np.asarray(square[:nb]),
                             rtol=1e-5, atol=1e-6)
  with pytest.raises(ValueError, match='num_dst'):
    module.apply(params, (xa, xb), ei, mask, num_dst=nb - 1)


def test_homogeneous_gat_trims_through_basic_gnn():
  """`GATConv` declares ``in_edge_local``, so `BasicGNN` trims the
  homogeneous `GAT` stack as it trims `GraphSAGE`."""
  from graphlearn_tpu.models import GAT
  rng = np.random.default_rng(0)
  n = 200
  ds = (Dataset()
        .init_graph((np.repeat(np.arange(n), 5), rng.integers(0, n, 5 * n)),
                    layout='COO', num_nodes=n)
        .init_node_features(rng.normal(size=(n, 6)).astype(np.float32))
        .init_node_labels((np.arange(n) % 3).astype(np.int32)))
  batch = next(iter(NeighborLoader(ds, [3, 2], np.arange(64),
                                   batch_size=8, seed=0)))
  model = GAT(hidden_features=8, out_features=3, num_layers=2, heads=2)
  params = model.init(jax.random.key(0), batch.x, batch.edge_index,
                      batch.edge_mask)
  trimmed = apply_to_batch(model.apply, params, batch)
  whole = model.apply(params, batch.x, batch.edge_index, batch.edge_mask)
  assert trimmed.shape == (8, 3) and whole.shape == (batch.x.shape[0], 3)
  np.testing.assert_allclose(np.asarray(trimmed), np.asarray(whole[:8]),
                             rtol=0, atol=1e-6)


# -- (d) the layout the typed sampler states ---------------------------------

def test_stated_capacities_are_the_plans_and_hold_of_the_batch(world):
  batch, s = world['batch'], world['loader'].sampler
  caps = batch.metadata['hop_capacities']
  assert caps == typed_hop_capacities(s.etypes, _plan(
      s.etypes, s.fanouts, {P: B}, s.num_hops, s._num_nodes))
  hash(caps)      # static pytree aux data
  _, table_cap, frontier_caps, edge_caps = _plan_capacities(
      s.etypes, s.fanouts, {P: B}, s.num_hops, s._num_nodes)
  node, edge = dict(caps[0]), dict(caps[1])
  assert set(node) == set(table_cap) == set(SIZES)
  for t, c in node.items():
    assert len(c) == s.num_hops + 1 and list(c) == sorted(c)
    assert c[-1] == table_cap[t] == batch.x_dict[t].shape[0]
    assert c[0] == (B if t == P else 0)
    # a hop adds what its frontiers can find, clamped by the type
    for h in range(s.num_hops - 1):
      found = sum(n for et, n in edge_caps[h].items() if et[2] == t)
      assert c[h + 1] == min(c[h] + found,
                             (B if t == P else 0) + SIZES[t])
  assert set(edge) == set(batch.edge_index_dict)
  for et in s.etypes:
    per_hop = [ec.get(et, 0) for ec in edge_caps]
    assert list(edge[reverse_edge_type(et)]) == list(np.cumsum(per_hop))
    assert (edge[reverse_edge_type(et)][-1]
            == batch.edge_index_dict[reverse_edge_type(et)].shape[1])
  # the batch itself: hop h's edges of a relation lie in its slot range
  # [E_{h-1}, E_h), their targets below C_h and their sources below
  # C_{h+1}; so the nodes found by hop h lie in [0, C_h)
  for (a, _, b), ends in edge.items():
    ei = np.asarray(batch.edge_index_dict[(a, _, b)])
    ok = np.asarray(batch.edge_mask_dict[(a, _, b)])
    start = 0
    for h, end in enumerate(ends):
      blk = slice(start, end)
      if ok[blk].any():
        assert ei[1, blk][ok[blk]].max() < node[b][h]
        assert ei[0, blk][ok[blk]].max() < node[a][h + 1]
      start = end
  # and every valid node that is no seed is the source of an edge of
  # the hop that found it
  for t, ids in batch.node_dict.items():
    valid = int((np.asarray(ids) >= 0).sum())
    tops = [np.asarray(batch.edge_index_dict[rel][0])[
        np.asarray(batch.edge_mask_dict[rel])].max(initial=-1) + 1
            for rel in edge if rel[0] == t]
    assert valid == max(tops + [B if t == P else 0])


def test_other_typed_batches_state_no_layout():
  """Link batches state none; the model then runs whole tables."""
  from graphlearn_tpu.loader import LinkNeighborLoader
  rng = np.random.default_rng(0)
  nu, ni = 30, 12
  rows, cols = rng.integers(0, nu, 90), rng.integers(0, ni, 90)
  et, rev = ('user', 'clicks', 'item'), ('item', 'rev_clicks', 'user')
  ds = (Dataset()
        .init_graph({et: (rows, cols), rev: (cols, rows)}, layout='COO',
                    num_nodes={et: nu, rev: ni})
        .init_node_features(
            {'user': rng.normal(size=(nu, 4)).astype(np.float32),
             'item': rng.normal(size=(ni, 4)).astype(np.float32)},
            split_ratio=1.0))
  batch = next(iter(LinkNeighborLoader(
      ds, [2, 2], (et, np.stack([rows[:8], cols[:8]])), batch_size=8)))
  assert 'hop_capacities' not in batch.metadata
  model = RGAT(etypes=tuple(sorted(batch.edge_index_dict)),
               hidden_features=8, out_features=3, num_layers=2, heads=2,
               target_ntype='user')
  params = jax.eval_shape(model.init, jax.random.key(0), batch.x_dict,
                          batch.edge_index_dict, batch.edge_mask_dict)
  out = jax.eval_shape(lambda p: apply_to_batch(model.apply, p, batch),
                       params)
  assert out.shape == (batch.x_dict['user'].shape[0], 3)


# -- (e) scopes on the typed programs ----------------------------------------

_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: opcodes that do a layer's real work: none may go unnamed
_HEAVY = re.compile(r'[\]\)\}] (gather|scatter|dot|sort)\(')


def _scoped_share(lowered):
  """``(share of the compiled program's ops that carry a glt. token,
  their op_names)``, over the ops the program traced — an op the
  compiler made (a broadcast constant, a relayout copy) has no
  ``op_name`` to carry one — with every heavy op among them."""
  hlo = lowered.compile().as_text()
  names = []
  for line in hlo.splitlines():
    m = _OP_NAME.search(line)
    if _HEAVY.search(line):
      assert m and 'glt.' in m.group(1), line.strip()[:160]
    if m and ' parameter(' not in line:   # an argument is no op
      names.append(m.group(1))
  return sum('glt.' in n for n in names) / max(len(names), 1), names


def test_typed_programs_carry_their_layers(world):
  from graphlearn_tpu.data.feature import _device_gather
  from graphlearn_tpu.sampler.hetero_neighbor_sampler import (
      _hetero_multihop)
  batch, model, s = world['batch'], world['model'], (
      world['loader'].sampler)
  tx = optax.adam(1e-3)
  state = TrainState(world['params'], tx.init(world['params']),
                     jnp.zeros((), jnp.int32))
  step = make_supervised_step(model.apply, tx, B, target_ntype=P)
  share, names = _scoped_share(step.lower(state, batch))
  assert share >= 0.95, share
  rel = as_str((A, 'rev_written_by', P))
  for scope in (f'glt.model/layer0/{rel}', 'glt.model/layer1/trim',
                f'glt.model/layer2/{rel}', 'glt.model/layer0/merge',
                'glt.model/layer2/merge', 'glt.model/head',
                'glt.model/loss', 'glt.optimizer'):
    assert any(scope in n for n in names), scope
  assert any(f'glt.model/layer0/{rel}' in n
             and 'transpose(' in n[:n.index('glt.model')] for n in names)
  # the last layer computes the relations into papers only
  assert not any(f'glt.model/layer2/{as_str((P, "written_by", A))}' in n
                 for n in names)

  _, table_cap, frontier_caps, _ = _plan_capacities(
      s.etypes, s.fanouts, {P: B}, s.num_hops, s._num_nodes)
  graphs = {et: (s.graphs[et].indptr, s.graphs[et].indices, None)
            for et in s.etypes}
  share, names = _scoped_share(_hetero_multihop.lower(
      graphs, (jnp.zeros((B,), jnp.int32),), jax.random.key(0),
      etypes=s.etypes,
      fanouts_t=tuple(s.fanouts[et] for et in s.etypes),
      seed_types=(P,), num_hops=s.num_hops,
      table_caps=tuple(sorted(table_cap.items())),
      frontier_caps_t=tuple(tuple(sorted(fc.items()))
                            for fc in frontier_caps),
      with_edge=False, sort_locality=True))
  assert share >= 0.95, share
  for scope in ('glt.sample/dedup', 'glt.sample/hop0/frontier',
                f'glt.sample/hop0/{as_str((P, "cites", P))}',
                f'glt.sample/hop2/{as_str((I, "rev_affiliated_to", A))}',
                'glt.sample/pack'):
    assert any(scope in n for n in names), scope
  assert not any(f'glt.sample/hop0/{as_str((A, "affiliated_to", I))}' in n
                 for n in names)

  feat = world['ds'].node_features[A]
  share, names = _scoped_share(_device_gather.lower(
      feat._hot, batch.node_dict[A], feat._id2index_dev,
      use_pallas=False, part=A))
  assert share >= 0.95 and any('glt.gather/author' in n for n in names)
