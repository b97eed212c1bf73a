"""The counts a compiled program makes only so that it can report them
run under one scope, ``glt.telemetry/<part>``, and the attribution
histogram is a compare and a dense sum, not a scatter.

(a) `dest_histogram` equals the ``segment_sum`` form it replaced, bin
    for bin, at every P, and its program holds no scatter;
(b) in the mesh tree epoch's program no ``s32[P+1]`` scatter is left
    (nor, since the exchange packs by sorts and slices, any scatter of
    an exchange), no op carries the
    old ``glt.exchange/stats``, and every op whose value reaches only
    the reported outputs (the stats tail, the hop counts) carries
    ``glt.telemetry/`` as its first ``glt.`` token;
(c) the waiting by-layer reader, and the benchmark's reader of a
    layer's time over it, book a ``glt.telemetry/...`` op to
    ``telemetry`` and not to the layer it used to sit in;
(d) what the counters report is what they reported under the scatter
    and the old scopes: totals of small fused epochs recorded before
    the change, held exactly.
"""
import collections
import os
import re
import sys

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N, D, CLASSES = 240, 8, 4
FANOUT = (3, 2)
P_PARTS = 4


# -- (a) the histogram ---------------------------------------------------------

def _segment_sum_histogram(ids, owner_fn, num_parts, valid=None):
  """The form `dest_histogram` had: a scatter-add into P + 1 bins."""
  if valid is None:
    valid = ids >= 0
  owner = jnp.where(valid, owner_fn(ids).astype(jnp.int32),
                    jnp.int32(num_parts))
  owner = jnp.clip(owner, 0, num_parts)
  return jax.ops.segment_sum(
      jnp.ones(ids.shape, jnp.int32), owner,
      num_segments=num_parts + 1)[:num_parts]


@pytest.mark.parametrize('num_parts', [1, 2, 4, 16])
def test_dest_histogram_equals_the_segment_sum_form(num_parts,
                                                    monkeypatch):
  from graphlearn_tpu.parallel import exchange
  rng = np.random.default_rng(num_parts)
  ids = jnp.asarray(rng.integers(-1, 500, 3001).astype(np.int32))
  # owners from -2 to P + 2: negative ones clip to bin 0, those past P
  # go to the dropped bin with the invalid ids
  owner_fn = lambda x: x % (num_parts + 5) - 2
  valid = jnp.asarray(rng.random(3001) < 0.7) & (ids >= 0)
  for v in (None, valid):
    want = _segment_sum_histogram(ids, owner_fn, num_parts, v)
    got = exchange.dest_histogram(ids, owner_fn, num_parts, v)
    assert got.dtype == jnp.int32 and got.shape == (num_parts,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    jitted = jax.jit(lambda i: exchange.dest_histogram(
        i, owner_fn, num_parts, v))
    np.testing.assert_array_equal(np.asarray(jitted(ids)),
                                  np.asarray(want))
    assert 'scatter' not in jitted.lower(ids).as_text()
  # counted chunk by chunk, the bins are the same
  monkeypatch.setattr(exchange, 'HISTOGRAM_CHUNK_ELEMS', 700)
  got = exchange.dest_histogram(ids, owner_fn, num_parts, valid)
  np.testing.assert_array_equal(
      np.asarray(got),
      np.asarray(_segment_sum_histogram(ids, owner_fn, num_parts, valid)))
  # no ids: zero in every bin
  empty = jnp.zeros((0,), jnp.int32)
  np.testing.assert_array_equal(
      np.asarray(exchange.dest_histogram(empty, owner_fn, num_parts)),
      np.zeros(num_parts, np.int32))


# -- (b) the mesh tree program --------------------------------------------------

def _graph():
  rng = np.random.default_rng(0)
  rows = np.repeat(np.arange(N), 6)
  cols = rng.integers(0, N, rows.shape[0])
  feats = rng.normal(size=(N, D)).astype(np.float32)
  labels = (np.arange(N) % CLASSES).astype(np.int32)
  return rows, cols, feats, labels


def _dist_dataset():
  from graphlearn_tpu.parallel import DistDataset
  rows, cols, feats, labels = _graph()
  return DistDataset.from_full_graph(P_PARTS, rows, cols, node_feat=feats,
                                     node_label=labels, num_nodes=N)


def _tree_epoch(batch_size=8, **kw):
  from graphlearn_tpu.models import TreeSAGE
  from graphlearn_tpu.parallel import FusedDistTreeEpoch, make_mesh
  return FusedDistTreeEpoch(
      _dist_dataset(), list(FANOUT), np.arange(N),
      TreeSAGE(hidden_features=16, out_features=CLASSES, num_layers=2),
      optax.adam(1e-2), batch_size=batch_size, mesh=make_mesh(P_PARTS),
      shuffle=True, seed=0, **kw)


@pytest.fixture(scope='module')
def tree_hlo():
  """The optimized HLO of the one program the mesh tree epoch
  dispatches (`_expand_collect` and the train tail, in a scan)."""
  fused = _tree_epoch()
  state = fused.init_state(jax.random.key(0))
  seeds = np.stack(list(fused._batcher)).reshape(-1, P_PARTS, 8)
  return fused._compiled.jitted.lower(
      state, fused._put_batches(seeds), fused._next_epoch_key(),
      fused._chunk_arrs()).compile().as_text()


_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TOKEN = re.compile(r'glt\.(\w+)(/\w+)?')


def test_mesh_tree_program_counts_owners_without_a_scatter(tree_hlo):
  bins = f's32[{P_PARTS + 1}]'
  small = [line for line in tree_hlo.splitlines()
           if re.search(r'= ' + re.escape(bins) + r'(\{[^}]*\})? scatter\(',
                        line)]
  scopes = []
  for line in small:
    m = _OP_NAME.search(line)
    tok = _TOKEN.search(m.group(1)) if m else None
    scopes.append(tok.group(0) if tok else '')
  # nor the dense pack's own per-owner count: the exchange plans pack
  # by sorts and slices (`dist_sampler._pack`), so no exchange op of
  # the program is a scatter at all
  assert scopes == []
  assert not [line for line in tree_hlo.splitlines()
              if re.search(r' scatter\(', line)
              and 'glt.exchange/' in line]
  assert 'glt.exchange/stats' not in tree_hlo
  names = _OP_NAME.findall(tree_hlo)
  parts = {t.group(0) for n in names for t in [_TOKEN.search(n)]
           if t and t.group(1) == 'telemetry'}
  assert parts == {'glt.telemetry/attribution', 'glt.telemetry/exchange',
                   'glt.telemetry/hops'}


def _first_layer(eqn) -> str:
  m = _TOKEN.search(str(eqn.source_info.name_stack))
  return m.group(1) if m else ''


def _reported_only(jaxpr, reported):
  """Equations of ``jaxpr`` whose outputs reach the outputs numbered in
  ``reported`` and no other output: the ops that compute only what is
  reported.  Equations with literal inputs alone (constants) are left
  out: they compute nothing."""
  def reach(outs):
    need = {v for v in outs if isinstance(v, jex_core.Var)}
    hit = []
    for eqn in reversed(jaxpr.eqns):
      if need & set(eqn.outvars):
        hit.append(eqn)
        need |= {v for v in eqn.invars if isinstance(v, jex_core.Var)}
    return hit
  outs = list(jaxpr.outvars)
  other = {id(e) for e in reach(
      [v for i, v in enumerate(outs) if i not in reported])}
  return [e for e in reach([outs[i] for i in reported])
          if id(e) not in other
          and any(isinstance(v, jex_core.Var) for v in e.invars)]


def _shard_map_body(closed):
  for eqn in closed.jaxpr.eqns:
    if eqn.primitive.name == 'shard_map':
      return eqn.params['jaxpr']
  raise AssertionError('no shard_map in the program')


def test_every_op_of_the_stats_tail_is_telemetry():
  """The per-device train step returns ``(state, loss, correct,
  n_valid, stats7, hop_g)``: what reaches only the last two is
  telemetry, and is scoped so."""
  fused = _tree_epoch()
  state = fused.init_state(jax.random.key(0))
  seeds = np.stack(list(fused._batcher)).reshape(-1, P_PARTS, 8)[0]
  arrs = fused._chunk_arrs()
  closed = jax.make_jaxpr(fused._sharded_step)(
      state, jnp.asarray(seeds), fused._next_epoch_key(),
      arrs['indptr'], arrs['indices'], arrs['bounds'], arrs['fshards'],
      arrs['lshards'])
  body = _shard_map_body(closed)
  n_out = len(body.outvars)
  n_state = n_out - 5                  # state leaves come first
  ops = _reported_only(body, {n_state + 3, n_state + 4})
  assert len(ops) > 10
  stray = [(e.primitive.name, str(e.source_info.name_stack))
           for e in ops if _first_layer(e) != 'telemetry']
  assert not stray, stray
  # and the counters do not reach the step: nothing of the telemetry
  # scope feeds the state, the loss, the accuracy or the seed count
  feeds = _reported_only(body, set(range(n_state + 3)))
  assert not [e for e in feeds if _first_layer(e) == 'telemetry']


# -- (c) the reader -------------------------------------------------------------

Ev = collections.namedtuple('Ev', 'name start_ns duration_ns stats',
                            defaults=((),))
Ln = collections.namedtuple('Ln', 'name events')
Pl = collections.namedtuple('Pl', 'name lines')
Pr = collections.namedtuple('Pr', 'planes')
PRE = 'jit(_epoch_fn)/while/body/closed_call/shard_map/'


def _ev(name, shape, op_name, start, dur):
  return Ev(f'%{name} = {shape} fusion(%p.1), '
            f'metadata={{op_name="{op_name}"}}', start, dur)


def test_reader_books_telemetry_to_telemetry():
  if REPO not in sys.path:
    sys.path.insert(0, REPO)
  from chipbench import load_file, readers
  waiting = load_file(os.path.join(
      REPO, 'tests', 'chipbench', 'layer_scopes', 'layer_metrics',
      'scope_device_ms.py'))
  ops = [_ev('fusion.820', 's32[4]{0}',
             PRE + 'glt.telemetry/attribution/reduce_sum', 0, 400),
         _ev('fusion.12', 's32[3]{0}',
             PRE + 'glt.telemetry/exchange/concatenate', 400, 100),
         _ev('fusion.773', 's32[5]{0}',
             PRE + 'glt.exchange/feature/scatter-add', 500, 3000),
         _ev('fusion.8', 'f32[8,16]{1,0}',
             PRE + 'jvp(TreeSAGE)/glt.model/layer0/dot_general', 3500,
             1000)]
  ctx = dict(profile=Pr([Pl('/device:TPU:0', [Ln('XLA Ops', ops)])]),
             window=dict(steps=2))
  assert waiting.read(ctx, 'telemetry', per='window') == pytest.approx(
      500 / 1e6)
  assert waiting.read(ctx, 'exchange', per='window') == pytest.approx(
      3000 / 1e6)
  # through the benchmark's reader of a layer's time, per step
  read = readers.resolve('waiting_scope_device_ms',
                         os.path.join(REPO, 'chipbench', 'layer_metrics'))
  assert read(ctx, layer='telemetry', per='step') == pytest.approx(
      500 / 1e6 / 2)
  # a program with no telemetry scope reads 0 ms of it
  bare = dict(ctx, profile=Pr([Pl('/device:TPU:0', [Ln('XLA Ops',
                                                       ops[2:])])]))
  assert read(bare, layer='telemetry', per='step') == 0.0


# -- (d) the values ---------------------------------------------------------------

#: recorded with the segment_sum histogram and the old scopes, by
#: `_counts` below on the 8 virtual CPU devices of `tests/conftest.py`
RECORDED = {'dedup': {'exchange_stats': {'dist.feature.cache_admits': 0,
                              'dist.feature.cache_evicts': 0,
                              'dist.feature.cache_hit_rate': 0,
                              'dist.feature.cache_hits': 0,
                              'dist.feature.cold_hit_rate': 0,
                              'dist.feature.cold_lookups': 0,
                              'dist.feature.cold_misses': 0,
                              'dist.feature.dropped': 0,
                              'dist.feature.hot_hit_rate': 1,
                              'dist.feature.lookups': 0,
                              'dist.feature.offered': 1621,
                              'dist.feature.slots': 5120,
                              'dist.frontier.dropped': 0,
                              'dist.frontier.offered': 850,
                              'dist.frontier.slots': 4096,
                              'dist.negative.lost': 0},
           'feature': [[107, 104, 103, 105],
                       [108, 106, 102, 112],
                       [116, 104, 112, 110],
                       [86, 91, 78, 77]],
           'frontier': [[63, 55, 52, 56],
                        [57, 49, 58, 60],
                        [54, 67, 51, 58],
                        [42, 53, 37, 38]]},
 'link': {'batch_fill': {'edge_slots': 2304,
                         'edges_valid': 1110,
                         'rows': 1216,
                         'rows_valid': 687}},
 'mesh_link': {'exchange_stats': {'dist.feature.cache_admits': 0,
                                  'dist.feature.cache_evicts': 0,
                                  'dist.feature.cache_hit_rate': 0,
                                  'dist.feature.cache_hits': 0,
                                  'dist.feature.cold_hit_rate': 0,
                                  'dist.feature.cold_lookups': 0,
                                  'dist.feature.cold_misses': 0,
                                  'dist.feature.dropped': 0,
                                  'dist.feature.hot_hit_rate': 1,
                                  'dist.feature.lookups': 0,
                                  'dist.feature.offered': 3082,
                                  'dist.feature.slots': 9728,
                                  'dist.frontier.dropped': 0,
                                  'dist.frontier.offered': 2312,
                                  'dist.frontier.slots': 10240,
                                  'dist.negative.lost': 0},
               'feature': [[197, 201, 195, 195],
                           [196, 203, 180, 192],
                           [187, 196, 177, 191],
                           [186, 203, 186, 197]],
               'frontier': [[158, 155, 139, 145],
                            [143, 157, 126, 142],
                            [138, 148, 133, 150],
                            [142, 147, 144, 145]]},
 'tree': {'exchange_stats': {'dist.feature.cache_admits': 0,
                             'dist.feature.cache_evicts': 0,
                             'dist.feature.cache_hit_rate': 0,
                             'dist.feature.cache_hits': 0,
                             'dist.feature.cold_hit_rate': 0,
                             'dist.feature.cold_lookups': 0,
                             'dist.feature.cold_misses': 0,
                             'dist.feature.dropped': 0,
                             'dist.feature.hot_hit_rate': 1,
                             'dist.feature.lookups': 0,
                             'dist.feature.offered': 2400,
                             'dist.feature.slots': 8192,
                             'dist.frontier.dropped': 0,
                             'dist.frontier.offered': 960,
                             'dist.frontier.slots': 4096,
                             'dist.negative.lost': 0},
          'feature': [[150, 178, 168, 144],
                      [173, 162, 139, 166],
                      [138, 174, 130, 118],
                      [127, 150, 144, 139]],
          'frontier': [[57, 74, 65, 60],
                       [67, 64, 60, 65],
                       [53, 68, 53, 50],
                       [47, 61, 56, 60]]},
 'tree_tight': {'exchange_stats': {'dist.feature.cache_admits': 0,
                                   'dist.feature.cache_evicts': 0,
                                   'dist.feature.cache_hit_rate': 0,
                                   'dist.feature.cache_hits': 0,
                                   'dist.feature.cold_hit_rate': 0,
                                   'dist.feature.cold_lookups': 0,
                                   'dist.feature.cold_misses': 0,
                                   'dist.feature.dropped': 692,
                                   'dist.feature.hot_hit_rate': 1,
                                   'dist.feature.lookups': 0,
                                   'dist.feature.offered': 1716,
                                   'dist.feature.slots': 1024,
                                   'dist.frontier.dropped': 216,
                                   'dist.frontier.offered': 852,
                                   'dist.frontier.slots': 640,
                                   'dist.negative.lost': 0},
                'feature': [[98, 108, 119, 111],
                            [127, 105, 95, 109],
                            [118, 125, 92, 101],
                            [97, 98, 107, 106]],
                'frontier': [[53, 55, 55, 57],
                             [57, 52, 57, 54],
                             [55, 61, 50, 54],
                             [49, 54, 46, 43]]}}


def _embed_apply():
  from graphlearn_tpu.models import GraphSAGE
  model = GraphSAGE(hidden_features=8, out_features=8, num_layers=2)
  x = jnp.zeros((4, D), jnp.float32)
  ei = jnp.zeros((2, 2), jnp.int32)
  params = model.init(jax.random.key(1), x, ei, jnp.ones((2,), bool))
  return model, params


def _counts(kind: str) -> dict:
  """One epoch of a small fused driver; what its counters report."""
  from graphlearn_tpu.models.train import TrainState
  from graphlearn_tpu.parallel import (FusedDistEpoch, FusedDistLinkEpoch,
                                       make_mesh, replicate)
  tx = optax.adam(1e-2)
  rows, cols, feats, _ = _graph()
  if kind == 'link':
    from graphlearn_tpu.data import Dataset
    from graphlearn_tpu.loader import FusedLinkEpoch
    ds = (Dataset().init_graph((rows, cols), layout='COO', num_nodes=N)
          .init_node_features(feats, split_ratio=1.0))
    model, params = _embed_apply()
    epoch = FusedLinkEpoch(ds, list(FANOUT), (rows[:40], cols[:40]),
                           model.apply, tx, batch_size=16, seed=7,
                           max_steps_per_program=4)
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    epoch.run(state)
    return dict(batch_fill=epoch.batch_fill())
  mesh = make_mesh(P_PARTS)
  if kind.startswith('tree'):
    # tight: capacities that drop ids, in the compact layout
    epoch = (_tree_epoch(64, exchange_slack=0.3, exchange_layout='compact')
             if kind == 'tree_tight' else _tree_epoch())
    state = epoch.init_state(jax.random.key(0))
  else:
    model, params = _embed_apply()
    state = replicate(TrainState(params, tx.init(params),
                                 jnp.zeros((), jnp.int32)), mesh)
    if kind == 'dedup':
      from graphlearn_tpu.models import GraphSAGE
      model = GraphSAGE(hidden_features=8, out_features=CLASSES,
                        num_layers=2)
      x = jnp.zeros((4, D), jnp.float32)
      ei = jnp.zeros((2, 2), jnp.int32)
      params = model.init(jax.random.key(1), x, ei, jnp.ones((2,), bool))
      state = replicate(TrainState(params, tx.init(params),
                                   jnp.zeros((), jnp.int32)), mesh)
      epoch = FusedDistEpoch(_dist_dataset(), list(FANOUT), np.arange(N),
                             model.apply, tx, batch_size=16, mesh=mesh,
                             shuffle=True, seed=0)
    else:
      epoch = FusedDistLinkEpoch(
          _dist_dataset(), list(FANOUT), (rows[:256], cols[:256]),
          model.apply, tx, batch_size=16, mesh=mesh,
          neg_sampling='binary', shuffle=True, seed=0)
  epoch.run(state)
  st = epoch.sampler.exchange_stats(tick_metrics=False)
  fr, ft = epoch.sampler.attribution_matrices()
  return dict(exchange_stats={k: int(v) for k, v in sorted(st.items())},
              frontier=fr.tolist(), feature=ft.tolist())


@pytest.mark.parametrize('kind', sorted(RECORDED))
def test_counters_report_what_they_reported(kind):
  assert _counts(kind) == RECORDED[kind]
