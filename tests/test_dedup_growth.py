"""The per-batch samplers grow their node tables insertion by
insertion (`ops.unique.induce_next(.., capacity=)`): a dedup sorts the
table as filled so far plus its candidates, and the output is that of
tables held at their final size from the first hop on.

  * `test_sampler_equals_full_capacity_oracle`: both samplers' whole
    outputs against an oracle built here from the public `init_node` /
    `induce_next` at the FINAL capacity from the first hop on;
  * `test_dedup_event_*`: the mechanism engaged, read off the
    trace-time `sample.dedup` event and the lowered text's largest sort
    — and, since a dedup's sorts carry what it gathered, that the
    lowered dedups hold four sorts each and no gather (`gathered` 0);
  * `test_induce_next_default_lowers_as_before`: a caller that passes
    no capacity (the mesh samplers) keeps its program.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from graphlearn_tpu.data import CSRTopo, Graph
from graphlearn_tpu.ops.neighbor import sample_one_hop
from graphlearn_tpu.ops.unique import (InducerState, induce_next,
                                       init_node, unique_stable)
from graphlearn_tpu.sampler import (EdgeSamplerInput,
                                    HeteroNeighborSampler,
                                    NeighborSampler, NodeSamplerInput)
from graphlearn_tpu.sampler.hetero_neighbor_sampler import (
    _hetero_multihop, _plan, typed_hop_capacities)
from graphlearn_tpu.sampler.neighbor_sampler import (_multihop_sample,
                                                     hop_capacities)
from graphlearn_tpu.telemetry.schema import EVENT_KINDS
from graphlearn_tpu.typing import as_str, reverse_edge_type
from graphlearn_tpu.utils.padding import INVALID_ID

P, A, I, F = 'paper', 'author', 'institute', 'fos'
# IGBH's relation set as `rgat-igbh` runs it: four stored, three
# reversed, `cites` within one type.
IGBH = {(P, 'cites', P): 6, (P, 'written_by', A): 3,
        (A, 'affiliated_to', I): 1, (P, 'topic', F): 4,
        (A, 'rev_written_by', P): 3, (I, 'rev_affiliated_to', A): 8,
        (F, 'rev_topic', P): 9}


def _skewed(n_src, n_dst, deg, seed):
  """A products-recipe relation: a third of the targets squared-
  uniform, so hubs are found again and again."""
  rng = np.random.default_rng(seed)
  rows = np.repeat(np.arange(n_src), deg)
  cols = rng.integers(0, n_dst, rows.shape[0])
  hub = rng.random(rows.shape[0]) < 0.3
  cols[hub] = (rng.random(int(hub.sum())) ** 2 * n_dst).astype(np.int64)
  return Graph(CSRTopo((rows, cols), num_nodes=n_src), mode='DEVICE')


def _typed_graphs(counts, seed=0):
  return {et: _skewed(counts[et[0]], counts[et[2]], deg, seed + i)
          for i, (et, deg) in enumerate(sorted(IGBH.items()))}


# -- the oracle: every table at its final capacity from the first hop --

def _window(state, start, size):
  slots = start + jnp.arange(size, dtype=jnp.int32)
  valid = slots < state.count
  nodes = state.nodes[jnp.clip(slots, 0, state.nodes.shape[0] - 1)]
  return jnp.where(valid, nodes, INVALID_ID), jnp.where(valid, slots, -1)


def _per_hop(cum):
  cum = np.asarray([int(c) for c in cum], np.int32)
  return np.concatenate([cum[:1], np.diff(cum)])


def _oracle_homo(graph, seeds, key, fanouts, node_cap):
  state, seed_local = init_node(seeds, node_cap)
  frontier, local = _window(state, 0, seeds.shape[0])
  rows, cols, counts = [], [], [state.count]
  for i, k in enumerate(fanouts):
    res = sample_one_hop(graph.indptr, graph.indices, frontier, k,
                         jax.random.fold_in(key, i))
    state, r, c, prev = induce_next(state, local, res.nbrs, res.mask)
    rows.append(r)
    cols.append(c)
    counts.append(state.count)
    frontier, local = _window(state, prev, frontier.shape[0] * k)
  row = jnp.concatenate(rows)
  return dict(node=state.nodes, node_count=state.count, row=row,
              col=jnp.concatenate(cols), edge_mask=row >= 0,
              seed_local=seed_local, num_sampled_nodes=_per_hop(counts))


def _oracle_typed(sampler, seeds_by_type, key):
  sizes = {nt: int(s.shape[0]) for nt, s in seeds_by_type.items()}
  ntypes, caps, frontier_caps, _, _ = _plan(
      sampler.etypes, sampler.fanouts, sizes, sampler.num_hops,
      sampler._num_nodes)
  states, seed_local = {}, {}
  for nt in ntypes:
    if nt in seeds_by_type:
      states[nt], seed_local[nt] = init_node(seeds_by_type[nt], caps[nt])
    else:
      states[nt] = InducerState(
          jnp.full((caps[nt],), INVALID_ID, jnp.int32),
          jnp.zeros((), jnp.int32))
  start = {nt: jnp.zeros((), jnp.int32) for nt in ntypes}
  rows = {et: [] for et in sampler.etypes}
  cols = {et: [] for et in sampler.etypes}
  counts = {nt: [states[nt].count] for nt in ntypes}
  for h in range(sampler.num_hops):
    at_start = {nt: states[nt].count for nt in ntypes}
    # frontiers are read before any insertion of the hop
    frontiers = {nt: _window(states[nt], start[nt], frontier_caps[h][nt])
                 for nt in ntypes if frontier_caps[h].get(nt, 0) > 0}
    for ei, et in enumerate(sampler.etypes):
      s, _, d = et
      k = sampler.fanouts[et][h] if h < len(sampler.fanouts[et]) else 0
      if k <= 0 or s not in frontiers:
        continue
      g = sampler.graphs[et]
      res = sample_one_hop(
          g.indptr, g.indices, frontiers[s][0], k,
          jax.random.fold_in(jax.random.fold_in(key, h), ei))
      states[d], r, c, _ = induce_next(states[d], frontiers[s][1],
                                       res.nbrs, res.mask)
      rows[et].append(r)
      cols[et].append(c)
    for nt in ntypes:
      start[nt] = at_start[nt]
      counts[nt].append(states[nt].count)
  out = dict(node={nt: states[nt].nodes for nt in ntypes},
             node_count={nt: states[nt].count for nt in ntypes},
             row={}, col={}, edge_mask={}, seed_local=seed_local,
             num_sampled_nodes={nt: _per_hop(counts[nt]) for nt in ntypes})
  for et in sampler.etypes:
    if rows[et]:
      rev = reverse_edge_type(et)
      out['row'][rev] = jnp.concatenate(rows[et])
      out['col'][rev] = jnp.concatenate(cols[et])
      out['edge_mask'][rev] = out['row'][rev] >= 0
  return out


def _assert_same(got, want, path=''):
  if isinstance(want, dict):
    assert sorted(got, key=str) == sorted(want, key=str), path
    for k in want:
      _assert_same(got[k], want[k], f'{path}/{k}')
  else:
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype, path
    np.testing.assert_array_equal(got, want, err_msg=path)


def _dedup_events(fn):
  """``(fn(), the `sample.dedup` events of running or tracing it)``."""
  from graphlearn_tpu.telemetry.recorder import recorder
  recorder.enable()
  recorder.clear()
  try:
    out = fn()
    return out, recorder.events('sample.dedup')
  finally:
    recorder.disable()
    recorder.clear()


def _first_key(seed):
  # `_next_key` of a sampler's first call
  return jax.random.fold_in(jax.random.key(seed), 1)


def _homogeneous(n, batch, fanouts):
  graph = _skewed(n, n, 6, seed=3)
  sampler = NeighborSampler(graph, fanouts, seed=11)
  seeds = np.arange(batch, dtype=np.int32) * 3 % n
  seeds[-2:] = INVALID_ID            # a padded batch
  out, events = _dedup_events(
      lambda: sampler.sample_from_nodes(NodeSamplerInput(node=seeds)))
  node_cap = sampler.node_capacity(batch)
  want = _oracle_homo(graph, jnp.asarray(seeds), _first_key(11),
                      sampler.num_neighbors, node_cap)
  stated = hop_capacities(batch, sampler.num_neighbors, node_cap)
  return (out, out.metadata['seed_local'], want, stated, {None: node_cap},
          events)


def _typed(counts, fanouts, seeds_by_type, link=None):
  sampler = HeteroNeighborSampler(_typed_graphs(counts), fanouts, seed=5)
  if link is None:
    (nt, seeds), = seeds_by_type.items()
    out, events = _dedup_events(lambda: sampler.sample_from_nodes(
        NodeSamplerInput(node=seeds, input_type=nt)))
    seed_local = {nt: out.metadata['seed_local']}
    key = _first_key(5)
  else:
    s_t, _, d_t = link
    out, events = _dedup_events(lambda: sampler.sample_from_edges(
        EdgeSamplerInput(row=seeds_by_type[s_t], col=seeds_by_type[d_t],
                         input_type=link)))
    seed_local = out.metadata['seed_local']
    # `sample_from_edges` spends one key on its negatives (none here)
    key = jax.random.fold_in(jax.random.key(5), 2)
  jseeds = {nt: jnp.asarray(s) for nt, s in seeds_by_type.items()}
  want = _oracle_typed(sampler, jseeds, key)
  plan = _plan(sampler.etypes, sampler.fanouts,
               {nt: len(s) for nt, s in seeds_by_type.items()},
               sampler.num_hops, sampler._num_nodes)
  stated = (typed_hop_capacities(sampler.etypes, plan)
            if link is None else None)
  return out, seed_local, want, stated, plan[1], events


ROOMY = {P: 4000, A: 5000, I: 300, F: 700}
TIGHT = {P: 300, A: 400, I: 7, F: 40}

CASES = {
    'homogeneous': lambda: _homogeneous(5000, 16, [5, 4, 3]),
    # batch + num_nodes caps the last hops' tables
    'homogeneous-clamped': lambda: _homogeneous(150, 16, [5, 4, 3]),
    'typed-node-seeded': lambda: _typed(
        ROOMY, [5, 4, 3], {P: np.arange(8, dtype=np.int32) * 7}),
    'typed-link-seeded': lambda: _typed(
        ROOMY, [4, 3], {P: np.arange(6, dtype=np.int32) * 5,
                        A: np.arange(6, dtype=np.int32) * 11},
        link=(P, 'written_by', A)),
    # institute (7 nodes) and fos (40) clamp at their node counts
    'typed-clamped': lambda: _typed(
        TIGHT, [5, 4, 3], {P: np.arange(8, dtype=np.int32) * 7}),
}


@pytest.mark.parametrize('case', sorted(CASES))
def test_sampler_equals_full_capacity_oracle(case):
  """Tables, counts, `row`, `col`, masks, `seed_local`,
  `num_sampled_nodes` and the stated `hop_capacities`, element for
  element."""
  out, seed_local, want, stated, final_caps, events = CASES[case]()
  typed = isinstance(want['node'], dict)
  got = dict(node=out.node, node_count=out.node_count, row=out.row,
             col=out.col, edge_mask=out.edge_mask, seed_local=seed_local,
             num_sampled_nodes=out.num_sampled_nodes)
  _assert_same(got, want)
  # the layout a batch states is the plan's, and the draw keeps to it
  tables = out.node if typed else {None: out.node}
  for nt, table in tables.items():
    assert table.shape == (final_caps[nt],)
  if stated is not None:
    assert out.metadata['hop_capacities'] == stated
    node_caps = dict(stated[0]) if typed else {None: stated[0]}
    nsn = out.num_sampled_nodes if typed else {None: out.num_sampled_nodes}
    for nt, hop_caps in node_caps.items():
      assert (np.cumsum(np.asarray(nsn[nt])) <= np.asarray(hop_caps)).all()
  # this very program grew its tables: no sort covers a final capacity
  # plus its candidates, and the last insertion into a table returns
  # it within the rounding `pack` pads
  ev, = events
  last = {}
  for scope, n, rows, cands in zip(ev['scope'], ev['sorted'],
                                   ev['table_rows'], ev['candidates']):
    nt = scope.split('__')[-1] if typed else None
    assert n == last.get(nt, n - cands) + cands and rows <= final_caps[nt]
    last[nt] = rows
  if case == 'typed-clamped':
    # `institute` finds all 7 of its nodes, its table holds 8 rows, and
    # its insertions past the first return fewer rows than they sort:
    # overflow drops the latest-appearing ids as before
    assert final_caps[I] == 8 and int(out.node_count[I]) == 7
    assert [rows for scope, rows in zip(ev['scope'], ev['table_rows'])
            if scope.endswith(I)] == [8, 8]


# -- the mechanism engaged ---------------------------------------------

def _largest_sort(text):
  """The longest operand of any sort in a lowered program's text."""
  return max(int(n) for n in re.findall(
      r'"stablehlo\.sort".*?\}\) : \(tensor<(\d+)x', text, flags=re.S))


_FUNC = re.compile(r'func\.func (?:public |private )?@([\w.$-]+)\(')


def _dedup_ops(text):
  """``(dedups, sorts, gathers, scatters)`` of a lowered program: its
  calls of `unique_stable`, and the `stablehlo` sorts, gathers and
  scatters those calls run — inside the `unique_stable` functions and
  whatever they call, a function called three times counted three
  times (`jnp.argsort` lowers to one shared function a shape)."""
  heads = list(_FUNC.finditer(text))
  body = {m.group(1): text[m.end():(heads[i + 1].start()
                                     if i + 1 < len(heads) else len(text))]
          for i, m in enumerate(heads)}

  @functools.lru_cache(None)
  def ops(name):
    own = [body[name].count(f'"stablehlo.{op}"(')
           for op in ('sort', 'gather', 'scatter')]
    for callee in re.findall(r'call @([\w.$-]+)', body[name]):
      own = [a + b for a, b in zip(own, ops(callee))]
    return tuple(own)

  dedups = re.findall(r'call @(unique_stable[\w.$-]*)', text)
  totals = [sum(col) for col in zip(*(ops(name) for name in dedups))]
  return (len(dedups), *totals)


def test_dedup_ops_counts_the_gathering_form():
  """The counter itself, on the form `tests/test_unique.py` keeps as
  the oracle: four sorts and five gathers a dedup."""
  from test_unique import _unique_stable_before

  @functools.partial(jax.jit, static_argnums=1)
  def unique_stable(x, capacity):     # the name `_dedup_ops` looks for
    return _unique_stable_before(x, capacity)

  def two(x):
    return unique_stable(x, 64), unique_stable(x[:50], 50)
  text = jax.jit(two).lower(
      jax.ShapeDtypeStruct((80,), jnp.int32)).as_text()
  assert _dedup_ops(text) == (2, 8, 10, 0)


def _flagship_lower():
  """`_multihop_sample` at `sage-products`' shapes (batch 1024, fanout
  [15, 10, 5], 9,796,116 nodes), traced and lowered, never compiled."""
  i32 = jnp.int32
  n, e, b = 9_796_116, 244_902_900, 1024
  return _multihop_sample.lower(
      jax.ShapeDtypeStruct((n + 1,), i32), jax.ShapeDtypeStruct((e,), i32),
      None, jax.ShapeDtypeStruct((b,), i32), jax.random.key(0),
      fanouts=(15, 10, 5), node_cap=937_984, with_edge=False)


def test_dedup_event_and_largest_sort_at_flagship_shapes():
  lowered, (ev,) = _dedup_events(_flagship_lower)
  assert ev['scope'] == ['hop0', 'hop1', 'hop2']
  assert ev['candidates'] == [15_360, 153_600, 768_000]
  assert ev['table_rows'] == [16_384, 169_984, 937_984]
  # old capacity + B*k, not the capacity the hop returns + B*k
  assert ev['sorted'] == [1024 + 15_360, 16_384 + 153_600,
                          169_984 + 768_000]
  assert sum(ev['sorted']) == 1_124_352
  assert _largest_sort(lowered.as_text()) == 937_984   # not 1,705,984
  # the seeds' dedup and three insertions: four sorts each, as before,
  # and nothing moved through a permutation gather (it was 5 x sorted)
  assert ev['gathered'] == [0, 0, 0]
  assert _dedup_ops(lowered.as_text()) == (4, 16, 0, 0)


def _igbh_lower(batch=32):
  """`_hetero_multihop` at `rgat-igbh`'s shapes (32 paper seeds, fanout
  [15, 10, 5] on the seven relations, igbh-small's node counts)."""
  counts = {P: 1_000_000, A: 1_926_066, I: 14_751, F: 190_449}
  etypes = tuple(sorted(IGBH))
  fanouts = {et: (15, 10, 5) for et in etypes}
  plan = _plan(etypes, fanouts, {P: batch}, 3, counts)
  _, table_cap, frontier_caps, _, _ = plan
  i32 = jnp.int32
  graphs = {et: (jax.ShapeDtypeStruct((counts[et[0]] + 1,), i32),
                 jax.ShapeDtypeStruct((counts[et[0]] * IGBH[et],), i32),
                 None) for et in etypes}
  lowered = _hetero_multihop.lower(
      graphs, (jax.ShapeDtypeStruct((batch,), i32),), jax.random.key(0),
      etypes=etypes, fanouts_t=tuple(fanouts[et] for et in etypes),
      seed_types=(P,), num_hops=3,
      table_caps=tuple(sorted(table_cap.items())),
      frontier_caps_t=tuple(tuple(sorted(fc.items()))
                            for fc in frontier_caps),
      with_edge=False)
  return lowered, plan, etypes


def test_dedup_event_and_largest_sort_at_igbh_shapes():
  (lowered, (_, caps, frontier_caps, _, _), etypes), (ev,) = _dedup_events(
      _igbh_lower)
  assert caps == {P: 134_912, A: 101_280, F: 77_280, I: 14_752}
  assert ev['insertions'] == 16
  # replay the growth from the plan alone: rows held + B*k per insertion
  rows = {nt: 0 for nt in caps}
  rows[P] = 32
  want = []
  for h, k in enumerate((15, 10, 5)):
    for et in etypes:
      s, _, d = et
      if frontier_caps[h][s] == 0:
        continue
      cands = frontier_caps[h][s] * k
      grown = min(rows[d] + cands, caps[d])
      want.append((f'hop{h}/{as_str(et)}', rows[d] + cands, grown, cands))
      rows[d] = grown
  assert list(zip(ev['scope'], ev['sorted'], ev['table_rows'],
                  ev['candidates'])) == want
  assert ev['scope'][0] == 'hop0/paper__cites__paper'
  assert ev['sorted'][0] == 32 + 480                     # not 135,392
  by_hop = [sum(n for s, n in zip(ev['scope'], ev['sorted'])
                if s.startswith(f'hop{h}/')) for h in range(3)]
  assert by_hop == [1_472, 45_696, 473_376]
  assert sum(ev['sorted']) == 520_544                    # not 1,953,088
  # every table ends at its planned capacity or below it only by the
  # rounding `pack` pads
  assert _largest_sort(lowered.as_text()) == max(ev['sorted'])
  assert max(ev['sorted']) < 134_912 + 72_000
  # the paper seeds' dedup and sixteen insertions, four sorts each: 68
  # in the dedups, 64 of them the insertions' (the three seedless
  # types' empty tables are dedups of no element and no sort)
  assert ev['gathered'] == [0] * 16
  assert _dedup_ops(lowered.as_text()) == (20, 68, 0, 0)


def test_dedup_event_lists_old_capacity_plus_candidates_small():
  """The event of a sampler that RAN (not only traced) names each
  insertion once, and a second call of the compiled program none."""
  graph = _skewed(500, 500, 6, seed=1)
  sampler = NeighborSampler(graph, [3, 2], seed=0)
  seeds = NodeSamplerInput(node=np.arange(7, dtype=np.int32))
  _, (ev,) = _dedup_events(lambda: sampler.sample_from_nodes(seeds))
  assert ev['sorted'] == [7 + 21, 28 + 42]
  assert ev['table_rows'] == [28, sampler.node_capacity(7)]
  assert ev['gathered'] == [0, 0]
  # the registry tells consumers of every field the emitter sets
  for field in ('insertions', 'scope', 'sorted', 'table_rows',
                'candidates', 'gathered'):
    assert field in ev and field in EVENT_KINDS['sample.dedup'], field
  assert _dedup_events(lambda: sampler.sample_from_nodes(seeds))[1] == []


# -- callers that pass no capacity keep their program ------------------

def _induce_next_before(state, src_local, nbrs, nbr_mask):
  """`induce_next` as it stood before it took a capacity, verbatim."""
  capacity = state.nodes.shape[0]
  b, k = nbrs.shape
  flat_nbrs = nbrs.reshape(-1)
  flat_mask = nbr_mask.reshape(-1)
  combined = jnp.concatenate([state.nodes, flat_nbrs])
  valid = jnp.concatenate(
      [jnp.arange(capacity) < state.count, flat_mask])
  res = unique_stable(combined, capacity, valid=valid)
  new_state = InducerState(nodes=res.values, count=res.count)
  nbr_local = res.inverse[capacity:]
  src_flat = jnp.broadcast_to(src_local[:, None], (b, k)).reshape(-1)
  edge_valid = flat_mask & (src_flat >= 0) & (nbr_local >= 0)
  rows = jnp.where(edge_valid, nbr_local, -1)
  cols = jnp.where(edge_valid, src_flat, -1)
  return new_state, rows, cols, state.count


# (table capacity, B, k): a hop of `parallel/dist_sampler.py`'s
# per-device multihop (its table is held at `node_cap` from the seeds
# on) at a test's and at the flagship's per-device shapes, and one of
# `parallel/dist_hetero.py`'s typed tables
@pytest.mark.parametrize('cap,b,k', [(2048, 64, 4), (937_984, 15_360, 10),
                                     (134_912, 480, 10)])
def test_induce_next_default_lowers_as_before(cap, b, k):
  i32 = jnp.int32
  args = (InducerState(jax.ShapeDtypeStruct((cap,), i32),
                       jax.ShapeDtypeStruct((), i32)),
          jax.ShapeDtypeStruct((b,), i32),
          jax.ShapeDtypeStruct((b, k), i32),
          jax.ShapeDtypeStruct((b, k), jnp.bool_))

  now = jax.jit(induce_next).lower(*args).as_text()
  before = jax.jit(_induce_next_before).lower(*args).as_text()
  # the program's name aside, letter for letter
  name = re.compile(r'module @\S+')
  assert name.sub('module', now) == name.sub('module', before)
  assert _largest_sort(now) == cap + b * k
