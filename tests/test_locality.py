"""Locality-aware mesh partitioning x exchange co-design (ISSUE 20).

Contracts pinned here:

  * `locality_partition` is deterministic under a fixed seed and honors
    the hard ``(1 + eps) * N / P`` balance cap BY CONSTRUCTION;
  * on a planted community graph it cuts decisively fewer edges than
    the historical random round-robin placement;
  * the partitioner is a PURE RELABEL: replaying the locality arm's
    placement as an explicit ``node_pb`` over the already-relabeled
    edge list yields the identity relabel and byte-identical batches —
    single-chip (P=1) and on the 8-device mesh;
  * the replica cache is EXACT: a replica-armed dataset's batches are
    byte-identical to the cache-less twin, with lookups measurably
    kept off the wire (`locally_served_ids`); a zero budget builds no
    cache at all;
  * `rebalance_plan` moves a measured-hot range off its overloaded
    owner onto the top underloaded REQUESTER, and `execute_rebalance`
    runs the plan through the PR 19 fenced handoff mid-epoch with the
    epoch still byte-identical;
  * the fused tree path ticks BOTH attribution matrices on a tiered
    epoch (the dead-feature-counter regression);
  * `GLT_PARTITIONER` unset keeps the historical placement
    byte-for-byte; the hetero builder partitions the disjoint union.
"""
import numpy as np
import pytest

from graphlearn_tpu.parallel import make_mesh
from graphlearn_tpu.parallel.dist_data import DistDataset
from graphlearn_tpu.parallel.dist_sampler import DistNeighborLoader
from graphlearn_tpu.parallel.failover import ShardStore
from graphlearn_tpu.parallel.locality import (edge_cut_frac,
                                              execute_rebalance,
                                              locality_partition,
                                              rebalance_plan,
                                              resolve_partitioner)
from graphlearn_tpu.parallel.partition_book import AdoptionRefusedError

P = 8
N, E = 200, 1200
C = N // P                       # planted community size


def _community_edges(seed=0, intra=0.85):
  """E edges, ``intra`` of them inside contiguous size-C communities —
  structure a locality partitioner should find."""
  rng = np.random.default_rng(seed)
  rows = rng.integers(0, N, E)
  within = (rows // C) * C + rng.integers(0, C, E)
  anywhere = rng.integers(0, N, E)
  cols = np.where(rng.random(E) < intra, within, anywhere)
  return rows, cols


def _hub_edges(seed=0, hubs=20, frac=0.5):
  """Half the destinations land on nodes [0, hubs) — concentrated
  demand for the rebalance tests."""
  rng = np.random.default_rng(seed)
  rows = rng.integers(0, N, E)
  cols = np.where(rng.random(E) < frac, rng.integers(0, hubs, E),
                  rng.integers(0, N, E))
  return rows, cols


def _feat():
  return (np.arange(N)[:, None] + np.zeros((1, 6))).astype(np.float32)


def _range_pb(seed=0):
  """The historical seeded round-robin placement, reproduced."""
  rng = np.random.default_rng(seed)
  pb = np.empty(N, np.int32)
  perm = rng.permutation(N)
  for p in range(P):
    pb[perm[p::P]] = p
  return pb


def _loader(ds, seeds=None, **kw):
  kw.setdefault('batch_size', 4)
  kw.setdefault('shuffle', True)
  kw.setdefault('seed', 0)
  kw.setdefault('exchange_slack', 1.5)   # static: cross-arm byte
  #                                      # equality must not depend on
  #                                      # the adaptive slack walk
  n = ds.graph.bounds[-1]
  return DistNeighborLoader(ds, [3, 2],
                            np.arange(n) if seeds is None else seeds,
                            **kw)


def _assert_batches_equal(ref, got, what=''):
  assert len(ref) == len(got), f'{what}: {len(got)} != {len(ref)}'
  for i, (a, b) in enumerate(zip(ref, got)):
    for f in ('node', 'x', 'edge_index', 'batch'):
      av, bv = getattr(a, f, None), getattr(b, f, None)
      if av is None and bv is None:
        continue
      assert np.array_equal(np.asarray(av), np.asarray(bv)), \
          f'{what}: {f} differs at batch {i}'


# -- the streaming partitioner ----------------------------------------------

def test_partition_deterministic_and_seed_sensitive():
  rows, cols = _community_edges()
  pb1, st1 = locality_partition(rows, cols, N, P, seed=7)
  pb2, st2 = locality_partition(rows, cols, N, P, seed=7)
  np.testing.assert_array_equal(pb1, pb2)    # same seed => same bytes
  assert st1 == st2
  pb3, _ = locality_partition(rows, cols, N, P, seed=8)
  assert not np.array_equal(pb1, pb3)        # the seed is load-bearing


@pytest.mark.parametrize('eps', (0.05, 0.2))
def test_balance_cap_holds_by_construction(eps):
  rows, cols = _community_edges()
  pb, st = locality_partition(rows, cols, N, P, balance_eps=eps)
  assert pb.shape == (N,) and (pb >= 0).all() and (pb < P).all()
  sizes = np.bincount(pb, minlength=P)
  cap = int(np.ceil((1.0 + eps) * N / P))
  assert sizes.max() <= cap == st['cap']
  assert np.isclose(st['max_part_frac'], sizes.max() * P / N)


def test_cut_beats_random_round_robin():
  rows, cols = _community_edges()
  pb_loc, st = locality_partition(rows, cols, N, P, seed=0)
  cut_rng = edge_cut_frac(rows, cols, _range_pb())
  cut_loc = edge_cut_frac(rows, cols, pb_loc)
  assert np.isclose(cut_loc, st['edge_cut_frac'])
  assert cut_rng > 0.8                       # ~ 1 - 1/P
  assert cut_loc < 0.6 * cut_rng             # structure was found


def test_partitioner_knob_resolution(monkeypatch):
  monkeypatch.delenv('GLT_PARTITIONER', raising=False)
  assert resolve_partitioner() == 'range'
  monkeypatch.setenv('GLT_PARTITIONER', 'locality')
  assert resolve_partitioner() == 'locality'
  rows, cols = _community_edges()
  ds = DistDataset.from_full_graph(P, rows, cols, _feat(), num_nodes=N)
  assert ds.partitioner == 'locality'        # the env knob engaged
  with pytest.raises(ValueError, match='fennel9000'):
    resolve_partitioner('fennel9000')


def test_default_placement_byte_identical(monkeypatch):
  """GLT_PARTITIONER unset: the build must reproduce the historical
  seeded round-robin placement byte-for-byte."""
  monkeypatch.delenv('GLT_PARTITIONER', raising=False)
  rows, cols = _community_edges()
  feat = _feat()
  lab = (np.arange(N) % 4).astype(np.int64)
  ds = DistDataset.from_full_graph(P, rows, cols, feat, lab,
                                   num_nodes=N)
  assert ds.partitioner == 'range'
  ref = DistDataset.from_full_graph(P, rows, cols, feat, lab,
                                    num_nodes=N, node_pb=_range_pb())
  np.testing.assert_array_equal(ds.old2new, ref.old2new)
  np.testing.assert_array_equal(ds.graph.bounds, ref.graph.bounds)
  np.testing.assert_array_equal(ds.graph.indptr, ref.graph.indptr)
  np.testing.assert_array_equal(ds.graph.indices, ref.graph.indices)
  np.testing.assert_array_equal(ds.node_features.shards,
                                ref.node_features.shards)
  np.testing.assert_array_equal(ds.node_labels, ref.node_labels)


# -- pure-rename equivalence ------------------------------------------------

def _rename_twin(ds_loc, rows, cols, feat, num_parts, replica_frac):
  """Replay ``ds_loc``'s placement as an explicit node_pb over the
  ALREADY-relabeled edge list; the relabel must come out the
  identity."""
  o2n, n2o = ds_loc.old2new, ds_loc.new2old
  n = int(ds_loc.graph.bounds[-1])
  pb_new = (np.searchsorted(ds_loc.graph.bounds, np.arange(n),
                            'right') - 1).astype(np.int32)
  ds_ren = DistDataset.from_full_graph(
      num_parts, o2n[rows], o2n[cols], node_feat=feat[n2o],
      num_nodes=n, node_pb=pb_new, replica_frac=replica_frac,
      hotness=np.bincount(o2n[cols], minlength=n))
  np.testing.assert_array_equal(ds_ren.old2new, np.arange(n))
  return ds_ren, o2n


@pytest.mark.parametrize('num_parts', (1, P))
def test_pure_rename_byte_equivalence(num_parts):
  """Single-chip (P=1) and mesh (P=8): the locality build and its
  renamed explicit-node_pb twin emit byte-identical batches — the
  partitioner is a relabel, nothing else."""
  rows, cols = _community_edges()
  feat = _feat()
  frac = 0.1
  ds_loc = DistDataset.from_full_graph(
      num_parts, rows, cols, feat, num_nodes=N, partitioner='locality',
      replica_frac=frac)
  assert ds_loc.partitioner == 'locality'
  ds_ren, o2n = _rename_twin(ds_loc, rows, cols, feat, num_parts, frac)
  mesh = make_mesh(num_parts)
  ref = list(_loader(ds_loc, mesh=mesh))
  got = list(_loader(ds_ren, seeds=o2n[np.arange(N)], mesh=mesh))
  _assert_batches_equal(ref, got, f'pure rename P={num_parts}')


# -- the replica cache ------------------------------------------------------

def test_replica_budget_zero_builds_no_cache():
  rows, cols = _community_edges()
  ds = DistDataset.from_full_graph(P, rows, cols, _feat(), num_nodes=N,
                                   partitioner='locality',
                                   replica_frac=0.0)
  assert not getattr(ds.node_features, 'cache_local', False)
  assert ds.node_features.cache_ids is None


def test_replica_rows_exact_and_off_wire():
  """A tiny replica budget changes NO bytes in any batch — hot remote
  rows are served from the local copy, exactly — while the attribution
  plane shows lookups kept off the wire and a lower cross fraction."""
  rows, cols = _hub_edges()
  feat = _feat()

  def build(frac):
    return DistDataset.from_full_graph(P, rows, cols, feat,
                                       num_nodes=N,
                                       partitioner='locality',
                                       replica_frac=frac)

  l0 = _loader(build(0.0))
  ref = list(l0)
  l1 = _loader(build(0.1))                   # 20 remote rows / device
  got = list(l1)
  _assert_batches_equal(ref, got, 'replica overlay')
  assert l1.sampler.replica_hits() > 0
  a0 = l0.sampler.attribution_stats(tick_metrics=False)
  a1 = l1.sampler.attribution_stats(tick_metrics=False)
  assert a1['locally_served_ids'] > 0 == a0['locally_served_ids']
  assert (a1['cross_partition_bytes_frac']
          < a0['cross_partition_bytes_frac'])


# -- online rebalance -------------------------------------------------------

def test_rebalance_plan_moves_hot_range_to_top_requester():
  m = np.ones((P, P))
  m[:, 3] = 40.0                             # range 3: hot everywhere
  m[5, 3] = 90.0                             # device 5 asks the most
  plan = rebalance_plan({'bytes_matrix': m})
  assert plan, 'the hot range must move'
  mv = plan[0]                               # hottest range first
  assert (mv['range'], mv['frm'], mv['to']) == (3, 3, 5)
  assert mv['demand'] == m[:, 3].sum()
  # every move leaves its identity owner, and no destination is
  # reused (one extra lane per device)
  assert all(p['range'] == p['frm'] for p in plan)
  dests = [p['to'] for p in plan]
  assert len(dests) == len(set(dests))
  assert rebalance_plan({'bytes_matrix': m}, max_moves=1) == [mv]
  # knobs and edges of the ladder
  assert rebalance_plan({'bytes_matrix': m}, max_moves=0) == []
  assert rebalance_plan({'bytes_matrix': m}, overload_factor=50.0) == []
  assert rebalance_plan({'bytes_matrix': None}) == []
  assert rebalance_plan({}) == []


def test_rebalance_plan_prefers_sketch_mass():
  """An attached sketch's exact decayed range histogram supersedes the
  matrix column mass for demand ranking."""
  class _Flat:
    range_mass = np.ones(P)

  class _Skewed:
    range_mass = np.r_[np.ones(3), 50.0, np.ones(P - 4)]

  m = np.ones((P, P))
  m[:, 3] = 40.0
  # flat sketch demand: nobody is overloaded, the hot column ignored
  assert rebalance_plan({'bytes_matrix': m}, sketch=_Flat()) == []
  # skewed sketch demand drives the move even with the same matrix
  plan = rebalance_plan({'bytes_matrix': m}, sketch=_Skewed())
  assert plan and plan[0]['range'] == 3
  assert plan[0]['demand'] == 50.0           # the sketch's mass, not
  #                                          # the matrix column sum


@pytest.mark.xfail(
    strict=True, raises=AdoptionRefusedError,
    reason="rebalance_plan's own second move is one the book refuses: "
           "after {range 3: 3 -> 5} it plans {range 5: 5 -> 3}, and "
           "device 3 no longer serves its own range, so "
           "PartitionBook.transfer raises 'destination 3 is itself "
           "dead (owned by 5)'. The planner tracks busy destinations "
           "but not the sources its earlier moves emptied "
           "(parallel/locality.py, a plane with no cell: ROADMAP D10)")
def test_mid_epoch_rebalance_byte_identical(tmp_path):
  """The online arm end-to-end: measured attribution -> plan -> fenced
  execution MID-EPOCH, with the epoch byte-identical to the
  undisturbed run and ownership actually moved."""
  rows, cols = _hub_edges()
  feat = _feat()
  # explicit skew: partition 3 owns every hub => measured demand
  # concentrates on range 3 and the planner must move it
  pb = (np.arange(N) % P).astype(np.int32)
  pb[:20] = 3

  def build():
    return DistDataset.from_full_graph(P, rows, cols, feat,
                                       num_nodes=N, node_pb=pb)

  ref = list(_loader(build()))
  ds = build()
  loader = _loader(ds)
  it = iter(loader)
  got = [next(it) for _ in range(3)]
  att = loader.sampler.attribution_stats(tick_metrics=False)
  plan = rebalance_plan(att, book=ds.partition_book)
  assert plan and plan[0]['range'] == 3      # the hot range moves
  infos = execute_rebalance(ds, plan,
                            store=ShardStore(tmp_path / 'shards'))
  got.extend(it)
  _assert_batches_equal(ref, got, 'mid-epoch rebalance')
  assert len(infos) == len(plan)
  book = ds.partition_book
  assert book.version == len(plan)           # one bump per move
  assert int(book.view().owners[3]) == plan[0]['to']
  assert book.transfers()[0]['range'] == 3
  assert book.adoptions() == []              # planned, not a crash
  # measurable post-rebalance drop: range 3's heaviest requester now
  # OWNS it, so its column flips local under the owner-aware mask
  att2 = loader.sampler.attribution_stats(tick_metrics=False)
  assert (att2['cross_partition_bytes_frac']
          < att['cross_partition_bytes_frac'])


# -- fused tree path: both attribution matrices tick ------------------------

def test_fused_tree_tiered_ticks_both_matrices():
  """The dead-feature-counter regression: a tiered FusedDistTreeEpoch
  must populate the FEATURE attribution matrix, not only the frontier
  one."""
  import jax
  import optax
  from graphlearn_tpu.models import TreeSAGE
  from graphlearn_tpu.parallel import FusedDistTreeEpoch
  n = 96
  rng = np.random.default_rng(0)
  rows = np.repeat(np.arange(n), 6)
  cols = rng.integers(0, n, 6 * n)
  feat = (np.arange(n, dtype=np.float32)[:, None]
          * np.ones((1, 4), np.float32))
  lab = (np.arange(n) % 5).astype(np.int32)
  ds = DistDataset.from_full_graph(P, rows, cols, feat, lab,
                                   num_nodes=n, split_ratio=0.4)
  model = TreeSAGE(hidden_features=8, out_features=5, num_layers=2)
  fused = FusedDistTreeEpoch(ds, [3, 2], np.arange(n), model,
                             optax.adam(1e-2), batch_size=8,
                             mesh=make_mesh(P), shuffle=True, seed=0)
  state = fused.init_state(jax.random.key(0))
  state, stats = fused.run(state)
  assert np.isfinite(np.asarray(stats.losses)).all()
  fr, ft = fused.sampler.attribution_matrices()
  assert fr.sum() > 0, 'frontier attribution dead on the fused path'
  assert ft.sum() > 0, 'feature attribution dead on the fused path'
  # off-diagonal traffic exists on both planes (P=8 random placement)
  assert (fr.sum() - np.trace(fr)) > 0
  assert (ft.sum() - np.trace(ft)) > 0


# -- hetero: joint-union partitioning ---------------------------------------

def test_hetero_locality_smoke():
  """`DistHeteroDataset.from_full_graph(partitioner='locality')`
  partitions the disjoint union; per-type layouts stay consistent and
  the hetero sampler runs on the mesh."""
  from graphlearn_tpu.parallel import (DistHeteroDataset,
                                       DistHeteroNeighborSampler)
  num_parts = 4
  nu, ni = 32, 16
  urow = np.repeat(np.arange(nu), 2)
  icol = np.stack([np.arange(nu) % ni, (np.arange(nu) + 1) % ni],
                  1).reshape(-1)
  et = ('user', 'clicks', 'item')
  et_rev = ('item', 'rev_clicks', 'user')
  ufeat = np.tile(np.arange(nu, dtype=np.float32)[:, None], (1, 4))
  ifeat = np.tile(np.arange(ni, dtype=np.float32)[:, None], (1, 4))
  ds = DistHeteroDataset.from_full_graph(
      num_parts, {et: (urow, icol), et_rev: (icol, urow)},
      node_feat_dict={'user': ufeat, 'item': ifeat},
      num_nodes_dict={'user': nu, 'item': ni},
      partitioner='locality')
  assert ds.num_nodes_dict() == {'user': nu, 'item': ni}
  # the balance cap holds on the UNION of both types
  union_sizes = (np.diff(ds.bounds['user'])
                 + np.diff(ds.bounds['item']))
  cap = int(np.ceil(1.05 * (nu + ni) / num_parts))
  assert union_sizes.max() <= cap
  sampler = DistHeteroNeighborSampler(ds, [2, 2],
                                      mesh=make_mesh(num_parts),
                                      seed=0)
  seeds = ds.old2new['user'][np.arange(nu).reshape(num_parts, -1)]
  out = sampler.sample_from_nodes('user', seeds)
  # every emitted item id decodes to a real node via its feature row
  inodes = np.asarray(out['node']['item'])
  valid = inodes >= 0
  assert valid.any()
  i_old = ds.new2old['item']
  assert (i_old[inodes[valid]] < ni).all()
