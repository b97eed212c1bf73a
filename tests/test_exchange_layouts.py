"""Exchange-layout invariants (ISSUE 3): the pluggable all-to-all
layouts (`parallel.exchange`) must keep the bucketing contract the
engines rely on —

  * capacity accounting: what was actually sent fits in the slots
    (``offered - dropped <= slots``) at every P and layout;
  * round trip: bucketed -> exchanged -> answered -> stitched equals
    the unbucketed reference for a deterministic reply function;
  * layout equivalence: dense / compacted / hierarchical deliver
    identical valid ids and masks for deterministic gathers;
  * the never-executed ragged backend is not selectable.

P in {2, 8} runs on the real 8-device test mesh; P in {16, 64} uses
the host-simulated bucketing twin (`simulate_assignment`), which
mirrors the traced slot assignment exactly.
"""
import numpy as np
import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from graphlearn_tpu.parallel.exchange import (
    AUTO_COMPACT_MIN_PARTS, ExchangeSpec, capacity_spec,
    mesh_factors, plan_exchange, resolve_layout, simulate_assignment)
from graphlearn_tpu.parallel.shard_map_compat import shard_map

LAYOUTS = ('dense', 'compact', 'hier')


def _mesh(p):
  return Mesh(np.array(jax.devices()[:p]), ('data',))


def _owner_fn(bounds):
  return lambda v: (jnp.searchsorted(bounds, v, side='right')
                    - 1).astype(jnp.int32)


def _run_plan(p, n_ids, spec, seed=0, num_nodes=4096):
  """Drive one plan on a real p-device mesh: exchange ids, answer with
  the deterministic reply ``3 * id + owner`` at the owner, stitch.
  Returns (ids, out, delivered) stacked host arrays."""
  rng = np.random.default_rng(seed)
  ids = rng.integers(0, num_nodes, (p, n_ids)).astype(np.int32)
  ids[:, -1] = -1                       # padded tail in every shard
  bounds_h = (np.arange(p + 1) * (num_nodes // p)).astype(np.int32)
  bounds_h[-1] = num_nodes
  mesh = _mesh(p)

  def body(ids_s, bounds):
    my = jax.lax.axis_index('data')
    plan = plan_exchange(ids_s[0], _owner_fn(bounds), p, 'data', spec)
    # deterministic owner-side answer: f(id) = 3 * id + owner(id);
    # invalid request slots answer 0
    ans = jnp.where(plan.recv >= 0,
                    3 * plan.recv + _owner_fn(bounds)(plan.recv), 0)
    out = plan.reply(ans, fill=-7)
    offered, dropped, slots = plan.stats
    stats = jnp.stack([offered, dropped, slots])
    return out[None], plan.delivered[None], stats[None]

  f = jax.jit(shard_map(body, mesh=mesh,
                        in_specs=(P('data'), P()),
                        out_specs=(P('data'), P('data'), P('data'))))
  out, delivered, stats = f(
      jax.device_put(ids, NamedSharding(mesh, P('data'))),
      jax.device_put(bounds_h, NamedSharding(mesh, P())))
  return (ids, np.asarray(out), np.asarray(delivered),
          np.asarray(stats))


@pytest.mark.parametrize('p', [2, 8])
@pytest.mark.parametrize('layout', LAYOUTS)
def test_roundtrip_matches_unbucketed_reference(p, layout):
  n = 96
  spec = capacity_spec(n, p, 2.0, layout=layout)
  if layout == 'hier' and p == 2:
    assert spec.layout == 'dense'       # too small to factor
  ids, out, delivered, stats = _run_plan(p, n, spec)
  num_nodes = 4096
  bounds = (np.arange(p + 1) * (num_nodes // p)).astype(np.int64)
  bounds[-1] = num_nodes
  owner = np.clip(np.searchsorted(bounds, ids, side='right') - 1,
                  0, p - 1)
  ref = 3 * ids.astype(np.int64) + owner      # unbucketed reference
  valid = ids >= 0
  # every delivered id's reply equals the reference; undelivered and
  # invalid slots carry the fill
  assert (out[valid & delivered] == ref[valid & delivered]).all()
  assert (out[~delivered] == -7).all()
  for d in range(p):
    offered, dropped, slots = stats[d]
    assert offered - dropped <= slots
  # mesh-wide: hier counts each id once per wire stage (stage-2
  # offered lives on the intermediate device, so only the SUM over
  # devices is meaningful); single-stage layouts count once
  total_offered = int(stats[:, 0].sum())
  total_valid = int(valid.sum())
  if spec.layout == 'hier':
    assert total_valid <= total_offered <= 2 * total_valid
  else:
    assert total_offered == total_valid


@pytest.mark.parametrize('p', [2, 8])
def test_layouts_identical_valid_ids_and_masks(p):
  """Deterministic replies: every layout must deliver the same values
  for the ids it kept, and at slack 2.0 with near-balanced buckets all
  layouts keep everything -> identical outputs and masks."""
  n = 64
  outs, masks = [], []
  for layout in LAYOUTS:
    spec = capacity_spec(n, p, 2.0, layout=layout)
    ids, out, delivered, _ = _run_plan(p, n, spec, seed=3)
    outs.append(np.where(delivered, out, -7))
    masks.append(delivered & (ids >= 0))
  for o, m in zip(outs[1:], masks[1:]):
    np.testing.assert_array_equal(masks[0], m)
    np.testing.assert_array_equal(outs[0], o)
  # and nothing was dropped at this slack on balanced ids
  assert masks[0].sum() == (ids >= 0).sum()


@pytest.mark.parametrize('p', [2, 8, 16, 64])
@pytest.mark.parametrize('layout', LAYOUTS)
def test_capacity_invariants_host_simulated(p, layout):
  """Property-style capacity accounting at every P (host-simulated
  bucketing — no mesh needed): sent fits in slots, kept ids never
  exceed any per-bucket capacity, pool never over-admits."""
  rng = np.random.default_rng(p * 7 + 1)
  for n, slack in ((32, 1.0), (320, 1.25), (1024, 2.0)):
    ids = rng.integers(0, 20000, n).astype(np.int64)
    ids[rng.random(n) < 0.1] = -1
    owner = np.clip(ids * p // 20000, 0, p - 1)
    spec = capacity_spec(n, p, slack, layout=layout)
    sim = simulate_assignment(ids, owner, spec)
    assert sim['offered'] == int((ids >= 0).sum())
    assert sim['offered'] - sim['dropped'] <= sim['slots']
    assert sim['dropped'] >= 0
    kept = sim['kept']
    assert not kept[ids < 0].any()
    if spec.layout == 'dense':
      # no owner bucket may exceed the per-destination cap
      for q in range(p):
        assert kept[owner == q].sum() <= spec.capacity
    elif spec.layout == 'compact':
      over = 0
      for q in range(p):
        over += max(kept[owner == q].sum() - spec.capacity, 0)
      assert over <= spec.pool
    # where the dense FLOOR binds (small per-destination shares — the
    # P=16/64 waste blowup), the compacted layouts must beat dense
    # slots; compact additionally auto-degrades to dense when the
    # floor never bound (its spec.layout comes back 'dense')
    dense = capacity_spec(n, p, slack, layout='dense')
    floor_bound = (n / p * slack) < dense.capacity
    if (p >= AUTO_COMPACT_MIN_PARTS and floor_bound
        and layout == 'compact'):
      assert spec.slots < dense.slots
    if layout == 'compact' and not floor_bound:
      assert spec.slots <= dense.slots


def test_compact_pool_catches_full_skew():
  """Every id owned by ONE partition: the tight base drops most, the
  pool admits up to its budget, accounting stays exact."""
  p = 16
  n = 256
  ids = np.arange(n).astype(np.int64)
  owner = np.zeros(n, np.int64)               # all on partition 0
  spec = capacity_spec(n, p, 1.25, layout='compact')
  sim = simulate_assignment(ids, owner, spec)
  assert sim['kept'].sum() == min(n, spec.capacity + spec.pool)
  assert sim['dropped'] == n - sim['kept'].sum()
  assert sim['offered'] - sim['dropped'] <= sim['slots']


def test_capacity_spec_shapes():
  # exact stays exact (None) — the walkers/subgraph contract
  assert capacity_spec(128, 8, None, layout='compact') is None
  # dense reproduces the legacy floor + rounding
  d = capacity_spec(100, 8, 2.0, layout='dense')
  assert d.layout == 'dense' and d.capacity == 64   # floor dominates
  # compact pool-only for tiny shares: slots ~ n, not P * floor
  c = capacity_spec(32, 64, 1.25, layout='compact')
  assert c.capacity == 0 and c.pool == 32 and c.slots == 32
  # hierarchical factors ~sqrt(P) and pays the floor 2*sqrt(P) times
  h = capacity_spec(320, 64, 1.25, layout='hier')
  assert (h.rows, h.cols) == (8, 8)
  assert h.slots < capacity_spec(320, 64, 1.25, layout='dense').slots


def test_auto_and_env_resolution(monkeypatch):
  assert resolve_layout(None, 8) == 'dense'
  assert resolve_layout('auto', AUTO_COMPACT_MIN_PARTS) == 'compact'
  monkeypatch.setenv('GLT_EXCHANGE_LAYOUT', 'hier')
  assert resolve_layout('auto', 64) == 'hier'
  # explicit beats env
  assert resolve_layout('dense', 64) == 'dense'
  monkeypatch.delenv('GLT_EXCHANGE_LAYOUT')
  with pytest.raises(ValueError):
    resolve_layout('mystery', 8)


def test_ragged_is_not_selectable():
  """'ragged' has never executed on a device: naming it must raise,
  not hand a user an unexercised plan (or silently another layout)."""
  with pytest.raises(NotImplementedError):
    resolve_layout('ragged', 16)
  with pytest.raises(NotImplementedError):
    capacity_spec(128, 16, 1.5, layout='ragged')


def test_mesh_factors():
  assert mesh_factors(64) == (8, 8)
  assert mesh_factors(16) == (4, 4)
  assert mesh_factors(8) == (4, 2)
  assert mesh_factors(7) == (7, 1)
  for p in (2, 4, 6, 8, 12, 16, 32, 64, 128):
    r, c = mesh_factors(p)
    assert r * c == p


def test_loader_layouts_agree_on_features():
  """End to end on the 8-device mesh: the three layouts serve
  identical (deterministic) feature rows for every valid node."""
  from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                       make_mesh)
  n = 512
  rng = np.random.default_rng(0)
  rows = np.repeat(np.arange(n), 4)
  cols = rng.integers(0, n, n * 4)
  feats = np.arange(n, dtype=np.float32)[:, None] * np.ones(
      (1, 3), np.float32)
  ds = DistDataset.from_full_graph(8, rows, cols, node_feat=feats,
                                   num_nodes=n)
  mesh = make_mesh(8)
  for layout in LAYOUTS:
    loader = DistNeighborLoader(ds, [3, 2], np.arange(n),
                                batch_size=16, shuffle=True, mesh=mesh,
                                seed=0, exchange_slack=1.5,
                                exchange_layout=layout)
    b = next(iter(loader))
    nodes = np.asarray(b.node)
    x = np.asarray(b.x)
    for p_ in range(8):
      m = nodes[p_] >= 0
      np.testing.assert_allclose(
          x[p_][m][:, 0], ds.new2old[nodes[p_][m]].astype(np.float32))
    st = loader.sampler.exchange_stats(tick_metrics=False)
    assert st['dist.frontier.dropped'] == 0
    assert st['dist.feature.dropped'] == 0


def test_hetero_engine_runs_on_compact_and_hier():
  """The hetero engine routes every per-etype hop and per-type gather
  through the same plan API — both non-dense layouts must deliver
  valid, drop-free node tables on the 8-device mesh."""
  from graphlearn_tpu.parallel import DistHeteroNeighborSampler, make_mesh
  from graphlearn_tpu.parallel.dist_hetero import DistHeteroDataset
  rng = np.random.default_rng(0)
  nu, ni = 64, 32
  urow = np.repeat(np.arange(nu), 2)
  icol = rng.integers(0, ni, nu * 2)
  ds = DistHeteroDataset.from_full_graph(
      8, {('u', 'to', 'i'): (urow, icol),
          ('i', 'rev_to', 'u'): (icol, urow)},
      num_nodes_dict={'u': nu, 'i': ni})
  mesh = make_mesh(8)
  for layout in ('compact', 'hier'):
    hs = DistHeteroNeighborSampler(ds, [2, 2], mesh=mesh, seed=0,
                                   collect_features=False,
                                   exchange_slack=2.0,
                                   exchange_layout=layout)
    seeds = ds.old2new['u'][np.arange(16).reshape(8, 2) % nu]
    out = hs.sample_from_nodes('u', seeds)
    nodes_u = np.asarray(out['node']['u'])
    assert (nodes_u >= 0).any()
    st = hs.exchange_stats(tick_metrics=False)
    assert st['dist.frontier.dropped'] == 0


def test_pad_1d_truncation_surfaces():
  """The pad_1d small fix: silent truncation of valid entries emits a
  telemetry event and raises under the strict flag."""
  from graphlearn_tpu.telemetry.recorder import EventRecorder, recorder
  from graphlearn_tpu.utils.padding import pad_1d
  # routine padding and fill-tail truncation stay silent
  out = pad_1d(np.array([1, 2]), 4)
  assert (out == np.array([1, 2, -1, -1])).all()
  pad_1d(np.array([1, 2, -1, -1]), 2)
  events = recorder.events('padding.truncate')
  n0 = len(events)
  pad_1d(np.arange(8), 4)                     # drops 4 valid entries
  assert len(recorder.events('padding.truncate')) >= n0  # no crash
  with pytest.raises(ValueError, match='truncate'):
    pad_1d(np.arange(8), 4, strict=True)
  # event payload (on a private recorder so the global one stays
  # clean for other tests); the recorder MODULE is fetched from
  # sys.modules — the telemetry package re-exports the instance under
  # the same name, shadowing attribute-style module access
  import sys
  rec_mod = sys.modules['graphlearn_tpu.telemetry.recorder']
  rec = EventRecorder()
  rec.enable()
  orig = rec_mod.recorder
  rec_mod.recorder = rec
  try:
    pad_1d(np.arange(10), 6)
  finally:
    rec_mod.recorder = orig
    evs = rec.events('padding.truncate')
    rec.disable()
  assert evs and evs[-1]['dropped'] == 4
  assert evs[-1]['requested'] == 10 and evs[-1]['size'] == 6
