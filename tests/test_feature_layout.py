"""The feature store keeps its device tier in the layout its row gather
reads: ``[rows, lane_width(D)]``, the ``D`` columns zero-padded to a
lane multiple once when the tier is placed, and every gather takes the
table's columns back inside its own program (`data.feature`'s layout
rule).

Held here, for row widths 1 / 100 / 128 / 1,024 and float32 / bfloat16
/ int32 tables: what every gather returns equals ``jnp.take`` over the
logical table exactly (invalid ids zero rows, with and without an
``id2index``) on the device-native, host and mixed paths and in the
fused tree and link epochs; the logical metadata reads ``D``; the tier
is the caller's own buffer wherever the width needs no padding; the
``feature.layout`` event says what was stored; and, compiled for a
described v5e, the flagship's gather and fused epoch read the stored
table in place.
"""
import gc
import os
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from graphlearn_tpu.data import Dataset, Feature
from graphlearn_tpu.data.feature import HotTier, _device_gather
from graphlearn_tpu.loader import FusedEpoch, FusedLinkEpoch, FusedTreeEpoch
from graphlearn_tpu.loader.fused_tree import expand_tree_levels
from graphlearn_tpu.models import GraphSAGE, TreeSAGE
from graphlearn_tpu.sampler import NegativeSampling
from graphlearn_tpu.telemetry.recorder import recorder
from graphlearn_tpu.utils.padding import GATHERED_IN_PLACE, lane_width

N = 40
WIDTHS = (1, 100, 128, 1024)
DTYPES = (jnp.float32, jnp.bfloat16, jnp.int32)
# storage row of global id v is N-1-v: a reversal every path must undo
ID2INDEX = np.arange(N - 1, -1, -1)
IDS = np.array([3, -1, 0, N - 1, 17, -1, 22, 3, 39, 8])


def _table(d, dtype):
  """``[N, D]`` logical rows whose values are exact in every dtype
  here (integers under 256)."""
  return (np.arange(N * d).reshape(N, d) % 251).astype(
      jnp.dtype(dtype))


def _expected(table, ids):
  """`jnp.take` over the logical table, zero rows for invalid ids."""
  ok = ids >= 0
  out = np.asarray(jnp.take(jnp.asarray(table), np.where(ok, ids, 0),
                            axis=0))
  return np.where(ok[:, None], out, 0)


def _feature(table, path, mapped):
  """The table behind one of the three paths, stored in the order
  ``ID2INDEX`` undoes where ``mapped``."""
  stored = table[ID2INDEX] if mapped else table
  i2i = ID2INDEX if mapped else None
  if path == 'device':
    return Feature(jnp.asarray(stored), id2index=i2i)
  return Feature(stored, id2index=i2i,
                 split_ratio={'host': 1.0, 'mixed': 0.5}[path])


def _equal(got, want):
  got = np.asarray(got)
  assert got.shape == want.shape and got.dtype == want.dtype
  np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize('mapped', [False, True])
@pytest.mark.parametrize('path', ['device', 'host', 'mixed'])
@pytest.mark.parametrize('dtype', DTYPES)
@pytest.mark.parametrize('d', WIDTHS)
def test_every_gather_equals_take_over_the_logical_table(d, dtype, path,
                                                         mapped):
  table = _table(d, dtype)
  f = _feature(table, path, mapped)
  want = _expected(table, IDS)
  _equal(f.get(IDS), want)
  # twice: the mixed path's second lookup serves admitted rows from
  # its HBM victim cache
  _equal(f.get(IDS), want)
  if path == 'mixed':
    assert f._cold_cache is not None
    return
  _equal(f.get(jnp.asarray(IDS, jnp.int32)), want)
  _equal(_device_gather(f.hot_tier, jnp.asarray(IDS, jnp.int32),
                        f._id2index_dev, use_pallas=False), want)


@pytest.mark.parametrize('path', ['device', 'host', 'mixed'])
@pytest.mark.parametrize('dtype', DTYPES)
@pytest.mark.parametrize('d', WIDTHS)
def test_logical_metadata_reads_the_table_width(d, dtype, path):
  table = _table(d, dtype)
  f = _feature(table, path, mapped=False)
  # the tier's device bytes, told before it is placed and read after
  stored = f.hot_rows * lane_width(d, dtype) * jnp.dtype(dtype).itemsize
  assert f.hot_bytes == stored
  f.lazy_init()
  assert f.hot_bytes == f.hot_tier.rows.nbytes == stored
  assert f.feature_dim == d and f.size(1) == d and f.size(0) == N
  assert tuple(f.shape) == (N, d)
  assert jnp.dtype(f.dtype) == jnp.dtype(dtype)
  assert f'shape=({N}, {d})' in repr(f)
  _equal(f.host_get(IDS), _expected(table, IDS))
  np.testing.assert_array_equal(np.asarray(f.host_get()), table)
  tier = f.hot_tier
  assert isinstance(tier, HotTier) and tier.width == d
  assert tier.rows.shape == (f.hot_rows, lane_width(d, dtype))
  # the table's columns hold the table's rows, the padding zeros
  rows = np.asarray(tier.rows)
  np.testing.assert_array_equal(rows[:, :d], table[:f.hot_rows])
  assert not rows[:, d:].any()


@pytest.mark.parametrize('dtype', DTYPES)
@pytest.mark.parametrize('d', WIDTHS)
def test_stored_tier_and_its_layout_event(d, dtype):
  arr = jnp.asarray(_table(d, dtype))
  recorder.enable()
  recorder.clear()
  try:
    f = Feature(arr)
    events = recorder.events('feature.layout')
  finally:
    recorder.disable()
    recorder.clear()
  width = lane_width(d, dtype)
  padded = d == 100
  assert (width != d) == padded
  assert events == [dict(events[0], width=d, stored_width=width,
                         dtype=str(jnp.dtype(dtype)), rows=N,
                         stored_bytes=N * width * jnp.dtype(dtype).itemsize,
                         padded=padded)]
  if padded:
    assert f.hot_tier.rows is not arr
  else:
    # the caller's own buffer: nothing copied, nothing allocated
    assert f.hot_tier.rows is arr
    assert (f.hot_tier.rows.unsafe_buffer_pointer()
            == arr.unsafe_buffer_pointer())


def test_lane_width_rule():
  assert [lane_width(d, jnp.float32)
          for d in (1, 56, 57, 63, 64, 100, 128, 129, 1024)] == [
              1, 56, 128, 128, 128, 128, 128, 256, 1024]
  assert lane_width(100, jnp.bfloat16) == 128
  assert lane_width(100, jnp.int32) == 128
  # no 8- or 64-bit rows are padded
  assert lane_width(100, np.uint8) == 100
  assert lane_width(100, np.float64) == 100


@pytest.mark.parametrize('d', [100, 128])
def test_pallas_gather_reads_the_padded_tier(d, monkeypatch):
  """Under ``GLT_PALLAS=1`` a 32-bit tier is DMA-eligible once padded;
  the kernel's rows are taken back to ``D`` (interpreted off-TPU)."""
  monkeypatch.setenv('GLT_PALLAS', '1')
  table = _table(d, jnp.float32)
  f = Feature(jnp.asarray(table))
  assert f.hot_tier.rows.shape[1] % 128 == 0
  _equal(_device_gather(f.hot_tier, jnp.asarray(IDS, jnp.int32), None,
                        use_pallas=True), _expected(table, IDS))
  _equal(f.get(IDS), _expected(table, IDS))


def _dataset(table, mapped):
  rng = np.random.default_rng(0)
  rows = np.repeat(np.arange(N), 3)
  cols = rng.integers(0, N, 3 * N)
  stored = table[ID2INDEX] if mapped else table
  return (Dataset()
          .init_graph((rows, cols), layout='COO', num_nodes=N)
          .init_node_features(jnp.asarray(stored),
                              id2idx=ID2INDEX if mapped else None)
          .init_node_labels(np.arange(N) % 3)), rows, cols


@pytest.mark.parametrize('mapped', [False, True])
@pytest.mark.parametrize('dtype', DTYPES)
@pytest.mark.parametrize('d', WIDTHS)
def test_fused_epochs_gather_the_logical_rows(d, dtype, mapped):
  table = _table(d, dtype)
  ds, rows, cols = _dataset(table, mapped)
  seeds = jnp.asarray([0, 5, -1, 9], jnp.int32)
  key = jax.random.key(3)

  tree = FusedTreeEpoch(ds, [3, 2], np.arange(N),
                        TreeSAGE(hidden_features=8, out_features=3),
                        optax.adam(1e-2), batch_size=4, seed=0)
  xs, _, _ = tree._expand(seeds, key, tree._dev, False)
  g = ds.get_graph()
  levels, _ = expand_tree_levels(g.indptr, g.indices, seeds, key, (3, 2),
                                 sort_locality=False)
  for x, lvl in zip(xs, levels):
    _equal(x, _expected(table, np.asarray(lvl)))

  model = GraphSAGE(hidden_features=8, out_features=8, num_layers=2)
  link = FusedLinkEpoch(ds, [3, 2], (rows[:8], cols[:8]), model.apply,
                        optax.adam(1e-2), batch_size=4,
                        neg_sampling=NegativeSampling('binary', 1.0),
                        shuffle=False, seed=7)
  batch = link._link_batch(jnp.asarray(rows[:4], jnp.int32),
                           jnp.asarray(cols[:4], jnp.int32), None, key,
                           link._dev, False)
  _equal(batch.x, _expected(table, np.asarray(batch.node)))


@pytest.mark.parametrize('driver', ['tree', 'link', 'epoch'])
def test_a_fused_epoch_lets_its_tier_go_with_the_last_reference(driver):
  """A fused driver's jitted methods hold the driver weakly
  (`_counted_jit`), so the stored tier dies with the last reference to
  the driver and its dataset, with no collection: a cycle would keep a
  table of gigabytes alive beside whatever is allocated next."""
  ds, rows, cols = _dataset(_table(100, jnp.float32), mapped=False)
  tier = weakref.ref(ds.node_features.hot_tier.rows)
  gc.disable()
  try:
    if driver == 'tree':
      ep = FusedTreeEpoch(ds, [3, 2], np.arange(N),
                          TreeSAGE(hidden_features=8, out_features=3),
                          optax.adam(1e-2), batch_size=4, seed=0)
      state, stats = ep.run(ep.init_state(jax.random.key(0)))
      assert np.isfinite(float(stats.losses[0]))
      del state, stats
    elif driver == 'link':
      ep = FusedLinkEpoch(
          ds, [3, 2], (rows[:8], cols[:8]),
          GraphSAGE(hidden_features=8, out_features=8,
                    num_layers=2).apply, optax.adam(1e-2), batch_size=4,
          neg_sampling=NegativeSampling('binary', 1.0), seed=7)
    else:
      ep = FusedEpoch(ds, [3, 2], np.arange(N),
                      GraphSAGE(hidden_features=8, out_features=3,
                                num_layers=2).apply,
                      optax.adam(1e-2), batch_size=4, seed=0)
    assert tier() is not None
    del ep, ds
    assert tier() is None
  finally:
    gc.enable()


# -- the flagship's table, compiled for a described v5e -------------------

FLAGSHIP_ROWS, FLAGSHIP_D = 9_796_116, 100


@pytest.fixture(scope='module')
def topo():
  from jax.experimental import topologies
  os.environ.setdefault('TPU_LOG_DIR', 'disabled')
  try:
    desc = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
  except Exception as e:  # noqa: BLE001 — no TPU compiler here
    pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
  # the suite compiles its CPU programs unoptimised (conftest); the
  # chip's compiler is asked for the production pipeline
  was = jax.config.read('jax_disable_most_optimizations')
  jax.config.update('jax_disable_most_optimizations', False)
  yield desc
  jax.config.update('jax_disable_most_optimizations', was)


@pytest.fixture(scope='module')
def one_chip(topo):
  from jax.sharding import SingleDeviceSharding
  return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip, d=FLAGSHIP_D):
  """``(sd, a [9,796,116, d] float32 table as the Feature stores it, as
  it was stored before)``."""
  sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                              sharding=one_chip)
  n = FLAGSHIP_ROWS
  stored = HotTier(sd((n, lane_width(d, jnp.float32)), jnp.float32), d)
  return sd, stored, HotTier(sd((n, d), jnp.float32), d)


def _table_copies(compiled):
  """Ops of the optimised program that copy or slice the whole table."""
  return [line for line in compiled.as_text().splitlines()
          if (' copy(' in line or ' slice(' in line)
          and f'[{FLAGSHIP_ROWS},' in line.split('=')[1]]


@pytest.mark.parametrize('d', [56, 57, 63, FLAGSHIP_D])
def test_flagship_gather_reads_its_stored_table_in_place(one_chip, d):
  """`_device_gather` over a stored table of the flagship's rows
  (937,984 ids) makes no copy of the table and under 1.5 GB of
  temporaries, at the widest width stored as it comes
  (`GATHERED_IN_PLACE`) and at the padded widths above it; over a
  padded width's table as it was stored before (``d`` columns, which
  the runtime lays out column-major) it copies the whole table first —
  the control that shows this test sees the copy, and that
  `lane_width`'s threshold is where the copy starts."""
  sd, stored, unpadded = _shapes(one_chip, d)
  assert (lane_width(d, jnp.float32) == d) == (d <= GATHERED_IN_PLACE)
  ids = sd((937_984,), jnp.int32)
  for hot, copies in ((stored, False), (unpadded, d > GATHERED_IN_PLACE)):
    c = _device_gather.lower(hot, ids, None, use_pallas=False).compile()
    temps = c.memory_analysis().temp_size_in_bytes
    assert bool(_table_copies(c)) == copies
    assert (temps > 5e9) if copies else (temps < 1.5e9), temps


def test_fused_epoch_reads_the_stored_table_in_place(one_chip):
  """The flagship's fused tree epoch (fanout [15, 10, 5], batch 1,024;
  a narrow TreeSAGE, since the model does not touch the table) over the
  stored table: its in-scan gathers read a bitcast of the stored rows,
  so the program holds no copy or slice of the table, and under 2 GB
  of temporaries where the copy alone took 5."""
  sd, stored, _ = _shapes(one_chip)
  n, b, steps = FLAGSHIP_ROWS, 1024, 2
  ds, _, _ = _dataset(_table(FLAGSHIP_D, jnp.float32), mapped=False)
  epoch = FusedTreeEpoch(
      ds, [15, 10, 5], np.arange(N),
      TreeSAGE(hidden_features=16, out_features=47, num_layers=3),
      optax.adam(3e-3), batch_size=b, seed=0)
  on = lambda tree: jax.tree_util.tree_map(
      lambda a: sd(a.shape, a.dtype), tree)
  dev = dict(indptr=sd((n + 1,), jnp.int32),
             indices=sd((25 * n,), jnp.int32), hot=stored, id2index=None,
             labels=sd((n,), jnp.int32))
  c = epoch._compiled.jitted.lower(
      on(jax.eval_shape(epoch.init_state, jax.random.key(0))),
      sd((steps, b), jnp.int32), on(jax.eval_shape(jax.random.key, 0)),
      dev, False).compile()
  assert not _table_copies(c)
  assert c.memory_analysis().temp_size_in_bytes < 2e9
