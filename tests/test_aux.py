"""Aux subsystems: metrics/tracing + checkpoint/resume.

These exceed the reference deliberately (SURVEY §5 lists tracing and
checkpointing as absent there); tests pin the public contracts.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from graphlearn_tpu.telemetry import recorder, span
from graphlearn_tpu.utils import Checkpointer, Metrics, metrics


def test_metrics_counts_and_timers():
  m = Metrics()
  m.inc('a')
  m.inc('a', 2)
  with m.timer('t'):
    pass
  snap = m.snapshot()
  assert snap['a'] == 3
  assert snap['t.calls'] == 1
  assert snap['t.secs'] >= 0
  m.reset()
  assert m.snapshot() == {}


def test_span_annotates_with_recorder_off_and_records_with_it_on(
    tmp_path):
  """`span` is the one host-span primitive: recorder off it yields no
  context and emits nothing; recorder on, the same block leaves one
  begin/end pair with a monotonic duration."""
  assert not recorder.enabled
  with span('region') as ctx:
    jnp.ones(4).block_until_ready()
  assert ctx is None
  recorder.enable(str(tmp_path / 'flight.jsonl'))
  try:
    with span('region') as ctx:
      jnp.ones(4).block_until_ready()
    events = [e for e in recorder.events() if e.get('name') == 'region']
  finally:
    recorder.disable()
  assert ctx is not None
  assert [e['kind'] for e in events] == ['span.begin', 'span.end']
  assert events[1]['dur'] >= 0


def test_loader_ticks_global_metrics():
  from graphlearn_tpu.data import Dataset
  from graphlearn_tpu.loader import NeighborLoader
  rows = np.repeat(np.arange(20), 2)
  cols = (rows + 1) % 20
  ds = Dataset().init_graph((rows, cols), layout='COO', num_nodes=20)
  loader = NeighborLoader(ds, [2], np.arange(20), batch_size=8)
  before = metrics.snapshot().get('loader.batches', 0)
  list(loader)
  after = metrics.snapshot()['loader.batches']
  assert after - before == 3


@pytest.mark.parametrize('use_orbax', [True, False])
def test_checkpoint_roundtrip(tmp_path, use_orbax):
  if use_orbax:
    pytest.importorskip('orbax.checkpoint')
  ck = Checkpointer(tmp_path / 'ck', max_to_keep=2, use_orbax=use_orbax)
  assert ck.restore(template=None if use_orbax else {'x': np.zeros(2)}
                    ) is None
  tree = {'w': jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
          'opt': {'mu': jnp.ones(3)}, 'step': jnp.asarray(7)}
  ck.save(1, tree)
  ck.save(5, jax.tree_util.tree_map(lambda v: v + 1, tree))
  ck.save(9, jax.tree_util.tree_map(lambda v: v * 2, tree))
  assert ck.all_steps() == [5, 9]        # max_to_keep=2 pruned step 1
  assert ck.latest_step() == 9
  out = ck.restore(template=tree)
  np.testing.assert_array_equal(out['w'], np.asarray(tree['w']) * 2)
  np.testing.assert_array_equal(out['opt']['mu'], 2 * np.ones(3))
  assert int(out['step']) == 14
  # restore a specific retained step
  out5 = ck.restore(template=tree, step=5)
  np.testing.assert_array_equal(out5['w'], np.asarray(tree['w']) + 1)


def test_checkpoint_resume_training_state(tmp_path):
  """Round-trips a real TrainState through save/restore and continues
  training — the examples' --ckpt-dir flow."""
  import optax
  from graphlearn_tpu.data import Dataset
  from graphlearn_tpu.loader import NeighborLoader
  from graphlearn_tpu.models import (GraphSAGE, create_train_state,
                                     make_supervised_step)
  rng = np.random.default_rng(0)
  n = 32
  rows = np.repeat(np.arange(n), 3)
  cols = rng.integers(0, n, n * 3)
  ds = (Dataset()
        .init_graph((rows, cols), layout='COO', num_nodes=n)
        .init_node_features(rng.standard_normal((n, 8)).astype(np.float32))
        .init_node_labels((np.arange(n) % 3).astype(np.int32)))
  loader = NeighborLoader(ds, [2], np.arange(n), batch_size=8)
  model = GraphSAGE(hidden_features=8, out_features=3, num_layers=1)
  tx = optax.adam(1e-2)
  state, apply_fn = create_train_state(
      model, jax.random.key(0), next(iter(loader)), tx)
  step = make_supervised_step(apply_fn, tx, 8)
  for b in loader:
    state, _, _ = step(state, b)

  ck = Checkpointer(tmp_path / 'run')
  ck.save(1, state)
  restored = ck.restore(template=state)
  chex_equal = jax.tree_util.tree_map(
      lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
      state, restored)
  del chex_equal
  # training continues from the restored pytree
  state2 = jax.tree_util.tree_map(jnp.asarray, restored)
  for b in loader:
    state2, loss, _ = step(state2, b)
  assert np.isfinite(float(loss))


def test_merge_hetero_sampler_output():
  """Partition partials merge with dedup + edge-index remap (reference
  `utils/common.py:55-98`)."""
  import jax.numpy as jnp
  from graphlearn_tpu.sampler.base import HeteroSamplerOutput
  from graphlearn_tpu.utils import (format_hetero_sampler_output,
                                    merge_hetero_sampler_output)

  # emission shape of the hetero samplers: u->i edges appear under the
  # REVERSED key with row = i-type (K[0]) locals, col = u-type locals
  ET = ('i', 'rev_to', 'u')
  a = HeteroSamplerOutput(
      node={'u': jnp.array([10, 11, -1, -1]), 'i': jnp.array([5, 6, -1, -1])},
      node_count={'u': jnp.int32(2), 'i': jnp.int32(2)},
      # edges (i-local row, u-local col): (5<-10), (6<-11)
      row={ET: jnp.array([0, 1])}, col={ET: jnp.array([0, 1])},
      edge_mask={ET: jnp.array([True, True])},
      batch={'u': jnp.array([10, 11])}, edge_types=[ET])
  b = HeteroSamplerOutput(
      node={'u': jnp.array([11, 12, -1, -1]), 'i': jnp.array([6, 7, -1, -1])},
      node_count={'u': jnp.int32(2), 'i': jnp.int32(2)},
      # edges: (6<-11), (7<-12)
      row={ET: jnp.array([0, 1])}, col={ET: jnp.array([0, 1])},
      edge_mask={ET: jnp.array([True, True])},
      batch={'u': jnp.array([11, 12])}, edge_types=[ET])
  m = merge_hetero_sampler_output(a, b)
  u = np.asarray(m.node['u'])
  i = np.asarray(m.node['i'])
  assert list(u[:int(m.node_count['u'])]) == [10, 11, 12]
  assert list(i[:int(m.node_count['i'])]) == [5, 6, 7]
  # remapped global edges must be exactly the union
  got = set()
  em = np.asarray(m.edge_mask[ET])
  for r, c, v in zip(np.asarray(m.row[ET]), np.asarray(m.col[ET]), em):
    if v:
      got.add((int(u[c]), int(i[r])))
  assert got == {(10, 5), (11, 6), (12, 7)}

  # merged batch carries BOTH partials' seeds
  assert list(np.asarray(m.batch['u'])) == [10, 11, 11, 12]
  m = format_hetero_sampler_output(m, ntypes=('w',),
                                   etypes=(('w', 'r', 'u'),),
                                   node_cap=16, edge_cap=24)
  assert m.node['w'].shape == (16,)
  assert m.row[('w', 'r', 'u')].shape == (24,)
