"""CPU rehearsal of the cell `rgat-igbh.train-loader-ahead` (ISSUE 30): the
cell's own files — `chipbench/builders/igbh*.py`, its configuration,
limits and metrics — cut to toy size here and run through the
harness's own `run.run_cell` and `limits.read_seed`.  Nothing read here
is a device number; the same files at the configuration's sizes are
what the driver runs on the chip.
"""
import collections
import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
  sys.path.insert(0, REPO)

import cellroot
from chipbench import limits, run

CELL = 'rgat-igbh.train-loader-ahead'
MIX = 'train-loader-ahead'
FAKE_TPU = dict(platform='cpu', kind='TPU v5 lite', count=1)
#: the typed configuration's own sizes (`cellroot.TINY`'s keys are the
#: homogeneous configuration's and do not size this one)
TOY = dict(num_nodes=dict(paper=500, author=300, institute=10, fos=40),
           feature_dim=16, hidden=16, heads=4, head_dim=4, classes=7,
           fanout=[3, 2, 2],
           traffic={MIX: dict(batch=8, steps_per_epoch=6,
                              trace_seconds=0.3, probe_reps=1)})


@pytest.fixture(scope='module')
def root(tmp_path_factory):
  """A benchmark root with every data file of `chipbench/` and this
  cell's configuration cut to `TOY`."""
  root = cellroot.make_root(str(tmp_path_factory.mktemp('igbh') / 'root'))
  path = os.path.join(root, 'chipbench', 'configs', 'rgat-igbh.json')
  with open(path) as f:
    cfg = json.load(f)
  cfg.update(TOY)
  with open(path, 'w') as f:
    json.dump(cfg, f)
  return root


def drive(root, seed, trace_on=False):
  return run.run_cell(root, CELL, seed, 0.3, trace_on, FAKE_TPU,
                      time.perf_counter())


def test_the_cell_runs_and_proves_correct(root):
  line = drive(root, 2 ** 31 + 29)
  assert line['correct'] is True and line['failed'] == 0
  assert line['in_window_compiles'] == 0
  assert set(line['metrics']) == {'train_seeds_per_s', 'setup_s'}
  spec = run.load_cell(root, CELL)
  assert set(line['checks']) == set(spec['limits'])
  for name, (value, limit) in line['checks'].items():
    assert value is not None and value <= limit, name
  assert line['window']['seeds'] == 8 * line['window']['steps']


def test_traced_run_reports_every_metric_the_cell_lists(root, monkeypatch):
  Ev = collections.namedtuple('Ev', 'name start_ns duration_ns')
  Ln = collections.namedtuple('Ln', 'name events')
  Pl = collections.namedtuple('Pl', 'name lines')
  Pr = collections.namedtuple('Pr', 'planes')

  def fake_traced(fn, where=None):
    t0 = time.perf_counter_ns()
    out = fn()
    span = time.perf_counter_ns() - t0
    return out, Pr([Pl('/device:TPU:0', [Ln('XLA Ops', [
        Ev('fusion.1', 0, span * 0.5)])])])
  monkeypatch.setattr(run, 'traced', fake_traced)
  line = drive(root, 31, trace_on=True)
  with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
    want = {m['name'] for m in json.load(f)['per_layer']
            if CELL in m['workloads']}
  assert len(want) == 13
  # the CPU reports no memory peak: that reader finds nothing and the
  # metric is left out rather than read as 0; so is the p90 of a window
  # under ten steps (a loaded CPU's)
  absent = {'peak_hbm_gb'}
  if line['window']['steps'] < 10:
    absent.add('loader_step_p90_ms')
  assert set(line['metrics']) == want - absent
  assert line['correct'] is True
  value = lambda name: line['metrics'][name]['value']
  assert 0 < value('batch_row_fill_share') <= 100
  assert 0 < value('batch_edge_fill_share') <= 100
  assert 0 < value('train_step_mfu') < 100
  assert 0 < value('gather_hbm_share') < 100
  assert value('in_window_compiles') == 0


def test_controls_and_faults_fail_the_shipped_limits(root):
  """The limits the cell ships with pass the program and fail the
  reference in bfloat16 and in float8, half of the batch left out and
  a state left unchanged."""
  spec = run.load_cell(root, CELL)
  with run.matmul_precision(spec['cfg']):
    got = limits.read_seed(spec, 9, True, [])
  fails = lambda gaps: [k for k, v in gaps.items()
                        if v > spec['limits'][k]]
  assert set(got['program']) == set(spec['limits'])
  assert fails(got['program']) == []
  assert fails(got['reference_bfloat16'])
  assert fails(got['reference_float8_e4m3'])
  assert fails(got['fault_half_batch'])
  assert set(fails(got['fault_state_unchanged'])) == {'grad_gap',
                                                      'delta_gap'}


def test_the_configuration_states_every_published_width_uncut():
  with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  entry = {c['name']: c for c in bench['configs']}['rgat-igbh']
  assert entry['reduced'] == ['num_nodes'] and len(entry['source']) <= 200
  cell = {w['name']: w for w in bench['workloads']}[CELL]
  assert (cell['config'], cell['traffic'], cell['chips']) == (
      'rgat-igbh', MIX, 1)
  assert len(cell['why']) <= 200
  with open(os.path.join(REPO, entry['file'])) as f:
    cfg = json.load(f)
  pub = cfg['published']
  for key in ('feature_dim', 'hidden', 'heads', 'num_layers', 'fanout',
              'classes'):
    assert cfg[key] == pub[key], key
  assert (cfg['feature_dim'], cfg['hidden'], cfg['heads'],
          cfg['head_dim'], cfg['num_layers'], cfg['fanout'],
          cfg['classes']) == (1024, 512, 4, 128, 3, [15, 10, 5], 2983)
  assert cfg['heads'] * cfg['head_dim'] == cfg['hidden']
  assert cfg['optimizer']['lr'] == pub['lr'] == 0.001
  assert sorted(cfg['node_types']) == sorted(cfg['num_nodes'])
  assert len(cfg['node_types']) == 4 and cfg['num_relations'] == 7
  assert cfg['architecture'] is None and cfg['builder'] == 'igbh'
  assert cfg['reduced'] == ['num_nodes']
  assert cfg['num_nodes'] == {k: v for k, v in
                              pub['igbh_small_num_nodes'].items()
                              if k != 'all'}
  assert sum(cfg['num_nodes'].values()) == 3131266
  assert pub['igbh_full_num_nodes']['all'] == 547306935
  assert cfg['precision']['table'] == 'bfloat16'
  assert cfg['precision']['matmul'] == 'highest'
  assert cfg['traffic'] == {MIX: {'batch': 32}}
  # the batch is a cut too: stated beside `reduced`, with what forces it
  cut = cfg['cut_beside_reduced']['batch']
  assert cut['run'] == 32 and cut['published'] and cut['forced_by']
  # resident on the chip: the four tables alone are over 4 GB
  assert 2 * cfg['feature_dim'] * sum(cfg['num_nodes'].values()) > 4e9
  for key in ('num_nodes', 'hyperparameters', 'degrees', 'topology',
              'labels_and_features', 'weights', 'table_dtype',
              'departures', 'batch', 'train_fraction'):
    assert cfg['assumed'][key], key
  # the seven stored relations, by the builder's own reading
  from chipbench import load_file
  build = load_file(os.path.join(REPO, 'chipbench', 'builders',
                                 'igbh_build.py'))
  stored = build.stored_relations(cfg)
  assert len(stored) == len(set(stored)) == cfg['num_relations']
  assert build.layer_dims(cfg) == [1024, 512, 512]


def test_bad_fanout_is_counted_hop_by_hop():
  """A target's in-edges are held to the fanout of the hop whose block
  of edge slots they lie in, not to the widest fanout."""
  import jax.numpy as jnp
  from chipbench import load_file
  ref = load_file(os.path.join(REPO, 'chipbench', 'builders',
                               'igbh_reference.py'))
  # stored relation (b -> a): node 0 of b has neighbours 0..5 of a
  indptr = jnp.asarray([0, 6, 6], jnp.int32)
  indices = jnp.arange(6, dtype=jnp.int32)
  node_a, node_b = jnp.arange(6, dtype=jnp.int32), jnp.arange(2, dtype=jnp.int32)
  src = jnp.asarray([0, 1, 2, 3, 4, 5, -1, -1], jnp.int32)
  dst = jnp.where(src >= 0, 0, -1)
  check = lambda ends, fanouts: tuple(int(v) for v in ref.check_relation(
      indptr, indices, node_a, node_b, src, dst, src >= 0, ends=ends,
      fanouts=fanouts))
  # six in-edges in the first hop's block, whose fanout is 8: sound
  assert check((8, 8), (8, 2)) == (0, 0)
  # the same six in the second hop's block, whose fanout is 2: one
  # target over its hop's fanout, though under the widest
  assert check((0, 8), (8, 2)) == (0, 1)
  # split over two blocks of fanout 3: three and three, sound
  assert check((3, 8), (3, 3)) == (0, 0)
  # an edge the CSR does not hold
  assert check((8, 8), (8, 2))[0] == 0
  indices_short = jnp.asarray([0, 1, 2, 3, 4, 4], jnp.int32)
  assert int(ref.check_relation(indptr, indices_short, node_a, node_b, src,
                                dst, src >= 0, ends=(8, 8),
                                fanouts=(8, 2))[0]) == 1


def test_the_plan_at_the_configurations_counts():
  """The typed capacities the cell runs at (PERF.md section 4, the
  configuration's arithmetic), from the sampler's own plan."""
  from graphlearn_tpu.sampler.hetero_neighbor_sampler import (
      _plan, normalize_fanouts, typed_hop_capacities)
  from chipbench import load_file
  build = load_file(os.path.join(REPO, 'chipbench', 'builders',
                                 'igbh_build.py'))
  spec = run.load_cell(REPO, CELL)
  cfg, batch = spec['cfg'], int(spec['traffic']['batch'])
  etypes, fanouts, hops = normalize_fanouts(
      tuple(build.stored_relations(cfg)), list(cfg['fanout']))
  node, edge = typed_hop_capacities(etypes, _plan(
      etypes, fanouts, {cfg['target']: batch}, hops,
      dict(cfg['num_nodes'])))
  assert batch == 32
  assert {t: c[-1] for t, c in node} == dict(
      paper=134912, author=101280, fos=77280, institute=14752)
  assert dict(node)['paper'][0] == batch
  assert len(edge) == cfg['num_relations']
  assert sum(e[-1] for _, e in edge) == 342240
  assert sum(e[-1] - e[-2] for _, e in edge) == 312000


def test_grad_gap_holds_the_best_of_the_steps_gradients():
  """One step's gradient may sit on the other side of a ReLU's kink;
  what is wrong in the program is wrong in every step."""
  import numpy as np
  from chipbench import load_file
  ref = load_file(os.path.join(REPO, 'chipbench', 'builders',
                               'igbh_reference.py'))
  rng = np.random.default_rng(0)
  hyper = dict(b1=0.9)
  grads = [[rng.normal(size=(6, 4)), rng.normal(size=(4,))]
           for _ in range(3)]
  # Adam's first moment after each step, as the program leaves it
  mus, mu = [], [np.zeros((6, 4)), np.zeros((4,))]
  for g in grads:
    mu = [0.9 * m + 0.1 * a for m, a in zip(mu, g)]
    mus.append(dict(w=mu[0].astype(np.float32),
                    b=mu[1].astype(np.float32)))
  w0 = dict(w=np.zeros((6, 4), np.float32), b=np.zeros(4, np.float32))
  w3 = dict(w=np.ones((6, 4), np.float32), b=np.ones(4, np.float32))
  losses, got, delta = ref.program_record([1.0, 0.9, 0.8], w0, mus, w3,
                                          hyper)
  # leaves in the tree's order: b, then w
  for g, want in zip(got, grads):
    np.testing.assert_allclose(g[0], want[1], atol=1e-6)
    np.testing.assert_allclose(g[1], want[0], atol=1e-6)
  sound = (losses, got, delta)
  assert ref.gaps(sound, sound)['grad_gap'] == 0
  scaled = lambda g, k: [a * k for a in g]
  one = (losses, [scaled(got[0], 1.01), got[1], got[2]], delta)
  assert ref.gaps(one, sound)['grad_gap'] == 0
  every = (losses, [scaled(g, 1.01) for g in got], delta)
  assert ref.gaps(every, sound)['grad_gap'] == pytest.approx(0.01,
                                                             rel=1e-3)
  assert set(ref.gaps(sound, sound)) == {'loss_gap', 'loss1_gap',
                                         'grad_gap', 'delta_gap'}


def test_the_reference_imports_nothing_of_the_package():
  with open(os.path.join(REPO, 'chipbench', 'builders',
                         'igbh_reference.py')) as f:
    source = f.read()
  imports = [line for line in source.splitlines()
             if line.startswith(('import ', 'from '))]
  assert imports and not any('graphlearn_tpu' in line for line in imports)
  assert 'Precision.HIGHEST' in source


class _Recorded:
  """A loader, a step and losses that write down what is asked of them
  and when: what `ahead_window` is held to."""
  batch = 8

  def __init__(self, losses, per_epoch=3):
    self.log, self.losses, self.per_epoch = [], losses, per_epoch
    self.drawn, self.state = 0, 0
    self.loader, self.it = self, iter(())

  def __iter__(self):
    for _ in range(self.per_epoch):
      self.log.append(('next', self.drawn))
      self.drawn += 1
      yield self.drawn - 1

  def step(self, state, n):
    self.log.append(('dispatch', n))
    stub = self

    class Loss:
      def __float__(self):
        stub.log.append(('pull', n))
        return stub.losses[n]
    return state + 1, Loss(), None


class _Clock:
  """A second passes at every reading."""

  def __init__(self):
    self.now = 0.0

  def perf_counter(self):
    self.now += 1.0
    return self.now


@pytest.fixture
def igbh(monkeypatch):
  from chipbench import load_file
  mod = load_file(os.path.join(REPO, 'chipbench', 'builders', 'igbh.py'))
  monkeypatch.setattr(mod, 'time', _Clock())
  return mod


def _held_to_the_loop(drv, res, steps):
  """Dispatch n, next n+1, pull n; every loss once and in order; never
  more than one batch ahead; every batch drawn is stepped and counted,
  and the last step draws nothing."""
  want = [('next', 0)]
  for n in range(steps):
    want += [('dispatch', n), ('next', n + 1), ('pull', n)]
  del want[-2]
  assert drv.log == want
  assert res['steps'] == steps and res['seeds'] == steps * drv.batch
  assert drv.drawn == steps and drv.state == steps
  assert len(res['loader_wait_s']) == len(res['step_s']) == steps
  assert sum(res['step_s']) == res['wall_s']
  ahead = 0
  for what, _ in drv.log:
    ahead += dict(next=1, dispatch=-1, pull=0)[what]
    assert 0 <= ahead <= 1
  assert ahead == 0
  pulls = [n for what, n in drv.log if what == 'pull']
  assert pulls == sorted(set(pulls)) == list(range(steps))


def test_the_window_draws_one_batch_ahead_and_pulls_every_loss(igbh):
  drv = _Recorded([1.0, float('nan'), 2.0, float('inf'), 1.0, 1.0, 1.0])
  # the clock is read twice before the first dispatch, three times in
  # a step that draws and twice in one that does not: a step takes
  # 3 s, the fourth is dispatched 11 s in and draws (14 < 16.5), the
  # fifth 14 s in and does not (17 >= 16.5)
  res = igbh.ahead_window(drv, 16.5)
  _held_to_the_loop(drv, res, 5)
  assert res['failed'] == 2
  assert res['wall_s'] == 15.0
  assert res['loader_wait_s'] == [1.0] * 5
  assert res['step_s'] == [4.0, 3.0, 3.0, 3.0, 2.0]


def test_a_batch_drawn_behind_a_late_step_is_stepped_and_ends_the_window(
    igbh, monkeypatch):
  """The fourth step is dispatched 11 s in and the shortest step so
  far (3 s) would end it short of 14.5 s: it draws.  Its pull comes
  4 s late, past 14.5 s; the batch it drew is stepped, draws nothing,
  and its pull ends the window."""
  clock = igbh.time
  drv = _Recorded([1.0] * 6, per_epoch=6)
  pull = drv.step

  def step(state, n):
    state, loss, aux = pull(state, n)
    if n == 3:
      late = type(loss).__float__

      def __float__(self):
        clock.now += 4.0
        return late(self)
      loss = type('Late', (type(loss),), {'__float__': __float__})()
    return state, loss, aux
  monkeypatch.setattr(drv, 'step', step)
  res = igbh.ahead_window(drv, 14.5)
  _held_to_the_loop(drv, res, 5)
  assert res['step_s'] == [4.0, 3.0, 3.0, 7.0, 2.0]
  assert res['wall_s'] == 19.0


def test_a_window_of_no_seconds_counts_one_step_and_draws_once(igbh):
  drv = _Recorded([0.5, 0.5])
  res = igbh.ahead_window(drv, 0.0)
  assert drv.log == [('next', 0), ('dispatch', 0), ('pull', 0)]
  assert (res['steps'], res['seeds'], res['failed']) == (1, 8, 0)


def test_the_driver_refuses_a_mix_that_asks_for_another_depth(igbh):
  spec = run.load_cell(REPO, CELL)
  assert spec['traffic']['driver'] == 'loader-ahead'
  assert spec['traffic']['ahead'] == 1
  with pytest.raises(ValueError, match='one batch ahead'):
    igbh.DRIVERS['loader-ahead'](spec['cfg'],
                                 dict(spec['traffic'], ahead=2), 1)
