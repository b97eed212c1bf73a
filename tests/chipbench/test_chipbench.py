"""CPU rehearsal of the benchmark harness (`chipbench/`).

Everything here runs the harness's own code at a tiny size on the
virtual CPU devices: nothing it reads is a device number.  The same
files, at the sizes `BENCHMARK.json` names, are what the driver runs
on the chip.
"""
import collections
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
  sys.path.insert(0, REPO)

import cellroot
from chipbench import (build, drivers, limits, readers, reference, run,
                       trace, yardstick)

FAKE_TPU = dict(platform='cpu', kind='TPU v5 lite', count=1)
CELLS = ['sage-products.train-fused', 'sage-products.train-loader',
         'sage-products-p4.train-fused']
#: a configuration the harness has never heard of (two node types,
#: three relations, RGCN), with a driver, a data builder and a plain
#: reference of its own in `foreign_cell/builders/`
FOREIGN = 'rgcn-toy.train-typed'


def tiny_root(tmp_path) -> str:
  return cellroot.make_tiny_root(str(tmp_path / 'root'))


def cell_root(tmp_path, workload) -> str:
  """`tiny_root`, with the foreign cell added where it is asked for
  (its files are toy-sized as they wait)."""
  root = tiny_root(tmp_path)
  if workload == FOREIGN:
    cellroot.add_cell(root, cellroot.FOREIGN_CELL)
  return root


def drive(root, workload, seed=3, trace_on=False, seconds=0.3):
  return run.run_cell(root, workload, seed, seconds, trace_on, FAKE_TPU,
                      time.perf_counter())


Ev = collections.namedtuple('Ev', 'name start_ns duration_ns')
Ln = collections.namedtuple('Ln', 'name events')
Pl = collections.namedtuple('Pl', 'name lines')
Pr = collections.namedtuple('Pr', 'planes')


def hand_profile():
  """Two devices over a 1000 ns window.  Device 0: ops over [0,400)
  and [300,500) (union 500), an all-to-all over [600,700) and its
  twin over [650,750) (union 150): busy 650, collective 150.  Device
  1: one op over [0,200)."""
  d0 = [Ev('fusion.1', 0, 400), Ev('copy.2', 300, 200),
        Ev('all-to-all.3', 600, 100), Ev('all-to-all.4', 650, 100)]
  d1 = [Ev('fusion.1', 0, 200)]
  host = [Ev('chipbench.dispatch', 0, 1000), Ev('chipbench.pull', 500, 90),
          Ev('other', 0, 1000)]
  return Pr([Pl('/device:TPU:0', [Ln('XLA Ops', d0), Ln('Steps', d1)]),
             Pl('/device:TPU:1', [Ln('XLA Ops', d1)]),
             Pl('/host:CPU', [Ln('main', host)])])


# -- the last line -----------------------------------------------------------

@pytest.mark.parametrize('workload,seed', [
    (CELLS[0], 3), (CELLS[1], 2 ** 31 + 7), (CELLS[2], 12345),
    (FOREIGN, 2 ** 31 + 11)])
def test_driver_prints_a_well_formed_last_line(tmp_path, capsys,
                                               workload, seed):
  line = drive(cell_root(tmp_path, workload), workload, seed=seed)
  run.report(line)
  out, err = capsys.readouterr()
  last = json.loads(out.strip().splitlines()[-1])
  assert list(last)[:5] == ['correct', 'attempted', 'failed', 'metrics',
                            'device']
  assert list(last)[-1] == 'checks'
  assert last['correct'] is True and last['failed'] == 0
  assert last['attempted'] > 0
  assert set(last['metrics']) == {'train_seeds_per_s', 'setup_s'}
  for m in last['metrics'].values():
    assert m['value'] > 0 and m['unit']
  assert last['device']['kind'] == 'TPU v5 lite'
  # every number compared stands beside its limit, on stderr too
  for name, (value, limit) in last['checks'].items():
    assert value is not None and limit is not None and value <= limit
    assert f'chipbench check: {name} {value} limit {limit}' in err
  assert err.strip().splitlines()[-1].startswith('chipbench check:')


@pytest.mark.parametrize('workload', [CELLS[1], FOREIGN])
def test_traced_run_reports_the_per_layer_metrics(tmp_path, monkeypatch,
                                                  workload):
  def fake_traced(fn, where=None):
    t0 = time.perf_counter_ns()
    out = fn()
    span = time.perf_counter_ns() - t0
    ops = [Ev('fusion.1', 0, span * 0.5),
           Ev('all-to-all.2', span * 0.6, span * 0.1)]
    return out, Pr([Pl('/device:TPU:0', [Ln('XLA Ops', ops)])])
  monkeypatch.setattr(run, 'traced', fake_traced)
  root = cell_root(tmp_path, workload)
  line = drive(root, workload, trace_on=True)
  want = {m['name'] for m in json.load(open(os.path.join(
      root, 'BENCHMARK.json')))['per_layer'] if workload in m['workloads']}
  assert len(want) >= 5
  # the CPU reports no memory peak, so that reader finds nothing and
  # the metric is left out rather than read as 0
  assert set(line['metrics']) == want - {'peak_hbm_gb'}
  assert line['metrics']['in_window_compiles']['value'] == 0
  assert 0 < line['device']['busy_s'] < line['device']['window_s']
  assert line['metrics']['device_idle_share']['value'] == pytest.approx(
      40.0, abs=1.0)
  assert len(line['breakdown']['device_ops']) == 2
  assert line['correct'] is True


def test_main_on_a_found_device_prints_the_line_last(tmp_path, capsys,
                                                     monkeypatch):
  """`main` with the look for a chip stood in for: the runtime's
  start is taken out of `setup_s` and kept out of the line."""
  monkeypatch.setattr(run, 'ROOT', tiny_root(tmp_path))
  monkeypatch.setattr(run, 'enable_cache', lambda: None)
  monkeypatch.setattr(
      run, 'find_device',
      lambda chips: dict(FAKE_TPU, runtime_init_s=1000.0))
  monkeypatch.setattr(run, 'T_START', time.perf_counter() - 1000.0)
  run.main(['--workload', CELLS[0], '--seed', '4', '--seconds', '0.2',
            '--trace', '0'])
  out, err = capsys.readouterr()
  last = json.loads(out.strip().splitlines()[-1])
  assert last['correct'] is True
  assert 'runtime_init_s' not in last['device']
  assert 0 < last['metrics']['setup_s']['value'] < 600
  assert 'the runtime took 1000.0 to start' in err


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
  with pytest.raises(SystemExit) as e:
    run.main(['--workload', CELLS[0], '--seed', '1', '--seconds', '1'])
  assert e.value.code not in (0, None)
  assert capsys.readouterr().out == ''


def test_unknown_device_kind_is_an_error(tmp_path):
  with pytest.raises(KeyError, match='no published peak'):
    yardstick.peaks('TPU v9 imaginary')
  with pytest.raises(KeyError):
    run.run_cell(tiny_root(tmp_path), CELLS[0], 1, 0.1, False,
                 dict(FAKE_TPU, kind='cpu'), time.perf_counter())


# -- the trace reduction -----------------------------------------------------

def test_trace_reduction_against_a_hand_count():
  red = trace.reduce(hand_profile(), window_s=1000e-9)
  assert red['devices'] == 2
  assert red['busy_by_device']['/device:TPU:0'] == pytest.approx(650e-9)
  assert red['busy_by_device']['/device:TPU:1'] == pytest.approx(200e-9)
  assert red['busy_s'] == pytest.approx(425e-9)
  # idle on the busiest device: 1 - 650/1000
  assert red['idle_share'] == pytest.approx(35.0)
  # collectives on device 0: 150 of its 650 busy ns
  assert red['collective_share'] == pytest.approx(100 * 150 / 650)
  top = dict(red['breakdown']['device_ops'])
  assert top['fusion.1'] == pytest.approx(400e-9)
  # the longest gap, [500,600), falls in the middle of `pull`
  assert red['breakdown']['idle_gaps'][0] == [
      'chipbench.pull', pytest.approx(100e-9)]
  with pytest.raises(ValueError, match='no device op'):
    trace.reduce(Pr([Pl('/host:CPU', [])]), 1.0)


# -- the data files ----------------------------------------------------------

@pytest.mark.parametrize('with_mesh_cell', [False, True])
def test_every_layer_metric_names_a_reader_and_a_reported_metric(
    tmp_path, with_mesh_cell):
  root = tiny_root(tmp_path) if with_mesh_cell else REPO
  bench = json.load(open(os.path.join(root, 'BENCHMARK.json')))
  mdir = os.path.join(root, 'chipbench', 'layer_metrics')
  e2e = {m['name']: m for m in bench['end_to_end']}
  cells = [w['name'] for w in bench['workloads']]
  listed = {m['name'] for m in bench['per_layer']}
  assert listed == {f[:-5] for f in os.listdir(mdir)
                    if f.endswith('.json')}
  for m in bench['per_layer']:
    spec = json.load(open(os.path.join(mdir, m['name'] + '.json')))
    assert callable(readers.resolve(spec['reader'], mdir))
    for k in ('layer', 'unit', 'better', 'source', 'moves'):
      assert spec[k] == m[k], (m['name'], k)
    moved = e2e[m['moves']]
    for cell in m.get('workloads', cells):
      assert cell in cells
      assert cell in moved.get('workloads', cells)
  for w in bench['workloads']:
    spec = run.load_cell(root, w['name'])
    assert spec['per_layer'] and len(spec['end_to_end']) >= 2
    assert set(spec['limits']) >= {'loss1_gap', 'loss_gap', 'grad_gap',
                                   'delta_gap'}


def _same_model_cell(waiting: str, root: str) -> str:
  """A cell of the model the harness knows, at other numbers, with a
  reader of its own: the files and entries a PR would bring."""
  home = os.path.join(root, 'chipbench')
  for sub in ('configs', 'traffic', 'cells', 'layer_metrics'):
    os.makedirs(os.path.join(waiting, sub))
  cfg = json.load(open(os.path.join(home, 'configs',
                                    'sage-products.json')))
  cfg.update(name='sage-small', hidden=8, fanout=[2, 2, 2])
  json.dump(cfg, open(os.path.join(waiting, 'configs', 'sage-small.json'),
                      'w'))
  json.dump(dict(driver='fused', batch=8, steps_per_dispatch=3,
                 trace_seconds=0.2, probe_reps=1),
            open(os.path.join(waiting, 'traffic', 'train-short.json'),
                 'w'))
  json.dump(dict(limits=dict(loss_gap=1e-4, loss1_gap=1e-4, grad_gap=1e-4,
                             delta_gap=1e-3,
                             bad_edges=0, bad_fanout=0)),
            open(os.path.join(waiting, 'cells',
                              'sage-small.train-short.json'), 'w'))
  with open(os.path.join(waiting, 'layer_metrics', 'dispatches.py'),
            'w') as f:
    f.write('def read(ctx, scale):\n'
            '  return scale * ctx["window"]["dispatches"]\n')
  json.dump(dict(layer='loader', unit='count', better='higher',
                 source='program_counter', moves='train_seeds_per_s',
                 reader='dispatches', params=dict(scale=2)),
            open(os.path.join(waiting, 'layer_metrics',
                              'dispatches_x2.json'), 'w'))
  cell = 'sage-small.train-short'
  json.dump(dict(
      configs=[dict(name='sage-small', source='test', reduced=[],
                    why='test', file='chipbench/configs/sage-small.json')],
      workloads=[dict(name=cell, config='sage-small',
                      traffic='train-short', chips=1, why='test')],
      per_layer=[dict(name='dispatches_x2', unit='count', better='higher',
                      source='program_counter', layer='loader',
                      moves='train_seeds_per_s', workloads=[cell])],
      reports={cell: ['train_step_mfu']}),
      open(os.path.join(waiting, 'entries.json'), 'w'))
  return cell


def _files_under(*dirs):
  return {os.path.join(d, f): open(os.path.join(d, f), 'rb').read()
          for top in dirs for d, _, files in os.walk(top) for f in files
          if f != 'BENCHMARK.json' and not f.endswith('.pyc')}


@pytest.mark.parametrize('kind', ['same_model', 'foreign'])
def test_a_cell_is_added_by_new_files_and_entries_only(tmp_path, kind):
  """A PR adds a cell by files that were not there and entries
  appended to `BENCHMARK.json` (`cellroot.add_cell` does nothing else)
  — the model the harness knows at other numbers with a reader of its
  own, or a configuration with a driver, a data builder and a plain
  reference of its own — and the cell runs and proves correct with
  no byte of any data file of the root or of any python file of the
  harness changed."""
  root = tiny_root(tmp_path)
  before = _files_under(root, os.path.join(REPO, 'chipbench'))
  assert any(p.endswith('drivers.py') for p in before)
  if kind == 'foreign':
    waiting, cell = cellroot.FOREIGN_CELL, FOREIGN
  else:
    waiting = str(tmp_path / 'waiting')
    cell = _same_model_cell(waiting, root)
  cellroot.add_cell(root, waiting)
  line = drive(root, cell)
  assert line['correct'] is True
  assert line['metrics']['train_seeds_per_s']['value'] > 0
  spec = run.load_cell(root, cell)
  names = [m['name'] for m in spec['per_layer']]
  assert 'train_step_mfu' in names
  if kind == 'same_model':
    assert 'dispatches_x2' in names
    read = readers.resolve('dispatches', spec['metrics_dir'])
    assert read(dict(window=dict(dispatches=7)), scale=2) == 14
  after = _files_under(root, os.path.join(REPO, 'chipbench'))
  for p, data in before.items():
    assert after[p] == data, f'{p} was edited'
  brought = sorted(os.path.relpath(p, root) for p in set(after) - set(before))
  assert brought and all(p.startswith('chipbench' + os.sep)
                         for p in brought), brought
  if kind == 'foreign':
    assert [p for p in brought if p.endswith('.py')] == [
        f'chipbench/builders/{f}.py'
        for f in ('typed', 'typed_build', 'typed_reference')]
  with pytest.raises(FileExistsError):
    cellroot.add_cell(root, waiting)


def test_an_unknown_builder_names_the_file_it_looked_for(tmp_path):
  root = cell_root(tmp_path, FOREIGN)
  spec = run.load_cell(root, FOREIGN)
  make = lambda cfg, traffic: drivers.make(
      cfg, traffic, 1, builders_dir=spec['builders_dir'])
  with pytest.raises(SystemExit) as e:
    make(dict(spec['cfg'], builder='nowhere'), spec['traffic'])
  assert os.path.join(spec['builders_dir'], 'nowhere.py') in str(e.value)
  with pytest.raises(SystemExit) as e:
    make(spec['cfg'], dict(spec['traffic'], driver='fused'))
  assert 'typed.py' in str(e.value) and "['loader']" in str(e.value)


# -- the work counts ---------------------------------------------------------

def test_work_counts_against_a_hand_count():
  dims = [(3, 4), (4, 4), (4, 2)]
  # tree with 2 seeds, fanout [2, 2, 2]: levels 2, 4, 8, 16, all valid
  # layer 0 on levels 0..2 (14 rows): fwd 2 matmuls * 2*14*3*4 = 672,
  #   backward weight gradients only (features take none): x2 = 1344
  # layer 1 on levels 0..1 (6 rows): fwd 2*2*6*4*4 = 384, x3 = 1152
  # layer 2 on level 0 (2 rows): fwd 2*2*2*4*2 = 64, x3 = 192
  assert yardstick.tree_step_flops([2, 4, 8, 16], dims) == 1344 + 1152 + 192
  # a subgraph that reached 2, 3, 5, 7 new nodes per hop needs the
  # same layers over the nodes within 2, 1 and 0 hops: 10, 5, 2 rows
  assert yardstick.subgraph_step_flops([2, 3, 5, 7], dims) == (
      2 * (2 * 2 * 10 * 3 * 4) + 3 * (2 * 2 * 5 * 4 * 4)
      + 3 * (2 * 2 * 2 * 4 * 2))
  # 14 frontier nodes read 2 pointers each, 28 drawn ids read + written
  assert yardstick.sample_bytes([2, 4, 8], [4, 8, 16]) == (
      14 * 8 + 28 * 8)
  # 30 rows of 12 B: ids read, rows read and written
  assert yardstick.gather_bytes(30, 12) == 30 * (4 + 24)
  # 819e9 bytes in one second is the whole of the v5e's HBM peak
  assert yardstick.share(819e9, 1.0, yardstick.peaks(
      'TPU v5 lite')['hbm_bytes_per_s']) == pytest.approx(100.0)
  ctx = dict(window=dict(steps=10, wall_s=2.0), chips=4,
             work=dict(step_flops=197e12),
             peaks=yardstick.peaks('TPU v5 lite'))
  # 10 steps of one chip-second's FLOPs in 2 s on 4 chips: 125 %
  assert readers.step_mfu(ctx) == pytest.approx(125.0)
  assert readers.step_mfu(dict(ctx, work={})) is None


# -- the plain reference -----------------------------------------------------

def _tiny_tables(seed=5, n=400, dim=6):
  rng = np.random.default_rng(seed)
  feats = rng.random((n, dim), np.float32)
  labels = rng.integers(0, 4, n).astype(np.int32)
  cfg = dict(feature_dim=dim, hidden=8, classes=4, num_layers=3)
  return feats, labels, build.host_layers(cfg, seed), cfg


def test_reference_agrees_with_the_programs_models_and_optax():
  import jax
  import jax.numpy as jnp
  import optax
  from graphlearn_tpu.models import GraphSAGE, TreeSAGE
  feats, labels, layers, cfg = _tiny_tables()
  rng = np.random.default_rng(0)
  b, fan = 8, (3, 2, 2)
  levels, size = [], b
  for k in (1,) + fan:
    size *= k
    lv = rng.integers(0, 400, size).astype(np.int32)
    lv[rng.random(size) < 0.2] = -1
    levels.append(jnp.asarray(lv))
  levels[0] = jnp.asarray(np.abs(np.asarray(levels[0])))
  tree = dict(seeds=levels[0], levels=levels)
  n, e = 60, 150
  sub = dict(seeds=jnp.arange(b, dtype=jnp.int32) + 7,
             node=jnp.asarray(np.r_[np.arange(b) + 7,
                                    rng.integers(0, 400, n - b)],
                              jnp.int32),
             src=jnp.asarray(rng.integers(0, n, e), jnp.int32),
             dst=jnp.asarray(rng.integers(0, n, e), jnp.int32),
             edge_ok=jnp.asarray(rng.random(e) < 0.8))
  kw = dict(hidden_features=8, out_features=4, num_layers=3)
  hyper = dict(lr=3e-3, b1=0.9, b2=0.999, eps=1e-8)
  f, l = jnp.asarray(feats), jnp.asarray(labels)
  for kind, shard, model in (('tree', tree, TreeSAGE(**kw)),
                             ('subgraph', sub, GraphSAGE(**kw))):
    params = build.program_params(kind, layers)
    assert all(np.array_equal(a, b_) for la, lb in zip(
        build.layers_of(kind, params), layers) for a, b_ in zip(la, lb))

    def prog_loss(params):
      if kind == 'tree':
        xs = [reference.take_rows(f, lv) for lv in shard['levels']]
        logits = model.apply(params, xs, [lv >= 0 for lv in levels])
      else:
        logits = model.apply(
            params, reference.take_rows(f, shard['node']),
            jnp.stack([shard['src'], shard['dst']]),
            shard['edge_ok'])[:b]
      return optax.softmax_cross_entropy_with_integer_labels(
          logits, reference.take_rows(l, shard['seeds'])).mean()

    tx = optax.adam(3e-3)
    opt = tx.init(params)
    losses = []
    for _ in range(3):
      loss, g = jax.value_and_grad(prog_loss)(params)
      losses.append(float(loss))
      upd, opt = tx.update(g, opt, params)
      if len(losses) == 1:
        mu1 = build.layers_of(kind, opt[0].mu)
      params = optax.apply_updates(params, upd)
    prog = reference.program_record(
        losses, layers, None, mu1, build.layers_of(kind, params), hyper)
    ref = reference.follow(kind, layers, [[shard]] * 3, f, l, hyper)
    gaps = reference.gaps(prog, ref)
    assert gaps['loss_gap'] < 1e-6, (kind, gaps)
    assert gaps['grad_gap'] < 1e-5, (kind, gaps)
    assert gaps['delta_gap'] < 1e-4, (kind, gaps)


@pytest.mark.parametrize('workload', CELLS[:2] + [FOREIGN])
def test_controls_and_faults_fail_the_shipped_limits(tmp_path, workload):
  """The limits the cells ship with, read the way `chipbench.limits`
  reads them on the chip: they pass the program and fail (a) the
  program's own bfloat16 path, (b) the reference in float8 put in the
  program's place, (c) half of the batch left out, (d) a state left
  unchanged."""
  spec = run.load_cell(cell_root(tmp_path, workload), workload)
  with run.matmul_precision(spec['cfg']):
    got = limits.read_seed(spec, 9, True, ['bfloat16'])
  fails = lambda gaps: [k for k, v in gaps.items()
                        if v > spec['limits'][k]]
  assert set(got['program']) == set(spec['limits'])
  assert fails(got['program']) == []
  assert fails(got['program_bfloat16'])
  assert fails(got['reference_float8_e4m3'])
  assert fails(got['fault_half_batch'])
  assert set(fails(got['fault_state_unchanged'])) == {'grad_gap',
                                                      'delta_gap'}


# -- the timed path broken underneath ----------------------------------------

class _HalfBatches:
  """A seed batcher whose batches lose their second half."""

  def __init__(self, inner):
    self.inner = inner

  def __len__(self):
    return len(self.inner)

  def __iter__(self):
    for batch in self.inner:
      batch = np.array(batch)
      batch[len(batch) // 2:] = -1
      yield batch

  def __getattr__(self, name):
    return getattr(self.inner, name)


def _break(monkeypatch, fault, workload):
  from graphlearn_tpu.loader import fused as lfused
  from graphlearn_tpu.models import train
  from graphlearn_tpu.parallel import fused as pfused
  mesh = 'p4' in workload
  per_batch = 'loader' in workload or workload == FOREIGN
  cls = (pfused._MeshEpochDriver if mesh
         else lfused._SupervisedScanEpoch)
  real_run = cls.run
  if fault == 'state_unchanged' and per_batch:
    real = train.make_extracted_supervised_step

    def make(extract, tx, batch_size):
      step = real(extract, tx, batch_size)
      return lambda state, batch: (state,) + step(state, batch)[1:]
    monkeypatch.setattr(train, 'make_extracted_supervised_step', make)
  elif fault == 'state_unchanged':
    def run_(self, state):
      import jax
      kept = jax.tree_util.tree_map(lambda a: a + 0, state)
      return kept, real_run(self, state)[1]
    monkeypatch.setattr(cls, 'run', run_)
  elif fault == 'half_batch' and per_batch:
    real_loss = train.supervised_loss
    monkeypatch.setattr(
        train, 'supervised_loss',
        lambda logits, y, seeds, b: real_loss(
            logits[:b // 2], y[:b // 2], seeds[:b // 2], b // 2))
  elif fault == 'half_batch':
    def run_(self, state):
      kept = self._batcher
      self._batcher = _HalfBatches(kept)
      try:
        return real_run(self, state)
      finally:
        self._batcher = kept
    monkeypatch.setattr(cls, 'run', run_)
  elif fault == 'no_exchange':
    import jax
    monkeypatch.setattr(
        jax.lax, 'all_to_all',
        lambda x, axis_name, split_axis, concat_axis, **kw: x)
  else:
    raise ValueError(fault)


@pytest.mark.parametrize('workload,fault', [
    (CELLS[0], 'state_unchanged'), (CELLS[0], 'half_batch'),
    (CELLS[1], 'state_unchanged'), (CELLS[1], 'half_batch'),
    (CELLS[2], 'half_batch'), (CELLS[2], 'no_exchange'),
    (FOREIGN, 'state_unchanged'), (FOREIGN, 'half_batch')])
def test_a_broken_timed_path_comes_out_not_correct(tmp_path, monkeypatch,
                                                   workload, fault):
  _break(monkeypatch, fault, workload)
  line = drive(cell_root(tmp_path, workload), workload)
  assert line['correct'] is False
  failed = [k for k, (v, lim) in line['checks'].items() if not v <= lim]
  assert failed, line['checks']
