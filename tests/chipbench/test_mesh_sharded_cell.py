"""CPU rehearsal of the cell `sage-papers100m-p4.train-fused` (ISSUE 34):
the cell's own files — `chipbench/builders/mesh_sharded*.py`, its
configuration, limits and metrics — cut to toy size here and run on
four virtual devices through the harness's own `run.run_cell` and
`limits.read_seed`.  Nothing read here is a device number; the same
files at the configuration's sizes are what the driver runs on the
four chips.
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
from chipbench import drivers, limits, run

CELL = 'sage-papers100m-p4.train-fused'
CONFIG = 'sage-papers100m-p4'
FAKE_TPU = dict(platform='cpu', kind='TPU v5 lite', count=4)
BATCH = 16
#: `cellroot.TINY` sizes the graph and the model; a toy graph's
#: per-device edge counts wander by a few percent, so its margin is wide
TOY_CAPACITY = dict(margin=0.2, multiple=8)
#: the cell's by-layer metrics: `layer_scopes/`' reader over the
#: window's own trace
BY_LAYER = {f'{layer}_device_ms_per_step.mesh'
            for layer in ('sample', 'gather', 'model', 'exchange')}


@pytest.fixture(scope='module')
def root(tmp_path_factory):
  root = cellroot.make_tiny_root(
      str(tmp_path_factory.mktemp('papers') / 'root'))
  path = os.path.join(root, 'chipbench', 'configs', CONFIG + '.json')
  with open(path) as f:
    cfg = json.load(f)
  cfg['edge_capacity'] = TOY_CAPACITY
  with open(path, 'w') as f:
    json.dump(cfg, f)
  return root


def drive(root, seed, trace_on=False):
  return run.run_cell(root, CELL, seed, 0.3, trace_on, FAKE_TPU,
                      time.perf_counter())


def test_the_configuration_states_its_sizes_and_its_cut():
  with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  entry = {c['name']: c for c in bench['configs']}[CONFIG]
  cell = {w['name']: w for w in bench['workloads']}[CELL]
  assert cell['chips'] == 4 and cell['traffic'] == 'train-fused'
  assert sum(w['chips'] == 4 for w in bench['workloads']) == 1
  cfg = run.load_cell(REPO, CELL)['cfg']
  assert entry['reduced'] == cfg['reduced'] == ['num_nodes']
  assert cfg['published'] == dict(num_nodes=111059956, edges=1615685872,
                                  feature_dim=128, classes=172)
  # no width is cut
  assert (cfg['feature_dim'], cfg['classes'], cfg['hidden'],
          cfg['num_layers'], cfg['fanout']) == (128, 172, 256, 3,
                                                [15, 10, 5])
  n, p = cfg['num_nodes'], cfg['chips']
  assert n % p == 0 and 3 * n < cfg['published']['num_nodes'] < 3 * n + 12
  # the stated capacity is what the builder computes from the stated
  # margin, and a device's share of the table is over a quarter of a chip
  from chipbench import beside
  build = beside(os.path.join(REPO, 'chipbench', 'builders',
                              'mesh_sharded.py'), 'mesh_sharded_build')
  cap = build.edge_capacity(cfg)
  assert cap == cfg['edge_capacity']['per_device']
  assert cap > n * cfg['avg_degree'] / p
  assert 4 * (n // p) * cfg['feature_dim'] + 4 * cap > 0.25 * 16e9
  for key in ('deployment', 'assumed', 'cut', 'precision', 'optimizer'):
    assert cfg[key]
  assert cfg['traffic']['train-fused']['steps_per_dispatch'] == 8


def test_the_cell_runs_and_proves_correct(root):
  line = drive(root, 2 ** 31 + 34)
  assert line['correct'] is True and line['failed'] == 0
  assert line['in_window_compiles'] == 0
  assert set(line['metrics']) == {'train_seeds_per_s', 'setup_s'}
  spec = run.load_cell(root, CELL)
  # every limit has a number, and every number a limit
  assert set(line['checks']) == set(spec['limits'])
  for name, (value, limit) in line['checks'].items():
    assert value is not None and limit is not None and value <= limit, name
  win = line['window']
  assert win['seeds'] == 4 * BATCH * win['steps']
  assert win['shard_build_s'] > 0


def test_traced_run_reports_every_metric_the_cell_lists(root, monkeypatch):
  Ev = collections.namedtuple('Ev', 'name start_ns duration_ns')
  Ln = collections.namedtuple('Ln', 'name events')
  Pl = collections.namedtuple('Pl', 'name lines')
  Pr = collections.namedtuple('Pr', 'planes')

  def fake_traced(fn, where=None):
    t0 = time.perf_counter_ns()
    out = fn()
    span = time.perf_counter_ns() - t0
    ops = [Ev('fusion.1', 0, span * 0.5),
           Ev('all-to-all.2', span * 0.6, span * 0.1)]
    return out, Pr([Pl(f'/device:TPU:{d}', [Ln('XLA Ops', ops)])
                    for d in range(4)])
  monkeypatch.setattr(run, 'traced', fake_traced)
  line = drive(root, 34, trace_on=True)
  want = {'exchange_collective_share', 'exchange_padding_share',
          'shard_build_s', 'train_step_mfu', 'device_idle_share',
          'peak_hbm_gb', 'in_window_compiles'}
  with open(os.path.join(root, 'BENCHMARK.json')) as f:
    listed = {m['name'] for m in json.load(f)['per_layer']
              if CELL in m['workloads']}
  assert listed == want | BY_LAYER
  # the CPU reports no memory peak, and this stand-in of a trace has no
  # scoped op: those readers find nothing and the metrics are left out
  # rather than read as 0
  assert set(line['metrics']) == want - {'peak_hbm_gb'}
  value = lambda name: line['metrics'][name]['value']
  assert value('shard_build_s') == line['window']['shard_build_s']
  assert 0 < value('exchange_padding_share') < 100
  assert value('exchange_collective_share') == pytest.approx(
      100 * 0.1 / 0.6, rel=1e-3)
  assert value('in_window_compiles') == 0
  assert value('train_step_mfu') > 0
  assert line['correct'] is True


def test_traced_run_reads_the_layers_from_the_windows_own_trace(
    root, monkeypatch):
  """The by-layer metrics of the cell: the reader that waits in
  `layer_scopes/`, found where it waits, over the trace the window
  left in ``ctx['trace_dir']`` (a hand-made one, laid out as the TPU
  runtime writes it: the CPU writes no device plane)."""
  import test_scope_reader as waiting
  profile = waiting.scoped_profile()
  data = waiting.as_the_runtime_writes_it(profile)

  def fake_traced(fn, where=None):
    out = fn()
    sub = os.path.join(where, 'plugins', 'profile', 'run')
    os.makedirs(sub)
    with open(os.path.join(sub, 'host.xplane.pb'), 'wb') as f:
      f.write(data)
    return out, profile
  monkeypatch.setattr(run, 'traced', fake_traced)
  line = drive(root, 35, trace_on=True)
  steps = line['window']['steps']
  got = {k: v['value'] for k, v in line['metrics'].items() if k in BY_LAYER}
  # `scoped_profile`'s sums by hand, in ns
  assert got == {'sample_device_ms_per_step.mesh': 340 / 1e6 / steps,
                 'gather_device_ms_per_step.mesh': 250 / 1e6 / steps,
                 'model_device_ms_per_step.mesh': 310 / 1e6 / steps,
                 'exchange_device_ms_per_step.mesh': 25 / 1e6 / steps}
  assert line['correct'] is True


def test_the_waiting_exchange_metrics_are_the_benchmarks_byte_for_byte():
  """`exchange_collective_share` and `exchange_padding_share` exist
  twice until a `benchmark` PR deletes the waiting copies
  (`conftest.py`): the two may not drift."""
  for name in ('exchange_collective_share', 'exchange_padding_share'):
    with open(os.path.join(REPO, 'chipbench', 'layer_metrics',
                           name + '.json'), 'rb') as a, \
        open(os.path.join(REPO, 'tests', 'chipbench', 'mesh_cell',
                          'layer_metrics', name + '.json'), 'rb') as b:
      assert a.read() == b.read(), name


def test_the_precision_controls_and_the_faults_fail_the_shipped_limits(
    root):
  """The limits the cell ships with, read the way `chipbench.limits`
  reads them on the chips: they pass the program and fail the three
  precision controls a CPU can run — the program's own bfloat16 path,
  the reference in bfloat16 and in float8 put in the program's place —
  and the faults: half of every batch left out, the exchange left out,
  a state left unchanged.  (`high` and `default` only differ from
  `highest` on a TPU; PERF.md gives their readings.)"""
  spec = run.load_cell(root, CELL)
  with run.matmul_precision(spec['cfg']):
    got = limits.read_seed(spec, 9, True, ['bfloat16'])
  fails = lambda gaps: [k for k, v in gaps.items()
                        if v > spec['limits'][k]]
  assert set(got['program']) == set(spec['limits'])
  assert fails(got['program']) == []
  for control in ('program_bfloat16', 'reference_bfloat16',
                  'reference_float8_e4m3', 'fault_half_batch',
                  'fault_no_exchange'):
    assert fails(got[control]), control
  assert set(fails(got['fault_state_unchanged'])) == {'grad_gap',
                                                      'delta_gap'}


@pytest.mark.parametrize('fault', ['no_exchange', 'wrong_rows'])
def test_a_broken_timed_path_comes_out_not_correct(root, monkeypatch,
                                                   fault):
  if fault == 'no_exchange':
    import jax
    from graphlearn_tpu.parallel import dist_data
    real = jax.lax.all_to_all
    # the build's exchange stays; the epoch's is left out
    built = dist_data.shard_coo_on_mesh

    def build_with_exchange(*a, **kw):
      monkeypatch.setattr(jax.lax, 'all_to_all', real)
      try:
        return built(*a, **kw)
      finally:
        monkeypatch.setattr(
            jax.lax, 'all_to_all',
            lambda x, axis_name, split_axis, concat_axis, **k: x)
    monkeypatch.setattr(dist_data, 'shard_coo_on_mesh', build_with_exchange)
  else:
    # a table that is not the seed's: one device's shard shifted a row
    from graphlearn_tpu.parallel import dist_data
    real = dist_data.shard_rows_on_mesh

    def shifted(source, *a, **kw):
      import jax.numpy as jnp
      out = real(source, *a, **kw)
      return jnp.roll(out, 1, axis=1) if out.ndim == 3 else out
    monkeypatch.setattr(dist_data, 'shard_rows_on_mesh', shifted)
  line = drive(root, 5)
  assert line['correct'] is False
  failed = [k for k, (v, lim) in line['checks'].items() if not v <= lim]
  assert ('bad_rows' in failed) if fault == 'wrong_rows' else failed


def test_a_tree_without_the_constructor_fails_before_anything_is_built(
    root, monkeypatch):
  """The parent's tree under this cell's files: the driver asks the
  program for `DistDataset.from_device_coo` first and exits non-zero,
  with nothing drawn."""
  from chipbench import beside
  from graphlearn_tpu.parallel import DistDataset
  spec = run.load_cell(root, CELL)
  build = beside(os.path.join(spec['builders_dir'], 'mesh_sharded.py'),
                 'mesh_sharded_build')
  monkeypatch.delattr(DistDataset, 'from_device_coo')
  monkeypatch.setattr(build, 'dataset', lambda *a, **k: pytest.fail(
      'data was built'))
  with pytest.raises(SystemExit) as e:
    drivers.make(spec['cfg'], spec['traffic'], 1,
                 builders_dir=spec['builders_dir'])
  assert e.value.code not in (0, None)
  assert 'from_device_coo' in str(e.value)


def test_a_second_seed_adds_nothing_to_the_first_seeds_compile_cache(
    root, tmp_path):
  """One compile serves every seed: every program a run compiles — the
  shard build, the table's and the labels' shards, the epoch, the
  collect, the comparison's — takes the seed's keys, graph and shards
  as arguments, so a second seed finds each of them in the persistent
  cache the first seed filled."""
  import jax
  from jax.experimental.compilation_cache import compilation_cache as cc
  names = ('jax_compilation_cache_dir',
           'jax_persistent_cache_min_compile_time_secs',
           'jax_persistent_cache_min_entry_size_bytes')
  was = {name: getattr(jax.config, name) for name in names}
  cache = tmp_path / 'cache'
  cache.mkdir()
  for name, value in zip(names, (str(cache), 0.0, -1)):
    jax.config.update(name, value)
  cc.reset_cache()
  entries = lambda: sorted(f for f in os.listdir(cache)
                           if not f.endswith('-atime'))
  try:
    # the cache opens at the first compile: here, on this thread, and
    # not in a race between the driver's compile threads
    jax.jit(lambda x: x + 1)(1.0)
    assert drive(root, 101)['correct'] is True
    first = entries()
    assert drive(root, 2 ** 31 + 202)['correct'] is True
    second = entries()
  finally:
    for name, value in was.items():
      jax.config.update(name, value)
    cc.reset_cache()
  assert len(first) > 5
  assert second == first, sorted(set(second) - set(first))
