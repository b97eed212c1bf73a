"""CPU rehearsal of the cell `sage-products-link.train-fused` (ISSUE 38):
the cell is added to a benchmark root the way a PR adds one — new files
and appended entries only, through `cellroot.add_cell` — then cut to
toy size and run through the harness's own `run.run_cell` and
`limits.read_seed`.  Nothing read here is a device number; the same
files at the configuration's sizes are what the driver runs on the
chip.
"""
import collections
import json
import os
import shutil
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
  sys.path.insert(0, REPO)

import cellroot
import chipbench
from chipbench import limits, run

CELL = 'sage-products-link.train-fused'
CONFIG = 'sage-products-link'
#: what this cell brought, under `chipbench/`: new files only
FILES = ['configs/sage-products-link.json',
         'cells/sage-products-link.train-fused.json',
         'builders/link_fused.py', 'builders/link_fused_build.py',
         'builders/link_fused_reference.py',
         'layer_metrics/negative_device_ms_per_step.json',
         'layer_metrics/negative_hbm_share.json',
         'layer_metrics/scope_part_device_ms.py',
         'layer_metrics/scope_part_hbm_share.py']
NEW_METRICS = ['negative_device_ms_per_step', 'negative_hbm_share']
#: the metrics the benchmark had, whose `workloads` the cell joins
REPORTS = ['train_step_mfu', 'device_idle_share', 'peak_hbm_gb',
           'in_window_compiles', 'batch_row_fill_share',
           'batch_edge_fill_share']
FAKE_TPU = dict(platform='cpu', kind='TPU v5 lite', count=1)
TOY = dict(num_nodes=3000, avg_degree=6, feature_dim=12, hidden=16,
           fanout=[3, 2, 2],
           traffic={'train-fused': dict(batch=8, steps_per_dispatch=4,
                                        trace_seconds=0.3, probe_reps=1)})


def _bench():
  with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
    return json.load(f)


def _without_the_cell(bench):
  """`BENCHMARK.json` as it stood before the cell: its entries out."""
  bench = json.loads(json.dumps(bench))
  bench['configs'] = [c for c in bench['configs'] if c['name'] != CONFIG]
  bench['workloads'] = [w for w in bench['workloads'] if w['name'] != CELL]
  bench['per_layer'] = [m for m in bench['per_layer']
                        if m['name'] not in NEW_METRICS]
  for m in bench['per_layer']:
    m['workloads'] = [w for w in m['workloads'] if w != CELL]
  return bench


def _by_name(bench):
  """``BENCHMARK.json`` with every list of named entries (and every
  metric's ``workloads``) in an order of its own: the tests' four-chip
  cell is appended before this one, a PR's cells after it."""
  out = {}
  for key, value in bench.items():
    if isinstance(value, list) and value and isinstance(value[0], dict):
      value = {e['name']: dict(e, workloads=sorted(e['workloads']))
               if 'workloads' in e else e for e in value}
    out[key] = value
  return out


def _waiting(where):
  """The cell as a PR brings it: its files and `entries.json`."""
  bench = _bench()
  for rel in FILES:
    os.makedirs(os.path.join(where, os.path.dirname(rel)), exist_ok=True)
    shutil.copy(os.path.join(REPO, 'chipbench', rel),
                os.path.join(where, rel))
  entries = dict(
      configs=[c for c in bench['configs'] if c['name'] == CONFIG],
      workloads=[w for w in bench['workloads'] if w['name'] == CELL],
      per_layer=[m for m in bench['per_layer'] if m['name'] in NEW_METRICS],
      reports={CELL: REPORTS})
  with open(os.path.join(where, 'entries.json'), 'w') as f:
    json.dump(entries, f)
  return where


@pytest.fixture(scope='module')
def root(tmp_path_factory):
  """A benchmark root without the cell, the cell added by
  `cellroot.add_cell`, its configuration cut to `TOY`."""
  base = tmp_path_factory.mktemp('link')
  root = cellroot.make_root(str(base / 'root'))
  for rel in FILES:
    os.remove(os.path.join(root, 'chipbench', rel))
  parent = _without_the_cell(_bench())
  with open(os.path.join(root, 'BENCHMARK.json')) as f:
    with_mesh = json.load(f)
  # the tests' four-chip cell, which `make_root` added, stays
  with_mesh_parent = _without_the_cell(with_mesh)
  with open(os.path.join(root, 'BENCHMARK.json'), 'w') as f:
    json.dump(with_mesh_parent, f)
  cellroot.add_cell(root, _waiting(str(base / 'waiting')))
  with open(os.path.join(root, 'BENCHMARK.json')) as f:
    added = json.load(f)
  assert _by_name(added) == _by_name(with_mesh), (
      'the cell is more than new files and entries')
  assert parent['workloads'] == _bench()['workloads'][:-1]
  path = os.path.join(root, 'chipbench', 'configs', CONFIG + '.json')
  with open(path) as f:
    cfg = json.load(f)
  cfg.update(TOY)
  with open(path, 'w') as f:
    json.dump(cfg, f)
  return root


def drive(root, seed, trace_on=False):
  return run.run_cell(root, CELL, seed, 0.3, trace_on, FAKE_TPU,
                      time.perf_counter())


def test_the_configuration_states_the_flagship_widths_uncut():
  bench = _bench()
  entry = {c['name']: c for c in bench['configs']}[CONFIG]
  cell = {w['name']: w for w in bench['workloads']}[CELL]
  assert (cell['chips'], cell['traffic'], cell['config']) == (
      1, 'train-fused', CONFIG)
  spec = run.load_cell(REPO, CELL)
  cfg, traffic = spec['cfg'], spec['traffic']
  assert entry['reduced'] == cfg['reduced'] == ['num_nodes']
  assert (cfg['feature_dim'], cfg['hidden'], cfg['num_layers'],
          cfg['fanout'], cfg['num_nodes'], cfg['avg_degree']) == (
              100, 256, 3, [15, 10, 5], 9796116, 25)
  assert cfg['negatives'] == dict(mode='binary', amount=1.0, strict=True,
                                  trials=5, padding=True)
  assert (traffic['driver'], traffic['batch'],
          traffic['steps_per_dispatch']) == ('fused', 256, 32)
  # 1,024 endpoints a step: the flagship's seeds, the same capacities
  from graphlearn_tpu.sampler import NegativeSampling
  from graphlearn_tpu.sampler.neighbor_sampler import (hop_capacities,
                                                       link_plan)
  width = link_plan(NegativeSampling('binary', 1.0), 256)[3]
  assert width == 1024
  assert hop_capacities(width, (15, 10, 5), 937984) == (
      (1024, 16384, 169984, 937984), (15360, 168960, 936960))
  assert set(spec['limits']) >= {'loss1_gap', 'loss_gap', 'grad_gap',
                                 'delta_gap', 'bad_negatives'}


def test_the_cell_runs_and_proves_correct(root):
  line = drive(root, 2 ** 31 + 38)
  assert line['correct'] is True and line['failed'] == 0
  assert line['in_window_compiles'] == 0
  assert set(line['metrics']) == {'train_seeds_per_s', 'setup_s'}
  spec = run.load_cell(root, CELL)
  assert set(line['checks']) == set(spec['limits'])
  for name, (value, limit) in line['checks'].items():
    assert value is not None and value <= limit, name
  # seeds/s counts the positive edges: 8 a step
  assert line['window']['seeds'] == 8 * line['window']['steps']


Ev = collections.namedtuple('Ev', 'name start_ns duration_ns')
Ln = collections.namedtuple('Ln', 'name events')
Pl = collections.namedtuple('Pl', 'name lines')
Pr = collections.namedtuple('Pr', 'planes')
BODY = 'jit(_epoch_fn)/while/body/'


def _op(name, scope, start, dur):
  return Ev(f'%{name} = s32[8]{{0}} fusion(%p.1), '
            f'metadata={{op_name="{scope}"}}', start, dur)


def window_profile():
  """One device over 1000 ns: 100 ns of the strict draw (two ops), a
  hop, and a part whose name only begins like the draw's."""
  return Pr([Pl('/device:TPU:0', [Ln('XLA Ops', [
      _op('fusion.1', BODY + 'glt.sample/hop2/gather', 0, 400),
      _op('fusion.2', BODY + 'glt.sample/negative/jit(sample_negative)/lt',
          400, 60),
      _op('fusion.3', BODY + 'glt.sample/negative', 460, 40),
      _op('fusion.4', BODY + 'glt.sample/negatives/add', 500, 50),
      _op('fusion.5', BODY + 'jvp(GraphSAGE)/glt.model/layer0/dot', 550,
          300)])])])


def test_traced_run_reports_every_metric_the_cell_lists(root, monkeypatch):
  """The window's trace as the runtime writes it (the CPU writes no
  device plane): every metric the cell lists is on the line, the draw's
  device time read by its scope's part."""
  from test_scope_reader import as_the_runtime_writes_it
  xspace = chipbench.load_file(os.path.join(
      REPO, 'tests', 'chipbench', 'layer_scopes', 'layer_metrics',
      'xspace.py'))
  data = as_the_runtime_writes_it(window_profile())

  def fake_traced(fn, where=None):
    out = fn()
    sub = os.path.join(where, 'plugins', 'profile', 'run')
    os.makedirs(sub)
    with open(os.path.join(sub, 'host.xplane.pb'), 'wb') as f:
      f.write(data)
    return out, xspace.parse(data)
  monkeypatch.setattr(run, 'traced', fake_traced)
  line = drive(root, 38, trace_on=True)
  spec = run.load_cell(root, CELL)
  want = set(REPORTS) | set(NEW_METRICS)
  assert {m['name'] for m in spec['per_layer']} == want
  # the CPU reports no memory peak: that reader finds nothing and the
  # metric is left out rather than read as 0
  assert set(line['metrics']) == want - {'peak_hbm_gb'}
  value = lambda name: line['metrics'][name]['value']
  assert 0 < value('batch_row_fill_share') < 100
  assert 0 < value('batch_edge_fill_share') < 100
  assert 0 < value('train_step_mfu') < 100
  assert value('in_window_compiles') == 0
  steps = line['window']['steps']
  assert value('negative_device_ms_per_step') == pytest.approx(
      100e-6 / steps)
  assert value('negative_hbm_share') > 0
  assert line['correct'] is True


def test_the_parent_refuses_the_cell_at_once(root, monkeypatch):
  """A tree whose `FusedLinkEpoch` has no `batch_fill` (the parent's,
  whose link batches state no layout) exits non-zero before it builds
  anything."""
  from graphlearn_tpu.loader import FusedLinkEpoch
  monkeypatch.delattr(FusedLinkEpoch, 'batch_fill')
  t0 = time.perf_counter()
  with pytest.raises(SystemExit, match='batch_fill'):
    drive(root, 1)
  assert time.perf_counter() - t0 < 5


def test_controls_and_faults_fail_the_shipped_limits(root):
  """The limits the cell ships with pass the program and fail the
  model's bfloat16 path, the negatives made positive, half of the pairs
  left out and a state left unchanged."""
  spec = run.load_cell(root, CELL)
  with run.matmul_precision(spec['cfg']):
    got = limits.read_seed(spec, 9, True, ['bfloat16'])
  fails = lambda gaps: [k for k, v in gaps.items()
                        if v > spec['limits'][k]]
  assert set(got['program']) == set(spec['limits'])
  assert fails(got['program']) == []
  for control in ('program_bfloat16', 'fault_negatives_positive',
                  'fault_half_pairs'):
    assert fails(got[control]), control
  assert set(fails(got['fault_state_unchanged'])) == {'grad_gap',
                                                      'delta_gap'}
