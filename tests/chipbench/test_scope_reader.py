"""The by-layer reader that waits in `tests/chipbench/layer_scopes/`
(ISSUE 25): its rule of attribution on hand-made planes, that it
joins the benchmark by new files and new entries only, and that a
traced run hands it the window's trace (ISSUE 27)."""
import collections
import json
import os
import re
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
  sys.path.insert(0, REPO)

import cellroot
from chipbench import load_file, readers, run, trace

HERE = os.path.dirname(os.path.abspath(__file__))
WAITING = os.path.join(HERE, 'layer_scopes')

Ev = collections.namedtuple('Ev', 'name start_ns duration_ns stats',
                            defaults=((),))
Ln = collections.namedtuple('Ln', 'name events')
Pl = collections.namedtuple('Pl', 'name lines')
Pr = collections.namedtuple('Pr', 'planes')


def _waiting_module(name):
  return load_file(os.path.join(WAITING, 'layer_metrics', name + '.py'))


@pytest.fixture(scope='module')
def reader():
  return _waiting_module('scope_device_ms')


def hlo(name, shape, opcode, op_name=None):
  """A device event's name as the TPU runtime writes it: the whole
  HLO line."""
  meta = f', metadata={{op_name="{op_name}"}}' if op_name else ''
  return f'%{name} = {shape} {opcode}(%p.1, %p.2){meta}'


PRE = 'jit(_epoch_fn)/while/body/closed_call/'


def scoped_profile():
  """Device 0 (the busiest), 12 leaf events inside one `while` of
  2000 ns; two steps.  By hand: sample 300 (+ 40 dedup) = 340, gather
  200 + 50 = 250, model forward 100 + 60 = 160 and backward 120 + 30 =
  150, optimizer 70, exchange 25, unattributed 45 + 15 = 60: 1055."""
  d0 = [
      Ev(hlo('while.3', '(s32[], f32[4])', 'while'), 0, 2000),
      Ev(hlo('fusion.532', 's32[768000]{0}', 'fusion',
             PRE + 'glt.sample/hop2/jit(sample_one_hop)/gather'), 0, 300),
      Ev(hlo('sort.38', '(s32[9]{0}, s32[9]{0})', 'sort',
             PRE + 'glt.sample/dedup/sort'), 300, 40),
      Ev(hlo('fusion.533', 'f32[768000,100]{1,0}', 'fusion',
             PRE + 'glt.gather/level3/jit(_device_gather)/glt.gather/'
             'jit(_take)/gather'), 340, 200),
      Ev(hlo('copy.7', 'f32[9796116,100]{1,0}', 'copy',
             'jit(_device_gather)/glt.gather/jit(_take)/gather'),
         540, 50),
      Ev(hlo('fusion.8', 'f32[1024,256]{1,0}', 'fusion',
             PRE + 'jvp(TreeSAGE)/glt.model/layer0/layer0_self/'
             'dot_general'), 590, 100),
      Ev(hlo('fusion.9', 'f32[1024]{0}', 'fusion',
             PRE + 'jvp(glt.model/loss)/reduce_sum'), 690, 60),
      Ev(hlo('fusion.10', 'f32[100,256]{1,0}', 'fusion',
             PRE + 'transpose(jvp(TreeSAGE))/glt.model/layer0/'
             'layer0_neigh/dot_general'), 750, 120),
      Ev(hlo('fusion.11', 'f32[1024,47]{1,0}', 'fusion',
             PRE + 'transpose(jvp(glt.model/loss))/mul'), 870, 30),
      Ev(hlo('fusion.12', 'f32[256,256]{1,0}', 'fusion',
             PRE + 'glt.optimizer/sub'), 900, 70),
      # its stat names it where the HLO line does not
      Ev('%all-to-all.90 = f32[4,8,100]{2,1,0} all-to-all(%p.1)', 970,
         25, (('tf_op', 'shard_map/glt.exchange/feature/all_to_all'),)),
      Ev(hlo('fusion.13', 's32[]', 'fusion',
             'jit(_epoch_fn)/while/body/add'), 995, 45),
      Ev(hlo('copy.14', 'f32[4]{0}', 'copy'), 1040, 15),
  ]
  d1 = [Ev(hlo('fusion.1', 'f32[4]{0}', 'fusion',
               PRE + 'glt.optimizer/sub'), 0, 200)]
  modules = [Ev('jit__multihop_sample(123)', 0, 340),
             Ev('jit__device_gather(77)', 340, 250),
             Ev('jit_supervised_step(9)', 590, 380),
             Ev('jit__device_gather(77)', 1000, 10)]
  host = [Ev('fused.dispatch', 0, 2000)]
  return Pr([Pl('/device:TPU:0', [Ln('XLA Ops', d0),
                                  Ln('XLA Modules', modules)]),
             Pl('/device:TPU:1', [Ln('XLA Ops', d1)]),
             Pl('/host:CPU', [Ln('main', host)])])


def test_rule_of_attribution(reader):
  cases = {
      'jit(f)/while/body/glt.gather/level0/gather': 'gather.fwd',
      'jit(f)/jvp(glt.model/loss)/dot_general': 'model.fwd',
      'jit(f)/jvp(TreeSAGE)/glt.model/layer1/relu': 'model.fwd',
      'jit(f)/transpose(jvp(glt.model/loss))/mul': 'model.bwd',
      'jit(f)/transpose(jvp(TreeSAGE))/glt.model/layer0/mul': 'model.bwd',
      # the first token decides
      'jit(f)/glt.gather/level1/jit(g)/glt.gather/take': 'gather.fwd',
      'jit(f)/glt.sample/hop0/glt.exchange/x': 'sample.fwd',
      'jit(f)/glt.optimizer/sub': 'optimizer.fwd',
      'jit(f)/while/body/add': 'unattributed',
      '': 'unattributed',
  }
  for scope, want in cases.items():
    assert reader.classify(scope) == want, scope


def test_layers_add_up_to_the_leaf_op_time(reader):
  prof = scoped_profile()
  assert reader.busiest(prof) == '/device:TPU:0'
  totals = reader.by_layer(prof)
  assert totals == {
      'sample.fwd': 340.0, 'gather.fwd': 250.0, 'model.fwd': 160.0,
      'model.bwd': 150.0, 'optimizer.fwd': 70.0, 'exchange.fwd': 25.0,
      'unattributed': 60.0}
  # the container is left out, and nothing else is
  assert sum(totals.values()) == reader.leaf_ns(prof) == 1055.0
  assert trace.busy_ns(trace.device_ops(prof)['/device:TPU:0']) == 2000.0


def test_reader_values(reader):
  ctx = dict(profile=scoped_profile(), window=dict(steps=2))
  ms = lambda ns: ns / 1e6
  assert reader.read(ctx, 'sample') == ms(340) / 2
  assert reader.read(ctx, 'gather', per='window') == ms(250)
  assert reader.read(ctx, 'model') == ms(310) / 2
  assert reader.read(ctx, 'model.bwd') == ms(150) / 2
  assert reader.read(ctx, 'model.fwd') == ms(160) / 2
  assert reader.read(ctx, 'exchange') == ms(25) / 2
  assert reader.read(ctx, 'unattributed', per='share') == pytest.approx(
      100 * 60 / 1055)
  # a layer the trace has no op of reads 0 once other layers are there
  ctx1 = dict(ctx, profile=Pr(scoped_profile().planes[1:]))
  assert reader.read(ctx1, 'sample') == 0.0
  # by program, from the device's own module line
  assert reader.read(ctx, 'jit__device_gather', by='module') == ms(260) / 2
  assert reader.read(ctx, 'jit_supervised_step', per='share',
                     by='module') == pytest.approx(100 * 380 / 980)
  assert reader.read(ctx, 'jit_nothing', by='module') is None


def test_none_without_scopes_or_profile(reader):
  """The parent's programs carry no scope: the reader returns nothing
  (never 0) and does not raise, so the line leaves the metric out."""
  bare = Pr([Pl('/device:TPU:0', [Ln('XLA Ops', [
      Ev(hlo('fusion.1', 'f32[4]{0}', 'fusion', 'jit(f)/add'), 0, 100),
      Ev('fusion.2', 100, 50)])])])
  ctx = dict(profile=bare, window=dict(steps=1))
  for layer in ('sample', 'model.bwd', 'unattributed'):
    assert reader.read(ctx, layer) is None
    assert reader.read(ctx, layer, per='share') is None
  assert reader.read(dict(window=dict(steps=1)), 'sample') is None
  assert reader.read(dict(profile=scoped_profile(), window={}),
                     'sample') is None


def merged_root(tmp_path, make=cellroot.make_root) -> str:
  """The tests' root, then `layer_scopes/` laid over it the way the
  `benchmark` issue will: files copied, entries appended."""
  root = make(str(tmp_path / 'root'))
  cellroot.add_cell(root, WAITING)
  return root


WAITING_METRICS = {
    'sage-products.train-fused': 5, 'sage-products.train-loader': 5,
    'sage-products-p4.train-fused': 6}


@pytest.mark.parametrize('workload', sorted(WAITING_METRICS))
def test_the_waiting_metrics_join_by_new_files_and_entries(
    tmp_path, reader, workload):
  root = merged_root(tmp_path)
  spec = run.load_cell(root, workload)
  mine = [m for m in spec['per_layer']
          if m['reader'] == 'scope_device_ms']
  assert len(mine) == WAITING_METRICS[workload]
  ctx = dict(profile=scoped_profile(), window=dict(steps=2))
  values = {}
  for m in mine:
    read = readers.resolve(m['reader'], spec['metrics_dir'])
    values[m['name']] = read(ctx, **m['params'])
  assert values['sample_device_ms_per_step'] == 340 / 1e6 / 2
  assert values['unattributed_device_share'] == pytest.approx(
      100 * 60 / 1055)
  if 'p4' in workload:
    assert values['exchange_device_ms_per_step'] == 25 / 1e6 / 2
  # every entry is a whole per_layer entry of the contract's form
  with open(os.path.join(WAITING, 'entries.json')) as f:
    for e in json.load(f)['per_layer']:
      assert set(e) == {'name', 'unit', 'better', 'source', 'layer',
                        'moves', 'workloads'}
      assert e['source'] == 'device_trace'
      assert e['moves'] == 'train_seeds_per_s'


# -- the trace as the runtime writes it --------------------------------------

@pytest.fixture(scope='module')
def xspace():
  return _waiting_module('xspace')


def _varint(n):
  out = bytearray()
  while True:
    out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
    n >>= 7
    if not n:
      return bytes(out)


def _field(num, value):
  """One protobuf field: an int as a varint, bytes / str / a list of
  fields as a length-delimited value."""
  if isinstance(value, int):
    return _varint(num << 3) + _varint(value)
  if isinstance(value, str):
    value = value.encode()
  if isinstance(value, list):
    value = b''.join(value)
  return _varint(num << 3 | 2) + _varint(len(value)) + value


def test_xspace_reads_the_metadata_stats_profiledata_leaves_out(
    xspace, reader):
  """A device plane as the TPU runtime lays it out: the event's own
  stats hold only device times; the instruction's ``tf_op`` sits in
  the event METADATA's stats, once per instruction."""
  stat_meta = lambda i, name: _field(5, [_field(1, i), _field(
      2, [_field(1, i), _field(2, name)])])
  event_meta = _field(4, [_field(1, 7), _field(2, [
      _field(1, 7), _field(2, '%fusion.5 = f32[8]{0} fusion(%p)'),
      _field(5, [_field(1, 2),
                 _field(5, 'jit(f)/while/body/glt.gather/level0/gather')]),
      _field(5, [_field(1, 3), _field(7, 4)])])])    # a ref_value
  event = _field(4, [_field(1, 7), _field(2, 5_000_000),
                     _field(3, 2_000_000),
                     _field(4, [_field(1, 1), _field(3, 2500)])])
  line = _field(3, [_field(2, 'XLA Ops'), _field(3, 1000), event])
  plane = _field(1, [_field(2, '/device:TPU:0'), line, event_meta,
                     stat_meta(1, 'device_duration_ps'),
                     stat_meta(2, 'tf_op'), stat_meta(3, 'hlo_category'),
                     stat_meta(4, 'data formatting')])
  space = xspace.parse(plane + _field(1, [_field(2, '/host:CPU')]))
  assert [p.name for p in space.planes] == ['/device:TPU:0', '/host:CPU']
  (ev,) = space.planes[0].lines[0].events
  assert ev.name == '%fusion.5 = f32[8]{0} fusion(%p)'
  assert ev.start_ns == 1000 + 5000 and ev.duration_ns == 2000
  assert dict(ev.stats) == {
      'device_duration_ps': 2500,
      'tf_op': 'jit(f)/while/body/glt.gather/level0/gather',
      'hlo_category': 'data formatting'}
  # and the reader walks it as it walks a `ProfileData`
  assert reader.scope_of(ev).endswith('glt.gather/level0/gather')
  assert reader.by_layer(space) == {'gather.fwd': 2000.0}


def test_xspace_agrees_with_profiledata_on_a_real_trace(xspace,
                                                        tmp_path):
  """On a trace the CPU backend writes: the same planes, lines and
  events, name for name and nanosecond for nanosecond, and every stat
  `ProfileData` shows."""
  import glob
  import jax
  import jax.numpy as jnp
  f = jax.jit(lambda x: jnp.sort(x @ x.T, axis=0))
  x = jnp.ones((64, 64))
  f(x).block_until_ready()
  jax.profiler.start_trace(str(tmp_path))
  try:
    with jax.profiler.TraceAnnotation('loader.sample'):
      f(x).block_until_ready()
  finally:
    jax.profiler.stop_trace()
  mine = xspace.load(str(tmp_path))
  (path,) = glob.glob(str(tmp_path / '**' / '*.xplane.pb'), recursive=True)
  theirs = jax.profiler.ProfileData.from_file(path)
  assert [p.name for p in mine.planes] == [p.name for p in theirs.planes]
  seen = 0
  for a, b in zip(mine.planes, theirs.planes):
    assert [l.name for l in a.lines] == [l.name for l in b.lines]
    for la, lb in zip(a.lines, b.lines):
      eb = list(lb.events)
      assert len(la.events) == len(eb)
      for x, y in zip(la.events, eb):
        assert x.name == y.name
        assert x.start_ns == pytest.approx(y.start_ns, abs=1)
        assert x.duration_ns == pytest.approx(y.duration_ns, abs=1)
        theirs_stats = {k: v for k, v in y.stats}
        mine_stats = dict(x.stats)
        for k, v in theirs_stats.items():
          assert k in mine_stats
          if isinstance(v, (int, str)):
            assert mine_stats[k] == v
        seen += 1
  assert seen > 10
  names = {e.name for p in mine.planes for l in p.lines for e in l.events}
  assert 'loader.sample' in names


# -- through the harness: a traced run hands the reader the trace ------------

def as_the_runtime_writes_it(profile) -> bytes:
  """``profile`` (hand-made planes) as a serialized ``XSpace`` laid
  out as the TPU runtime lays it out: an event is named by its HLO
  line without the metadata, and the ``op_name`` sits in the stat
  ``tf_op`` of the event's METADATA."""
  tf_op = 1
  planes = []
  for plane in profile.planes:
    ids, metas, lines = {}, [], []
    for line in plane.lines:
      events = []
      for e in line.events:
        if e.name not in ids:
          ids[e.name] = len(ids) + 1
          scope = re.search(r', metadata=\{op_name="([^"]*)"\}', e.name)
          scope = scope.group(1) if scope else dict(e.stats).get('tf_op')
          meta = [_field(1, ids[e.name]),
                  _field(2, re.sub(r', metadata=\{[^}]*\}', '', e.name))]
          if scope:
            meta.append(_field(5, [_field(1, tf_op), _field(5, scope)]))
          metas.append(_field(4, [_field(1, ids[e.name]),
                                  _field(2, meta)]))
        events.append(_field(4, [
            _field(1, ids[e.name]), _field(2, int(e.start_ns * 1000)),
            _field(3, int(e.duration_ns * 1000))]))
      lines.append(_field(3, [_field(2, line.name), _field(3, 0)]
                          + events))
    stat_meta = _field(5, [_field(1, tf_op), _field(
        2, [_field(1, tf_op), _field(2, 'tf_op')])])
    planes.append(_field(1, [_field(2, plane.name)] + lines + metas
                         + [stat_meta]))
  return b''.join(planes)


def test_a_traced_run_hands_the_reader_the_windows_trace(
    tmp_path, monkeypatch, xspace):
  """`chipbench.run` keeps the window's trace until the readers have
  run and names it in ``ctx['trace_dir']``; the waiting reader opens
  it there, with the scopes `ProfileData` leaves out, and its metrics
  come out on the line.  The profiler is stood in for (the CPU writes
  no device plane); everything from the file on is the real path."""
  data = as_the_runtime_writes_it(scoped_profile())
  seen = []

  def fake_traced(fn, where=None):
    out = fn()
    if where is not None:
      sub = os.path.join(where, 'plugins', 'profile', 'run')
      os.makedirs(sub)
      with open(os.path.join(sub, 'host.xplane.pb'), 'wb') as f:
        f.write(data)
      seen.append(where)
    return out, xspace.parse(data)
  monkeypatch.setattr(run, 'traced', fake_traced)
  workload = 'sage-products.train-loader'
  root = merged_root(tmp_path, cellroot.make_tiny_root)
  line = run.run_cell(root, workload, 5, 0.3, True,
                      dict(platform='cpu', kind='TPU v5 lite', count=1),
                      time.perf_counter())
  steps = line['window']['steps']
  got = {k: v['value'] for k, v in line['metrics'].items()}
  ms = lambda ns: ns / 1e6
  assert got['sample_device_ms_per_step'] == ms(340) / steps
  assert got['gather_device_ms_per_step'] == ms(250) / steps
  assert got['model_device_ms_per_step'] == ms(310) / steps
  assert got['optimizer_device_ms_per_step'] == ms(70) / steps
  assert got['unattributed_device_share'] == pytest.approx(100 * 60 / 1055)
  # the metrics the benchmark has are on the line beside them
  assert {'device_idle_share', 'train_step_mfu'} <= set(got)
  assert line['correct'] is True
  # one window, one trace directory, gone once the run is over
  (where,) = seen
  assert not os.path.exists(where)
