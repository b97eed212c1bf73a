"""Causal span layer (ISSUE 2 tentpole): begin/end pairing, parentage
through the distributed pipeline, monotonic durations, channel context
propagation, log2 histograms, Chrome trace export, and the report CLI.
"""
import json
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from graphlearn_tpu.telemetry import (Histogram, from_snapshot, metrics,
                                      recorder, span)
from graphlearn_tpu.telemetry import histogram as histogram_mod
from graphlearn_tpu.telemetry import spans as spans_mod
from graphlearn_tpu.telemetry.export import (load_events,
                                             span_durations,
                                             to_chrome_trace)
from graphlearn_tpu.utils.profiling import Metrics

P = 8
N = 256
FANOUT = [2, 2]
BATCH = 8


def _events(path):
  return [json.loads(ln) for ln in open(path).read().splitlines()]


# -- span mechanics ---------------------------------------------------------

def test_span_noop_when_recorder_off():
  recorder.disable()
  with span('x') as ctx:
    assert ctx is None
  assert spans_mod.current() is None


def test_span_pairing_parentage_and_duration(tmp_path):
  p = str(tmp_path / 'f.jsonl')
  recorder.enable(p)
  try:
    with span('root', tag='r') as rctx:
      assert spans_mod.current() == rctx
      with span('child') as cctx:
        assert cctx.trace_id == rctx.trace_id
        time.sleep(0.02)
    assert spans_mod.current() is None
  finally:
    recorder.disable()
  evs = _events(p)
  begins = {e['span_id']: e for e in evs if e['kind'] == 'span.begin'}
  ends = {e['span_id']: e for e in evs if e['kind'] == 'span.end'}
  assert set(begins) == set(ends) and len(begins) == 2
  root = begins[rctx.span_id]
  child = begins[cctx.span_id]
  assert root['parent_id'] is None
  assert root['span_id'] == root['trace_id']    # root id == trace id
  assert root['tag'] == 'r'                     # caller fields ride
  assert child['parent_id'] == root['span_id']
  assert child['trace_id'] == root['trace_id']
  # durations are monotonic-clock and nest: child <= root
  assert ends[cctx.span_id]['dur'] >= 0.02
  assert ends[rctx.span_id]['dur'] >= ends[cctx.span_id]['dur']
  # every event carries the mono timebase the durations derive from
  assert all('mono' in e for e in evs)


def test_span_explicit_parent_and_error_field(tmp_path):
  p = str(tmp_path / 'f.jsonl')
  recorder.enable(p)
  try:
    with span('other') as octx:
      pass
    with pytest.raises(ValueError):
      with span('linked', parent=octx):
        raise ValueError('boom')
  finally:
    recorder.disable()
  evs = _events(p)
  linked_b = [e for e in evs if e['kind'] == 'span.begin'
              and e['name'] == 'linked'][0]
  linked_e = [e for e in evs if e['kind'] == 'span.end'
              and e['name'] == 'linked'][0]
  assert linked_b['parent_id'] == octx.span_id
  assert linked_b['trace_id'] == octx.trace_id
  assert linked_e['error'] == 'ValueError'


def test_span_reserved_kwargs_renamed_not_raised(tmp_path):
  """Caller fields colliding with the span machinery's own event
  fields are suffixed, so enabling telemetry can never TypeError a
  pipeline that ran clean with it off."""
  p = str(tmp_path / 'f.jsonl')
  recorder.enable(p)
  try:
    with span('stagey', name='user-name', dur=3, error='prior'):
      pass
  finally:
    recorder.disable()
  b = [e for e in _events(p) if e['kind'] == 'span.begin'][0]
  assert b['name'] == 'stagey'                  # machinery field wins
  assert b['name_'] == 'user-name'              # caller field renamed
  assert b['dur_'] == 3 and b['error_'] == 'prior'


def test_events_carry_pid_tid(tmp_path):
  """Every recorder event (not just spans) lands on a real
  process/thread row — the Chrome-trace instant rows."""
  import os as os_mod
  p = str(tmp_path / 'f.jsonl')
  recorder.enable(p)
  try:
    recorder.emit('channel.stall', op='recv', secs=0.02)
  finally:
    recorder.disable()
  ev = _events(p)[0]
  assert ev['pid'] == os_mod.getpid()
  assert ev['tid'] == threading.get_ident()


def test_span_instance_not_reentrant(tmp_path):
  """Re-entering one OPEN span instance raises (it would leak the
  contextvar); sequential reuse of a closed instance stays fine."""
  recorder.enable(str(tmp_path / 'f.jsonl'))
  try:
    s = span('once')
    with s:
      with pytest.raises(RuntimeError, match='re-entered'):
        with s:
          pass
    with s:                                   # sequential reuse: ok
      pass
  finally:
    recorder.disable()
  assert spans_mod.current() is None          # no contextvar leak


def test_span_decorator(tmp_path):
  p = str(tmp_path / 'f.jsonl')

  @span('decorated')
  def work():
    return 7

  recorder.enable(p)
  try:
    assert work() == 7
  finally:
    recorder.disable()
  names = [e['name'] for e in _events(p)]
  assert names == ['decorated', 'decorated']


def test_span_thread_isolation(tmp_path):
  """A fresh thread starts its own trace — no parent leaks across
  threads (contextvars semantics the prefetch workers rely on)."""
  p = str(tmp_path / 'f.jsonl')
  recorder.enable(p)
  seen = {}
  try:
    with span('main') as mctx:
      def other():
        with span('worker') as wctx:
          seen['ctx'] = wctx
      t = threading.Thread(target=other)
      t.start()
      t.join()
  finally:
    recorder.disable()
  assert seen['ctx'].trace_id != mctx.trace_id
  wb = [e for e in _events(p) if e['kind'] == 'span.begin'
        and e['name'] == 'worker'][0]
  assert wb['parent_id'] is None


# -- histogram --------------------------------------------------------------

def test_histogram_bucket_edges():
  assert histogram_mod.bucket_index(0.0) == 0
  assert histogram_mod.bucket_index(0.5e-6) == 0
  assert histogram_mod.bucket_index(1e-6) == 1
  assert histogram_mod.bucket_index(3e-6) == 2      # [2, 4) us
  assert histogram_mod.bucket_index(1.0) == 20      # 2^19..2^20 us
  assert histogram_mod.bucket_index(1e6) == \
      histogram_mod.NUM_BUCKETS - 1                 # overflow clamps


def test_histogram_record_merge_quantile_roundtrip():
  reg = Metrics()
  for secs in (1e-5, 2e-5, 4e-4, 0.1):
    histogram_mod.record('stage', secs, registry=reg)
  hists = from_snapshot(reg.snapshot())
  assert set(hists) == {'stage'}
  h = hists['stage']
  assert h.count == 4
  assert h.secs == pytest.approx(1e-5 + 2e-5 + 4e-4 + 0.1)
  # quantiles are log2 upper edges: p50 lands in the 16-32us bucket
  assert h.quantile(0.5) == pytest.approx(32e-6)
  assert h.quantile(1.0) >= 0.1
  # merge == the sum gather_metrics computes on the flat encoding
  h2 = Histogram('stage')
  h2.add(0.2)
  merged_flat = dict(h.to_flat())
  for k, v in h2.to_flat().items():
    merged_flat[k] = merged_flat.get(k, 0) + v
  via_flat = from_snapshot(merged_flat)['stage']
  h.merge(h2)
  assert via_flat.count == h.count == 5
  assert via_flat.buckets == h.buckets


def test_span_ticks_histogram(tmp_path):
  recorder.enable(str(tmp_path / 'f.jsonl'))
  base = metrics.snapshot().get('span.histest.hist.count', 0)
  try:
    with span('histest'):
      pass
  finally:
    recorder.disable()
  assert metrics.snapshot()['span.histest.hist.count'] == base + 1


# -- channel context propagation --------------------------------------------

def test_inject_extract_roundtrip(tmp_path):
  recorder.enable(str(tmp_path / 'f.jsonl'))
  try:
    msg = {'ids': np.arange(3)}
    with span('producer.sample') as ctx:
      spans_mod.inject(msg)
    assert spans_mod.SPAN_KEY in msg
    got = spans_mod.extract(msg)
    assert got == ctx
    assert spans_mod.SPAN_KEY not in msg        # extract strips it
    # no ambient span -> no injection
    msg2 = {}
    spans_mod.inject(msg2)
    assert msg2 == {}
  finally:
    recorder.disable()
  # recorder off -> injection is a no-op
  msg3 = {}
  with span('x'):
    spans_mod.inject(msg3)
  assert msg3 == {}


def test_send_retries_without_span_on_budget_overflow(tmp_path):
  """A '#SPAN' tensor pushing a message past a fixed transport budget
  (the shm slot size) drops the LINK, never the message — telemetry
  on must not fail sends that succeed with it off."""
  from graphlearn_tpu.channel.base import ChannelBase

  class TightChannel(ChannelBase):
    def __init__(self):
      self.sent = []

    def _put(self, msg):
      if spans_mod.SPAN_KEY in msg:
        raise ValueError('message exceeds slot size')
      self.sent.append(msg)

    def send(self, msg):
      self._send_traced('send', self._put, msg)

    def recv(self):
      return self._recv_traced('recv', self.sent.pop, 0)

  ch = TightChannel()
  recorder.enable(str(tmp_path / 'f.jsonl'))
  try:
    with span('producer.sample'):
      ch.send({'a': np.arange(3)})
  finally:
    recorder.disable()
  assert len(ch.sent) == 1                      # message survived
  assert spans_mod.SPAN_KEY not in ch.sent[0]   # link degraded
  # a ValueError NOT caused by the span context still propagates
  class AlwaysFull(TightChannel):
    def _put(self, msg):
      raise ValueError('oversize regardless')
  ch2 = AlwaysFull()
  with pytest.raises(ValueError):
    ch2.send({'a': np.arange(3)})


def test_mp_channel_carries_span_context(tmp_path):
  """The channel ships the sender's ambient context and parks it at
  `last_span_context` on recv — the cross-process causal link."""
  from graphlearn_tpu.channel import MpChannel
  recorder.enable(str(tmp_path / 'f.jsonl'))
  ch = MpChannel()
  sent = {}
  try:
    def produce():
      with span('producer.sample') as ctx:
        sent['ctx'] = ctx
        ch.send({'a': np.arange(3)})

    t = threading.Thread(target=produce)
    t.start()
    msg = ch.recv()
    t.join()
    assert msg['a'].tolist() == [0, 1, 2]
    assert spans_mod.SPAN_KEY not in msg
    assert ch.last_span_context == sent['ctx']
    link = spans_mod.link_fields(ch.last_span_context)
    assert link == {'producer_trace': sent['ctx'].trace_id,
                    'producer_span': sent['ctx'].span_id}
  finally:
    recorder.disable()
    ch.close()


# -- the distributed pipeline (8-device virtual mesh) -----------------------

def _dist_dataset():
  from graphlearn_tpu.parallel import DistDataset
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N,
                         (np.arange(N) + 2) % N])
  feats = np.random.default_rng(0).random((N, 8), np.float32)
  # tiered (split_ratio): the feature.lookup span only exists where
  # there is a cold overlay to attribute
  return DistDataset.from_full_graph(P, rows, cols, node_feat=feats,
                                     num_nodes=N, split_ratio=0.5)


@pytest.fixture(scope='module')
def traced_run(tmp_path_factory):
  """One DistNeighborLoader epoch with the recorder on; several tests
  read the resulting trace (the acceptance artifact)."""
  from graphlearn_tpu.parallel import DistNeighborLoader, make_mesh
  path = str(tmp_path_factory.mktemp('spans') / 'flight.jsonl')
  ds = _dist_dataset()
  loader = DistNeighborLoader(ds, FANOUT, np.arange(N),
                              batch_size=BATCH, mesh=make_mesh(P),
                              shuffle=True, seed=0)
  recorder.enable(path, max_events=8192)
  try:
    batches = sum(1 for _ in loader)
  finally:
    recorder.disable()
  return {'path': path, 'batches': batches}


def test_dist_loader_spans_pair_and_nest(traced_run):
  """Acceptance: every span.end pairs with a span.begin, and the
  exchange/feature spans are children of the batch span."""
  evs = _events(traced_run['path'])
  begins = {e['span_id']: e for e in evs if e['kind'] == 'span.begin'}
  ends = {e['span_id']: e for e in evs if e['kind'] == 'span.end'}
  assert begins and set(begins) == set(ends)
  batch_spans = {s: e for s, e in begins.items() if e['name'] == 'batch'}
  assert len(batch_spans) == traced_run['batches']
  for kind in ('sample.exchange', 'feature.lookup', 'stitch'):
    ks = [e for e in begins.values() if e['name'] == kind]
    assert len(ks) == traced_run['batches'], kind
    for e in ks:
      assert e['parent_id'] in batch_spans, (kind, e)
      assert e['trace_id'] == begins[e['parent_id']]['trace_id']
  # every batch is its own trace (root span id == trace id)
  for s, e in batch_spans.items():
    assert e['parent_id'] is None and e['trace_id'] == s


def test_chrome_trace_export_structure(traced_run, tmp_path):
  """Acceptance: the Chrome trace-event export is structurally valid —
  ph/ts/dur/pid/tid on every slice, begin/end balanced."""
  evs = load_events(traced_run['path'])
  trace = to_chrome_trace(evs)
  assert 'traceEvents' in trace
  xs = [e for e in trace['traceEvents'] if e['ph'] == 'X']
  n_ends = sum(1 for e in evs if e['kind'] == 'span.end')
  assert len(xs) == n_ends        # every pair became exactly one slice
  for e in xs:
    assert isinstance(e['name'], str) and e['name']
    assert isinstance(e['ts'], float) and e['ts'] >= 0
    assert isinstance(e['dur'], float) and e['dur'] >= 0
    assert isinstance(e['pid'], int) and isinstance(e['tid'], int)
    assert 'span_id' in e['args'] and 'trace_id' in e['args']
  # slices are time-ordered and json-serializable end to end
  ts = [e['ts'] for e in trace['traceEvents']]
  assert ts == sorted(ts)
  out = tmp_path / 'chrome.json'
  out.write_text(json.dumps(trace))
  assert json.loads(out.read_text())['traceEvents']


def test_mixed_timebase_events_stay_on_one_timeline():
  """A pre-`mono` dump appended to by the new recorder: each timebase
  gets its own origin, so no event lands decades down the timeline."""
  evs = [{'kind': 'channel.stall', 'ts': 1.7e9, 'op': 'recv'},   # old
         {'kind': 'channel.stall', 'ts': 1.7e9 + 1.0, 'op': 'recv'},
         {'kind': 'span.begin', 'name': 'b', 'span_id': 's',
          'trace_id': 's', 'parent_id': None, 'mono': 6000.0,
          'ts': 1.7e9 + 2.0, 'pid': 1, 'tid': 1},
         {'kind': 'span.end', 'name': 'b', 'span_id': 's',
          'trace_id': 's', 'mono': 6000.5, 'ts': 1.7e9 + 2.5,
          'dur': 0.5, 'pid': 1, 'tid': 1}]
  trace = to_chrome_trace(evs)
  ts = [e['ts'] for e in trace['traceEvents']]
  assert len(ts) == 3                    # 2 instants + 1 slice
  assert all(0 <= t <= 10e6 for t in ts), ts   # all within 10 s


def test_unpaired_begin_dropped():
  evs = [{'kind': 'span.begin', 'name': 'a', 'span_id': 's1',
          'trace_id': 's1', 'parent_id': None, 'mono': 1.0,
          'pid': 1, 'tid': 1},
         {'kind': 'span.end', 'name': 'b', 'span_id': 'ghost',
          'trace_id': 'g', 'mono': 2.0, 'dur': 0.5, 'pid': 1,
          'tid': 1}]
  trace = to_chrome_trace(evs, include_instants=False)
  assert trace['traceEvents'] == []     # no guessed slices


def test_report_cli_table_and_diff(traced_run, tmp_path):
  out = subprocess.run(
      [sys.executable, '-m', 'graphlearn_tpu.telemetry.report',
       traced_run['path'], '--diff', traced_run['path'],
       '--chrome', str(tmp_path / 'c.json')],
      capture_output=True, text=True,
      env={**__import__('os').environ, 'JAX_PLATFORMS': 'cpu'})
  assert out.returncode == 0, out.stderr[-2000:]
  for stage in ('batch', 'sample.exchange', 'feature.lookup'):
    assert stage in out.stdout
  # self-diff: every Δmean% is +0.0
  assert '+0.0' in out.stdout
  chrome = json.loads((tmp_path / 'c.json').read_text())
  assert chrome['traceEvents']


def test_span_durations_helper(traced_run):
  durs = span_durations(load_events(traced_run['path']))
  assert set(durs) >= {'batch', 'sample.exchange', 'feature.lookup',
                       'stitch'}
  assert all(d >= 0 for ds in durs.values() for d in ds)


def test_span_children_tree(traced_run):
  from graphlearn_tpu.telemetry.export import span_children
  evs = load_events(traced_run['path'])
  tree = span_children(evs)
  names = {e['span_id']: e.get('name') for e in evs
           if e['kind'] == 'span.begin'}
  roots = tree[None]
  assert len(roots) == traced_run['batches']
  # each batch root parents runtime stage children; the FIRST batch
  # additionally parents build-time spans (the exchange.layout
  # step-construction marker lands inside the batch that triggered
  # the compile — honest attribution of build cost).  The tiered
  # loader's cold pipeline dispatches batch k+1 inside batch k's span
  # (honest attribution of the overlap), so one root may parent two
  # sample.exchange children and the last none — but the EPOCH total
  # is exactly 3 stage spans per batch.
  stage_names = {'sample.exchange', 'feature.lookup', 'stitch'}
  per_root = []
  for r in roots:
    stages = [c for c in tree[r] if names.get(c) in stage_names]
    per_root.append(len(stages))
    assert all(names.get(c) in stage_names | {'exchange.layout'}
               for c in tree[r])
  assert sum(per_root) == 3 * traced_run['batches']
  assert all(2 <= n <= 4 for n in per_root)
  # malformed begin (no span_id) is skipped, not a KeyError
  assert span_children([{'kind': 'span.begin', 'parent_id': None}]) \
      == {}


def test_histograms_merge_across_two_process_mesh(tmp_path):
  """Acceptance: per-stage latency histograms recorded on a REAL
  2-process jax.distributed mesh merge via gather_metrics (sum per
  flat key) and render in the report CLI."""
  import os
  import socket
  from pathlib import Path
  with socket.socket() as s:
    s.bind(('localhost', 0))
    port = s.getsockname()[1]
  worker = Path(__file__).parent / '_span_hist_worker.py'
  env = dict(os.environ)
  env['JAX_PLATFORMS'] = 'cpu'
  env['XLA_FLAGS'] = ' '.join(
      f for f in env.get('XLA_FLAGS', '').split()
      if '--xla_force_host_platform_device_count' not in f)
  env['PYTHONPATH'] = (str(Path(__file__).resolve().parent.parent)
                       + os.pathsep + env.get('PYTHONPATH', ''))
  outs = [tmp_path / f'agg{i}.json' for i in range(2)]
  procs = [subprocess.Popen(
      [sys.executable, str(worker), f'localhost:{port}', '2', str(i),
       str(outs[i])],
      env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
      text=True) for i in range(2)]
  for pr in procs:
    try:
      stdout, _ = pr.communicate(timeout=180)
    except subprocess.TimeoutExpired:
      for q in procs:
        q.kill()
      raise
    assert pr.returncode == 0, stdout[-4000:]
  r0, r1 = (json.loads(o.read_text()) for o in outs)
  # both processes computed the SAME merged aggregate
  assert r0['num_hosts'] == 2
  assert r0['aggregate'] == r1['aggregate']
  hists = from_snapshot(r0['aggregate'])
  # proc 0 recorded 1 span, proc 1 recorded 2 — the merge sums them
  assert hists['mesh.stage'].count == 3
  assert hists['mesh.stage'].secs > 0
  # and the merged view renders through the report CLI
  agg_file = tmp_path / 'merged.json'
  agg_file.write_text(json.dumps(r0))
  out = subprocess.run(
      [sys.executable, '-m', 'graphlearn_tpu.telemetry.report',
       '--metrics-json', str(agg_file)],
      capture_output=True, text=True, env=env)
  assert out.returncode == 0, out.stderr[-2000:]
  assert 'mesh.stage' in out.stdout
  assert ' 3 ' in out.stdout or '  3' in out.stdout
