"""The bench artifact contract, pinned.

Round 3 shipped rc=124 with NO perf number because the aggregate JSON
printed only once, at the very end.  The contract since r4: the FULL
cumulative aggregate prints after every completed phase, tolerates
prefix-only (salvaged) session dicts, and the headline `value` is the
fused whole-epoch time when the fused session landed.  These tests
import the harness module directly (no chip, no subprocesses) and pin
the schema a driver's last-JSON-line salvage depends on.
"""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

_BENCH = Path(__file__).resolve().parent.parent / 'bench.py'


@pytest.fixture(scope='module')
def bench():
  spec = importlib.util.spec_from_file_location('bench_under_test',
                                                _BENCH)
  mod = importlib.util.module_from_spec(spec)
  argv = sys.argv
  sys.argv = ['bench.py']
  try:
    spec.loader.exec_module(mod)
  finally:
    sys.argv = argv
  return mod


def _primary(**extra):
  r = {'epoch_secs': 0.25, 'compile_secs': 6.0, 'steps': 200,
       'mode': 'primary', 'platform': 'tpu'}
  r.update(extra)
  return r


FULL = dict(edges_per_sec=1.6e9, sample_hbm_frac=0.11,
            gather_hbm_frac=0.05, gather_gbps=38.0)


def test_aggregate_full_schema(bench):
  fused = {'mode': 'fused-session', 'platform': 'tpu',
           'fused_compile_secs': [70.0, 66.0],
           'epoch_secs_fused': 0.007}
  dist = {'label': 'virtual CPU mesh - relative only',
          'edges_per_sec_per_chip': 2e4}
  out = bench._aggregate([_primary(**FULL)], fused, dist)
  json.dumps(out)                         # must be JSON-serializable
  assert out['metric'].startswith('graphsage_fused_epoch_secs')
  assert out['value'] == 0.007            # fused IS the headline
  assert out['vs_baseline'] == pytest.approx(2.0 / 0.007, rel=1e-3)
  assert out['epoch_secs_min_med_max'][1] == 0.25
  assert out['fused_compile_secs'] == [70.0, 66.0]
  assert out['achieved_hbm_frac'] == {'sample': 0.11, 'gather': 0.05}
  assert out['dist'] is dist


def test_aggregate_prefix_only_sessions(bench):
  """Salvaged sessions carry only the phases that finished: an
  epoch-only line plus a compile-only fused line must still produce
  a parseable aggregate with the per-batch headline."""
  fused_partial = {'mode': 'fused-session', 'platform': 'tpu',
                   'fused_compile_secs': [70.0, 66.0]}
  out = bench._aggregate([_primary()], fused_partial, None)
  json.dumps(out)
  assert out['metric'].startswith('graphsage_epoch_secs')
  assert out['value'] == 0.25
  assert out['fused_epoch_secs'] is None
  assert out['fused_compile_secs'] == [70.0, 66.0]
  assert out['sampled_edges_per_sec_M_min_med_max'] is None
  assert out['achieved_hbm_frac'] is None


def test_aggregate_mixed_sessions_median(bench):
  rs = [_primary(**FULL),
        _primary(epoch_secs=0.35),           # salvaged: epoch only
        _primary(epoch_secs=0.30, **FULL)]
  out = bench._aggregate(rs, None, None)
  assert out['epoch_secs_min_med_max'] == [0.25, 0.3, 0.35]
  # sampling median over the two sessions that reached that phase
  assert out['sampled_edges_per_sec_M_min_med_max'][1] == 1600.0
  assert out['sessions'] == 3


def test_aggregate_dist_only(bench):
  """A day where every chip session dies must still leave a
  parseable line with the dist numbers."""
  dist = {'label': 'virtual CPU mesh - relative only'}
  out = bench._aggregate([], None, dist)
  json.dumps(out)
  assert out['value'] is None
  assert out['dist'] is dist
  assert out['sessions'] == 0


def test_aggregate_floor_filters_elided_runs(bench):
  """r5 protocol: a wall below the session's analytic HBM floor must
  not reappear as the artifact's series min."""
  r = _primary(epoch_runs=[0.007, 8.2, 8.4], epoch_secs=8.3,
               epoch_floor_secs=1.5)
  out = bench._aggregate([r], None, None)
  assert out['epoch_secs_min_med_max'][0] == 8.2
  assert out['protocol'].startswith('r5')


def test_aggregate_elision_suspect_fused_not_headline(bench):
  """A fused number flagged suspect_elision must NOT become the
  headline value."""
  fused = {'mode': 'fused-session', 'platform': 'tpu',
           'fused_compile_secs': 62.0, 'epoch_secs_fused': 0.007,
           'suspect_elision': True, 'fused_layout': 'tree'}
  out = bench._aggregate([_primary()], fused, None)
  assert out['metric'].startswith('graphsage_epoch_secs')
  assert out['value'] == 0.25
  assert out['fused_suspect_elision'] is True


def test_artifact_file_written_and_parseable(bench, tmp_path,
                                             monkeypatch):
  """r6 sink contract: the FULL aggregate lands in BENCH_ARTIFACT.json
  (env-overridable), parseable, while stdout carries only the bounded
  summary naming the file."""
  dest = tmp_path / 'BENCH_ARTIFACT.json'
  monkeypatch.setenv('GLT_BENCH_ARTIFACT', str(dest))
  # a dist payload far beyond any stdout tail: the file must carry it
  # all, the summary must still fit
  dist = {'label': 'virtual CPU mesh - relative only',
          'padding_waste_pct': 71.2, 'drop_rate_pct': 0.0,
          'num_parts': 8,
          'scale_envelope': [{'row': i, 'blob': 'x' * 500}
                             for i in range(16)]}
  fused = {'mode': 'fused-session', 'platform': 'tpu',
           'fused_compile_secs': 60.0, 'epoch_secs_fused': 7.1,
           'fused_layout': 'tree'}
  art = bench._aggregate([_primary(**FULL)], fused, dist)
  line = bench._emit_artifact(art)
  assert dest.exists()
  full = json.loads(dest.read_text())
  assert full['value'] == 7.1
  assert len(full['dist']['scale_envelope']) == 16   # nothing truncated
  # the stdout line: bounded, parseable, names the artifact, carries
  # the headline
  assert len(line) <= 2000
  summary = json.loads(line)
  assert summary['artifact'] == str(dest)
  assert summary['value'] == 7.1
  assert summary['metric'].startswith('graphsage_fused_epoch_secs')
  assert summary['dist']['padding_waste_pct'] == 71.2


def test_summary_line_bounded_on_pathological_artifact(bench, tmp_path,
                                                       monkeypatch):
  """Even an artifact whose every headline field is huge must yield a
  parseable summary under the 2000-char tail budget."""
  from graphlearn_tpu.telemetry import sink
  art = {'metric': 'm' * 500, 'value': 1.0, 'unit': 's',
         'protocol': 'p' * 900,
         'epoch_secs_min_med_max': [0.1] * 200,
         'dist': {'padding_waste_pct': 1.0, 'error': 'e' * 900}}
  line = sink.summary_line(art, artifact=str(tmp_path / 'a.json'))
  assert len(line) <= 2000
  parsed = json.loads(line)
  assert parsed['value'] == 1.0
