"""`chip_smoke.py` run tiny on the CPU: the same phase functions the
chip run drives at full width (the on-chip guide's "make it run here
first"), plus the two exit-code contracts — no TPU means a non-zero
exit before any work, and a phase that raises is never swallowed."""
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

_ROOT = Path(__file__).resolve().parent.parent
SIZES = dict(fanout=(3, 2), hidden=16, classes=5)


@pytest.fixture(scope='module')
def smoke():
  spec = importlib.util.spec_from_file_location('chip_smoke',
                                                _ROOT / 'chip_smoke.py')
  mod = importlib.util.module_from_spec(spec)
  sys.modules['chip_smoke'] = mod
  spec.loader.exec_module(mod)
  return mod


@pytest.fixture(scope='module')
def ds(smoke):
  return smoke.build_dataset(600, 6, 8, SIZES['classes'])


@pytest.fixture(scope='module')
def trained(smoke, ds):
  return smoke.trainer_phase(ds, batch=16, steps=2, eval_seeds=32,
                             **SIZES)


def test_trainer_phase(trained):
  assert np.isfinite([trained['loss_first'], trained['loss_steady']]).all()
  assert 0.0 <= trained['eval_acc'] <= 1.0


def test_loader_phase(smoke, ds):
  out = smoke.loader_phase(ds, batch=16, steps=2, **SIZES)
  assert np.isfinite([out['loss_first'], out['loss_last']]).all()


def test_server_phase_checks_answers_against_references(smoke, ds,
                                                        trained):
  out = smoke.server_phase(ds, params=trained['params'], buckets=(1, 4),
                           request_sizes=(1, 3, 4, 2), **SIZES)
  assert out['requests'] == 4 and out['buckets'] == (1, 4)
  assert out['max_ref_err'] < 1e-4          # f32 on the CPU

  # the float64 reference is a real check: perturbed weights fail it
  bad = {'params': {k: dict(v) for k, v in
                    trained['params']['params'].items()}}
  bad['params']['layer0_self']['kernel'] = \
      bad['params']['layer0_self']['kernel'] + 1.0
  xs = [np.ones((n, 8), np.float32) for n in (1, 3, 6)]
  masks = [np.ones(n, bool) for n in (1, 3, 6)]
  good = smoke.tree_sage_reference(trained['params'], xs, masks)
  with pytest.raises(AssertionError, match='max abs error'):
    smoke._close('perturbed',
                 smoke.tree_sage_reference(bad, xs, masks), good)


@pytest.mark.slow          # `dryrun_multichip` compiles ~40 programs
def test_mesh_phase(smoke):
  out = smoke.mesh_phase(4, num_nodes=600, avg_deg=6, dim=8, batch=8,
                         steps=2, **SIZES)
  assert out['layout'] == 'dense'
  assert any(line.startswith('fshards') for line in out['placement'])


def test_main_refuses_a_machine_without_a_tpu(smoke, capsys):
  """Tier-1 runs under JAX_PLATFORMS=cpu: `main()` must exit non-zero
  before building anything, and print no result line."""
  with pytest.raises(SystemExit) as exc:
    smoke.main()
  assert exc.value.code not in (0, None)
  out = capsys.readouterr().out
  assert 'platform=cpu' in out and '"ok"' not in out


def test_a_failing_phase_fails_the_run(smoke, ds, monkeypatch, capsys):
  """No phase error is recorded and carried past: it propagates out of
  `main()` (a non-zero exit) and the result line is never printed."""
  def boom(*_a, **_k):
    raise RuntimeError('phase made to fail')
  monkeypatch.setattr(smoke, 'require_tpu', lambda: dict(
      platform='tpu', kind='fake', count=1))
  monkeypatch.setattr(smoke, 'build_dataset', lambda *a: ds)
  monkeypatch.setattr(smoke, 'trainer_phase', boom)
  with pytest.raises(RuntimeError, match='phase made to fail'):
    smoke.main()
  assert '"ok"' not in capsys.readouterr().out


def test_result_line_is_the_contract_shape(smoke, ds, trained,
                                           monkeypatch, capsys):
  info = dict(platform='tpu', kind='fake', count=1)
  monkeypatch.setattr(smoke, 'require_tpu', lambda: dict(info))
  monkeypatch.setattr(smoke, 'build_dataset', lambda *a: ds)
  for name in ('trainer_phase', 'loader_phase', 'server_phase'):
    monkeypatch.setattr(smoke, name,
                        lambda *a, **k: {'params': None, 'x': 1.0})
  smoke.main()
  lines = capsys.readouterr().out.strip().splitlines()
  assert json.loads(lines[-1]) == {'ok': True, 'device': info}
  assert 'mesh phase: not run (1 device)' in lines[-3]
