"""P=16 scale smoke: every mesh engine beyond the suite's 8 devices.

Scale validation must not stop at P=8: the suite's
conftest fixes an 8-device mesh, so this test spawns a subprocess with
16 virtual CPU devices and runs one batch each of the node, hetero,
and induced-subgraph engines with full provenance checks
(tests/_p16_worker.py).
"""
import os
import json
import subprocess
import sys
from pathlib import Path
import pytest

#: CPU-mesh scan-compile heavy (multi-minute): excluded from the
#: default run, selected by `pytest -m slow` (see pyproject.toml)
pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent


def test_engines_at_p16(tmp_path):
  env = dict(os.environ)
  env['JAX_PLATFORMS'] = 'cpu'
  flags = ' '.join(
      f for f in env.get('XLA_FLAGS', '').split()
      if '--xla_force_host_platform_device_count' not in f)
  env['XLA_FLAGS'] = (
      flags + ' --xla_force_host_platform_device_count=16').strip()
  env['PYTHONPATH'] = str(REPO) + os.pathsep + env.get('PYTHONPATH', '')
  out = tmp_path / 'p16.json'
  r = subprocess.run(
      [sys.executable, str(Path(__file__).parent / '_p16_worker.py'),
       str(out)],
      env=env, capture_output=True, text=True, timeout=900)
  assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
  rep = json.loads(out.read_text())
  assert rep['node_edges'] > 0
  assert rep['hetero_nodes'] > 0
  assert rep['subgraph_edges'] > 0
  assert rep['dropped'] == 0
