"""Canary: a parity slice re-run under the PRODUCTION XLA pipeline.

`tests/conftest.py` sets ``jax_disable_most_optimizations`` for the
whole suite (compile-wall economics), which means every parity test
normally runs a different pass pipeline than production — a fusion
bug that changes masked-reduction numerics would be invisible
.  This canary re-executes one fused-epoch parity test and
one device-native loader parity test in a SUBPROCESS with
``GLT_TEST_NO_FAST_XLA=1``, i.e. with the full optimization pipeline
on, so at least one representative of each family runs production
passes on every default `pytest` invocation.
"""
import os
import subprocess
import sys

import pytest


def _run_with_full_passes(*test_ids: str):
  env = dict(os.environ, GLT_TEST_NO_FAST_XLA='1')
  out = subprocess.run(
      [sys.executable, '-m', 'pytest', '-q', '-p', 'no:cacheprovider',
       *test_ids],
      cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
      env=env, capture_output=True, text=True, timeout=420)
  assert out.returncode == 0, (
      f'parity failed under the production XLA pipeline:\n'
      f'{out.stdout[-2000:]}\n{out.stderr[-1000:]}')


@pytest.mark.slow
def test_parity_under_production_passes():
  _run_with_full_passes(
      'tests/test_fused_epoch.py::test_fused_step_matches_manual_batch',
      'tests/test_device_native.py::test_device_loader_parity')
