"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's test strategy of running the real distributed
stack all-locally (`test/python/dist_test_utils.py`): multi-chip
sharding paths compile and execute on 8 virtual CPU devices; the same
code runs unchanged on a real TPU slice.

``XLA_FLAGS`` is parsed at first backend init, which has not happened
yet when conftest loads, and ``jax_platforms`` is pinned to the CPU so
the suite never takes a chip even on a machine that has one.  The
chip is checked by `python chip_smoke.py`, not through pytest.
"""
import os

_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
  os.environ['XLA_FLAGS'] = (
      _flags + ' --xla_force_host_platform_device_count=8').strip()

import jax

jax.config.update('jax_platforms', 'cpu')
# Tests assert SEMANTICS (provenance, masks, parity), not kernel perf:
# skipping XLA's heavy optimization passes cuts the CPU-mesh compile
# wall ~35% across the suite (measured) with identical test outcomes.
# GLT_TEST_NO_FAST_XLA=1 runs under the PRODUCTION pass pipeline —
# `tests/test_optimization_canary.py` re-runs a parity slice that way
# in-suite so an optimization-pass numerics bug cannot hide behind
# this flag.
if os.environ.get('GLT_TEST_NO_FAST_XLA') != '1':
  jax.config.update('jax_disable_most_optimizations', True)
