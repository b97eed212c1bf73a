"""Where JAX's persistent compilation cache lives — decided in ONE place.

Entry-point scripts (`chip_smoke.py`, `chipbench/run.py`) call
`enable_compile_cache()` once, after choosing their platform and
before their first compile.  The library never
calls it at import, and the test suite never calls it at all: a
process that did not ask for a cache has none.

Placement rules:

  * ``JAX_COMPILATION_CACHE_DIR`` set: nothing is touched.  JAX reads
    the variable itself, so the operator's directory wins and no code
    in this repo sets another.
  * unset: the cache goes to `DEFAULT_DIR`, one fixed git-ignored path
    inside the checkout.  The directory is part of what identifies an
    entry to later processes, so it is never a temporary name, a pid
    or a time.
  * unset and the backend is not a TPU: no cache.  The checkout (and
    so `DEFAULT_DIR`) is copied between machines with different host
    CPUs, and XLA:CPU refuses — or worse, runs — AOT entries built for
    another machine's target features.

JAX's own thresholds stay at their defaults (entries that took >= 1 s
to compile, any size): the fused epoch programs and the serving bucket
programs clear them by a wide margin on a TPU.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV = 'JAX_COMPILATION_CACHE_DIR'
CONFIG_KEY = 'jax_compilation_cache_dir'
DEFAULT_DIR = str(Path(__file__).resolve().parents[2] / '.jax_cache')


def enable_compile_cache() -> Optional[str]:
  """Apply the placement rules above; returns the directory in use
  (None when the process runs without a persistent cache).
  Idempotent."""
  import jax
  env = os.environ.get(ENV)
  if env:
    return env
  if jax.default_backend() != 'tpu':
    return None
  jax.config.update(CONFIG_KEY, DEFAULT_DIR)
  return DEFAULT_DIR
