"""`utils.compile_cache.enable_compile_cache`: one placement rule for
JAX's persistent compilation cache.  `jax.config.update` is stubbed
throughout — the test suite itself must never switch a cache on."""
import os
from pathlib import Path

import jax
import pytest

from graphlearn_tpu.utils import compile_cache as cc


@pytest.fixture
def updates(monkeypatch):
  seen = []
  monkeypatch.setattr(jax.config, 'update',
                      lambda key, val: seen.append((key, val)))
  return seen


def test_env_dir_wins_and_no_config_is_touched(monkeypatch, updates,
                                               tmp_path):
  monkeypatch.setenv(cc.ENV, str(tmp_path))
  # must not even ask for the backend: JAX reads the variable itself
  monkeypatch.setattr(jax, 'default_backend', lambda: 1 / 0)
  assert cc.enable_compile_cache() == str(tmp_path)
  assert updates == []


def test_unset_on_tpu_selects_the_fixed_checkout_path(monkeypatch,
                                                      updates):
  monkeypatch.delenv(cc.ENV, raising=False)
  monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
  repo = Path(__file__).resolve().parent.parent
  assert cc.DEFAULT_DIR == str(repo / '.jax_cache')
  assert cc.enable_compile_cache() == cc.DEFAULT_DIR
  assert cc.enable_compile_cache() == cc.DEFAULT_DIR      # idempotent
  assert updates == [(cc.CONFIG_KEY, cc.DEFAULT_DIR)] * 2
  # a cache that moves never hits: no temp dir, pid or time in the path
  assert not cc.DEFAULT_DIR.startswith('/tmp')
  assert str(os.getpid()) not in cc.DEFAULT_DIR


def test_unset_off_tpu_runs_without_a_cache(monkeypatch, updates):
  """The checkout travels between machines; XLA:CPU AOT entries built
  for another host's CPU must never be found in it."""
  monkeypatch.delenv(cc.ENV, raising=False)
  monkeypatch.setattr(jax, 'default_backend', lambda: 'cpu')
  assert cc.enable_compile_cache() is None
  assert updates == []


def test_checkout_ignores_the_default_dir():
  repo = Path(__file__).resolve().parent.parent
  ignored = (repo / '.gitignore').read_text().split()
  assert '.jax_cache/' in ignored
