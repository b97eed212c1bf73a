"""CSR window-gather experiment kernel: the
aligned-overfetch DMA path must agree with the XLA window gather
(interpret mode on the CPU mesh; its speed is not measured on the
chip, ROADMAP D2).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from graphlearn_tpu.ops.pallas_window import (MAX_W, csr_window_gather,
                                              xla_window_gather)


@pytest.mark.parametrize('e,w', [(5000, 128), (5000, 64), (130000, 128),
                                 (1024, 16)])
def test_window_matches_direct(e, w):
  rng = np.random.default_rng(0)
  ind = rng.integers(0, 1 << 20, e).astype(np.int32)
  starts = rng.integers(0, e, 97).astype(np.int32)
  # force unit-boundary crossings and edge positions into the set
  starts[:3] = [max(e - 1, 0), max(e - w, 0), min(1020, e - 1)]
  out = np.asarray(csr_window_gather(jnp.asarray(ind),
                                     jnp.asarray(starts), w,
                                     interpret=True))
  assert out.shape == (97, w)
  for i, s in enumerate(starts):
    valid = min(w, e - s)
    np.testing.assert_array_equal(out[i, :valid], ind[s:s + valid])


def test_window_width_bound():
  ind = jnp.zeros((100,), jnp.int32)
  with pytest.raises(AssertionError):
    csr_window_gather(ind, jnp.zeros((4,), jnp.int32), MAX_W + 1,
                      interpret=True)


def test_xla_window_gather_clamps():
  ind = jnp.arange(100, dtype=jnp.int32)
  out = np.asarray(xla_window_gather(ind, jnp.asarray([95]), 10))
  np.testing.assert_array_equal(out[0], [95, 96, 97, 98, 99, 99, 99,
                                         99, 99, 99])
