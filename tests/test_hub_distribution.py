"""Distribution tests at hub degrees.

`ops/neighbor.py::sample_one_hop` has three degree regimes:
``deg <= k`` takes every neighbor; ``k < deg <= W`` samples EXACTLY
without replacement (Gumbel top-k over the W-wide window); ``deg > W``
falls back to k independent uniform draws WITH replacement (documented
deviation: expected colliding slots < k/16, duplicates later deduped
by the inducer).  These tests pin the STATISTICS of both sampling
regimes on a hub node:

  * marginal uniformity over the hub's neighbors (chi-square against
    the uniform null at ~4-sigma thresholds);
  * the window path never emits a duplicate within a row;
  * the with-replacement path's per-row collision rate sits in a
    confidence band around its analytic expectation k(k-1)/(2*deg).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from graphlearn_tpu.ops.neighbor import default_window, sample_one_hop

K = 8


def _hub_csr(deg: int):
  """Node 0 is a hub with out-neighbors 1..deg; all others empty."""
  n = deg + 1
  indptr = np.zeros(n + 1, np.int64)
  indptr[1:] = deg
  indices = np.arange(1, deg + 1, dtype=np.int32)
  return jnp.asarray(indptr), jnp.asarray(indices)


def _frequencies(indptr, indices, deg, calls, batch, seed):
  seeds = jnp.zeros(batch, jnp.int32)
  counts = np.zeros(deg + 1, np.int64)
  dup_slots = 0
  base = jax.random.key(seed)
  for i in range(calls):
    res = sample_one_hop(indptr, indices, seeds, K,
                         jax.random.fold_in(base, i))
    nb = np.asarray(res.nbrs)
    assert np.asarray(res.mask).all()          # deg > k: full rows
    counts += np.bincount(nb.reshape(-1), minlength=deg + 1)
    for row in nb:
      dup_slots += K - len(np.unique(row))
  return counts[1:], dup_slots, calls * batch


def test_hub_with_replacement_uniform_and_bounded_collisions():
  """deg > W regime: uniform marginals, collision rate at its
  analytic expectation (and far under the documented k/16 bound)."""
  w = default_window(K)
  deg = 4 * w                                   # 256 with K=8
  indptr, indices = _hub_csr(deg)
  counts, dup_slots, rows = _frequencies(indptr, indices, deg,
                                         calls=40, batch=256, seed=0)
  mean = counts.sum() / deg
  chi2 = float(((counts - mean) ** 2 / mean).sum())
  # df = deg-1 = 255: mean 255, sd ~22.6; 380 is ~5.5 sigma
  assert chi2 < 380, f'non-uniform hub marginals: chi2={chi2:.1f}'
  rate = dup_slots / rows
  expect = K * (K - 1) / (2 * deg)              # ~0.109 duplicate
  assert rate < K / 16, rate                    # slots per row
  assert 0.3 * expect < rate < 3 * expect, (rate, expect)


def test_window_path_exact_without_replacement():
  """k < deg <= W regime: NEVER a duplicate in a row, uniform
  marginals, full support coverage."""
  w = default_window(K)
  indptr, indices = _hub_csr(w)
  seeds = jnp.zeros(128, jnp.int32)
  counts = np.zeros(w + 1, np.int64)
  base = jax.random.key(1)
  for i in range(30):
    res = sample_one_hop(indptr, indices, seeds, K,
                         jax.random.fold_in(base, i))
    nb = np.asarray(res.nbrs)
    for row in nb:
      assert len(np.unique(row)) == K, 'duplicate in exact regime'
    counts += np.bincount(nb.reshape(-1), minlength=w + 1)
  counts = counts[1:]
  assert (counts > 0).all(), 'neighbor never sampled'
  mean = counts.sum() / w
  chi2 = float(((counts - mean) ** 2 / mean).sum())
  # df = w-1 = 63: mean 63, sd ~11.2; 130 is ~6 sigma
  assert chi2 < 130, f'non-uniform window marginals: chi2={chi2:.1f}'
