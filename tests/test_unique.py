"""Tests for the sort-based inducer (ops/unique.py).

Mirrors the coverage of reference `test/cpp/test_inducer.cu` /
`test_hash_table.cu`: dedup correctness, insertion-order preservation,
relabeling, capacity overflow.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from graphlearn_tpu.ops import induce_next, init_node, unique_stable
from graphlearn_tpu.ops import unique as unique_module


def test_unique_stable_basic():
  x = jnp.array([5, 3, 5, 7, 3, 9], dtype=jnp.int32)
  res = unique_stable(x, capacity=8)
  assert int(res.count) == 4
  np.testing.assert_array_equal(np.asarray(res.values[:4]), [5, 3, 7, 9])
  np.testing.assert_array_equal(np.asarray(res.values[4:]), [-1] * 4)
  np.testing.assert_array_equal(np.asarray(res.inverse), [0, 1, 0, 2, 1, 3])


def test_unique_stable_with_invalid():
  x = jnp.array([4, -1, 4, 2, -1, 0], dtype=jnp.int32)
  res = unique_stable(x, capacity=4)
  assert int(res.count) == 3
  np.testing.assert_array_equal(np.asarray(res.values[:3]), [4, 2, 0])
  np.testing.assert_array_equal(np.asarray(res.inverse), [0, -1, 0, 1, -1, 2])


def test_unique_stable_overflow():
  x = jnp.arange(10, dtype=jnp.int32)
  res = unique_stable(x, capacity=4)
  assert int(res.count) == 4
  # Which 4 survive is defined by value-sort segment order; the
  # guarantee is: exactly `capacity` uniques, inverse in [-1, cap).
  inv = np.asarray(res.inverse)
  assert ((inv >= -1) & (inv < 4)).all()


def test_inducer_init_and_induce():
  seeds = jnp.array([10, 20, 30, -1], dtype=jnp.int32)
  state, seed_local = init_node(seeds, capacity=16)
  assert int(state.count) == 3
  np.testing.assert_array_equal(np.asarray(seed_local), [0, 1, 2, -1])

  # hop: node 10 sampled [20, 40], node 20 sampled [40, 50]
  nbrs = jnp.array([[20, 40], [40, 50], [-1, -1], [-1, -1]], jnp.int32)
  mask = nbrs >= 0
  src_local = seed_local
  state2, rows, cols, frontier_start = induce_next(state, src_local, nbrs,
                                                   mask)
  assert int(frontier_start) == 3
  assert int(state2.count) == 5
  nodes = np.asarray(state2.nodes[:5])
  np.testing.assert_array_equal(nodes, [10, 20, 30, 40, 50])
  # rows = neighbor local idx, cols = src local idx (PyG transposed);
  # static [B*k] layout with -1 padding for masked slots.
  np.testing.assert_array_equal(np.asarray(rows),
                                [1, 3, 3, 4, -1, -1, -1, -1])
  np.testing.assert_array_equal(np.asarray(cols),
                                [0, 0, 1, 1, -1, -1, -1, -1])


def test_inducer_idempotent_reinsert():
  seeds = jnp.array([1, 2], dtype=jnp.int32)
  state, _ = init_node(seeds, capacity=8)
  nbrs = jnp.array([[2, 1], [1, 2]], jnp.int32)
  state2, rows, cols, _ = induce_next(state, jnp.array([0, 1]), nbrs,
                                      nbrs >= 0)
  assert int(state2.count) == 2  # nothing new
  np.testing.assert_array_equal(np.asarray(rows), [1, 0, 0, 1])
  np.testing.assert_array_equal(np.asarray(cols), [0, 0, 1, 1])


def test_unique_overflow_drops_latest_not_largest():
  # Regression: overflow must drop the latest-appearing ids, keeping
  # earlier local indices stable (id 10 appears first and must survive).
  import jax.numpy as jnp
  from graphlearn_tpu.ops import unique_stable
  res = unique_stable(jnp.array([10, 1, 2, 3], jnp.int32), capacity=3)
  np.testing.assert_array_equal(np.asarray(res.values), [10, 1, 2])
  np.testing.assert_array_equal(np.asarray(res.inverse), [0, 1, 2, -1])


def test_unique_capacity_larger_than_input():
  res = unique_stable(jnp.array([7, 7, 5], jnp.int32), capacity=10)
  assert int(res.count) == 2
  np.testing.assert_array_equal(np.asarray(res.values[:2]), [7, 5])
  assert (np.asarray(res.values[2:]) == -1).all()


def test_inducer_overflow_keeps_existing_table():
  # Regression: existing table entries must keep their local indices on
  # overflow; only new arrivals get dropped.
  state, _ = init_node(jnp.array([100, 5], jnp.int32), capacity=4)
  nbrs = jnp.array([[1, 2, 3]], jnp.int32)
  state2, rows, cols, _ = induce_next(state, jnp.array([0]), nbrs,
                                      nbrs >= 0)
  nodes = np.asarray(state2.nodes)
  np.testing.assert_array_equal(nodes, [100, 5, 1, 2])  # 3 dropped
  # dropped neighbor's edge is masked out
  np.testing.assert_array_equal(np.asarray(rows), [2, 3, -1])


@pytest.mark.parametrize('seeds,hops,final', [
    (4, [(4, 3), (12, 2), (24, 2)], 88),   # never clamps: 4 + 12 + 24 + 48
    (4, [(4, 3), (12, 2), (24, 2)], 24),   # the last two insertions clamp
    (6, [(6, 5), (30, 1)], 8),             # overflows at the first
    (0, [(3, 4), (12, 2)], 40),            # a table that starts empty
])
def test_induce_next_grown_table_equals_final_capacity(seeds, hops, final):
  """A table handed in at the rows filled so far and asked back at
  ``min(rows + B*k, final)`` gives, insertion for insertion, the ids,
  count, ``rows`` and ``cols`` of a table held at ``final`` from the
  start — overflow past ``final`` drops the latest-appearing ids in
  both — from a sort of ``rows + B*k`` elements."""
  rng = np.random.default_rng(seeds + final)
  ids = jnp.asarray(rng.choice(1000, seeds, replace=False), jnp.int32)
  grown, _ = init_node(ids, seeds)
  whole, _ = init_node(ids, final)
  for b, k in hops:
    src = jnp.asarray(rng.integers(-1, 5, b), jnp.int32)
    nbrs = jnp.asarray(rng.integers(0, 60, (b, k)), jnp.int32)
    mask = jnp.asarray(rng.random((b, k)) < 0.8)
    held = grown.nodes.shape[0]
    cap = min(held + b * k, final)
    grown, rows_g, cols_g, start_g = induce_next(grown, src, nbrs, mask,
                                                 capacity=cap)
    whole, rows_w, cols_w, start_w = induce_next(whole, src, nbrs, mask)
    assert grown.nodes.shape == (cap,)
    assert int(grown.count) == int(whole.count) == int(
        min(whole.count, final))
    np.testing.assert_array_equal(np.asarray(grown.nodes),
                                  np.asarray(whole.nodes[:cap]))
    assert (np.asarray(whole.nodes[cap:]) == -1).all()
    np.testing.assert_array_equal(np.asarray(rows_g), np.asarray(rows_w))
    np.testing.assert_array_equal(np.asarray(cols_g), np.asarray(cols_w))
    assert int(start_g) == int(start_w)


# -- the form before its sorts carried their payloads, as the oracle ---

def _unique_stable_before(x, capacity, fill_value=-1, valid=None):
  """`unique_stable` as it stood when it fetched `xs`, `vals_by_rank`,
  `values`, the heads' ranks and `inverse` with five permutation
  gathers, verbatim (the `jit` decorator and the docstring aside)."""
  n = x.shape[0]
  if n == 0:
    return (jnp.full((capacity,), fill_value, x.dtype),
            jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32))
  if valid is None:
    valid = x != fill_value
  else:
    valid = valid & (x != fill_value)
  big = jnp.iinfo(x.dtype).max
  xv = jnp.where(valid, x, big)

  order = jnp.argsort(xv, stable=True)          # positions sorted by value
  xs = xv[order]
  head = jnp.concatenate([jnp.ones((1,), bool), xs[1:] != xs[:-1]])
  head = head & (xs != big)
  uid = jnp.where(xs != big, jnp.cumsum(head) - 1, n)

  count = jnp.minimum(jnp.sum(head), capacity)

  first_pos = jnp.where(head, order, jnp.iinfo(jnp.int32).max)
  rank_to_sorted = jnp.argsort(first_pos)       # appearance rank -> sorted pos
  vals_by_rank = xs[rank_to_sorted]             # [n] value of rank j
  slot = jnp.arange(capacity)
  values = jnp.where(slot < count,
                     vals_by_rank[jnp.clip(slot, 0, n - 1)].astype(x.dtype),
                     fill_value)

  head_pos = jax.lax.cummax(
      jnp.where(head, jnp.arange(n, dtype=jnp.int32), -1))
  sorted_to_rank = jnp.argsort(rank_to_sorted)  # sorted pos -> rank
  inv_sorted = jnp.where(
      (uid < n) & (head_pos >= 0),
      sorted_to_rank[jnp.clip(head_pos, 0, n - 1)], -1)
  inv_sorted = jnp.where(inv_sorted < capacity, inv_sorted, -1)
  inverse = inv_sorted[jnp.argsort(order)]
  return values, inverse, count


def _random_ids(rng, n, hi, invalid):
  x = rng.integers(0, hi, n).astype(np.int32)
  x[rng.random(n) < invalid] = -1
  return x


# name -> (n, ids drawn below, share of invalid ids, share kept by a
# `valid` mask or None, capacity)
UNIQUE_CASES = {
    'one-element': (1, 5, 0.0, None, 1),
    'one-invalid-element': (1, 5, 1.0, None, 3),
    'two-elements-capacity-above-n': (2, 2, 0.0, None, 7),
    'all-invalid': (40, 9, 1.0, None, 40),
    'all-masked': (40, 9, 0.0, 0.0, 16),
    'all-one-id': (300, 1, 0.0, None, 300),
    'heavy-duplicates': (1000, 10, 0.0, None, 1000),
    'invalid-ids': (1000, 100, 0.3, None, 1000),
    'valid-mask': (1000, 100, 0.0, 0.6, 1000),
    'invalid-ids-and-mask': (1000, 100, 0.2, 0.7, 1000),
    'overflow': (1000, 10_000, 0.1, 0.9, 200),
    'overflow-to-one': (257, 10_000, 0.0, None, 1),
    'capacity-above-n': (257, 10_000, 0.1, 0.9, 1000),
    'distinct': (4099, 2**31 - 1, 0.0, None, 4099),
    'largest-id-valid': (64, 2**31 - 1, 0.0, None, 64),
    # past 2**15 elements the heads' ranks travel in two chunks
    'two-chunks': (40_000, 5_000, 0.1, 0.9, 40_000),
    'two-chunks-overflow': (70_001, 2**31 - 1, 0.05, None, 33_000),
    # and past 2**20 in three (the flagship's largest dedup, 937,984
    # elements, is the last size that needs two)
    'three-chunks': (1_048_600, 3_000_000, 0.1, 0.9, 1_048_600),
}


@pytest.mark.parametrize('case', sorted(UNIQUE_CASES))
@pytest.mark.parametrize('seed', [0, 1, 2])
def test_unique_stable_equals_the_gathering_form(case, seed):
  """`values`, `inverse` and `count` exactly, dtype and shape included:
  integers only, nothing to round."""
  n, hi, invalid, kept, capacity = UNIQUE_CASES[case]
  rng = np.random.default_rng(1000 * seed + n)
  x = _random_ids(rng, n, hi, invalid)
  if case == 'largest-id-valid':
    x[::3] = 2**31 - 1           # the sentinel's own value, stated valid
  valid = None if kept is None else jnp.asarray(rng.random(n) < kept)
  got = unique_stable(jnp.asarray(x), capacity, valid=valid)
  want = _unique_stable_before(jnp.asarray(x), capacity, valid=valid)
  for name, a, b in zip(got._fields, got, want):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, name
    np.testing.assert_array_equal(a, b, err_msg=name)


def test_unique_stable_empty_input():
  res = unique_stable(jnp.zeros((0,), jnp.int32), capacity=4)
  np.testing.assert_array_equal(np.asarray(res.values), [-1] * 4)
  assert res.inverse.shape == (0,) and int(res.count) == 0


@pytest.mark.parametrize('seeds,hops,final', [
    (4, [(4, 3), (12, 2), (24, 2)], 88),   # never clamps
    (4, [(4, 3), (12, 2), (24, 2)], 24),   # the last two insertions clamp
    (6, [(6, 5), (30, 1)], 8),             # overflows at the first
    (0, [(3, 4), (12, 2)], 40),            # a table that starts empty
    (64, [(64, 15), (960, 10), (9600, 5)], 40_000),   # two chunks, clamps
])
def test_induce_next_on_grown_tables_equals_the_gathering_form(
    seeds, hops, final, monkeypatch):
  """Insertion for insertion over tables that grow: the table, its
  count, `rows`, `cols` and the frontier's start of `induce_next` are
  those of `induce_next` around the gathering form."""
  rng = np.random.default_rng(seeds + final)
  ids = jnp.asarray(rng.choice(100_000, seeds, replace=False), jnp.int32)
  state, _ = init_node(ids, seeds)
  for b, k in hops:
    src = jnp.asarray(rng.integers(-1, max(seeds, 2), b), jnp.int32)
    nbrs = jnp.asarray(rng.integers(0, 20 * b, (b, k)), jnp.int32)
    mask = jnp.asarray(rng.random((b, k)) < 0.8)
    cap = min(state.nodes.shape[0] + b * k, final)
    got = induce_next(state, src, nbrs, mask, capacity=cap)
    with monkeypatch.context() as m:
      m.setattr(unique_module, 'unique_stable',
                lambda x, capacity, valid: unique_module.UniqueResult(
                    *_unique_stable_before(x, capacity, valid=valid)))
      want = induce_next(state, src, nbrs, mask, capacity=cap)
    for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                     strict=True):
      a, b_ = np.asarray(a), np.asarray(b_)
      assert a.dtype == b_.dtype and a.shape == b_.shape
      np.testing.assert_array_equal(a, b_)
    state = got[0]
