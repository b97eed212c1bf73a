"""Tests for the sort-based inducer (ops/unique.py).

Mirrors the coverage of reference `test/cpp/test_inducer.cu` /
`test_hash_table.cu`: dedup correctness, insertion-order preservation,
relabeling, capacity overflow.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from graphlearn_tpu.ops import induce_next, init_node, unique_stable


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
