"""RDMA feature-exchange prototype vs the all_to_all reference path.

Interpret-mode validation on the virtual CPU mesh:
the per-row remote-DMA gather must return exactly what
`dist_gather` returns for the same sharded table and id sets —
including invalid ids and capacity-dropped slots.
"""
import numpy as np
import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from graphlearn_tpu.parallel import make_mesh
from graphlearn_tpu.parallel.dist_sampler import dist_gather
from graphlearn_tpu.parallel.rdma_gather import rdma_gather
from graphlearn_tpu.parallel.shard_map_compat import shard_map

NP = 8
ROWS = 16          # per shard
D = 8


def _setup():
  mesh = make_mesh(NP)
  bounds = np.arange(NP + 1, dtype=np.int64) * ROWS
  # shard p row r holds value (global id = p*ROWS + r) in every column
  shards = np.arange(NP * ROWS, dtype=np.float32).reshape(
      NP, ROWS)[:, :, None] * np.ones((1, 1, D), np.float32)
  return mesh, bounds, shards


def _run(fn, mesh, shards, bounds, ids, **kw):
  sh = NamedSharding(mesh, P('data'))
  rp = NamedSharding(mesh, P())

  def per_dev(shard_s, bounds_r, ids_s):
    return fn(shard_s[0], bounds_r, ids_s[0], 'data', NP, **kw)[None]

  f = shard_map(per_dev, mesh=mesh, in_specs=(P('data'), P(), P('data')),
                out_specs=P('data'))
  return np.asarray(jax.jit(f)(
      jax.device_put(shards, sh), jax.device_put(bounds, rp),
      jax.device_put(ids, sh)))


def test_rdma_gather_matches_all_to_all():
  mesh, bounds, shards = _setup()
  rng = np.random.default_rng(0)
  ids = rng.integers(0, NP * ROWS, (NP, 24)).astype(np.int32)
  ids[0, 3] = -1                      # invalid slots return zero rows
  ids[5, 0] = -1
  ref = _run(dist_gather, mesh, shards, bounds, ids)
  got = _run(rdma_gather, mesh, shards, bounds, ids)
  np.testing.assert_allclose(got, ref)
  # value check against first principles too
  for p in range(NP):
    for i, gid in enumerate(ids[p]):
      expect = 0.0 if gid < 0 else float(gid)
      assert got[p, i, 0] == expect, (p, i, gid)


def test_rdma_gather_respects_capacity_drops():
  mesh, bounds, shards = _setup()
  # all ids owned by partition 0 -> a capacity of 8 drops the tail
  ids = np.tile(np.arange(12, dtype=np.int32), (NP, 1))
  got = _run(rdma_gather, mesh, shards, bounds, ids,
             exchange_capacity=8)
  for p in range(NP):
    kept = (got[p, :, 0] != 0).sum()
    assert kept <= 8
    for i in range(12):
      v = got[p, i, 0]
      assert v == float(ids[p, i]) or v == 0.0
