"""Data of the `mesh_sharded` builder: a graph, a table and labels that
no host and no single device ever holds whole, each piece a function
of ``--seed`` that any device can compute for itself.

  coo         the products recipe (uniform sources, 30 % of targets
              squared-uniform: `chipbench.build.device_data`'s), drawn
              block by block: device ``d`` of the mesh draws edges
              ``[d * E/P, (d + 1) * E/P)`` under its own key
              (`coo_block`), so the ``[E]`` COO exists only sharded;
  feat_rows   row ``i`` of the table is ``uniform(fold_in(key, i))``:
              the program's shards are filled by
              `DistDataset.from_device_coo` calling this on the ids each
              device owns, and the comparison recomputes the very rows
              a tree names — no copy of the table anywhere;
  label_rows  likewise ``randint(fold_in(key, i))``.

Every key is an ARGUMENT of the program that uses it, never a value it
closes over: a closed-over key is a constant of the compiled program,
and every seed would compile its own.

Weights are `chipbench.build.host_layers`' (small, host).  The edge
capacity is the configuration's: the mean edge count of a device plus
the stated margin, rounded up to the stated multiple
(`edge_capacity`).
"""
import numpy as np

from chipbench import build as base

AXIS = 'data'


def keys(seed):
  import jax
  key = jax.random.key(base.fold_seed(seed))
  return dict(graph=jax.random.fold_in(key, 0),
              feats=jax.random.fold_in(key, 1),
              labels=jax.random.fold_in(key, 2))


def num_edges(cfg) -> int:
  return int(cfg['num_nodes']) * int(cfg['avg_degree'])


def edge_capacity(cfg) -> int:
  """Width of one device's ``indices``, from what the configuration
  states (``edge_capacity``: ``margin`` over the mean per device,
  rounded up to ``multiple``)."""
  stated = cfg['edge_capacity']
  mean = num_edges(cfg) / int(cfg['chips'])
  cap = int(np.ceil(mean * (1.0 + float(stated['margin']))))
  mult = int(stated['multiple'])
  return -(-cap // mult) * mult


def coo_block(key, block, count: int, n: int):
  """``(rows, cols)`` of block ``block`` of the seed's COO: ``count``
  edges, original ids."""
  import jax
  import jax.numpy as jnp
  k1, k2, k3 = jax.random.split(jax.random.fold_in(key, block), 3)
  rows = jax.random.randint(k1, (count,), 0, n, jnp.int32)
  hub = jax.random.uniform(k2, (count,)) < 0.3
  u = jax.random.uniform(k3, (count,))
  cols = jnp.where(hub, (u * u * n).astype(jnp.int32),
                   (u * n).astype(jnp.int32))
  return rows, cols


def coo(cfg, seed, mesh):
  """The seed's COO as two ``[E]`` device arrays sharded over the
  mesh: each device draws its own block."""
  import jax
  from jax.sharding import PartitionSpec as P
  p, n = int(cfg['chips']), int(cfg['num_nodes'])
  if num_edges(cfg) % p:
    raise ValueError('num_nodes * avg_degree must be a multiple of chips')
  count = num_edges(cfg) // p
  draw = lambda key: coo_block(key, jax.lax.axis_index(AXIS), count, n)
  return jax.jit(jax.shard_map(
      draw, mesh=mesh, in_specs=P(), out_specs=(P(AXIS), P(AXIS)),
      check_vma=False))(keys(seed)['graph'])


def feat_rows(cfg):
  """``f(ids, key) -> [len(ids), feature_dim]`` float32 rows of the
  table of the seed whose ``keys(seed)['feats']`` is ``key`` (``ids``
  original, valid)."""
  import jax
  import jax.numpy as jnp
  dim = int(cfg['feature_dim'])
  return lambda ids, key: jax.vmap(lambda i: jax.random.uniform(
      jax.random.fold_in(key, i), (dim,), jnp.float32))(ids)


def label_rows(cfg):
  """``f(ids, key) -> [len(ids)]`` int32 labels (``keys(seed)['labels']``)."""
  import jax
  import jax.numpy as jnp
  classes = int(cfg['classes'])
  return lambda ids, key: jax.vmap(lambda i: jax.random.randint(
      jax.random.fold_in(key, i), (), 0, classes, jnp.int32))(ids)


def dataset(cfg, seed, mesh):
  """The partitioned dataset, every shard built on the mesh at the
  configuration's capacity."""
  from graphlearn_tpu.parallel import DistDataset
  rows, cols = coo(cfg, seed, mesh)
  return DistDataset.from_device_coo(
      int(cfg['chips']), rows, cols, num_nodes=int(cfg['num_nodes']),
      edge_capacity=edge_capacity(cfg),
      node_feat=(feat_rows(cfg), (keys(seed)['feats'],)),
      node_label=(label_rows(cfg), (keys(seed)['labels'],)), mesh=mesh,
      axis=AXIS, seed=base.fold_seed(seed))
