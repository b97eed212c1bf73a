"""Graph, table, labels and weights, made on the device from ``--seed``.

The graph recipe is a copy of `benchmarks/common.build_graph_csr_device`
(0.3 hub mixture, squared-uniform hub targets, rows sorted, columns
ascending within a row) — copied, not imported, so the yardstick cannot
move under a later PR.  Weights are drawn here too, in the layout-free
form the plain reference reads (`chipbench.reference`): a list of
``(w_self, b_self, w_neigh)`` per layer; `program_params` folds them
into the flax tree the program's model wants.
"""
from __future__ import annotations


import numpy as np

SEED_MASK = (1 << 31) - 1


def fold_seed(seed: int) -> int:
  """``--seed`` may pass 2**31; every RNG here takes 31 bits."""
  seed = int(seed)
  return (seed ^ (seed >> 31)) & SEED_MASK


def layer_dims(cfg: dict):
  dims = [cfg['feature_dim']] + [cfg['hidden']] * (cfg['num_layers'] - 1)
  outs = [cfg['hidden']] * (cfg['num_layers'] - 1) + [cfg['classes']]
  return list(zip(dims, outs))


def device_data(cfg: dict, seed: int):
  """``(indptr, indices, feats, labels, layers)`` as device arrays from
  two jitted calls: the CSR sort, and everything else."""
  import jax
  import jax.numpy as jnp
  n, deg = int(cfg['num_nodes']), int(cfg['avg_degree'])
  dim, classes = int(cfg['feature_dim']), int(cfg['classes'])
  dims = layer_dims(cfg)

  @jax.jit
  def graph(key):
    e = n * deg
    k1, k2, k3 = jax.random.split(key, 3)
    rows = jax.random.randint(k1, (e,), 0, n, jnp.int32)
    hub = jax.random.uniform(k2, (e,)) < 0.3
    u = jax.random.uniform(k3, (e,))
    cols = jnp.where(hub, (u * u * n).astype(jnp.int32),
                     (u * n).astype(jnp.int32))
    return _sorted_csr(rows, cols, n)

  @jax.jit
  def rest(key):
    kf, kl, kw = jax.random.split(key, 3)
    feats = jax.random.uniform(kf, (n, dim), jnp.float32)
    labels = jax.random.randint(kl, (n,), 0, classes, jnp.int32)
    layers = []
    for i, (din, dout) in enumerate(dims):
      ks, kn, kb = jax.random.split(jax.random.fold_in(kw, i), 3)
      scale = 1.0 / np.sqrt(din)
      layers.append((
          jax.random.normal(ks, (din, dout), jnp.float32) * scale,
          jax.random.normal(kb, (dout,), jnp.float32) * 0.01,
          jax.random.normal(kn, (din, dout), jnp.float32) * scale))
    return feats, labels, layers

  key = jax.random.key(fold_seed(seed))
  indptr, indices = graph(jax.random.fold_in(key, 0))
  feats, labels, layers = rest(jax.random.fold_in(key, 1))
  return indptr, indices, feats, labels, layers


def _sorted_csr(rows, cols, n):
  """``(indptr, indices)``: rows sorted, columns ascending within a
  row — one lexicographic sort of the pairs (the recipe's original
  makes two stable argsorts because it also wants edge ids)."""
  import jax
  import jax.numpy as jnp
  rows, cols = jax.lax.sort((rows, cols), num_keys=2)
  indptr = jnp.searchsorted(
      rows, jnp.arange(n + 1, dtype=jnp.int32),
      side='left').astype(jnp.int32)
  return indptr, cols


def device_csr(rows, cols, n: int):
  """The CSR of a host COO, sorted on the first device."""
  import jax
  import jax.numpy as jnp
  return jax.jit(_sorted_csr, static_argnums=(2,))(
      jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32), n)


def host_coo(cfg: dict, seed: int):
  """The same recipe on the host, for the mesh builder (its
  partitioner takes host COO): ``(rows, cols, feats, labels)``."""
  n, deg = int(cfg['num_nodes']), int(cfg['avg_degree'])
  rng = np.random.default_rng(fold_seed(seed))
  e = n * deg
  rows = rng.integers(0, n, e, dtype=np.int64)
  u = rng.random(e)
  cols = np.where(rng.random(e) < 0.3, u * u * n, u * n).astype(np.int64)
  feats = rng.random((n, int(cfg['feature_dim'])), np.float32)
  labels = rng.integers(0, int(cfg['classes']), n).astype(np.int32)
  return rows, cols, feats, labels


def host_layers(cfg: dict, seed: int):
  rng = np.random.default_rng(fold_seed(seed) + 1)
  layers = []
  for din, dout in layer_dims(cfg):
    scale = 1.0 / np.sqrt(din)
    layers.append((
        (rng.standard_normal((din, dout)) * scale).astype(np.float32),
        (rng.standard_normal((dout,)) * 0.01).astype(np.float32),
        (rng.standard_normal((din, dout)) * scale).astype(np.float32)))
  return layers


def program_params(model_kind: str, layers):
  """The flax tree of `models.tree.TreeSAGE` (``tree``) or
  `models.GraphSAGE` (``subgraph``) holding ``layers``."""
  p = {}
  for i, (ws, bs, wn) in enumerate(layers):
    if model_kind == 'tree':
      p[f'layer{i}_self'] = {'kernel': ws, 'bias': bs}
      p[f'layer{i}_neigh'] = {'kernel': wn}
    elif model_kind == 'subgraph':
      p[f'conv{i}'] = {'lin_self': {'kernel': ws, 'bias': bs},
                       'lin_neigh': {'kernel': wn}}
    else:
      raise ValueError(f'unknown model kind {model_kind!r}')
  return {'params': p}


def layers_of(model_kind: str, params):
  """Inverse of `program_params`, to host float32."""
  p = params['params']
  out, i = [], 0
  while True:
    if model_kind == 'tree' and f'layer{i}_self' in p:
      s, nb = p[f'layer{i}_self'], p[f'layer{i}_neigh']
    elif model_kind == 'subgraph' and f'conv{i}' in p:
      s, nb = p[f'conv{i}']['lin_self'], p[f'conv{i}']['lin_neigh']
    else:
      return out
    out.append(tuple(np.asarray(a, np.float32)
                     for a in (s['kernel'], s['bias'], nb['kernel'])))
    i += 1
