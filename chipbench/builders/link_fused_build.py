"""The `sage-products-link` configuration's data, made on the device
from ``--seed``: the flagship's graph, table and weights
(`chipbench.build.device_data`, the very recipe of `sage-products`,
with the model's output as wide as its hidden layers, as the
unsupervised recipe's embedding is) and the seed edges — positive
pairs drawn uniformly over the graph's edge slots."""
import numpy as np

from chipbench import build


def embedding_cfg(cfg: dict) -> dict:
  """The configuration as `chipbench.build` reads it: the last layer's
  width (``classes`` there) is the embedding's, ``hidden``."""
  return dict(cfg, classes=int(cfg['hidden']))


def layer_dims(cfg: dict):
  return build.layer_dims(embedding_cfg(cfg))


def device_data(cfg: dict, seed: int):
  """``(indptr, indices, feats, layers)`` as `build.device_data` makes
  them from the seed; its labels are dropped at once (a link loss
  reads none)."""
  indptr, indices, feats, _, layers = build.device_data(
      embedding_cfg(cfg), seed)
  return indptr, indices, feats, layers


def seed_edges(indptr, indices, count: int, seed: int):
  """``count`` positive pairs ``(src, dst)`` on the host, ``dst`` in
  ``src``'s CSR row: edge slots drawn uniformly without replacement
  from the seed, each slot's row found in ``indptr``."""
  import jax
  import jax.numpy as jnp
  rng = np.random.default_rng(build.fold_seed(seed) + 2)
  slots = np.sort(rng.choice(int(indices.shape[0]), count, replace=False))

  @jax.jit
  def ends(indptr, indices, slots):
    src = jnp.searchsorted(indptr, slots, side='right') - 1
    return src.astype(jnp.int32), indices[slots]

  src, dst = ends(indptr, indices, jnp.asarray(slots, jnp.int32))
  order = rng.permutation(count)
  return np.asarray(src)[order], np.asarray(dst)[order]
