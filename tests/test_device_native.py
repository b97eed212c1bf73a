"""Device-native construction path: `Graph.from_device_arrays`,
device `Feature`, device labels — the zero-upload setup
`chip_smoke.py` uses (its `build_graph_csr_device`).

The contract under test: a Dataset built from device arrays behaves
identically to one built from the same arrays via the host path.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from graphlearn_tpu.data import Dataset
from graphlearn_tpu.loader import NeighborLoader


def _device_dataset(n, indptr, indices, feats, labels):
  return (Dataset()
          .init_graph((jnp.asarray(indptr), jnp.asarray(indices)),
                      layout='CSR', num_nodes=n)
          .init_node_features(jnp.asarray(feats))
          .init_node_labels(jnp.asarray(labels)))


def _host_dataset(n, indptr, indices, feats, labels):
  return (Dataset()
          .init_graph((indptr, indices), layout='CSR', num_nodes=n)
          .init_node_features(feats)
          .init_node_labels(labels))


@pytest.fixture(scope='module')
def tiny():
  rng = np.random.default_rng(0)
  n, e = 200, 1600
  rows = rng.integers(0, n, e)
  cols = rng.integers(0, n, e).astype(np.int64)
  # canonical sorted-CSR: the device path trusts its input as-is
  order = np.lexsort((cols, rows))
  rows, cols = rows[order], cols[order]
  indptr = np.searchsorted(rows, np.arange(n + 1)).astype(np.int64)
  feats = rng.random((n, 8), np.float32)
  labels = rng.integers(0, 5, n).astype(np.int32)
  return n, indptr, cols, feats, labels


def test_device_graph_metadata(tiny):
  n, indptr, cols, feats, labels = tiny
  ds = _device_dataset(n, indptr, cols, feats, labels)
  g = ds.get_graph()
  assert g.num_nodes == n
  assert g.num_edges == len(cols)
  assert g.max_degree == int(np.max(np.diff(indptr)))
  assert g.indices.dtype == jnp.int32


def test_device_feature_matches_host(tiny):
  n, indptr, cols, feats, labels = tiny
  dev = _device_dataset(n, indptr, cols, feats, labels)
  host = _host_dataset(n, indptr, cols, feats, labels)
  ids = jnp.asarray([0, 3, -1, n - 1], jnp.int32)
  np.testing.assert_allclose(np.asarray(dev.node_features[ids]),
                             np.asarray(host.node_features[ids]))
  # host-side access works through the shim (one lazy pull)
  np.testing.assert_allclose(dev.node_features.host_get([2, 5]),
                             host.node_features.host_get([2, 5]))


def test_device_feature_rejects_cold_tier(tiny):
  n, indptr, cols, feats, labels = tiny
  with pytest.raises(ValueError, match='split_ratio'):
    Dataset().init_node_features(jnp.asarray(feats), split_ratio=0.5)


def test_device_loader_parity(tiny):
  """Same seed → identical batches from the device- and host-built
  datasets (the sampler consumes the same CSR either way)."""
  n, indptr, cols, feats, labels = tiny
  dev = _device_dataset(n, indptr, cols, feats, labels)
  host = _host_dataset(n, indptr, cols, feats, labels)
  seeds = np.arange(0, n, 2)
  for ds_a, ds_b in ((dev, host),):
    la = NeighborLoader(ds_a, [3, 2], seeds, batch_size=32, shuffle=False)
    lb = NeighborLoader(ds_b, [3, 2], seeds, batch_size=32, shuffle=False)
    for ba, bb in zip(la, lb):
      np.testing.assert_array_equal(np.asarray(ba.node),
                                    np.asarray(bb.node))
      np.testing.assert_allclose(np.asarray(ba.x), np.asarray(bb.x))
      np.testing.assert_array_equal(np.asarray(ba.y), np.asarray(bb.y))


def test_build_graph_csr_device_valid():
  import sys, os
  sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
  from chip_smoke import build_graph_csr_device
  n = 500
  indptr, indices, eids = build_graph_csr_device(num_nodes=n, avg_deg=4,
                                                 seed=1)
  indptr_h = np.asarray(indptr)
  assert indptr_h[0] == 0 and indptr_h[-1] == n * 4
  assert np.all(np.diff(indptr_h) >= 0)
  assert np.asarray(indices).min() >= 0
  assert np.asarray(indices).max() < n
  # determinism across calls (cross-session comparability contract)
  indptr2, indices2, _ = build_graph_csr_device(num_nodes=n, avg_deg=4,
                                                seed=1)
  np.testing.assert_array_equal(np.asarray(indptr), np.asarray(indptr2))
  np.testing.assert_array_equal(np.asarray(indices), np.asarray(indices2))


def test_device_native_hetero_dataset():
  """Per-etype device CSR + device feature/label dicts (the bench's
  hetero session path) behave like the host construction."""
  rng = np.random.default_rng(2)
  nu, ni, e = 60, 40, 300
  rows = rng.integers(0, nu, e)
  cols = rng.integers(0, ni, e)
  order = np.lexsort((cols, rows))
  rows, cols = rows[order], cols[order]
  indptr = np.searchsorted(rows, np.arange(nu + 1)).astype(np.int64)
  fu = rng.random((nu, 6), np.float32)
  fi = rng.random((ni, 6), np.float32)
  lab = rng.integers(0, 3, nu).astype(np.int32)
  et = ('u', 'to', 'i')
  ds = (Dataset()
        .init_graph({et: (jnp.asarray(indptr), jnp.asarray(cols))},
                    layout='CSR', num_nodes={'u': nu, 'i': ni})
        .init_node_features({'u': jnp.asarray(fu), 'i': jnp.asarray(fi)})
        .init_node_labels({'u': jnp.asarray(lab)}))
  g = ds.get_graph(et)
  assert g.num_edges == e
  assert ds.num_nodes_dict() == {'u': nu, 'i': ni}
  np.testing.assert_array_equal(
      np.asarray(ds.get_node_label_device('u')), lab)
  ids = jnp.asarray([0, 5, -1], jnp.int32)
  np.testing.assert_allclose(
      np.asarray(ds.node_features['i'][ids]),
      np.vstack([fi[[0, 5]], np.zeros((1, 6), np.float32)]))
