"""OGB on-disk layout ingestion: raw CSV + binary
layouts round-trip into Dataset / partition layout; the accuracy
harness' ingestion path learns on a synthetic OGB-layout dataset.
Real ogbn-products accuracy asserts in `examples/acc_ogbn_products.py`
wherever the data exists (clean SKIP offline)."""
import gzip

import numpy as np
import pytest

from graphlearn_tpu.data import (Dataset, load_ogb_dir, ogb_to_dataset,
                                 partition_ogb, save_binary)

N, E, D = 30, 90, 5


def _write_raw(root, with_split=True):
  rng = np.random.default_rng(0)
  rows = rng.integers(0, N, E)
  cols = rng.integers(0, N, E)
  feats = rng.normal(size=(N, D)).astype(np.float32)
  feats[:, 0] = np.arange(N)
  labels = (np.arange(N) % 4).astype(np.int64)
  raw = root / 'raw'
  raw.mkdir(parents=True)
  with gzip.open(raw / 'edge.csv.gz', 'wt') as f:
    for r, c in zip(rows, cols):
      f.write(f'{r},{c}\n')
  with gzip.open(raw / 'node-feat.csv.gz', 'wt') as f:
    for row in feats:
      f.write(','.join(f'{v:.6f}' for v in row) + '\n')
  with gzip.open(raw / 'node-label.csv.gz', 'wt') as f:
    for v in labels:
      f.write(f'{v}\n')
  with gzip.open(raw / 'num-node-list.csv.gz', 'wt') as f:
    f.write(f'{N}\n')
  if with_split:
    sp = root / 'split' / 'sales_ranking'
    sp.mkdir(parents=True)
    idx = np.arange(N)
    for name, sl in (('train', idx[:20]), ('valid', idx[20:25]),
                     ('test', idx[25:])):
      with gzip.open(sp / f'{name}.csv.gz', 'wt') as f:
        for v in sl:
          f.write(f'{v}\n')
  return rows, cols, feats, labels


def test_raw_csv_layout(tmp_path):
  rows, cols, feats, labels = _write_raw(tmp_path)
  d = load_ogb_dir(tmp_path)
  assert d['num_nodes'] == N
  np.testing.assert_array_equal(d['edge_index'][0], rows)
  np.testing.assert_array_equal(d['edge_index'][1], cols)
  np.testing.assert_allclose(d['node_feat'], feats, atol=1e-5)
  np.testing.assert_array_equal(d['node_label'], labels)
  np.testing.assert_array_equal(d['train_idx'], np.arange(20))
  np.testing.assert_array_equal(d['test_idx'], np.arange(25, N))


def test_binary_roundtrip(tmp_path):
  rows, cols, feats, labels = _write_raw(tmp_path)
  out = tmp_path / 'bin'
  save_binary(tmp_path, out)
  d = load_ogb_dir(out)
  assert d['num_nodes'] == N
  np.testing.assert_array_equal(d['edge_index'][0], rows)
  np.testing.assert_allclose(d['node_feat'], feats, atol=1e-5)
  np.testing.assert_array_equal(d['node_label'], labels)
  np.testing.assert_array_equal(d['valid_idx'], np.arange(20, 25))


def test_ogb_to_dataset_and_partition(tmp_path):
  rows, cols, feats, labels = _write_raw(tmp_path)
  ds, splits = ogb_to_dataset(tmp_path)
  assert isinstance(ds, Dataset)
  got = np.asarray(ds.get_node_feature().host_get(np.arange(N)))
  np.testing.assert_allclose(got[:, 0], np.arange(N), atol=1e-5)
  np.testing.assert_array_equal(np.asarray(ds.get_node_label()), labels)
  assert set(splits) == {'train', 'valid', 'test'}
  # partition layout feeds the distributed loaders
  pdir = tmp_path / 'part'
  partition_ogb(tmp_path, pdir, 2)
  from graphlearn_tpu.parallel import DistDataset
  dd = DistDataset.from_partition_dir(pdir)
  assert dd.num_partitions == 2
  assert dd.graph.num_nodes == N


def test_sort_hot_split(tmp_path):
  _write_raw(tmp_path)
  ds, _ = ogb_to_dataset(tmp_path, split_ratio=0.5, sort_hot=True)
  feat = ds.get_node_feature()
  assert feat.hot_rows == N // 2
  got = np.asarray(feat.host_get(np.arange(N)))
  np.testing.assert_allclose(got[:, 0], np.arange(N), atol=1e-5)


def test_accuracy_harness_ingestion_path(tmp_path):
  """The acc harness' exact pipeline (ogb_to_dataset -> NeighborLoader
  -> GraphSAGE) learns a clustered OGB-layout dataset to high accuracy
  — validates everything but the real download."""
  import sys
  from pathlib import Path
  sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
  from examples._synthetic import clustered_graph
  rows, cols, feats, labels = clustered_graph(n=600, deg=8, classes=4,
                                              d=16, seed=0)
  out = tmp_path / 'bin'
  out.mkdir()
  np.save(out / 'edge_index.npy', np.stack([rows, cols]))
  np.save(out / 'node_feat.npy', feats)
  np.save(out / 'node_label.npy', labels.astype(np.int64))
  idx = np.random.default_rng(0).permutation(600)
  np.save(out / 'train_idx.npy', idx[:400])
  np.save(out / 'test_idx.npy', idx[400:])

  import jax
  import optax
  from graphlearn_tpu.data import ogb_to_dataset
  from graphlearn_tpu.loader import NeighborLoader
  from graphlearn_tpu.models import (GraphSAGE, create_train_state,
                                     make_eval_step,
                                     make_supervised_step)
  ds, splits = ogb_to_dataset(out)
  train_loader = NeighborLoader(ds, [5, 5], splits['train'],
                                batch_size=64, shuffle=True, seed=0)
  test_loader = NeighborLoader(ds, [5, 5], splits['test'], batch_size=64)
  model = GraphSAGE(hidden_features=32, out_features=4, num_layers=2)
  tx = optax.adam(5e-3)
  state, apply_fn = create_train_state(
      model, jax.random.key(0), next(iter(train_loader)), tx)
  step = make_supervised_step(apply_fn, tx, 64)
  eval_step = make_eval_step(apply_fn, 64)
  for _ in range(5):
    for batch in train_loader:
      state, _, _ = step(state, batch)
  correct = total = 0
  for batch in test_loader:
    c, t = eval_step(state.params, batch)
    correct += int(c)
    total += int(t)
  assert correct / total > 0.9, correct / total


def test_multitask_labels_keep_shape(tmp_path):
  """Multi-column label tables (ogbn-proteins style) must keep [N, K]
  — flattening would silently misalign labels with nodes."""
  _write_raw(tmp_path)
  raw = tmp_path / 'raw'
  (raw / 'node-label.csv.gz').unlink()
  lab = np.arange(N * 3).reshape(N, 3)
  with gzip.open(raw / 'node-label.csv.gz', 'wt') as f:
    for row in lab:
      f.write(','.join(str(v) for v in row) + '\n')
  d = load_ogb_dir(tmp_path)
  assert d['node_label'].shape == (N, 3)
  np.testing.assert_array_equal(d['node_label'], lab)
  out = tmp_path / 'bin'
  save_binary(tmp_path, out)
  d2 = load_ogb_dir(out)
  assert d2['node_label'].shape == (N, 3)
