"""End-to-end .npz ingestion with the REAL ogbn-products export schema.

The ingestion path must run against a
products-schema file.  This test writes an `.npz` with the exact
shapes/dtypes a straight OGB export produces (int64 COO, float32
[N, 100] features, labels in OGB's [N, 1] layout with a float/nan
variant) and runs `examples/train_sage.py` end-to-end on it, enforcing
the example-level accuracy acceptance (``--expect-acc``, the
clustered-graph threshold pattern promoted from tests/test_models.py).
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def _products_schema_npz(path, n=4000, d=100, classes=12, seed=0):
  from examples._synthetic import clustered_graph
  rows, cols, feats, labels = clustered_graph(n=n, deg=8,
                                              classes=classes, d=d,
                                              seed=seed)
  idx = np.random.default_rng(seed).permutation(n)
  # OGB label layout: [N, 1] float with nan for unlabeled nodes
  lab = labels.astype(np.float32)[:, None]
  lab[idx[-5:], 0] = np.nan
  np.savez(path,
           rows=rows.astype(np.int64), cols=cols.astype(np.int64),
           feats=feats.astype(np.float32), labels=lab,
           train_idx=idx[:int(n * .6)].astype(np.int64),
           val_idx=idx[int(n * .6):int(n * .8)].astype(np.int64),
           test_idx=idx[int(n * .8):n - 5].astype(np.int64))


@pytest.mark.slow
@pytest.mark.parametrize('split_ratio', ['1.0', '0.5'])
def test_train_sage_on_products_schema_npz(tmp_path, split_ratio):
  npz = tmp_path / 'products_schema.npz'
  _products_schema_npz(npz)
  env = dict(os.environ)
  env['JAX_PLATFORMS'] = 'cpu'
  env['PYTHONPATH'] = str(REPO) + os.pathsep + env.get('PYTHONPATH', '')
  out = subprocess.run(
      [sys.executable, str(REPO / 'examples' / 'train_sage.py'),
       '--data', str(npz), '--epochs', '2', '--batch-size', '512',
       '--fanout', '5', '3', '--hidden', '64',
       '--split-ratio', split_ratio, '--expect-acc', '0.5'],
      env=env, capture_output=True, text=True, timeout=600)
  assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
  assert 'test acc:' in out.stdout
