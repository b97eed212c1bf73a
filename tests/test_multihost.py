"""Two-process jax.distributed smoke test.

Spawns 2 REAL ``jax.distributed`` CPU processes on localhost (4
virtual devices each -> one 8-device global mesh), runs
`multihost.initialize()` + a full DistNeighborLoader epoch + one DP
training step in each, and asserts: identical per-host seed-shard
schedules (disjoint, covering), equal finite losses (the psum'd DP
step is replicated), and matching batch counts.  The JAX analog of the
reference's localhost multi-role tests
(`test/python/dist_test_utils.py:15-120`) — no mocks, the real
cross-process runtime.
"""
import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

#: CPU-mesh scan-compile heavy (multi-minute): excluded from the
#: default run, selected by `pytest -m slow` (see pyproject.toml)
pytestmark = pytest.mark.slow


def _free_port() -> int:
  with socket.socket() as s:
    s.bind(('localhost', 0))
    return s.getsockname()[1]


def test_two_process_distributed_epoch(tmp_path):
  port = _free_port()
  worker = Path(__file__).parent / '_multihost_worker.py'
  env = dict(os.environ)
  env['JAX_PLATFORMS'] = 'cpu'
  flags = ' '.join(
      f for f in env.get('XLA_FLAGS', '').split()
      if '--xla_force_host_platform_device_count' not in f)
  env['XLA_FLAGS'] = (
      flags + ' --xla_force_host_platform_device_count=4').strip()
  env['PYTHONPATH'] = (str(Path(__file__).resolve().parent.parent)
                       + os.pathsep + env.get('PYTHONPATH', ''))
  # partition layout for the HOST-LOCAL loading phase: each process
  # materializes only its 4 mesh positions' shards
  from graphlearn_tpu.partition import RandomPartitioner
  n = 64
  rows = np.concatenate([np.arange(n), np.arange(n)])
  cols = np.concatenate([(np.arange(n) + 1) % n, (np.arange(n) + 2) % n])
  feats = (np.arange(n, dtype=np.float32)[:, None]
           * np.ones((1, 4), np.float32))
  pdir = tmp_path / 'parts'
  RandomPartitioner(pdir, 8, n, (rows, cols), node_feat=feats,
                    node_label=(np.arange(n) % 4).astype(np.int32),
                    seed=0).partition()
  # rich layout for the COMPOSED phase (r4): provenance features
  # (col 0 = old id + 1), edge features encoding eids, cache plan —
  # loaded host-local + tiered by the workers
  e = len(rows)
  efeat = np.stack([np.arange(e), rows, cols], 1).astype(np.float32)
  feats2 = np.tile((np.arange(n, dtype=np.float32) + 1)[:, None],
                   (1, 4))
  pdir2 = tmp_path / 'rich'
  RandomPartitioner(pdir2, 8, n, (rows, cols), node_feat=feats2,
                    node_label=(np.arange(n) % 4).astype(np.int32),
                    edge_feat=efeat, cache_ratio=0.1,
                    seed=0).partition()
  procs = []
  outs = []
  for pid in range(2):
    out = tmp_path / f'worker{pid}.json'
    outs.append(out)
    procs.append(subprocess.Popen(
        [sys.executable, str(worker), f'localhost:{port}', '2',
         str(pid), str(out), str(pdir), str(pdir2)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True))
  results = []
  for p in procs:
    try:
      stdout, _ = p.communicate(timeout=360)
    except subprocess.TimeoutExpired:
      for q in procs:
        q.kill()
      raise
    assert p.returncode == 0, stdout[-4000:]
    results.append(stdout)
  r0, r1 = (json.loads(o.read_text()) for o in outs)
  # deterministic, disjoint, covering seed shards
  s0, s1 = set(r0['shard']), set(r1['shard'])
  assert not (s0 & s1)
  assert s0 | s1 == set(range(64))
  assert r0['host_slice'] == [0, 4] and r1['host_slice'] == [4, 8]
  # both ran the full epoch and agree on the replicated DP loss
  assert r0['batches'] == r1['batches'] == 64 // (4 * 8)
  assert np.isfinite(r0['loss'])
  assert abs(r0['loss'] - r1['loss']) < 1e-5
  # host-local loading: each process materialized ITS 4 partitions and
  # the assembled global batch carried provenance-correct features
  assert r0['host_local']['host_parts'] == [0, 1, 2, 3]
  assert r1['host_local']['host_parts'] == [4, 5, 6, 7]
  assert r0['host_local']['provenance_rows'] > 0
  assert r1['host_local']['provenance_rows'] > 0
  # composed phase: tiered + cache + edge features host-local, with
  # cold rows OWNER-served across the two real processes
  for r in (r0, r1):
    assert r['composed']['provenance_rows'] > 0
    assert r['composed']['cold_misses'] > 0
    assert (r['composed']['cold_lookups']
            >= r['composed']['cold_misses'])
