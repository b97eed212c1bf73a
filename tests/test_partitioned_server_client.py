"""Partitioned server-client deployment: every sampling server owns ONE
shard, producers fan each hop/feature lookup out to peer servers over
RPC.

All roles are local processes (SURVEY §4: real RPC + shm + producer
subprocesses, no mocks): 2 shard servers x 1 producer worker each, one
client loader spread over both servers, provenance features asserting
remote rows arrive intact and exact (fanout >= degree) neighborhoods
asserting per-hop fan-out actually happened.
"""
import multiprocessing as mp

import numpy as np
import pytest

from graphlearn_tpu import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason='native lib unavailable')

N = 40


def _write_partitions(root):
  from graphlearn_tpu.partition import RandomPartitioner
  rows = np.repeat(np.arange(N), 2)
  cols = np.stack([(np.arange(N) + 1) % N,
                   (np.arange(N) + 2) % N], 1).reshape(-1)
  feats = np.tile(np.arange(N, dtype=np.float32)[:, None], (1, 4))
  RandomPartitioner(root, 2, N, (rows, cols), node_feat=feats,
                    node_label=(np.arange(N) % 4), seed=0).partition()


def _shard_server_proc(root, rank, port_q):
  from graphlearn_tpu.distributed import (HostDataset, init_server,
                                          wait_and_shutdown_server)
  shard = HostDataset.from_partition_dir(root, rank)
  srv = init_server(num_servers=2, num_clients=1, rank=rank,
                    dataset=shard, host='127.0.0.1', port=0)
  port_q.put(srv.port)
  wait_and_shutdown_server(timeout=120)


@pytest.mark.slow
def test_partitioned_server_client_loader(tmp_path):
  _write_partitions(tmp_path)
  ctx = mp.get_context('forkserver')
  procs, ports = [], []
  for rank in range(2):
    q = ctx.Queue()
    p = ctx.Process(target=_shard_server_proc,
                    args=(str(tmp_path), rank, q), daemon=False)
    p.start()
    procs.append(p)
    ports.append(q.get(timeout=60))

  from graphlearn_tpu.distributed import (
      DistNeighborLoader, HostSamplingConfig,
      RemoteDistSamplingWorkerOptions, init_client, shutdown_client)
  addrs = tuple(('127.0.0.1', pt) for pt in ports)
  init_client(list(addrs), rank=0, num_clients=1)
  loader = DistNeighborLoader(
      None, [2, 2], np.arange(N), batch_size=8, shuffle=False,
      worker_options=RemoteDistSamplingWorkerOptions(
          server_rank=[0, 1], num_workers=1, prefetch_size=2),
      sampling_config=HostSamplingConfig(sampling_type='node',
                                         peer_addrs=addrs),
      to_device=False)
  for _ in range(2):
    seeds_seen = []
    for batch in loader:
      ids = np.asarray(batch.node)
      valid = np.asarray(batch.node_mask)
      # remote feature rows intact (zero-filled -> mismatch)
      np.testing.assert_allclose(np.asarray(batch.x)[:, 0][valid],
                                 ids[valid].astype(np.float32))
      np.testing.assert_array_equal(np.asarray(batch.y)[valid],
                                    ids[valid] % 4)
      s = np.asarray(batch.batch)
      s = s[s >= 0]
      seeds_seen.append(s)
      # fanout == degree: the 2-hop closure must be EXACT — a shard-
      # local sampler would miss every remotely-owned frontier row
      expect = set()
      for sd in s:
        expect.update(((sd + d) % N) for d in range(5))
      assert set(ids[valid].tolist()) == expect
    np.testing.assert_array_equal(np.sort(np.concatenate(seeds_seen)),
                                  np.arange(N))
  loader.shutdown()
  shutdown_client()
  for p in procs:
    p.join(timeout=30)
    assert not p.is_alive()
