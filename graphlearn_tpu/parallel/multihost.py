"""Multi-host (pod / multi-slice) launch helpers.

The reference scales out by launching one process group per machine
with torch RPC worlds knitted over TCP/RDMA (`distributed/rpc.py:
236-292`, an ssh fan-out launcher).  JAX is single-controller
per host: every host runs the SAME program, `jax.distributed`
initializes the cross-host runtime, and the mesh spans all hosts'
devices — collectives ride ICI within a slice and DCN across slices
automatically.  What the framework must add is exactly two things:

  * a mesh over ALL devices with the partition axis aligned to the
    global device order (`global_mesh`);
  * deterministic per-host seed sharding so every host feeds its own
    devices' seed batches without coordination (`host_seed_shard`) —
    the multi-host analog of the reference's per-worker `randperm`
    splits (`dist_sampling_producer.py:249-260`): same epoch
    permutation everywhere (shared seed), disjoint slices by host.

Typical launch (same script on every host)::

    from graphlearn_tpu.parallel import multihost
    multihost.initialize()                  # env-driven on TPU pods
    mesh = multihost.global_mesh()
    ds = DistDataset.from_partition_dir(
        root, mesh.devices.size,
        # each host materializes ONLY its partitions' tensors
        # (per-host RAM = 1/num_hosts of the dataset)
        host_parts=multihost.host_partition_ids(mesh))
    seeds = multihost.host_seed_shard(all_seeds, epoch=e, seed=0)
    loader = DistNeighborLoader(ds, fanouts, seeds, mesh=mesh, ...)
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
  """Bring up the cross-host runtime (no-op if already initialized).

  On TPU pods all three arguments resolve from the environment; set
  them explicitly for CPU/GPU multi-process testing.
  """
  # NOTE: nothing here may touch the XLA backend (jax.devices(),
  # jax.process_count(), ...) before initialize() — backend init makes
  # distributed init impossible, and that failure must stay LOUD.
  if jax.distributed.is_initialized():
    return
  try:
    jax.distributed.initialize(coordinator_address, num_processes,
                               process_id)
  except ValueError:
    # Swallow ONLY the fully-implicit case (no cluster environment
    # detected, nothing requested): single-process tests.  Any
    # explicitly-requested multi-process setup must fail loudly.
    if (coordinator_address is not None or num_processes is not None
        or process_id is not None):
      raise


def global_mesh(axis: str = 'data') -> Mesh:
  """One partition-axis mesh over every device of every host."""
  return Mesh(np.asarray(jax.devices()), (axis,))


def host_device_slice(num_parts: Optional[int] = None) -> slice:
  """This host's contiguous slice of the mesh partition axis."""
  num_parts = num_parts or len(jax.devices())
  per_host = num_parts // jax.process_count()
  lo = jax.process_index() * per_host
  return slice(lo, lo + per_host)


def host_partition_ids(mesh: Mesh) -> np.ndarray:
  """The partition indices whose devices live on THIS process, in mesh
  order — feed `DistDataset.from_partition_dir(host_parts=...)` so
  each host materializes only the shards its devices will hold."""
  flat = mesh.devices.reshape(-1)
  return np.asarray([i for i, d in enumerate(flat)
                     if d.process_index == jax.process_index()],
                    np.int64)


def global_max(value: int, mesh: Mesh) -> int:
  """Max of a per-process host scalar across every process of the mesh
  — e.g. the class count over host-local label shards (each host sees
  only its partitions; model widths must agree globally).  Works
  unchanged single-process."""
  import jax.numpy as jnp
  from jax.sharding import NamedSharding, PartitionSpec
  axis = mesh.axis_names[0]
  flat = mesh.devices.reshape(-1)
  shards = [jax.device_put(np.asarray([value], np.int64), flat[i])
            for i in host_partition_ids(mesh)]
  g = jax.make_array_from_single_device_arrays(
      (flat.size,), NamedSharding(mesh, PartitionSpec(axis)), shards)
  out = jax.jit(jnp.max,
                out_shardings=NamedSharding(mesh, PartitionSpec()))(g)
  return int(out)


def host_seed_shard(seeds: np.ndarray, epoch: int = 0, seed: int = 0,
                    shuffle: bool = True) -> np.ndarray:
  """This host's disjoint slice of the (globally shuffled) seed set.

  Every host computes the SAME permutation from ``(seed, epoch)`` and
  takes its process-index slice — globally consistent epoch shuffling
  with zero cross-host coordination.  Shards are wrap-around padded to
  EQUAL length (torch DistributedSampler semantics): unequal shards
  would run different step counts and desynchronize the SPMD
  collectives at epoch end.
  """
  seeds = np.asarray(seeds)
  if shuffle:
    rng = np.random.default_rng((int(seed), int(epoch)))
    seeds = seeds[rng.permutation(len(seeds))]
  n_hosts = jax.process_count()
  per = -(-len(seeds) // n_hosts)
  if per * n_hosts > len(seeds) and len(seeds):
    # wrap-around pad to exactly per * n_hosts even when the pad
    # exceeds the seed count (tiny seed sets on many hosts)
    seeds = np.resize(seeds, (per * n_hosts,) + seeds.shape[1:])
  lo = jax.process_index() * per
  return seeds[lo:lo + per]
