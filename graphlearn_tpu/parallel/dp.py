"""Data-parallel training over a device mesh.

TPU-native replacement for the reference's DP story — vanilla
`torch.nn.parallel.DistributedDataParallel` + NCCL allreduce in its
examples (`examples/multi_gpu/train_sage_ogbn_papers100m.py:33-41`,
SURVEY §2.3.1).  Instead of per-process replicas + NCCL, one SPMD
program over a `jax.sharding.Mesh`: params replicated, per-device batch
shards, gradients averaged with `psum` over the ``data`` axis riding
ICI.  The host side feeds stacked per-device batches (leading axis =
mesh size), the cross-device part is entirely XLA collectives.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.train import TrainState, supervised_loss


def make_mesh(n_devices: Optional[int] = None, axis: str = 'data') -> Mesh:
  """1-D device mesh over the first ``n_devices`` devices (all of them
  when None).  Asking for more devices than exist is an error, never a
  silently smaller mesh."""
  devs = jax.devices()
  if n_devices:
    if n_devices > len(devs):
      raise ValueError(f'make_mesh({n_devices}): only {len(devs)} '
                       f'{devs[0].platform} device(s) visible')
    devs = devs[:n_devices]
  return Mesh(np.asarray(devs), (axis,))


def stack_batches(batches: Sequence[Any]):
  """Stack per-device Batch pytrees along a new leading device axis."""
  return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)


def replicate(tree, mesh: Mesh):
  """Place a pytree fully replicated on the mesh (params / opt state)."""
  return jax.device_put(tree, NamedSharding(mesh, P()))


def local_batch_piece(batch, num_parts: int):
  """One device's slice of a ``[P, ...]``-stacked batch pytree — the
  single-device template `create_train_state` wants for param init
  under the mesh engines.  Reads only ADDRESSABLE shards, so it works
  on multi-host meshes where ``np.asarray(global_array)`` would not;
  leaves without the leading device axis pass through."""
  def pick(v):
    if (isinstance(v, jax.Array) and v.ndim
        and v.shape[0] == num_parts):
      return np.asarray(v.addressable_shards[0].data)[0]
    return v
  return jax.tree_util.tree_map(pick, batch)


def shard_stacked(tree, mesh: Mesh, axis: str = 'data'):
  """Place a stacked (leading device axis) pytree sharded over ``axis``."""
  return jax.device_put(tree, NamedSharding(mesh, P(axis)))


def make_dp_supervised_step(apply_fn: Callable,
                            tx: optax.GradientTransformation,
                            batch_size: int, mesh: Mesh,
                            axis: str = 'data'):
  """Build the SPMD data-parallel step.

  Returns ``step(state, stacked_batch) -> (state, mean_loss, correct)``
  where ``stacked_batch`` has a leading axis equal to the mesh size.
  Gradient averaging = ``jax.lax.pmean`` over the mesh axis — the XLA
  collective that replaces the reference's NCCL allreduce.
  """
  from .shard_map_compat import shard_map

  def per_device(state: TrainState, batch):
    # batch leaves carry a leading singleton shard axis; drop it.
    batch = jax.tree_util.tree_map(lambda x: x[0], batch)

    def loss_fn(params):
      from ..models.train import apply_to_batch
      # the example SAGE path: GNS batches carry metadata
      # ['edge_weight'] (PR 10 1/q weights) — threaded into the
      # aggregation so GNS-on DP training is unbiased at the model;
      # stacked `NeighborLoader` batches carry ['hop_capacities'], and
      # each device trims its own layers to the hops they feed
      logits = apply_to_batch(apply_fn, params, batch)
      loss = supervised_loss(logits, batch.y, batch.batch, batch_size)
      return loss, logits

    (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        state.params)
    grads = jax.lax.pmean(grads, axis)
    loss = jax.lax.pmean(loss, axis)
    updates, opt_state = tx.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    valid = batch.batch >= 0
    pred = jnp.argmax(logits[:batch_size], axis=-1)
    correct = jax.lax.psum(
        jnp.sum((pred == batch.y[:batch_size]) & valid), axis)
    return TrainState(params, opt_state, state.step + 1), loss, correct

  sharded = shard_map(
      per_device, mesh=mesh,
      in_specs=(P(), P(axis)),
      out_specs=(P(), P(), P()))

  @jax.jit
  def dp_supervised_step(state, stacked_batch):
    new_state, loss, correct = sharded(state, stacked_batch)
    return new_state, loss, correct

  return dp_supervised_step


def make_dp_eval_step(apply_fn: Callable, batch_size: int, mesh: Mesh,
                      axis: str = 'data'):
  """SPMD evaluation step: ``(params, stacked_batch) -> (correct,
  total)``, both psum-reduced over the mesh axis — the eval
  counterpart of `make_dp_supervised_step` (mirrors the single-chip
  `models.train.make_extracted_eval_step` contract)."""
  from .shard_map_compat import shard_map

  def per_device(params, batch):
    batch = jax.tree_util.tree_map(lambda x: x[0], batch)
    logits = apply_fn(params, batch.x, batch.edge_index, batch.edge_mask)
    valid = batch.batch >= 0
    pred = jnp.argmax(logits[:batch_size], axis=-1)
    correct = jax.lax.psum(
        jnp.sum((pred == batch.y[:batch_size]) & valid), axis)
    total = jax.lax.psum(jnp.sum(valid), axis)
    return correct, total

  return shard_map(per_device, mesh=mesh, in_specs=(P(), P(axis)),
                   out_specs=(P(), P()))


def make_dp_unsupervised_step(apply_fn: Callable,
                              tx: optax.GradientTransformation,
                              mesh: Mesh, axis: str = 'data'):
  """SPMD data-parallel UNSUPERVISED (link-loss) step for stacked
  link batches (`DistLinkNeighborLoader` output): per-device link loss
  (binary sigmoid or max-margin triplet, picked by the batch's
  metadata keys) on its own positives/negatives, pmean-averaged
  gradients — the distributed form of the reference's unsupervised
  SAGE objective (`examples/graph_sage_unsup_ppi.py:41-45`)."""
  from ..models.train import link_loss_from_metadata
  from .shard_map_compat import shard_map

  def per_device(state: TrainState, batch):
    batch = jax.tree_util.tree_map(lambda x: x[0], batch)

    def loss_fn(params):
      emb = apply_fn(params, batch.x, batch.edge_index, batch.edge_mask)
      return link_loss_from_metadata(emb, batch.metadata)

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    grads = jax.lax.pmean(grads, axis)
    loss = jax.lax.pmean(loss, axis)
    updates, opt_state = tx.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    return TrainState(params, opt_state, state.step + 1), loss

  sharded = shard_map(
      per_device, mesh=mesh,
      in_specs=(P(), P(axis)),
      out_specs=(P(), P()))

  @jax.jit
  def dp_unsupervised_step(state, stacked_batch):
    return sharded(state, stacked_batch)

  return dp_unsupervised_step


class DataParallelLoader:
  """Wraps a single-chip loader, emitting mesh-size stacks of batches.

  The host-side analog of the reference's per-rank seed splits
  (`dist_sampling_producer.py:249-260`): one host drives all local
  devices; each step consumes ``mesh_size`` consecutive batches.
  """

  def __init__(self, loader, mesh_size: int):
    self.loader = loader
    self.mesh_size = int(mesh_size)

  def __len__(self):
    return len(self.loader) // self.mesh_size

  def __iter__(self):
    it = iter(self.loader)
    while True:
      group = []
      try:
        for _ in range(self.mesh_size):
          group.append(next(it))
      except StopIteration:
        return
      yield stack_batches(group)
