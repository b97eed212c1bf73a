"""Supervised / unsupervised training steps on Batch pytrees.

The reference leaves the training loop to user code + DDP
(`examples/train_sage_ogbn_products.py:90-130`); here the loop is a
jitted optax step.  Loss is computed on the **seed slots** only (table
positions ``[0, batch_size)``), masked by seed validity — padded seeds
contribute zero, so the tail batch trains correctly with one compiled
program.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ..utils.profiling import layer_scope


class TrainState(NamedTuple):
  params: Any
  opt_state: Any
  step: jax.Array


def create_train_state(model, rng, sample_batch, tx: optax.GradientTransformation
                       ) -> Tuple[TrainState, Callable]:
  """Init params from a sample batch; returns (state, apply_fn)."""
  params = model.init(rng, sample_batch.x, sample_batch.edge_index,
                      sample_batch.edge_mask)
  return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32)), \
      model.apply


def supervised_loss(logits: jax.Array, y: jax.Array, batch_seeds: jax.Array,
                    batch_size: int) -> jax.Array:
  """Masked softmax CE over seed slots [0, batch_size)."""
  with layer_scope('model', 'loss'):
    seed_logits = logits[:batch_size]
    seed_y = y[:batch_size]
    valid = (batch_seeds >= 0).astype(seed_logits.dtype)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        seed_logits, seed_y.astype(jnp.int32))
    return (ce * valid).sum() / jnp.maximum(valid.sum(), 1.0)


def make_extracted_supervised_step(extract: Callable,
                                   tx: optax.GradientTransformation,
                                   batch_size: int):
  """Build ``(state, batch) -> (state, loss, correct)`` from an
  ``extract(params, batch) -> (logits, y, seeds)`` adapter — ONE
  update body (masked seed-slot CE, optax update, masked correct
  count) shared by the homogeneous and hetero step builders and the
  fused epoch runners."""

  def supervised_step(state: TrainState, batch):
    def loss_fn(params):
      logits, y, seeds = extract(params, batch)
      loss = supervised_loss(logits, y, seeds, batch_size)
      return loss, (logits, y, seeds)

    (loss, (logits, y, seeds)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(state.params)
    with layer_scope('optimizer'):
      updates, opt_state = tx.update(grads, state.opt_state,
                                     state.params)
      params = optax.apply_updates(state.params, updates)
    with layer_scope('model', 'metrics'):
      valid = seeds >= 0
      pred = jnp.argmax(logits[:batch_size], axis=-1)
      correct = jnp.sum((pred == y[:batch_size]) & valid)
    return TrainState(params, opt_state, state.step + 1), loss, correct

  return supervised_step


def apply_to_batch(apply_fn, params, batch):
  """One definition of "apply the model to a batch" — a `Batch` or a
  typed `HeteroBatch`: what the sampler stated about the batch in its
  metadata goes on to the model (the presence checks are static per
  pytree structure — no retrace churn).

  * ``edge_weight``, the GNS 1/q importance weights (PR 10), threads
    into the aggregation so biased sampling stays unbiased at the
    model.
  * ``hop_capacities``, the static hop layout `NeighborSampler`
    (`sampler.neighbor_sampler.hop_capacities`) or
    `HeteroNeighborSampler` (per node type and relation:
    `sampler.hetero_neighbor_sampler.typed_hop_capacities`) states,
    goes to a model that declares ``takes_hop_capacities`` (`BasicGNN`
    and `RGAT`, which trim each layer to the hops it feeds when their
    convs allow it); the logits are then the seed rows ``[C_0, out]``,
    which is all the loss and the accuracy read.  Any other
    ``apply_fn`` and any batch without the entry get the whole table,
    as before.
  * ``hop_windows``, the fanout windows of the edge blocks the same
    samplers state beside it (`sampler.neighbor_sampler.hop_windows`,
    `sampler.hetero_neighbor_sampler.typed_hop_windows`), goes to the
    same models: their `SAGEConv` / `GATConv` layers then aggregate by
    window instead of scattering every edge slot into the target rows
    — the same logits to float32 round-off.  A batch without the entry
    (the mesh loader's, a fused node epoch's, induced-subgraph
    batches) keeps the `segment_*` path; link batches state it since
    PR 38.
  """
  md = getattr(batch, 'metadata', None) or {}
  kwargs = {}
  if isinstance(md, dict):
    if md.get('edge_weight') is not None:
      kwargs['edge_weight'] = md['edge_weight']
    model = getattr(apply_fn, '__self__', None)
    if getattr(model, 'takes_hop_capacities', False):
      for stated in ('hop_capacities', 'hop_windows'):
        if md.get(stated) is not None:
          kwargs[stated] = md[stated]
  if hasattr(batch, 'x_dict'):
    return apply_fn(params, batch.x_dict, batch.edge_index_dict,
                    batch.edge_mask_dict, **kwargs)
  return apply_fn(params, batch.x, batch.edge_index, batch.edge_mask,
                  **kwargs)


def _extract_of(apply_fn, target_ntype):
  """The ``extract`` adapter of a model applied to whole batches: the
  logits with the seed slots' labels and ids — of ``target_ntype`` in a
  typed batch."""
  def extract(params, batch):
    logits = apply_to_batch(apply_fn, params, batch)
    if target_ntype is None:
      return logits, batch.y, batch.batch
    return (logits, batch.y_dict[target_ntype],
            batch.batch_dict[target_ntype])
  return extract


def make_supervised_step(apply_fn, tx: optax.GradientTransformation,
                         batch_size: int, target_ntype=None):
  """Build a jitted ``(state, batch) -> (state, loss, correct)`` step;
  ``target_ntype`` names the seeded node type of typed batches."""
  return jax.jit(make_extracted_supervised_step(
      _extract_of(apply_fn, target_ntype), tx, batch_size))


def make_extracted_eval_step(extract: Callable, batch_size: int):
  """``(params, batch) -> (correct, total)`` from the same extract
  adapter `make_extracted_supervised_step` takes — ONE definition of
  the masked seed-slot accuracy."""

  def eval_step(params, batch):
    logits, y, seeds = extract(params, batch)
    with layer_scope('model', 'metrics'):
      valid = seeds >= 0
      pred = jnp.argmax(logits[:batch_size], axis=-1)
      correct = jnp.sum((pred == y[:batch_size]) & valid)
      return correct, jnp.sum(valid)

  return eval_step


def make_eval_step(apply_fn, batch_size: int, target_ntype=None):
  return jax.jit(make_extracted_eval_step(
      _extract_of(apply_fn, target_ntype), batch_size))


def unsupervised_link_loss(emb: jax.Array, metadata: dict) -> jax.Array:
  """Binary link-prediction loss from sampler metadata
  (``edge_label_index`` / ``edge_label`` / ``edge_label_mask``), the
  objective of the reference's unsupervised SAGE example
  (`examples/graph_sage_unsup_ppi.py:41-45`)."""
  eli = metadata['edge_label_index']
  label = metadata['edge_label'].astype(emb.dtype)
  mask = metadata.get('edge_label_mask')
  n = emb.shape[0]
  src = emb[jnp.clip(eli[0], 0, n - 1)]
  dst = emb[jnp.clip(eli[1], 0, n - 1)]
  logit = jnp.sum(src * dst, axis=-1)
  ls = optax.sigmoid_binary_cross_entropy(logit, jnp.minimum(label, 1.0))
  if mask is not None:
    valid = mask & (eli[0] >= 0) & (eli[1] >= 0)
  else:
    valid = (eli[0] >= 0) & (eli[1] >= 0)
  v = valid.astype(emb.dtype)
  return (ls * v).sum() / jnp.maximum(v.sum(), 1.0)


def triplet_link_loss(emb: jax.Array, metadata: dict,
                      margin: float = 1.0) -> jax.Array:
  """Max-margin triplet loss from sampler metadata (``src_index`` /
  ``dst_pos_index`` / ``dst_neg_index`` with -1 invalid slots) — the
  triplet-mode counterpart of :func:`unsupervised_link_loss`."""
  si = metadata['src_index']
  dp = metadata['dst_pos_index']
  dn = metadata['dst_neg_index']
  n = emb.shape[0]
  es = emb[jnp.clip(si, 0, n - 1)]
  ep = emb[jnp.clip(dp, 0, n - 1)]
  en = emb[jnp.clip(dn, 0, n - 1)]                  # [B, A, D]
  pos = jnp.sum(es * ep, axis=-1)                   # [B]
  neg = jnp.sum(es[:, None, :] * en, axis=-1)       # [B, A]
  ls = jnp.maximum(0.0, margin - pos[:, None] + neg)
  valid = ((si >= 0) & (dp >= 0))[:, None] & (dn >= 0)
  v = valid.astype(emb.dtype)
  return (ls * v).sum() / jnp.maximum(v.sum(), 1.0)


def link_loss_from_metadata(emb: jax.Array, metadata: dict) -> jax.Array:
  """Dispatch binary vs triplet link loss by the (static) metadata
  keys a link batch carries."""
  if 'edge_label_index' in metadata:
    return unsupervised_link_loss(emb, metadata)
  if 'src_index' in metadata:
    return triplet_link_loss(emb, metadata)
  raise KeyError('batch metadata carries neither edge_label_index '
                 '(binary) nor src_index (triplet) link labels')


def make_unsupervised_step(apply_fn, tx: optax.GradientTransformation,
                           remat: bool = False):
  """Build a jitted link-loss step.  The loss dispatches binary vs
  triplet by the batch's (static) metadata keys
  (`link_loss_from_metadata`), so one builder serves both the
  per-batch loaders and `loader.fused.FusedLinkEpoch`.  The model is
  applied through `apply_to_batch`: a link batch that states its hop
  layout gets the seed rows ``[C_0, out]``, below which every
  endpoint's seed-local row lies.  ``remat`` rematerialises the
  model's forward in the backward pass (`jax.checkpoint`)."""

  @jax.jit
  def unsupervised_step(state: TrainState, batch):
    def loss_fn(params):
      embed = lambda p: apply_to_batch(apply_fn, p, batch)
      emb = (jax.checkpoint(embed) if remat else embed)(params)
      with layer_scope('model', 'loss'):
        return link_loss_from_metadata(emb, batch.metadata)

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    with layer_scope('optimizer'):
      updates, opt_state = tx.update(grads, state.opt_state,
                                     state.params)
      params = optax.apply_updates(state.params, updates)
    return TrainState(params, opt_state, state.step + 1), loss

  return unsupervised_step
