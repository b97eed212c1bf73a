"""Whole-epoch fused TREE-layout training: the TPU-first flagship path.

`FusedEpoch` fuses the subgraph pipeline (sample → dedup → gather →
`SAGEConv` scatter aggregation) into one program; this module goes one
design level deeper and removes the subgraph itself.  The scan body
keeps the sampler's native tree layout end to end:

  * per hop, `ops.neighbor.sample_one_hop` expands the level frontier
    to a ``[F_t, k]`` window tensor — no dedup, NO SORT (the
    capacity-bounded unique that dominates the subgraph sampler's
    device time is structurally unnecessary here);
  * features gather per level; aggregation inside `models.tree.
    TreeSAGE` is reshape + masked mean — NO SCATTER, forward or
    backward;
  * supervised CE on the seed level + optax update.

Why: the subgraph fused step spends its device time in the sort-based
dedup and the scatter-dominated model; the tree path replaces both
with streaming ops, and what remains is gather-bound (per-level
feature rows + neighbor-id windows + indptr degrees).  The last
on-chip split of that step is quoted once, in ROADMAP.md (S1-S3), and
has not been re-measured on today's code.  The exact
without-replacement draw is the default; ``replace=True`` skips the
window.

Also the epoch-length compile story: ``max_steps_per_program`` runs
the epoch as ceil(S/chunk) dispatches of ONE compiled ``[chunk, B]``
program — every epoch length reuses the same executable (tail steps
are INVALID_ID-padded; a fully-invalid step is a guarded no-op on the
state).  That reuse (plus snapshot seams between chunks) is all
chunking buys: nothing limits the length of one dispatch — a whole
2,391-step epoch ran as ONE 82 s program on a v5e (PR 21 bring-up).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..data.dataset import Dataset
from ..data.feature import _device_gather
from ..models.train import TrainState
from ..ops.pallas_gather import pallas_enabled
from ..ops.pallas_sample import sample_one_hop_auto
from ..utils.profiling import layer_scope
from .fused import _SupervisedScanEpoch, _counted_jit
from .node_loader import SeedBatcher
from .transform import _gather_labels


def expand_tree_levels(indptr, indices, seeds, key, fanouts, *,
                       sort_locality: bool = False):
  """The bucketed single-shot tree expansion: ``[B]`` seeds → per-level
  ``(levels, masks)`` lists (``levels[t]`` is ``[B * k_1 ... k_t]``
  node ids, INVALID_ID where masked).  ONE definition shared by the
  epoch drivers here and the online serving plane
  (`serving.engine.ServingEngine` — which vmaps it per seed so a
  seed's tree depends only on (key, seed), never on batch
  composition), so the level layout the model consumes cannot drift
  between training and serving."""
  levels, masks = [seeds], [seeds >= 0]
  frontier = seeds
  for i, k in enumerate(fanouts):
    # `sample_one_hop_auto` re-reads GLT_PALLAS_SAMPLE at trace time;
    # the epoch drivers compile once per config so the choice is baked
    # per program (value-identical either way)
    with layer_scope('sample', f'hop{i}'):
      res = sample_one_hop_auto(indptr, indices, frontier, k,
                                jax.random.fold_in(key, i),
                                sort_locality=sort_locality)
      nxt = jnp.where(res.mask, res.nbrs, -1).reshape(-1)
    levels.append(nxt)
    masks.append(nxt >= 0)
    frontier = nxt
  return levels, masks


class FusedTreeEpoch(_SupervisedScanEpoch):
  """One-program tree-layout supervised epochs (see module docstring).

  Example::

      model = TreeSAGE(hidden_features=256, out_features=47,
                       num_layers=3)
      fused = FusedTreeEpoch(ds, [15, 10, 5], train_idx, model, tx,
                             batch_size=1024, seed=0)
      state = fused.init_state(jax.random.key(0))
      for _ in range(epochs):
        state, stats = fused.run(state)
      acc = fused.evaluate(state.params, test_idx)

  Args:
    data: `Dataset`, homogeneous, fully device-resident features +
      labels (same contract as `FusedEpoch`).
    num_neighbors: per-hop fanouts; ``len == model.num_layers``.
    input_nodes: seed ids (or boolean mask).
    model: a `models.tree.TreeSAGE` (or any flax module with the same
      ``(xs, masks) -> [B, C]`` signature).
    tx: optax transformation.
    batch_size / shuffle / drop_last / seed: epoch controls.
    max_steps_per_program: split each epoch into dispatches of at most
      this many steps, all served by ONE compiled program (None = the
      whole epoch as one program, compiled per epoch length).
    remat: `jax.checkpoint` the model apply.
  """

  def __init__(self, data: Dataset, num_neighbors: Sequence[int],
               input_nodes, model, tx: optax.GradientTransformation,
               batch_size: int, shuffle: bool = True,
               drop_last: bool = False, seed: Optional[int] = None,
               max_steps_per_program: Optional[int] = None,
               remat: bool = False):
    if data.is_hetero:
      raise ValueError('FusedTreeEpoch is homogeneous-only')
    feat = data.node_features
    if feat is None or feat.hot_rows < feat.size(0):
      raise ValueError(
          'FusedTreeEpoch needs fully device-resident features '
          '(split_ratio == 1.0)')
    labels = data.get_node_label_device()
    if labels is None:
      raise ValueError('FusedTreeEpoch needs node labels')
    self.data = data
    self.model = model
    self.tx = tx
    self.batch_size = int(batch_size)
    self.fanouts = tuple(int(k) for k in num_neighbors)
    if getattr(model, 'num_layers', len(self.fanouts)) != \
        len(self.fanouts):
      raise ValueError(
          f'model.num_layers={model.num_layers} must equal '
          f'len(num_neighbors)={len(self.fanouts)}')
    graph = data.get_graph()
    # big tables as jit ARGUMENTS, never closures (`loader.fused`)
    self._dev = dict(indptr=graph.indptr, indices=graph.indices,
                     hot=feat.hot_tier, id2index=feat._id2index_dev,
                     labels=labels)
    input_nodes = np.asarray(input_nodes)
    if input_nodes.dtype == np.bool_:
      input_nodes = np.nonzero(input_nodes)[0]
    self._batcher = SeedBatcher(input_nodes, self.batch_size, shuffle,
                                drop_last, seed)
    self._base_key = jax.random.key(seed or 0)
    self._epoch_idx = 0
    self._chunk = (int(max_steps_per_program)
                   if max_steps_per_program else None)
    apply = model.apply
    self._apply = jax.checkpoint(apply) if remat else apply
    self._eval_apply = apply
    self._compiled = _counted_jit(self._epoch_fn, donate_argnums=(0,),
                                  static_argnums=(4,))
    self._compiled_eval = _counted_jit(self._eval_fn,
                                       static_argnums=(4,))

  def __len__(self) -> int:
    return len(self._batcher)

  def init_state(self, rng) -> TrainState:
    """Init params from one dummy tree batch (host-cheap: shapes
    only)."""
    from ..telemetry.spans import span
    with span('fused.init_state', scope=type(self).__name__):
      d = self.data.node_features.feature_dim
      sizes = [self.batch_size]
      for k in self.fanouts:
        sizes.append(sizes[-1] * k)
      xs = [jnp.zeros((s, d), self.data.node_features.dtype)
            for s in sizes]
      masks = [jnp.ones((s,), jnp.bool_) for s in sizes]
      params = self.model.init(rng, xs, masks)
      return TrainState(params, self.tx.init(params),
                        jnp.zeros((), jnp.int32))

  # -- tree expansion + collation (the scan-body front half) --------------

  def _expand(self, seeds: jax.Array, key: jax.Array, dev: dict,
              use_pallas: bool):
    # no sort: what a sorted frontier is worth to this program is not
    # measured on the chip (ROADMAP S3), and turning it on changes
    # every per-seed draw
    levels, masks = expand_tree_levels(dev['indptr'], dev['indices'],
                                       seeds, key, self.fanouts,
                                       sort_locality=False)
    xs = []
    for t, lvl in enumerate(levels):
      with layer_scope('gather', f'level{t}'):
        xs.append(_device_gather(dev['hot'], lvl, dev['id2index'],
                                 use_pallas=use_pallas))
    with layer_scope('gather', 'labels'):
      y = _gather_labels(dev['labels'], seeds)
    return xs, masks, y

  # -- the one program ------------------------------------------------------

  def _epoch_fn(self, state: TrainState, seeds_all: jax.Array,
                key: jax.Array, dev: dict, use_pallas: bool):
    b = self.batch_size

    def body(state, xs_in):
      i, seeds = xs_in
      with layer_scope('sample', 'key'):
        step_key = jax.random.fold_in(key, i)
      xs, masks, y = self._expand(seeds, step_key, dev, use_pallas)

      def loss_fn(params):
        logits = self._apply(params, xs, masks)
        with layer_scope('model', 'loss'):
          valid = (seeds >= 0).astype(logits.dtype)
          ce = optax.softmax_cross_entropy_with_integer_labels(
              logits, y.astype(jnp.int32))
          return (ce * valid).sum() / jnp.maximum(valid.sum(), 1.0), \
              logits

      (loss, logits), grads = jax.value_and_grad(
          loss_fn, has_aux=True)(state.params)
      with layer_scope('optimizer'):
        updates, opt_state = self.tx.update(grads, state.opt_state,
                                            state.params)
        params = optax.apply_updates(state.params, updates)
        new_state = TrainState(params, opt_state, state.step + 1)
        # fully-padded steps (epoch-length chunking) must be no-ops:
        # zero grads still move adam's moments/bias correction
        any_valid = jnp.any(seeds >= 0)
        state = jax.tree_util.tree_map(
            lambda new, old: jnp.where(any_valid, new, old),
            new_state, state)
      with layer_scope('model', 'metrics'):
        valid = seeds >= 0
        correct = jnp.sum(
            (jnp.argmax(logits, axis=-1) == y) & valid)
        n_valid = jnp.sum(valid)
      return state, (loss, correct, n_valid)

    steps = jnp.arange(seeds_all.shape[0], dtype=jnp.int32)
    state, (losses, corrects, valids) = jax.lax.scan(
        body, state, (steps, seeds_all))
    return state, losses, jnp.sum(corrects), jnp.sum(valids)

  def _eval_fn(self, params, seeds_all: jax.Array, key: jax.Array,
               dev: dict, use_pallas: bool):
    def body(carry, xs_in):
      i, seeds = xs_in
      with layer_scope('sample', 'key'):
        step_key = jax.random.fold_in(key, i)
      xs, masks, y = self._expand(seeds, step_key, dev, use_pallas)
      logits = self._eval_apply(params, xs, masks)
      with layer_scope('model', 'metrics'):
        valid = seeds >= 0
        correct = jnp.sum((jnp.argmax(logits, axis=-1) == y) & valid)
        n_valid = jnp.sum(valid)
      return carry, (correct, n_valid)

    steps = jnp.arange(seeds_all.shape[0], dtype=jnp.int32)
    _, (correct, total) = jax.lax.scan(body, 0, (steps, seeds_all))
    return jnp.sum(correct), jnp.sum(total)

  # host driver (`run` / `evaluate` / `_chunks` / `__len__`) comes
  # from `_SupervisedScanEpoch` — one chunking implementation for the
  # whole fused family, so the key-schedule and padded-tail contracts
  # cannot drift between the subgraph and tree paths
