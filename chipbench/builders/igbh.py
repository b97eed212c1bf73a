"""The `rgat-igbh` cells' driver: the typed `NeighborLoader` over a
`Dataset` with IGBH's four node types and seven relations ->
`Feature.get` per type (16-bit tables) -> `models.RGAT` ->
`make_supervised_step`, one batch per step, the next batch drawn
before each loss is pulled (`ahead_window`).

Found by `chipbench.drivers.make` under the configuration's
``builder`` (``igbh``) and the mix's ``driver``; its data builder and
its copy of the plain reference sit beside it.  It keeps to the
protocol of `chipbench.drivers._Driver` and takes that class's
defaults where the GraphSAGE cells' answer is its own: ``controls``
(the reference in bfloat16 and float8, half a batch).  ``gaps`` is the
reference's own (`igbh_reference.gaps`: every step's gradient is
compared where the program stood, and the best step is held to the
limit, because one ReLU can make one step's gradient two-valued).

The work counts (`work`) are kept here, from counts and shapes alone:

  sample_bytes  per hop and stored relation, two row pointers per node
                that asks and each drawn id read and written
                (`yardstick.sample_bytes`);
  gather_bytes  per valid row of every type its id read and the row
                read and written at the table's width
                (`yardstick.gather_bytes`);
  step_flops    matmul FLOPs, forward and backward, of the rows each
                layer NEEDS for the seeds' logits: layer ``l`` of ``L``
                projects, per relation ``(a -> b)`` with a valid edge
                within ``L-1-l`` hops, the valid rows of ``a`` within
                ``L-l`` hops and (across types) those of ``b`` within
                ``L-1-l``; the head the seed rows.  The first layer
                takes no input gradient.  Attention's per-edge
                arithmetic is no matmul and is left out.

The window is this file's own (`ahead_window`): one thread over the
loader's public iterator, batch n+1 asked for after step n is
dispatched and before its loss is pulled, every loss pulled in order;
its clock starts before the first draw and its last step draws
nothing, so the window contains the device work it starts.
`first_steps` and `warm` drive the same step program, one batch at a
time (the comparison needs no batch ahead).

The window's two counters (`exchange_counts`) — valid node rows and
valid edge slots beside the padded extents a batch is laid out over —
are counted from the batches' masks when the harness asks, outside the
window; the timed loop only keeps the masks.
"""
import time

import numpy as np

from chipbench import beside, drivers, yardstick

build = beside(__file__, 'igbh_build')
ref = beside(__file__, 'igbh_reference')


def _conv_name(rel):
  return 'conv_' + '__'.join(rel)


def program_params(weights):
  """The flax tree of `models.RGAT` holding ``weights``."""
  p = {}
  for i, lay in enumerate(weights['layers']):
    p[f'conv{i}'] = {
        _conv_name(rel): {'GATConv_0': {
            'Dense_0': {'kernel': q['w']},
            'att_src': q['a_src'], 'att_dst': q['a_dst']}}
        for rel, q in lay.items()}
  p['head'] = {'kernel': weights['head']['w'], 'bias': weights['head']['b']}
  return {'params': p}


def weights_of(weights0, params):
  """Inverse of `program_params`, to host float32, in ``weights0``'s
  structure."""
  host = lambda a: np.asarray(a, np.float32)
  p = params['params']
  layers = []
  for i, lay in enumerate(weights0['layers']):
    conv = p[f'conv{i}']
    layers.append({
        rel: dict(w=host(conv[_conv_name(rel)]['GATConv_0']['Dense_0']
                         ['kernel']),
                  a_src=host(conv[_conv_name(rel)]['GATConv_0']['att_src']),
                  a_dst=host(conv[_conv_name(rel)]['GATConv_0']['att_dst']))
        for rel in lay})
  return dict(layers=layers, head=dict(w=host(p['head']['kernel']),
                                       b=host(p['head']['bias'])))


def step_flops(cfg, rows, edges, batch):
  """``rows[t][h]``: valid rows of type ``t`` within ``h`` hops of the
  seeds; ``edges[(a, rel, b)][h]``: valid edges of the emitted
  relation within hops ``0..h``."""
  hidden, depth = int(cfg['hidden']), int(cfg['num_layers'])
  total = 0.0
  for l, d in enumerate(build.layer_dims(cfg)):
    hop = depth - 1 - l
    fwd = 0.0
    for (a, _, b), within in edges.items():
      if within[hop] > 0:
        fwd += 2.0 * d * hidden * (
            rows[a][hop + 1] + (rows[b][hop] if a != b else 0))
    total += fwd * (2 if l == 0 else 3)
  return total + 3 * 2.0 * batch * hidden * int(cfg['classes'])


def ahead_window(drv, seconds):
  """One batch per step, the next one asked of the loader while the
  step runs:

      b = next(it)                                  the clock has started
      loop:  state, loss, _ = step(state, b)        dispatch step n
             b = next(it)                           batch n+1
             float(loss)                            pull step n

  so a step that ends finds the next batch's draw and gather queued
  behind it, and the device's queue is never empty.  Every step's loss
  is pulled and checked finite, in order; at most one batch is ever
  drawn ahead.  The window ends with the pull at which ``seconds``
  have passed, and the window holds all the device work it started:
  ``wall_s`` runs from before the first draw to the last pull, and no
  batch is drawn behind a step whose pull must end the window (one
  that, dispatched now, the shortest step so far carries past
  ``seconds``), so every batch drawn is stepped and counted, a window
  of n steps holds n draws, and nothing is left running on the device
  when it ends: a trace of the window reads a busy time that the
  window contains.  A batch drawn behind a step that then ran late and
  ended past ``seconds`` is stepped too, and its pull ends the window.
  ``loader_wait_s`` is the host's time inside the ``next(it)`` that
  drew each step's batch, ``step_s`` the time from one pull to the
  next (the first: from the start).  ``drv`` has ``loader``, ``it``,
  ``state``, ``batch`` and ``step(state, batch) -> (state, loss,
  ...)``."""
  t0 = last = time.perf_counter()
  with drivers._annot('chipbench.next_loader'):
    b = drivers.next_batch(drv)
  waits, step_s = [time.perf_counter() - t0], []
  seeds = failed = 0
  while b is not None:
    with drivers._annot('chipbench.step'):
      drv.state, loss, _ = drv.step(drv.state, b)
    t1 = time.perf_counter()
    b = None
    if t1 - t0 + min(step_s, default=0.0) < seconds:
      with drivers._annot('chipbench.next_loader'):
        b = drivers.next_batch(drv)
      waits.append(time.perf_counter() - t1)
    with drivers._annot('chipbench.step'):
      loss = float(loss)
    t3 = time.perf_counter()
    failed += int(not np.isfinite(loss))
    seeds += drv.batch
    step_s.append(t3 - last)
    last = t3
  return dict(seeds=seeds, failed=failed, wall_s=last - t0,
              steps=len(step_s), loader_wait_s=waits, step_s=step_s)


class IgbhLoaderDriver(drivers._Driver):

  def __init__(self, cfg, traffic, seed, model_dtype=None, data=None):
    import jax
    import jax.numpy as jnp
    import optax
    from graphlearn_tpu.data import Dataset
    from graphlearn_tpu.loader import NeighborLoader
    from graphlearn_tpu.models import RGAT, make_supervised_step
    from graphlearn_tpu.models.train import TrainState
    super().__init__(cfg, traffic, seed, model_dtype)
    if int(traffic['ahead']) != 1:
      raise ValueError(f'the window draws one batch ahead of the step '
                       f'(`ahead_window`); the mix asks for '
                       f'{traffic["ahead"]}')
    self.data = data or build.tables(cfg, seed)
    self.weights0 = build.weights(cfg, seed)
    self.target = target = cfg['target']
    self.sizes = build.sizes(cfg)
    self.stored = build.stored_relations(cfg)
    self.ds = (Dataset()
               .init_graph(self.data['graphs'], layout='CSR',
                           num_nodes=self.sizes)
               .init_node_features(self.data['feats'], split_ratio=1.0)
               .init_node_labels({target: self.data['labels']}))
    n_seeds = int(traffic['steps_per_epoch']) * self.batch
    if n_seeds < 3 * self.batch:
      raise ValueError('a mix needs three batches or more: the first '
                       'three steps are held against the reference')
    rng = np.random.default_rng(self.seed)
    train = rng.permutation(self.sizes[target])[
        :int(float(cfg['train_fraction']) * self.sizes[target])]
    self.ids = train[:n_seeds]
    self.loader = NeighborLoader(
        self.ds, list(self.fanout), (target, self.ids),
        batch_size=self.batch, shuffle=True, seed=self.seed)
    self.it = iter(self.loader)
    kw = {} if model_dtype is None else dict(dtype=model_dtype)
    self.model = RGAT(
        etypes=tuple(sorted(build.message_type(et) for et in self.stored)),
        hidden_features=int(cfg['hidden']),
        out_features=int(cfg['classes']),
        num_layers=int(cfg['num_layers']), heads=int(cfg['heads']),
        target_ntype=target, **kw)
    h = self.hyper
    tx = optax.adam(h['lr'], b1=h['b1'], b2=h['b2'], eps=h['eps'])
    self._train = make_supervised_step(self.model.apply, tx, self.batch,
                                       target_ntype=target)
    params = program_params(self.weights0)
    self.state = TrainState(params, tx.init(params),
                            jnp.zeros((), jnp.int32))

    @jax.jit
    def count(acc, node_masks, edge_masks):
      return acc + jnp.stack([
          sum(jnp.sum(m, dtype=jnp.int32) for m in node_masks.values()),
          sum(jnp.sum(m, dtype=jnp.int32) for m in edge_masks.values())])
    self._count = count
    self._valid = jnp.zeros((2,), jnp.int32)
    self._extent = np.zeros((2,), np.int64)
    self._masks = []
    self._drawn, self._counts, self._batch = [], [], None

  def step(self, state, batch):
    """The jitted train step.  The batch's masks are kept (two thirds
    of a megabyte a step) and counted when the harness asks, outside
    the window: nothing is dispatched or pulled for them here."""
    self._masks.append((batch.node_mask_dict, batch.edge_mask_dict))
    return self._train(state, batch)

  def compile_count(self):
    return self._train._cache_size()

  def exchange_counts(self):
    """Valid node rows and edge slots of every batch stepped so far,
    beside the padded extents they were laid out over."""
    for node_masks, edge_masks in self._masks:
      self._valid = self._count(self._valid, node_masks, edge_masks)
      self._extent += (sum(m.shape[0] for m in node_masks.values()),
                       sum(m.shape[0] for m in edge_masks.values()))
    self._masks = []
    rows, slots = (int(v) for v in np.asarray(self._valid))
    return dict(batch_rows_valid=rows, batch_rows=int(self._extent[0]),
                batch_edges_valid=slots,
                batch_edge_slots=int(self._extent[1]))

  def row_bytes(self):
    import jax.numpy as jnp
    return (jnp.dtype(self.cfg['precision']['table']).itemsize
            * int(self.cfg['feature_dim']))

  def _check(self, b):
    """Exact counts of what is wrong in one drawn batch, on the device
    (pulled after the window)."""
    bad = dict(bad_edges=0, bad_fanout=0, dup_nodes=0, bad_seeds=0,
               bad_rows=0)
    slot_ends = dict(b.metadata['hop_capacities'][1])
    for rel, ei in b.edge_index_dict.items():
      a, _, t = rel
      indptr, indices = self.data['graphs'][build.message_type(rel)]
      e, f = ref.check_relation(
          indptr, indices, b.node_dict[a], b.node_dict[t], ei[0], ei[1],
          b.edge_mask_dict[rel], ends=slot_ends[rel], fanouts=self.fanout)
      bad['bad_edges'] += e
      bad['bad_fanout'] += f
    for t, node in b.node_dict.items():
      dup, rows = ref.check_table(self.data['feats'][t], node, b.x_dict[t])
      bad['dup_nodes'] += dup
      bad['bad_rows'] += rows
    seeds, labels = ref.check_seeds(
        self.data['labels'], b.node_dict[self.target],
        b.batch_dict[self.target], b.y_dict[self.target])
    bad['bad_seeds'] += seeds
    bad['bad_rows'] += labels
    return bad

  def _count_hops(self, b):
    """Per emitted relation and hop ``h`` (device arrays): the valid
    edges within hops ``0..h`` and one past the highest source row
    among them.  Edge blocks are laid out by hop (the batch states
    where each ends); a table is in first-occurrence order and every
    node that is no seed is the source of the edge that found it, so
    the highest source row within ``h`` hops counts a type's rows
    within ``h + 1``."""
    import jax
    import jax.numpy as jnp
    out = {}
    for rel, ends in b.metadata['hop_capacities'][1]:
      mask, src = b.edge_mask_dict[rel], b.edge_index_dict[rel][0]
      within = jnp.cumsum(mask.astype(jnp.int32))
      top = jax.lax.cummax(jnp.where(mask, src + 1, 0), axis=0)
      at = lambda a: jnp.stack([a[e - 1] if e else jnp.int32(0)
                                for e in ends])
      out[rel] = (at(within), at(top))
    return out

  def first_steps(self):
    losses, steps, mus = [], [], []
    for i in range(3):
      b = drivers.next_batch(self)
      step = dict(
          seeds=b.batch_dict[self.target], node=dict(b.node_dict),
          edges={rel: (ei[0], ei[1], b.edge_mask_dict[rel])
                 for rel, ei in b.edge_index_dict.items()},
          # what the program holds as it takes this step: the
          # reference computes this step's gradient there as well
          weights=(self.weights0 if i == 0 else
                   weights_of(self.weights0, self.state.params)))
      # checked and counted here, while the batch is alive; pulled
      # after the window
      self._drawn.append(self._check(b))
      self._counts.append(self._count_hops(b))
      steps.append([step])
      self.state, loss, _ = self.step(self.state, b)
      losses.append(float(loss))
      mus.append(weights_of(self.weights0, self.state.opt_state[0].mu))
    weights3 = weights_of(self.weights0, self.state.params)
    self._batch = b
    return dict(steps=steps, prog=ref.program_record(
        losses, self.weights0, mus, weights3, self.hyper))

  def warm(self):
    """The first steps warmed every program; one more step shows a
    second compile, if there is one, before the window."""
    self.state, loss, _ = self.step(self.state, drivers.next_batch(self))
    float(loss)
    self.exchange_counts()      # compiles the counter before the window

  def window(self, seconds):
    return ahead_window(self, seconds)

  def work(self, steps):
    """Mean over the first steps (module docstring)."""
    del steps
    depth = len(self.fanout)
    per_step = []
    for counts in self._counts:
      edges = {rel: np.asarray(e) for rel, (e, _) in counts.items()}
      # a type's valid rows within h hops of the seeds
      rows = {t: np.zeros(depth + 1, np.int64) for t in self.sizes}
      rows[self.target][:] = self.batch
      for (a, _, _), (_, top) in counts.items():
        rows[a][1:] = np.maximum(rows[a][1:], np.asarray(top))
      new_rows = {t: np.diff(r, prepend=0) for t, r in rows.items()}
      sample = 0
      for (_, _, b), within in edges.items():
        drawn = np.diff(within, prepend=0)
        # at hop h the relation's asking side reads two pointers per
        # node of b first found at hop h (hop 0: the seeds)
        sample += yardstick.sample_bytes(new_rows[b][:depth] * (drawn > 0),
                                         drawn)
      per_step.append(dict(
          step_flops=step_flops(self.cfg, rows, edges, self.batch),
          sample_bytes=sample,
          gather_bytes=sum(yardstick.gather_bytes(int(r[-1]),
                                                  self.row_bytes())
                           for r in rows.values())))
    return {k: float(np.mean([p[k] for p in per_step]))
            for k in per_step[0]}

  def probes(self):
    from graphlearn_tpu.sampler import NodeSamplerInput
    b, state = self._batch, self.state
    seeds = np.asarray(b.batch_dict[self.target])
    sampler, feats = self.loader.sampler, self.ds.node_features
    return dict(
        sample=lambda: sampler.sample_from_nodes(NodeSamplerInput(
            node=seeds, input_type=self.target)).node,
        gather=lambda: {t: feats[t].get(ids, part=t)
                        for t, ids in b.node_dict.items()},
        model=lambda: self._train(state, b)[1])

  def free(self):
    self.loader = self.it = self.state = self.ds = self._train = None
    self._batch = self.model = None
    self._masks = []
    # the reference reads the tables and the labels, not the graphs
    self.data = dict(feats=self.data['feats'], labels=self.data['labels'])

  def draw_counts(self, steps):
    """Counted in `first_steps`."""
    del steps
    return {k: int(sum(int(d[k]) for d in self._drawn))
            for k in self._drawn[0]}

  def follow(self, steps, rnd=None, half=False):
    return ref.follow(self.weights0, steps, self.data['feats'],
                      self.data['labels'], self.hyper, self.target,
                      rnd=rnd, half=half)

  def gaps(self, record, other):
    return ref.gaps(record, other)

  def unchanged(self, record):
    losses, grads, delta = record
    zeros = lambda leaves: [np.zeros_like(a) for a in leaves]
    return losses, [zeros(g) for g in grads], zeros(delta)


DRIVERS = {'loader-ahead': IgbhLoaderDriver}
