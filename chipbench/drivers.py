"""The three drivers: what a cell's window runs, and its first steps.

A driver builds ONE program object with its state from the seed,
drives it through its first three steps (`first_steps`: the record the
plain reference is held against — these dispatches are also the
warm-up), and hands that same object to `window`.  Nothing here
computes a metric; `chipbench.run` does.

  fused       `FusedTreeEpoch.run`, back to back, one dispatch per epoch
  loader      `NeighborLoader` -> `GraphSAGE` -> `make_supervised_step`
  mesh_fused  `FusedDistTreeEpoch.run` on a 4-device mesh

A configuration whose model, graph or path is none of these brings a
driver of its own as a new file (`make`), held to the same protocol
(`_Driver`).

The fused programs keep their trees inside the scan, so `first_steps`
feeds the compiled ``[steps, B]`` program one, then two, valid batches
(the rest of the dispatch is padding, which the program treats as
no-op steps) and re-draws those steps' trees with the program's own
sampler under the program's key schedule; the loss, which the timed
program computed, ties the two together (`PERF.md`, "correct").
"""
from __future__ import annotations

import os
import time

import numpy as np

from . import build, load_file, reference


def _annot(name):
  import jax
  return jax.profiler.TraceAnnotation(name)


def _hyper(cfg):
  o = cfg['optimizer']
  return dict(lr=o['lr'], b1=o['b1'], b2=o['b2'], eps=o['eps'])


def _tx(cfg):
  import optax
  h = _hyper(cfg)
  return optax.adam(h['lr'], b1=h['b1'], b2=h['b2'], eps=h['eps'])


def _state(params, tx):
  import jax.numpy as jnp
  from graphlearn_tpu.models.train import TrainState
  return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))


def _host_layers(kind, state):
  """``(params, first moment)`` of a train state as host layer lists."""
  return (build.layers_of(kind, state.params),
          build.layers_of(kind, state.opt_state[0].mu))


class _Driver:
  """The protocol `chipbench.run` and `chipbench.limits` hold a driver
  to; they read nothing else of one.  The methods below carry the
  GraphSAGE cells' answers as defaults; a configuration's own driver
  (`make`) overrides what differs and may subclass this or not.

  Built as ``cls(cfg, traffic, seed, model_dtype=None, data=None)``:
  data and weights from the seed, ONE program object with its state.
  ``model_dtype`` switches on the model's own lower-precision path and
  ``data`` is another driver's ``.data`` (the same seed's tables, made
  once); only `limits` passes either.

  Set-up, in this order:
    first_steps()      the first three steps through the window's own
                       call and feed -> ``dict(steps=, prog=)``: what
                       was drawn (a list over steps of a list over
                       devices) and the record of what the program
                       made of it, both the driver's own to read
    warm()             whatever else the window would compile
  The window:
    compile_count()    executables the program holds, before and after
    exchange_counts()  ``{counter: n}``, before and after ({} here)
    window(seconds)    -> ``dict(seeds=, failed=, wall_s=, steps=,
                       ...)``; lists in it (per-step times) reach the
                       readers and stay off the result line
  A traced run only:
    work(steps)        ``{name: count}`` for the readers ({} here)
    probes()           ``{name: call}``, each timed alone ({} here)
  The comparison, after the window, in this order:
    exchange_checks()  exact counts only the live program can give
    free()             drop the program and its state
    draw_counts(steps) exact counts of what was drawn against the data
    follow(steps, **control)
                       the plain reference following ``steps``, in the
                       record's form; with a control's keywords, that
                       control or fault put in the program's place
    gaps(record, ref)  ``{name: number}``, each held to the limit of
                       its name in `cells/<cell>.json`
  `limits` only:
    data               (see above)
    controls()         ``{name: follow's keywords}``
    unchanged(record)  the record of a state that no step moved
  """

  def __init__(self, cfg, traffic, seed, model_dtype):
    self.cfg, self.traffic = cfg, traffic
    self.seed = build.fold_seed(seed)
    self.batch = int(traffic['batch'])
    self.fanout = tuple(cfg['fanout'])
    self.hyper = _hyper(cfg)
    self.model_dtype = model_dtype
    self._tables = None

  def model_kwargs(self):
    kw = dict(hidden_features=self.cfg['hidden'],
              out_features=self.cfg['classes'],
              num_layers=self.cfg['num_layers'])
    if self.model_dtype is not None:
      kw['dtype'] = self.model_dtype
    return kw

  def dims(self):
    return build.layer_dims(self.cfg)

  def row_bytes(self):
    return 4 * int(self.cfg['feature_dim'])

  def exchange_counts(self):
    return {}

  def exchange_checks(self):
    return {}

  def work(self, steps):
    del steps
    return {}

  def probes(self):
    return {}

  def _held(self):
    """`tables()`, asked for once: the mesh driver's puts them on the
    device."""
    if self._tables is None:
      self._tables = self.tables()
    return self._tables

  def draw_counts(self, steps):
    """Every tree of the first steps against the CSR, which nothing
    reads after this."""
    indptr, indices, feats, labels = self._held()
    self._tables = (None, None, feats, labels)
    return _tree_draw_counts(steps, indptr, indices, self.fanout)

  def follow(self, steps, rnd=None, half=False, local_only=False):
    """`reference.follow` from the seed's weights on the seed's table;
    ``rnd`` rounds every matmul operand (the controls), ``half`` leaves
    half of each batch out, ``local_only`` the exchange (the faults)."""
    import jax
    import jax.numpy as jnp
    _, _, feats, labels = self._held()
    steps = jax.tree_util.tree_map(jnp.asarray, steps)
    return reference.follow(self.kind, self.layers0, steps, feats, labels,
                            self.hyper, rnd=rnd, half=half,
                            local_only=local_only)

  def gaps(self, record, ref):
    return reference.gaps(record, ref)

  def controls(self):
    """The reference with its matmul operands rounded to bfloat16 and
    to float8 put in the program's place, and the fault a reference
    can plant on one chip."""
    return dict(reference_bfloat16=dict(rnd='bfloat16'),
                reference_float8_e4m3=dict(rnd='float8_e4m3'),
                fault_half_batch=dict(half=True))

  def unchanged(self, record):
    losses, g1, delta = record
    return (losses, [np.zeros_like(a) for a in g1],
            [np.zeros_like(a) for a in delta])


class _Single(_Driver):
  """One chip: the graph, the table and the weights, on the device."""

  def __init__(self, cfg, traffic, seed, model_dtype=None, data=None):
    import jax
    from graphlearn_tpu.data import Dataset
    super().__init__(cfg, traffic, seed, model_dtype)
    self.data = data or build.device_data(cfg, seed)
    self.indptr, self.indices, self.feats, self.labels, layers = self.data
    self.layers0 = jax.tree_util.tree_map(np.asarray, layers)
    n = int(cfg['num_nodes'])
    self.ds = (Dataset()
               .init_graph((self.indptr, self.indices), layout='CSR',
                           num_nodes=n)
               .init_node_features(self.feats)
               .init_node_labels(self.labels))
    if self.seed_set_size() < 3 * self.batch:
      raise ValueError('a mix needs three batches or more: the first '
                       'three steps are held against the reference')
    rng = np.random.default_rng(self.seed)
    self.ids = rng.permutation(n)[:self.seed_set_size()].astype(np.int64)
    self.num_devices = 1

  def tables(self):
    """What the reference reads: ``(indptr, indices, feats, labels)``
    as made from the seed (the program was handed these very
    buffers; it never wrote them)."""
    return self.indptr, self.indices, self.feats, self.labels



class FusedDriver(_Single):
  kind = 'tree'

  def seed_set_size(self):
    return int(self.traffic['steps_per_dispatch']) * self.batch

  def __init__(self, cfg, traffic, seed, model_dtype=None, data=None):
    super().__init__(cfg, traffic, seed, model_dtype, data)
    from graphlearn_tpu.loader import FusedTreeEpoch
    from graphlearn_tpu.models import TreeSAGE
    tx = _tx(cfg)
    self.epoch = FusedTreeEpoch(
        self.ds, list(self.fanout), self.ids,
        TreeSAGE(**self.model_kwargs()), tx, batch_size=self.batch,
        shuffle=True, seed=self.seed,
        max_steps_per_program=int(traffic['steps_per_dispatch']))
    self.state = _state(build.program_params('tree', self.layers0), tx)

  def compile_count(self):
    return self.epoch.compile_count()

  def _run_on(self, ids):
    """One dispatch of the compiled program over ``ids`` (whole
    batches); the dispatch's remaining steps are padding."""
    from graphlearn_tpu.loader.node_loader import SeedBatcher
    kept = self.epoch._batcher
    self.epoch._batcher = SeedBatcher(ids, self.batch, shuffle=False)
    try:
      self.state, stats = self.epoch.run(self.state)
    finally:
      self.epoch._batcher = kept
    return np.asarray(stats.losses), self.epoch._epoch_idx

  def _trees(self, epoch_idx, batches):
    """The trees the program drew in the first ``len(batches)`` steps
    of epoch ``epoch_idx``: its own sampler, its own key schedule."""
    import jax
    import jax.numpy as jnp
    from graphlearn_tpu.loader.fused_tree import expand_tree_levels
    key = jax.random.fold_in(jax.random.key(self.seed), epoch_idx)
    draw = jax.jit(lambda ip, ix, s, k: expand_tree_levels(
        ip, ix, s, k, self.fanout, sort_locality=False)[0])
    out = []
    for i, seeds in enumerate(batches):
      seeds = jnp.asarray(seeds, jnp.int32)
      levels = draw(self.indptr, self.indices, seeds,
                    jax.random.fold_in(key, i))
      out.append([dict(seeds=seeds, levels=levels)])
    return out

  def first_steps(self):
    b = self.batch
    first = self.ids[:3 * b].reshape(3, b)
    loss1, e1 = self._run_on(first[0])
    layers1, mu1 = _host_layers('tree', self.state)
    loss23, e2 = self._run_on(first[1:].reshape(-1))
    layers3, _ = _host_layers('tree', self.state)
    steps = self._trees(e1, first[:1]) + self._trees(e2, first[1:])
    return dict(
        steps=steps,
        prog=reference.program_record(
            list(loss1) + list(loss23), self.layers0, layers1, mu1,
            layers3, self.hyper))

  def warm(self):
    """One whole dispatch over the window's own seed set."""
    self.state, stats = self.epoch.run(self.state)
    _pull(stats)

  def window(self, seconds):
    return _dispatch_window(self, seconds,
                            int(self.traffic['steps_per_dispatch']))

  def work(self, steps):
    return _tree_work(self, steps)

  def probes(self):
    import jax
    import jax.numpy as jnp
    import optax
    from graphlearn_tpu.loader.fused_tree import expand_tree_levels
    seeds = jnp.asarray(self.ids[:self.batch], jnp.int32)
    key = jax.random.key(self.seed + 1)
    draw = jax.jit(lambda ip, ix, s, k: expand_tree_levels(
        ip, ix, s, k, self.fanout, sort_locality=False))
    levels, masks = draw(self.indptr, self.indices, seeds, key)
    feat = self.ds.node_features
    xs = [feat[lv] for lv in levels]
    all_ids = jnp.concatenate(levels)
    y = reference.take_rows(self.labels, seeds)
    apply, tx = self.epoch.model.apply, self.epoch.tx

    @jax.jit
    def model(state, xs, masks, y):
      def loss_fn(params):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            apply(params, xs, masks), y)
        return ce.mean()
      loss, grads = jax.value_and_grad(loss_fn)(state.params)
      updates, opt = tx.update(grads, state.opt_state, state.params)
      return optax.apply_updates(state.params, updates), opt, loss

    state = self.state
    return dict(
        sample=lambda: draw(self.indptr, self.indices, seeds, key),
        gather=lambda: feat[all_ids],
        model=lambda: model(state, xs, masks, y))

  def free(self):
    self.epoch = self.state = self.ds = self.data = None


def _pull(stats):
  """A value pull ends a dispatch: ``(seeds trained, steps whose loss
  is not finite)``, once its losses are on the host."""
  losses = np.asarray(stats.losses)
  return int(stats.seeds), int(np.sum(~np.isfinite(losses)))


def _dispatch_window(drv, seconds, steps_per_dispatch):
  """Dispatches back to back, one in flight behind the one being
  pulled, until the first dispatch boundary past ``seconds``; the
  window's wall runs from the first dispatch to the last pull."""
  t0 = time.perf_counter()
  seeds = failed = dispatches = 0
  pending = None
  while True:
    with _annot('chipbench.dispatch'):
      drv.state, stats = drv.epoch.run(drv.state)
    dispatches += 1
    if pending is not None:
      with _annot('chipbench.pull'):
        s, f = _pull(pending)
      seeds, failed = seeds + s, failed + f
      if time.perf_counter() - t0 >= seconds:
        break
    pending = stats
  with _annot('chipbench.pull'):
    s, f = _pull(stats)
  return dict(seeds=seeds + s, failed=failed + f,
              wall_s=time.perf_counter() - t0,
              steps=dispatches * steps_per_dispatch,
              dispatches=dispatches)


def _tree_draw_counts(steps, indptr, indices, fanout):
  """Every tree of the first steps against the CSR."""
  import jax.numpy as jnp
  bad = dict(bad_edges=0, bad_fanout=0)
  for shards in steps:
    for s in shards:
      e, f = reference.check_tree(
          indptr, indices, [jnp.asarray(lv) for lv in s['levels']],
          fanouts=tuple(fanout))
      bad['bad_edges'] += int(e)
      bad['bad_fanout'] += int(f)
  return bad


def _tree_work(drv, steps):
  """Work one step needs, from the valid slots of the trees the first
  steps drew (mean over steps; per device on a mesh)."""
  import jax.numpy as jnp
  from . import yardstick
  shards = [s for step in steps for s in step]
  counts = np.mean(
      [[int(jnp.sum(lv >= 0)) for lv in s['levels']] for s in shards],
      axis=0)
  per_step = len(steps[0])
  counts = counts * per_step
  return dict(
      step_flops=yardstick.tree_step_flops(list(counts), drv.dims()),
      sample_bytes=yardstick.sample_bytes(counts[:-1] / per_step,
                                          counts[1:] / per_step),
      gather_bytes=yardstick.gather_bytes(counts.sum() / per_step,
                                          drv.row_bytes()))


def next_batch(drv):
  """The next batch of ``drv.loader``, epoch after epoch (``drv.it`` is
  the epoch's iterator)."""
  try:
    return next(drv.it)
  except StopIteration:
    drv.it = iter(drv.loader)
    return next(drv.it)


def per_batch_window(drv, seconds):
  """One batch per step (`next_batch`), each step ended by a value
  pull, until the step in which ``seconds`` passes.
  ``drv.step(drv.state, batch)`` returns ``(state, loss, ...)`` and
  every batch trains ``drv.batch`` seeds."""
  t0 = time.perf_counter()
  waits, step_s = [], []
  seeds = failed = 0
  while True:
    t1 = time.perf_counter()
    with _annot('chipbench.next_loader'):
      b = next_batch(drv)
    t2 = time.perf_counter()
    with _annot('chipbench.step'):
      drv.state, loss, _ = drv.step(drv.state, b)
      loss = float(loss)
    t3 = time.perf_counter()
    failed += int(not np.isfinite(loss))
    seeds += drv.batch
    waits.append(t2 - t1)
    step_s.append(t3 - t1)
    if t3 - t0 >= seconds:
      break
  return dict(seeds=seeds, failed=failed, wall_s=t3 - t0,
              steps=len(step_s), loader_wait_s=waits, step_s=step_s)


class LoaderDriver(_Single):
  kind = 'subgraph'

  def seed_set_size(self):
    return int(self.traffic['steps_per_epoch']) * self.batch

  def __init__(self, cfg, traffic, seed, model_dtype=None, data=None):
    super().__init__(cfg, traffic, seed, model_dtype, data)
    from graphlearn_tpu.loader import NeighborLoader
    from graphlearn_tpu.models import GraphSAGE, make_supervised_step
    tx = _tx(cfg)
    self.loader = NeighborLoader(self.ds, list(self.fanout), self.ids,
                                 batch_size=self.batch, shuffle=True,
                                 seed=self.seed)
    self.model = GraphSAGE(**self.model_kwargs())
    self.step = make_supervised_step(self.model.apply, tx, self.batch)
    self.state = _state(build.program_params('subgraph', self.layers0),
                        tx)
    self.it = iter(self.loader)
    self._drawn, self._hops, self._batch = None, [], None

  def compile_count(self):
    return self.step._cache_size()

  def first_steps(self):
    losses, steps, drawn = [], [], []
    for i in range(3):
      b = next_batch(self)
      shard = dict(seeds=b.batch, node=b.node, src=b.edge_index[0],
                   dst=b.edge_index[1], edge_ok=b.edge_mask)
      # the gathered rows are checked here, while the batch is alive;
      # the counts are pulled after the window
      self._hops.append((b.num_sampled_nodes, b.num_sampled_edges))
      drawn.append(reference.check_subgraph(
          self.indptr, self.indices, self.feats, self.labels, b.node,
          shard['src'], shard['dst'], shard['edge_ok'], b.batch, b.x,
          b.y, max(self.fanout)))
      steps.append([shard])
      self.state, loss, _ = self.step(self.state, b)
      losses.append(float(loss))
      if i == 0:
        layers1, mu1 = _host_layers('subgraph', self.state)
    layers3, _ = _host_layers('subgraph', self.state)
    self._drawn, self._batch = drawn, b
    return dict(
        steps=steps,
        prog=reference.program_record(losses, self.layers0, layers1, mu1,
                                      layers3, self.hyper))

  def draw_counts(self, steps):
    """Counted in `first_steps`, while each batch was alive."""
    del steps
    bad = {}
    for counts in self._drawn:
      for k, v in counts.items():
        bad[k] = bad.get(k, 0) + int(v)
    return bad

  def warm(self):
    """The first steps warmed every program; one more step shows a
    second compile, if there is one, before the window."""
    self.state, loss, _ = self.step(self.state, next_batch(self))
    float(loss)

  def work(self, steps):
    from . import yardstick
    del steps
    nodes = np.mean([np.asarray(n) for n, _ in self._hops], axis=0)
    edges = np.mean([np.asarray(e) for _, e in self._hops], axis=0)
    return dict(
        step_flops=yardstick.subgraph_step_flops(list(nodes),
                                                 self.dims()),
        sample_bytes=yardstick.sample_bytes(nodes[:-1], edges),
        gather_bytes=yardstick.gather_bytes(nodes.sum(),
                                            self.row_bytes()))

  def probes(self):
    from graphlearn_tpu.sampler import NodeSamplerInput
    b, state = self._batch, self.state
    seeds = np.asarray(b.batch)
    sampler, feat = self.loader.sampler, self.ds.node_features
    return dict(
        sample=lambda: sampler.sample_from_nodes(
            NodeSamplerInput(node=seeds)).node,
        gather=lambda: feat[b.node],
        model=lambda: self.step(state, b)[1])

  def window(self, seconds):
    return per_batch_window(self, seconds)

  def free(self):
    self.loader = self.it = self.state = self.ds = self.step = None
    self._batch = self.data = None


class MeshFusedDriver(_Driver):
  """Four chips, one process: the graph partitioned over a mesh by
  `DistDataset.from_full_graph`, `FusedDistTreeEpoch.run` back to
  back.  The benchmark's own tables stay on the HOST until the window
  has closed, so that no device's peak is the reference's."""
  kind = 'tree'

  def __init__(self, cfg, traffic, seed, model_dtype=None, data=None):
    from graphlearn_tpu.models import TreeSAGE
    from graphlearn_tpu.parallel import (DistDataset, FusedDistTreeEpoch,
                                         make_mesh, replicate)
    super().__init__(cfg, traffic, seed, model_dtype)
    self.num_devices = p = int(cfg['chips'])
    self.steps = int(traffic['steps_per_dispatch'])
    n = self.n = int(cfg['num_nodes'])
    if data is None:
      coo = build.host_coo(cfg, seed)
      data = coo + (DistDataset.from_full_graph(
          p, coo[0], coo[1], node_feat=coo[2], node_label=coo[3],
          num_nodes=n),)
    self.data = data
    self.rows, self.cols, self.feats, self.labels, self.dds = data
    self.layers0 = build.host_layers(cfg, seed)
    self.new2old = np.empty(n, np.int64)
    self.new2old[self.dds.old2new] = np.arange(n)
    self.mesh = make_mesh(p)
    rng = np.random.default_rng(self.seed)
    self.ids = rng.permutation(n)[:self.steps * p * self.batch]
    tx = _tx(cfg)
    self.epoch = FusedDistTreeEpoch(
        self.dds, list(self.fanout), self.ids,
        TreeSAGE(**self.model_kwargs()), tx,
        batch_size=self.batch, mesh=self.mesh, shuffle=True,
        seed=self.seed)
    self.state = replicate(
        _state(build.program_params('tree', self.layers0), tx), self.mesh)
    self._rows_bad = 0

  def compile_count(self):
    return self.epoch.compile_count()

  def exchange_counts(self):
    st = self.epoch.sampler.exchange_stats(tick_metrics=False)
    sent = st['dist.frontier.slots'] + st['dist.feature.slots']
    kept = (st['dist.frontier.offered'] - st['dist.frontier.dropped']
            + st['dist.feature.offered'] - st['dist.feature.dropped'])
    return dict(
        exchange_sent=sent, exchange_padded=sent - kept,
        exchange_dropped=(st['dist.frontier.dropped']
                          + st['dist.feature.dropped']))

  def exchange_checks(self):
    """Nothing the exchange was offered may be dropped, from the first
    step to the end of the window; no row that crossed a shard may
    differ from the table."""
    return dict(dropped=self.exchange_counts()['exchange_dropped'],
                bad_rows=self._rows_bad)

  def _run_on(self, old_ids):
    """One dispatch of the compiled ``[steps, P, B]`` program whose
    first steps train ``old_ids`` (whole global batches); the rest of
    the dispatch is padding."""
    from graphlearn_tpu.loader.node_loader import SeedBatcher
    g = self.batch * self.num_devices
    new = self.dds.old2new[old_ids]
    padded = np.concatenate(
        [new, np.full(self.steps * g - len(new), -1, new.dtype)])
    kept = self.epoch._batcher
    self.epoch._batcher = SeedBatcher(padded, g, shuffle=False)
    try:
      self.state, stats = self.epoch.run(self.state)
    finally:
      self.epoch._batcher = kept
    return np.asarray(stats.losses)[:len(new) // g], self.epoch._epoch_idx

  def _trees(self, epoch_idx, batches_old):
    """What each device drew and was sent in the first steps of epoch
    ``epoch_idx``: the program's own collect body, its key schedule.
    Ids go back to the seed's numbering through the dataset's
    relabelling; the rows that crossed shards are held against the
    table at once (three columns exactly, the row sum to rounding)."""
    import jax
    collect = jax.jit(self.epoch._make_collect_sharded())
    arrs = self.epoch.sampler._arrays()
    key = jax.random.fold_in(jax.random.key(self.seed), epoch_idx)
    p, b = self.num_devices, self.batch
    sizes = np.cumsum([0] + [b * int(np.prod(self.fanout[:t]))
                             for t in range(len(self.fanout) + 1)])
    cols = np.array([0, self.feats.shape[1] // 3, self.feats.shape[1] - 1])
    out = []
    for i, old in enumerate(batches_old):
      seeds = self.epoch._put_batches(
          self.dds.old2new[old].reshape(1, p, b))[0]
      ids, x, y = collect(
          seeds, jax.random.fold_in(key, i), arrs['indptr'],
          arrs['indices'], arrs['bounds'], arrs['fshards'],
          arrs['lshards'], arrs['hcounts'])[:3]
      ids = np.asarray(ids)
      ok = ids >= 0
      old_ids = np.where(ok, self.new2old[np.where(ok, ids, 0)], -1)
      want = self.feats[np.where(ok, old_ids, 0)]
      want[~ok] = 0
      got_cols = np.asarray(x[..., cols])
      got_sum = np.asarray(x.sum(-1))
      bad = np.any(got_cols != want[..., cols], axis=-1)
      bad |= np.abs(got_sum - want.sum(-1)) > 1e-3
      self._rows_bad += int(bad.sum())
      old_seeds = old.reshape(p, b)
      self._rows_bad += int(np.sum(np.asarray(y) != self.labels[old_seeds]))
      bounds = np.asarray(self.dds.graph.bounds)
      owned = (ids >= bounds[:-1, None]) & (ids < bounds[1:, None])
      out.append([dict(seeds=old_seeds[d].astype(np.int32),
                       levels=[old_ids[d, s:e].astype(np.int32)
                               for s, e in zip(sizes, sizes[1:])],
                       owned=[owned[d, s:e].astype(np.float32)
                              for s, e in zip(sizes, sizes[1:])])
                  for d in range(p)])
    return out

  def first_steps(self):
    g = self.batch * self.num_devices
    first = self.ids[:3 * g].reshape(3, g)
    loss1, e1 = self._run_on(first[0])
    layers1, mu1 = _host_layers('tree', self.state)
    loss23, e2 = self._run_on(first[1:].reshape(-1))
    layers3, _ = _host_layers('tree', self.state)
    steps = self._trees(e1, first[:1]) + self._trees(e2, first[1:])
    return dict(
        steps=steps,
        prog=reference.program_record(
            list(loss1) + list(loss23), self.layers0, layers1, mu1,
            layers3, self.hyper))

  def warm(self):
    self.state, stats = self.epoch.run(self.state)
    _pull(stats)

  def window(self, seconds):
    return _dispatch_window(self, seconds, self.steps)

  def work(self, steps):
    import jax.numpy as jnp
    steps = [[dict(s, levels=[jnp.asarray(lv) for lv in s['levels']])
              for s in shards] for shards in steps]
    return _tree_work(self, steps)

  def free(self):
    import gc
    self.epoch = self.state = self.dds = self.mesh = self.data = None
    gc.collect()

  def tables(self):
    """The seed's COO sorted to a CSR, the table and the labels, put
    on the first device now that the program is gone."""
    import jax.numpy as jnp
    indptr, indices = build.device_csr(self.rows, self.cols, self.n)
    self.rows = self.cols = None
    return (indptr, indices, jnp.asarray(self.feats),
            jnp.asarray(self.labels))

  def controls(self):
    return dict(super().controls(),
                fault_no_exchange=dict(local_only=True))


DRIVERS = {('single', 'fused'): FusedDriver,
           ('single', 'loader'): LoaderDriver,
           ('mesh', 'fused'): MeshFusedDriver}


def make(cfg, traffic, seed, builders_dir=None, **kw):
  """The driver of a configuration's ``builder`` under a mix's
  ``driver``: one of `DRIVERS`, or — for a configuration that brings
  its own — ``DRIVERS[<driver>]`` of `<builders_dir>/<builder>.py`
  (`chipbench/builders/` of the root that holds `BENCHMARK.json`),
  which keeps its data builder and its copy of the plain reference
  beside it (`chipbench.beside`)."""
  key = (cfg['builder'], traffic['driver'])
  cls = DRIVERS.get(key)
  if cls is None:
    path = os.path.join(
        builders_dir or os.path.join(os.path.dirname(__file__), 'builders'),
        key[0] + '.py')
    if not os.path.exists(path):
      raise SystemExit(f'chipbench: no driver for builder/driver {key}: '
                       f'not in drivers.DRIVERS, and no file {path}')
    found = getattr(load_file(path), 'DRIVERS', {})
    if key[1] not in found:
      raise SystemExit(f'chipbench: no driver for builder/driver {key}: '
                       f'{path} has DRIVERS {sorted(found)}')
    cls = found[key[1]]
  return cls(cfg, traffic, seed, **kw)
