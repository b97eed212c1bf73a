"""The `sage-products-link` cell's driver: `FusedLinkEpoch.run` back to
back — per step the strict negative draw, the deduplicated expansion
of the 1,024 endpoints, the feature gather, `GraphSAGE` over the hops
each layer feeds, the link loss and Adam, in one scan program — under
the window of the fused cells (`drivers._dispatch_window`).

Found by `chipbench.drivers.make` under the configuration's
``builder`` (``link_fused``) and the mix's ``driver`` (``fused``); its
data builder and its copy of the plain reference sit beside it.  The
driver asks the program for what the parent of this cell lacks
(`FusedLinkEpoch.batch_fill`, whose batches state their hop layout)
before it builds anything: a tree without it exits non-zero at once.

The first three steps are three dispatches of one valid batch each
(the rest of a dispatch is padding, which the program treats as no-op
steps): Adam's first moment after each is what the program's gradient
was, and the weights before each are where the reference computes
that step's gradient too (`link_fused_reference.gaps`).  Each step's
batch is drawn again by the epoch's own sample-only scan under the
epoch's own key (`FusedLinkEpoch.epoch_key`), and held, while it is
alive, against the CSR and the table (`link_fused_reference.
check_batch`).

Compiling is most of a cold run (a program with a device sort takes a
minute, and the dedup's sixteen sorts sit in both scans), so the
comparison's programs — the collect, the check, the reference's step —
are compiled from shapes one after the other on a thread as soon as the
data exists, and the epoch program, for the persistent cache's sake,
on a second (`_Chain`).  Two at a time, not four: the four compiled at
once for a described v5e overflowed the TPU compiler's stack in one of
its passes (`ReplaceElementwiseGroupSurroundedByReshapesAndBroadcasts`)
where each alone, and each pair tried (the epoch beside the collect
or the check, the check beside the collect or the reference's step),
compiles (`tests/chipbench/real_size_compile_link.py`).

The work counts (`work`), from counts and shapes alone:

  step_flops      matmul FLOPs, forward and backward, of the valid rows
                  within reach of the endpoints
                  (`yardstick.subgraph_step_flops` over the first
                  steps' per-hop node counts);
  negative_bytes  what the strict draw must read and write
                  (`negative_bytes`).

The window's counters (`exchange_counts`): the valid node rows and
edge slots of every batch the program ran, beside the padded extents,
counted on the device by the program (`FusedLinkEpoch.batch_fill`) and
pulled when the harness asks, outside the window.
"""
import concurrent.futures
import contextlib

import numpy as np

from chipbench import beside, build as base, drivers, yardstick

build = beside(__file__, 'link_fused_build')
ref = beside(__file__, 'link_fused_reference')


class _Chain:
  """Jitted programs compiled one after another on a thread of their
  own, each ``(jitted, args)`` for its ``args`` (arrays or shapes),
  under the matmul precision in force where they were asked for (a
  thread inherits none).  ``chain[i]`` waits for the ``i``-th and is
  its executable; ``wait()`` waits for them all."""
  _pool = concurrent.futures.ThreadPoolExecutor(
      max_workers=2, thread_name_prefix='chipbench-link-compile')

  def __init__(self, *programs):
    import jax
    precision = jax.config.jax_default_matmul_precision

    def compile_(jitted, args):
      ctx = (jax.default_matmul_precision(precision) if precision
             else contextlib.nullcontext())
      with ctx:
        return jitted.lower(*args).compile()

    self._done = [concurrent.futures.Future() for _ in programs]

    def compile_all():
      for (jitted, args), done in zip(programs, self._done):
        try:
          done.set_result(compile_(jitted, args))
        except Exception as e:     # the caller's to meet, in `wait`
          done.set_exception(e)
    self._pool.submit(compile_all)

  def wait(self):
    for done in self._done:
      done.result()

  def __getitem__(self, i):
    return self._done[i].result()


def negative_bytes(indptr, req_num: int, trials: int,
                   id_bytes: int = 4) -> float:
  """Bytes a strict binary draw of ``req_num`` pairs with ``trials``
  redraws must move: per slot and trial the drawn row's two row
  pointers and a binary search of ``ceil(log2(deg + 1))`` of its
  column ids, and the pair written out once.  The row is uniform, so
  ``deg`` is averaged over the graph's rows (the search's depth
  depends only on the drawn row's degree).  The random draws and the
  padding fall-back are not counted."""
  import jax.numpy as jnp
  deg = (indptr[1:] - indptr[:-1]).astype(jnp.float32)
  depth = float(jnp.mean(jnp.ceil(jnp.log2(deg + 1.0))))
  return req_num * (trials * id_bytes * (2.0 + depth) + 2 * id_bytes)


class LinkFusedDriver(drivers._Driver):
  kind = 'subgraph'

  def __init__(self, cfg, traffic, seed, model_dtype=None, data=None):
    import jax
    from graphlearn_tpu.data import Dataset
    from graphlearn_tpu.loader import FusedLinkEpoch
    from graphlearn_tpu.models import GraphSAGE
    from graphlearn_tpu.sampler import NegativeSampling
    from graphlearn_tpu.sampler import neighbor_sampler
    if not hasattr(FusedLinkEpoch, 'batch_fill'):
      raise SystemExit(
          'chipbench: the link_fused builder needs FusedLinkEpoch.'
          'batch_fill and link batches that state their hop layout; '
          'this tree has neither')
    super().__init__(cfg, traffic, seed, model_dtype)
    neg = cfg['negatives']
    if (neg['strict'], neg['padding'], neg['trials']) != (
        True, True, neighbor_sampler.NEG_TRIALS):
      raise ValueError(f'the program draws strict, padded negatives with '
                       f'{neighbor_sampler.NEG_TRIALS} trials; the '
                       f'configuration states {neg}')
    self.neg = NegativeSampling(neg['mode'], neg['amount'])
    self.steps = int(traffic['steps_per_dispatch'])
    if self.steps < 3:
      raise ValueError('a mix needs three batches or more: the first '
                       'three steps are held against the reference')
    n = int(cfg['num_nodes'])
    self.data = data or build.device_data(cfg, seed)
    self.indptr, self.indices, self.feats, layers = self.data
    self.layers0 = jax.tree_util.tree_map(np.asarray, layers)
    self.src, self.dst = build.seed_edges(self.indptr, self.indices,
                                          self.steps * self.batch, seed)
    ds = (Dataset()
          .init_graph((self.indptr, self.indices), layout='CSR',
                      num_nodes=n)
          .init_node_features(self.feats))
    self.model = GraphSAGE(**self.model_kwargs())
    tx = drivers._tx(cfg)
    self.epoch = FusedLinkEpoch(
        ds, list(self.fanout), (self.src, self.dst), self.model.apply, tx,
        batch_size=self.batch, neg_sampling=self.neg, shuffle=True,
        seed=self.seed, max_steps_per_program=self.steps)
    self.state = drivers._state(
        base.program_params('subgraph', self.layers0), tx)
    self.num_neg = neighbor_sampler.link_plan(self.neg, self.batch)[1]
    self._drawn, self._hops = [], []
    self._compile_ahead()

  def model_kwargs(self):
    kw = dict(hidden_features=self.cfg['hidden'],
              out_features=self.cfg['hidden'],
              num_layers=self.cfg['num_layers'])
    if self.model_dtype is not None:
      kw['dtype'] = self.model_dtype
    return kw

  def dims(self):
    return build.layer_dims(self.cfg)

  def _one_batch(self, i):
    """The ``i``-th batch of the seed edges, as ``[1, B]`` arrays."""
    b = self.batch
    return (self.src[i * b:(i + 1) * b][None].astype(np.int32),
            self.dst[i * b:(i + 1) * b][None].astype(np.int32))

  def _compile_ahead(self):
    """The comparison's programs from shapes on one thread — the
    epoch's collect with the gather, the check, the reference's step —
    and, where a persistent compile cache is on, the epoch program on
    another, for the cache's sake (module docstring)."""
    import functools
    import jax
    import jax.numpy as jnp
    ep, b = self.epoch, self.batch
    (nodes, edges), _ = ep._layout
    cap, slots, width = nodes[-1], edges[-1], nodes[0]
    pairs = b + self.num_neg
    sds = jax.ShapeDtypeStruct
    i32, ok = jnp.int32, jnp.bool_
    one = sds((1, b), i32)
    key = ep.epoch_key(0)
    step = dict(node=sds((cap,), i32), src=sds((slots,), i32),
                dst=sds((slots,), i32), edge_ok=sds((slots,), ok),
                eli=sds((2, pairs), i32), label=sds((pairs,), i32),
                mask=sds((pairs,), ok))
    self._chains = []
    if jax.config.jax_compilation_cache_dir:
      full = sds((self.steps, b), i32)
      self._chains.append(_Chain((ep._compiled.jitted, (
          self.state, full, full, None, key, ep._dev, False))))
    self._ahead = _Chain(
        (jax.jit(functools.partial(ep._link_collect_fn, collect_x=True)),
         (one, one, one, key, ep._dev)),
        (jax.jit(functools.partial(ref.check_batch, batch=b,
                                   ends=tuple(edges),
                                   fanouts=self.fanout)),
         (self.indptr, self.indices, self.feats, sds((cap,), i32),
          sds((slots,), i32), sds((slots,), i32), sds((slots,), ok),
          sds((width,), i32), sds((2, pairs), i32), sds((pairs,), i32),
          sds((pairs,), ok), sds((cap, self.feats.shape[1]),
                                  jnp.float32))),
        (jax.jit(ref.loss_and_grad),
         (jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                 self.layers0), step, self.feats)))
    self._chains.append(self._ahead)

  def compile_count(self):
    return self.epoch.compile_count()

  def exchange_counts(self):
    fill = self.epoch.batch_fill()
    return dict(batch_rows_valid=fill['rows_valid'],
                batch_rows=fill['rows'],
                batch_edges_valid=fill['edges_valid'],
                batch_edge_slots=fill['edge_slots'])

  def _run_on(self, i):
    """One dispatch of the compiled program over the ``i``-th batch;
    the dispatch's other steps are padding.  ``(loss, epoch index)``."""
    from graphlearn_tpu.loader.link_loader import EdgeSeedBatcher
    src, dst = self._one_batch(i)
    kept = self.epoch._batcher
    self.epoch._batcher = EdgeSeedBatcher(src[0], dst[0], None,
                                          self.batch, shuffle=False)
    try:
      self.state, stats = self.epoch.run(self.state)
    finally:
      self.epoch._batcher = kept
    return float(np.asarray(stats.losses)[0]), self.epoch._epoch_idx

  def _draw(self, i, epoch_idx):
    """Step ``i``'s batch drawn again by the epoch's own collect under
    its own key, checked against the CSR and the table while it is
    alive (counts pulled after the window); the ids the reference
    reads."""
    import jax
    import jax.numpy as jnp
    sp, dp = (jnp.asarray(a) for a in self._one_batch(i))
    got = self._ahead[0](sp, dp, jnp.ones_like(sp),
                         self.epoch.epoch_key(epoch_idx), self.epoch._dev)
    b = jax.tree_util.tree_map(lambda a: a[0], got)
    md = b.metadata
    self._drawn.append(self._ahead[1](
        self.indptr, self.indices, self.feats, b.node, b.edge_index[0],
        b.edge_index[1], b.edge_mask, b.batch, md['edge_label_index'],
        md['edge_label'], md['edge_label_mask'], b.x))
    self._hops.append(b.num_sampled_nodes)
    return dict(node=b.node, src=b.edge_index[0], dst=b.edge_index[1],
                edge_ok=b.edge_mask, eli=md['edge_label_index'],
                label=md['edge_label'], mask=md['edge_label_mask'])

  def first_steps(self):
    for chain in self._chains:
      chain.wait()
    losses, mus, steps = [], [], []
    for i in range(3):
      before = base.layers_of('subgraph', self.state.params)
      loss, epoch_idx = self._run_on(i)
      losses.append(loss)
      mus.append(base.layers_of('subgraph', self.state.opt_state[0].mu))
      steps.append([dict(self._draw(i, epoch_idx), weights=before)])
    layers3 = base.layers_of('subgraph', self.state.params)
    return dict(steps=steps, prog=ref.program_record(
        losses, self.layers0, mus, layers3, self.hyper))

  def warm(self):
    """One whole dispatch over the window's own seed edges."""
    self.state, stats = self.epoch.run(self.state)
    drivers._pull(stats)

  def window(self, seconds):
    return drivers._dispatch_window(self, seconds, self.steps)

  def work(self, steps):
    """Mean over the first steps (module docstring)."""
    del steps
    hops = np.mean([np.asarray(h) for h in self._hops], axis=0)
    from graphlearn_tpu.sampler.neighbor_sampler import NEG_TRIALS
    return dict(
        step_flops=yardstick.subgraph_step_flops(list(hops), self.dims()),
        negative_bytes=negative_bytes(self.indptr, self.num_neg,
                                      NEG_TRIALS))

  def free(self):
    """The program, its state and the CSR go: the reference reads the
    table and the ids the first steps drew, beside its own 8 GB of
    temporaries (the real-size compile)."""
    import gc
    self.epoch = self.state = self.model = None
    self._chains = []
    self.indptr = self.indices = self.data = None
    gc.collect()          # the epoch's jitted methods hold it in a cycle

  def draw_counts(self, steps):
    """Counted in `first_steps`, while each batch was alive."""
    del steps
    return {k: int(sum(int(d[k]) for d in self._drawn))
            for k in self._drawn[0]}

  def follow(self, steps, **fault):
    """The plain reference following ``steps``; a fault is a program
    of its own, compiled when asked for (`chipbench.limits` only)."""
    import functools
    import jax
    step_fn = (jax.jit(functools.partial(ref.loss_and_grad, **fault))
               if fault else self._ahead[2])
    return ref.follow(self.layers0, steps, self.feats, self.hyper,
                      step_fn)

  def gaps(self, record, other):
    return ref.gaps(record, other)

  def controls(self):
    """The faults a reference can plant; the precision controls are
    the program's own variants (`chipbench.limits --variants`)."""
    return dict(fault_negatives_positive=dict(neg_positive=True),
                fault_half_pairs=dict(half=True))

  def unchanged(self, record):
    losses, grads, delta = record
    zeros = lambda leaves: [np.zeros_like(a) for a in leaves]
    return losses, [zeros(g) for g in grads], zeros(delta)


DRIVERS = {'fused': LinkFusedDriver}
