"""The typed cell's driver: `NeighborLoader` over a `Dataset` with
several node and edge types -> `models.hetero.RGCN` ->
`make_extracted_supervised_step`, one batch per step.

Found by `chipbench.drivers.make` under the configuration's
``builder`` (``typed``) and the mix's ``driver``; its data builder and
its copy of the plain reference sit beside it.  It keeps to the
protocol of `chipbench.drivers._Driver` and takes that class's
defaults where the GraphSAGE cells' answer is its own: ``gaps`` (the
worst step, the worst leaf), ``controls`` (bfloat16, float8, half a
batch) and ``unchanged``.
"""
import numpy as np

from chipbench import beside, drivers

build = beside(__file__, 'typed_build')
ref = beside(__file__, 'typed_reference')


def program_params(layers):
  """The flax tree of `models.hetero.RGCN` holding ``layers``."""
  from graphlearn_tpu.typing import as_str
  p = {}
  for i, lay in enumerate(layers):
    conv = {f'lin_self_{t}': {'kernel': q['w'], 'bias': q['b']}
            for t, q in lay['self'].items()}
    conv.update({f'lin_{as_str(et)}': {'kernel': w}
                 for et, w in lay['rel'].items()})
    p[f'conv{i}'] = conv
  return {'params': p}


def layers_of(layers0, params):
  """Inverse of `program_params`, to host float32, in ``layers0``'s
  structure."""
  from graphlearn_tpu.typing import as_str
  host = lambda a: np.asarray(a, np.float32)
  out = []
  for i, lay in enumerate(layers0):
    conv = params['params'][f'conv{i}']
    out.append(dict(
        self={t: dict(w=host(conv[f'lin_self_{t}']['kernel']),
                      b=host(conv[f'lin_self_{t}']['bias']))
              for t in lay['self']},
        rel={et: host(conv[f'lin_{as_str(et)}']['kernel'])
             for et in lay['rel']}))
  return out


class TypedLoaderDriver(drivers._Driver):

  def __init__(self, cfg, traffic, seed, model_dtype=None, data=None):
    import jax
    import optax
    from graphlearn_tpu.data import Dataset
    from graphlearn_tpu.loader import NeighborLoader
    from graphlearn_tpu.models import RGCN
    from graphlearn_tpu.models.train import (
        TrainState, make_extracted_supervised_step)
    super().__init__(cfg, traffic, seed, model_dtype)
    self.data = data or build.tables(cfg, seed)
    self.layers0 = build.weights(cfg, seed)
    self.target = target = cfg['target']
    sizes = {t: int(v['num_nodes']) for t, v in cfg['node_types'].items()}
    self.ds = (Dataset()
               .init_graph(self.data['edges'], layout='COO',
                           num_nodes=sizes)
               .init_node_features(self.data['feats'], split_ratio=1.0)
               .init_node_labels({target: self.data['labels']}))
    n_seeds = int(traffic['steps_per_epoch']) * self.batch
    rng = np.random.default_rng(self.seed)
    self.ids = rng.permutation(sizes[target])[:n_seeds]
    self.loader = NeighborLoader(self.ds, list(self.fanout),
                                 (target, self.ids),
                                 batch_size=self.batch, shuffle=True,
                                 seed=self.seed)
    self.it = iter(self.loader)
    kw = {} if model_dtype is None else dict(dtype=model_dtype)
    model = RGCN(etypes=tuple(build.message_type(et)
                              for et in build.relations(cfg)),
                 hidden_features=int(cfg['hidden']),
                 out_features=int(cfg['classes']),
                 num_layers=int(cfg['num_layers']), target_ntype=target,
                 **kw)
    h = self.hyper
    tx = optax.adam(h['lr'], b1=h['b1'], b2=h['b2'], eps=h['eps'])
    extract = lambda params, b: (
        model.apply(params, b.x_dict, b.edge_index_dict, b.edge_mask_dict),
        b.y_dict[target], b.batch_dict[target])
    self.step = jax.jit(make_extracted_supervised_step(extract, tx,
                                                       self.batch))
    params = program_params(self.layers0)
    self.state = TrainState(params, tx.init(params),
                            jax.numpy.zeros((), jax.numpy.int32))
    self._drawn, self._work = [], None

  def compile_count(self):
    return self.step._cache_size()

  def first_steps(self):
    edge_sets = {build.message_type(et): set(zip(cols.tolist(),
                                                 rows.tolist()))
                 for et, (rows, cols) in self.data['edges'].items()}
    losses, steps = [], []
    for i in range(3):
      b = drivers.next_batch(self)
      step = dict(
          seeds=b.batch_dict[self.target], node=dict(b.node_dict),
          edges={et: (ei[0], ei[1], b.edge_mask_dict[et])
                 for et, ei in b.edge_index_dict.items()})
      # checked here, while the batch is alive
      self._drawn.append(ref.check_draw(
          edge_sets, self.data, self.target, step, b.x_dict,
          b.y_dict[self.target]))
      steps.append([step])
      self.state, loss, _ = self.step(self.state, b)
      losses.append(float(loss))
      if i == 0:
        mu1 = layers_of(self.layers0, self.state.opt_state[0].mu)
    layers3 = layers_of(self.layers0, self.state.params)
    return dict(steps=steps, prog=ref.program_record(
        losses, self.layers0, mu1, layers3, self.hyper))

  def warm(self):
    batch = drivers.next_batch(self)
    self.state, loss, _ = self.step(self.state, batch)
    float(loss)

  def window(self, seconds):
    return drivers.per_batch_window(self, seconds)

  def work(self, steps):
    """Matmul FLOPs a step needs, forward and backward, from the valid
    rows and edges of the first steps: per layer a self product per
    node row and a message product per edge; the first layer takes no
    input gradient."""
    rows = {t: np.mean([int(np.sum(np.asarray(s['node'][t]) >= 0))
                        for (s,) in steps]) for t in steps[0][0]['node']}
    edges = {et: np.mean([int(np.sum(np.asarray(s['edges'][et][2])))
                          for (s,) in steps])
             for et in steps[0][0]['edges']}
    flops = 0
    for l, lay in enumerate(self.layers0):
      fwd = sum(2 * rows[t] * q['w'].size for t, q in lay['self'].items())
      fwd += sum(2 * edges.get(et, 0) * w.size
                 for et, w in lay['rel'].items())
      flops += fwd * (2 if l == 0 else 3)
    return dict(step_flops=flops)

  def free(self):
    self.loader = self.it = self.state = self.ds = self.step = None

  def draw_counts(self, steps):
    """Counted in `first_steps`."""
    del steps
    return {k: sum(d[k] for d in self._drawn) for k in self._drawn[0]}

  def follow(self, steps, rnd=None, half=False):
    import jax
    import jax.numpy as jnp
    as_device = lambda tree: jax.tree_util.tree_map(jnp.asarray, tree)
    return ref.follow(self.layers0, as_device(steps),
                      as_device(self.data['feats']),
                      jnp.asarray(self.data['labels']), self.hyper,
                      self.target, rnd=rnd, half=half)


DRIVERS = {'loader': TypedLoaderDriver}
