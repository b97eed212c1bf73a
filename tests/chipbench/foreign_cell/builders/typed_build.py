"""A typed graph, its tables, labels and weights from ``--seed``.

Per relation ``(a, rel, b)`` a COO of ``avg_degree * num_nodes[a]``
edges with uniform ends; per node type a float32 table; labels on the
target type.  Weights come in the layout-free form the plain reference
reads (`typed_reference`): per layer ``dict(self={type: dict(w=, b=)},
rel={message type: w})``, keyed by the type a relation's *messages*
carry — the sampler draws a relation outwards from the seeds and
hands its edges over reversed (`message_type`), neighbour to seed.
Host `numpy`: the fixture is toy-sized; a cell of `BENCHMARK.json`
makes its tables on the device in one jitted call.
"""
import numpy as np

from chipbench import build


def message_type(etype):
  """``(b, rev_rel, a)`` of ``(a, rel, b)``: the reversal rule of
  GraphLearn's typing (``rev_`` added or stripped; a relation within
  one type keeps its name)."""
  a, rel, b = etype
  if a != b:
    rel = rel[4:] if rel.startswith('rev_') else 'rev_' + rel
  return (b, rel, a)


def relations(cfg):
  return [tuple(r['type']) for r in cfg['relations']]


def tables(cfg, seed):
  """``dict(edges={etype: (rows, cols)}, feats={type: f32[n, d]},
  labels=int32[n_target])``."""
  rng = np.random.default_rng(build.fold_seed(seed))
  sizes = {t: int(v['num_nodes']) for t, v in cfg['node_types'].items()}
  edges = {}
  for r in cfg['relations']:
    a, _, b = etype = tuple(r['type'])
    e = int(r['avg_degree']) * sizes[a]
    edges[etype] = (rng.integers(0, sizes[a], e),
                    rng.integers(0, sizes[b], e))
  feats = {t: rng.random((sizes[t], int(v['feature_dim'])), np.float32)
           for t, v in cfg['node_types'].items()}
  labels = rng.integers(0, int(cfg['classes']),
                        sizes[cfg['target']]).astype(np.int32)
  return dict(edges=edges, feats=feats, labels=labels)


def weights(cfg, seed):
  rng = np.random.default_rng(build.fold_seed(seed) + 1)
  normal = lambda shape, scale: (
      rng.standard_normal(shape) * scale).astype(np.float32)
  din = {t: int(v['feature_dim']) for t, v in cfg['node_types'].items()}
  layers = []
  for l in range(int(cfg['num_layers'])):
    last = l == int(cfg['num_layers']) - 1
    dout = int(cfg['classes'] if last else cfg['hidden'])
    layers.append(dict(
        self={t: dict(w=normal((d, dout), d ** -0.5),
                      b=normal((dout,), 0.01))
              for t, d in sorted(din.items())},
        rel={message_type(et): normal((din[et[2]], dout),
                                      din[et[2]] ** -0.5)
             for et in relations(cfg)}))
    din = {t: dout for t in din}
  return layers
