"""RGNN (RGAT / RSAGE) on an IGBH-style academic heterogeneous graph.

TPU counterpart of reference `examples/igbh/{dataset,rgnn,train_rgnn}.py`
— the BASELINE scaling workload: 4 node types (paper, author,
institute, fos), 4 relation types + reversed, hetero neighbor sampling
with per-hop fanouts, and a relational GNN classifying papers.
``--model rgat`` is the package's `graphlearn_tpu.models.RGAT`
(per-relation GAT attention summed into the target type, the
reference's RGAT); ``--model rsage`` is the same stack with its
per-relation conv swapped for `SAGEConv`.  Both train through
`make_supervised_step`, so each layer runs only over the hops it feeds
(the typed loader's batches state their hop layout).
Zero-egress stand-in for IGBH-tiny: a synthetic academic graph whose
paper topic is encoded in its fos (field-of-study) links.

Usage::

    python examples/igbh/train_rgnn.py --model rgat [--epochs 4] [--cpu]
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

import numpy as np

P, A, I, F = 'paper', 'author', 'institute', 'fos'
ETYPES = {
    'cites': (P, 'cites', P),
    'written_by': (P, 'written_by', A),
    'rev_written_by': (A, 'rev_written_by', P),
    'affiliated_to': (A, 'affiliated_to', I),
    'rev_affiliated_to': (I, 'rev_affiliated_to', A),
    'topic': (P, 'topic', F),
    'rev_topic': (F, 'rev_topic', P),
}


def synthetic(npaper=4000, nauthor=1600, ninst=80, nfos=64, classes=8,
              d=32, seed=0):
  rng = np.random.default_rng(seed)
  topic = rng.integers(0, classes, npaper)
  fos_of_class = nfos // classes

  def paper_peers(src_topic):
    order = np.argsort(topic, kind='stable')
    ptr = np.searchsorted(topic[order], np.arange(classes + 1))
    out = np.empty(len(src_topic), np.int64)
    for c in range(classes):
      m = src_topic == c
      out[m] = order[rng.integers(ptr[c], ptr[c + 1], m.sum())]
    return out

  crow = np.repeat(np.arange(npaper), 3)
  ccol = np.where(rng.random(npaper * 3) < 0.7, paper_peers(topic[crow]),
                  rng.integers(0, npaper, npaper * 3))
  wrow = np.repeat(np.arange(npaper), 2)
  wcol = rng.integers(0, nauthor, npaper * 2)
  arow = np.arange(nauthor)
  acol = rng.integers(0, ninst, nauthor)
  # fos links carry the class signal
  frow = np.repeat(np.arange(npaper), 2)
  fcol = (topic[frow] * fos_of_class
          + rng.integers(0, fos_of_class, npaper * 2))

  edges = {
      ETYPES['cites']: (crow, ccol),
      ETYPES['written_by']: (wrow, wcol),
      ETYPES['rev_written_by']: (wcol, wrow),
      ETYPES['affiliated_to']: (arow, acol),
      ETYPES['rev_affiliated_to']: (acol, arow),
      ETYPES['topic']: (frow, fcol),
      ETYPES['rev_topic']: (fcol, frow),
  }
  feats = {P: rng.standard_normal((npaper, d)).astype(np.float32),
           A: rng.standard_normal((nauthor, d)).astype(np.float32),
           I: rng.standard_normal((ninst, d)).astype(np.float32),
           F: rng.standard_normal((nfos, d)).astype(np.float32)}
  nnodes = {P: npaper, A: nauthor, I: ninst, F: nfos}
  return edges, feats, nnodes, topic.astype(np.int32)


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--model', choices=['rgat', 'rsage'], default='rgat')
  ap.add_argument('--epochs', type=int, default=4)
  ap.add_argument('--batch-size', type=int, default=256)
  ap.add_argument('--fanout', type=int, nargs='+', default=[4, 4])
  ap.add_argument('--hidden', type=int, default=64)
  ap.add_argument('--heads', type=int, default=2)
  ap.add_argument('--cpu', action='store_true')
  args = ap.parse_args()

  import jax
  if args.cpu:
    jax.config.update('jax_platforms', 'cpu')
  import optax
  import flax.linen as nn
  from graphlearn_tpu.data import Dataset
  from graphlearn_tpu.loader import NeighborLoader
  from graphlearn_tpu.models import (RGAT, SAGEConv, TrainState,
                                     make_eval_step, make_supervised_step)

  edges, feats, nnodes, topic = synthetic()
  npaper, classes = len(topic), int(topic.max()) + 1
  ds = (Dataset()
        .init_graph(edges, layout='COO', num_nodes=nnodes)
        .init_node_features(feats, split_ratio=1.0)
        .init_node_labels({P: topic}))

  idx = np.random.default_rng(1).permutation(npaper)
  train_idx, test_idx = idx[:int(npaper * .8)], idx[int(npaper * .8):]
  bs = args.batch_size
  loader = NeighborLoader(ds, args.fanout, (P, train_idx), batch_size=bs,
                          shuffle=True, seed=0)
  test_loader = NeighborLoader(ds, args.fanout, (P, test_idx),
                               batch_size=bs)
  batch0 = next(iter(loader))

  class RSAGE(RGAT):
    """`RGAT`'s stack with a per-relation `SAGEConv`."""

    @nn.nowrap
    def make_conv(self):
      return SAGEConv(self.hidden_features)

  model = (RGAT if args.model == 'rgat' else RSAGE)(
      etypes=tuple(batch0.edge_index_dict), hidden_features=args.hidden,
      out_features=classes, num_layers=len(args.fanout),
      heads=args.heads, target_ntype=P)
  tx = optax.adam(1e-3)
  params = model.init(jax.random.key(0), batch0.x_dict,
                      batch0.edge_index_dict, batch0.edge_mask_dict)
  state = TrainState(params, tx.init(params), jax.numpy.zeros((), 'int32'))
  step = make_supervised_step(model.apply, tx, bs, target_ntype=P)
  eval_step = make_eval_step(model.apply, bs, target_ntype=P)

  for epoch in range(args.epochs):
    tot = cnt = 0
    for batch in loader:
      state, loss, _ = step(state, batch)
      tot += float(loss)
      cnt += 1
    print(f'epoch {epoch}: loss {tot / max(cnt, 1):.4f}')

  correct = total = 0
  for batch in test_loader:
    c, t = eval_step(state.params, batch)
    correct += int(c)
    total += int(t)
  print(f'{args.model} test acc: {correct / max(total, 1):.4f}')


if __name__ == '__main__':
  main()
