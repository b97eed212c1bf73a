"""The plain reference and the comparison that decides ``correct``.

Straightforward `jax.numpy`, float32, every matmul at ``highest``
precision, no kernels and nothing of `graphlearn_tpu`: GraphSAGE's
mean-aggregator layer equations over a sampled tree (the fused cells)
or a sampled subgraph (the per-batch cell), masked softmax
cross-entropy on the seed rows, its gradient, and Adam as published
(Kingma & Ba; optax's `adam` computes the same update).  It reads the
table, the labels and the initial weights that `chipbench.build` made
from the seed, and the ids the timed path drew; it gathers its own
rows.

``round_to`` is the control: the same reference with every matmul
operand rounded to a lower precision.  A control has to come out as
not correct (`chipbench/limits.py` reads it on the chip,
`tests/chipbench` keeps it at a small size).
"""
from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROUNDINGS = {
    None: None, 'float32': None,
    'bfloat16': jnp.bfloat16,
    'float8_e4m3': jnp.float8_e4m3fn,
}


def _mm(a, w, rnd):
  if rnd is not None:
    a = a.astype(rnd).astype(jnp.float32)
    w = w.astype(rnd).astype(jnp.float32)
  return jnp.dot(a, w, precision=HIGHEST)


def take_rows(table, ids):
  """Rows of ``table`` at ``ids``; zero rows where ``ids < 0``."""
  ok = ids >= 0
  rows = jnp.take(table, jnp.where(ok, ids, 0), axis=0)
  return jnp.where(ok.reshape(ok.shape + (1,) * (rows.ndim - 1)), rows, 0)


def tree_forward(layers, xs, masks, rnd=None):
  """Seed-level logits of GraphSAGE-mean over tree levels: level ``t``
  holds ``B*k_1*..*k_t`` slots, each parent owns the next level's
  contiguous window of ``k`` children."""
  hs = [x * m[:, None].astype(x.dtype) for x, m in zip(xs, masks)]
  depth = len(layers)
  for l, (w_self, b_self, w_neigh) in enumerate(layers):
    nxt = []
    for t in range(depth - l):
      parent, child = hs[t], hs[t + 1]
      k = child.shape[0] // parent.shape[0]
      cm = masks[t + 1].reshape(parent.shape[0], k).astype(jnp.float32)
      cd = child.reshape(parent.shape[0], k, child.shape[1])
      mean = ((cd * cm[..., None]).sum(1)
              / jnp.maximum(cm.sum(1), 1.0)[:, None])
      h = _mm(parent, w_self, rnd) + b_self + _mm(mean, w_neigh, rnd)
      nxt.append(jax.nn.relu(h) if l < depth - 1 else h)
    hs = nxt
  return hs[0]


def subgraph_forward(layers, x, src, dst, edge_ok, rnd=None):
  """Per-node outputs of GraphSAGE-mean over a padded local COO:
  messages flow ``src -> dst``; masked edges carry nothing."""
  n = x.shape[0]
  seg = jnp.where(edge_ok, dst, n)
  cnt = jax.ops.segment_sum(edge_ok.astype(jnp.float32), seg,
                            num_segments=n)
  h = x
  for l, (w_self, b_self, w_neigh) in enumerate(layers):
    @jax.checkpoint
    def layer(h, w_self, b_self, w_neigh):
      tot = jax.ops.segment_sum(h[jnp.clip(src, 0, n - 1)], seg,
                                num_segments=n)
      mean = tot / jnp.maximum(cnt, 1.0)[:, None]
      return _mm(h, w_self, rnd) + b_self + _mm(mean, w_neigh, rnd)
    h = layer(h, w_self, b_self, w_neigh)
    if l < len(layers) - 1:
      h = jax.nn.relu(h)
  return h


def masked_ce(logits, y, ok):
  """Mean softmax cross-entropy over the rows where ``ok``."""
  logz = jax.nn.logsumexp(logits, axis=-1)
  picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
  w = ok.astype(jnp.float32)
  return ((logz - picked) * w).sum() / jnp.maximum(w.sum(), 1.0)


def shard_loss(kind, layers, shard, feats, labels, rnd=None,
               keep=None, local_only=False):
  """Loss of one device's batch.  ``shard`` holds the ids the timed
  path drew: ``levels`` (tree) or ``node``/``src``/``dst``/``edge_ok``
  (subgraph), and ``seeds``.  Two faults, for the tests and the
  limits: ``keep`` masks seed rows out of the mean; ``local_only``
  zeroes the rows another device owns (``shard['owned']``), which is
  what a device is left with when the exchange is left out."""
  seeds = shard['seeds']
  ok = seeds >= 0
  if keep is not None:
    ok = ok & keep
  y = take_rows(labels, seeds)
  if kind == 'tree':
    xs = [take_rows(feats, lv) for lv in shard['levels']]
    if local_only:
      xs = [x * o[:, None] for x, o in zip(xs, shard['owned'])]
    logits = tree_forward(layers, xs, [lv >= 0 for lv in shard['levels']],
                          rnd)
  else:
    x = take_rows(feats, shard['node'])
    logits = subgraph_forward(layers, x, shard['src'], shard['dst'],
                              shard['edge_ok'], rnd)[:seeds.shape[0]]
  return masked_ce(logits, y, ok)


@functools.partial(jax.jit, static_argnames=('kind', 'rnd', 'half',
                                             'local_only'))
def loss_and_grad(layers, shards, feats, labels, *, kind, rnd=None,
                  half=False, local_only=False):
  """Mean over the shards (the devices of a data-parallel step) of
  each shard's loss, and its gradient."""
  def total(layers):
    losses = []
    for s in shards:
      keep = None
      if half:
        b = s['seeds'].shape[0]
        keep = jnp.arange(b) < b // 2
      losses.append(shard_loss(kind, layers, s, feats, labels,
                               ROUNDINGS[rnd], keep, local_only))
    return sum(losses) / len(losses)
  return jax.value_and_grad(total)(layers)


def adam(layers, grads, m, v, t, hyper):
  """One Adam step as published; ``t`` counts from 1."""
  b1, b2 = hyper['b1'], hyper['b2']
  lr, eps = hyper['lr'], hyper['eps']
  upd = lambda f, *a: jax.tree_util.tree_map(f, *a)
  m = upd(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
  v = upd(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
  layers = upd(
      lambda p, m, v: p - lr * (m / (1 - b1 ** t))
      / (jnp.sqrt(v / (1 - b2 ** t)) + eps), layers, m, v)
  return layers, m, v


def follow(kind, layers0, steps, feats, labels, hyper, rnd=None,
           half=False, local_only=False):
  """Follow ``steps`` (a list over steps of a list over shards) from
  ``layers0``: ``(losses, first gradient, parameter change)`` with
  the two trees as flat lists of host arrays."""
  layers = jax.tree_util.tree_map(jnp.asarray, layers0)
  zeros = jax.tree_util.tree_map(jnp.zeros_like, layers)
  m, v, losses, g1 = zeros, zeros, [], None
  for t, shards in enumerate(steps, 1):
    loss, grads = loss_and_grad(layers, shards, feats, labels,
                                kind=kind, rnd=rnd, half=half,
                                local_only=local_only)
    losses.append(float(loss))
    if g1 is None:
      g1 = grads
    layers, m, v = adam(layers, grads, m, v, t, hyper)
  delta = jax.tree_util.tree_map(lambda a, b: a - jnp.asarray(b), layers,
                                 layers0)
  return losses, flat(g1), flat(delta)


def flat(layers):
  return [np.asarray(a, np.float32) for lay in layers for a in lay]


def program_record(losses, layers0, layers1, mu1, layers3, hyper):
  """What the timed path produced, in the reference's terms: the
  first gradient as the optimizer got it is Adam's first moment after
  one step over ``1 - b1``."""
  g1 = [a / (1.0 - hyper['b1']) for a in flat(mu1)]
  delta = [a - b for a, b in zip(flat(layers3), flat(layers0))]
  del layers1
  return [float(x) for x in losses], g1, delta


def gaps(prog, ref):
  """The numbers compared, program against reference, by the worst
  step and the worst leaf: the gap between the two norms over the
  reference's norm of that leaf or of the median leaf, whichever is
  larger.  Leaves whose reference gradient is under a thousandth of
  the median leaf's are left out of the parameter change (Adam moves
  them by round-off alone)."""
  (pl, pg, pd), (rl, rg, rd) = prog, ref
  norm = lambda a: float(np.linalg.norm(np.asarray(a, np.float64)))
  step_gaps = [abs(p - r) / abs(r) for p, r in zip(pl, rl)]
  out = {'loss_gap': max(step_gaps), 'loss1_gap': step_gaps[0]}
  rgn = [norm(a) for a in rg]
  med_g = statistics.median(rgn)
  out['grad_gap'] = max(abs(norm(p) - r) / max(r, med_g)
                        for p, r in zip(pg, rgn))
  live = [i for i, r in enumerate(rgn) if r >= 1e-3 * med_g]
  rdn = [norm(rd[i]) for i in live]
  med_d = statistics.median(rdn)
  out['delta_gap'] = max(abs(norm(pd[i]) - r) / max(r, med_d)
                         for i, r in zip(live, rdn))
  return out


# -- what was drawn, against the CSR ---------------------------------------

def _in_csr(indptr, indices, parent, child):
  """Is ``child`` in ``parent``'s row?  Rows are sorted ascending, so
  a binary search of 32 halvings; ``parent`` must be valid."""
  parent, child = jnp.broadcast_arrays(parent, child)
  lo0, hi0 = indptr[parent], indptr[parent + 1]
  last = indices.shape[0] - 1

  def halve(_, lh):
    lo, hi = lh
    mid = (lo + hi) // 2
    right = indices[jnp.clip(mid, 0, last)] < child
    return jnp.where(right, mid + 1, lo), jnp.where(right, hi, mid)

  lo, _ = jax.lax.fori_loop(0, 32, halve, (lo0, hi0))
  return (lo < hi0) & (indices[jnp.clip(lo, 0, last)] == child)


@functools.partial(jax.jit, static_argnames=('fanouts',))
def check_tree(indptr, indices, levels, fanouts):
  """``(bad_edges, bad_fanout)`` of one drawn tree: valid children
  that are no neighbour of their parent (or hang under a masked
  parent), and parents that did not get ``min(k, degree)`` children."""
  bad_e = bad_f = jnp.int32(0)
  for t in range(len(levels) - 1):
    par = levels[t]
    kids = levels[t + 1].reshape(par.shape[0], -1)
    pv = par >= 0
    p0 = jnp.where(pv, par, 0)
    kv = kids >= 0
    edge = _in_csr(indptr, indices, p0[:, None], jnp.where(kv, kids, 0))
    bad_e += jnp.sum(kv & ~(edge & pv[:, None]), dtype=jnp.int32)
    deg = indptr[p0 + 1] - indptr[p0]
    want = jnp.where(pv, jnp.minimum(deg, fanouts[t]), 0)
    bad_f += jnp.sum(kv.sum(1) != want, dtype=jnp.int32)
  return bad_e, bad_f


@jax.jit
def check_subgraph(indptr, indices, feats, labels, node, src, dst,
                   edge_ok, seeds, x, y, max_fanout):
  """Counts of what is wrong in one per-batch subgraph: edges that are
  no CSR edge, node slots that repeat an id, seed slots out of place,
  targets with more in-edges than the widest fanout, gathered rows
  and labels that differ from the table."""
  n = node.shape[0]
  gs = node[jnp.clip(src, 0, n - 1)]
  gd = node[jnp.clip(dst, 0, n - 1)]
  ends_ok = (gs >= 0) & (gd >= 0)
  edge = _in_csr(indptr, indices, jnp.where(ends_ok, gd, 0),
                 jnp.where(ends_ok, gs, 0))
  bad_e = jnp.sum(edge_ok & ~(edge & ends_ok), dtype=jnp.int32)
  srt = jnp.sort(node)
  dup = jnp.sum((srt[1:] == srt[:-1]) & (srt[1:] >= 0), dtype=jnp.int32)
  b = seeds.shape[0]
  bad_seed = jnp.sum(node[:b] != seeds, dtype=jnp.int32)
  indeg = jax.ops.segment_sum(edge_ok.astype(jnp.int32),
                              jnp.where(edge_ok, dst, n), num_segments=n)
  bad_f = jnp.sum(indeg > max_fanout, dtype=jnp.int32)
  bad_x = jnp.sum(jnp.any(x != take_rows(feats, node), axis=1),
                  dtype=jnp.int32)
  bad_y = jnp.sum(y[:b] != take_rows(labels, seeds), dtype=jnp.int32)
  return dict(bad_edges=bad_e, dup_nodes=dup, bad_seeds=bad_seed,
              bad_fanout=bad_f, bad_rows=bad_x + bad_y)
