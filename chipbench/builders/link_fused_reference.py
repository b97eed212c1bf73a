"""The plain reference of the `sage-products-link` cell: unsupervised
GraphSAGE link prediction over a sampled subgraph, float32
`jax.numpy`, every matmul at ``highest``, no kernels and nothing of
`graphlearn_tpu`.

The equations.  A step holds the node table a link batch was expanded
into (``node``: global ids, -1 padded; rows in first-occurrence
order), its edge slots ``(src, dst)`` with a mask (messages flow from
the found node ``src`` to the node that asked, ``dst``), and the label
pairs: ``eli`` (two rows of table indices, positives first), ``label``
(1 for a positive edge, 0 for a sampled negative) and ``mask``.
``h^0 = feats[node]`` (zero rows where padded).  For ``l = 0 .. L-1``:

  mean_v = sum over valid in-edges (u -> v) of h^l_u / max(indeg v, 1)
  h^{l+1}_v = W_self^l h^l_v + b^l + W_neigh^l mean_v   (ReLU but last)

over EVERY row and every edge slot of the recorded subgraph — the
program computes each layer over the hops it feeds only, and
aggregates by fanout window: that the two agree is the point.  The
logit of a pair is ``<h^L_a, h^L_b>`` and the loss the mean binary
cross-entropy (with logits) over the valid pairs, the objective of
GraphSAGE's unsupervised loss at one negative per positive (Hamilton
et al. 2017) as the reference's `examples/graph_sage_unsup_ppi.py`
computes it.  Adam as published (`chipbench.reference.adam`).

The faults a reference can plant (`chipbench.limits` reads them):
``neg_positive`` gives the sampled negatives the positive label,
``half`` leaves every second pair out of the mean.
"""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, w):
  return jnp.dot(a, w, precision=HIGHEST)


def forward(layers, x, src, dst, ok):
  """Per-row outputs of GraphSAGE-mean over the whole padded COO."""
  n = x.shape[0]
  seg = jnp.where(ok, dst, n)
  cnt = jax.ops.segment_sum(ok.astype(jnp.float32), seg, num_segments=n)
  h = x
  for l, (w_self, b_self, w_neigh) in enumerate(layers):
    @jax.checkpoint
    def layer(h, w_self, b_self, w_neigh):
      tot = jax.ops.segment_sum(h[jnp.clip(src, 0, n - 1)], seg,
                                num_segments=n)
      mean = tot / jnp.maximum(cnt, 1.0)[:, None]
      return _mm(h, w_self) + b_self + _mm(mean, w_neigh)
    h = layer(h, w_self, b_self, w_neigh)
    if l < len(layers) - 1:
      h = jax.nn.relu(h)
  return h


def link_loss(emb, eli, label, mask, neg_positive=False, half=False):
  """Mean binary cross-entropy with logits ``<emb_a, emb_b>`` over the
  valid pairs."""
  n = emb.shape[0]
  ok = mask & (eli[0] >= 0) & (eli[1] >= 0)
  if half:
    ok = ok & (jnp.arange(ok.shape[0]) % 2 == 0)
  y = jnp.minimum(label, 1).astype(jnp.float32)
  if neg_positive:
    y = jnp.ones_like(y)
  z = jnp.sum(emb[jnp.clip(eli[0], 0, n - 1)]
              * emb[jnp.clip(eli[1], 0, n - 1)], axis=-1)
  bce = jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
  w = ok.astype(jnp.float32)
  return (bce * w).sum() / jnp.maximum(w.sum(), 1.0)


def loss_and_grad(layers, step, feats, *, neg_positive=False, half=False):
  """One step's loss and its gradient at ``layers``; ``step`` holds
  ``node``, ``src``, ``dst``, ``edge_ok``, ``eli``, ``label``,
  ``mask``."""
  x = reference.take_rows(feats, step['node'])

  def loss(layers):
    emb = forward(layers, x, step['src'], step['dst'], step['edge_ok'])
    return link_loss(emb, step['eli'], step['label'], step['mask'],
                     neg_positive, half)
  return jax.value_and_grad(loss)(layers)


def follow(layers0, steps, feats, hyper, step_fn=None):
  """``(losses, every step's gradient, parameter change)`` over
  ``steps`` (a list over steps of ``[step]``); ``step_fn(layers, step,
  feats) -> (loss, gradient)``, compiled: `loss_and_grad` jitted where
  none is handed in, or a fault's form of it.  The losses and the
  change are the reference's own three Adam steps from ``layers0``; a
  step's gradient is computed at ``step['weights']``, what the
  PROGRAM held as it took that step (the first step's are
  ``layers0``), so that no step inherits another's differences
  (`gaps`)."""
  step_fn = step_fn or jax.jit(loss_and_grad)
  layers = jax.tree_util.tree_map(jnp.asarray, layers0)
  m = v = jax.tree_util.tree_map(jnp.zeros_like, layers)
  losses, grads = [], []
  for t, (step,) in enumerate(steps, 1):
    drawn = {k: jnp.asarray(a) for k, a in step.items() if k != 'weights'}
    loss, g = step_fn(layers, drawn, feats)
    losses.append(float(loss))
    there = g if t == 1 else step_fn(
        jax.tree_util.tree_map(jnp.asarray, step['weights']), drawn,
        feats)[1]
    grads.append(reference.flat(there))
    layers, m, v = reference.adam(layers, g, m, v, t, hyper)
  return losses, grads, [a - b for a, b in zip(reference.flat(layers),
                                               reference.flat(layers0))]


def program_record(losses, layers0, mus, layers3, hyper):
  """The timed path's record in the same form.  ``mus``: Adam's first
  moment after each step; a step's gradient as Adam got it is
  ``(mu_t - b1 mu_{t-1}) / (1 - b1)``, from ``mu_0 = 0``."""
  b1 = hyper['b1']
  grads, before = [], None
  for mu in mus:
    mu = [a.astype(np.float64) for a in reference.flat(mu)]
    grads.append([(a - b1 * b) / (1.0 - b1) for a, b in zip(mu, before)]
                 if before else [a / (1.0 - b1) for a in mu])
    before = mu
  return ([float(x) for x in losses], grads,
          [a - b for a, b in zip(reference.flat(layers3),
                                 reference.flat(layers0))])


def gaps(prog, ref):
  """`chipbench.reference.gaps`, with ``grad_gap`` the worst leaf's gap
  of the step that agrees best: each of the three steps' gradients is
  computed by both sides at the weights the program held, and a ReLU
  whose input is round-off away from 0 makes one step's gradient
  two-valued (PERF.md section 6, PRs 29-30), where lower precision, a
  wrong mean or a stale state shows in every step."""
  (pl, pg, pd), (rl, rg, rd) = prog, ref
  out = reference.gaps((pl, pg[0], pd), (rl, rg[0], rd))
  by_step = [reference.gaps((pl, p, pd), (rl, r, rd))['grad_gap']
             for p, r in zip(pg, rg)]
  print('chipbench link: grad_gap by step '
        + ' '.join(f'{g:.3e}' for g in by_step), file=sys.stderr)
  out['grad_gap'] = min(by_step)
  return out


def check_batch(indptr, indices, feats, node, src, dst, edge_ok, seeds,
                eli, label, mask, x, *, batch, ends, fanouts):
  """Exact counts of what is wrong in one drawn link batch:

    bad_edges      valid edge slots whose found node is no CSR
                   neighbour of the node that asked
    bad_fanout     per hop block (``ends``: where each ends), asking
                   nodes with more in-edges than that hop's fanout
    dup_nodes      table slots that repeat an id
    bad_seeds      label pairs whose rows hold other ids than the
                   endpoints drawn (``seeds``: ``[src, dst, negative
                   rows, negative cols]``, ``batch`` positives), and
                   positive pairs that are no edge of the graph
    bad_negatives  valid negative pairs that are an edge of the graph
    bad_rows       gathered rows that differ from the table's
  """
  n = node.shape[0]
  found = node[jnp.clip(src, 0, n - 1)]
  asked = node[jnp.clip(dst, 0, n - 1)]
  sound = (found >= 0) & (asked >= 0) & (src >= 0) & (dst >= 0)
  edge = reference._in_csr(indptr, indices, jnp.where(sound, asked, 0),
                           jnp.where(sound, found, 0))
  bad_e = jnp.sum(edge_ok & ~(edge & sound), dtype=jnp.int32)
  bad_f, start = jnp.int32(0), 0
  for end, k in zip(ends, fanouts):
    hop = edge_ok[start:end]
    indeg = jax.ops.segment_sum(hop.astype(jnp.int32),
                                jnp.where(hop, dst[start:end], n),
                                num_segments=n)
    bad_f += jnp.sum(indeg > k, dtype=jnp.int32)
    start = end
  srt = jnp.sort(node)
  dup = jnp.sum((srt[1:] == srt[:-1]) & (srt[1:] >= 0), dtype=jnp.int32)
  b, nn = batch, eli.shape[1] - batch
  want = jnp.stack([
      jnp.concatenate([seeds[:b], seeds[2 * b:2 * b + nn]]),
      jnp.concatenate([seeds[b:2 * b], seeds[2 * b + nn:]])])
  ok = mask & (want[0] >= 0) & (want[1] >= 0)
  got = node[jnp.clip(eli, 0, n - 1)]
  placed = jnp.sum(ok & jnp.any((got != want) | (eli < 0), axis=0),
                   dtype=jnp.int32)
  pair_edge = reference._in_csr(indptr, indices, jnp.where(ok, want[0], 0),
                                jnp.where(ok, want[1], 0))
  pos = ok & (label > 0)
  neg = ok & (label == 0)
  bad_s = placed + jnp.sum(pos & ~pair_edge, dtype=jnp.int32)
  bad_n = jnp.sum(neg & pair_edge, dtype=jnp.int32)
  bad_x = jnp.sum(jnp.any(x != reference.take_rows(feats, node), axis=1),
                  dtype=jnp.int32)
  return dict(bad_edges=bad_e, bad_fanout=bad_f, dup_nodes=dup,
              bad_seeds=bad_s, bad_negatives=bad_n, bad_rows=bad_x)
