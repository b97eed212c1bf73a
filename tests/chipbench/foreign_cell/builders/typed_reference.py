"""The plain reference of the typed cell: R-GCN over a sampled typed
subgraph, float32 `jax.numpy`, every matmul at ``highest``, no kernels
and nothing of `graphlearn_tpu`.

Layer equations (Schlichtkrull et al. 2018, mean-normalised, no basis
decomposition): ``h'_b = W_self[b] h_b + bias[b] + sum over message
types (a, rel, b) of mean over the edges into a node of W_rel h_a``;
ReLU between layers; masked softmax cross-entropy on the seed rows of
the target type; Adam as published (`chipbench.reference.adam`).  It
reads the tables and the weights `typed_build` made from the seed and
the ids the timed path drew; it gathers its own rows.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, w, rnd):
  if rnd is not None:
    a = a.astype(rnd).astype(jnp.float32)
    w = w.astype(rnd).astype(jnp.float32)
  return jnp.dot(a, w, precision=HIGHEST)


def forward(layers, x, edges, rnd=None):
  """Per-type outputs; ``x``: ``{type: rows}``, ``edges``: ``{message
  type (a, rel, b): (src into a's rows, dst into b's rows, ok)}``."""
  h = x
  for l, lay in enumerate(layers):
    out = {t: _mm(h[t], p['w'], rnd) + p['b']
           for t, p in lay['self'].items()}
    for (a, rel, b), (src, dst, ok) in edges.items():
      n = h[b].shape[0]
      seg = jnp.where(ok, dst, n)
      msg = _mm(h[a][jnp.clip(src, 0, h[a].shape[0] - 1)],
                lay['rel'][(a, rel, b)], rnd)
      tot = jax.ops.segment_sum(msg, seg, num_segments=n)
      cnt = jax.ops.segment_sum(ok.astype(jnp.float32), seg,
                                num_segments=n)
      out[b] = out[b] + tot / jnp.maximum(cnt, 1.0)[:, None]
    h = (out if l == len(layers) - 1
         else {t: jax.nn.relu(v) for t, v in out.items()})
  return h


@functools.partial(jax.jit, static_argnames=('target', 'rnd', 'half'))
def loss_and_grad(layers, step, feats, labels, *, target, rnd=None,
                  half=False):
  """``step``: ``dict(seeds=, node={type: ids}, edges=)`` as the timed
  path drew it.  ``half`` leaves the second half of the batch out of
  the mean (the fault)."""
  seeds = step['seeds']
  ok = seeds >= 0
  if half:
    ok = ok & (jnp.arange(seeds.shape[0]) < seeds.shape[0] // 2)

  def loss(layers):
    x = {t: reference.take_rows(feats[t], ids)
         for t, ids in step['node'].items()}
    logits = forward(layers, x, step['edges'],
                     reference.ROUNDINGS[rnd])[target]
    return reference.masked_ce(logits[:seeds.shape[0]],
                               reference.take_rows(labels, seeds), ok)
  return jax.value_and_grad(loss)(layers)


def leaves(layers):
  return [np.asarray(a, np.float32)
          for a in jax.tree_util.tree_leaves(layers)]


def follow(layers0, steps, feats, labels, hyper, target, rnd=None,
           half=False):
  """``(losses, first gradient, parameter change)`` over ``steps``, in
  the form `chipbench.reference.gaps` compares."""
  layers = jax.tree_util.tree_map(jnp.asarray, layers0)
  m = v = jax.tree_util.tree_map(jnp.zeros_like, layers)
  losses, g1 = [], None
  for t, (step,) in enumerate(steps, 1):
    loss, grads = loss_and_grad(layers, step, feats, labels,
                                target=target, rnd=rnd, half=half)
    losses.append(float(loss))
    g1 = grads if g1 is None else g1
    layers, m, v = reference.adam(layers, grads, m, v, t, hyper)
  return losses, leaves(g1), [a - b for a, b in zip(leaves(layers),
                                                    leaves(layers0))]


def program_record(losses, layers0, mu1, layers3, hyper):
  """The timed path's record in the same form: the first gradient as
  Adam got it is its first moment after one step over ``1 - b1``."""
  return ([float(x) for x in losses],
          [a / (1.0 - hyper['b1']) for a in leaves(mu1)],
          [a - b for a, b in zip(leaves(layers3), leaves(layers0))])


def check_draw(edge_sets, tables, target, step, x, y):
  """Exact counts of what is wrong in one drawn batch: message edges
  that are no edge of their relation (``edge_sets``: per message type
  the set of ``(src id, dst id)``), node slots of a type that repeat an
  id, seed slots out of place, gathered rows and labels that differ
  from the tables."""
  bad = dict(bad_edges=0, dup_nodes=0, bad_seeds=0, bad_rows=0)
  node = {t: np.asarray(ids) for t, ids in step['node'].items()}
  for (a, rel, b), (src, dst, ok) in step['edges'].items():
    for s, d in zip(node[a][np.asarray(src)[np.asarray(ok)]],
                    node[b][np.asarray(dst)[np.asarray(ok)]]):
      bad['bad_edges'] += (int(s), int(d)) not in edge_sets[(a, rel, b)]
  for t, ids in node.items():
    valid = ids[ids >= 0]
    bad['dup_nodes'] += len(valid) - len(np.unique(valid))
    want = np.where((ids >= 0)[:, None],
                    tables['feats'][t][np.maximum(ids, 0)], 0)
    bad['bad_rows'] += int(np.any(np.asarray(x[t]) != want, axis=1).sum())
  seeds = np.asarray(step['seeds'])
  bad['bad_seeds'] += int(np.sum(node[target][:len(seeds)]
                                 != seeds))
  bad['bad_rows'] += int(np.sum(
      np.asarray(y)[:len(seeds)][seeds >= 0]
      != tables['labels'][seeds[seeds >= 0]]))
  return bad
