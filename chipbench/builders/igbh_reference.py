"""The plain reference of the `rgat-igbh` cells: relational graph
attention over a sampled typed subgraph, float32 `jax.numpy`, every
matmul at ``highest``, no kernels and nothing of `graphlearn_tpu`.

The layer equations.  A batch holds typed node tables ``x_t`` (rows in
first-occurrence order, seeds first) and, for every relation ``r = (a
-> b)`` as the batch emits it (messages flow from the found side ``a``
to the side ``b`` that asked), edge lists ``(u, v)`` with a mask.
``h^0_t = float32(x_t)``.  For ``l = 0 .. L-1`` and every relation
``r``, with ``W_r^l`` of shape ``[d_l, heads * f]`` (no bias, one
projection for both ends) and ``a^l_{r,src}``, ``a^l_{r,dst}`` of
shape ``[heads, f]``:

  z_u = W_r^l h^l_a[u],  z_v = W_r^l h^l_b[v]      (each [heads, f])
  e_uv = LeakyReLU_0.2(<a_src, z_u> + <a_dst, z_v>)          per head
  alpha_uv = softmax of e_uv over the valid in-edges of v WITHIN r
             (a target without valid in-edges gets 0)
  out_r[v] = concat over heads of sum_u alpha_uv z_u
  h^{l+1}_b = ReLU(sum over the relations r into b of out_r)

``logits = W_o h^L_target[:B] + b_o``; the loss is the mean softmax
cross-entropy over the valid seed slots; Adam as published
(`chipbench.reference.adam`).  This is `GATConv` as published
(Velickovic et al. 2018, arXiv:1710.10903) without self-loops and with
one projection for both ends, per relation as in GraphLearn-for-
PyTorch's `examples/igbh/rgnn.py`.  Known departures from that script
(the configuration lists them under ``assumed``): no dropout, no
per-relation bias, a plain sum across relations, the input goes into
layer 0 at its own width without a projection.  In these cells every
node type is the target of some relation in every layer; a type that
no relation reaches would need the program's self term and is refused
here.

Every layer is computed over the WHOLE recorded subgraph — every row
of every table, every edge slot of every relation — where the program
computes each layer only over the hops it feeds: that the two agree is
the point.  Each relation's convolution is rematerialised in the
backward pass (`jax.checkpoint`), so that what is kept is a layer's
tables and not every relation's projections and messages: it has to
fit beside the feature tables once the program is freed.
"""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference

HIGHEST = jax.lax.Precision.HIGHEST
NEGATIVE_SLOPE = 0.2


def _mm(a, w, rnd):
  if rnd is not None:
    a = a.astype(rnd).astype(jnp.float32)
    w = w.astype(rnd).astype(jnp.float32)
  return jnp.dot(a, w, precision=HIGHEST)


@functools.partial(jax.checkpoint, static_argnums=(6,))
def relation(p, h_src, h_dst, src, dst, ok, rnd):
  """``out_r`` for every row of ``h_dst``: one relation's attention."""
  heads, f = p['a_src'].shape
  n = h_dst.shape[0]
  z_src = _mm(h_src, p['w'], rnd).reshape(-1, heads, f)
  z_dst = _mm(h_dst, p['w'], rnd).reshape(-1, heads, f)
  s_src = (z_src * p['a_src']).sum(-1)
  s_dst = (z_dst * p['a_dst']).sum(-1)
  u = jnp.clip(src, 0, h_src.shape[0] - 1)
  v = jnp.clip(dst, 0, n - 1)
  e = s_src[u] + s_dst[v]
  e = jnp.where(e >= 0, e, NEGATIVE_SLOPE * e)
  seg = jnp.where(ok, dst, n)            # masked edges fall off the end
  e = jnp.where(ok[:, None], e, -jnp.inf)
  top = jax.ops.segment_max(e, seg, num_segments=n)
  top = jnp.where(jnp.isfinite(top), top, 0.0)
  ex = jnp.where(ok[:, None], jnp.exp(e - top[v]), 0.0)
  den = jax.ops.segment_sum(ex, seg, num_segments=n)
  alpha = ex / jnp.maximum(den[v], 1e-16)
  msg = (z_src[u] * alpha[:, :, None]).reshape(-1, heads * f)
  return jax.ops.segment_sum(msg, seg, num_segments=n)


def forward(weights, x, edges, rnd=None):
  """The target rows' logits are the caller's to slice: returns
  ``{type: h^L}`` over whole tables.  ``x``: ``{type: rows}``;
  ``edges``: ``{(a, rel, b): (src into a's rows, dst into b's rows,
  ok)}``."""
  h = {t: v.astype(jnp.float32) for t, v in x.items()}
  for lay in weights['layers']:
    out = {}
    for (a, rel, b), (src, dst, ok) in sorted(edges.items()):
      o = relation(lay[(a, rel, b)], h[a], h[b], src, dst, ok, rnd)
      out[b] = o if b not in out else out[b] + o
    missing = sorted(set(h) - set(out))
    if missing:
      raise ValueError(f'no relation reaches {missing}: the reference '
                       'has no self term')
    h = {t: jax.nn.relu(v) for t, v in out.items()}
  return h


def logits_of(weights, x, edges, target, batch, rnd=None):
  h = forward(weights, x, edges, rnd)[target][:batch]
  return _mm(h, weights['head']['w'], rnd) + weights['head']['b']


@functools.partial(jax.jit, static_argnames=('target', 'rnd', 'half'))
def loss_and_grad(weights, step, feats, labels, *, target, rnd=None,
                  half=False):
  """``step``: ``dict(seeds=, node={type: ids}, edges=)`` as the timed
  path drew it; the reference gathers its own rows (16-bit table ->
  float32).  ``half`` leaves the second half of the batch out of the
  mean (the fault)."""
  seeds = step['seeds']
  ok = seeds >= 0
  if half:
    ok = ok & (jnp.arange(seeds.shape[0]) < seeds.shape[0] // 2)
  x = {t: reference.take_rows(feats[t], ids).astype(jnp.float32)
       for t, ids in step['node'].items()}
  y = reference.take_rows(labels, seeds)

  def loss(weights):
    logits = logits_of(weights, x, step['edges'], target,
                       seeds.shape[0], reference.ROUNDINGS[rnd])
    return reference.masked_ce(logits, y, ok)
  return jax.value_and_grad(loss)(weights)


def leaves(weights):
  return [np.asarray(a, np.float32)
          for a in jax.tree_util.tree_leaves(weights)]


def follow(weights0, steps, feats, labels, hyper, target, rnd=None,
           half=False):
  """``(losses, every step's gradient, parameter change)`` over
  ``steps``.  The losses and the change are the reference's own three
  Adam steps from ``weights0``.  A step's gradient is computed at
  ``step['weights']``, what the PROGRAM held as it took that step (the
  first step's are ``weights0``): three readings of the same gradient
  code, none of which inherits the others' differences (`gaps`)."""
  weights = jax.tree_util.tree_map(jnp.asarray, weights0)
  m = v = jax.tree_util.tree_map(jnp.zeros_like, weights)
  grad_at = lambda w, drawn: loss_and_grad(
      w, drawn, feats, labels, target=target, rnd=rnd, half=half)
  losses, grads = [], []
  for t, (step,) in enumerate(steps, 1):
    drawn = {k: a for k, a in step.items() if k != 'weights'}
    loss, g = grad_at(weights, drawn)
    losses.append(float(loss))
    # the first step's weights are the seed's on both sides; later the
    # reference's own have moved apart from the program's by Adam's
    # ``lr * sign(g)`` on elements whose gradient is round-off
    there = g if t == 1 else grad_at(
        jax.tree_util.tree_map(jnp.asarray, step['weights']), drawn)[1]
    grads.append(leaves(there))
    weights, m, v = reference.adam(weights, g, m, v, t, hyper)
  return losses, grads, [a - b for a, b in zip(leaves(weights),
                                               leaves(weights0))]


def program_record(losses, weights0, mus, weights3, hyper):
  """The timed path's record in the same form.  ``mus``: Adam's first
  moment after each step; a step's gradient as Adam got it is
  ``(mu_t - b1 mu_{t-1}) / (1 - b1)``, from ``mu_0 = 0``."""
  b1 = hyper['b1']
  grads, before = [], None
  for mu in mus:
    mu = [a.astype(np.float64) for a in leaves(mu)]
    grads.append([(a - b1 * b) / (1.0 - b1) for a, b in zip(mu, before)]
                 if before else [a / (1.0 - b1) for a in mu])
    before = mu
  return ([float(x) for x in losses], grads,
          [a - b for a, b in zip(leaves(weights3), leaves(weights0))])


def gaps(prog, ref):
  """`chipbench.reference.gaps` with the gradient taken at every step:
  ``grad_gap`` is the worst leaf's gap of the step that agrees best.

  ReLU is piecewise linear, so where one unit's input is round-off
  away from 0 the gradient has two values, each as right as the other,
  and two float32 computations that sum in different orders (whole
  tables here, the hops a layer feeds there) can land on different
  ones.  One unit of a seed row's last hidden layer carries about
  1/(32 x 512) of the gradient: a flip there read ``1.85e-4`` on one
  seed in fifty where the others read under ``5e-7`` (PERF.md section
  6), the size of what the limit is there to catch.  A flip is one
  step's accident; lower precision, a wrong mean or a stale state
  shows in every step.  So each of the three steps' gradients is
  compared, each computed by both sides at the same weights, and the
  best is held to the limit."""
  (pl, pg, pd), (rl, rg, rd) = prog, ref
  out = reference.gaps((pl, pg[0], pd), (rl, rg[0], rd))
  by_step = [reference.gaps((pl, p, pd), (rl, r, rd))['grad_gap']
             for p, r in zip(pg, rg)]
  print('chipbench igbh: grad_gap by step '
        + ' '.join(f'{g:.3e}' for g in by_step), file=sys.stderr)
  out['grad_gap'] = min(by_step)
  return out


@functools.partial(jax.jit, static_argnames=('ends', 'fanouts'))
def check_relation(indptr, indices, node_src, node_dst, src, dst, ok,
                   ends, fanouts):
  """``(bad_edges, bad_fanout)`` of one emitted relation ``(a -> b)``,
  drawn from the stored relation ``(b -> a)`` whose CSR this is: valid
  edges whose found node is no neighbour of the node that asked, and,
  hop by hop, asking nodes with more in-edges than that hop's fanout.
  ``ends``: where each hop's block of edge slots ends, as the batch
  states it; ``fanouts``: the draw's width per hop."""
  na, nb = node_src.shape[0], node_dst.shape[0]
  found = node_src[jnp.clip(src, 0, na - 1)]
  asked = node_dst[jnp.clip(dst, 0, nb - 1)]
  sound = (found >= 0) & (asked >= 0) & (src >= 0) & (dst >= 0)
  edge = reference._in_csr(indptr, indices, jnp.where(sound, asked, 0),
                           jnp.where(sound, found, 0))
  bad_e = jnp.sum(ok & ~(edge & sound), dtype=jnp.int32)
  bad_f, start = jnp.int32(0), 0
  for end, k in zip(ends, fanouts):
    if end > start:
      hop = ok[start:end]
      indeg = jax.ops.segment_sum(
          hop.astype(jnp.int32), jnp.where(hop, dst[start:end], nb),
          num_segments=nb)
      bad_f += jnp.sum(indeg > k, dtype=jnp.int32)
    start = end
  return bad_e, bad_f


@jax.jit
def check_table(table, node, x):
  """``(dup_nodes, bad_rows)`` of one type's node table: slots that
  repeat an id, gathered rows that differ from the table's."""
  srt = jnp.sort(node)
  dup = jnp.sum((srt[1:] == srt[:-1]) & (srt[1:] >= 0), dtype=jnp.int32)
  bad = jnp.sum(jnp.any(x != reference.take_rows(table, node), axis=1),
                dtype=jnp.int32)
  return dup, bad


@jax.jit
def check_seeds(labels, node, seeds, y):
  """``(bad_seeds, bad_rows)``: seed slots out of place, labels that
  differ from the table's."""
  b = seeds.shape[0]
  return (jnp.sum(node[:b] != seeds, dtype=jnp.int32),
          jnp.sum(y[:b] != reference.take_rows(labels, seeds),
                  dtype=jnp.int32))
