"""The `mesh_sharded` builder's copy of the plain reference.

Straightforward `jax.numpy`, float32, every matmul at ``highest``, no
kernels and nothing of `graphlearn_tpu`: GraphSAGE's mean-aggregator
layer equations over a sampled tree, masked softmax cross-entropy on
the seed rows, its gradient, Adam as published — the equations of
`chipbench.reference` (whose `adam`, `masked_ce`, `take_rows`, `flat`
and `gaps` it reuses) — for a table that exists nowhere whole:

  * rows come from ``rows_of(ids, key)``, the seed's own definition of
    the table (`mesh_sharded_build.feat_rows`), recomputed for the ids
    a tree names; nothing is read from the program's shards or through
    its exchange.  The seed's keys are arguments of every program here,
    so that one compile serves every seed;
  * a step is computed in BLOCKS, one device's batch on each device of
    the mesh (`step_loss_and_grad`), and the step's loss and gradient
    are the mean over the blocks;
  * what was drawn is held against the seed's COO (`tree_counts`)
    block by block: every device draws one block of the COO again,
    sorts it to a CSR of its own, and the counts are summed over the
    blocks — the graph is never whole either.
"""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as ref

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, w, rnd):
  if rnd is not None:
    a = a.astype(rnd).astype(jnp.float32)
    w = w.astype(rnd).astype(jnp.float32)
  return jnp.dot(a, w, precision=HIGHEST)


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


def rows_at(rows_of, ids, key):
  """``rows_of`` at ``ids``; zero rows where ``ids < 0``."""
  ok = ids >= 0
  rows = rows_of(jnp.where(ok, ids, 0), key)
  return jnp.where(ok.reshape(ok.shape + (1,) * (rows.ndim - 1)), rows,
                   jnp.zeros((), rows.dtype))


def step_loss_and_grad(mesh, axis, rows_of, labels_of, rnd=None,
                       half=False, local_only=False):
  """``f(layers, shards, keys) -> (loss, grads)`` of one data-parallel
  step, jitted: ``shards`` holds every device's batch stacked ``[P,
  ...]`` (`stack`), block ``d`` is computed on device ``d`` of the
  mesh, and the step's loss and gradient are the mean over the blocks;
  ``keys`` are the seed's (``feats``, ``labels``).  The
  faults are `chipbench.reference.shard_loss`'s: ``half`` masks the
  second half of the seeds out of the mean, ``local_only`` zeroes the
  rows another device owns."""
  from jax.sharding import PartitionSpec as P
  rnd = ref.ROUNDINGS[rnd]

  def loss(layers, shard, keys):
    seeds = shard['seeds']
    ok = seeds >= 0
    if half:
      ok = ok & (jnp.arange(seeds.shape[0]) < seeds.shape[0] // 2)
    xs = [rows_at(rows_of, lv, keys['feats']) for lv in shard['levels']]
    if local_only:
      xs = [x * o[:, None] for x, o in zip(xs, shard['owned'])]
    logits = tree_forward(layers, xs,
                          [lv >= 0 for lv in shard['levels']], rnd)
    return ref.masked_ce(logits,
                         rows_at(labels_of, seeds, keys['labels']), ok)

  def per_device(layers, shards, keys):
    block = jax.tree_util.tree_map(lambda a: a[0], shards)
    return jax.lax.pmean(
        jax.value_and_grad(loss)(layers, block, keys), axis)

  return jax.jit(jax.shard_map(per_device, mesh=mesh,
                               in_specs=(P(), P(axis), P()),
                               out_specs=P(), check_vma=False))


def stack(shards, mesh, axis):
  """One step's per-device batches (host arrays) as one pytree of
  ``[P, ...]`` device arrays, block ``d`` on device ``d``."""
  from jax.sharding import NamedSharding, PartitionSpec as P
  return jax.device_put(
      jax.tree_util.tree_map(lambda *a: np.stack(a), *shards),
      NamedSharding(mesh, P(axis)))


def follow(layers0, steps, step_fn, keys, mesh, axis, hyper):
  """`chipbench.reference.follow` with each step computed by
  ``step_fn`` (`step_loss_and_grad`): ``(losses, first gradient,
  parameter change)`` in that function's form."""
  layers = jax.tree_util.tree_map(jnp.asarray, layers0)
  zeros = jax.tree_util.tree_map(jnp.zeros_like, layers)
  m, v, losses, g1 = zeros, zeros, [], None
  for t, shards in enumerate(steps, 1):
    loss, grads = step_fn(layers, stack(shards, mesh, axis), keys)
    losses.append(float(loss))
    if g1 is None:
      g1 = grads
    layers, m, v = ref.adam(layers, grads, m, v, t, hyper)
  delta = jax.tree_util.tree_map(lambda a, b: a - jnp.asarray(b), layers,
                                 layers0)
  return losses, ref.flat(g1), ref.flat(delta)


# -- what was drawn, against the seed's COO ---------------------------------

def _block_csr(rows, cols, n):
  """One COO block as a CSR over all ``n`` rows: rows sorted, columns
  ascending within a row."""
  rows, cols = jax.lax.sort((rows, cols), num_keys=2)
  indptr = jnp.searchsorted(rows, jnp.arange(n + 1, dtype=jnp.int32),
                            side='left').astype(jnp.int32)
  return indptr, cols


def _in_rows(indptr, indices, parent, child):
  """Is ``child`` in ``parent``'s row of this block?  A binary search
  of 32 halvings over the row's ascending columns."""
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


def tree_counts(mesh, axis, block_of, n: int, fanouts):
  """``f(key, levels) -> (bad_edges, bad_fanout)`` over the mesh:
  ``levels`` are the drawn trees' levels (original ids, every device's
  and every step's side by side along axis 0, replicated); device
  ``d`` draws block ``d`` of the seed's COO again (``block_of(key,
  d)``), and an edge is there if ANY block holds it, a node's degree
  the sum over the blocks.  Counted as `chipbench.reference.check_tree`
  counts: valid children that are no neighbour of their parent (or
  hang under a masked parent), and parents that did not get ``min(k,
  degree)`` children."""
  from jax.sharding import PartitionSpec as P

  def per_device(key, levels):
    rows, cols = block_of(key, jax.lax.axis_index(axis))
    indptr, indices = _block_csr(rows, cols, n)
    bad_e = bad_f = jnp.int32(0)
    for t, k in enumerate(fanouts):
      par = levels[t]
      kids = levels[t + 1].reshape(par.shape[0], k)
      pv, kv = par >= 0, kids >= 0
      p0 = jnp.where(pv, par, 0)
      here = _in_rows(indptr, indices, p0[:, None], jnp.where(kv, kids, 0))
      edge = jax.lax.psum(here.astype(jnp.int32), axis) > 0
      bad_e += jnp.sum(kv & ~(edge & pv[:, None]), dtype=jnp.int32)
      deg = jax.lax.psum(indptr[p0 + 1] - indptr[p0], axis)
      want = jnp.where(pv, jnp.minimum(deg, k), 0)
      bad_f += jnp.sum(kv.sum(1) != want, dtype=jnp.int32)
    return bad_e, bad_f

  return jax.jit(jax.shard_map(per_device, mesh=mesh, in_specs=(P(), P()),
                               out_specs=(P(), P()), check_vma=False))
