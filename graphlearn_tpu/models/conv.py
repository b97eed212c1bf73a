"""Message-passing convolutions on padded COO batches (flax).

The reference deliberately leaves model compute to PyG
(`README.md` "Architecture Overview"); its examples train PyG's
``SAGEConv``/``GATConv``/HGT on the batches GLT loads.  A standalone
TPU framework has no PyG to lean on, so the model family lives here —
designed for the padding contract: edges are ``[2, E]`` local COO with
-1 masked slots, aggregation is `segment_sum` over static-size node
tables (no atomics, no dynamic shapes).  On a v5e XLA lowers that to a
row gather on the source side and, on the target side, a scatter-add
with one update per edge slot that the memory system paces, not the
MXU: the flagship per-batch step read ``train_step_mfu`` 0.068 % with
212.9 ms of its 350 ms in these convs (PERF_LEDGER.jsonl, PR 25), so
what a conv costs is the rows and edge slots it is handed — which is
why `BasicGNN` hands each layer only the hops that layer feeds.

The target side needs no scatter where the caller states the edge
list's fanout windows (``windows``, what a `NeighborLoader` batch
carries as ``metadata['hop_windows']``:
`sampler.neighbor_sampler.hop_windows`).  Block ``h`` of the slots is
then ``[F_h, k_h]`` flattened and slot ``(i, j)`` of it targets row
``start_h + i`` or is masked, so mean, sum, max and the per-target
softmax are dense reductions over the ``k`` axis, placed at rows
``[start_h, start_h + F_h)`` (`_Windows`); a target's own values reach
its slots by a broadcast, not a gather.  `SAGEConv` and `GATConv` take
``windows`` and declare ``takes_windows``; the source side (the
gather ``x[src]`` and its backward scatter) is the same either way,
and an edge list without the statement keeps the `segment_*` path —
the general case, and the windowed form's oracle in the tests.

A conv whose output row depends on that row and its in-edges only
declares ``in_edge_local = True`` and takes the bipartite form:
``num_dst`` — messages gathered from all ``n_src`` input rows and
aggregated into the first ``num_dst`` of them — or ``x`` as a pair
``(x_src, x_dst)`` of separate source and target tables (a relation
between two node types, `models.hetero.HeteroConv`).  `SAGEConv` and
`GATConv` do.  `GCNConv` may not (its normalisation counts a source's
out-edges over the whole subgraph); `GINConv` / `GATv2Conv` could and
have not been given the form yet.

Edge direction follows the loader's transposed emission
(reference `sampler/neighbor_sampler.py:159-166`): ``edge_index[0]`` is
the message *source* (sampled neighbor), ``edge_index[1]`` the
*target* (seed side) — i.e. messages flow src→dst like PyG.
"""
from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


def segment_mean(data: jax.Array, segment_ids: jax.Array,
                 num_segments: int, mask: Optional[jax.Array] = None,
                 weights: Optional[jax.Array] = None) -> jax.Array:
  """Masked mean-aggregation of edge messages into node slots.

  Invalid edges (mask False or negative target) are routed to segment
  ``num_segments`` which is out of range and therefore dropped by XLA's
  segment_sum — the standard static-shape trick.

  ``weights`` (``[E]``, the GNS 1/q importance weights from
  ``Batch.metadata['edge_weight']``) scale the NUMERATOR only while
  the denominator stays the valid-edge count: the estimator is
  ``Σ_j w_j·x_j / k``, exactly the form `ops.gns` proves unbiased for
  the uniform neighbor mean under ANY sampling bias (the weights
  average to 1 in expectation).  ``weights=None`` is bit-identical to
  the unweighted path.
  """
  if mask is not None:
    segment_ids = jnp.where(mask, segment_ids, num_segments)
  else:
    segment_ids = jnp.where(segment_ids >= 0, segment_ids, num_segments)
  if weights is not None:
    data = data * weights.astype(data.dtype)[:, None]
  tot = jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)
  # count in f32: low-precision ones (bf16) saturate near 256 under
  # scatter-add, corrupting hub-node means
  cnt = jax.ops.segment_sum(jnp.ones((data.shape[0],), jnp.float32),
                            segment_ids, num_segments=num_segments)
  mean = tot.astype(jnp.float32) / jnp.maximum(cnt, 1.0)[:, None]
  return mean.astype(data.dtype)


def segment_max(data: jax.Array, segment_ids: jax.Array,
                num_segments: int, mask: Optional[jax.Array] = None
                ) -> jax.Array:
  if mask is not None:
    segment_ids = jnp.where(mask, segment_ids, num_segments)
  out = jax.ops.segment_max(data, segment_ids, num_segments=num_segments)
  return jnp.where(jnp.isfinite(out), out, 0.0)


def segment_softmax(e: jax.Array, dst: jax.Array, num_segments: int,
                    valid: jax.Array) -> jax.Array:
  """Masked per-target softmax over edge scores ``e`` ``[E, h]`` —
  THE attention normalizer (GAT/GATv2 share it): route invalid edges
  out of range, subtract the per-target max, exp, normalize."""
  dsafe = jnp.where(valid, dst, num_segments)
  dc = jnp.clip(dst, 0, num_segments - 1)
  e = jnp.where(valid[:, None], e, -jnp.inf)
  emax = jax.ops.segment_max(e, dsafe, num_segments=num_segments)
  emax = jnp.where(jnp.isfinite(emax), emax, 0.0)
  ex = jnp.where(valid[:, None], jnp.exp(e - emax[dc]), 0.0)
  denom = jax.ops.segment_sum(ex, dsafe, num_segments=num_segments)
  return ex / jnp.maximum(denom[dc], 1e-16)


class _Windows:
  """An edge list's target side as its sampler's fanout windows.

  ``windows`` — static ``((F_0, k_0), (F_1, k_1), ...)`` — says that
  ``dst`` is the concatenation of blocks ``[F_h, k_h]`` (flattened;
  ``F_h * k_h`` may be 0) in which slot ``(i, j)`` holds ``start_h +
  i`` or -1, with ``start_h <= F_0 + .. + F_{h-1}`` and every valid
  target below ``num_dst``
  (`sampler.neighbor_sampler.hop_windows`).  The blocks' valid row
  ranges follow one another; past its valid windows a block is wholly
  masked, so block results are zero there and may overlap other
  blocks' rows.  ``start_h`` is read from ``dst`` itself (any valid
  slot of window ``i`` holds ``start_h + i``; a block with none adds
  nothing wherever it is placed).

  The reductions run over a block laid out draw by draw, ``[k_h, F_h,
  ...]``: `by_draw` reorders a per-slot *vector* (ids, masks, weights)
  so; the rows gathered by the reordered ids come out in that order,
  and `split` views them per block — ``k`` slabs of ``[F, ...]`` whose
  sum or max is elementwise over whole tiles, where ``[F, k, ...]``
  would put the ``k <= 15`` draws on the tiled second-minor axis and
  cost a relayout of every gathered row.
  """

  def __init__(self, dst: jax.Array, windows, num_dst: int):
    self.num_dst = num_dst
    self.blocks = []       # (first slot, F, k) of the non-empty blocks
    self.starts = []       # their dynamic target offsets
    at = before = 0
    # rows the placement buffer needs so that no block overruns it
    # (`dynamic_update_slice` would clamp the start): a trimmed layer
    # of an unclamped sampler needs none beyond ``num_dst``
    self.rows = num_dst
    for f, k in windows:
      f, k = int(f), int(k)
      if f * k:
        self.blocks.append((at, f, k))
        self.rows = max(self.rows, min(before, num_dst) + f)
        blk = dst[at:at + f * k]
        window = jnp.arange(f * k, dtype=blk.dtype) // k
        self.starts.append(jnp.max(jnp.where(blk >= 0, blk - window, 0)))
      at += f * k
      before += f
    if at != dst.shape[0]:
      raise ValueError(f'windows {tuple(windows)} cover {at} edge slots, '
                       f'the edge list has {dst.shape[0]}')

  def by_draw(self, per_slot: jax.Array) -> jax.Array:
    """A per-slot vector ``[E]`` with every block reordered from window
    by window (slot ``i * k + j``) to draw by draw (``j * F + i``)."""
    return jnp.concatenate([
        per_slot[at:at + f * k].reshape(f, k).T.reshape(-1)
        for at, f, k in self.blocks])

  def split(self, by_draw: jax.Array):
    """``by_draw[E, ...]`` (in `by_draw`'s order) as its blocks,
    ``[k_h, F_h, ...]`` each."""
    return [by_draw[at:at + f * k].reshape((k, f) + by_draw.shape[1:])
            for at, f, k in self.blocks]

  def take(self, per_target: jax.Array):
    """Per block the rows of ``per_target[num_dst, ...]`` its windows
    aggregate into, ``[F_h, ...]``: a slice, to be broadcast over the
    draws, where the segment path gathers once per slot."""
    pad = [(0, self.rows - self.num_dst)] + [(0, 0)] * (per_target.ndim - 1)
    rows = jnp.pad(per_target, pad)
    return [jax.lax.dynamic_slice_in_dim(rows, start, f)
            for start, (_, f, _) in zip(self.starts, self.blocks)]

  def place(self, per_window):
    """``[num_dst, ...]`` from per block ``[F_h, ...]``: block ``h``
    added at rows ``[start_h, start_h + F_h)``."""
    like = per_window[0]
    out = jnp.zeros((self.rows,) + like.shape[1:], like.dtype)
    for start, (_, f, _), rows in zip(self.starts, self.blocks,
                                      per_window):
      held = jax.lax.dynamic_slice_in_dim(out, start, f)
      out = jax.lax.dynamic_update_slice_in_dim(out, held + rows, start,
                                                axis=0)
    return out[:self.num_dst]


def window_aggregate(x_src: jax.Array, src: jax.Array, dst: jax.Array,
                     num_dst: int, windows, aggr: str = 'mean',
                     mask: Optional[jax.Array] = None,
                     weights: Optional[jax.Array] = None) -> jax.Array:
  """`segment_mean` / `segment_sum` / `segment_max` of the messages
  ``x_src[src]`` into ``num_dst`` target rows for an edge list that
  states its fanout windows (`_Windows`): the same ``E`` rows gathered,
  draw by draw, a masked reduction over each block's draws and a
  placement — no scatter over the edge slots.  Same equations as the
  segment forms — the mean's count in float32, ``weights`` on the
  numerator only, a target without valid in-edges 0 — with the sums of
  at most ``k`` terms in window order.
  """
  valid = dst >= 0 if mask is None else mask & (dst >= 0)
  win = _Windows(dst, windows, num_dst)
  if not win.blocks:
    return jnp.zeros((num_dst,) + x_src.shape[1:], x_src.dtype)
  data = x_src[jnp.clip(win.by_draw(src), 0, x_src.shape[0] - 1)]
  if weights is not None:
    data = data * win.by_draw(weights).astype(data.dtype)[:, None]

  def reduce(m, v):   # one block's draws: [k, F, d], [k, F] -> [F, d]
    if aggr == 'max':
      top = jnp.where(v[:, :, None], m, -jnp.inf).max(axis=0)
      return jnp.where(jnp.isfinite(top), top, 0.0).astype(m.dtype)
    tot = jnp.where(v[:, :, None], m, 0).sum(axis=0)
    if aggr == 'sum':
      return tot
    cnt = v.sum(axis=0, dtype=jnp.float32)
    return (tot.astype(jnp.float32)
            / jnp.maximum(cnt, 1.0)[:, None]).astype(m.dtype)

  return win.place([reduce(m, v) for m, v in zip(
      win.split(data), win.split(win.by_draw(valid)))])


def _window_attention(z: jax.Array, alpha_src: jax.Array,
                      alpha_dst: jax.Array, src: jax.Array, dst: jax.Array,
                      valid: jax.Array, n: int, windows,
                      negative_slope: float, concat: bool) -> jax.Array:
  """GAT's per-target softmax and weighted sum (`segment_softmax` +
  `_attention_aggregate`) for an edge list that states its fanout
  windows: the sources' rows ``z[src]`` and scores are gathered draw by
  draw, per block the scores ``[k, F, h]`` take the targets' term by
  broadcast, max and sum run over the draws, and the weighted messages
  ``[k, F, h, f]`` reduce to ``[F, h, f]`` rows that are placed — three
  segment operations and two gathers back to the slots fewer than the
  segment path, no scatter over the edge slots."""
  heads, features = z.shape[1:]
  width = heads * features if concat else features
  win = _Windows(dst, windows, n)
  if not win.blocks:
    return jnp.zeros((n, width), z.dtype)
  sc = jnp.clip(win.by_draw(src), 0, z.shape[0] - 1)
  out = []
  for zs, a_src, a_dst, v in zip(win.split(z[sc]), win.split(alpha_src[sc]),
                                 win.take(alpha_dst),
                                 win.split(win.by_draw(valid))):
    v = v[:, :, None]
    e = nn.leaky_relu(a_src + a_dst[None], negative_slope)     # [k, F, h]
    e = jnp.where(v, e, -jnp.inf)
    emax = e.max(axis=0, keepdims=True)
    emax = jnp.where(jnp.isfinite(emax), emax, 0.0)
    ex = jnp.where(v, jnp.exp(e - emax), 0.0)
    w = ex / jnp.maximum(ex.sum(axis=0, keepdims=True), 1e-16)
    agg = (zs * w.astype(zs.dtype)[..., None]).sum(axis=0)     # [F, h, f]
    out.append(agg.reshape(-1, width) if concat else agg.mean(axis=1))
  return win.place(out)


def _bipartite(x, num_dst):
  """``(x_src, x_dst or None, n_dst)`` of a conv's ``x`` argument: one
  table (targets are its first ``num_dst`` rows, all of them when
  ``None``) or a ``(x_src, x_dst)`` pair."""
  if isinstance(x, (tuple, list)):
    x_src, x_dst = x
    if num_dst is not None and num_dst != x_dst.shape[0]:
      raise ValueError(f'num_dst={num_dst} with {x_dst.shape[0]} target '
                       'rows: a (x_src, x_dst) pair states its own')
    return x_src, x_dst, x_dst.shape[0]
  return x, None, x.shape[0] if num_dst is None else num_dst


def _attention_aggregate(z_src_sel: jax.Array, w: jax.Array,
                         dst: jax.Array, valid: jax.Array, n: int,
                         heads: int, features: int,
                         concat: bool) -> jax.Array:
  """Shared GAT/GATv2 tail: weight edge messages by the softmaxed
  scores, scatter into node slots, merge heads."""
  dsafe = jnp.where(valid, dst, n)
  msg = z_src_sel * w.astype(z_src_sel.dtype)[:, :, None]  # [E, h, f]
  agg = jax.ops.segment_sum(msg.reshape(-1, heads * features), dsafe,
                            num_segments=n).reshape(n, heads, features)
  if concat:
    return agg.reshape(n, heads * features)
  return agg.mean(axis=1)


class SAGEConv(nn.Module):
  """GraphSAGE convolution (mean aggregator).

  ``out[v] = W_l · x[v] + W_r · mean_{u→v} x[u]`` — the layer the
  reference's flagship examples use via PyG
  (`examples/train_sage_ogbn_products.py`).

  ``edge_weight`` threads the GNS per-edge 1/q importance weights
  (``Batch.metadata['edge_weight']``, PR 10) into the aggregation so
  cache-biased sampling stays unbiased END TO END at the model, not
  just the estimator (mean: weighted numerator over valid-count
  denominator; sum: weighted sum).  None = the unweighted path,
  bit-identical to before.

  ``num_dst`` selects the bipartite form: sources are all ``n_src``
  rows of ``x``, targets its first ``num_dst`` rows (every valid
  ``edge_index[1] < num_dst``), and the result is ``[num_dst, out]``
  — each row what the square form gives it over the same edges.  None
  is the square form, ``[n_src, out]``.  ``x`` may also be a pair
  ``(x_src, x_dst)`` of equal width: sources and targets in tables of
  their own, ``edge_index[0]`` into the first and ``[1]`` into the
  second.  The parameters are the same either way.

  ``windows`` — the edge list's static fanout windows (`_Windows`;
  those of the blocks the caller kept) — aggregates by window where
  the default scatters every edge slot into the target rows: the same
  result to float32 round-off (`window_aggregate`).
  """
  out_features: int
  use_bias: bool = True
  aggr: str = 'mean'
  dtype: Optional[jnp.dtype] = None   # compute dtype (e.g. bfloat16
                                      # for the MXU); params stay f32
  # an output row reads its own row and its in-edges, nothing else
  in_edge_local = True
  # `__call__` accepts ``windows``
  takes_windows = True

  @nn.compact
  def __call__(self, x: jax.Array, edge_index: jax.Array,
               edge_mask: Optional[jax.Array] = None,
               edge_weight: Optional[jax.Array] = None,
               num_dst: Optional[int] = None,
               windows=None) -> jax.Array:
    x, x_dst, n = _bipartite(x, num_dst)
    if self.dtype is not None:
      x = x.astype(self.dtype)
      x_dst = None if x_dst is None else x_dst.astype(self.dtype)
    n_src = x.shape[0]
    src, dst = edge_index[0], edge_index[1]
    if self.aggr not in ('mean', 'max', 'sum'):
      raise ValueError(f'Unknown aggr {self.aggr!r}')
    if self.aggr == 'max' and edge_weight is not None:
      raise ValueError('edge_weight has no unbiased meaning under '
                       "max aggregation — use aggr='mean'/'sum' "
                       'with GNS importance weights')
    if windows is not None:
      agg = window_aggregate(x, src, dst, n, windows, self.aggr, edge_mask,
                             weights=edge_weight)
    else:
      msg = x[jnp.clip(src, 0, n_src - 1)]
      if self.aggr == 'mean':
        agg = segment_mean(msg, dst, n, edge_mask, weights=edge_weight)
      elif self.aggr == 'max':
        agg = segment_max(msg, dst, n, edge_mask)
      else:
        if edge_weight is not None:
          msg = msg * edge_weight.astype(msg.dtype)[:, None]
        seg = jnp.where(edge_mask, dst, n) if edge_mask is not None else dst
        agg = jax.ops.segment_sum(msg, seg, num_segments=n)
    if x_dst is None:
      x_dst = x if num_dst is None else x[:num_dst]
    out = (nn.Dense(self.out_features, use_bias=self.use_bias,
                    dtype=self.dtype, name='lin_self')(x_dst)
           + nn.Dense(self.out_features, use_bias=False,
                      dtype=self.dtype, name='lin_neigh')(agg))
    return out


class GCNConv(nn.Module):
  """Graph convolution with symmetric degree normalization (masked)."""
  out_features: int
  use_bias: bool = True
  dtype: Optional[jnp.dtype] = None

  @nn.compact
  def __call__(self, x: jax.Array, edge_index: jax.Array,
               edge_mask: Optional[jax.Array] = None) -> jax.Array:
    if self.dtype is not None:
      x = x.astype(self.dtype)
    n = x.shape[0]
    src, dst = edge_index[0], edge_index[1]
    valid = edge_mask if edge_mask is not None else (dst >= 0)
    ssafe = jnp.where(valid, src, n)
    dsafe = jnp.where(valid, dst, n)
    # degrees count in f32 (bf16 scatter-add saturates near 256)
    ones = valid.astype(jnp.float32)
    deg_in = jax.ops.segment_sum(ones, dsafe, num_segments=n) + 1.0
    deg_out = jax.ops.segment_sum(ones, ssafe, num_segments=n) + 1.0
    w = (jax.lax.rsqrt(deg_out)[jnp.clip(src, 0, n - 1)]
         * jax.lax.rsqrt(deg_in)[jnp.clip(dst, 0, n - 1)])
    h = nn.Dense(self.out_features, use_bias=self.use_bias,
                 dtype=self.dtype)(x)
    msg = h[jnp.clip(src, 0, n - 1)] * w.astype(h.dtype)[:, None]
    agg = jax.ops.segment_sum(msg, dsafe, num_segments=n)
    # self loop with 1/deg normalization
    self_w = jax.lax.rsqrt(deg_in) * jax.lax.rsqrt(deg_out)
    return agg + h * self_w.astype(h.dtype)[:, None]


class GINConv(nn.Module):
  """Graph isomorphism convolution (sum aggregator + MLP).

  ``out[v] = MLP((1 + eps) * x[v] + sum_{u→v} x[u])`` — the
  expressiveness-maximal aggregator of the standard zoo (Xu et al.);
  masked edges route to the out-of-range segment like every conv
  here.  ``train_eps`` learns the self-weight; otherwise eps stays a
  constant.
  """
  out_features: int
  hidden_features: Optional[int] = None
  eps: float = 0.0
  train_eps: bool = False
  dtype: Optional[jnp.dtype] = None

  @nn.compact
  def __call__(self, x: jax.Array, edge_index: jax.Array,
               edge_mask: Optional[jax.Array] = None) -> jax.Array:
    if self.dtype is not None:
      x = x.astype(self.dtype)
    n = x.shape[0]
    src, dst = edge_index[0], edge_index[1]
    valid = edge_mask if edge_mask is not None else (dst >= 0)
    dsafe = jnp.where(valid, dst, n)
    msg = x[jnp.clip(src, 0, n - 1)]
    agg = jax.ops.segment_sum(msg, dsafe, num_segments=n)
    if self.train_eps:
      eps = self.param('eps', nn.initializers.constant(self.eps),
                       ()).astype(x.dtype)
    else:
      eps = self.eps
    h = (1.0 + eps) * x + agg
    hidden = self.hidden_features or self.out_features
    h = nn.Dense(hidden, dtype=self.dtype, name='mlp_0')(h)
    h = nn.relu(h)
    return nn.Dense(self.out_features, dtype=self.dtype, name='mlp_1')(h)


class GATConv(nn.Module):
  """Graph attention convolution (masked softmax over incoming edges;
  Velickovic et al. 2018 without self-loops, one projection ``W`` for
  both ends of an edge and no bias).

  ``z = W x`` per head, ``e_uv = leaky_relu(<a_src, z_u> + <a_dst,
  z_v>)``, ``alpha = softmax`` of ``e`` over the valid in-edges of
  ``v``, ``out[v] = sum_u alpha_uv z_u`` (a target without valid
  in-edges gets 0).  The bipartite form is `SAGEConv`'s: ``num_dst``
  (targets are the first ``num_dst`` rows, result ``[num_dst, .]``) or
  ``x`` as ``(x_src, x_dst)``, both ends projected by the one ``W``.
  ``windows`` is `SAGEConv`'s too: softmax and weighted sum by fanout
  window (`_window_attention`), no segment operation over the slots.
  """
  out_features: int
  heads: int = 1
  concat: bool = True
  negative_slope: float = 0.2
  dtype: Optional[jnp.dtype] = None
  # an output row reads its own row and its in-edges, nothing else
  in_edge_local = True
  # `__call__` accepts ``windows``
  takes_windows = True

  @nn.compact
  def __call__(self, x, edge_index: jax.Array,
               edge_mask: Optional[jax.Array] = None,
               num_dst: Optional[int] = None,
               windows=None) -> jax.Array:
    x, x_dst, n = _bipartite(x, num_dst)
    h, f = self.heads, self.out_features
    src, dst = edge_index[0], edge_index[1]
    valid = edge_mask if edge_mask is not None else (dst >= 0)
    lin = nn.Dense(h * f, use_bias=False, dtype=self.dtype)
    project = lambda rows: lin(rows).reshape(rows.shape[0], h, f)
    z = project(x)
    z_dst = z[:n] if x_dst is None else project(x_dst)
    a_src = self.param('att_src', nn.initializers.glorot_uniform(),
                       (h, f))
    a_dst = self.param('att_dst', nn.initializers.glorot_uniform(),
                       (h, f))
    a_src = a_src.astype(z.dtype)
    a_dst = a_dst.astype(z.dtype)
    alpha_src = (z * a_src[None]).sum(-1).astype(jnp.float32)  # [n_src, h]
    alpha_dst = (z_dst * a_dst[None]).sum(-1).astype(jnp.float32)
    sc = jnp.clip(src, 0, z.shape[0] - 1)
    if windows is not None:
      return _window_attention(z, alpha_src, alpha_dst, src, dst,
                               valid & (dst >= 0), n, windows,
                               self.negative_slope, self.concat)
    e = nn.leaky_relu(alpha_src[sc] + alpha_dst[jnp.clip(dst, 0, n - 1)],
                      self.negative_slope)          # [E, h]
    w = segment_softmax(e, dst, n, valid)
    return _attention_aggregate(z[sc], w, dst, valid, n, h, f,
                                self.concat)


class GATv2Conv(nn.Module):
  """GATv2 attention (Brody et al.): the score applies the nonlinearity
  BEFORE the attention vector — ``e(u, v) = a^T leaky_relu(W_s x[u] +
  W_d x[v])`` — fixing GAT's static-attention limitation.  Same masked
  segment-softmax machinery as `GATConv`."""
  out_features: int
  heads: int = 1
  concat: bool = True
  negative_slope: float = 0.2
  dtype: Optional[jnp.dtype] = None

  @nn.compact
  def __call__(self, x: jax.Array, edge_index: jax.Array,
               edge_mask: Optional[jax.Array] = None) -> jax.Array:
    if self.dtype is not None:
      x = x.astype(self.dtype)
    n = x.shape[0]
    h, f = self.heads, self.out_features
    src, dst = edge_index[0], edge_index[1]
    valid = edge_mask if edge_mask is not None else (dst >= 0)
    sc = jnp.clip(src, 0, n - 1)
    dc = jnp.clip(dst, 0, n - 1)
    z_src = nn.Dense(h * f, use_bias=False, dtype=self.dtype,
                     name='lin_src')(x).reshape(n, h, f)
    z_dst = nn.Dense(h * f, use_bias=False, dtype=self.dtype,
                     name='lin_dst')(x).reshape(n, h, f)
    att = self.param('att', nn.initializers.glorot_uniform(), (h, f))
    pre = nn.leaky_relu(z_src[sc] + z_dst[dc],
                        self.negative_slope)         # [E, h, f]
    e = (pre * att[None].astype(pre.dtype)).sum(-1).astype(jnp.float32)
    w = segment_softmax(e, dst, n, valid)
    return _attention_aggregate(z_src[sc], w, dst, valid, n, h, f,
                                self.concat)
