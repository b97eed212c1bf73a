"""Stacked GNN models over padded batches.

The TPU counterparts of the PyG models the reference's examples train
(GraphSAGE: `examples/train_sage_ogbn_products.py`; GAT/GCN variants in
`examples/`).  Each model is a flax module whose ``__call__`` takes
``(x, edge_index, edge_mask)`` — the `Batch` pytree fields — and
returns per-node embeddings/logits over the static node table.

A batch that states its sampler's hop layout (``metadata
['hop_capacities']``, `sampler.neighbor_sampler.hop_capacities`) lets
a stack of in-edge-local convs compute each layer only over the hops
that layer feeds (PyG's ``trim_to_layer`` with static shapes); the
result is then ``[C_0, out]``, the seed rows, instead of the whole
table.  Where the batch also states its fanout windows (``metadata
['hop_windows']``, `sampler.neighbor_sampler.hop_windows`) the convs
that can aggregate by window do, over the windows of the blocks their
layer keeps, instead of scattering every edge slot into the target
rows (`models.conv`).  `models.train.apply_to_batch` is the seam that
passes both on.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..telemetry.recorder import recorder
from ..utils.profiling import layer_scope
from .conv import GINConv, GATConv, GCNConv, SAGEConv


def _layer_extent(hop_capacities, hop: int):
  """``(rows in, rows out, edge slots)`` of the layer whose outputs
  feed the nodes within ``hop`` hops of the seeds: it writes ``[0,
  C_hop)`` from ``[0, C_{hop+1})`` over edge blocks ``0..hop``.  A
  stack deeper than the sampler (``hop`` past its last) keeps whole
  tables in its first layers."""
  node_caps, edge_caps = hop_capacities
  hops = len(edge_caps)
  hop = min(hop, hops)
  return (node_caps[min(hop + 1, hops)], node_caps[hop],
          edge_caps[min(hop, hops - 1)])


class BasicGNN(nn.Module):
  """L-layer stack: conv → relu → dropout, last layer linear.

  ``hop_capacities`` — static ``((C_0..C_H), (E_0..E_{H-1}))``, the
  cumulative node and edge-slot capacities per hop of the batch's
  sampler — trims the stack when its convs declare ``in_edge_local``
  (`SAGEConv`, `GATConv`; not `GCNConv`, whose normalisation reads the
  whole subgraph, and not yet `GINConv`): layer ``l`` of ``L``
  reads rows ``[0, C_{L-l})`` and edge slots ``[:E_{L-1-l}]`` and
  writes rows ``[0, C_{L-1-l})``, so a trimmed call returns ``[C_0,
  out]`` — the seed rows, each valid seed's equal to the untrimmed
  call's (a padded seed slot may hold a later hop's node, whose row is
  then partial; loss and accuracy mask those slots) — and one
  ``model.trim`` flight-recorder event per trace.  A
  stack of other convs ignores the argument; without it every layer
  runs over the whole table and the result is ``[n, out]``.  The
  parameters are the same either way.

  ``hop_windows`` — static ``((F_0, k_0), ..)``, the fanout windows of
  the sampler's edge blocks — goes to the convs that declare
  ``takes_windows`` (`SAGEConv`, `GATConv`): a layer that keeps
  edge blocks ``0..h`` hands its conv ``hop_windows[:h + 1]``, a layer
  over the whole table all of them, and the conv reduces over each
  window in place of a scatter over the edge slots — the same values to
  float32 round-off.  The ``model.trim`` event lists per layer the
  slots aggregated either way (``windowed_slots`` /
  ``scattered_slots``).
  """
  hidden_features: int
  out_features: int
  num_layers: int = 2
  dropout: float = 0.0
  aggr: str = 'mean'
  dtype: Optional[jnp.dtype] = None   # compute dtype (bfloat16 puts
                                      # the matmuls on the MXU at half
                                      # width; params/outputs stay f32)

  # `__call__` accepts ``hop_capacities`` (whether it then trims is
  # the convs' say)
  takes_hop_capacities = True

  def make_conv(self, out_features: int, idx: int) -> nn.Module:
    raise NotImplementedError

  @nn.compact
  def __call__(self, x, edge_index, edge_mask=None, *,
               edge_weight=None, hop_capacities=None, hop_windows=None,
               train: bool = False):
    # per trimmed layer: (rows in, rows out, edge slots, by window?)
    trim = []
    for i in range(self.num_layers):
      last = i == self.num_layers - 1
      out = self.out_features if last else self.hidden_features
      conv = self.make_conv(out, i)
      with layer_scope('model', f'layer{i}'):
        kwargs = {}
        windows = (hop_windows if getattr(conv, 'takes_windows', False)
                   else None)
        if (hop_capacities is not None and hop_capacities[1]
            and getattr(conv, 'in_edge_local', False)):
          hop = self.num_layers - 1 - i
          rows_in, rows_out, slots = _layer_extent(hop_capacities, hop)
          if windows is not None:
            # the blocks a trimmed layer keeps are a prefix of the list
            windows = windows[:hop + 1]
          trim.append((rows_in, rows_out, slots, windows is not None))
          x = x[:rows_in]
          edge_index = edge_index[:, :slots]
          if edge_mask is not None:
            edge_mask = edge_mask[:slots]
          if edge_weight is not None:
            edge_weight = edge_weight[:slots]
          kwargs['num_dst'] = rows_out
        if windows is not None:
          kwargs['windows'] = windows
        if edge_weight is not None:
          # GNS 1/q importance weights (Batch.metadata['edge_weight']):
          # only convs that define an unbiased weighted aggregation
          # accept them (SAGEConv) — passing to others raises loudly
          # rather than silently dropping the correction
          kwargs['edge_weight'] = edge_weight
        x = conv(x, edge_index, edge_mask, **kwargs)
        if not last:
          x = nn.relu(x)
          if self.dropout > 0:
            x = nn.Dropout(self.dropout, deterministic=not train)(x)
    if trim and not self.is_initializing():
      # trace time: one event per compiled program that trims
      rows_in, rows_out, slots, by_window = zip(*trim)
      recorder.emit('model.trim', layers=len(trim),
                    rows_in=list(rows_in), rows_out=list(rows_out),
                    edge_slots=list(slots),
                    windowed_slots=[s if w else 0
                                    for s, w in zip(slots, by_window)],
                    scattered_slots=[0 if w else s
                                     for s, w in zip(slots, by_window)],
                    table_rows=hop_capacities[0][-1],
                    table_slots=hop_capacities[1][-1])
    return x.astype(jnp.float32) if self.dtype is not None else x


class GraphSAGE(BasicGNN):
  """The flagship model (reference flagship example
  `examples/train_sage_ogbn_products.py`: 3 layers, hidden 256)."""

  def make_conv(self, out_features: int, idx: int) -> nn.Module:
    return SAGEConv(out_features, aggr=self.aggr, dtype=self.dtype,
                    name=f'conv{idx}')


class GCN(BasicGNN):

  def make_conv(self, out_features: int, idx: int) -> nn.Module:
    return GCNConv(out_features, dtype=self.dtype, name=f'conv{idx}')


class GIN(BasicGNN):
  """GIN stack (sum aggregator + per-layer MLP) — the
  expressiveness-maximal member of the standard zoo."""

  def make_conv(self, out_features: int, idx: int) -> nn.Module:
    return GINConv(out_features, hidden_features=self.hidden_features,
                   train_eps=True, dtype=self.dtype, name=f'conv{idx}')


class GAT(BasicGNN):
  heads: int = 4

  def make_conv(self, out_features: int, idx: int) -> nn.Module:
    last = idx == self.num_layers - 1
    return GATConv(out_features if last else out_features // self.heads,
                   heads=self.heads, concat=not last, dtype=self.dtype,
                   name=f'conv{idx}')


class DGCNN(nn.Module):
  """Deep Graph CNN: sort-pooling + 1-D convolutions.

  The classifier the reference's SEAL example trains (its
  `examples/seal_link_pred.py` uses PyG's DGCNN: stacked tanh-GCN
  layers, concatenate all layer outputs, SortPool the top ``k`` nodes
  by the last 1-wide layer's value, then Conv1d -> MLP).  TPU
  re-design: the pool is a masked top-k (static ``k``) instead of a
  dynamic-size sort, the "kernel = total-width, stride = total-width"
  Conv1d of the paper is the equivalent per-node width-1 convolution
  over the ``[k, D]`` sequence, and everything keeps static shapes.

  Call with node features (or label embeddings), padded local COO and
  masks; returns ``[out_features]`` graph-level logits.
  """
  hidden_features: int = 32
  out_features: int = 2
  num_layers: int = 3
  k: int = 30
  dtype: Optional[jnp.dtype] = None

  @nn.compact
  def __call__(self, x, edge_index, edge_mask=None, node_mask=None):
    if node_mask is None:
      node_mask = jnp.ones((x.shape[0],), bool)
    hs = []
    h = x
    for i in range(self.num_layers):
      h = jnp.tanh(GCNConv(self.hidden_features, dtype=self.dtype,
                           name=f'conv{i}')(h, edge_index, edge_mask))
      hs.append(h)
    # final 1-wide layer provides the canonical sort key
    h = jnp.tanh(GCNConv(1, dtype=self.dtype,
                         name=f'conv{self.num_layers}')(
                             h, edge_index, edge_mask))
    hs.append(h)
    hcat = jnp.concatenate(hs, axis=-1)                   # [n, D]
    sort_key = jnp.where(node_mask, h[:, 0], -jnp.inf)
    top = jax.lax.top_k(sort_key, min(self.k, x.shape[0]))[1]
    valid = sort_key[top] > -jnp.inf
    pooled = jnp.where(valid[:, None], hcat[top], 0.0)    # [k, D]
    if pooled.shape[0] < self.k:                          # tiny graphs
      pooled = jnp.concatenate(
          [pooled, jnp.zeros((self.k - pooled.shape[0], pooled.shape[1]),
                             pooled.dtype)])
    seq = pooled[None]                                    # [1, k, D]
    z = nn.relu(nn.Conv(16, kernel_size=(1,), dtype=self.dtype,
                        name='conv1d_a')(seq))
    if z.shape[1] >= 2:
      z = nn.max_pool(z, window_shape=(2,), strides=(2,))
    # kernel clamps for small k so the VALID conv never emits length 0
    z = nn.relu(nn.Conv(32, kernel_size=(min(5, z.shape[1]),),
                        padding='VALID', dtype=self.dtype,
                        name='conv1d_b')(z))
    z = z.reshape(1, -1)
    z = nn.relu(nn.Dense(128, dtype=self.dtype)(z))
    out = nn.Dense(self.out_features, dtype=self.dtype)(z)[0]
    return out.astype(jnp.float32) if self.dtype is not None else out
