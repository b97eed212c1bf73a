"""Heterogeneous GNN models over `HeteroBatch` pytrees.

TPU counterparts of the PyG models the reference's hetero examples
train: R-GCN/RGAT/RSAGE (`examples/igbh/rgnn.py`) and HGT
(`examples/hetero/train_hgt_mag.py`).  Convention matches the hetero
batch emission: ``edge_index_dict[(a, rel, b)][0]`` indexes type-``a``
nodes (message sources), ``[1]`` indexes type-``b`` nodes (targets).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..telemetry.recorder import recorder
from ..typing import EdgeType, NodeType, as_str
from ..utils.profiling import layer_scope
from .conv import GATConv, segment_mean


class _NamedConv(nn.Module):
  """Binds a factory-made conv under an explicit etype-keyed scope, so
  params never depend on positional auto-naming (which shifts when a
  batch lacks some edge type), and runs it over one relation:
  ``x_src`` the source type's rows, ``x_dst`` the target type's
  (``None`` for a relation within one type, whose targets are the
  first ``num_dst`` source rows).

  A conv that declares ``in_edge_local`` (`models.conv`) takes the two
  tables as they are.  Any other conv gets the concatenation ``[x_dst;
  x_src]`` — a copy of both tables per relation, kept for the backward
  pass — with the source ids shifted, and cannot be trimmed."""
  factory: Callable[[], nn.Module]

  @nn.compact
  def __call__(self, x_src, x_dst, edge_index, edge_mask, num_dst=None,
               windows=None):
    conv = self.factory()
    # the relation's fanout windows, for a conv that aggregates by them
    kwargs = ({'windows': windows} if windows is not None
              and getattr(conv, 'takes_windows', False) else {})
    if getattr(conv, 'in_edge_local', False):
      if x_dst is None:
        return conv(x_src, edge_index, edge_mask, num_dst=num_dst,
                    **kwargs)
      return conv((x_src, x_dst), edge_index, edge_mask, **kwargs)
    if num_dst is not None:
      raise ValueError(
          f'{type(conv).__name__} is not in-edge-local: it cannot '
          'compute a trimmed relation (num_dst)')
    if x_dst is None:
      return conv(x_src, edge_index, edge_mask)
    na, nb = x_src.shape[0], x_dst.shape[0]
    src2 = jnp.clip(edge_index[0], 0, na - 1) + nb
    return conv(jnp.concatenate([x_dst, x_src], axis=0),
                jnp.stack([src2, edge_index[1]]), edge_mask)[:nb]


class HeteroConv(nn.Module):
  """Applies a per-edge-type conv and aggregates per target type.

  Two modes (reference analog: PyG's ``HeteroConv`` the examples wrap,
  `examples/igbh/rgnn.py`):

    * default (``make_conv=None``): per-etype linear message +
      mean-aggregation, plus a per-type self term — the RGCN flavor;
    * ``make_conv`` given: each edge type gets a fresh conv from the
      factory (e.g. ``lambda: GATConv(d, heads=h)`` for RGAT), run
      bipartite — a conv that declares ``in_edge_local`` takes the
      source and the target type's rows as two tables, any other the
      source-offset concatenation (`_NamedConv`); no extra self term
      (the conv's own self path applies, PyG semantics).

  ``num_dst_dict`` — ``{node type: rows}`` — computes, per target
  type, only its first ``rows`` rows (a type left out or at 0 gets no
  output): what a stack trimmed to the hops each layer feeds asks of
  its layers (`RGAT`).  The parameters are the same either way.

  ``windows_dict`` — ``{edge type: ((F_0, k_0), ..)}``, the fanout
  windows of each relation's edge list as handed in
  (`sampler.hetero_neighbor_sampler.typed_hop_windows`) — goes to the
  factory convs that declare ``takes_windows``, which then reduce
  over each window in place of the segment operations over the edge
  slots (`models.conv`); the default mode keeps `segment_mean`.

  Device ops carry ``glt.model/<scope>/<relation>`` per relation's
  convolution and ``glt.model/<scope>/merge`` over the sum into the
  target types (``<scope>`` is ``part``: the stack's ``layer<l>``; the
  module's name by default).

  Args:
    etypes: edge types to convolve.
    out_features: per-type output width (factory convs must produce
      this width too — e.g. ``GATConv(d // heads, heads=heads)``).
    aggr: cross-etype aggregation into a target type ('sum'/'mean').
    make_conv: optional factory of homogeneous convs with signature
      ``conv(x, edge_index, edge_mask)``.
  """
  etypes: Tuple[EdgeType, ...]
  out_features: int
  aggr: str = 'sum'
  make_conv: Optional[Callable[[], nn.Module]] = None
  dtype: Optional[jnp.dtype] = None   # compute dtype; params stay f32
  part: Optional[str] = None   # the scopes' ``<scope>``

  @nn.compact
  def __call__(self, x_dict, edge_index_dict, edge_mask_dict=None,
               num_dst_dict=None, windows_dict=None):
    scope = self.part or self.name or 'hetero'
    rows_out = lambda nt: (x_dict[nt].shape[0] if num_dst_dict is None
                           else int(num_dst_dict.get(nt, 0)))
    if self.make_conv is not None and self.dtype is not None:
      # the factory owns its convs' compute dtype; accepting both
      # would leave the dominant per-etype matmuls silently f32
      raise ValueError(
          'HeteroConv(make_conv=..., dtype=...): set the compute dtype '
          'inside the factory instead, e.g. '
          'lambda: SAGEConv(d, dtype=jnp.bfloat16)')
    out: Dict[NodeType, Any] = {}
    counts: Dict[NodeType, int] = {}
    for et in self.etypes:
      a, _, b = et
      if a not in x_dict or b not in x_dict or not rows_out(b):
        continue
      windows = None
      if et in edge_index_dict:
        ei = edge_index_dict[et]
        em = (edge_mask_dict or {}).get(et)
        windows = (windows_dict or {}).get(et)
      else:
        # etype configured but absent from this batch: run the conv on
        # an empty edge set so the param structure stays a function of
        # `self.etypes`, never of batch content (otherwise a batch
        # missing one etype would init/apply a different pytree).
        ei = jnp.zeros((2, 0), jnp.int32)
        em = jnp.zeros((0,), jnp.bool_)
      na, nb = x_dict[a].shape[0], rows_out(b)
      with layer_scope('model', f'{scope}/{as_str(et)}'):
        if self.make_conv is not None:
          conv = _NamedConv(self.make_conv, name=f'conv_{as_str(et)}')
          if a == b:
            # a relation within one type: one table, no second copy
            agg = conv(x_dict[a], None, ei, em,
                       None if num_dst_dict is None else nb, windows)
          else:
            xa, xb = x_dict[a], x_dict[b][:nb]
            if xa.shape[-1] != xb.shape[-1]:
              raise ValueError(
                  f'HeteroConv(make_conv=...) needs equal feature '
                  f'widths for {et}: {xa.shape[-1]} vs {xb.shape[-1]} — '
                  f'project per-type inputs first (e.g. a Dense per '
                  f'node type)')
            agg = conv(xa, xb, ei, em, windows=windows)
        else:
          msg = nn.Dense(self.out_features, use_bias=False,
                         dtype=self.dtype, name=f'lin_{as_str(et)}')(
                             x_dict[a][jnp.clip(ei[0], 0, na - 1)])
          agg = segment_mean(msg, ei[1], nb, em)
      with layer_scope('model', f'{scope}/merge'):
        out[b] = out.get(b, 0) + agg
      counts[b] = counts.get(b, 0) + 1
    res = {}
    with layer_scope('model', f'{scope}/merge'):
      for nt, x in x_dict.items():
        if not rows_out(nt):
          continue
        h = out.get(nt)
        if h is not None and self.aggr == 'mean':
          h = h / counts[nt]
        if self.make_conv is not None and h is not None:
          # factory mode: conv output only (the conv's own self path
          # applies)
          res[nt] = h
          continue
        # the default mode's self term; in factory mode, what a type
        # no relation reaches passes through so that widths stay
        # consistent across layers
        self_term = nn.Dense(self.out_features, dtype=self.dtype,
                             name=f'lin_self_{nt}')(x[:rows_out(nt)])
        res[nt] = self_term if h is None else self_term + h
    return res


class RGCN(nn.Module):
  """Relational GCN stack — the reference's hetero workhorse
  (`examples/igbh/rgnn.py` RGCN/RSAGE flavor)."""
  etypes: Tuple[EdgeType, ...]
  hidden_features: int
  out_features: int
  num_layers: int = 2
  dropout: float = 0.0
  target_ntype: Optional[NodeType] = None
  dtype: Optional[jnp.dtype] = None

  @nn.compact
  def __call__(self, x_dict, edge_index_dict, edge_mask_dict=None, *,
               train: bool = False):
    h = x_dict
    for i in range(self.num_layers):
      last = i == self.num_layers - 1
      feats = self.out_features if last else self.hidden_features
      h = HeteroConv(self.etypes, feats, dtype=self.dtype,
                     part=f'layer{i}', name=f'conv{i}')(
                         h, edge_index_dict, edge_mask_dict)
      if not last:
        h = {nt: nn.relu(v) for nt, v in h.items()}
        if self.dropout > 0:
          h = {nt: nn.Dropout(self.dropout, deterministic=not train)(v)
               for nt, v in h.items()}
    if self.dtype is not None:
      h = {nt: v.astype(jnp.float32) for nt, v in h.items()}
    if self.target_ntype is not None:
      return h[self.target_ntype]
    return h


def typed_layer_extent(hop_capacities, hop: int):
  """``(rows in, rows out, edge slots)`` — per node type, per node
  type, per relation — of the layer whose outputs feed the nodes
  within ``hop`` hops of the seeds, from the layout a typed sampler
  states (`sampler.hetero_neighbor_sampler.typed_hop_capacities`): it
  writes rows ``[0, C_hop(b))`` of every target type from rows ``[0,
  C_{hop+1}(a))`` of the source types over each relation's edge blocks
  ``0..hop``.  A stack deeper than the sampler keeps whole tables in
  its first layers (`models.basic_gnn._layer_extent`)."""
  node_caps, edge_caps = (dict(c) for c in hop_capacities)
  hops = max((len(e) for e in edge_caps.values()), default=0)
  hop = min(hop, hops)
  return ({nt: c[min(hop + 1, hops)] for nt, c in node_caps.items()},
          {nt: c[hop] for nt, c in node_caps.items()},
          {et: e[min(hop, hops - 1)] for et, e in edge_caps.items()})


class RGAT(nn.Module):
  """Relational graph attention — the model of MLPerf Training's GNN
  benchmark (R-GAT on IGBH; reference `examples/igbh/rgnn.py`): per
  layer and relation ``(a, rel, b)`` a `GATConv` (its own projection
  and attention vectors, softmax over a target's in-edges *within the
  relation*), summed into the target type, ReLU after every layer, and
  a linear head on the target type's rows.

  Every node type's rows must have the same width (the relation's one
  projection serves both ends); the input goes into layer 0 as it is,
  cast to the compute dtype (float32 by default).  `make_conv` is the
  one thing a sibling stack overrides, with another in-edge-local
  conv (per-relation `SAGEConv`: `examples/igbh/train_rgnn.py`).

  ``hop_capacities`` — the static per-type, per-relation hop layout a
  typed `NeighborLoader` batch states (``metadata['hop_capacities']``,
  handed on by the step builders' seam, `models.train.apply_to_batch`)
  — trims the stack (its convs must be in-edge-local; another conv
  raises when asked for a trimmed relation): layer ``l`` of
  ``L`` reads rows ``[0, C_{L-l}(a))`` of each source type and edge
  slots ``[:E_{L-1-l}(r)]`` of each relation and writes rows ``[0,
  C_{L-1-l}(b))`` of each target type, so the result is
  ``[C_0(target), out]``, the seed rows (`typed_layer_extent`;
  `BasicGNN` says what holds of padded seed slots), with one
  ``model.trim`` flight-recorder event per trace.  Without it, and
  while initialising, every layer runs over whole tables and the
  result is ``[n_target, out]``.  The parameters are the same either
  way.

  ``hop_windows`` — the fanout windows of each relation's edge blocks
  (``metadata['hop_windows']``,
  `sampler.hetero_neighbor_sampler.typed_hop_windows`) — has every
  relation's conv aggregate by window over the blocks its layer keeps
  (a prefix of the relation's list), where it otherwise runs three
  segment operations over every edge slot (`models.conv.GATConv`): the
  same values to float32 round-off; the ``model.trim`` event lists per
  relation and layer the slots aggregated either way
  (``windowed_slots`` / ``scattered_slots``).
  """
  etypes: Tuple[EdgeType, ...]
  hidden_features: int
  out_features: int
  num_layers: int = 3
  heads: int = 4
  target_ntype: NodeType = 'paper'
  dtype: Optional[jnp.dtype] = None

  # `__call__` accepts ``hop_capacities``
  takes_hop_capacities = True
  # `make_conv`'s convs declare ``takes_windows`` (a sibling stack
  # whose conv does not says so here, and keeps the segment path)
  windowed_convs = True

  @nn.nowrap
  def make_conv(self) -> nn.Module:
    """One relation's conv; it is built inside that relation's scope
    of the layer's `HeteroConv` (hence ``nowrap``)."""
    assert self.hidden_features % self.heads == 0
    return GATConv(self.hidden_features // self.heads, heads=self.heads,
                   dtype=self.dtype)

  @nn.compact
  def __call__(self, x_dict, edge_index_dict, edge_mask_dict=None, *,
               hop_capacities=None, hop_windows=None):
    trim = hop_capacities is not None and not self.is_initializing()
    windows = None
    if (hop_windows is not None and self.windowed_convs
        and not self.is_initializing()):
      windows = dict(hop_windows)
    with layer_scope('model', 'input'):
      h = {nt: x.astype(self.dtype or jnp.float32)
           for nt, x in x_dict.items()}
    ei, em = dict(edge_index_dict), dict(edge_mask_dict or {})
    trimmed = []
    for i in range(self.num_layers):
      num_dst = None
      if trim:
        rows_in, num_dst, slots = typed_layer_extent(
            hop_capacities, self.num_layers - 1 - i)
        if windows is not None:
          # the blocks a trimmed layer keeps are a prefix of the list
          hop = self.num_layers - 1 - i
          windows = {et: w[:hop + 1] for et, w in windows.items()
                     if et in slots}
        trimmed.append((rows_in, num_dst, slots, set(windows or ())))
        with layer_scope('model', f'layer{i}/trim'):
          h = {nt: v[:rows_in[nt]] for nt, v in h.items()
               if rows_in.get(nt)}
          ei = {et: e[:, :slots[et]] for et, e in ei.items()
                if et in slots}
          em = {et: m[:slots[et]] for et, m in em.items() if et in slots}
      h = HeteroConv(self.etypes, self.hidden_features,
                     make_conv=self.make_conv, part=f'layer{i}',
                     name=f'conv{i}')(h, ei, em, num_dst, windows)
      with layer_scope('model', f'layer{i}/merge'):
        h = {nt: nn.relu(v) for nt, v in h.items()}
    if trimmed:
      # trace time: one event per compiled program that trims
      rows_in, rows_out, slots, by_window = zip(*trimmed)
      aggregated = lambda windowed: {
          as_str(et): [s[et] if (et in w) == windowed else 0
                       for s, w in zip(slots, by_window)]
          for et in slots[0]}
      recorder.emit(
          'model.trim', layers=len(trimmed),
          rows_in={nt: [r[nt] for r in rows_in] for nt in rows_in[0]},
          rows_out={nt: [r[nt] for r in rows_out] for nt in rows_out[0]},
          edge_slots={as_str(et): [s[et] for s in slots]
                      for et in slots[0]},
          windowed_slots=aggregated(True),
          scattered_slots=aggregated(False),
          table_rows={nt: c[-1] for nt, c in hop_capacities[0]},
          table_slots={as_str(et): e[-1] for et, e in hop_capacities[1]})
    with layer_scope('model', 'head'):
      out = nn.Dense(self.out_features, dtype=self.dtype,
                     name='head')(h[self.target_ntype])
    return out.astype(jnp.float32) if self.dtype is not None else out


class HGTConv(nn.Module):
  """Heterogeneous Graph Transformer convolution.

  Type-specific Q/K/V projections + per-edge-type relation transforms
  and priors, masked segment-softmax attention per target node — the
  model of reference `examples/hetero/train_hgt_mag.py:102-121`
  (there via PyG's HGTConv; re-designed here for padded batches).
  """
  ntypes: Tuple[NodeType, ...]
  etypes: Tuple[EdgeType, ...]
  out_features: int
  heads: int = 2
  dtype: Optional[jnp.dtype] = None

  @nn.compact
  def __call__(self, x_dict, edge_index_dict, edge_mask_dict=None):
    h, f = self.heads, self.out_features // self.heads
    assert self.out_features % self.heads == 0
    q_dict, k_dict, v_dict = {}, {}, {}
    for nt in self.ntypes:
      if nt not in x_dict:
        continue
      n = x_dict[nt].shape[0]
      q_dict[nt] = nn.Dense(h * f, dtype=self.dtype,
                           name=f'q_{nt}')(x_dict[nt]).reshape(
          n, h, f)
      k_dict[nt] = nn.Dense(h * f, dtype=self.dtype,
                           name=f'k_{nt}')(x_dict[nt]).reshape(
          n, h, f)
      v_dict[nt] = nn.Dense(h * f, dtype=self.dtype,
                           name=f'v_{nt}')(x_dict[nt]).reshape(
          n, h, f)

    # accumulate per-target-type attention numerators/denominators
    agg = {nt: 0.0 for nt in q_dict}
    den = {nt: 0.0 for nt in q_dict}
    for et in self.etypes:
      if et not in edge_index_dict:
        continue
      a, _, b = et
      if a not in k_dict or b not in q_dict:
        continue
      ei = edge_index_dict[et]
      em = (edge_mask_dict or {}).get(et)
      na, nb = k_dict[a].shape[0], q_dict[b].shape[0]
      src = jnp.clip(ei[0], 0, na - 1)
      dst = ei[1]
      valid = em if em is not None else (dst >= 0)
      dsafe = jnp.where(valid, dst, nb)
      w_att = self.param(f'w_att_{as_str(et)}',
                         nn.initializers.glorot_uniform(), (h, f, f))
      w_msg = self.param(f'w_msg_{as_str(et)}',
                         nn.initializers.glorot_uniform(), (h, f, f))
      prior = self.param(f'prior_{as_str(et)}', nn.initializers.ones, (h,))
      k = jnp.einsum('ehf,hfg->ehg', k_dict[a][src],
                     w_att.astype(k_dict[a].dtype))
      v = jnp.einsum('ehf,hfg->ehg', v_dict[a][src],
                     w_msg.astype(v_dict[a].dtype))
      q = q_dict[b][jnp.clip(dst, 0, nb - 1)]
      score = ((q * k).sum(-1).astype(jnp.float32)
               * prior[None, :] / jnp.sqrt(f))         # [E, h]
      score = jnp.where(valid[:, None], score, -jnp.inf)
      smax = jax.ops.segment_max(score, dsafe, num_segments=nb)
      smax = jnp.where(jnp.isfinite(smax), smax, 0.0)
      ex = jnp.where(valid[:, None],
                     jnp.exp(score - smax[jnp.clip(dst, 0, nb - 1)]), 0.0)
      num = jax.ops.segment_sum(
          (ex.astype(v.dtype)[:, :, None] * v).reshape(-1, h * f), dsafe,
          num_segments=nb).reshape(nb, h, f)
      agg[b] = agg[b] + num
      den[b] = den[b] + jax.ops.segment_sum(ex, dsafe, num_segments=nb)

    out = {}
    for nt in q_dict:
      n = x_dict[nt].shape[0]
      if isinstance(agg[nt], float):
        out[nt] = nn.Dense(self.out_features, dtype=self.dtype,
                           name=f'skip_{nt}')(x_dict[nt])
        continue
      att = agg[nt] / jnp.maximum(den[nt], 1e-16)[:, :, None]
      att = att.reshape(n, h * f)
      out[nt] = (nn.Dense(self.out_features, dtype=self.dtype,
                          name=f'out_{nt}')(nn.gelu(att))
          + nn.Dense(self.out_features, dtype=self.dtype,
                     name=f'skip_{nt}')(x_dict[nt]))
    return out


class HGT(nn.Module):
  """HGT stack with a final target-type head."""
  ntypes: Tuple[NodeType, ...]
  etypes: Tuple[EdgeType, ...]
  hidden_features: int
  out_features: int
  num_layers: int = 2
  heads: int = 2
  target_ntype: Optional[NodeType] = None
  dtype: Optional[jnp.dtype] = None

  @nn.compact
  def __call__(self, x_dict, edge_index_dict, edge_mask_dict=None, *,
               train: bool = False):
    h = {nt: nn.Dense(self.hidden_features, dtype=self.dtype,
                      name=f'in_{nt}')(x)
         for nt, x in x_dict.items()}
    for i in range(self.num_layers):
      h = HGTConv(self.ntypes, self.etypes, self.hidden_features,
                  self.heads, dtype=self.dtype, name=f'conv{i}')(
                      h, edge_index_dict, edge_mask_dict)
      h = {nt: nn.relu(v) for nt, v in h.items()}
    if self.target_ntype is not None:
      out = nn.Dense(self.out_features, dtype=self.dtype,
                     name='head')(h[self.target_ntype])
      return (out.astype(jnp.float32) if self.dtype is not None else out)
    out = {nt: nn.Dense(self.out_features, dtype=self.dtype,
                        name=f'head_{nt}')(v)
           for nt, v in h.items()}
    if self.dtype is not None:
      out = {nt: v.astype(jnp.float32) for nt, v in out.items()}
    return out
