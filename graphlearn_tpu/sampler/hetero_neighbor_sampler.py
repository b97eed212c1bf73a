"""Heterogeneous multi-hop neighbor sampling.

TPU-native re-design of the reference's hetero path
(`sampler/neighbor_sampler.py:192-253`: per-hop per-edge-type lazy CUDA
samplers + per-node-type hetero inducer, `csrc/cuda/inducer.cu:149+`)
as ONE jitted XLA program per static config.

Semantics (matching the reference's contract):
  * Each stored edge type ``(src, rel, dst)`` is sampled *from* nodes
    of type ``src``, discovering neighbors of type ``dst`` with that
    type's per-hop fanout.
  * Node tables are per node type, deduplicated across hops in
    first-occurrence order (seeds of the input type occupy ``0..B-1``).
  * Sampled edges are emitted under the REVERSED edge type
    (`reverse_edge_type`, reference `:236-243`) with transposed
    direction — ``edge_index[0]`` = neighbor-side (``dst``-type local
    id), ``edge_index[1]`` = seed-side (``src``-type local id) — so
    messages flow discovered→seed for PyG-style aggregation, exactly
    like the homogeneous transposed emission.
  * Hop ``h`` frontier of a node type = the nodes first discovered at
    hop ``h-1`` (static table windows masked by dynamic counts).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.graph import Graph
from ..ops.neighbor import sample_one_hop
from ..ops.unique import emit_dedup, init_node, induce_next
from ..typing import EdgeType, NodeType, as_str, reverse_edge_type
from ..utils.padding import INVALID_ID, round_up
from ..utils.profiling import layer_scope
from .base import (BaseSampler, HeteroSamplerOutput, NodeSamplerInput)


def normalize_fanouts(etypes: Tuple[EdgeType, ...], num_neighbors):
  """Resolve ``num_neighbors`` (shared list or per-etype dict) into
  ``(etypes, fanouts, num_hops)`` — etypes absent from a dict spec
  don't participate.  Shared by the single-host and distributed hetero
  samplers."""
  if isinstance(num_neighbors, dict):
    fanouts = {et: tuple(int(k) for k in num_neighbors[et])
               for et in etypes if et in num_neighbors}
    etypes = tuple(et for et in etypes if et in fanouts)
  else:
    fan = tuple(int(k) for k in num_neighbors)
    fanouts = {et: fan for et in etypes}
  num_hops = max((len(f) for f in fanouts.values()), default=0)
  return etypes, fanouts, num_hops


def _plan(
    etypes: Sequence[EdgeType],
    fanouts: Dict[EdgeType, Tuple[int, ...]],
    input_sizes: Dict[NodeType, int],
    num_hops: int,
    num_nodes: Dict[NodeType, int],
):
  """Host-side static-shape plan.

  Returns per-ntype table capacities, per-(hop, ntype) frontier
  capacities, per-(hop, etype) edge capacities — the hetero analog
  of the reference's `_max_sampled_nodes` bound
  (`sampler/neighbor_sampler.py:595-612`) — and per (hop, ntype) the
  table capacity after that hop (entry 0: the seeds).  ``input_sizes``
  gives the seed count per seeded node type (link sampling seeds two
  types).
  """
  ntypes = sorted({t for (s, _, d) in etypes for t in (s, d)}
                  | set(input_sizes))
  frontier = {nt: int(input_sizes.get(nt, 0)) for nt in ntypes}
  frontier_caps = [dict(frontier)]
  table_cap = {nt: frontier[nt] for nt in ntypes}
  hop_table_caps = [dict(table_cap)]
  edge_caps: List[Dict[EdgeType, int]] = []
  for h in range(num_hops):
    add = {nt: 0 for nt in ntypes}
    ecap: Dict[EdgeType, int] = {}
    for et in etypes:
      s, _, d = et
      k = fanouts[et][h] if h < len(fanouts[et]) else 0
      if k <= 0 or frontier[s] == 0:
        continue
      ecap[et] = frontier[s] * k
      add[d] += frontier[s] * k
    frontier = {nt: min(add[nt], num_nodes.get(nt, add[nt]))
                for nt in ntypes}
    frontier_caps.append(dict(frontier))
    for nt in ntypes:
      table_cap[nt] = min(table_cap[nt] + add[nt],
                          input_sizes.get(nt, 0)
                          + num_nodes.get(nt, 1 << 60))
    hop_table_caps.append(dict(table_cap))
    edge_caps.append(ecap)
  table_cap = {nt: round_up(max(c, 1), 8) for nt, c in table_cap.items()}
  return ntypes, table_cap, frontier_caps, edge_caps, hop_table_caps


def _plan_capacities(etypes, fanouts, input_sizes, num_hops, num_nodes):
  """`_plan`'s ``(ntypes, table_cap, frontier_caps, edge_caps)``: what
  the jitted loops read."""
  return _plan(etypes, fanouts, input_sizes, num_hops, num_nodes)[:4]


def typed_hop_capacities(etypes, plan):
  """The static layout of one `_hetero_multihop` output, the typed
  twin of `sampler.neighbor_sampler.hop_capacities`: ``(((node type,
  (C_0..C_L)), ...), ((emitted edge type, (E_0..E_{L-1})), ...))``,
  cumulative table and edge-slot capacities per hop, sorted by type
  (hashable: a batch carries it as pytree aux data).

  Hop ``h`` samples relation ``(s, rel, d)`` from the nodes of ``s``
  found by hop ``h`` (table slots below ``C_h(s)``), appends what it
  finds to ``d``'s table (``count <= C_{h+1}(d)``) and emits its edges,
  reversed, as block ``[E_{h-1}, E_h)`` of the relation's ``row`` /
  ``col``; a relation not sampled at a hop has an empty block there.
  So of the emitted relation ``(d, rev_rel, s)`` everything within
  ``h`` hops of the seeds lives in edge slots ``[:E_h]``, with targets
  below ``C_h(s)`` and sources below ``C_{h+1}(d)`` (what
  `models.hetero.RGAT` trims its layers to).  ``C_L`` is a table's
  shape, ``E_{L-1}`` the shape of a relation's ``row``.  ``plan`` is
  `_plan`'s result, the one plan the jitted loop reads.  Within a
  block the slots lie by target, window by window: `typed_hop_windows`
  states that half of the layout.
  """
  _, table_cap, _, edge_caps, hop_table_caps = plan
  node = tuple(
      (nt, tuple(min(c[nt], cap) for c in hop_table_caps[:-1]) + (cap,))
      for nt, cap in sorted(table_cap.items()))
  edge = []
  for et in etypes:
    slots = np.cumsum([ec.get(et, 0) for ec in edge_caps]).tolist()
    if slots and slots[-1]:
      edge.append((reverse_edge_type(et), tuple(slots)))
  return node, tuple(sorted(edge))


def typed_hop_windows(etypes, fanouts, plan):
  """The fanout windows of one `_hetero_multihop` output, beside
  `typed_hop_capacities` and in its order: ``((emitted edge type,
  ((F_0, k_0), .., (F_{L-1}, k_{L-1}))), ...)``.  Block ``h`` of the
  emitted relation ``(d, rev_rel, s)`` is ``[F_h, k_h]`` flattened:
  ``F_h`` the frontier capacity of ``s`` at hop ``h``, ``k_h`` the
  relation's fanout there, 0 for a hop at which it is not sampled (an
  empty block).

  The contract is `sampler.neighbor_sampler.hop_windows`', per
  relation: slot ``(i, j)`` of block ``h`` has target ``col ==
  start_h + i`` or is masked (-1), ``start_h`` being the count of
  ``s``'s table when hop ``h - 1`` began (0 for the first hop), so
  ``start_h <= F_0 + .. + F_{h-1}``; a node is in its type's frontier
  once, so within a relation all of a target's in-edges are one window
  of ``k_h`` consecutive slots.
  """
  _, _, frontier_caps, edge_caps, _ = plan
  out = []
  for et in etypes:
    if not any(ec.get(et, 0) for ec in edge_caps):
      continue
    s = et[0]
    out.append((reverse_edge_type(et), tuple(
        (fc.get(s, 0), fanouts[et][h] if et in ec else 0)
        for h, (fc, ec) in enumerate(zip(frontier_caps, edge_caps)))))
  return tuple(sorted(out))


@functools.partial(
    jax.jit,
    static_argnames=('etypes', 'fanouts_t', 'seed_types', 'num_hops',
                     'table_caps', 'frontier_caps_t', 'with_edge',
                     'sort_locality'))
def _hetero_multihop(
    graphs,           # dict etype -> (indptr, indices, edge_ids|None)
    seeds_t: Tuple[jax.Array, ...],   # aligned with seed_types
    key: jax.Array,
    *,
    etypes: Tuple[EdgeType, ...],
    fanouts_t: Tuple[Tuple[int, ...], ...],   # aligned with etypes
    seed_types: Tuple[NodeType, ...],
    num_hops: int,
    table_caps: Tuple[Tuple[NodeType, int], ...],
    frontier_caps_t: Tuple[Tuple[Tuple[NodeType, int], ...], ...],
    with_edge: bool,
    sort_locality: bool = True,
):
  """One fused typed multi-hop sample. Returns raw pytree pieces.

  Every node type's table GROWS insertion by insertion: it starts at
  its seed count (empty for a type without seeds) and the insertion of
  relation ``(s, rel, d)`` at hop ``h`` hands `induce_next` the rows
  ``d`` could hold so far and asks for ``min(rows + frontier_caps[h][s]
  * k, table_caps[d])`` back, so a dedup sorts what is there plus its
  candidates and never the padding of later hops (`hop0/cites` of the
  IGBH shapes: 32 + 480 elements, not 134,912 + 480).  `pack` pads
  every table to ``table_caps``; ids, counts and local indices are
  those of tables held at that size from the start.  The relation
  order (``etypes``) and the key schedule are part of the output; a
  `sample.dedup` event lists the insertions at trace time.
  """
  caps = dict(table_caps)
  fanouts = dict(zip(etypes, fanouts_t))
  frontier_caps = [dict(fc) for fc in frontier_caps_t]
  ntypes = list(caps.keys())

  # per-ntype inducer state; seeded types (one for node sampling, the
  # two endpoint types for link sampling) start with their seed sets.
  states = {}
  seed_locals = {}
  seed_by_type = dict(zip(seed_types, seeds_t))
  with layer_scope('sample', 'dedup'):
    for nt in ntypes:
      seeds = seed_by_type.get(nt, jnp.zeros((0,), jnp.int32))
      states[nt], seed_local = init_node(seeds, seeds.shape[0])
      if nt in seed_by_type:
        seed_locals[nt] = seed_local
  dedups = []   # (name, sorted, table_rows, candidates) per insertion

  # frontier windows: (start, cap) per ntype.
  fr_start = {nt: jnp.zeros((), jnp.int32) for nt in ntypes}

  rows_acc = {et: [] for et in etypes}
  cols_acc = {et: [] for et in etypes}
  eids_acc = {et: [] for et in etypes}
  nsn = {nt: [states[nt].count] for nt in ntypes}

  for h in range(num_hops):
    # Snapshot hop-start state: frontiers are nodes discovered at h-1.
    hop_start_count = {nt: states[nt].count for nt in ntypes}
    frontiers = {}
    with layer_scope('sample', f'hop{h}/frontier'):
      for nt in ntypes:
        fcap = frontier_caps[h].get(nt, 0)
        if fcap <= 0:
          frontiers[nt] = None
          continue
        slots = fr_start[nt] + jnp.arange(fcap, dtype=jnp.int32)
        valid = slots < hop_start_count[nt]
        nodes = states[nt].nodes[
            jnp.clip(slots, 0, states[nt].nodes.shape[0] - 1)]
        frontiers[nt] = (jnp.where(valid, nodes, INVALID_ID),
                         jnp.where(valid, slots, -1))

    for ei, et in enumerate(etypes):
      s, _, d = et
      k = fanouts[et][h] if h < len(fanouts[et]) else 0
      if k <= 0 or frontiers.get(s) is None:
        continue
      fr_nodes, fr_local = frontiers[s]
      indptr, indices, edge_ids = graphs[et]
      # one relation's draw and its dedup into the found type's table
      with layer_scope('sample', f'hop{h}/{as_str(et)}'):
        hop_key = jax.random.fold_in(jax.random.fold_in(key, h), ei)
        res = sample_one_hop(indptr, indices, fr_nodes, int(k), hop_key,
                             edge_ids, with_edge_ids=with_edge,
                             sort_locality=sort_locality)
        held, cands = states[d].nodes.shape[0], fr_nodes.shape[0] * int(k)
        grown = min(held + cands, caps[d])
        dedups.append((f'hop{h}/{as_str(et)}', held + cands, grown, cands))
        states[d], rows, cols, _ = induce_next(
            states[d], fr_local, res.nbrs, res.mask, capacity=grown)
        rows_acc[et].append(rows)
        cols_acc[et].append(cols)
        if with_edge:
          eids_acc[et].append(
              jnp.where(rows >= 0, res.eids.reshape(-1), INVALID_ID))

    for nt in ntypes:
      fr_start[nt] = hop_start_count[nt]
      nsn[nt].append(states[nt].count)

  emit_dedup(dedups)
  with layer_scope('sample', 'pack'):
    # consumers expect every table at its planned shape
    node = {nt: jnp.concatenate([
        states[nt].nodes,
        jnp.full((caps[nt] - states[nt].nodes.shape[0],), INVALID_ID,
                 states[nt].nodes.dtype)]) for nt in ntypes}
    node_count = {nt: states[nt].count for nt in ntypes}
    # Emit under reversed etypes with transposed direction.
    row_out, col_out, eid_out, emask_out = {}, {}, {}, {}
    for et in etypes:
      if not rows_acc[et]:
        continue
      rev = reverse_edge_type(et)
      r = jnp.concatenate(rows_acc[et])
      c = jnp.concatenate(cols_acc[et])
      row_out[rev] = r
      col_out[rev] = c
      emask_out[rev] = r >= 0
      if with_edge:
        eid_out[rev] = jnp.concatenate(eids_acc[et])
    num_sampled_nodes = {
        nt: jnp.concatenate([jnp.stack(v)[:1],
                             jnp.stack(v)[1:] - jnp.stack(v)[:-1]])
        for nt, v in nsn.items()}
  return (node, node_count, row_out, col_out,
          eid_out if with_edge else None, emask_out, seed_locals,
          num_sampled_nodes)


class HeteroNeighborSampler(BaseSampler):
  """Uniform hetero multi-hop sampler over a dict of device graphs.

  Args:
    graphs: ``{EdgeType: Graph}`` (sampling direction src→dst).
    num_neighbors: per-hop fanouts — list (shared by all etypes) or
      ``{EdgeType: list}``.
    num_nodes: optional per-ntype node counts for tighter capacity
      planning (defaults derived from topologies).
  """

  def __init__(self, graphs: Dict[EdgeType, Graph], num_neighbors,
               device=None, with_edge: bool = False,
               num_nodes: Optional[Dict[NodeType, int]] = None,
               seed: int = 0, sort_locality: bool = True):
    self.sort_locality = bool(sort_locality)
    self.graphs = dict(graphs)
    self.etypes, self.fanouts, self.num_hops = normalize_fanouts(
        tuple(sorted(self.graphs.keys())), num_neighbors)
    self.with_edge = with_edge
    self.device = device
    self._num_nodes = dict(num_nodes or {})
    for (s, _, d), g in self.graphs.items():
      self._num_nodes[s] = max(self._num_nodes.get(s, 0), g.num_nodes)
      dmax = int(g.csr_topo.indices.max(initial=-1)) + 1
      self._num_nodes[d] = max(self._num_nodes.get(d, 0), dmax)
    self._base_key = jax.random.key(seed)
    self._step = 0
    self._plans = {}

  def _next_key(self) -> jax.Array:
    self._step += 1
    return jax.random.fold_in(self._base_key, self._step)

  def _planned(self, input_sizes: Dict[NodeType, int]):
    """`_plan` for these seed counts, made once per batch shape (a
    loader asks for the same one every step)."""
    key = tuple(sorted(input_sizes.items()))
    if key not in self._plans:
      self._plans[key] = _plan(self.etypes, self.fanouts, input_sizes,
                               self.num_hops, self._num_nodes)
    return self._plans[key]

  def _run_multihop(self, seeds_by_type: Dict[NodeType, jax.Array]):
    """One fused hetero multi-hop from per-type seed sets; returns the
    raw pieces plus per-type seed-local maps."""
    input_sizes = {nt: int(s.shape[0]) for nt, s in seeds_by_type.items()}
    ntypes, table_cap, frontier_caps = self._planned(input_sizes)[:3]
    graphs = {}
    for et in self.etypes:
      g = self.graphs[et]
      graphs[et] = (g.indptr, g.indices,
                    g.edge_ids if self.with_edge else None)
    seed_types = tuple(sorted(seeds_by_type))
    return _hetero_multihop(
        graphs, tuple(seeds_by_type[nt] for nt in seed_types),
        self._next_key(),
        etypes=self.etypes,
        fanouts_t=tuple(self.fanouts[et] for et in self.etypes),
        seed_types=seed_types,
        num_hops=self.num_hops,
        table_caps=tuple(sorted(table_cap.items())),
        frontier_caps_t=tuple(
            tuple(sorted(fc.items())) for fc in frontier_caps),
        with_edge=self.with_edge, sort_locality=self.sort_locality)

  def sample_from_nodes(self, inputs: NodeSamplerInput,
                        **kwargs) -> HeteroSamplerOutput:
    input_type = inputs.input_type
    assert input_type is not None, 'hetero sampling needs input_type'
    seeds = jnp.asarray(np.asarray(inputs.node, dtype=np.int32))
    (node, node_count, row, col, eid, emask, seed_locals,
     nsn) = self._run_multihop({input_type: seeds})
    # the plan the multi-hop program was built from
    plan = self._planned({input_type: int(seeds.shape[0])})
    return HeteroSamplerOutput(
        node=node, node_count=node_count, row=row, col=col, edge=eid,
        edge_mask=emask, batch={input_type: seeds},
        num_sampled_nodes=nsn,
        edge_types=[reverse_edge_type(et) for et in self.etypes],
        # static ints, the same for every batch of a loader:
        # `HeteroBatch` carries them as pytree aux data, not as arrays
        metadata={'seed_local': seed_locals[input_type],
                  'input_type': input_type,
                  'hop_capacities': typed_hop_capacities(self.etypes,
                                                         plan),
                  'hop_windows': typed_hop_windows(
                      self.etypes, self.fanouts, plan)})

  def sample_from_edges(self, inputs, neg_sampling=None,
                        **kwargs) -> HeteroSamplerOutput:
    """Hetero link-prediction sampling.

    Counterpart of the reference's hetero ``sample_from_edges``
    (`sampler/neighbor_sampler.py:255-381`): seed edges of one edge
    type; endpoints (+ sampled negatives of the dst type) seed their
    respective node-type tables, multi-hop expand, and the metadata
    carries PyG's link-label indices *per endpoint type*:
    ``edge_label_index[0]`` indexes the src-type table,
    ``edge_label_index[1]`` the dst-type table.
    """
    from ..ops.negative import sample_negative
    from .base import NegativeSampling
    from .neighbor_sampler import _triplet_neg_dst

    et = inputs.input_type
    assert et is not None, 'hetero link sampling needs input_type=etype'
    assert et in self.graphs, f'unknown edge type {et}'
    s_t, _, d_t = et
    neg = neg_sampling or inputs.neg_sampling
    neg = NegativeSampling.cast(neg)
    src = jnp.asarray(np.asarray(inputs.row, dtype=np.int32))
    dst = jnp.asarray(np.asarray(inputs.col, dtype=np.int32))
    b = src.shape[0]
    pair_valid = (src >= 0) & (dst >= 0)
    g = self.graphs[et]
    key = self._next_key()

    if neg is not None and neg.is_binary():
      num_neg = neg.sample_size(b)
      nres = sample_negative(g.indptr, g.indices, num_neg, key,
                             strict=True, padding=True,
                             num_cols=self._num_nodes[d_t])
      src_seeds = jnp.concatenate([src, nres.rows])
      dst_seeds = jnp.concatenate([dst, nres.cols])
    elif neg is not None:        # triplet
      amount = int(np.ceil(float(neg.amount)))
      num_neg = b * amount
      neg_dst = _triplet_neg_dst(g.indptr, g.indices, src, key,
                                 amount=amount,
                                 num_nodes=self._num_nodes[d_t])
      src_seeds = src
      dst_seeds = jnp.concatenate([dst, neg_dst.reshape(-1)])
    else:
      num_neg = 0
      src_seeds, dst_seeds = src, dst

    if s_t == d_t:
      seeds_by_type = {s_t: jnp.concatenate([src_seeds, dst_seeds])}
    else:
      seeds_by_type = {s_t: src_seeds, d_t: dst_seeds}
    (node, node_count, row, col, eid, emask, seed_locals,
     nsn) = self._run_multihop(seeds_by_type)
    if s_t == d_t:
      ns = src_seeds.shape[0]
      sl_src = seed_locals[s_t][:ns]
      sl_dst = seed_locals[s_t][ns:]
    else:
      sl_src = seed_locals[s_t]
      sl_dst = seed_locals[d_t]

    if neg is not None and neg.is_binary():
      pos_label = (jnp.asarray(np.asarray(inputs.label))
                   if inputs.label is not None
                   else jnp.ones((b,), jnp.int32))
      metadata = {
          'edge_label_index': jnp.stack([sl_src, sl_dst]),
          'edge_label': jnp.concatenate(
              [pos_label, jnp.zeros((num_neg,), pos_label.dtype)]),
          'edge_label_mask': jnp.concatenate(
              [pair_valid, jnp.ones((num_neg,), jnp.bool_)]),
      }
    elif neg is not None:
      metadata = {
          'src_index': sl_src,
          'dst_pos_index': sl_dst[:b],
          'dst_neg_index': sl_dst[b:].reshape(b, -1),
          'pair_mask': pair_valid,
      }
    else:
      pos_label = (jnp.asarray(np.asarray(inputs.label))
                   if inputs.label is not None
                   else jnp.ones((b,), jnp.int32))
      metadata = {
          'edge_label_index': jnp.stack([sl_src, sl_dst]),
          'edge_label': pos_label,
          'edge_label_mask': pair_valid,
      }
    metadata['input_type'] = et
    # seed_local aligns 1:1 with `batch` (the POSITIVE endpoints only),
    # matching the node-loader pattern consumers rely on; negatives'
    # locals live in edge_label_index / dst_neg_index.
    if s_t == d_t:
      batch = {s_t: jnp.concatenate([src, dst])}
      metadata['seed_local'] = {
          s_t: jnp.concatenate([sl_src[:b], sl_dst[:b]])}
    else:
      batch = {s_t: src, d_t: dst}
      metadata['seed_local'] = {s_t: sl_src[:b], d_t: sl_dst[:b]}
    return HeteroSamplerOutput(
        node=node, node_count=node_count, row=row, col=col, edge=eid,
        edge_mask=emask, batch=batch,
        num_sampled_nodes=nsn,
        edge_types=[reverse_edge_type(e) for e in self.etypes],
        metadata=metadata)
