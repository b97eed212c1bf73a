"""Multi-hop neighbor sampling engine.

TPU-native re-design of the reference `sampler/neighbor_sampler.py`
(:37-627) — the class that fuses per-hop uniform sampling
(`csrc/cuda/random_sampler.cu`), dedup/relabel (`csrc/cuda/inducer.cu`)
and negative sampling into PyG-shaped `SamplerOutput`s.

Design notes (vs the reference):
  * The whole multi-hop loop is ONE jitted XLA program per static
    config ``(batch_size, fanouts, with_edge)``; hop results are
    accumulated with static capacities (`utils.padding.
    max_sampled_nodes` — the same bound the reference computes at
    `sampler/neighbor_sampler.py:595-612` to size its inducer).
  * Each hop samples the *frontier of newly discovered unique nodes*
    (exactly the reference's ``InduceNext`` contract) — frontier slots
    are a static window over the accumulated node table, masked by the
    dynamic node count.
  * Edges are emitted transposed (row=neighbor, col=seed-side) for PyG
    message passing, matching `sampler/neighbor_sampler.py:159-166`.
  * Randomness: `jax.random` threefry keys folded per call — counter
    based like curand Philox, reproducible across hosts.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..data.graph import Graph
from ..ops.neighbor import sample_one_hop, cal_nbr_prob
from ..ops.pallas_sample import fused_sample_enabled, sample_one_hop_auto
from ..ops.pallas_window import prepare_window_table
from ..ops.negative import edge_in_csr, sample_negative
from ..ops.subgraph import induced_subgraph
from ..ops.unique import InducerState, emit_dedup, induce_next, init_node
from ..telemetry.recorder import recorder
from ..utils.padding import INVALID_ID, max_sampled_nodes, round_up
from ..utils.profiling import layer_scope
from .base import (BaseSampler, EdgeSamplerInput, NegativeSampling,
                   NodeSamplerInput, SamplerOutput)


def hop_capacities(batch_size: int, fanouts: Sequence[int],
                   node_cap: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
  """The static layout of one `_multihop_sample` output:
  ``((C_0..C_L), (E_0..E_{L-1}))``, cumulative node and edge-slot
  capacities per hop.

  Hop ``h`` appends its new nodes to table slots ``[count_{h-1},
  count_h)`` with ``count_h <= C_h``, and emits its edges as block
  ``[E_{h-1}, E_h)`` of ``row``/``col``: targets are that hop's
  frontier (``col < C_h``), sources anything found so far
  (``row < C_{h+1}``), and a node's sampled in-edges all sit in the
  block of the hop that discovered it.  So everything within ``h``
  hops of the seeds lives in the prefixes ``[0, C_h)`` / ``[:E_h]``
  (what `models.BasicGNN` trims its layers to).  ``C_L`` is the
  table's shape ``node_cap``, ``E_{L-1}`` the shape of ``row``.
  Within a block the slots lie by target, window by window:
  `hop_windows` states that half of the layout.
  """
  cap, edges = min(int(batch_size), node_cap), 0
  node_caps, edge_caps = [cap], []
  for f, k in hop_windows(batch_size, fanouts):
    edges += f * k
    cap = min(cap + f * k, node_cap)
    node_caps.append(cap)
    edge_caps.append(edges)
  if edge_caps:
    node_caps[-1] = node_cap
  return tuple(node_caps), tuple(edge_caps)


def hop_windows(batch_size: int, fanouts: Sequence[int]
                ) -> Tuple[Tuple[int, int], ...]:
  """The fanout windows of one `_multihop_sample` output, beside
  `hop_capacities`: ``((F_0, k_0), .., (F_{L-1}, k_{L-1}))``, edge
  block ``h`` being ``[F_h, k_h]`` flattened (``F_h * k_h = E_h -
  E_{h-1}`` slots, ``F_0`` the batch size, ``F_{h+1} = F_h * k_h``).

  The contract: slot ``(i, j)`` of block ``h`` has target ``col ==
  start_h + i`` or is masked (-1), where ``start_h`` is the table's
  count when hop ``h - 1`` began (0 for the first hop) — hop ``h``
  samples the frontier ``[start_h, start_h + F_h)``, window ``i`` holds
  the ``k_h`` draws of frontier node ``i``, and a node is in a frontier
  once.  So all of a target's in-edges are one window of ``k_h``
  consecutive slots, consecutive windows are consecutive target rows,
  the windows past the frontier's valid end are wholly masked, and
  ``start_h <= F_0 + .. + F_{h-1}`` (what `models.conv` aggregates by
  in place of a scatter over the edge slots).
  """
  f, windows = int(batch_size), []
  for k in fanouts:
    windows.append((f, int(k)))
    f *= int(k)
  return tuple(windows)


@functools.partial(
    jax.jit,
    static_argnames=('fanouts', 'node_cap', 'with_edge', 'sort_locality',
                     'use_fused', 'win_e'))
def _multihop_sample(
    indptr: jax.Array,
    indices: jax.Array,
    edge_ids: Optional[jax.Array],
    seeds: jax.Array,
    key: jax.Array,
    win_table: Optional[jax.Array] = None,
    *,
    fanouts: Tuple[int, ...],
    node_cap: int,
    with_edge: bool,
    sort_locality: bool = True,
    use_fused: bool = False,
    win_e: int = 0,
):
  """One fused multi-hop sample. Returns raw pytree pieces.

  seeds: ``[B]`` global ids, INVALID_ID-padded.
  """
  b = seeds.shape[0]
  # The node table GROWS hop by hop instead of starting at the final
  # bound: `induce_next` sorts (rows of the table handed in + B*k)
  # elements, so a hop is handed the table at the capacity the hops
  # before it could fill (`hop_capacities`, static) and asks for the
  # next one back.  Its sort then covers every live element and none
  # of the padding the hop is about to need: hop 2 of the flagship
  # (batch 1024, fanout [15, 10, 5]) sorts 169,984 + 768,000 and not
  # 937,984 + 768,000.  A `sample.dedup` event says so at trace time.
  node_caps, _ = hop_capacities(b, fanouts, node_cap)
  cap = node_caps[0]
  with layer_scope('sample', 'dedup'):
    state, seed_local = init_node(seeds, cap)

    # hop-0 frontier: the deduped seeds occupy table slots [0, count).
    f_cap = b
    slots = jnp.arange(f_cap, dtype=jnp.int32)
    fr_valid = slots < state.count
    frontier = jnp.where(fr_valid,
                         state.nodes[jnp.clip(slots, 0, cap - 1)],
                         INVALID_ID)
    frontier_local = jnp.where(fr_valid, slots, -1)

  rows_acc, cols_acc, eids_acc = [], [], []
  hop_node_counts = [state.count]
  hop_edge_counts = []
  dedups = []   # (name, sorted, table_rows, candidates) per insertion

  for i, k in enumerate(fanouts):
    # dispatch resolves at trace time: use_fused is a static arg, so
    # flipping GLT_PALLAS_SAMPLE recompiles onto the Pallas kernel
    # (value-identical draws either way — see ops/pallas_sample.py)
    with layer_scope('sample', f'hop{i}'):
      hop_key = jax.random.fold_in(key, i)
      res = sample_one_hop_auto(
          indptr, indices, frontier, int(k), hop_key, edge_ids,
          with_edge_ids=with_edge, sort_locality=sort_locality,
          table=((win_table, win_e) if win_table is not None else None),
          use_fused=use_fused)
    with layer_scope('sample', 'dedup'):
      f_cap = f_cap * int(k)        # this hop's candidates, B*k
      dedups.append((f'hop{i}', cap + f_cap, node_caps[i + 1], f_cap))
      cap = node_caps[i + 1]
      state, rows, cols, prev_cnt = induce_next(
          state, frontier_local, res.nbrs, res.mask, capacity=cap)
      rows_acc.append(rows)
      cols_acc.append(cols)
      if with_edge:
        eids_acc.append(jnp.where(rows >= 0, res.eids.reshape(-1),
                                  INVALID_ID))
      hop_node_counts.append(state.count)
      hop_edge_counts.append(jnp.sum(rows >= 0))

      # next frontier = nodes appended this hop: table slots
      # [prev, count).
      slots = prev_cnt + jnp.arange(f_cap, dtype=jnp.int32)
      fr_valid = slots < state.count
      frontier = jnp.where(
          fr_valid, state.nodes[jnp.clip(slots, 0, cap - 1)], INVALID_ID)
      frontier_local = jnp.where(fr_valid, slots, -1)

  emit_dedup(dedups)
  with layer_scope('sample', 'pack'):
    if cap < node_cap:
      # consumers expect the [node_cap] table shape
      state = InducerState(
          nodes=jnp.concatenate([
              state.nodes,
              jnp.full((node_cap - cap,), INVALID_ID, state.nodes.dtype)]),
          count=state.count)

    row = (jnp.concatenate(rows_acc) if rows_acc
           else jnp.zeros((0,), jnp.int32))
    col = (jnp.concatenate(cols_acc) if cols_acc
           else jnp.zeros((0,), jnp.int32))
    edge = jnp.concatenate(eids_acc) if (with_edge and eids_acc) else None
    # cumulative -> per-hop new-node counts.
    cum = jnp.stack(hop_node_counts)
    num_sampled_nodes = jnp.concatenate(
        [cum[:1], cum[1:] - cum[:-1]]).astype(jnp.int32)
    num_sampled_edges = (jnp.stack(hop_edge_counts).astype(jnp.int32)
                         if hop_edge_counts
                         else jnp.zeros((0,), jnp.int32))
    return (state.nodes, state.count, row, col, edge, row >= 0, seed_local,
            num_sampled_nodes, num_sampled_edges)


#: redraws per strict-negative slot, binary (`ops.negative.sample_negative`)
#: and triplet (`_triplet_neg_dst`) alike
NEG_TRIALS = 5


@functools.partial(jax.jit, static_argnames=('amount', 'num_nodes'))
def _triplet_neg_dst(indptr: jax.Array, indices: jax.Array, src: jax.Array,
                     key: jax.Array, *, amount: int, num_nodes: int
                     ) -> jax.Array:
  """Per-source negative destinations with strict rejection (up to 5
  trials), the vectorized analog of the curand retry loop
  (`csrc/cuda/random_negative_sampler.cu:56-94`)."""
  b = src.shape[0]
  trials = NEG_TRIALS
  cand = jax.random.randint(key, (trials, b * amount), 0, num_nodes,
                            dtype=jnp.int32)
  rows = jnp.tile(jnp.repeat(src, amount)[None, :], (trials, 1))
  exists = edge_in_csr(indptr, indices, rows.reshape(-1), cand.reshape(-1))
  ok = ~exists.reshape(trials, b * amount)
  pick = jnp.where(jnp.any(ok, axis=0), jnp.argmax(ok, axis=0), trials - 1)
  out = cand[pick, jnp.arange(b * amount)]
  return out.reshape(b, amount)


def link_plan(neg: NegativeSampling, batch_size: int):
  """``(mode, num_neg, amount, seed_width)`` of a link batch of
  ``batch_size`` seed edges: binary draws ``num_neg`` pairs (two
  endpoints each), triplet ``amount`` destinations per seed edge
  (``num_neg = batch_size * amount`` endpoints); the seeds are ``[src,
  dst, negatives]``, ``seed_width`` of them."""
  b = int(batch_size)
  if neg.is_binary():
    num_neg = neg.sample_size(b)
    return 'binary', num_neg, 0, 2 * b + 2 * num_neg
  amount = int(np.ceil(float(neg.amount)))
  return 'triplet', b * amount, amount, 2 * b + b * amount


def link_seeds(indptr: jax.Array, indices: jax.Array, src: jax.Array,
               dst: jax.Array, key: jax.Array, *, mode: str, num_neg: int,
               amount: int, num_nodes: int, layout) -> jax.Array:
  """The seeds of one link batch, ``[src, dst, negatives]``: the strict
  negative draw (binary pairs by `sample_negative`, triplet
  destinations by `_triplet_neg_dst`) under ``glt.sample/negative``.
  ONE definition for `NeighborSampler.sample_from_edges` and
  `loader.fused.FusedLinkEpoch`.  ``layout`` is ``(hop_capacities,
  hop_windows)`` of the expansion the seeds go on to.

  Where it is traced (a compiled program: the fused epochs' scan
  bodies, `_link_seeds`), its flight-recorder events are the program's
  record, once per compile: ``sample.negative`` — ``mode``,
  ``req_num``, ``trials``, ``strict``, ``padding``, ``seed_width`` —
  and ``link.batch`` — the hop capacities and windows the batch
  states and ``negative_endpoints``, the seeds that are negatives'."""
  with layer_scope('sample', 'negative'):
    if mode == 'binary':
      nres = sample_negative(indptr, indices, num_neg, key,
                             trials=NEG_TRIALS, strict=True, padding=True)
      negs = jnp.concatenate([nres.rows, nres.cols])
    else:
      negs = _triplet_neg_dst(indptr, indices, src, key, amount=amount,
                              num_nodes=num_nodes).reshape(-1)
  seeds = jnp.concatenate([src, dst, negs])
  recorder.emit('sample.negative', mode=mode, req_num=num_neg,
                trials=NEG_TRIALS, strict=True, padding=True,
                seed_width=int(seeds.shape[0]))
  recorder.emit('link.batch', mode=mode, batch=int(src.shape[0]),
                seed_width=int(seeds.shape[0]),
                negative_endpoints=int(negs.shape[0]),
                hop_capacities=layout[0], hop_windows=layout[1])
  return seeds


_link_seeds = jax.jit(link_seeds, static_argnames=(
    'mode', 'num_neg', 'amount', 'num_nodes', 'layout'))


def link_metadata(seed_local: jax.Array, batch_size: int, mode: str,
                  num_neg: int, amount: int, pair_valid: jax.Array,
                  pos_label: jax.Array, layout) -> dict:
  """A link batch's metadata from the seed-local rows of ``[src, dst,
  negatives]``: PyG's link keys (binary ``edge_label_index`` /
  ``edge_label`` / ``edge_label_mask``, positives first; triplet
  ``src_index`` / ``dst_pos_index`` / ``dst_neg_index`` / ``pair_mask``)
  beside ``seed_local`` and the static ``hop_capacities`` /
  ``hop_windows`` (``layout``) that `models.train.apply_to_batch`
  hands to the model."""
  b, sl = int(batch_size), seed_local
  if mode == 'binary':
    md = {
        'edge_label_index': jnp.stack([
            jnp.concatenate([sl[:b], sl[2 * b:2 * b + num_neg]]),
            jnp.concatenate([sl[b:2 * b], sl[2 * b + num_neg:]])]),
        # raw labels here, positives then zeros; binary user labels
        # get the reference's +1 shift at the loader
        # (`loader/link_loader.py:146-186`)
        'edge_label': jnp.concatenate(
            [pos_label, jnp.zeros((num_neg,), pos_label.dtype)]),
        'edge_label_mask': jnp.concatenate(
            [pair_valid, jnp.ones((num_neg,), jnp.bool_)]),
    }
  else:
    md = {
        'src_index': sl[:b],
        'dst_pos_index': sl[b:2 * b],
        'dst_neg_index': sl[2 * b:].reshape(b, amount),
        'pair_mask': pair_valid,
    }
  md['seed_local'] = sl
  md['hop_capacities'], md['hop_windows'] = layout
  return md


class NeighborSampler(BaseSampler):
  """Uniform multi-hop neighbor sampler over a device `Graph`.

  Mirrors the reference `NeighborSampler` (`sampler/neighbor_sampler.py:
  37-627`) for the homogeneous case; hetero lives in
  `hetero_neighbor_sampler.py`.

  Args:
    graph: device graph handle.
    num_neighbors: per-hop fanouts, e.g. ``[15, 10, 5]``.
    with_edge: emit global edge ids.
    with_neg: build the negative-sampling path (link loaders).
    seed: PRNG seed (counter-based; each call folds in a step id).
  """

  def __init__(
      self,
      graph: Graph,
      num_neighbors: Sequence[int],
      device=None,
      with_edge: bool = False,
      with_neg: bool = False,
      strategy: str = 'random',
      seed: int = 0,
      sort_locality: bool = True,
  ):
    self.graph = graph
    self.num_neighbors = tuple(int(k) for k in num_neighbors)
    self.device = device
    self.with_edge = with_edge
    self.with_neg = with_neg
    self.strategy = strategy
    # sorted-frontier gather locality (~25% faster hops at scale);
    # turn off to reproduce pre-sort per-seed draws for a pinned key
    self.sort_locality = bool(sort_locality)
    self._base_key = jax.random.key(seed)
    self._step = 0
    self._win_table = None   # lazy prepare_window_table cache (r19)

  # -- helpers --------------------------------------------------------------

  def _next_key(self) -> jax.Array:
    self._step += 1
    return jax.random.fold_in(self._base_key, self._step)

  def _fused_state(self):
    """``(use_fused, win_table, win_e)`` for `_multihop_sample` —
    GLT_PALLAS_SAMPLE is re-read per call (kill switch; the static
    arg makes a flip recompile onto/off the kernel), and the O(E)
    window repack is cached once per sampler."""
    if not fused_sample_enabled():
      return False, None, 0
    if self._win_table is None:
      self._win_table = prepare_window_table(self.graph.indices)
    tbl, e = self._win_table
    return True, tbl, int(e)

  def node_capacity(self, batch_size: int) -> int:
    cap = max_sampled_nodes(batch_size, self.num_neighbors)
    cap = min(cap, batch_size + self.graph.num_nodes)
    return round_up(cap, 8)

  # -- node sampling --------------------------------------------------------

  def sample_from_nodes(self, inputs: NodeSamplerInput,
                        **kwargs) -> SamplerOutput:
    """Reference `sampler/neighbor_sampler.py:138-190`."""
    seeds = jnp.asarray(np.asarray(inputs.node, dtype=np.int32))
    b = seeds.shape[0]
    node_cap = self.node_capacity(b)
    use_fused, win_table, win_e = self._fused_state()
    (nodes, count, row, col, edge, emask, seed_local, nsn,
     nse) = _multihop_sample(
         self.graph.indptr, self.graph.indices,
         self.graph.edge_ids if self.with_edge else None,
         seeds, self._next_key(), win_table,
         fanouts=self.num_neighbors, node_cap=node_cap,
         with_edge=self.with_edge, sort_locality=self.sort_locality,
         use_fused=use_fused, win_e=win_e)
    return SamplerOutput(
        node=nodes, node_count=count, row=row, col=col, edge=edge,
        edge_mask=emask, batch=seeds,
        num_sampled_nodes=nsn, num_sampled_edges=nse,
        # static ints, the same for every batch of a loader: `Batch`
        # carries them as pytree aux data, not as arrays
        metadata={'seed_local': seed_local,
                  'hop_capacities': hop_capacities(
                      b, self.num_neighbors, node_cap),
                  'hop_windows': hop_windows(b, self.num_neighbors)})

  # -- link sampling --------------------------------------------------------

  def sample_from_edges(self, inputs: EdgeSamplerInput,
                        neg_sampling: Optional[NegativeSampling] = None,
                        **kwargs) -> SamplerOutput:
    """Link-prediction sampling with binary/triplet negatives.

    Reference `sampler/neighbor_sampler.py:255-381`: seeds are the
    positive endpoints plus sampled negatives (`link_seeds`); metadata
    carries the local label indices PyG expects (`link_metadata`) and,
    as a node batch does, the expansion's static hop layout.
    """
    neg = neg_sampling or inputs.neg_sampling
    src = jnp.asarray(np.asarray(inputs.row, dtype=np.int32))
    dst = jnp.asarray(np.asarray(inputs.col, dtype=np.int32))
    b = src.shape[0]
    # Static-batch padding: (-1, -1) pairs are mask-outs, never examples.
    pair_valid = (src >= 0) & (dst >= 0)
    key = self._next_key()
    pos_label = (jnp.asarray(inputs.label) if inputs.label is not None
                 else jnp.ones((b,), jnp.int32))

    if neg is None:
      out = self.sample_from_nodes(
          NodeSamplerInput(node=jnp.concatenate([src, dst])))
      sl = out.metadata['seed_local']
      out.metadata.update({
          'edge_label_index': jnp.stack([sl[:b], sl[b:2 * b]]),
          'edge_label': pos_label,
          'edge_label_mask': pair_valid,
      })
      return out

    mode, num_neg, amount, width = link_plan(neg, b)
    node_cap = self.node_capacity(width)
    layout = (hop_capacities(width, self.num_neighbors, node_cap),
              hop_windows(width, self.num_neighbors))
    seeds = _link_seeds(
        self.graph.indptr, self.graph.indices, src, dst, key, mode=mode,
        num_neg=num_neg, amount=amount, num_nodes=self.graph.num_nodes,
        layout=layout)
    out = self.sample_from_nodes(NodeSamplerInput(node=seeds))
    out.metadata = link_metadata(out.metadata['seed_local'], b, mode,
                                 num_neg, amount, pair_valid, pos_label,
                                 layout)
    return out

  # (triplet negative sampling lives in module-level `_triplet_neg_dst`
  # so graph arrays are passed in concrete — a jitted *method* touching
  # `self.graph.indptr` would run the graph's lazy device_put inside
  # tracing and leak tracers into the handle.)

  # -- induced subgraph -----------------------------------------------------

  def subgraph(self, inputs: NodeSamplerInput,
               max_degree: Optional[int] = None,
               **kwargs) -> SamplerOutput:
    """Multi-hop closure then induced edges among collected nodes.

    Reference `sampler/neighbor_sampler.py:409-433` (used by
    `SubGraphLoader` / SEAL).

    Args:
      max_degree: static per-node window for the induced-edge scan;
        defaults to the graph's max degree (exact).  On power-law
        graphs with huge hubs pass a smaller cap to bound the
        ``[node_cap * max_degree]`` intermediate (truncates hub rows).
    """
    seeds = jnp.asarray(np.asarray(inputs.node, dtype=np.int32))
    b = seeds.shape[0]
    node_cap = self.node_capacity(b)
    use_fused, win_table, win_e = self._fused_state()
    (nodes, count, _row, _col, _edge, _emask, seed_local, nsn,
     _nse) = _multihop_sample(
         self.graph.indptr, self.graph.indices, None,
         seeds, self._next_key(), win_table,
         fanouts=self.num_neighbors, node_cap=node_cap, with_edge=False,
         sort_locality=self.sort_locality,
         use_fused=use_fused, win_e=win_e)
    max_deg = max(int(max_degree) if max_degree else self.graph.max_degree, 1)
    sub = induced_subgraph(
        self.graph.indptr, self.graph.indices, nodes,
        max_degree=max_deg,
        edge_ids=self.graph.edge_ids if self.with_edge else None,
        with_edge_ids=self.with_edge)
    return SamplerOutput(
        node=nodes, node_count=count, row=sub.rows, col=sub.cols,
        edge=sub.eids, edge_mask=sub.edge_mask, batch=seeds,
        num_sampled_nodes=nsn, num_sampled_edges=None,
        metadata={'seed_local': seed_local, 'mapping': seed_local})

  # -- frequency-partitioner support ---------------------------------------

  def sample_prob(self, seed_ids, num_nodes: Optional[int] = None
                  ) -> jax.Array:
    """Per-node visit probability under this sampler's fanout schedule.

    Reference `sampler/neighbor_sampler.py:435-562` (`sample_prob` /
    `cal_nbr_prob`) — drives the `FrequencyPartitioner`.
    """
    n = num_nodes or self.graph.num_nodes
    prob = jnp.zeros((n,), jnp.float32)
    seed_ids = jnp.asarray(np.asarray(seed_ids, dtype=np.int32))
    valid = seed_ids >= 0  # INVALID_ID-padded seed batches are welcome
    prob = prob.at[jnp.where(valid, seed_ids, 0)].max(
        valid.astype(jnp.float32))
    for k in self.num_neighbors:
      hop = cal_nbr_prob(self.graph.indptr, self.graph.indices, prob, int(k))
      prob = jnp.minimum(prob + hop, 1.0)
    return prob
