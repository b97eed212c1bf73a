"""Heterogeneous distributed sampling over the device mesh.

The hetero counterpart of `parallel/dist_sampler.py` — and the engine
behind IGBH-scale distributed RGNN (reference `examples/igbh/
dist_train_rgnn.py` + `distributed/dist_neighbor_sampler.py`'s hetero
branch, `:255-324`): every node type is range-sharded with its own
bounds, every edge type's local CSR lives on its source type's owner
device, and each hop's cross-partition neighbor exchange rides
`all_to_all` per edge type inside ONE SPMD program.

Layout (`DistHeteroDataset`):
  * per node type: contiguous relabel by its partition book →
    ``bounds[nt]`` (`RangePartitionBook` form), feature/label shards
    ``[P, rows_max_nt, D]``;
  * per edge type ``(s, rel, d)``: edges owned by the SRC node's
    partition; stacked local CSRs ``[P, ...]`` with local rows in
    ``s``-space and GLOBAL (relabeled) ``d``-space columns, so sampled
    neighbors enter ``d``'s tables with no translation.

Engine (`DistHeteroNeighborSampler`): the hetero multihop loop of
`sampler/hetero_neighbor_sampler.py` with every one-hop replaced by
the collective exchange of `dist_sampler._dist_one_hop` (bucket by
``searchsorted(bounds[s], frontier)`` → all_to_all → local sample →
all_to_all back → stitch), and per-type feature collection via
`dist_gather_multi` against that type's shards.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..data.cold_cache import emit_cache_events
from ..loader.prefetch import PrefetchingLoader
from ..ops.unique import init_node, induce_next
from ..sampler.hetero_neighbor_sampler import (_plan_capacities,
                                               normalize_fanouts)
from ..typing import EdgeType, NodeType, reverse_edge_type
from ..utils.padding import INVALID_ID
from .dist_data import build_dist_edge_feature, build_dist_feature
from .dist_sampler import (ExchangeTelemetry, NEG_TRIALS, _dist_one_hop,
                           _slack_cap, dist_gather_multi,
                           dist_sample_negative, overlay_cold_host,
                           resolve_exchange_slack)


class DistHeteroDataset:
  """Per-type sharded hetero layout.

  Attributes:
    graphs: ``{EdgeType: DistGraph}`` (bounds of the SRC type).
    bounds: ``{NodeType: [P+1]}`` ownership ranges.
    node_features: ``{NodeType: DistFeature}``.
    node_labels: ``{NodeType: [P, rows_max]}``.
    edge_features: ``{EdgeType: DistFeature}`` MOD-sharded over that
      type's GLOBAL edge ids (owner = eid % P,
      `build_dist_edge_feature`).
    old2new / new2old: ``{NodeType: [N_nt]}`` id-space maps.
  """

  def __init__(self, graphs, bounds, node_features=None, node_labels=None,
               old2new=None, edge_features=None, host_parts=None):
    self.graphs = dict(graphs)
    self.bounds = {nt: np.asarray(b, np.int64) for nt, b in bounds.items()}
    self.node_features = dict(node_features or {})
    self.node_labels = dict(node_labels or {})
    self.edge_features = {tuple(et): f
                          for et, f in (edge_features or {}).items()}
    self.old2new = dict(old2new or {})
    self.new2old = {nt: np.argsort(m) for nt, m in self.old2new.items()}
    #: multi-host: partition indices THIS process materialized (see
    #: `DistDataset.host_parts`).  None = all partitions.
    self.host_parts = (np.asarray(host_parts, np.int64)
                       if host_parts is not None else None)

  @property
  def num_partitions(self) -> int:
    return len(next(iter(self.bounds.values()))) - 1

  @property
  def etypes(self) -> Tuple[EdgeType, ...]:
    return tuple(sorted(self.graphs.keys()))

  @property
  def ntypes(self) -> Tuple[NodeType, ...]:
    return tuple(sorted(self.bounds.keys()))

  def num_nodes_dict(self) -> Dict[NodeType, int]:
    return {nt: int(b[-1]) for nt, b in self.bounds.items()}

  @classmethod
  def from_full_graph(cls, num_parts: int, edge_index_dict,
                      node_feat_dict=None, node_label_dict=None,
                      num_nodes_dict=None, node_pb_dict=None,
                      seed: int = 0, edge_feat_dict=None,
                      edge_ids_dict=None,
                      split_ratio: float = 1.0,
                      partitioner=None) -> 'DistHeteroDataset':
    """In-memory partition + shard (testing & single-host path) — the
    hetero analog of `DistDataset.from_full_graph`.  ``edge_ids_dict``
    preserves caller-global edge ids (``edge_feat_dict`` rows index by
    them); defaults to input order per etype.  ``split_ratio < 1``
    tiers every node-type feature store (HBM hot / host-DRAM cold,
    hotness = cross-etype in-degree) — the IGBH-scale lever
    (`build_dist_feature`).

    ``partitioner`` (or ``GLT_PARTITIONER``): ``'locality'`` runs the
    ISSUE 20 streaming partitioner over the DISJOINT UNION of all node
    types (one joint stream, so an etype's endpoints co-locate across
    types) and splits the joint assignment back per type; the balance
    bound then holds on the union, not per type.  Unset/'range' keeps
    the historical seeded round-robin byte-for-byte.  An explicit
    ``node_pb_dict`` entry always wins for its type."""
    node_feat_dict = node_feat_dict or {}
    node_label_dict = node_label_dict or {}
    num_nodes_dict = dict(num_nodes_dict or {})
    ntypes = sorted({t for (s, _, d) in edge_index_dict for t in (s, d)}
                    | set(node_feat_dict) | set(num_nodes_dict))
    for (s, _, d), (rows, cols) in edge_index_dict.items():
      num_nodes_dict[s] = max(num_nodes_dict.get(s, 0),
                              int(np.max(rows, initial=-1)) + 1)
      num_nodes_dict[d] = max(num_nodes_dict.get(d, 0),
                              int(np.max(cols, initial=-1)) + 1)
    for nt, f in node_feat_dict.items():
      num_nodes_dict[nt] = max(num_nodes_dict.get(nt, 0), len(f))

    hotness = {}
    if split_ratio < 1.0:
      # hotness = in-degree summed over every etype landing on nt
      hotness = {nt: np.zeros(num_nodes_dict[nt], np.int64)
                 for nt in ntypes}
      for (s, _, d), (rows, cols) in edge_index_dict.items():
        hotness[d] += np.bincount(np.asarray(cols),
                                  minlength=num_nodes_dict[d])

    rng = np.random.default_rng(seed)
    node_pb_dict = dict(node_pb_dict or {})
    from .locality import locality_partition, resolve_partitioner
    part_kind = resolve_partitioner(partitioner)
    missing = [nt for nt in ntypes if nt not in node_pb_dict]
    if missing and isinstance(part_kind, str) and part_kind == 'locality':
      # joint stream over the disjoint union: offset each type's id
      # space, partition once, split the assignment back per type
      off, tot = {}, 0
      for nt in ntypes:
        off[nt] = tot
        tot += num_nodes_dict[nt]
      g_rows = [off[s] + np.asarray(r, np.int64)
                for (s, _, d), (r, c) in edge_index_dict.items()]
      g_cols = [off[d] + np.asarray(c, np.int64)
                for (s, _, d), (r, c) in edge_index_dict.items()]
      pb_joint, _ = locality_partition(
          np.concatenate(g_rows) if g_rows else np.empty(0, np.int64),
          np.concatenate(g_cols) if g_cols else np.empty(0, np.int64),
          tot, num_parts, seed=seed)
      for nt in missing:
        node_pb_dict[nt] = pb_joint[off[nt]:off[nt]
                                    + num_nodes_dict[nt]].copy()
    old2new, bounds = {}, {}
    for nt in ntypes:
      n = num_nodes_dict[nt]
      pb = node_pb_dict.get(nt)
      if pb is None:
        pb = np.empty(n, dtype=np.int32)
        perm = rng.permutation(n)
        for p in range(num_parts):
          pb[perm[p::num_parts]] = p
        node_pb_dict[nt] = pb
      if nt in hotness:
        order = np.lexsort((np.arange(n), -hotness[nt], pb))
      else:
        order = np.argsort(pb, kind='stable')
      m = np.empty(n, dtype=np.int64)
      m[order] = np.arange(n)
      old2new[nt] = m
      counts = np.bincount(pb, minlength=num_parts)
      bounds[nt] = np.concatenate([[0], np.cumsum(counts)])

    graphs = {}
    for et, (rows, cols) in edge_index_dict.items():
      s, _, d = et
      graphs[et] = _build_etype_graph(
          old2new[s][np.asarray(rows)], old2new[d][np.asarray(cols)],
          bounds[s], num_parts,
          edge_ids=(edge_ids_dict or {}).get(et))

    feats = {nt: build_dist_feature(f, old2new[nt], bounds[nt],
                                    split_ratio=split_ratio)
             for nt, f in node_feat_dict.items()}
    labels = {}
    for nt, lab in node_label_dict.items():
      labels[nt] = build_dist_feature(
          np.asarray(lab), old2new[nt], bounds[nt]).shards[..., 0]
    efeats = {tuple(et): build_dist_edge_feature(f, num_parts)
              for et, f in (edge_feat_dict or {}).items()}
    return cls(graphs, bounds, feats, labels, old2new,
               edge_features=efeats)

  @classmethod
  def from_partition_dir(cls, root, num_parts: Optional[int] = None,
                         split_ratio: float = 1.0,
                         host_parts=None) -> 'DistHeteroDataset':
    """Assemble from the offline partitioner's hetero layout
    (`partition/base.py` hetero branch; reference `DistDataset.load`).
    ``split_ratio < 1`` tiers every node-type feature store.
    ``host_parts`` materializes only this process's partitions (see
    `DistDataset.from_partition_dir`) and serves the full composition:
    tiered stores (owner-served cold tiers, `overlay_cold_owner`),
    per-etype edge features, and ``by_dst`` layouts."""
    if host_parts is not None:
      return _hetero_host_local(cls, root, num_parts, split_ratio,
                                host_parts)
    from ..partition import load_partition
    p0 = load_partition(root, 0)
    meta = p0['meta']
    assert meta['hetero'], 'homogeneous layout: use DistDataset'
    num_parts = num_parts or meta['num_parts']
    parts = [p0] + [load_partition(root, i) for i in range(1, num_parts)]

    edge_index_dict, node_pb_dict, edge_ids_dict = {}, {}, {}
    for nt in meta['node_types']:
      node_pb_dict[nt] = np.asarray(parts[0]['node_pb'][nt].table)
    for et in parts[0]['graph']:
      rows = np.concatenate([p['graph'][et].edge_index[0] for p in parts])
      cols = np.concatenate([p['graph'][et].edge_index[1] for p in parts])
      edge_index_dict[et] = (rows, cols)
      # keep the partitioner's GLOBAL edge ids: edge features (and any
      # user-side eid provenance) index by them, not by concat order
      edge_ids_dict[et] = np.concatenate(
          [p['graph'][et].eids for p in parts])
    node_feat_dict = {}
    for nt in meta['node_types']:
      fparts = [p['node_feat'].get(nt) for p in parts]
      if any(f is not None for f in fparts):
        n = int(meta['num_nodes'][nt])
        d = next(f for f in fparts if f is not None).feats.shape[1]
        feats = np.zeros((n, d), next(f for f in fparts
                                      if f is not None).feats.dtype)
        for f in fparts:
          if f is not None:
            feats[f.ids] = f.feats
        node_feat_dict[nt] = feats
    node_label_dict = {}
    for nt in meta['node_types']:
      lparts = [p['node_label'].get(nt) for p in parts]
      if any(l is not None for l in lparts):
        n = int(meta['num_nodes'][nt])
        lab0 = next(l for l in lparts if l is not None)[0]
        labels = np.zeros((n,), lab0.dtype)
        for l in lparts:
          if l is not None:
            labels[l[1]] = l[0]
        node_label_dict[nt] = labels
    edge_feat_dict = {}
    from ..typing import as_str
    for et in edge_index_dict:
      fparts = [(p.get('edge_feat') or {}).get(et) for p in parts]
      if any(f is not None for f in fparts):
        e = int(meta.get('num_edges', {}).get(
            as_str(et), len(edge_index_dict[et][0])))
        f0 = next(f for f in fparts if f is not None)
        efeats = np.zeros((e, f0.feats.shape[1]), f0.feats.dtype)
        for f in fparts:
          if f is not None:
            efeats[f.ids] = f.feats
        edge_feat_dict[et] = efeats
    return cls.from_full_graph(
        num_parts, edge_index_dict, node_feat_dict, node_label_dict,
        num_nodes_dict={nt: int(meta['num_nodes'][nt])
                        for nt in meta['node_types']},
        node_pb_dict=node_pb_dict, edge_feat_dict=edge_feat_dict,
        edge_ids_dict=edge_ids_dict, split_ratio=split_ratio)


def _hetero_host_local(cls, root, num_parts, split_ratio, host_parts):
  """Host-local arm of `DistHeteroDataset.from_partition_dir`:
  materialize only ``host_parts`` — global relabels/bounds/padding/
  hotness from per-type ``node_pb_*`` files, chunked mmap scans, and
  mmap'd array shapes; local CSR/feature/label/edge-feature stacks
  from per-partition files.  Tiered stores get per-type owner-served
  cold stacks (`DistFeature.cold_local`); ``by_dst`` layouts are
  re-bucketed by src owner with chunked scans."""
  import json as _json
  from pathlib import Path
  from ..typing import as_str, edge_type_from_str
  from .dist_data import (DistFeature, DistGraph, partition_in_degree,
                          relabel_by_partition, scatter_partition_rows,
                          stack_mod_edge_features, stack_partition_csr,
                          stack_partition_csr_rebucket,
                          tiered_local_feature)
  root = Path(root)
  with open(root / 'META.json') as f:
    meta = _json.load(f)
  assert meta['hetero'], 'homogeneous layout: use DistDataset'
  by_src = meta.get('edge_assign', 'by_src') == 'by_src'
  num_parts = num_parts or meta['num_parts']
  host_parts = np.asarray(host_parts, np.int64)
  etypes = [edge_type_from_str(ets) for ets in meta['edge_types']]

  # hotness per node type = in-degree summed over etypes landing on it
  # (the from_full_graph tiering policy, chunked) — MUST match the
  # single-controller relabel of the same (layout, split_ratio)
  hotness = {}
  if split_ratio < 1.0:
    hotness = {nt: np.zeros(int(meta['num_nodes'][nt]), np.int64)
               for nt in meta['node_types']}
    for et in etypes:
      hotness[et[2]] += partition_in_degree(
          root, f'graph/{as_str(et)}', int(meta['num_nodes'][et[2]]),
          num_parts)

  node_pbs, old2new, bounds, counts = {}, {}, {}, {}
  for nt in meta['node_types']:
    node_pbs[nt] = np.load(root / f'node_pb_{nt}.npy')
    old2new[nt], counts[nt], bounds[nt] = relabel_by_partition(
        node_pbs[nt], num_parts, hotness.get(nt))

  graphs = {}
  for et in etypes:
    s, _, d = et
    if by_src:
      indptr_s, indices_s, eids_s = stack_partition_csr(
          root, host_parts, f'graph/{as_str(et)}', old2new[s],
          old2new[d], bounds[s], counts[s], num_parts)
    else:
      indptr_s, indices_s, eids_s = stack_partition_csr_rebucket(
          root, host_parts, f'graph/{as_str(et)}', node_pbs[s],
          old2new[s], old2new[d], bounds[s], counts[s], num_parts)
    graphs[et] = DistGraph(indptr_s, indices_s, eids_s, bounds[s])

  feats, labels = {}, {}
  for nt in meta['node_types']:
    max_nodes = int(counts[nt].max())
    fs = scatter_partition_rows(root, host_parts, f'node_feat/{nt}',
                                'feats', old2new[nt], bounds[nt],
                                max_nodes)
    ls = scatter_partition_rows(root, host_parts, f'node_label/{nt}',
                                'labels', old2new[nt], bounds[nt],
                                max_nodes)
    if fs is not None:
      if split_ratio < 1.0:
        feats[nt] = tiered_local_feature(fs, counts[nt], split_ratio,
                                         host_parts, bounds[nt])
      else:
        feats[nt] = DistFeature(fs, bounds[nt])
    if ls is not None:
      labels[nt] = ls

  efeats = {}
  for et in etypes:
    ef = stack_mod_edge_features(
        root, host_parts, f'edge_feat/{as_str(et)}', num_parts,
        int(meta.get('num_edges', {}).get(as_str(et), 0)))
    if ef is not None:
      efeats[et] = ef
  return cls(graphs, bounds, feats, labels, old2new,
             edge_features=efeats, host_parts=host_parts)


def _build_etype_graph(rows_new: np.ndarray, cols_new: np.ndarray,
                       bounds_s: np.ndarray, num_parts: int,
                       edge_ids: Optional[np.ndarray] = None):
  """Stacked per-partition local CSRs for one edge type.

  ``rows_new`` are RELABELED src-type ids (sharded by ``bounds_s``),
  ``cols_new`` RELABELED dst-type ids kept global — the hetero twist
  `build_dist_graph` can't express (its single relabel map would be
  applied to both endpoint spaces).  ``edge_ids`` preserves the
  caller's GLOBAL edge ids (edge features index by them); defaults to
  input order.
  """
  from .dist_data import DistGraph
  from .partition_book import range_of_host
  from ..utils.topo import coo_to_csr
  counts = np.diff(bounds_s)
  owner = range_of_host(bounds_s, rows_new, num_parts=num_parts)
  if edge_ids is None:
    edge_ids = np.arange(len(rows_new), dtype=np.int64)
  else:
    edge_ids = np.asarray(edge_ids, np.int64)
  max_nodes = int(counts.max()) if num_parts else 0
  max_edges = max(int(np.bincount(owner, minlength=num_parts).max()), 1)
  indptr_s = np.zeros((num_parts, max_nodes + 1), dtype=np.int64)
  indices_s = np.full((num_parts, max_edges), -1, dtype=np.int32)
  eids_s = np.full((num_parts, max_edges), -1, dtype=np.int64)
  for p in range(num_parts):
    sel = owner == p
    local_rows = rows_new[sel] - bounds_s[p]
    iptr, idx, eid = coo_to_csr(local_rows, cols_new[sel],
                                int(counts[p]), edge_ids[sel])
    indptr_s[p, :len(iptr)] = iptr
    indptr_s[p, len(iptr):] = iptr[-1]
    indices_s[p, :len(idx)] = idx
    eids_s[p, :len(eid)] = eid
  return DistGraph(indptr_s, indices_s, eids_s, bounds_s)


class DistHeteroNeighborSampler(ExchangeTelemetry):
  """SPMD hetero multihop sampler (+ per-type feature collection).

  Args:
    dataset: `DistHeteroDataset`.
    num_neighbors: per-hop fanouts — list (all etypes) or per-etype
      dict.
    mesh: mesh whose ``axis`` size == partition count.
    exchange_slack: per-destination exchange capacity as a multiple of
      the balanced share (see `dist_sampler.DistNeighborSampler`);
      None = exact.
  """

  def __init__(self, dataset: DistHeteroDataset, num_neighbors,
               mesh: Optional[Mesh] = None, axis: str = 'data',
               with_edge: bool = False, collect_features: bool = True,
               seed: int = 0, exchange_slack: Optional[float] = None,
               exchange_layout: Optional[str] = None):
    from .dp import make_mesh
    self.ds = dataset
    self.etypes, self.fanouts, self.num_hops = normalize_fanouts(
        dataset.etypes, num_neighbors)
    self.num_parts = dataset.num_partitions
    self.mesh = mesh or make_mesh(self.num_parts, axis)
    self.axis = axis
    self.with_edge = with_edge
    self.collect_features = collect_features
    self.exchange_slack = exchange_slack
    # see DistNeighborSampler: dense/compact/hier/ragged per-etype
    # exchange layout; every per-type hop and gather below shares it
    self.exchange_layout = exchange_layout or 'auto'
    self._base_key = jax.random.key(seed)
    self._step_cnt = 0
    self._steps = {}
    self._device_arrays = None
    self._init_stats()

  def _arrays(self):
    if self._device_arrays is None:
      from .dist_sampler import put_stacked_host_local
      shard = NamedSharding(self.mesh, P(self.axis))
      repl = NamedSharding(self.mesh, P())
      put = jax.device_put
      if getattr(self.ds, 'host_parts', None) is not None:
        putS = lambda a: put_stacked_host_local(    # noqa: E731
            self.mesh, self.axis, self.num_parts, self.ds.host_parts,
            np.asarray(a))
      else:
        putS = lambda a: put(np.asarray(a), shard)  # noqa: E731
      arrs = {'graphs': {}, 'bounds': {}, 'feats': {}, 'labels': {},
              'efeats': {}, 'hcounts': {}}
      for et in self.etypes:
        g = self.ds.graphs[et]
        arrs['graphs'][et] = (putS(g.indptr), putS(g.indices),
                              putS(g.edge_ids))
      for nt, b in self.ds.bounds.items():
        arrs['bounds'][nt] = put(b, repl)
      if self.collect_features:
        for nt, f in self.ds.node_features.items():
          arrs['feats'][nt] = putS(f.shards)
          arrs['hcounts'][nt] = put(
              np.asarray(f.hot_counts, np.int32), repl)
        if self.with_edge:
          # only fanout-selected etypes sample edges; features of
          # unselected etypes would never be gathered (and their
          # eids_acc keys don't exist in the step)
          for et, f in self.ds.edge_features.items():
            if et in self.etypes:
              arrs['efeats'][et] = (putS(f.shards),
                                    put(f.bounds, repl))
      for nt, l in self.ds.node_labels.items():
        arrs['labels'][nt] = putS(l)
      self._device_arrays = arrs
    return self._device_arrays

  def _make_step(self, input_sizes: Dict[NodeType, int],
                 link: Optional[dict] = None):
    from .shard_map_compat import shard_map
    ntypes, table_cap, frontier_caps, _ = _plan_capacities(
        self.etypes, self.fanouts, input_sizes, self.num_hops,
        self.ds.num_nodes_dict())
    num_nodes = self.ds.num_nodes_dict()
    seed_types = tuple(sorted(input_sizes))
    etypes = self.etypes
    fanouts = self.fanouts
    num_parts = self.num_parts
    axis = self.axis
    with_edge = self.with_edge
    arrs = self._arrays()
    feat_nts = tuple(sorted(arrs['feats'])) if self.collect_features else ()
    label_nts = tuple(sorted(arrs['labels']))
    efeat_ets = tuple(sorted(arrs['efeats']))
    tiered_nts = {nt: self.ds.node_features[nt].is_tiered
                  for nt in feat_nts}
    # per-TABLE ownership scheme: a mixed mod/range edge_features dict
    # must not collapse to one global mode (wrong-owner gathers return
    # silent zeros)
    ef_modes = {et: ('mod' if self.ds.edge_features[et].mod_sharded
                     else 'range') for et in efeat_ets}
    num_hops = self.num_hops
    exchange_slack = self.exchange_slack
    exchange_layout = self.exchange_layout

    def per_device(graphs_t, bounds_t, feats_t, labels_t, efeats_t,
                   ebounds_t, hcounts_t, seeds_s, key):
      graphs = {et: tuple(a[0] for a in g)
                for et, g in zip(etypes, graphs_t)}
      bounds = dict(zip(ntypes, bounds_t))
      fshards = {nt: f[0] for nt, f in zip(feat_nts, feats_t)}
      lshards = {nt: l[0] for nt, l in zip(label_nts, labels_t)}
      efshards = {et: f[0] for et, f in zip(efeat_ets, efeats_t)}
      ebounds = dict(zip(efeat_ets, ebounds_t))
      hcounts = dict(zip(feat_nts, hcounts_t))
      seeds = seeds_s[0]

      neg_ok = None
      if link is None:
        seed_sets = {seed_types[0]: seeds}
      else:
        # link mode: endpoints + collective strict negatives on the
        # seed edge type's sharded CSR (the hetero arm of
        # `dist_sampler._make_dist_link_step`)
        let = link['etype']
        s_t, _, d_t = let
        pairs = seeds
        src, dst = pairs[:, 0], pairs[:, 1]
        li, lx, _ = graphs[let]
        my_idx = jax.lax.axis_index(axis)
        neg_key = jax.random.fold_in(jax.random.fold_in(key, my_idx), 977)
        neg_cap = _slack_cap(link['num_neg'] * NEG_TRIALS, num_parts,
                             exchange_slack, exchange_layout)
        if link['mode'] == 'binary':
          nrows, ncols, neg_ok = dist_sample_negative(
              li, lx, bounds[s_t], num_nodes[s_t], num_nodes[d_t],
              link['num_neg'], neg_key, axis, num_parts,
              exchange_capacity=neg_cap)
          src_seeds = jnp.concatenate([src, nrows])
          dst_seeds = jnp.concatenate([dst, ncols])
        elif link['mode'] == 'triplet':
          amount = link['num_neg'] // link['batch']
          srcs_rep = jnp.repeat(jnp.where(src >= 0, src, 0), amount)
          _, negs, neg_ok = dist_sample_negative(
              li, lx, bounds[s_t], num_nodes[s_t], num_nodes[d_t],
              link['num_neg'], neg_key, axis, num_parts,
              exchange_capacity=neg_cap,
              rows_fixed=srcs_rep.astype(jnp.int32))
          src_seeds = src
          dst_seeds = jnp.concatenate([dst, negs])
        else:
          src_seeds, dst_seeds = src, dst
        clean = lambda v: jnp.where(v >= 0, v, INVALID_ID).astype(
            jnp.int32)
        if s_t == d_t:
          seed_sets = {s_t: clean(jnp.concatenate([src_seeds,
                                                   dst_seeds]))}
        else:
          seed_sets = {s_t: clean(src_seeds), d_t: clean(dst_seeds)}

      states, seed_locals = {}, {}
      for nt in ntypes:
        if nt in seed_sets:
          states[nt], seed_locals[nt] = init_node(seed_sets[nt],
                                                  table_cap[nt])
        else:
          states[nt] = init_node(
              jnp.full((1,), INVALID_ID, jnp.int32), table_cap[nt])[0]
      fr_start = {nt: jnp.zeros((), jnp.int32) for nt in ntypes}
      rows_acc = {et: [] for et in etypes}
      cols_acc = {et: [] for et in etypes}
      eids_acc = {et: [] for et in etypes}
      nsn = {nt: [states[nt].count] for nt in ntypes}
      fr_stats = jnp.zeros((3,), jnp.int32)
      ft_stats = jnp.zeros((3,), jnp.int32)

      for h in range(num_hops):
        hop_start = {nt: states[nt].count for nt in ntypes}
        frontiers = {}
        for nt in ntypes:
          fcap = frontier_caps[h].get(nt, 0)
          if fcap <= 0:
            frontiers[nt] = None
            continue
          slots = fr_start[nt] + jnp.arange(fcap, dtype=jnp.int32)
          valid = slots < hop_start[nt]
          nodes = states[nt].nodes[
              jnp.clip(slots, 0, table_cap[nt] - 1)]
          frontiers[nt] = (jnp.where(valid, nodes, INVALID_ID),
                           jnp.where(valid, slots, -1))
        for ei_i, et in enumerate(etypes):
          s, _, d = et
          k = fanouts[et][h] if h < len(fanouts[et]) else 0
          if k <= 0 or frontiers.get(s) is None:
            continue
          fr_nodes, fr_local = frontiers[s]
          indptr, indices, eids = graphs[et]
          hop_key = jax.random.fold_in(jax.random.fold_in(key, h), ei_i)
          nbrs, mask, e, _w, hstats = _dist_one_hop(
              indptr, indices, eids if with_edge else None, bounds[s],
              fr_nodes, int(k), hop_key, axis, num_parts, with_edge,
              exchange_capacity=_slack_cap(fr_nodes.shape[0], num_parts,
                                           exchange_slack,
                                           exchange_layout))
          fr_stats = fr_stats + jnp.stack(hstats)
          states[d], rows, cols, _ = induce_next(
              states[d], fr_local, nbrs, mask)
          rows_acc[et].append(rows)
          cols_acc[et].append(cols)
          if with_edge:
            eids_acc[et].append(
                jnp.where(rows >= 0, e.reshape(-1), INVALID_ID))
        for nt in ntypes:
          fr_start[nt] = hop_start[nt]
          nsn[nt].append(states[nt].count)

      x = {}
      for nt in feat_nts:
        (x[nt],), gstats = dist_gather_multi(
            (fshards[nt],), bounds[nt], states[nt].nodes, axis,
            num_parts,
            exchange_capacity=_slack_cap(table_cap[nt], num_parts,
                                         exchange_slack,
                                         exchange_layout),
            hot_counts=hcounts[nt] if tiered_nts[nt] else None)
        ft_stats = ft_stats + jnp.stack(gstats)
      y = {}
      for nt in label_nts:
        (y[nt],), gstats = dist_gather_multi(
            (lshards[nt],), bounds[nt], states[nt].nodes, axis,
            num_parts,
            exchange_capacity=_slack_cap(table_cap[nt], num_parts,
                                         exchange_slack,
                                         exchange_layout))
        ft_stats = ft_stats + jnp.stack(gstats)

      ef = {}
      for et in efeat_ets:
        if not eids_acc.get(et):
          continue
        all_eids = jnp.concatenate(eids_acc[et])
        (ef[et],), gstats = dist_gather_multi(
            (efshards[et],), ebounds[et], all_eids, axis, num_parts,
            exchange_capacity=_slack_cap(all_eids.shape[0], num_parts,
                                         exchange_slack,
                                         exchange_layout),
            shard_mode=ef_modes[et])
        ft_stats = ft_stats + jnp.stack(gstats)

      neg_lost = (jnp.sum((~neg_ok).astype(jnp.int32))
                  if neg_ok is not None else jnp.int32(0))
      stats = jnp.concatenate([fr_stats, ft_stats, neg_lost[None]])
      if neg_ok is None:
        neg_ok = jnp.ones((1,), bool)

      def lead(v):
        return None if v is None else v[None]
      node_t = tuple(lead(states[nt].nodes) for nt in ntypes)
      cnt_t = tuple(lead(states[nt].count[None]) for nt in ntypes)
      row_t = tuple(
          lead(jnp.concatenate(rows_acc[et])) if rows_acc[et] else None
          for et in etypes)
      col_t = tuple(
          lead(jnp.concatenate(cols_acc[et])) if cols_acc[et] else None
          for et in etypes)
      eid_t = tuple(
          lead(jnp.concatenate(eids_acc[et]))
          if (with_edge and eids_acc[et]) else None
          for et in etypes)
      x_t = tuple(lead(x[nt]) for nt in feat_nts)
      y_t = tuple(lead(y[nt]) for nt in label_nts)
      nsn_t = tuple(
          lead(jnp.concatenate(
              [jnp.stack(nsn[nt])[:1],
               jnp.stack(nsn[nt])[1:] - jnp.stack(nsn[nt])[:-1]]))
          for nt in ntypes)
      sl_t = tuple(lead(seed_locals[nt]) for nt in seed_types)
      ef_t = tuple(lead(ef[et]) if et in ef else None
                   for et in efeat_ets)
      return (node_t, cnt_t, row_t, col_t, eid_t, sl_t,
              x_t, y_t, ef_t, nsn_t, lead(neg_ok), lead(stats))

    sh = P(axis)
    rp = P()
    in_specs = (
        tuple((sh, sh, sh) for _ in etypes),      # graphs
        tuple(rp for _ in ntypes),                # bounds
        tuple(sh for _ in feat_nts),              # feature shards
        tuple(sh for _ in label_nts),             # label shards
        tuple(sh for _ in efeat_ets),             # edge-feature shards
        tuple(rp for _ in efeat_ets),             # edge-feature bounds
        tuple(rp for _ in feat_nts),              # feature hot counts
        sh,                                       # seeds
        rp,                                       # key
    )
    out_specs = (
        tuple(sh for _ in ntypes), tuple(sh for _ in ntypes),
        tuple(sh for _ in etypes), tuple(sh for _ in etypes),
        tuple(sh for _ in etypes), tuple(sh for _ in seed_types),
        tuple(sh for _ in feat_nts), tuple(sh for _ in label_nts),
        tuple(sh for _ in efeat_ets),
        tuple(sh for _ in ntypes), sh, sh,
    )
    sharded = shard_map(per_device, mesh=self.mesh, in_specs=in_specs,
                        out_specs=out_specs)
    meta = dict(ntypes=ntypes, feat_nts=feat_nts, label_nts=label_nts,
                seed_types=seed_types, efeat_ets=efeat_ets)
    return jax.jit(sharded), meta

  def _overlay_cold_types(self, feat_nts, ntypes, x_t, node_t):
    """Per-node-type cold-tier overlay (+ telemetry) for tiered
    feature stores — the hetero arm of
    `dist_sampler.overlay_cold_host` / `overlay_cold_owner`.  All
    requester-side (``cold_host``) node tables come down in ONE
    device_get (one sync per batch, like the homo path); owner-served
    (``cold_local``, host-local layouts) types run the second-gather
    protocol, which reads only this process's addressable shards."""
    from .dist_sampler import overlay_cold_owner
    tiered = [(i, nt) for i, (nt, x) in enumerate(zip(feat_nts, x_t))
              if x is not None and self.ds.node_features[nt].is_tiered]
    if not tiered:
      return x_t
    host_side = [(i, nt) for i, nt in tiered
                 if self.ds.node_features[nt].cold_host is not None]
    fetched = (jax.device_get([node_t[ntypes.index(nt)]
                               for _, nt in host_side])
               if host_side else [])
    out = list(x_t)
    for (i, nt), nodes_h in zip(host_side, fetched):
      nf = self.ds.node_features[nt]
      out[i], lookups, misses = overlay_cold_host(
          out[i], node_t[ntypes.index(nt)], self.ds.bounds[nt],
          nf.hot_counts, nf.cold_host, self.mesh, self.axis,
          self.num_parts, nodes_host=nodes_h)
      with self._stats_lock:
        # hetero engine: no dynamic cache yet — every cold request is
        # host-served (cold_lookups == cold_misses)
        self._feat_lookups += lookups
        self._cold_lookups += misses
        self._cold_misses += misses
      # surface the no-cache economics LIVE (ISSUE 14 satellite):
      # cache.misses_total{scope=hetero} ticks with hits pinned at 0,
      # so `cold_lookups == cold_misses` (ROADMAP item 3's hetero
      # cold-cache gap) reads off /metrics instead of artifact-only
      emit_cache_events('hetero', 0, int(misses), 0, 0)
    hp = (self.ds.host_parts if self.ds.host_parts is not None
          else np.arange(self.num_parts))
    # ONE capacity handshake for every owner-served type (a
    # per-(type, batch) allgather dominates at large P x many
    # types): plan all types first, agree on all capacities in a
    # single `_global_max_vec`, then execute each overlay
    from .dist_sampler import _global_max_vec, plan_cold_requests
    owner_served = [(i, nt) for i, nt in tiered
                    if self.ds.node_features[nt].cold_host is None]
    plans = []
    for i, nt in owner_served:
      nf = self.ds.node_features[nt]
      plans.append(plan_cold_requests(
          node_t[ntypes.index(nt)], self.ds.bounds[nt], nf.hot_counts,
          hp, cache_ids=nf.cache_ids))
    agreed = _global_max_vec(
        [int(p[5].max(initial=0)) for p in plans]) if plans else []
    for (i, nt), plan, cap in zip(owner_served, plans, agreed):
      nf = self.ds.node_features[nt]
      out[i], lookups, misses = overlay_cold_owner(
          out[i], node_t[ntypes.index(nt)], self.ds.bounds[nt],
          nf.hot_counts, nf.cold_local, self.mesh, self.axis,
          self.num_parts, hp, cache_ids=nf.cache_ids, plan_=plan,
          agreed_capacity=cap)
      with self._stats_lock:
        # hetero engine: no dynamic cache yet — every cold request is
        # host-served (cold_lookups == cold_misses)
        self._feat_lookups += lookups
        self._cold_lookups += misses
        self._cold_misses += misses
      # same live accounting for the owner-served arm (see above)
      emit_cache_events('hetero', 0, int(misses), 0, 0)
    return tuple(out)

  def sample_from_nodes(self, input_type: NodeType,
                        seeds_stacked: np.ndarray):
    """``seeds_stacked``: ``[P, B]`` per-device seeds of ``input_type``
    in that type's RELABELED id space (-1 padded).  Returns a dict of
    per-type stacked pieces."""
    b = int(seeds_stacked.shape[1])
    cfg = (input_type, b)
    if cfg not in self._steps:
      self._steps[cfg] = self._make_step({input_type: b})
    step, meta = self._steps[cfg]
    arrs = self._arrays()
    self._step_cnt += 1
    key = jax.random.fold_in(self._base_key, self._step_cnt)
    seeds_dev = jax.device_put(
        np.asarray(seeds_stacked, dtype=np.int32),
        NamedSharding(self.mesh, P(self.axis)))
    graphs_t = tuple(arrs['graphs'][et] for et in self.etypes)
    bounds_t = tuple(arrs['bounds'][nt] for nt in meta['ntypes'])
    feats_t = tuple(arrs['feats'][nt] for nt in meta['feat_nts'])
    labels_t = tuple(arrs['labels'][nt] for nt in meta['label_nts'])
    efeats_t = tuple(arrs['efeats'][et][0] for et in meta['efeat_ets'])
    ebounds_t = tuple(arrs['efeats'][et][1] for et in meta['efeat_ets'])
    hcounts_t = tuple(arrs['hcounts'][nt] for nt in meta['feat_nts'])
    (node_t, cnt_t, row_t, col_t, eid_t, sl_t, x_t, y_t, ef_t,
     nsn_t, _, stats) = step(graphs_t, bounds_t, feats_t, labels_t,
                             efeats_t, ebounds_t, hcounts_t, seeds_dev,
                             key)
    self._accumulate_stats(stats)
    x_t = self._overlay_cold_types(meta['feat_nts'], meta['ntypes'],
                                   x_t, node_t)
    seed_local = sl_t[meta['seed_types'].index(input_type)]
    ntypes = meta['ntypes']
    out = dict(
        node=dict(zip(ntypes, node_t)),
        node_count={nt: c[..., 0] for nt, c in zip(ntypes, cnt_t)},
        row={reverse_edge_type(et): r
             for et, r in zip(self.etypes, row_t) if r is not None},
        col={reverse_edge_type(et): c
             for et, c in zip(self.etypes, col_t) if c is not None},
        edge={reverse_edge_type(et): e
              for et, e in zip(self.etypes, eid_t) if e is not None},
        seed_local=seed_local,
        x=dict(zip(meta['feat_nts'], x_t)),
        y=dict(zip(meta['label_nts'], y_t)),
        ef={reverse_edge_type(et): e
            for et, e in zip(meta['efeat_ets'], ef_t) if e is not None},
        num_sampled_nodes=dict(zip(ntypes, nsn_t)),
        batch=seeds_dev, input_type=input_type)
    return out

  def _link_input_sizes(self, etype, mode, amount, b):
    """Per-type seed counts for link expansion — negative counts from
    the ONE shared definition (`distributed.dist_options.
    binary_num_negatives`)."""
    from ..distributed.dist_options import binary_num_negatives
    s_t, _, d_t = etype
    if mode == 'binary':
      nn = binary_num_negatives(b, amount)
      src_n = dst_n = b + nn
    elif mode == 'triplet':
      nn = b * int(np.ceil(amount))
      src_n, dst_n = b, b + nn
    else:
      nn = 0
      src_n = dst_n = b
    if s_t == d_t:
      return {s_t: src_n + dst_n}, nn
    return {s_t: src_n, d_t: dst_n}, nn

  def sample_from_edges(self, input_type: EdgeType,
                        pairs_stacked: np.ndarray,
                        neg_sampling=None):
    """``pairs_stacked``: ``[P, B, 2|3]`` per-device (src, dst[,
    label]) seed edges of edge type ``input_type``, each endpoint in
    its node type's RELABELED id space.  Negatives are strict against
    the global sharded etype CSR (collective `dist_edge_exists`)."""
    from ..sampler.base import NegativeSampling
    et = tuple(input_type)
    s_t, _, d_t = et
    ns = (NegativeSampling.cast(neg_sampling)
          if neg_sampling is not None else None)
    mode = ns.mode if ns is not None else None
    amount = float(ns.amount) if ns is not None else 1.0
    b = int(pairs_stacked.shape[1])
    input_sizes, num_neg = self._link_input_sizes(et, mode, amount, b)
    cfg = ('link', et, mode, amount, b, pairs_stacked.shape[2])
    if cfg not in self._steps:
      self._steps[cfg] = self._make_step(
          input_sizes, link=dict(etype=et, mode=mode,
                                 num_neg=num_neg, batch=b))
    step, meta = self._steps[cfg]
    arrs = self._arrays()
    self._step_cnt += 1
    key = jax.random.fold_in(self._base_key, self._step_cnt)
    pairs_dev = jax.device_put(
        np.asarray(pairs_stacked, dtype=np.int32),
        NamedSharding(self.mesh, P(self.axis)))
    graphs_t = tuple(arrs['graphs'][e] for e in self.etypes)
    bounds_t = tuple(arrs['bounds'][nt] for nt in meta['ntypes'])
    feats_t = tuple(arrs['feats'][nt] for nt in meta['feat_nts'])
    labels_t = tuple(arrs['labels'][nt] for nt in meta['label_nts'])
    efeats_t = tuple(arrs['efeats'][e][0] for e in meta['efeat_ets'])
    ebounds_t = tuple(arrs['efeats'][e][1] for e in meta['efeat_ets'])
    hcounts_t = tuple(arrs['hcounts'][nt] for nt in meta['feat_nts'])
    (node_t, cnt_t, row_t, col_t, eid_t, sl_t, x_t, y_t, ef_t, nsn_t,
     neg_ok, stats) = step(graphs_t, bounds_t, feats_t, labels_t,
                           efeats_t, ebounds_t, hcounts_t, pairs_dev,
                           key)
    self._accumulate_stats(stats)
    x_t = self._overlay_cold_types(meta['feat_nts'], meta['ntypes'],
                                   x_t, node_t)
    ntypes = meta['ntypes']
    seed_types = meta['seed_types']
    sl = dict(zip(seed_types, sl_t))
    if s_t == d_t:
      all_sl = sl[s_t]
      if mode == 'triplet':
        n_src = b
      elif mode == 'binary':
        n_src = b + num_neg
      else:
        n_src = b
      sl_s, sl_d = all_sl[:, :n_src], all_sl[:, n_src:]
    else:
      sl_s, sl_d = sl[s_t], sl[d_t]
    pair_valid = (pairs_dev[:, :, 0] >= 0) & (pairs_dev[:, :, 1] >= 0)
    pos_label = jnp.where(
        pair_valid,
        pairs_dev[:, :, 2] if pairs_stacked.shape[2] > 2
        else jnp.ones_like(pair_valid, jnp.int32), 0)
    md = {'seed_local': sl}
    if mode == 'binary':
      # sl_s/sl_d are already laid out positives-then-negatives
      eli = jnp.stack([sl_s, sl_d], axis=1)
      quota = jnp.ceil(jnp.sum(pair_valid, axis=1, keepdims=True)
                       * jnp.float32(amount)).astype(jnp.int32)
      neg_keep = neg_ok & (jnp.arange(num_neg)[None, :] < quota)
      md.update(
          edge_label_index=eli,
          edge_label=jnp.concatenate(
              [pos_label, jnp.zeros((pos_label.shape[0], num_neg),
                                    jnp.int32)], axis=1),
          edge_label_mask=jnp.concatenate([pair_valid, neg_keep],
                                          axis=1))
    elif mode == 'triplet':
      amount_i = num_neg // b
      dn = jnp.where(neg_ok, sl_d[:, b:], -1).reshape(
          sl_d.shape[0], b, amount_i)
      md.update(src_index=sl_s[:, :b], dst_pos_index=sl_d[:, :b],
                dst_neg_index=dn, pair_mask=sl_s[:, :b] >= 0)
    else:
      md.update(edge_label_index=jnp.stack([sl_s, sl_d], axis=1),
                edge_label=pos_label, edge_label_mask=pair_valid)
    return dict(
        node=dict(zip(ntypes, node_t)),
        node_count={nt: c[..., 0] for nt, c in zip(ntypes, cnt_t)},
        row={reverse_edge_type(e): r
             for e, r in zip(self.etypes, row_t) if r is not None},
        col={reverse_edge_type(e): c
             for e, c in zip(self.etypes, col_t) if c is not None},
        edge={reverse_edge_type(e): v
              for e, v in zip(self.etypes, eid_t) if v is not None},
        x=dict(zip(meta['feat_nts'], x_t)),
        y=dict(zip(meta['label_nts'], y_t)),
        ef={reverse_edge_type(e): v
            for e, v in zip(meta['efeat_ets'], ef_t) if v is not None},
        num_sampled_nodes=dict(zip(ntypes, nsn_t)),
        batch=pairs_dev[:, :, 0], metadata=md, input_type=et)


class DistHeteroNeighborLoader(PrefetchingLoader):
  """Distributed hetero loader: stacked `HeteroBatch`-shaped pytrees
  (leading axis = device), ready for a DP hetero train step.

  The facade reference users reach via ``DistNeighborLoader`` on a
  hetero `DistDataset` (`distributed/dist_neighbor_loader.py:27-94`).
  ``prefetch=N`` overlaps the next batch's host work (incl. tiered
  cold overlays) with the current device step.
  """

  def __init__(self, dataset: DistHeteroDataset, num_neighbors,
               input_nodes, batch_size: int = 1, shuffle: bool = False,
               drop_last: bool = False, mesh: Optional[Mesh] = None,
               with_edge: bool = False, collect_features: bool = True,
               seed: int = 0, input_space: str = 'old',
               exchange_slack='auto',
               exchange_layout: Optional[str] = None,
               prefetch: int = 0):
    from ..loader.node_loader import SeedBatcher
    from .dist_sampler import DEFAULT_EXCHANGE_SLACK, AdaptiveSlack
    self.prefetch = int(prefetch)
    input_type, seeds = input_nodes
    self.input_type = input_type
    slack = resolve_exchange_slack(exchange_slack, shuffle)
    self.sampler = DistHeteroNeighborSampler(
        dataset, num_neighbors, mesh=mesh, with_edge=with_edge,
        collect_features=collect_features, seed=seed,
        exchange_slack=(DEFAULT_EXCHANGE_SLACK if slack == 'adaptive'
                        else slack),
        exchange_layout=exchange_layout)
    self._adaptive = (AdaptiveSlack(self.sampler)
                      if slack == 'adaptive' else None)
    self._epoch_count = 0
    self.ds = dataset
    seeds = np.asarray(seeds).reshape(-1)
    if input_space == 'old' and input_type in dataset.old2new:
      seeds = dataset.old2new[input_type][seeds]
    self.num_parts = dataset.num_partitions
    self.batch_size = int(batch_size)
    self._batcher = SeedBatcher(seeds, batch_size * self.num_parts,
                                shuffle, drop_last, seed)

  def __len__(self):
    return len(self._batcher)

  def _produce(self, seed_iter):
    from ..loader.transform import HeteroBatch
    flat = next(seed_iter)
    seeds = flat.reshape(self.num_parts, self.batch_size)
    out = self.sampler.sample_from_nodes(self.input_type, seeds)
    ei = {et: jnp.stack([out['row'][et], out['col'][et]], axis=1)
          for et in out['row']}
    em = {et: out['row'][et] >= 0 for et in out['row']}
    md = {'seed_local': out['seed_local'],
          'input_type': self.input_type}
    if out['edge']:
      # global eids per reversed etype — same key the host runtime
      # collates (`distributed/dist_loader.py::_collate_hetero`)
      md['edge_dict'] = out['edge']
    return HeteroBatch(
        x_dict=out['x'], y_dict=out['y'], edge_index_dict=ei,
        edge_attr_dict=dict(out.get('ef') or {}), node_dict=out['node'],
        node_mask_dict={nt: v >= 0 for nt, v in out['node'].items()},
        edge_mask_dict=em,
        batch_dict={self.input_type: out['batch']},
        batch_size=self.batch_size,
        metadata=md)


class DistHeteroLinkNeighborLoader(PrefetchingLoader):
  """Distributed hetero link-prediction loader over the device mesh
  (the hetero arm of `dist_sampler.DistLinkNeighborLoader`; reference
  users reach it via ``DistLinkNeighborLoader`` on a hetero dataset,
  `distributed/dist_link_neighbor_loader.py:30-153`).

  Args:
    edge_label_index: ``(edge_type, (rows, cols))`` seed edges, each
      endpoint in its node type's id space.
    edge_label: optional integer labels (binary mode applies the
      reference's +1 shift).
    neg_sampling: ``'binary'`` / ``('triplet', amount)`` / None.
  """

  def __init__(self, dataset: DistHeteroDataset, num_neighbors,
               edge_label_index, edge_label=None, neg_sampling=None,
               batch_size: int = 1, shuffle: bool = False,
               drop_last: bool = False, mesh: Optional[Mesh] = None,
               with_edge: bool = False, collect_features: bool = True,
               seed: int = 0, input_space: str = 'old',
               exchange_slack='auto',
               exchange_layout: Optional[str] = None,
               prefetch: int = 0):
    from ..loader.node_loader import SeedBatcher
    from ..sampler.base import NegativeSampling
    self.prefetch = int(prefetch)
    from .dist_sampler import pack_link_seeds
    input_type, pairs = edge_label_index
    self.input_type = tuple(input_type)
    # cast ONCE at construction: validates the mode up front and keeps
    # the +1 label shift in lockstep with the sampler's parsing
    ns = (NegativeSampling.cast(neg_sampling)
          if neg_sampling is not None else None)
    self.neg_sampling = ns
    from .dist_sampler import DEFAULT_EXCHANGE_SLACK, AdaptiveSlack
    slack = resolve_exchange_slack(exchange_slack, shuffle)
    self.sampler = DistHeteroNeighborSampler(
        dataset, num_neighbors, mesh=mesh, with_edge=with_edge,
        collect_features=collect_features, seed=seed,
        exchange_slack=(DEFAULT_EXCHANGE_SLACK if slack == 'adaptive'
                        else slack),
        exchange_layout=exchange_layout)
    self._adaptive = (AdaptiveSlack(self.sampler)
                      if slack == 'adaptive' else None)
    rows, cols, colsarr = pack_link_seeds(
        pairs, edge_label, ns.mode if ns is not None else None)
    s_t, _, d_t = self.input_type
    if input_space == 'old':
      if s_t in dataset.old2new:
        colsarr[0] = dataset.old2new[s_t][rows]
      if d_t in dataset.old2new:
        colsarr[1] = dataset.old2new[d_t][cols]
    self.num_parts = dataset.num_partitions
    self.batch_size = int(batch_size)
    self._batcher = SeedBatcher(np.stack(colsarr, axis=1),
                                batch_size * self.num_parts, shuffle,
                                drop_last, seed)

  def __len__(self):
    return len(self._batcher)

  def _produce(self, seed_iter):
    from ..loader.transform import HeteroBatch
    flat = next(seed_iter)
    pairs = flat.reshape(self.num_parts, self.batch_size, -1)
    out = self.sampler.sample_from_edges(self.input_type, pairs,
                                         neg_sampling=self.neg_sampling)
    ei = {et: jnp.stack([out['row'][et], out['col'][et]], axis=1)
          for et in out['row']}
    em = {et: out['row'][et] >= 0 for et in out['row']}
    md = dict(out['metadata'])
    md['input_type'] = self.input_type
    if out['edge']:
      md['edge_dict'] = out['edge']
    return HeteroBatch(
        x_dict=out['x'], y_dict=out['y'], edge_index_dict=ei,
        edge_attr_dict=dict(out.get('ef') or {}), node_dict=out['node'],
        node_mask_dict={nt: v >= 0 for nt, v in out['node'].items()},
        edge_mask_dict=em,
        batch_dict={self.input_type[0]: out['batch']},
        batch_size=self.batch_size, metadata=md)
