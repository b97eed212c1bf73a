"""Distributed (sharded) graph + feature layout.

TPU-native replacement for the reference's per-process partition world
(`distributed/dist_dataset.py`, `dist_graph.py`, `dist_feature.py`):
instead of one dataset object per RPC worker, ONE host builds a
device-sharded layout over a `jax.sharding.Mesh`:

  * nodes are **relabeled to contiguous ownership ranges** so the
    partition book collapses to a `RangePartitionBook` (``bounds``
    [P+1]) — owner lookup is a `searchsorted`, O(P) memory, jittable
    (vs the reference's N-entry dense book, `typing.py:77`);
  * each device holds a **local CSR** of its owned nodes' out-edges
    (rows local, columns GLOBAL ids so sampled neighbors need no
    translation), padded to the max partition size and stacked
    ``[P, ...]`` for `shard_map`;
  * each device holds its **feature/label shard** ``[rows_max, D]``.

The reference's load path (`DistDataset.load` -> `load_partition` +
`cat_feature_cache`) maps to :meth:`DistDataset.from_partition_dir`.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from ..typing import RangePartitionBook
from ..utils.topo import coo_to_csr, ptr2ind


def _stack(a):
  """A ``[P, ...]`` stack as its holder keeps it: a device array (the
  shards `DistDataset.from_device_coo` built on the mesh) as it is, so
  that it never comes back to the host; anything else as a host
  array."""
  return a if isinstance(a, jax.Array) else np.asarray(a)


class DistGraph:
  """Stacked per-partition local CSRs + ownership bounds.

  Attributes:
    indptr: ``[P, max_local_nodes + 1]``.
    indices: ``[P, max_local_edges]`` (GLOBAL neighbor ids, -1 pad).
    edge_ids: ``[P, max_local_edges]`` global edge ids (-1 pad), or
      None where none were built (`DistDataset.from_device_coo`): a
      sampler asked for edge ids (``with_edge=True``) then raises.
    bounds: ``[P + 1]`` ownership ranges (RangePartitionBook).

  The stacks are host arrays, or device arrays already sharded over
  the mesh axis (`_stack`).
  """

  def __init__(self, indptr, indices, edge_ids, bounds):
    self.indptr = _stack(indptr)
    self.indices = _stack(indices)
    self.edge_ids = None if edge_ids is None else _stack(edge_ids)
    self.bounds = np.asarray(bounds, dtype=np.int64)

  @property
  def num_partitions(self) -> int:
    return len(self.bounds) - 1

  @property
  def num_nodes(self) -> int:
    return int(self.bounds[-1])

  @property
  def node_pb(self) -> RangePartitionBook:
    return RangePartitionBook(self.bounds)

  @property
  def max_local_nodes(self) -> int:
    return self.indptr.shape[1] - 1


def round_robin_book(num_nodes: int, num_parts: int,
                     seed: int = 0) -> np.ndarray:
  """THE default placement (``partitioner='range'``): a seeded random
  permutation of the nodes dealt round-robin, so partition ``p`` owns
  ``perm[p::P]`` — with ``num_nodes`` a multiple of ``P`` exactly
  ``N / P`` nodes each.  One definition for the host path
  (`DistDataset.from_full_graph`) and the device path
  (`DistDataset.from_device_coo`)."""
  rng = np.random.default_rng(seed)
  node_pb = np.empty(num_nodes, dtype=np.int32)
  perm = rng.permutation(num_nodes)
  for p in range(num_parts):
    node_pb[perm[p::num_parts]] = p
  return node_pb


def edge_width(per_part, edge_capacity: Optional[int]) -> int:
  """Width of the stacked ``indices``: the largest partition's edge
  count — which changes with the graph, and the compiled programs
  with it — or the STATED ``edge_capacity``, the same for every graph
  of a configuration.  A partition that holds more edges than the
  stated capacity is an error: never a silent drop, never a resize."""
  most = int(np.max(per_part, initial=0))
  if edge_capacity is None:
    return max(most, 1)
  if most > int(edge_capacity):
    raise ValueError(
        f'a partition holds {most} edges, over the stated '
        f'edge_capacity {int(edge_capacity)} (edges per partition: '
        f'{[int(c) for c in per_part]}); state a larger capacity')
  return int(edge_capacity)


def relabel_by_partition(node_pb: np.ndarray, num_parts: int,
                         hotness: Optional[np.ndarray] = None):
  """THE contiguous-ownership relabel — single definition shared by
  every loader path (a host-local and a single-controller load of the
  same layout must agree on the id space, or precomputed seeds/splits
  mis-address every row).  Sort nodes by (partition[, -hotness],
  old id); returns ``(old2new, counts, bounds)``."""
  node_pb = np.asarray(node_pb)
  num_nodes = len(node_pb)
  if hotness is not None:
    hot = np.asarray(hotness)
    if hot.dtype.kind == 'u':
      hot = hot.astype(np.int64)   # unsigned negation would wrap
    order = np.lexsort((np.arange(num_nodes), -hot,
                        node_pb))                    # new id -> old id
  else:
    order = np.argsort(node_pb, kind='stable')       # new id -> old id
  old2new = np.empty(num_nodes, dtype=np.int64)
  old2new[order] = np.arange(num_nodes)
  counts = np.bincount(node_pb, minlength=num_parts)
  bounds = np.concatenate([[0], np.cumsum(counts)])
  return old2new, counts, bounds


def stack_partition_csr(root, host_parts, subpath: str,
                        old2new_src, old2new_dst, bounds_src, counts_src,
                        num_parts: int):
  """Shared host-local CSR stacking (homo + hetero loaders): pad
  widths from mmap'd shapes over ALL partitions, materialize only
  ``host_parts`` — one definition so the two loaders cannot drift.

  ``subpath``: dir under ``part{i}/`` holding rows/cols/eids
  (``'graph'`` or ``'graph/<etype>'``).  Returns
  ``(indptr_s, indices_s, eids_s)`` stacked ``[len(host_parts), ...]``.
  """
  from pathlib import Path
  from ..utils.topo import coo_to_csr
  root = Path(root)
  edge_counts = [
      np.load(root / f'part{i}' / subpath / 'rows.npy',
              mmap_mode='r').shape[0] for i in range(num_parts)]
  max_edges = max(max(edge_counts), 1)
  max_nodes = int(counts_src.max()) if num_parts else 0
  pl = len(host_parts)
  indptr_s = np.zeros((pl, max_nodes + 1), np.int64)
  indices_s = np.full((pl, max_edges), -1, np.int32)
  eids_s = np.full((pl, max_edges), -1, np.int64)
  for j, p in enumerate(host_parts):
    gdir = root / f'part{p}' / subpath
    rows = np.load(gdir / 'rows.npy')
    cols = np.load(gdir / 'cols.npy')
    eids = np.load(gdir / 'eids.npy')
    local_rows = old2new_src[rows] - bounds_src[p]
    if len(local_rows) and (local_rows.min() < 0
                            or local_rows.max() >= counts_src[p]):
      raise ValueError(
          f'partition {p} ({subpath}) holds edges whose src it does '
          'not own (corrupt or non-by_src layout)')
    iptr, idx, eid = coo_to_csr(local_rows, old2new_dst[cols],
                                int(counts_src[p]), eids)
    indptr_s[j, :len(iptr)] = iptr
    indptr_s[j, len(iptr):] = iptr[-1]
    indices_s[j, :len(idx)] = idx
    eids_s[j, :len(eid)] = eid
  return indptr_s, indices_s, eids_s


def scatter_partition_rows(root, host_parts, subpath: str, fname: str,
                           old2new, bounds, max_nodes: int):
  """Shared host-local row scatter (features ``fname='feats'`` or
  labels ``fname='labels'``): stack ``[len(host_parts), max_nodes
  (, D)]`` with each partition's owned rows placed at their local
  offsets; None when the files do not exist."""
  from pathlib import Path
  root = Path(root)
  out = None
  for j, p in enumerate(host_parts):
    d = root / f'part{p}' / subpath
    if not (d / f'{fname}.npy').exists():
      continue
    vals = np.load(d / f'{fname}.npy')
    ids = np.load(d / 'ids.npy')
    if out is None:
      out = np.zeros((len(host_parts), max_nodes) + vals.shape[1:],
                     vals.dtype)
    out[j, old2new[ids] - bounds[p]] = vals
  return out


def hot_count(counts, split_ratio: float) -> np.ndarray:
  """THE hot-row arithmetic of the tiered store: how many of each
  partition's ``counts`` rows are HBM-served at ``split_ratio``.
  ONE definition shared by every site that tiers or addresses a
  tiered layout (`build_dist_feature`, `tiered_local_feature`, and
  any loader-side HBM-served predicate): the ceil-vs-round rounding
  must agree everywhere, or the builder and the lookup path silently
  disagree on which rows are hot and mis-tier the boundary row of
  every partition."""
  return np.ceil(np.asarray(counts) * float(split_ratio)).astype(
      np.int64)


_SCAN_CHUNK = 1 << 22


def partition_in_degree(root, subpath: str, num_nodes: int,
                        num_parts: int) -> np.ndarray:
  """Chunked in-degree (OLD id space) over every partition dir's
  ``cols.npy`` — the host-local twin of the single-controller
  ``np.bincount(concat(cols))`` hotness (`from_partition_dir`), so a
  host-local and a single-controller load of the same tiered layout
  produce THE SAME relabel.  mmap + fixed chunks keep RAM at
  O(num_nodes) counts, never O(E) edges."""
  from pathlib import Path
  root = Path(root)
  deg = np.zeros(num_nodes, np.int64)
  for i in range(num_parts):
    cols = np.load(root / f'part{i}' / subpath / 'cols.npy',
                   mmap_mode='r')
    for s in range(0, len(cols), _SCAN_CHUNK):
      deg += np.bincount(np.asarray(cols[s:s + _SCAN_CHUNK]),
                         minlength=num_nodes)
  return deg


def stack_partition_csr_rebucket(root, host_parts, subpath: str,
                                 node_pb, old2new_src, old2new_dst,
                                 bounds_src, counts_src, num_parts: int):
  """Host-local CSR stacking for ``by_dst`` layouts: partition dirs
  bucket edges by DST owner, so one src's out-edges are scattered
  across ALL dirs — re-bucket them by SRC owner with chunked mmap
  scans (the host-local twin of the reference's chunked re-bucketing,
  `partition/base.py:218-290`).  Pass 1 counts edges per src
  partition for the global pad width; pass 2 materializes only
  ``host_parts``.  RAM stays O(this host's edges), never O(E)."""
  from pathlib import Path
  from ..utils.topo import coo_to_csr
  root = Path(root)
  node_pb = np.asarray(node_pb)
  # pass 1 — per-src-partition edge counts over every dir
  counts_e = np.zeros(num_parts, np.int64)
  for i in range(num_parts):
    rows_f = np.load(root / f'part{i}' / subpath / 'rows.npy',
                     mmap_mode='r')
    for s in range(0, len(rows_f), _SCAN_CHUNK):
      chunk = np.asarray(rows_f[s:s + _SCAN_CHUNK])
      counts_e += np.bincount(node_pb[chunk], minlength=num_parts)
  max_edges = max(int(counts_e.max()), 1)
  max_nodes = int(counts_src.max()) if num_parts else 0
  pl = len(host_parts)
  # pass 2 — ONE more scan over the files, each chunk bucketed into
  # per-host-part accumulators (not one full scan per part: at IGBH
  # scale with P=64 that multiplies tens of GB of reads by P)
  part_of = {int(p): j for j, p in enumerate(host_parts)}
  acc = [([], [], []) for _ in range(pl)]
  for i in range(num_parts):
    gdir = root / f'part{i}' / subpath
    rows_f = np.load(gdir / 'rows.npy', mmap_mode='r')
    cols_f = np.load(gdir / 'cols.npy', mmap_mode='r')
    eids_f = np.load(gdir / 'eids.npy', mmap_mode='r')
    for s in range(0, len(rows_f), _SCAN_CHUNK):
      chunk = np.asarray(rows_f[s:s + _SCAN_CHUNK])
      owner_c = node_pb[chunk]
      cchunk = echunk = None
      for p, j in part_of.items():
        sel = owner_c == p
        if sel.any():
          if cchunk is None:
            cchunk = np.asarray(cols_f[s:s + _SCAN_CHUNK])
            echunk = np.asarray(eids_f[s:s + _SCAN_CHUNK])
          acc[j][0].append(chunk[sel])
          acc[j][1].append(cchunk[sel])
          acc[j][2].append(echunk[sel])
  indptr_s = np.zeros((pl, max_nodes + 1), np.int64)
  indices_s = np.full((pl, max_edges), -1, np.int32)
  eids_s = np.full((pl, max_edges), -1, np.int64)
  for j, p in enumerate(host_parts):
    rs, cs, es = acc[j]
    rows = np.concatenate(rs) if rs else np.empty(0, np.int64)
    cols = np.concatenate(cs) if cs else np.empty(0, np.int64)
    eids = np.concatenate(es) if es else np.empty(0, np.int64)
    local_rows = old2new_src[rows] - bounds_src[p]
    iptr, idx, eid = coo_to_csr(local_rows, old2new_dst[cols],
                                int(counts_src[p]), eids)
    indptr_s[j, :len(iptr)] = iptr
    indptr_s[j, len(iptr):] = iptr[-1]
    indices_s[j, :len(idx)] = idx
    eids_s[j, :len(eid)] = eid
  return indptr_s, indices_s, eids_s


def stack_mod_edge_features(root, host_parts, subpath: str,
                            num_parts: int, num_edges: int):
  """Host-local MOD-sharded edge-feature stacking: shard ``p`` row
  ``r`` holds edge ``r * P + p`` (`build_dist_edge_feature`
  semantics), built by scanning every partition dir's
  ``edge_feat/{feats,ids}.npy`` and materializing only the rows whose
  ``eid % P`` lands in ``host_parts`` — RAM is 1/num_hosts of the
  table while file reads stay global (the layout lives on shared
  storage, exactly like the reference's per-process `load_partition`
  reads).  Returns a `DistFeature` or None."""
  from pathlib import Path
  root = Path(root)
  part_set = {int(p): j for j, p in enumerate(host_parts)}
  pl = len(host_parts)
  rows_max = max(-(-num_edges // num_parts), 1)
  shards = None
  for i in range(num_parts):
    d = root / f'part{i}' / subpath
    if not (d / 'feats.npy').exists():
      continue
    ids = np.load(d / 'ids.npy')
    feats = np.load(d / 'feats.npy', mmap_mode='r')
    if shards is None:
      de = feats.shape[1] if feats.ndim > 1 else 1
      shards = np.zeros((pl, rows_max, de), feats.dtype)
    from .partition_book import edge_local_rows_host, edge_owner_host
    owner = edge_owner_host(ids, num_parts)
    for p, j in part_set.items():
      sel = owner == p
      if sel.any():
        vals = np.asarray(feats[sel])
        shards[j, edge_local_rows_host(ids[sel], num_parts)] = (
            vals if vals.ndim > 1 else vals[:, None])
  if shards is None:
    return None
  return DistFeature(shards, np.arange(num_parts + 1, dtype=np.int64),
                     mod_sharded=True)


def tiered_local_feature(fs: np.ndarray, counts: np.ndarray,
                         split_ratio: float, host_parts,
                         bounds) -> 'DistFeature':
  """Tier a host-local feature stack: slice each partition's hot rows
  (hottest-first after the hotness relabel) into the HBM shard and
  keep the FULL local stack as this host's cold tier.  ONE definition
  shared by the homo and hetero host-local loaders — the rounding and
  clamp must stay bit-identical to `build_dist_feature` or the
  host-local/single-controller relabel parity breaks."""
  hot_counts = hot_count(counts, split_ratio)
  hot_max = max(int(hot_counts.max()), 1)
  shards = np.zeros((len(host_parts), hot_max, fs.shape[-1]), fs.dtype)
  for j, p in enumerate(host_parts):
    shards[j, :hot_counts[p]] = fs[j, :hot_counts[p]]
  return DistFeature(shards, bounds, hot_counts=hot_counts,
                     cold_local=fs)


def stack_partition_cache(root, host_parts, subpath: str, old2new,
                          num_parts: int):
  """Host-local offline-cache-plan stacking: every partition's cache
  file is self-contained (its own REMOTE-hot rows), so each host reads
  only its partitions' files; the pad width ``C`` comes from mmap'd
  SHAPES across all partitions (the stacked arrays must agree
  globally).  Returns ``(cache_ids [pl, C], cache_rows [pl, C, D])``
  sorted by relabeled id, or ``(None, None)``."""
  from pathlib import Path
  root = Path(root)
  sizes = []
  for i in range(num_parts):
    f = root / f'part{i}' / subpath / 'cache_ids.npy'
    sizes.append(np.load(f, mmap_mode='r').shape[0] if f.exists() else 0)
  cmax = max(sizes, default=0)
  if cmax == 0:
    return None, None
  pl = len(host_parts)
  ids_out = np.full((pl, cmax), CACHE_PAD_ID, np.int32)
  rows_out = None
  for j, p in enumerate(host_parts):
    d = root / f'part{p}' / subpath
    if not (d / 'cache_ids.npy').exists():
      continue
    cid = np.load(d / 'cache_ids.npy')
    cfeat = np.load(d / 'cache_feats.npy')
    if rows_out is None:
      rows_out = np.zeros((pl, cmax, cfeat.shape[1]), cfeat.dtype)
    new = old2new[cid].astype(np.int32)
    order = np.argsort(new)
    ids_out[j, :len(cid)] = new[order]
    rows_out[j, :len(cid)] = cfeat[order]
  if rows_out is None:
    return None, None
  return ids_out, rows_out


def build_dist_graph(rows: np.ndarray, cols: np.ndarray,
                     node_pb: np.ndarray, num_nodes: int,
                     edge_ids: Optional[np.ndarray] = None,
                     num_parts: Optional[int] = None,
                     hotness: Optional[np.ndarray] = None,
                     edge_capacity: Optional[int] = None
                     ) -> Tuple[DistGraph, np.ndarray]:
  """Relabel + shard a COO graph by a node partition book.

  Returns ``(dist_graph, old2new)`` — feed seeds/features through
  ``old2new`` to enter the relabeled id space.  Pass ``num_parts``
  explicitly when trailing partitions may be empty (the book's max
  value alone would under-count them).

  ``hotness`` (optional ``[N]``) orders rows WITHIN each partition
  hottest-first, so a tiered feature store's ``split_ratio`` keeps the
  hottest rows in HBM — the sharded analog of `sort_by_in_degree`
  (reference `data/reorder.py:19-31`).

  ``edge_capacity`` states the width of the stacked ``indices``
  (`edge_width`): with it every graph of a configuration gives the
  same shard shapes, so one compiled program serves them all; without
  it the width is the largest partition's edge count of THIS graph.
  """
  node_pb = np.asarray(node_pb)
  if num_parts is None:
    num_parts = int(node_pb.max()) + 1 if node_pb.size else 1
  old2new, counts, bounds = relabel_by_partition(node_pb, num_parts,
                                                 hotness)

  rows_n = old2new[np.asarray(rows)]
  cols_n = old2new[np.asarray(cols)]
  if edge_ids is None:
    edge_ids = np.arange(len(rows_n), dtype=np.int64)

  # per-partition local CSR (rows local, cols global).
  max_nodes = int(counts.max()) if num_parts else 0
  owner = node_pb[np.asarray(rows)]
  max_edges = edge_width(np.bincount(owner, minlength=num_parts),
                         edge_capacity)
  indptr_s = np.zeros((num_parts, max_nodes + 1), dtype=np.int64)
  indices_s = np.full((num_parts, max_edges), -1, dtype=np.int32)
  eids_s = np.full((num_parts, max_edges), -1, dtype=np.int64)
  for p in range(num_parts):
    sel = owner == p
    local_rows = rows_n[sel] - bounds[p]
    iptr, idx, eid = coo_to_csr(local_rows, cols_n[sel],
                                int(counts[p]), edge_ids[sel])
    # pad indptr by repeating the terminal value so padded local rows
    # have degree zero.
    indptr_s[p, :len(iptr)] = iptr
    indptr_s[p, len(iptr):] = iptr[-1]
    indices_s[p, :len(idx)] = idx
    eids_s[p, :len(eid)] = eid
  return DistGraph(indptr_s, indices_s, eids_s, bounds), old2new


def restack_stream_view(view, old2new: np.ndarray, bounds: np.ndarray,
                        min_edge_width: int = 0):
  """Re-shard one published streaming `GraphView` by an EXISTING
  partition book (ISSUE 14: the mesh arm of version fencing).

  The view lives in the original (old) id space; ``old2new`` and
  ``bounds`` are the dataset's frozen relabel + ownership — features,
  labels, caches and the GNS hot split are all built against them, so
  a streamed topology refresh must never move a node.  Edges are
  recovered in EVENT order (``argsort(edge_ids)`` — edge ids are the
  global event positions) and pushed through the exact
  `build_dist_graph` per-partition ``coo_to_csr`` path, so a quiesced
  streamed mesh graph is byte-identical to `DistDataset.from_full_graph`
  over the same event sequence (pinned by tests).

  ``min_edge_width`` floors the stacked indices width (the previous
  stack's width): shapes only GROW, and only to the next power of two
  — a compiled mesh step recompiles logarithmically over any growth,
  never per publish.
  """
  from ..utils.padding import next_power_of_two
  bounds = np.asarray(bounds, np.int64)
  num_parts = len(bounds) - 1
  counts = np.diff(bounds)
  max_nodes = int(counts.max()) if num_parts else 0
  order = np.argsort(np.asarray(view.edge_ids), kind='stable')
  rows_old = ptr2ind(np.asarray(view.indptr))[order]
  cols_old = np.asarray(view.indices)[order]
  eids = np.asarray(view.edge_ids)[order]
  rows_n = np.asarray(old2new)[rows_old]
  cols_n = np.asarray(old2new)[cols_old]
  from .partition_book import range_of_host
  owner = range_of_host(bounds, rows_n)
  per_part = np.bincount(owner, minlength=num_parts)
  width = max(next_power_of_two(max(int(per_part.max(initial=0)), 1)),
              int(min_edge_width))
  indptr_s = np.zeros((num_parts, max_nodes + 1), dtype=np.int64)
  indices_s = np.full((num_parts, width), -1, dtype=np.int32)
  eids_s = np.full((num_parts, width), -1, dtype=np.int64)
  for p in range(num_parts):
    sel = owner == p
    local_rows = rows_n[sel] - bounds[p]
    iptr, idx, eid = coo_to_csr(local_rows, cols_n[sel],
                                int(counts[p]), eids[sel])
    indptr_s[p, :len(iptr)] = iptr
    indptr_s[p, len(iptr):] = iptr[-1]
    indices_s[p, :len(idx)] = idx
    eids_s[p, :len(eid)] = eid
  return indptr_s, indices_s, eids_s


CACHE_PAD_ID = np.iinfo(np.int32).max  # sorts AFTER every real id


class DistFeature:
  """Stacked per-partition feature shards + optional remote-hot cache
  + optional host-DRAM cold tier.

  Attributes:
    shards: ``[P, hot_max, D]`` HBM-bound hot rows (zero where padded).
      When untier'd (``split_ratio=1``), ``hot_max = rows_max`` and the
      table is fully device-resident.
    bounds: ``[P + 1]`` — row ``r`` of shard ``p`` holds global id
      ``bounds[p] + r``.
    hot_counts: ``[P]`` hot rows per partition: id ``g`` is HBM-served
      iff ``g - bounds[owner] < hot_counts[owner]``.
    cold_host: optional ``[N, D]`` host-DRAM table addressed by
      relabeled global id — the TPU-VM analog of the reference's
      pinned-CPU UVA chunk (`csrc/cuda/unified_tensor.cu:202+`,
      `data/feature.py:174-206`): cold misses are host-gathered per
      batch and overlaid post-exchange (`DistNeighborSampler.
      _overlay_cold`).  None = fully HBM-resident.
    cold_local: optional ``[len(host_parts), max_nodes, D]`` host-DRAM
      stack holding only THIS HOST'S partitions' rows (local offsets)
      — the multi-host form of the cold tier: each host keeps
      1/num_hosts of the cold bytes and serves them at the OWNER via
      the second-gather overlay (`dist_sampler.overlay_cold_owner`).
      Mutually exclusive with ``cold_host``.
    cache_ids: optional ``[P, C]`` SORTED (relabeled) ids of remote
      rows partition ``p`` caches locally, ``CACHE_PAD_ID``-padded —
      the collective-era `cat_feature_cache`
      (`partition/base.py:606-647`): lookups hit the cache first and
      only misses ride the all_to_all.
    cache_rows: optional ``[P, C, D]`` the cached rows.
  """

  def __init__(self, shards, bounds, cache_ids=None, cache_rows=None,
               mod_sharded: bool = False, hot_counts=None,
               cold_host=None, cold_local=None,
               cache_local: bool = False):
    self.shards = _stack(shards)
    self.bounds = np.asarray(bounds, dtype=np.int64)
    self.hot_counts = (np.asarray(hot_counts, np.int32)
                       if hot_counts is not None
                       else np.diff(self.bounds).astype(np.int32))
    self.cold_host = (np.asarray(cold_host)
                      if cold_host is not None else None)
    self.cold_local = (np.asarray(cold_local)
                       if cold_local is not None else None)
    assert self.cold_host is None or self.cold_local is None
    self.cache_ids = (np.asarray(cache_ids, np.int32)
                      if cache_ids is not None else None)
    self.cache_rows = (np.asarray(cache_rows)
                       if cache_rows is not None else None)
    #: True = strided ownership (owner = id % P, row = id // P) —
    #: `build_dist_edge_feature`; False = range ownership by `bounds`.
    self.mod_sharded = mod_sharded
    #: True = the cache is the ISSUE 20 read-only replica set: the
    #: sampler's feature lookup treats cached rows as LOCAL (they are
    #: masked out of the exchange request and overlaid from the
    #: replica, and the attribution credits them to the diagonal).
    #: False (offline cache plans) keeps the post-exchange-overlay
    #: semantics — identical exchanged bytes.
    self.cache_local = cache_local

  @property
  def feature_dim(self) -> int:
    return self.shards.shape[-1]

  @property
  def has_cache(self) -> bool:
    return self.cache_ids is not None and self.cache_ids.shape[1] > 0

  @property
  def is_tiered(self) -> bool:
    return self.cold_host is not None or self.cold_local is not None


def build_feature_cache(cache_ids_old, cache_feats, old2new, num_parts):
  """Assemble per-partition sorted cache arrays from the offline
  layout's ``cache_ids/cache_feats`` (old id space)."""
  cmax = max((len(c) for c in cache_ids_old), default=0)
  if cmax == 0:
    return None, None
  d = next(f.shape[1] for f in cache_feats if f is not None and len(f))
  dtype = next(f.dtype for f in cache_feats if f is not None and len(f))
  ids = np.full((num_parts, cmax), CACHE_PAD_ID, np.int32)
  rows = np.zeros((num_parts, cmax, d), dtype)
  for p in range(num_parts):
    cid = np.asarray(cache_ids_old[p], np.int64)
    if not len(cid):
      continue
    new = old2new[cid].astype(np.int32)
    order = np.argsort(new)
    ids[p, :len(cid)] = new[order]
    rows[p, :len(cid)] = np.asarray(cache_feats[p])[order]
  return ids, rows


def build_replica_cache(feats_new: np.ndarray, bounds: np.ndarray,
                        hotness_new: np.ndarray, frac: float):
  """Mesh-plane `cat_feature_cache` analog (ISSUE 20): replicate the
  globally hottest rows read-only into every partition's cache so the
  PartitionBook-routed feature lookup can serve them locally.

  Each partition caches the top ``ceil(frac * N)`` hottest rows it
  does NOT own (its own rows are already local); ``hotness_new`` ranks
  in the RELABELED id space (a `DecayedSketch` export or in-degree).
  Returns ``(cache_ids [P, C] sorted CACHE_PAD_ID-padded,
  cache_rows [P, C, D])`` or ``(None, None)`` at a zero budget.
  """
  bounds = np.asarray(bounds, np.int64)
  num_parts = len(bounds) - 1
  n = int(bounds[-1])
  c = int(np.ceil(float(frac) * n))
  if c <= 0 or n == 0:
    return None, None
  feats_new = np.asarray(feats_new)
  if feats_new.ndim == 1:
    feats_new = feats_new[:, None]
  hot = np.asarray(hotness_new, np.float64)
  order = np.argsort(-hot, kind='stable')        # hottest first, stable
  ids = np.full((num_parts, c), CACHE_PAD_ID, np.int32)
  rows = np.zeros((num_parts, c, feats_new.shape[1]), feats_new.dtype)
  for p in range(num_parts):
    remote = order[(order < bounds[p]) | (order >= bounds[p + 1])][:c]
    remote = np.sort(remote)
    ids[p, :len(remote)] = remote
    rows[p, :len(remote)] = feats_new[remote]
  from ..telemetry.live import live
  live.gauge('partition.replicated_rows').set(float(c))
  return ids, rows


def replica_budget_frac(replica_frac=None) -> float:
  """Resolve the replication budget: argument wins, else the
  ``GLT_LOCALITY_REPLICA_FRAC`` knob (fraction of ALL nodes each
  device replicates; 0 = no replica cache, the default)."""
  import os
  if replica_frac is not None:
    return float(replica_frac)
  try:
    return float(os.environ.get('GLT_LOCALITY_REPLICA_FRAC', 0.0))
  except ValueError:
    return 0.0


def build_dist_feature(feats: np.ndarray, old2new: np.ndarray,
                       bounds: np.ndarray,
                       split_ratio: float = 1.0) -> DistFeature:
  """Shard a feature table by the relabeled ownership ranges.

  ``split_ratio < 1`` builds the TIERED store (reference `data/feature.py:174-206` + `unified_tensor.cu:202+`):
  only the first ``ceil(split_ratio * rows)`` rows of each partition —
  the hottest, when the relabel was built with ``hotness`` — go to the
  HBM shard; the full table stays in host DRAM as the cold tier, so
  the distributed store serves tables larger than aggregate HBM.
  """
  feats = np.asarray(feats)
  if feats.ndim == 1:
    feats = feats[:, None]
  num_parts = len(bounds) - 1
  counts = np.diff(bounds)
  split_ratio = float(split_ratio)
  if not 0.0 <= split_ratio <= 1.0:
    raise ValueError(f'split_ratio must be in [0, 1], got {split_ratio}')
  tiered = split_ratio < 1.0
  hot_counts = (hot_count(counts, split_ratio)
                if tiered else counts.astype(np.int64))
  hot_max = int(hot_counts.max()) if num_parts else 0
  if tiered:
    hot_max = max(hot_max, 1)   # keep the gather shape non-degenerate
                                # at split_ratio=0 (rows stay masked)
  shards = np.zeros((num_parts, hot_max, feats.shape[1]), feats.dtype)
  reordered = np.empty_like(feats)
  reordered[old2new] = feats          # new id -> features
  for p in range(num_parts):
    shards[p, :hot_counts[p]] = (
        reordered[bounds[p]:bounds[p] + hot_counts[p]])
  return DistFeature(shards, bounds, hot_counts=hot_counts,
                     cold_host=reordered if tiered else None)


def build_dist_edge_feature(efeats: np.ndarray,
                            num_parts: int) -> DistFeature:
  """MOD-shard an edge-feature table ``[E, De]`` (indexed by GLOBAL
  edge id): shard ``p`` row ``r`` holds edge ``r * P + p``.

  Edge ids are stable through the node relabel (`build_dist_graph`
  keeps the input edge order), so no id map is needed — the collective
  analog of the reference's separate ``edge_feat_pb``
  (`distributed/dist_dataset.py:183-193`).  Mod (strided) assignment,
  not ranges, on purpose: a node's out-edges have CONSECUTIVE ids in
  the usual COO order, so range sharding would send one seed's whole
  edge set to a single owner and systematically overflow the
  capacity-bounded gather; mod sharding spreads every consecutive run
  evenly, making the balanced-share capacity assumption hold by
  construction.
  """
  efeats = np.asarray(efeats)
  if efeats.ndim == 1:
    efeats = efeats[:, None]
  e = efeats.shape[0]
  rows_max = max(-(-e // num_parts), 1)
  shards = np.zeros((num_parts, rows_max, efeats.shape[1]), efeats.dtype)
  for p in range(num_parts):
    own = efeats[p::num_parts]
    shards[p, :len(own)] = own
  return DistFeature(shards, np.arange(num_parts + 1, dtype=np.int64),
                     mod_sharded=True)


def exchange_width(edge_capacity: int, num_parts: int) -> int:
  """What one device may hold for one owner in the shard build's
  exchange: ``ceil(edge_capacity / P)`` — blocks that mix the owners
  evenly, as any block of a shuffled COO under a round-robin placement
  does, send each owner a ``P``-th of what it may hold."""
  return -(-int(edge_capacity) // int(num_parts))


def coo_shard_program(mesh, axis: str, max_nodes: int,
                      edge_capacity: int):
  """The jitted program of `shard_coo_on_mesh`: ``(rows [E], cols [E],
  old2new [N], bounds [P + 1]) -> (indptr [P, max_nodes + 1], indices
  [P, edge_capacity], sent [P, P])``, the COO and the outputs sharded
  over ``axis``, the two tables replicated, everything int32.  Its
  shapes follow the arguments here and ``E``, ``N`` alone."""
  import jax.numpy as jnp
  from jax.sharding import PartitionSpec as P
  from .shard_map_compat import shard_map
  num_parts = mesh.shape[axis]
  cap, send = int(edge_capacity), exchange_width(edge_capacity, num_parts)
  big = np.iinfo(np.int32).max

  def per_device(rows, cols, o2n, lo):
    ok = rows >= 0
    r = jnp.where(ok, o2n[jnp.where(ok, rows, 0)], big)
    c = jnp.where(ok, o2n[jnp.where(ok, cols, 0)], -1)
    # new ids are contiguous per owner: sorted by new row id, owner
    # p's edges are one run
    r, c = jax.lax.sort((r, c), num_keys=1)
    starts = jnp.searchsorted(r, lo).astype(jnp.int32)        # [P + 1]
    sent = starts[1:] - starts[:-1]
    # a run may start within ``send`` of the block's end
    r = jnp.concatenate([r, jnp.full((send,), big, jnp.int32)])
    c = jnp.concatenate([c, jnp.full((send,), -1, jnp.int32)])
    lane = jnp.arange(send, dtype=jnp.int32)
    out_r, out_c = [], []
    for p in range(num_parts):
      keep = lane < sent[p]
      run_r = jax.lax.dynamic_slice(r, (starts[p],), (send,)) - lo[p]
      run_c = jax.lax.dynamic_slice(c, (starts[p],), (send,))
      out_r.append(jnp.where(keep, run_r, big))
      out_c.append(jnp.where(keep, run_c, -1))
    got_r = jax.lax.all_to_all(jnp.stack(out_r), axis, 0, 0)
    got_c = jax.lax.all_to_all(jnp.stack(out_c), axis, 0, 0)
    # rows, then columns within a row: `utils.topo.coo_to_csr`'s order
    lr, lc = jax.lax.sort((got_r.reshape(-1), got_c.reshape(-1)),
                          num_keys=2)
    if lc.shape[0] < cap:
      lc = jnp.concatenate(
          [lc, jnp.full((cap - lc.shape[0],), -1, jnp.int32)])
    indptr = jnp.searchsorted(
        lr, jnp.arange(max_nodes + 1, dtype=jnp.int32),
        side='left').astype(jnp.int32)
    return indptr[None], lc[None, :cap], sent[None]

  return jax.jit(shard_map(
      per_device, mesh=mesh, in_specs=(P(axis), P(axis), P(), P()),
      out_specs=(P(axis), P(axis), P(axis))))


def shard_coo_on_mesh(rows, cols, old2new: np.ndarray,
                      bounds: np.ndarray, mesh, axis: str,
                      edge_capacity: int):
  """`build_dist_graph`'s stacks, built ON the mesh from a COO that
  lives there: ``(indptr [P, max_nodes + 1], indices [P,
  edge_capacity], sent [P, P])``, the first two sharded over ``axis``
  and equal to the host path's byte for byte (as a sampler places
  them: int32), ``sent[d, p]`` the edges device ``d`` held for owner
  ``p`` (a host array; its column sums are the partitions' counts).

  ``rows`` / ``cols``: ``[E]`` ids in the original space (``-1``
  pads), device arrays or anything `jax.device_put` takes; each device
  works on the ``E / P`` block the mesh sharding gives it and the host
  never holds the COO.  Per device, one program
  (`coo_shard_program`): relabel both ends through ``old2new``
  (replicated, int32), sort the block by new row id, send each owner
  its run (one ``all_to_all`` of ``[P, exchange_width]`` buckets of
  local rows and new columns), sort what arrived by (row, column), and
  read ``indptr`` off the sorted rows.

  ``edge_capacity`` is STATED, so the program's shapes do not follow
  the graph: it is the width of ``indices`` (`edge_width`), and what
  one device may hold for one owner follows from it
  (`exchange_width`).  A graph over either raises `ValueError` once
  the counts are back — never a silent drop or a resize."""
  import jax.numpy as jnp
  from jax.sharding import NamedSharding, PartitionSpec as P
  from ..utils.padding import round_up
  bounds = np.asarray(bounds, np.int64)
  num_parts = len(bounds) - 1
  cap, send = int(edge_capacity), exchange_width(edge_capacity, num_parts)
  shard = NamedSharding(mesh, P(axis))
  repl = NamedSharding(mesh, P())

  def block(a):
    a = jnp.asarray(a, jnp.int32).reshape(-1)
    pad = round_up(a.shape[0], num_parts) - a.shape[0]
    if pad:
      a = jnp.concatenate([a, jnp.full((pad,), -1, jnp.int32)])
    return jax.device_put(a, shard)

  indptr, indices, sent = coo_shard_program(
      mesh, axis, int(np.diff(bounds).max()), cap)(
          block(rows), block(cols),
          jax.device_put(np.asarray(old2new).astype(np.int32), repl),
          jax.device_put(bounds.astype(np.int32), repl))
  sent = np.asarray(sent)
  if int(sent.max(initial=0)) > send:
    d, p = np.unravel_index(int(np.argmax(sent)), sent.shape)
    raise ValueError(
        f'device {d} holds {int(sent[d, p])} edges for partition {p}, '
        f'over the exchange width {send} (edge_capacity {cap} / '
        f'{num_parts}); state a larger edge_capacity, or hand the COO '
        'in shuffled, so that every block mixes the owners evenly')
  edge_width(sent.sum(0), cap)          # raises over the capacity
  return indptr, indices, sent


def owned_ids_on_mesh(new2old: np.ndarray, bounds: np.ndarray, mesh,
                      axis: str):
  """``[P, rows_max]`` int32, sharded over ``axis``: the original ids
  each device owns, in the relabelled order, ``-1`` past its count."""
  from jax.sharding import NamedSharding, PartitionSpec as P
  bounds = np.asarray(bounds, np.int64)
  counts = np.diff(bounds)
  owned = np.full((len(counts), int(counts.max())), -1, np.int32)
  for p, count in enumerate(counts):
    owned[p, :count] = new2old[bounds[p]:bounds[p + 1]]
  return jax.device_put(owned, NamedSharding(mesh, P(axis)))


def shard_rows_on_mesh(source, owned, mesh, axis: str):
  """`build_dist_feature`'s untiered shards (``[P, rows_max, D]``; a
  1-D table gives the label stack ``[P, rows_max]``), built ON the
  mesh: device ``p`` takes the rows of the original ids it owns
  (``owned``: `owned_ids_on_mesh`), rows past its count zero.

  ``source`` is the ``[N, ...]`` table in the original id order —
  replicated over the mesh first, so it must fit one device, as the
  one-chip `Dataset`'s does — or, for a table no device holds whole,
  ``(f, operands)``: a jittable ``f(old_ids, *operands) -> rows`` that
  each device calls on the ``[rows_max]`` ids it owns (pads asked as
  id 0 and zeroed).  Operands are replicated and enter the program as
  ARGUMENTS, so one compiled program serves every value of them (a
  random key, say)."""
  import jax.numpy as jnp
  from jax.sharding import NamedSharding, PartitionSpec as P
  from .shard_map_compat import shard_map
  if isinstance(source, tuple):
    fn, operands = source
  else:
    fn, operands = (lambda ids, table: table[ids]), (jnp.asarray(source),)
  operands = jax.device_put(tuple(operands), NamedSharding(mesh, P()))

  def per_device(ids, *operands):
    ids = ids[0]
    ok = ids >= 0
    rows = fn(jnp.where(ok, ids, 0), *operands)
    ok = ok.reshape(ok.shape + (1,) * (rows.ndim - 1))
    return jnp.where(ok, rows, jnp.zeros((), rows.dtype))[None]

  return jax.jit(shard_map(
      per_device, mesh=mesh,
      in_specs=(P(axis),) + (P(),) * len(operands), out_specs=P(axis)))(
          owned, *operands)


class DistDataset:
  """Sharded dataset: graph + features + labels in the relabeled space.

  Attributes:
    graph: `DistGraph`.
    node_features: `DistFeature` or None.
    node_labels: ``[P, rows_max]`` stacked label shards or None.
    edge_features: `DistFeature` MOD-sharded over GLOBAL edge ids
      (owner = eid % P; see `build_dist_edge_feature`) or None.
    old2new / new2old: id-space maps.
  """

  def __init__(self, graph: DistGraph, node_features=None, node_labels=None,
               old2new: Optional[np.ndarray] = None, edge_features=None,
               host_parts: Optional[np.ndarray] = None):
    self.graph = graph
    self.node_features = node_features
    self.node_labels = node_labels
    self.edge_features = edge_features
    self.old2new = old2new
    self.new2old = (np.argsort(old2new) if old2new is not None else None)
    #: multi-host: the partition indices THIS process materialized
    #: (stacked arrays then hold only these, in this order) — see
    #: `from_partition_dir(host_parts=...)`.  None = all partitions.
    self.host_parts = (np.asarray(host_parts, np.int64)
                       if host_parts is not None else None)
    #: placement identity ('range' | 'locality' | 'custom' |
    #: 'explicit') — recorded so that runs are never compared across
    #: a partitioner change.
    self.partitioner = 'explicit'
    self._partition_book = None
    #: ISSUE 15: durably re-loaded shards parked by `failover.
    #: adopt_shard`, keyed by the ORPHANED partition index.  Samplers
    #: build the adopted lane's device arrays from these payloads (the
    #: bytes that survived, not the dead owner's live memory).
    self.adopted_shards = {}

  @property
  def num_partitions(self) -> int:
    return self.graph.num_partitions

  @property
  def partition_book(self):
    """THE routing authority (ISSUE 15): one `PartitionBook` per
    dataset, shared by every sampler/loader/driver built over it so an
    adoption observed by one reader is observed by all at their next
    fence.  Version 0 (identity) compiles the pre-book programs."""
    if self._partition_book is None:
      from .partition_book import PartitionBook
      self._partition_book = PartitionBook(self.graph.bounds)
    return self._partition_book

  def attach_stream(self, stream) -> 'DistDataset':
    """Back this dataset's topology with a streaming graph (ISSUE
    14).  The stream lives in the ORIGINAL (old) id space; the
    dataset's relabel/ownership stay frozen (features, caches and the
    GNS hot split are built against them) and only the per-partition
    CSR stacks refresh.  Samplers pick the handle up at their
    dispatch/chunk seams (`DistNeighborSampler.maybe_refresh_stream`)
    — one published ``graph_version`` per dispatch, never a torn
    stack.  Single-controller only: the multi-host restack (each host
    re-sharding its own partitions) is follow-on work."""
    if self.host_parts is not None:
      raise NotImplementedError(
          'streaming refresh of a multi-host (host_parts) layout is '
          'not supported yet — each host would need to restack its '
          'own partitions from the stream')
    if self.edge_features is not None:
      raise NotImplementedError(
          'attach_stream on a dataset with edge features is not '
          'supported yet — streamed edges get eids past the frozen '
          'edge-feature shards, so collect_edge_features would '
          'gather wrong rows (growable edge-feature tiers are '
          'follow-on work)')
    if self.old2new is None:
      raise ValueError('attach_stream needs a dataset with an '
                       'old2new relabel (from_full_graph-style)')
    self.stream = stream
    view = stream.pin()
    g = self.graph
    indptr_s, indices_s, eids_s = restack_stream_view(
        view, self.old2new, g.bounds,
        min_edge_width=int(g.indices.shape[1]))
    self.graph = DistGraph(indptr_s, indices_s, eids_s, g.bounds)
    #: the version self.graph's stacks were built from — samplers
    #: seed their seam fence here so the first dispatch skips a
    #: redundant restack of the identical graph
    self.stream_version = view.version
    return self

  @classmethod
  def from_full_graph(cls, num_parts: int, rows, cols, node_feat=None,
                      node_label=None, num_nodes: Optional[int] = None,
                      node_pb: Optional[np.ndarray] = None,
                      seed: int = 0, edge_feat=None,
                      split_ratio: float = 1.0,
                      hotness: Optional[np.ndarray] = None,
                      partitioner=None,
                      replica_frac: Optional[float] = None,
                      edge_capacity: Optional[int] = None
                      ) -> 'DistDataset':
    """In-memory partition + shard (testing & single-host path).

    ``edge_capacity`` states the per-partition width of ``indices``
    (`edge_width`; `build_dist_graph`): the graphs of one
    configuration then give identical shard shapes — and
    `from_device_coo`, handed the same capacity, the same bytes —
    where the default follows the largest partition of this graph.

    ``split_ratio < 1`` tiers the node-feature store (HBM hot /
    host-DRAM cold, see `build_dist_feature`); ``hotness`` defaults to
    in-degree so the HBM tier keeps the most-gathered rows
    (`sort_by_in_degree` policy, reference `data/reorder.py:19-31`).

    ``partitioner`` (ISSUE 20) selects node placement when ``node_pb``
    is not given: ``'range'`` (default / ``GLT_PARTITIONER`` unset) is
    the historical seeded random round-robin, byte-identical to the
    pre-locality path; ``'locality'`` runs the
    `locality.locality_partition` streaming edge-cut minimizer
    (hotness-weighted when ``hotness`` — an array or a `DecayedSketch`
    — is supplied); an array is taken as a precomputed ``node_pb``
    (e.g. the offline `FrequencyPartitioner` output); a callable is
    invoked as ``partitioner(rows, cols, num_nodes, num_parts)``.
    Every mode relabels through the same `build_dist_graph` path and
    the dataset carries ``old2new``/``new2old`` so batches, labels and
    served predictions surface original ids.

    ``replica_frac > 0`` (or ``GLT_LOCALITY_REPLICA_FRAC``) builds the
    read-only replica cache (`build_replica_cache`): each device
    additionally holds the top ``ceil(frac * N)`` hottest REMOTE
    feature rows and the sampler serves them as local.
    """
    from .locality import resolve_partitioner
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    n = int(num_nodes if num_nodes is not None
            else max(rows.max(initial=-1), cols.max(initial=-1)) + 1)
    if hotness is not None and hasattr(hotness, 'score'):
      hotness = hotness.score(np.arange(n))    # DecayedSketch export
    part_identity = 'explicit'
    if node_pb is None:
      part = resolve_partitioner(partitioner)
      if isinstance(part, str) and part == 'range':
        part_identity = 'range'
        node_pb = round_robin_book(n, num_parts, seed)
      elif isinstance(part, str):              # 'locality'
        from .locality import locality_partition
        part_identity = 'locality'
        if hotness is None:
          hotness = np.bincount(cols, minlength=n)   # in-degree
        node_pb, _ = locality_partition(rows, cols, n, num_parts,
                                        seed=seed, hotness=hotness)
      elif callable(part):
        part_identity = 'custom'
        node_pb = np.asarray(part(rows, cols, n, num_parts))
      else:
        part_identity = 'custom'
        node_pb = part
    if split_ratio < 1.0 and hotness is None:
      hotness = np.bincount(cols, minlength=n)       # in-degree
    g, old2new = build_dist_graph(rows, cols, node_pb, n,
                                  num_parts=num_parts, hotness=hotness,
                                  edge_capacity=edge_capacity)
    nf = (build_dist_feature(node_feat, old2new, g.bounds,
                             split_ratio=split_ratio)
          if node_feat is not None else None)
    rep = replica_budget_frac(replica_frac)
    if nf is not None and rep > 0:
      feats = np.asarray(node_feat)
      if feats.ndim == 1:
        feats = feats[:, None]
      feats_new = np.empty_like(feats)
      feats_new[old2new] = feats
      rank = (np.asarray(hotness) if hotness is not None
              else np.bincount(cols, minlength=n))
      rank_new = np.empty(n, np.float64)
      rank_new[old2new] = rank
      cids, crows = build_replica_cache(feats_new, g.bounds, rank_new,
                                        rep)
      if cids is not None:
        nf.cache_ids, nf.cache_rows = cids, crows
        nf.cache_local = True
    nl = None
    if node_label is not None:
      # build_dist_feature preserves dtype — no float round-trip.
      lab = np.asarray(node_label)
      nl = build_dist_feature(lab, old2new, g.bounds).shards[..., 0]
    ef = (build_dist_edge_feature(edge_feat, num_parts)
          if edge_feat is not None else None)
    ds = cls(g, nf, nl, old2new, edge_features=ef)
    ds.partitioner = part_identity
    return ds

  @classmethod
  def from_device_coo(cls, num_parts: int, rows, cols, *,
                      num_nodes: int, edge_capacity: int,
                      node_feat=None, node_label=None, mesh=None,
                      axis: str = 'data', seed: int = 0
                      ) -> 'DistDataset':
    """`from_full_graph`'s dataset, with every shard built on the mesh
    it will be sampled on — the way in for a graph that lives on the
    devices (as the one-chip `Dataset` takes device arrays) or is too
    large for the host to partition: the COO is never on the host, and
    a table no device holds whole never is in one place.

    The partition book is `from_full_graph`'s default: the seeded
    round-robin placement (`round_robin_book`) and the same relabel
    (`relabel_by_partition`) — ``old2new``, ``bounds`` and with them
    every id a batch surfaces are the host path's.  ``rows`` /
    ``cols`` are the ``[E]`` COO in original ids
    (`shard_coo_on_mesh`); ``node_feat`` / ``node_label`` a table in
    original id order that fits one device, or ``(f, operands)`` with
    a jittable ``f(old_ids, *operands) -> rows``
    (`shard_rows_on_mesh`).

    ``edge_capacity`` is REQUIRED: the stated width of each device's
    ``indices`` (a configuration's mean edge count per device plus its
    margin).  What one device may hold for one owner in the build's
    exchange follows from it (`exchange_width`).  Shapes follow it and
    ``num_nodes`` alone, so every graph of a configuration runs the
    same compiled programs, the build's included; a graph over it —
    a partition's edges, or one block's edges for one owner — raises.
    Handed the same capacity,
    `from_full_graph` gives the same bytes (``indptr``, ``indices``,
    feature and label shards as a sampler places them; held by
    ``tests/test_device_shards.py``).  No edge ids are built: a
    sampler that needs them (``with_edge=True``) says so.  Tiered
    stores, replica caches, edge features and hotness orders stay
    `from_full_graph`'s.

    The build runs inside a ``dist.shard_build`` span whose fields —
    and ``ds.shard_build`` — give the nodes and edges each device
    holds, the stated capacity with the exchange width it gives, and
    the seconds it took, the devices' work included.
    """
    import time
    from ..telemetry.spans import span
    from .dp import make_mesh
    mesh = mesh or make_mesh(num_parts, axis)
    n = int(num_nodes)
    cap = int(edge_capacity)
    built = dict(edge_capacity=cap,
                 exchange_capacity=exchange_width(cap, num_parts))
    build_span = span('dist.shard_build', num_parts=num_parts, **built)
    with build_span:
      t0 = time.monotonic()
      old2new, counts, bounds = relabel_by_partition(
          round_robin_book(n, num_parts, seed), num_parts)
      indptr, indices, sent = shard_coo_on_mesh(
          rows, cols, old2new, bounds, mesh, axis, cap)
      ds = cls(DistGraph(indptr, indices, None, bounds),
               old2new=old2new)
      owned = owned_ids_on_mesh(ds.new2old, bounds, mesh, axis)
      if node_feat is not None:
        ds.node_features = DistFeature(
            shard_rows_on_mesh(node_feat, owned, mesh, axis), bounds)
      if node_label is not None:
        ds.node_labels = shard_rows_on_mesh(node_label, owned, mesh, axis)
      jax.block_until_ready(
          (ds.node_features and ds.node_features.shards, ds.node_labels))
      # the span's end event carries what the build found
      built.update(nodes=[int(c) for c in counts],
                   edges=[int(c) for c in sent.sum(0)])
      build_span.fields.update(built)
      built['secs'] = time.monotonic() - t0
    ds.partitioner = 'range'
    #: what the build found and took: the span's fields, and the
    #: seconds inside it
    ds.shard_build = built
    return ds

  @classmethod
  def from_partition_dir(cls, root, num_parts: Optional[int] = None,
                         split_ratio: float = 1.0,
                         host_parts=None) -> 'DistDataset':
    """Assemble from the offline partitioner's layout
    (reference `DistDataset.load`, `distributed/dist_dataset.py:77-164`).
    ``split_ratio < 1`` tiers the node-feature store (HBM hot /
    host-DRAM cold; hotness = in-degree).

    ``host_parts`` (multi-host): materialize ONLY those partitions'
    graph/feature/label tensors on this process — the others live on
    their own hosts and enter the mesh via
    `jax.make_array_from_single_device_arrays` (the sampler's
    host-local put).  At IGBH scale this is what keeps per-host RAM
    at ``1/num_hosts`` of the dataset instead of all of it.  Pass
    `multihost.host_partition_ids(mesh)`.  The host-local arm serves
    the FULL composition (reference parity `data/feature.py:174-206`
    + `partition/base.py:502-647`): tiered stores (``split_ratio <
    1`` keeps only hot rows in HBM; each host's cold rows stay in its
    own DRAM and are owner-served per batch,
    `dist_sampler.overlay_cold_owner`), edge features (mod-sharded,
    built host-locally), the offline cache plan, and ``by_dst``
    layouts (chunked re-bucketing).
    """
    if host_parts is not None:
      return cls._from_partition_dir_host_local(
          root, num_parts, split_ratio, host_parts)
    from ..partition import load_partition
    parts = []
    p0 = load_partition(root, 0)
    meta = p0['meta']
    num_parts = num_parts or meta['num_parts']
    parts = [p0] + [load_partition(root, i) for i in range(1, num_parts)]
    assert not meta['hetero'], (
        'hetero layout: use DistHeteroDataset.from_partition_dir')
    node_pb = parts[0]['node_pb'].table
    n = len(node_pb)
    rows = np.concatenate([p['graph'].edge_index[0] for p in parts])
    cols = np.concatenate([p['graph'].edge_index[1] for p in parts])
    eids = np.concatenate([p['graph'].eids for p in parts])
    hotness = (np.bincount(cols, minlength=n) if split_ratio < 1.0
               else None)
    g, old2new = build_dist_graph(rows, cols, node_pb, n, edge_ids=eids,
                                  num_parts=num_parts, hotness=hotness)
    nf = None
    if parts[0]['node_feat'] is not None:
      d = parts[0]['node_feat'].feats.shape[1]
      feats = np.zeros((n, d), parts[0]['node_feat'].feats.dtype)
      for p in parts:
        feats[p['node_feat'].ids] = p['node_feat'].feats
      nf = build_dist_feature(feats, old2new, g.bounds,
                              split_ratio=split_ratio)
      # remote-hot cache planned by the partitioner (cache_ratio /
      # FrequencyPartitioner): served locally, misses ride all_to_all.
      cache_ids = [p['node_feat'].cache_ids
                   if p['node_feat'].cache_ids is not None else []
                   for p in parts]
      cache_feats = [p['node_feat'].cache_feats for p in parts]
      cids, crows = build_feature_cache(cache_ids, cache_feats, old2new,
                                        num_parts)
      nf.cache_ids, nf.cache_rows = cids, crows
    nl = None
    if parts[0]['node_label'] is not None:
      lab0, ids0 = parts[0]['node_label']
      labels = np.zeros((n,), lab0.dtype)
      for p in parts:
        lab, ids = p['node_label']
        labels[ids] = lab
      nl = build_dist_feature(labels, old2new, g.bounds).shards[..., 0]
    ef = None
    if parts[0].get('edge_feat') is not None:
      e = len(rows)
      d = parts[0]['edge_feat'].feats.shape[1]
      efeats = np.zeros((e, d), parts[0]['edge_feat'].feats.dtype)
      for p in parts:
        efeats[p['edge_feat'].ids] = p['edge_feat'].feats
      ef = build_dist_edge_feature(efeats, num_parts)
    return cls(g, nf, nl, old2new, edge_features=ef)

  @classmethod
  def _from_partition_dir_host_local(cls, root, num_parts, split_ratio,
                                     host_parts) -> 'DistDataset':
    """Materialize only ``host_parts`` (see `from_partition_dir`).

    Global quantities (relabel, bounds, padding widths, hotness) come
    from the tiny per-layout metadata — ``node_pb.npy``, chunked mmap
    scans, and mmap'd array SHAPES — never from other hosts' tensors.
    """
    import json as _json
    from pathlib import Path
    root = Path(root)
    with open(root / 'META.json') as f:
      meta = _json.load(f)
    if meta['hetero']:
      raise ValueError(
          'hetero layout: use DistHeteroDataset.from_partition_dir')
    num_parts = num_parts or meta['num_parts']
    host_parts = np.asarray(host_parts, np.int64)
    node_pb = np.load(root / 'node_pb.npy')
    # the relabel must MATCH a single-controller load of the same
    # (layout, split_ratio): tiered loads order rows within each
    # partition by in-degree hotness, computed here by chunked scan
    hotness = (partition_in_degree(root, 'graph', len(node_pb),
                                   num_parts)
               if split_ratio < 1.0 else None)
    old2new, counts, bounds = relabel_by_partition(node_pb, num_parts,
                                                   hotness)
    max_nodes = int(counts.max()) if num_parts else 0
    if meta.get('edge_assign', 'by_src') == 'by_src':
      indptr_s, indices_s, eids_s = stack_partition_csr(
          root, host_parts, 'graph', old2new, old2new, bounds, counts,
          num_parts)
    else:
      indptr_s, indices_s, eids_s = stack_partition_csr_rebucket(
          root, host_parts, 'graph', node_pb, old2new, old2new, bounds,
          counts, num_parts)
    feats_s = scatter_partition_rows(root, host_parts, 'node_feat',
                                     'feats', old2new, bounds,
                                     max_nodes)
    labels_s = scatter_partition_rows(root, host_parts, 'node_label',
                                      'labels', old2new, bounds,
                                      max_nodes)
    g = DistGraph(indptr_s, indices_s, eids_s, bounds)
    nf = None
    if feats_s is not None:
      if split_ratio < 1.0:
        nf = tiered_local_feature(feats_s, counts, split_ratio,
                                  host_parts, bounds)
      else:
        nf = DistFeature(feats_s, bounds)
      cids, crows = stack_partition_cache(root, host_parts, 'node_feat',
                                          old2new, num_parts)
      nf.cache_ids, nf.cache_rows = cids, crows
    ef = stack_mod_edge_features(root, host_parts, 'edge_feat',
                                 num_parts, int(meta['num_edges']))
    return cls(g, nf, labels_s, old2new, edge_features=ef,
               host_parts=host_parts)
