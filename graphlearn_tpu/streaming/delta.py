"""Delta-CSR segments + RCU-published graph versions.

The mutation half of the streaming ingestion plane (ISSUE 14).  Every
static structure in this repo — the sort-based sampling kernels, the
serving engine's warm bucket executables, the GNS bitmask, the fused
chunk loops — assumes the CSR it was handed never changes.  This
module makes change safe by never changing anything a reader holds:

  * **delta segments** — each applied edge-insert batch is one
    :class:`DeltaSegment` (the "chunk seam" merge unit);
  * **merge at seams** — :func:`merge_delta_csr` folds a segment into
    the base CSR touching only the DIRTY rows (one vectorized shift
    of the clean bulk + a per-dirty-row stable sort), producing
    arrays byte-identical to `utils.topo.coo_to_csr` over the full
    event-ordered edge list — so a quiesced streamed graph is
    indistinguishable from the same graph loaded statically (pinned
    by tests);
  * **RCU publish** — each merge lands as a NEW immutable
    :class:`GraphView` behind a monotonically increasing
    ``graph_version``; readers :meth:`StreamingGraph.pin` one view
    for the duration of a dispatch and can never observe a torn
    graph — writers replace the reference, they never mutate what a
    pinned view points at.

**Shape stability.**  Device consumers (the serving bucket programs,
the mesh steps) compile against array SHAPES; a graph that grew one
edge must not cost a recompile.  Published device indices ride a
power-of-two-padded buffer (``reserve_edges`` floors the initial
capacity); the shape changes only when the edge count crosses a
power of two — logarithmically many recompiles over any growth, the
same INVALID_ID-padding idiom as the serving bucket ladder.  The
padded tail is never read: every kernel bounds its window reads by
``indptr``.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

from ..utils.padding import next_power_of_two
from ..utils.topo import coo_to_csr, ptr2ind


@dataclass(frozen=True)
class DeltaSegment:
  """One applied edge-insert batch (the chunk-seam merge unit).
  ``eids`` are the global event positions — the same consecutive ids
  `data.topology.CSRTopo` fabricates, so streamed and static edge
  identity agree."""
  src: np.ndarray
  dst: np.ndarray
  eids: np.ndarray

  @property
  def count(self) -> int:
    return int(self.src.shape[0])


def merge_delta_csr(indptr: np.ndarray, indices: np.ndarray,
                    eids: np.ndarray, seg: DeltaSegment
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
  """Fold one delta segment into a sorted CSR.

  Byte-identity contract: the result equals
  ``coo_to_csr(base_coo ++ segment_coo)`` — the base edges keep their
  within-row order, segment edges append in event order, and each
  DIRTY row is re-sorted by column with a STABLE sort (matching
  `coo_to_csr`'s stable lexsort, so duplicate columns tie-break by
  event order).  Clean rows move by one vectorized shift; the
  per-row python loop runs only over the segment's distinct source
  rows (the batch-sized dirty set, not the graph).
  """
  num_nodes = len(indptr) - 1
  src = np.asarray(seg.src, np.int64)
  if src.size and (src.min() < 0 or src.max() >= num_nodes):
    raise ValueError(
        f'delta source ids out of range for num_nodes={num_nodes}')
  add = np.bincount(src, minlength=num_nodes).astype(np.int64)
  new_indptr = np.zeros(num_nodes + 1, np.int64)
  np.cumsum(np.diff(indptr) + add, out=new_indptr[1:])
  e_new = int(new_indptr[-1])
  new_indices = np.empty(e_new, indices.dtype)
  new_eids = np.empty(e_new, eids.dtype)
  # shift the whole base in one scatter: edge at old position j of row
  # r lands at j + (new_indptr[r] - indptr[r])
  if len(indices):
    rows_of = ptr2ind(indptr)
    pos = np.arange(len(indices)) + (new_indptr[:-1] - indptr[:-1]
                                     )[rows_of]
    new_indices[pos] = indices
    new_eids[pos] = eids
  # segment edges at each dirty row's tail, in event order
  order = np.argsort(src, kind='stable')
  tail_base = new_indptr[src[order]] + np.diff(indptr)[src[order]]
  tail_off = np.arange(len(src)) - np.concatenate(
      [[0], np.cumsum(add)])[src[order]]
  tail_pos = tail_base + tail_off
  new_indices[tail_pos] = np.asarray(seg.dst)[order].astype(
      new_indices.dtype)
  new_eids[tail_pos] = np.asarray(seg.eids)[order].astype(
      new_eids.dtype)
  # re-sort only the dirty rows (stable: base order + event order are
  # both preserved among equal columns, = coo_to_csr's lexsort)
  for r in np.unique(src):
    lo, hi = int(new_indptr[r]), int(new_indptr[r + 1])
    sl = new_indices[lo:hi]
    perm = np.argsort(sl, kind='stable')
    new_indices[lo:hi] = sl[perm]
    new_eids[lo:hi] = new_eids[lo:hi][perm]
  return new_indptr, new_indices, new_eids


@dataclass(frozen=True)
class GraphView:
  """One immutable published graph version.

  ``indptr`` / ``indices`` / ``edge_ids`` are host arrays trimmed to
  the real edge count; ``indptr_dev`` / ``indices_dev`` are the
  device twins with ``indices_dev`` power-of-two padded (tail filled
  with 0 — a valid row index that no kernel ever dereferences, since
  reads are ``indptr``-bounded and masked).  A reader pins ONE view
  per dispatch; everything it touches through the view is frozen.
  """
  version: int
  indptr: np.ndarray
  indices: np.ndarray
  edge_ids: np.ndarray
  indptr_dev: object = field(repr=False, default=None)
  indices_dev: object = field(repr=False, default=None)

  @property
  def num_nodes(self) -> int:
    return len(self.indptr) - 1

  @property
  def num_edges(self) -> int:
    return int(self.indices.shape[0])

  def as_topo(self):
    """A `data.topology`-shaped host topology over this view (no
    re-sort: the view is already canonical sorted-CSR).  For the
    single-chip samplers and byte-identity tests."""
    from ..data.topology import CSRTopo
    topo = CSRTopo.__new__(CSRTopo)
    topo._indptr = self.indptr
    topo._indices = self.indices.astype(np.int32, copy=False)
    topo._edge_ids = self.edge_ids
    return topo

  def as_graph(self):
    """A device `data.graph.Graph` over THIS view's device arrays —
    what `Dataset.attach_stream` hands the samplers.  The padded
    indices buffer is shared with the serving engine's programs, so
    one publish feeds every reader."""
    from ..data.graph import Graph
    return Graph.from_device_arrays(self.indptr_dev, self.indices_dev)


class StreamingGraph:
  """A mutable graph publishing immutable `GraphView` versions.

  Writers: :meth:`apply_events` appends one delta segment and
  publishes the merged CSR as version ``N+1`` (RCU: the previous
  view stays valid for whoever pinned it).  Readers: :meth:`pin`
  returns the current view — one attribute read of an immutable
  object, safe from any thread, no lock on the read path.

  Args:
    indptr/indices/edge_ids: the base CSR (canonical sorted form —
      build through `CSRTopo`/`coo_to_csr` first).
    num_nodes: fixed node universe (edge inserts only — ISSUE 14;
      node inserts are follow-on work).
    reserve_edges: floor for the padded device-indices capacity; size
      it to the expected growth so steady-state ingest publishes at
      ONE shape and the warm serving executables stay warm.
    device: build device twins of every published view (on by
      default; host-only consumers may pass ``device=False``).
  """

  def __init__(self, indptr, indices, edge_ids=None,
               num_nodes: Optional[int] = None,
               reserve_edges: int = 0, device: bool = True):
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices)
    if num_nodes is not None and len(indptr) - 1 != int(num_nodes):
      raise ValueError(
          f'indptr implies {len(indptr) - 1} nodes, '
          f'num_nodes={num_nodes} was given')
    if edge_ids is None:
      edge_ids = np.arange(len(indices), dtype=np.int64)
    self._lock = threading.Lock()
    self._device = bool(device)
    self._edge_cap = next_power_of_two(
        max(int(reserve_edges), len(indices), 1))
    self._num_events = len(indices)          # guarded-by: self._lock
    self._view: GraphView = self._build_view(
        1, indptr, np.asarray(indices), np.asarray(edge_ids, np.int64))
    # memory accounting (ISSUE 17): host CSR arrays of the published
    # view + the padded device twins (reads the LIVE view, so tier
    # bytes track publishes without any hook in the write path)
    from ..telemetry.memaccount import register_tier

    def _stream_bytes():
      v = self._view
      total = 0
      for arr in (v.indptr, v.indices, v.edge_ids,
                  v.indptr_dev, v.indices_dev):
        total += int(getattr(arr, 'nbytes', 0) or 0)
      return total

    register_tier('streaming', _stream_bytes)

  def _build_view(self, version: int, indptr, indices, eids
                  ) -> GraphView:
    indptr_dev = indices_dev = None
    if self._device:
      import jax.numpy as jnp
      if len(indices) > self._edge_cap:
        self._edge_cap = next_power_of_two(len(indices))
      padded = np.zeros(self._edge_cap, np.int32)
      padded[:len(indices)] = indices
      indptr_dev = jnp.asarray(indptr.astype(
          np.int32 if int(indptr[-1]) < np.iinfo(np.int32).max
          else np.int64))
      indices_dev = jnp.asarray(padded)
    return GraphView(version=version, indptr=indptr,
                     indices=np.asarray(indices),
                     edge_ids=np.asarray(eids, np.int64),
                     indptr_dev=indptr_dev, indices_dev=indices_dev)

  # -- read side (lock-free) -------------------------------------------------
  def pin(self) -> GraphView:
    """The current published view.  Immutable — hold it for the whole
    dispatch and every read is from exactly one ``graph_version``."""
    return self._view

  @property
  def version(self) -> int:
    return self._view.version

  @property
  def num_nodes(self) -> int:
    return self._view.num_nodes

  @property
  def num_edges(self) -> int:
    return self._view.num_edges

  @property
  def edge_capacity(self) -> int:
    """Current padded device-indices capacity (a growth past it is
    the one event that changes a compiled consumer's shape)."""
    return self._edge_cap

  # -- write side ------------------------------------------------------------
  def apply_events(self, src, dst) -> GraphView:
    """Merge one edge-insert batch and publish it as the next
    version.  The merge builds entirely NEW arrays; the swap is one
    reference assignment under the writer lock — a concurrent reader
    holds either the old complete view or the new complete view."""
    src = np.asarray(src, np.int64).reshape(-1)
    dst = np.asarray(dst, np.int64).reshape(-1)
    if dst.size and (dst.min() < 0 or dst.max() >= self.num_nodes):
      # src is range-checked by the merge (it indexes indptr); dst
      # must be checked HERE — an out-of-range neighbor id would
      # publish cleanly and then read garbage at feature-gather time
      raise ValueError(
          f'delta destination ids out of range for '
          f'num_nodes={self.num_nodes}')
    with self._lock:
      prev = self._view
      seg = DeltaSegment(
          src=src, dst=dst,
          eids=np.arange(self._num_events,
                         self._num_events + len(src), dtype=np.int64))
      merged = self._merge_device(prev, seg)
      if merged is None:
        merged = merge_delta_csr(
            prev.indptr, prev.indices, prev.edge_ids, seg)
      new_indptr, new_indices, new_eids = merged
      view = self._build_view(prev.version + 1, new_indptr,
                              new_indices, new_eids)
      self._num_events += len(src)
      self._view = view
      return view

  def _merge_device(self, prev: GraphView, seg: DeltaSegment):
    """The r19 Pallas merge path: ``GLT_PALLAS_DELTA`` gates the
    rank-kernel merge (`ops.pallas_delta`), byte-identical to
    `merge_delta_csr` by contract; a disqualifying shape
    (`DeltaMergeUnsupported`, a documented rule) falls back to the
    host merge (``None`` return) with a ``pallas.fallback`` event,
    any other kernel failure raises — the fault-free default path
    never imports jax from here."""
    import os
    if os.environ.get('GLT_PALLAS_DELTA', '').strip().lower() not in (
        '1', 'true', 'on', 'yes'):
      return None
    from ..telemetry.recorder import recorder
    from ..ops.pallas_delta import (DeltaMergeUnsupported,
                                    merge_delta_csr_device)
    try:
      merged = merge_delta_csr_device(
          prev.indptr, prev.indices, prev.edge_ids, seg)
    except DeltaMergeUnsupported as ex:
      if recorder.enabled:
        recorder.emit('pallas.fallback', kernel='delta_merge',
                      reason=str(ex), events=seg.count)
      return None
    if recorder.enabled:
      recorder.emit('pallas.dispatch', kernel='delta_merge',
                    events=seg.count, version=prev.version + 1)
    return merged

  # -- DataPlaneState (utils.checkpoint): the compacted base ----------------
  def state_dict(self) -> dict:
    with self._lock:
      view = self._view
      num_events = self._num_events
    return {'indptr': view.indptr, 'indices': view.indices,
            'edge_ids': view.edge_ids,
            'version': np.int64(view.version),
            'num_events': np.int64(num_events),
            'edge_cap': np.int64(self._edge_cap)}

  def load_state_dict(self, state: dict) -> None:
    with self._lock:
      self._edge_cap = max(self._edge_cap,
                           int(np.asarray(state['edge_cap'])))
      self._num_events = int(np.asarray(state['num_events']))
      self._view = self._build_view(
          int(np.asarray(state['version'])),
          np.asarray(state['indptr'], np.int64),
          np.asarray(state['indices']),
          np.asarray(state['edge_ids'], np.int64))

  @classmethod
  def from_coo(cls, rows, cols, num_nodes: Optional[int] = None,
               reserve_edges: int = 0, device: bool = True
               ) -> 'StreamingGraph':
    """Build from a COO edge list through the SAME canonicalization
    as `data.topology.CSRTopo` (coo_to_csr, fabricated consecutive
    edge ids) — the static-load twin of a stream that ingested the
    same edges."""
    indptr, indices, eids = coo_to_csr(
        np.asarray(rows), np.asarray(cols), num_nodes)
    return cls(indptr, indices, eids, reserve_edges=reserve_edges,
               device=device)
