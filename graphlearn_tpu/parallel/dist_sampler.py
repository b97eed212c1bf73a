"""Distributed neighbor sampling + feature collection over ICI.

TPU-native replacement for the reference's distributed engine
(`distributed/dist_neighbor_sampler.py:88-673` — asyncio RPC fan-out
per hop, `RpcSamplingCallee`, `stitch_sample_results`;
`distributed/dist_feature.py:134-269` — rpc feature fan-out + stitch).

The whole per-batch pipeline is ONE SPMD program under `shard_map`:

  hop:  owner = searchsorted(bounds, frontier)        (partition book)
        send buckets --all_to_all-->  peers           (seed exchange)
        local sample on owned CSR shard               (XLA, no host)
        results --all_to_all--> requesters            (reply)
        gather back to request order                  (the "stitch")
        dedup/relabel into the device's node table    (inducer)

  feat: same exchange pattern against feature shards.

The reference's pull-based variable-size RPC becomes fixed-capacity
collectives: each hop's exchange buffer is ``[P, F]`` where ``F`` is
that hop's static frontier capacity — padding waste instead of RPC
latency, the standard TPU trade.  Per-device batches make this data
parallel at the same time: device d samples ITS seed batch while
serving its partition to peers — what the reference needs a sampling
subprocess pool + event loop for (`dist_sampling_producer.py`).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..loader.prefetch import PrefetchingLoader
from ..ops.negative import edge_in_csr
from ..ops.neighbor import sample_one_hop
from ..ops.pallas_sample import sample_one_hop_auto
from ..ops.unique import init_node, induce_next
from ..utils.padding import INVALID_ID, max_sampled_nodes, round_up
from ..utils.profiling import layer_scope
from .dist_data import DistDataset
from .exchange import (MIN_EXCHANGE_CAP, capacity_spec, dest_histogram,
                       offered_dropped, plan_exchange, resolve_layout,
                       scoped_plan)
from .partition_book import (book_owner_fn, edge_book_owner_fn,
                             edge_local_rows, edge_owner_fn,
                             hot_split_host, range_owner_fn)

#: default per-destination exchange capacity, as a multiple of the
#: balanced share (frontier / P).  2.0 tolerates 2x ownership skew
#: while shrinking every all_to_all buffer by P/2 — the right trade
#: for SHUFFLED seeds (near-balanced buckets); unshuffled loaders keep
#: exact (uncapped) exchanges since contiguous seed ranges can land
#: entirely on one owner.  See `bucket_by_owner` for drop semantics.
DEFAULT_EXCHANGE_SLACK = 2.0

#: layout of the per-step exchange-telemetry vector (stacked [P, 7]).
#: offered = valid ids entering an exchange; dropped = valid ids past
#: an owner's capacity (their neighbors/features are lost that hop);
#: slots = total send-buffer width (padding waste = 1 - offered/slots);
#: negative.lost = strict-negative slots whose every trial collided.
EXCHANGE_STAT_NAMES = (
    'frontier.offered', 'frontier.dropped', 'frontier.slots',
    'feature.offered', 'feature.dropped', 'feature.slots',
    'negative.lost')


def bucket_by_owner(ids: jax.Array, owner: jax.Array, num_parts: int,
                    self_idx: jax.Array, capacity: Optional[int] = None):
  """Pack ids into per-owner rows of a ``[P, C]`` send buffer.

  Returns ``(send, slot_p, slot_j)``: ``send[p]`` holds the ids owned
  by partition ``p`` (-1 padded); original position ``i`` landed at
  ``send[slot_p[i], slot_j[i]]`` — the inverse map used to stitch
  replies back into request order (the collective-era
  `stitch_sample_results`, `csrc/cuda/stitch_sample_results.cu:27-100`).

  ``capacity`` bounds the per-destination row width ``C`` (default:
  the full frontier size ``F``).  With shuffled seeds each owner gets
  ~``F/P`` ids, so the uncapped buffer is ~``P``x padding — the
  SURVEY §7 "partition-aware capacity tuning" trade.  Ids past an
  owner's capacity are DROPPED: their ``slot_j`` is -1 and callers
  must mask their results invalid (a capped neighbor sample loses
  those neighbors — statistically a slight under-sample, never a
  wrong edge).

  Built from two sorts and contiguous slices, with no scatter and no
  gather of the ``F`` ids (`_pack`).
  """
  (send,), slot_p, slot_j = _pack(ids, owner, num_parts, capacity,
                                  (ids,))
  return send, slot_p, slot_j


def bucket_with_payload(ids: jax.Array, payload: jax.Array,
                        owner: jax.Array, num_parts: int,
                        self_idx: jax.Array,
                        capacity: Optional[int] = None):
  """`bucket_by_owner` carrying a companion array: ``payload[i]`` lands
  in the same ``[p, j]`` slot as ``ids[i]`` (used to ship (row, col)
  pairs to the row's owner for distributed edge-existence tests)."""
  (send, send_pl), slot_p, slot_j = _pack(ids, owner, num_parts,
                                          capacity, (ids, payload))
  return send, send_pl, slot_p, slot_j


def _pack(ids, owner, num_parts: int, capacity: Optional[int], rows):
  """The ``[P, C]`` send buffer of each array in ``rows`` (each ``[F]``,
  laid out by ``ids``' owners), and ``(slot_p, slot_j)``.

  A scatter or gather of ``F`` elements is what the TPU prices worst
  (a permutation gather of 937,984 ids 6.7 ms, a scatter onto a few
  addresses 8.7 ns an id, on a TPU v5e), so the pack has neither: one
  sort keyed by (owner, position) carries ``rows`` into owner order,
  each owner's row is a contiguous slice of it, and a second sort keyed
  by position brings each id's rank among its owner's ids back to
  request order.  Both sorts have unique keys, so neither is asked to
  be stable; without a payload they have one signature (two 32-bit
  operands), which the TPU's compiler compiles once.
  """
  f = ids.shape[0]
  cap = f if capacity is None else min(int(capacity), f)
  bits = max(f - 1, 0).bit_length()
  if (num_parts + 1) << bits > 1 << 32:
    raise ValueError(f'{num_parts} owners x {f} ids do not fit the '
                     'pack\'s 32-bit sort key')
  parts = jnp.arange(num_parts, dtype=jnp.int32)
  # invalid ids key AFTER every real owner: they never consume a
  # capacity slot (parking them at self could evict valid self-owned
  # ids under a cap); an owner outside [0, P) is dropped the same way,
  # so the key's owner field stays in [0, P] and cannot wrap
  owned = (ids >= 0) & (owner >= 0) & (owner < num_parts)
  key = jnp.where(owned, owner, num_parts).astype(jnp.int32)
  pos = jax.lax.iota(jnp.uint32, f)
  packed, *rows_s = jax.lax.sort(
      (key.astype(jnp.uint32) << bits | pos,) + tuple(rows), num_keys=1)
  key_s = (packed >> bits).astype(jnp.int32)
  counts = jnp.sum(key[None, :] == parts[:, None], axis=1,
                   dtype=jnp.int32)
  offsets = jnp.cumsum(counts) - counts
  # rank within the owner, in owner order: the position less the ids
  # of every lower owner (a select over P, not a gather of offsets)
  rank_s = pos.astype(jnp.int32) - jnp.sum(
      jnp.where(key_s[None, :] > parts[:, None], counts[:, None], 0),
      axis=0)
  _, slot_j = jax.lax.sort(
      (packed & ((1 << bits) - 1),
       jnp.where((key_s < num_parts) & (rank_s < cap), rank_s, -1)),
      num_keys=1)
  slot_p = jnp.where(key < num_parts, key, 0)
  j = jnp.arange(cap, dtype=jnp.int32)

  def owner_rows(r):
    # padded by `cap`, so no owner's slice is clamped back into the
    # one before it
    r = jnp.concatenate([r, jnp.full((cap,), INVALID_ID, r.dtype)])
    fill = jnp.asarray(INVALID_ID, r.dtype)
    return jnp.stack([
        jnp.where(j < counts[p],
                  jax.lax.dynamic_slice(r, (offsets[p],), (cap,)), fill)
        for p in range(num_parts)])

  return tuple(owner_rows(r) for r in rows_s), slot_p, slot_j


class _BookPlan:
  """Adopted-`PartitionBook` exchange: ids bucket to *(owner device,
  lane)* virtual destinations, ship as one ``[P, S*C]`` all_to_all,
  and each lane's receive buffer comes out laid exactly as the
  range's ORIGINAL owner would have seen it (per-range capacity,
  per-range positions) — the property that makes adopted epochs
  byte-identical to fault-free runs (`partition_book` module
  docstring).  Dense-style: post-adoption exchanges rebuild onto this
  plan whatever layout the identity book ran.
  """

  layout = 'book'

  def __init__(self, ids, bounds, spec, axis: str,
               capacity: Optional[int], payload=None,
               owner_mode: str = 'range'):
    from .exchange import ExchangeSpec, _bcast
    self._bcast = _bcast
    p, s = int(spec.num_parts), int(spec.num_lanes)
    f = ids.shape[0]
    if capacity is None:
      cap = f
    elif isinstance(capacity, ExchangeSpec):
      # per-RANGE capacity from the identity plan's slot budget: the
      # dense cap verbatim (the byte-identity arm — a range's lane
      # buffer must hold exactly what its original owner's dense row
      # held); compact/hier budgets flatten to slots/P rounded up,
      # floored like the dense rule
      if capacity.layout == 'dense':
        cap = min(int(capacity.capacity), f)
      else:
        cap = min(f, max(int(round_up(-(-capacity.slots // p), 8)),
                         MIN_EXCHANGE_CAP))
    else:
      cap = min(int(capacity), f)
    if owner_mode == 'mod':
      owner = edge_book_owner_fn(p, spec)(ids).astype(jnp.int32)
    else:
      owner = book_owner_fn(bounds, spec)(ids).astype(jnp.int32)
    self._p, self._s, self._cap, self._axis = p, s, cap, axis
    if payload is None:
      send, self.slot_p, self.slot_j = bucket_by_owner(
          ids, owner, p * s, None, cap)               # [P*S, cap]
      recv2 = jax.lax.all_to_all(send.reshape(p, s * cap), axis, 0, 0,
                                 tiled=True)          # [P_src, S*cap]
    else:
      send, send_pl, self.slot_p, self.slot_j = bucket_with_payload(
          ids, payload, owner, p * s, None, cap)
      both = jax.lax.all_to_all(
          jnp.concatenate([send.reshape(p, s * cap),
                           send_pl.reshape(p, s * cap)], axis=1),
          axis, 0, 0, tiled=True)
      recv2, recv_pl = both[:, :s * cap], both[:, s * cap:]
      self.recv_payload_lanes = recv_pl.reshape(p, s, cap).transpose(
          1, 0, 2).reshape(s, p * cap)
    #: lane j's receive buffer ``[P_src * cap]`` — bit-identical to
    #: the identity-book recv of the range assigned to (me, lane j)
    self.recv_lanes = recv2.reshape(p, s, cap).transpose(
        1, 0, 2).reshape(s, p * cap)
    self.kept = self.slot_j >= 0
    self.delivered = self.kept
    self._ids = ids
    #: requester index per lane-recv row (the per-requester GNS mask
    #: needs the source device of every received frontier id)
    self.req_of_lane_recv = jnp.repeat(
        jnp.arange(p, dtype=jnp.int32), cap)

  def count(self):
    return offered_dropped(self._ids, self.kept) + (
        jnp.int32(self._p * self._s * self._cap),)

  def reply(self, values_lanes, fill=0):
    """``[S, P*cap, ...]`` per-lane owner-side values -> ``[F, ...]``
    in request order; un-kept positions get ``fill``."""
    p, s, cap = self._p, self._s, self._cap
    trail = values_lanes.shape[2:]
    v = values_lanes.reshape((s, p, cap) + trail)
    v = jnp.moveaxis(v, 0, 1).reshape((p, s * cap) + trail)
    back = jax.lax.all_to_all(v, self._axis, 0, 0, tiled=True)
    flat = back.reshape((p * s, cap) + trail)
    out = flat[self.slot_p, jnp.where(self.kept, self.slot_j, 0)]
    return jnp.where(self._bcast(self.kept, out), out,
                     jnp.asarray(fill, out.dtype))


def dist_edge_exists(indptr_loc, indices_loc, bounds, rows, cols,
                     axis: str, num_parts: int,
                     exchange_capacity: Optional[int] = None,
                     book_spec=None):
  """Distributed membership test over the range-sharded CSR: is
  ``(rows[i], cols[i])`` an edge of the global graph?

  Pairs travel to the row's owner (one all_to_all each way), which
  answers with its local `edge_in_csr` binary search — the collective
  analog of the reference's strict-rejection check
  (`csrc/cuda/random_negative_sampler.cu:37-54`) for graphs larger
  than one chip.  Pairs dropped by ``exchange_capacity`` overflow
  report True (conservatively "exists", so they are never used as
  strict negatives).
  """
  my_idx = jax.lax.axis_index(axis)
  if book_spec is not None:
    plan = scoped_plan('pairs', lambda: _BookPlan(
        rows, bounds, book_spec, axis, exchange_capacity, payload=cols))
    slot_ranges = jnp.asarray(book_spec.slot_ranges, jnp.int32)
    lanes_ex = []
    with layer_scope('sample', 'negative'):
      for j in range(book_spec.num_lanes):
        r_j = jnp.clip(slot_ranges[my_idx, j], 0, num_parts - 1)
        flat_r = plan.recv_lanes[j]
        local_r = jnp.where(flat_r >= 0, flat_r - bounds[r_j],
                            INVALID_ID).astype(jnp.int32)
        lanes_ex.append(edge_in_csr(
            indptr_loc[j], indices_loc[j], local_r,
            plan.recv_payload_lanes[j].astype(jnp.int32)))
      ex = jnp.stack(lanes_ex)
    return plan.reply(ex, fill=True)
  my_start = bounds[my_idx]
  owner_fn = range_owner_fn(bounds)
  plan = plan_exchange(rows, owner_fn, num_parts, axis,
                       exchange_capacity, payload=cols, what='pairs')
  with layer_scope('sample', 'negative'):
    flat_r = plan.recv
    local_r = jnp.where(flat_r >= 0, flat_r - my_start,
                        INVALID_ID).astype(jnp.int32)
    ex = edge_in_csr(indptr_loc, indices_loc, local_r,
                     plan.recv_payload.astype(jnp.int32))
  # undelivered pairs fill True ("exists", so never a strict negative)
  return plan.reply(ex, fill=True)


NEG_TRIALS = 5     # redraw attempts per strict-negative slot


def dist_sample_negative(indptr_loc, indices_loc, bounds,
                         num_rows: int, num_cols: int, req_num: int,
                         key, axis: str, num_parts: int,
                         trials: int = NEG_TRIALS,
                         exchange_capacity: Optional[int] = None,
                         rows_fixed: Optional[jax.Array] = None,
                         book_spec=None):
  """``req_num`` strict negative pairs over the sharded graph
  (collective analog of `ops.negative.sample_negative`): trials-stacked
  draws, ONE existence exchange for all trials, first-non-edge pick.
  Returns ``(rows, cols, ok)`` — ``ok`` False marks slots where every
  trial hit an existing edge (the padding fallback pair may be a REAL
  edge; consumers must mask it out of the negative label set).
  ``rows_fixed`` pins the row of each slot (triplet mode's per-source
  negatives)."""
  kr, kc = jax.random.split(key)
  if rows_fixed is None:
    rows = jax.random.randint(kr, (trials, req_num), 0, num_rows,
                              dtype=jnp.int32)
  else:
    rows = jnp.broadcast_to(rows_fixed[None, :], (trials, req_num))
  cols = jax.random.randint(kc, (trials, req_num), 0, num_cols,
                            dtype=jnp.int32)
  exists = dist_edge_exists(
      indptr_loc, indices_loc, bounds, rows.reshape(-1),
      cols.reshape(-1), axis, num_parts,
      exchange_capacity, book_spec=book_spec).reshape(trials, req_num)
  ok = ~exists
  any_ok = jnp.any(ok, axis=0)
  pick = jnp.where(any_ok, jnp.argmax(ok, axis=0), trials - 1)
  slot = jnp.arange(req_num)
  return rows[pick, slot], cols[pick, slot], any_ok


def _dist_one_hop_book(indptr_l, indices_l, eids_l, bounds, frontier,
                       k: int, key, axis: str, num_parts: int,
                       with_edge: bool, book_spec,
                       sort_locality: bool = True,
                       exchange_capacity: Optional[int] = None,
                       gns_bits=None,
                       gns_boost: Optional[float] = None):
  """Adopted-book hop: route per *(owner, lane)*, sample per RANGE.

  Each lane's receive buffer and sampling key are keyed by the range
  (``fold_in(key, range)``, not the device index), so an adopted
  shard's draws are bit-identical to what its original owner would
  have produced — the byte-identity half of the exact-completion
  contract.  Local arrays carry a leading lane axis (``[S, ...]``).
  """
  my_idx = jax.lax.axis_index(axis)
  plan = scoped_plan('frontier', lambda: _BookPlan(
      frontier, bounds, book_spec, axis, exchange_capacity))
  slot_ranges = jnp.asarray(book_spec.slot_ranges, jnp.int32)
  outs_n, outs_m, outs_e, outs_w = [], [], [], []
  with layer_scope('sample', 'owner'):
    for j in range(book_spec.num_lanes):
      r_j = jnp.clip(slot_ranges[my_idx, j], 0, num_parts - 1)
      flat = plan.recv_lanes[j]
      local = jnp.where(flat >= 0, flat - bounds[r_j],
                        INVALID_ID).astype(jnp.int32)
      lane_key = jax.random.fold_in(key, r_j)
      # sample_one_hop_auto resolves the GLT_PALLAS_SAMPLE dispatch at
      # trace time (value-identical either way — the gns.bias build-
      # time-event precedent); the dedup bits tuple flows as a pytree
      if gns_bits is not None:
        from ..ops.gns import is_per_requester
        res = sample_one_hop_auto(
            indptr_l[j], indices_l[j], local, k, lane_key,
            eids_l[j] if eids_l is not None else None,
            bits=gns_bits, boost=float(gns_boost),
            req=(plan.req_of_lane_recv if is_per_requester(gns_bits)
                 else None),
            with_edge_ids=with_edge, sort_locality=sort_locality)
      else:
        res = sample_one_hop_auto(
            indptr_l[j], indices_l[j], local, k, lane_key,
            eids_l[j] if eids_l is not None else None,
            with_edge_ids=with_edge, sort_locality=sort_locality)
      outs_n.append(res.nbrs)
      outs_m.append(res.mask)
      if with_edge:
        outs_e.append(res.eids)
      if res.weights is not None:
        outs_w.append(res.weights)
    outs_n, outs_m = jnp.stack(outs_n), jnp.stack(outs_m)
    outs_e = jnp.stack(outs_e) if with_edge else None
    outs_w = jnp.stack(outs_w) if outs_w else None
  out_nbrs = plan.reply(outs_n, fill=INVALID_ID)
  out_mask = plan.reply(outs_m, fill=False)
  out_eids = (plan.reply(outs_e, fill=INVALID_ID)
              if outs_e is not None else None)
  out_w = (plan.reply(outs_w, fill=0.0)
           if outs_w is not None else None)
  return out_nbrs, out_mask, out_eids, out_w, plan.stats


def _dist_one_hop(indptr_loc, indices_loc, eids_loc, bounds, frontier,
                  k: int, key, axis: str, num_parts: int,
                  with_edge: bool, sort_locality: bool = True,
                  exchange_capacity: Optional[int] = None,
                  gns_bits=None, gns_boost: Optional[float] = None,
                  book_spec=None):
  """One distributed hop for this device's ``frontier`` ids.

  ``exchange_capacity`` caps the per-destination exchange width
  (default: the full frontier — ~P x padding with balanced buckets);
  overflowed frontier entries sample nothing this hop (masked).
  ``gns_bits`` (+ static ``gns_boost``) switches the owner-side
  kernel to cache-aware GNS sampling (`ops.gns.sample_one_hop_gns`):
  cached neighbors draw with boosted probability and per-edge
  importance weights ride the reply collective next to the ids.
  Returns ``(nbrs, mask, eids, weights, stats)`` — ``weights`` is
  None without GNS; ``stats`` is the (offered, dropped, slots)
  telemetry triple.
  """
  if book_spec is not None:
    return _dist_one_hop_book(
        indptr_loc, indices_loc, eids_loc, bounds, frontier, k, key,
        axis, num_parts, with_edge, book_spec,
        sort_locality=sort_locality,
        exchange_capacity=exchange_capacity, gns_bits=gns_bits,
        gns_boost=gns_boost)
  my_idx = jax.lax.axis_index(axis)
  my_start = bounds[my_idx]
  owner_fn = range_owner_fn(bounds)
  plan = plan_exchange(frontier, owner_fn, num_parts, axis,
                       exchange_capacity, what='frontier')
  with layer_scope('sample', 'owner'):
    flat = plan.recv
    local = jnp.where(flat >= 0, flat - my_start,
                      INVALID_ID).astype(jnp.int32)
    if gns_bits is not None:
      from ..ops.gns import fallback_req_index, is_per_requester
      req = None
      if is_per_requester(gns_bits):
        # per-requester masks (ISSUE 15): the plan attributes each recv
        # row to its source device; layouts that cannot (hier's
        # two-stage re-bucketing) fall back to the hot-split-only row —
        # conservative (never over-boosts), still exactly corrected.
        # r19 carries the masks as the dedup (table, row_index) tuple —
        # O(distinct caches) VMEM instead of O(P) replication
        req = getattr(plan, 'requester_of_recv', None)
        if req is None:
          req = jnp.full(flat.shape, fallback_req_index(gns_bits),
                         jnp.int32)
      res = sample_one_hop_auto(indptr_loc, indices_loc, local, k,
                                jax.random.fold_in(key, my_idx),
                                eids_loc, bits=gns_bits,
                                boost=float(gns_boost), req=req,
                                with_edge_ids=with_edge,
                                sort_locality=sort_locality)
    else:
      res = sample_one_hop_auto(indptr_loc, indices_loc, local, k,
                                jax.random.fold_in(key, my_idx),
                                eids_loc, with_edge_ids=with_edge,
                                sort_locality=sort_locality)
  out_nbrs = plan.reply(res.nbrs, fill=INVALID_ID)
  out_mask = plan.reply(res.mask, fill=False)
  out_eids = plan.reply(res.eids, fill=INVALID_ID) if with_edge else None
  out_w = (plan.reply(res.weights, fill=0.0)
           if res.weights is not None else None)
  return out_nbrs, out_mask, out_eids, out_w, plan.stats


def _dist_gather_multi_book(shard_locs, bounds, ids, axis: str,
                            num_parts: int, book_spec,
                            exchange_capacity: Optional[int] = None,
                            shard_mode: str = 'range',
                            hot_counts: Optional[jax.Array] = None):
  """Adopted-book row gather: tables carry a leading lane axis
  (``[S, rows, ...]``); requests route per *(owner, lane)* and the
  hot-tier gate keys on the RANGE's hot count (placement is frozen;
  only the serving device moved)."""
  my_idx = jax.lax.axis_index(axis)
  plan = scoped_plan('feature', lambda: _BookPlan(
      ids, bounds, book_spec, axis, exchange_capacity,
      owner_mode=shard_mode))
  slot_ranges = jnp.asarray(book_spec.slot_ranges, jnp.int32)
  ok = (ids >= 0) & plan.delivered
  outs = []
  for t, shard_l in enumerate(shard_locs):
    lane_rows = []
    with layer_scope('gather', 'owner'):
      for j in range(book_spec.num_lanes):
        flat = plan.recv_lanes[j]
        valid = flat >= 0
        r_j = jnp.clip(slot_ranges[my_idx, j], 0, num_parts - 1)
        if shard_mode == 'mod':
          local = jnp.where(valid, edge_local_rows(flat, num_parts), 0)
        else:
          local = jnp.where(valid, flat - bounds[r_j], 0)
        row_valid = valid
        if t == 0 and hot_counts is not None:
          row_valid = valid & (local < hot_counts[r_j])
        idx = jnp.clip(local, 0, shard_l.shape[1] - 1)
        rows = shard_l[j][idx]
        if rows.ndim == 1:
          rows = jnp.where(row_valid, rows, 0)
        else:
          rows = jnp.where(row_valid[:, None], rows, 0)
        lane_rows.append(rows)
      lane_rows = jnp.stack(lane_rows)
    out = plan.reply(lane_rows, fill=0)
    with layer_scope('gather', 'mask'):
      if out.ndim == 1:
        outs.append(jnp.where(ok, out, 0))
      else:
        outs.append(jnp.where(ok[:, None], out, 0))
  return tuple(outs), plan.stats


def dist_gather_multi(shard_locs, bounds, ids, axis: str, num_parts: int,
                      exchange_capacity: Optional[int] = None,
                      shard_mode: str = 'range',
                      hot_counts: Optional[jax.Array] = None,
                      book_spec=None):
  """Distributed row gather from several sharded tables that share an
  ownership scheme: ``out_t[i] = table_t[ids[i]]`` (the collective-era
  `DistFeature.async_get`, `distributed/dist_feature.py:134-269`).

  ``shard_mode='range'``: owner by ``searchsorted(bounds, id)`` (node
  tables); ``'mod'``: owner = ``id % P``, local row = ``id // P``
  (edge-feature tables, `build_dist_edge_feature` — strided so
  consecutive-id runs spread across owners under a capacity cap).

  The id bucketing and request all_to_all run ONCE for all tables —
  feature + label collection share a single exchange.  Invalid ids
  (-1) return zero rows; ids past ``exchange_capacity`` per owner
  return zero rows too (callers choosing a capacity accept that tail).
  ``hot_counts`` (``[P]``, tiered feature stores) marks the FIRST
  table HBM-partial: rows past the owner's hot count return zero and
  the caller overlays them from the host cold tier post-step.
  Returns ``(outs, stats)`` with the (offered, dropped, slots)
  telemetry triple.
  """
  if book_spec is not None:
    return _dist_gather_multi_book(
        shard_locs, bounds, ids, axis, num_parts, book_spec,
        exchange_capacity=exchange_capacity, shard_mode=shard_mode,
        hot_counts=hot_counts)
  my_idx = jax.lax.axis_index(axis)
  if shard_mode == 'mod':
    owner_fn = edge_owner_fn(num_parts)
  else:
    my_start = bounds[my_idx]
    owner_fn = range_owner_fn(bounds)
  plan = plan_exchange(ids, owner_fn, num_parts, axis,
                       exchange_capacity, what='feature')
  with layer_scope('gather', 'owner'):
    flat = plan.recv
    valid = flat >= 0
    if shard_mode == 'mod':
      local = jnp.where(valid, edge_local_rows(flat, num_parts), 0)
    else:
      local = jnp.where(valid, flat - my_start, 0)
  ok = (ids >= 0) & plan.delivered
  outs = []
  for t, shard_loc in enumerate(shard_locs):
    with layer_scope('gather', 'owner'):
      row_valid = valid
      if t == 0 and hot_counts is not None:
        row_valid = valid & (local < hot_counts[my_idx])
      idx = jnp.clip(local, 0, shard_loc.shape[0] - 1)
      rows = shard_loc[idx]
      if rows.ndim == 1:
        rows = jnp.where(row_valid, rows, 0)
      else:
        rows = jnp.where(row_valid[:, None], rows, 0)
    out = plan.reply(rows, fill=0)
    with layer_scope('gather', 'mask'):
      if out.ndim == 1:
        outs.append(jnp.where(ok, out, 0))
      else:
        outs.append(jnp.where(ok[:, None], out, 0))
  return tuple(outs), plan.stats


def dist_gather(shard_loc, bounds, ids, axis: str, num_parts: int):
  """Single-table convenience wrapper over :func:`dist_gather_multi`."""
  (out,), _ = dist_gather_multi((shard_loc,), bounds, ids, axis,
                                num_parts)
  return out


def cache_overlay(gathered, ids, cache_ids_loc, cache_rows_loc):
  """Overlay this device's remote-hot CACHE rows on exchanged results
  — the collective-era `cat_feature_cache` trick
  (`distributed/dist_dataset.py:77-164`: cached remote rows count as
  local).

  In the RPC world a cache hit skips a network round-trip; under
  fixed-capacity collectives the all_to_all buffers do not shrink with
  the hit count, so the cache is applied as a post-exchange OVERLAY
  (identical bytes, ONE shared feature+label exchange) rather than a
  miss-only second exchange — its value here is serving hot rows from
  the freshest local copy and keeping the offline cache plan
  meaningful for RPC-backed deployments.

  ``cache_ids_loc``: ``[C]`` sorted ids (CACHE_PAD_ID padded);
  ``cache_rows_loc``: ``[C, D]``.
  """
  c = cache_ids_loc.shape[0]
  pos = jnp.clip(jnp.searchsorted(cache_ids_loc, ids), 0, c - 1)
  hit = (cache_ids_loc[pos] == ids) & (ids >= 0)
  cache_val = cache_rows_loc[pos]
  return jnp.where(hit[:, None], cache_val, gathered)


def resolve_exchange_slack(exchange_slack, shuffle: bool):
  """Resolve the loaders' ``'auto'`` default: capped at
  `DEFAULT_EXCHANGE_SLACK` for shuffled seeds (near-balanced owner
  buckets), exact for sequential seeds (contiguous ranges can land
  entirely on one owner and a cap would drop most of them).
  ``'adaptive'`` passes through — the loaders attach an
  `AdaptiveSlack` controller (shuffled seeds only)."""
  if isinstance(exchange_slack, str):
    if exchange_slack == 'adaptive':
      if not shuffle:
        raise ValueError(
            "exchange_slack='adaptive' needs shuffle=True: sequential "
            'seed ranges can land entirely on one owner, where any '
            'cap silently drops most of a batch')
      return 'adaptive'
    if exchange_slack != 'auto':
      raise ValueError(f'unknown exchange_slack {exchange_slack!r}')
    return DEFAULT_EXCHANGE_SLACK if shuffle else None
  return exchange_slack


#: `AdaptiveSlack` ladder, tightest first.  2.0 is the static default;
#: the controller walks DOWN when an epoch ends drop-free (less
#: padding = smaller exchanges) and UP on drops, pinning after the
#: first reversal so it never oscillates.  The sub-1.25 rungs only
#: bite under the compact/hier layouts (the dense layout's
#: `MIN_EXCHANGE_CAP` floor dominates their caps) — they are what
#: lets the ladder keep reclaiming padding on drop-free workloads
#: instead of pinning at 1.25.
SLACK_LADDER = (0.75, 1.0, 1.25, 1.5, 2.0, 3.0, None)

#: tightest rung the ladder may reach by default (override per
#: controller or via ``GLT_SLACK_FLOOR``): the last step to 0.75
#: undercuts the BALANCED share and is opt-in.
DEFAULT_SLACK_FLOOR = 1.0

#: per-epoch frontier drop-rate above which the controller widens.
ADAPTIVE_DROP_TOLERANCE = 1e-3


class AdaptiveSlack:
  """Epoch-level exchange-capacity tuner (SURVEY §7 "partition-aware
  capacity tuning", made self-tuning).

  The static trade: a capacity of ``slack``x the balanced share
  shrinks every all_to_all by ``P/slack`` but drops frontier ids when
  ownership skews.  The right slack depends on the partition balance,
  which the telemetry measures per epoch — so the controller walks the
  `SLACK_LADDER` on epoch boundaries: drop-free epochs tighten one
  rung, a dropping epoch widens one rung, and the first tighten ->
  widen reversal PINS the setting (no oscillation).  Each change
  clears the sampler's step cache (one recompile, amortized over the
  remaining epochs).

  One slack value drives EVERY capacity knob of the selected exchange
  layout (`parallel.exchange.capacity_spec`): the dense per-
  destination cap, the compacted base width (its global overflow
  budget scales with the request width), and both hierarchical stage
  capacities — so the ladder tunes the new layouts with the same
  telemetry loop that tuned the dense cap.

  Args:
    floor: tightest slack the ladder may reach (default
      `DEFAULT_SLACK_FLOOR`, env ``GLT_SLACK_FLOOR``).  A drop-free
      epoch at the floor PINS there (``pin_reason='floor'``) — the
      controller is done, not stuck.
  """

  def __init__(self, sampler: 'DistNeighborSampler',
               start: float = DEFAULT_EXCHANGE_SLACK,
               floor: Optional[float] = None):
    import os
    self.sampler = sampler
    if floor is None:
      try:
        floor = float(os.environ.get('GLT_SLACK_FLOOR',
                                     DEFAULT_SLACK_FLOOR))
      except ValueError:
        floor = DEFAULT_SLACK_FLOOR
    finite = [s for s in SLACK_LADDER if s is not None]
    self._min_idx = min(
        (i for i, s in enumerate(SLACK_LADDER)
         if s is not None and s >= floor - 1e-9),
        default=len(finite) - 1)
    self.floor = SLACK_LADDER[self._min_idx]
    self._idx = SLACK_LADDER.index(start)
    self._pinned = False
    self._pin_reason = ''
    self._tightened_from = None
    self._last = {}
    sampler.exchange_slack = SLACK_LADDER[self._idx]

  @property
  def slack(self):
    return SLACK_LADDER[self._idx]

  def _set(self, idx: int, reason: str = '',
           drop_rate: float = 0.0, pin_reason: str = '') -> None:
    if idx == self._idx:
      return
    from ..telemetry.recorder import recorder
    from ..utils.profiling import metrics
    frm = SLACK_LADDER[self._idx]
    self._idx = idx
    self.sampler.exchange_slack = SLACK_LADDER[idx]
    self.sampler._steps.clear()       # new capacity = new program
    metrics.inc('dist.slack.transitions')
    recorder.emit('slack.transition', from_slack=frm,
                  to_slack=SLACK_LADDER[idx], reason=reason,
                  drop_rate=round(float(drop_rate), 6),
                  pin_reason=pin_reason)

  def _pin(self, reason: str, rate: float) -> None:
    self._pinned = True
    self._pin_reason = reason
    from ..telemetry.recorder import recorder
    recorder.emit('slack.pinned', slack=SLACK_LADDER[self._idx],
                  drop_rate=round(float(rate), 6), pin_reason=reason)

  #: ALL loss channels the shared slack caps gate — a clean frontier
  #: with skewed feature buckets must still read as "dropping"
  OFFER_KEYS = ('dist.frontier.offered', 'dist.feature.offered')
  DROP_KEYS = ('dist.frontier.dropped', 'dist.feature.dropped',
               'dist.negative.lost')

  def on_epoch_end(self) -> None:
    """Inspect the epoch's exchange telemetry and retune.  Ticks the
    metrics registry (a drain here must not swallow the epoch's
    residual delta from the global counters)."""
    st = self.sampler.exchange_stats()
    offered = sum(st[k] - self._last.get(k, 0) for k in self.OFFER_KEYS)
    dropped = sum(st[k] - self._last.get(k, 0) for k in self.DROP_KEYS)
    self._last = {k: st[k] for k in self.OFFER_KEYS + self.DROP_KEYS}
    if offered <= 0:
      return
    rate = dropped / offered
    # the hierarchical layout counts each id ONCE PER WIRE STAGE in
    # 'offered' (the per-wire fill contract), so its drop ratio reads
    # up to 2x low — compensate so the widen trigger fires at the
    # same per-id loss as the single-stage layouts
    tol = ADAPTIVE_DROP_TOLERANCE
    if resolve_layout(getattr(self.sampler, 'exchange_layout', None),
                      getattr(self.sampler, 'num_parts', 1)) == 'hier':
      tol = ADAPTIVE_DROP_TOLERANCE / 2
    if self._pinned and (self._pin_reason != 'floor'
                         or rate <= tol):
      # a reversal pin is final; a FLOOR pin only stops tightening —
      # drops at the floor must still get their capacity back
      return
    if rate > tol:
      # widen; if this reverses our own tighten, pin there
      wider = min(self._idx + 1, len(SLACK_LADDER) - 1)
      pin = (self._tightened_from is not None
             and wider >= self._tightened_from)
      self._set(wider, reason='drops', drop_rate=rate,
                pin_reason='reversal' if pin else '')
      if pin:
        self._pin('reversal', rate)
      else:
        self._pinned = False        # left the floor; resume tuning
    elif self._idx > self._min_idx:
      self._tightened_from = self._idx
      self._set(self._idx - 1, reason='drop_free', drop_rate=rate)
    elif not self._pinned:
      # drop-free AT the floor: the ladder is done tightening — pin
      # and say why, so 'slack_final == floor' is readable as
      # converged rather than stuck (the r5 envelope ambiguity)
      self._pin('floor', rate)

  # -- DataPlaneState (utils.checkpoint): the ladder's position -----------
  def state_dict(self) -> dict:
    """Rung index + pin state + the tighten-origin marker.  The
    telemetry baselines (``_last``) are NOT captured — they reference
    process-local cumulative counters that restart at zero in the
    resuming process; `load_state_dict` re-baselines against the live
    registry instead."""
    return {'idx': self._idx, 'pinned': int(self._pinned),
            'pin_reason': self._pin_reason,
            'tightened_from': (-1 if self._tightened_from is None
                               else int(self._tightened_from))}

  def load_state_dict(self, state: dict) -> None:
    idx = int(np.asarray(state['idx']))
    if idx != self._idx:
      self._set(idx, reason='restore')
    self._pinned = bool(int(np.asarray(state['pinned'])))
    self._pin_reason = str(np.asarray(state['pin_reason']))
    tf = int(np.asarray(state['tightened_from']))
    self._tightened_from = None if tf < 0 else tf
    st = self.sampler.exchange_stats()
    self._last = {k: st[k] for k in self.OFFER_KEYS + self.DROP_KEYS}


def _slack_cap(n: int, num_parts: int,
               exchange_slack: Optional[float],
               exchange_layout: Optional[str] = None, caps=None):
  """Capacity plan for one ``n``-id exchange: None = exact, else an
  `exchange.ExchangeSpec` under the sampler's layout (the dense spec
  reproduces the original ``max(ceil(n/P * slack), MIN_EXCHANGE_CAP)``
  rounded cap bit-for-bit).  ``caps``: the `EwmaCapacityModel`'s
  quantized ``(dest_cap, traffic_cap)`` for this channel (None keeps
  the uniform-share plan)."""
  d, t = caps if caps is not None else (None, None)
  return capacity_spec(n, num_parts, exchange_slack,
                       layout=exchange_layout, dest_cap=d,
                       traffic_cap=t)


def _expand_and_collect(indptr, indices, eids, bounds, seeds, key, *,
                        fanouts, node_cap, with_edge, collect_features,
                        collect_labels, with_cache, fshard, lshard,
                        cids, crows, axis, num_parts, exchange_slack,
                        exchange_layout=None,
                        collect_edge_features=False, efshard=None,
                        ebounds=None, ef_shard_mode='mod',
                        hot_counts=None, gns_bits=None,
                        gns_boost=None, book_spec=None,
                        cache_local=False, fr_caps=None, ft_caps=None):
  """Per-device multihop expansion + feature/label collection — the
  shared body of the node and link SPMD steps.  When
  ``collect_edge_features`` is set, every sampled edge's feature row is
  gathered by GLOBAL edge id through the same exchange machinery (the
  collective analog of the reference's efeats collation,
  `distributed/dist_neighbor_sampler.py:600-673`).  With ``gns_bits``
  set the hops sample cache-aware (GNS) and the per-edge importance
  weights come back aligned with the ``row``/``col`` edge list."""
  b = seeds.shape[0]
  state, seed_local = init_node(seeds, node_cap)
  f_cap = b
  slots = jnp.arange(f_cap, dtype=jnp.int32)
  fr_valid = slots < state.count
  frontier = jnp.where(
      fr_valid, state.nodes[jnp.clip(slots, 0, node_cap - 1)], INVALID_ID)
  frontier_local = jnp.where(fr_valid, slots, -1)

  rows_acc, cols_acc, eids_acc, ew_acc = [], [], [], []
  hop_counts = [state.count]
  fr_stats = jnp.zeros((3,), jnp.int32)
  ft_stats = jnp.zeros((3,), jnp.int32)
  # per-(src->dst)-RANGE traffic attribution (ISSUE 16): histogram the
  # ids each wire stage offers by their PartitionBook range owner —
  # this device's row of the fleet's P x P matrix.  Keyed by the RANGE
  # (identity book), so a row keeps meaning "ids in range r" even
  # after an adopted book remaps which physical device serves r.
  attr_owner = range_owner_fn(bounds)
  attr_fr = jnp.zeros((num_parts,), jnp.int32)
  attr_ft = jnp.zeros((num_parts,), jnp.int32)
  for h, k in enumerate(fanouts):
    hop_key = jax.random.fold_in(key, h)
    with layer_scope('telemetry', 'attribution'):
      attr_fr = attr_fr + dest_histogram(frontier, attr_owner, num_parts)
    nbrs, mask, e, hw, hstats = _dist_one_hop(
        indptr, indices, eids, bounds, frontier, int(k), hop_key,
        axis, num_parts, with_edge,
        exchange_capacity=_slack_cap(frontier.shape[0], num_parts,
                                     exchange_slack, exchange_layout,
                                     caps=fr_caps),
        gns_bits=gns_bits, gns_boost=gns_boost, book_spec=book_spec)
    with layer_scope('telemetry', 'exchange'):
      fr_stats = fr_stats + jnp.stack(hstats)
    state, rows, cols, prev_cnt = induce_next(
        state, frontier_local, nbrs, mask)
    rows_acc.append(rows)
    cols_acc.append(cols)
    if with_edge:
      eids_acc.append(jnp.where(rows >= 0, e.reshape(-1), INVALID_ID))
    if gns_bits is not None:
      # induce_next flattens [F, k] row-major, so the weight layout
      # matches the edge list's; masked/dropped edges carry 0
      ew_acc.append(jnp.where(rows >= 0, hw.reshape(-1), 0.0))
    hop_counts.append(state.count)
    f_cap = f_cap * int(k)
    slots = prev_cnt + jnp.arange(f_cap, dtype=jnp.int32)
    fr_valid = slots < state.count
    frontier = jnp.where(
        fr_valid, state.nodes[jnp.clip(slots, 0, node_cap - 1)],
        INVALID_ID)
    frontier_local = jnp.where(fr_valid, slots, -1)

  row = jnp.concatenate(rows_acc)
  col = jnp.concatenate(cols_acc)
  edge = jnp.concatenate(eids_acc) if with_edge else None
  ew = jnp.concatenate(ew_acc) if gns_bits is not None else None
  x = y = ef = None
  if collect_edge_features and edge is not None:
    (ef,), estats = dist_gather_multi(
        (efshard,), ebounds, edge, axis, num_parts,
        exchange_capacity=_slack_cap(edge.shape[0], num_parts,
                                     exchange_slack, exchange_layout,
                                     caps=ft_caps),
        shard_mode=ef_shard_mode, book_spec=book_spec)
    ef_owner = (edge_owner_fn(num_parts) if ef_shard_mode == 'mod'
                else range_owner_fn(ebounds))
    with layer_scope('telemetry', 'exchange'):
      ft_stats = ft_stats + jnp.stack(estats)
    with layer_scope('telemetry', 'attribution'):
      attr_ft = attr_ft + dest_histogram(edge, ef_owner, num_parts)
  tables = (((fshard,) if collect_features else ())
            + ((lshard,) if collect_labels else ()))
  replica_hits = jnp.zeros((1,), jnp.int32)
  if tables:
    node_valid = jnp.arange(node_cap, dtype=jnp.int32) < state.count
    gather_ids = state.nodes
    if with_cache and cache_local:
      # ISSUE 20 replica mode: rows replicated into this device's
      # cache are LOCAL — mask them out of the exchange request (the
      # overlay below fills them), and credit them to the attribution
      # diagonal via the dedicated stats slot.  This is what turns
      # hot-range coverage into avoided exchange bytes; the plain
      # offline cache plan (cache_local=False) keeps the byte-
      # identical post-exchange overlay.
      c = cids.shape[0]
      pos = jnp.clip(jnp.searchsorted(cids, state.nodes), 0, c - 1)
      local_hit = (cids[pos] == state.nodes) & (state.nodes >= 0) \
          & node_valid
      if hot_counts is None and book_spec is None:
        # owner bypass: rows THIS device already owns never need the
        # round trip either — serve them by a direct local-shard take
        # below.  With the diagonal off the wire, the EWMA capacity
        # model sizes the feature lanes from true REMOTE demand (the
        # diagonal otherwise pins `dest_cap`: locality partitioning
        # makes self-traffic the busiest cell).  Gated to the
        # full-resident store under the identity book — a tiered
        # shard holds only hot rows, and an adopted/remapped book
        # means the local shard no longer spans [bounds[p],
        # bounds[p+1]).
        my = jax.lax.axis_index(axis)
        lo = jnp.take(jnp.asarray(bounds), my)
        hi = jnp.take(jnp.asarray(bounds), my + 1)
        local_hit = local_hit | ((state.nodes >= lo)
                                 & (state.nodes < hi) & node_valid)
      gather_ids = jnp.where(local_hit, INVALID_ID, state.nodes)
      with layer_scope('telemetry', 'cold'):
        replica_hits = jnp.sum(local_hit.astype(jnp.int32))[None]
    got, gstats = dist_gather_multi(
        tables, bounds, gather_ids, axis, num_parts,
        exchange_capacity=_slack_cap(node_cap, num_parts,
                                     exchange_slack, exchange_layout,
                                     caps=ft_caps),
        hot_counts=hot_counts if collect_features else None,
        book_spec=book_spec)
    got = list(got)
    with layer_scope('telemetry', 'exchange'):
      ft_stats = ft_stats + jnp.stack(gstats)
    with layer_scope('telemetry', 'attribution'):
      attr_ft = attr_ft + dest_histogram(
          gather_ids, attr_owner, num_parts,
          valid=node_valid & (gather_ids >= 0))
    if collect_features:
      x = got.pop(0)
      if with_cache:
        # overlay local cache hits on the exchanged rows (see
        # `cache_overlay` for why this is an overlay, not a
        # miss-only exchange)
        x = cache_overlay(x, state.nodes, cids, crows)
        if cache_local and hot_counts is None and book_spec is None:
          # owner-bypass fill: the ids masked out above as self-owned
          # come straight from the resident shard
          my = jax.lax.axis_index(axis)
          lo = jnp.take(jnp.asarray(bounds), my)
          hi = jnp.take(jnp.asarray(bounds), my + 1)
          own = (state.nodes >= lo) & (state.nodes < hi) & node_valid
          rowsl = jnp.take(
              fshard, jnp.clip(state.nodes - lo, 0,
                               fshard.shape[0] - 1), axis=0)
          x = jnp.where(own[:, None], rowsl, x)
    if collect_labels:
      y = got.pop(0)
  cum = jnp.stack(hop_counts)
  nsn = jnp.concatenate([cum[:1], cum[1:] - cum[:-1]]).astype(jnp.int32)
  # stats layout: [7] scalar triple pairs + negative.lost slot, then
  # the [2P + 1] attribution tail (frontier dests, feature dests,
  # replica-hit count) — see `ExchangeTelemetry._accumulate_stats`
  # for the host-side split
  with layer_scope('telemetry', 'exchange'):
    stats = jnp.concatenate([fr_stats, ft_stats,
                             jnp.zeros((1,), jnp.int32), attr_fr, attr_ft,
                             replica_hits])
  return state, row, col, edge, seed_local, x, y, ef, nsn, stats, ew


def _make_dist_step(mesh: Mesh, num_parts: int, fanouts: Tuple[int, ...],
                    node_cap: int, with_edge: bool, collect_features: bool,
                    collect_labels: bool, axis: str = 'data',
                    with_cache: bool = False,
                    exchange_slack: Optional[float] = None,
                    exchange_layout: Optional[str] = None,
                    collect_edge_features: bool = False,
                    ef_shard_mode: str = 'mod', tiered: bool = False,
                    gns_boost: Optional[float] = None,
                    book_spec=None, cache_local: bool = False,
                    ewma_caps=None):
  """Build the jitted SPMD sample(+collect) step.

  ``exchange_slack``: per-destination exchange capacity as a multiple
  of the balanced share (``frontier/P``); None = uncapped (full
  frontier width, ~P x padding).  See `bucket_by_owner`.
  ``tiered``: the feature table is HBM-partial — owners zero rows past
  their hot count (``hcounts``) and the caller overlays the cold tier.
  ``gns_boost``: non-None builds the GNS variant — the step takes a
  replicated cached-set bitmask (``gns_bits``) before ``key`` and
  returns the per-edge importance weights as a 12th output; None
  builds EXACTLY the unbiased step (same signature, same program —
  the ``GLT_GNS=0`` byte-identity contract).
  """
  from .shard_map_compat import shard_map
  gns = gns_boost is not None

  def per_device(indptr_s, indices_s, eids_s, bounds, seeds_s, fshard_s,
                 lshard_s, cids_s, crows_s, efshard_s, ebounds, hcounts,
                 *rest):
    gns_bits = rest[0] if gns else None
    key = rest[-1]
    (state, row, col, edge, seed_local, x, y, ef, nsn, stats,
     ew) = _expand_and_collect(
        indptr_s[0], indices_s[0], eids_s[0] if with_edge else None,
        bounds, seeds_s[0], key,
        fanouts=fanouts, node_cap=node_cap, with_edge=with_edge,
        collect_features=collect_features, collect_labels=collect_labels,
        with_cache=with_cache,
        fshard=fshard_s[0] if collect_features else None,
        lshard=lshard_s[0] if collect_labels else None,
        cids=cids_s[0] if with_cache else None,
        crows=crows_s[0] if with_cache else None,
        axis=axis, num_parts=num_parts, exchange_slack=exchange_slack,
        exchange_layout=exchange_layout,
        collect_edge_features=collect_edge_features,
        efshard=efshard_s[0] if collect_edge_features else None,
        ebounds=ebounds, ef_shard_mode=ef_shard_mode,
        hot_counts=hcounts if tiered else None,
        gns_bits=gns_bits, gns_boost=gns_boost, book_spec=book_spec,
        cache_local=cache_local,
        fr_caps=ewma_caps.get('frontier') if ewma_caps else None,
        ft_caps=ewma_caps.get('feature') if ewma_caps else None)

    def lead(v):   # re-add the shard axis for stacked outputs
      return None if v is None else v[None]
    out = (lead(state.nodes), lead(state.count[None]), lead(row),
           lead(col), lead(edge), lead(seed_local), lead(x), lead(y),
           lead(ef), lead(nsn), lead(stats))
    return out + (lead(ew),) if gns else out

  specs_in = (P(axis), P(axis), P(axis), P(), P(axis), P(axis), P(axis),
              P(axis), P(axis), P(axis), P(), P()) \
      + ((P(),) if gns else ()) + (P(),)
  specs_out = tuple(P(axis) for _ in range(12 if gns else 11))
  sharded = shard_map(per_device, mesh=mesh, in_specs=specs_in,
                      out_specs=specs_out)

  @jax.jit
  def step(indptr_s, indices_s, eids_s, bounds, seeds_s, fshard_s,
           lshard_s, cids_s, crows_s, efshard_s, ebounds, hcounts,
           *rest):
    return sharded(indptr_s, indices_s, eids_s, bounds, seeds_s,
                   fshard_s, lshard_s, cids_s, crows_s, efshard_s,
                   ebounds, hcounts, *rest)

  return step


def _make_dist_link_step(mesh: Mesh, num_parts: int,
                         fanouts: Tuple[int, ...], node_cap: int,
                         batch: int, num_nodes: int,
                         neg_mode: Optional[str], num_neg: int,
                         neg_amount: float,
                         with_edge: bool, collect_features: bool,
                         collect_labels: bool, axis: str = 'data',
                         with_cache: bool = False,
                         exchange_slack: Optional[float] = None,
                         exchange_layout: Optional[str] = None,
                         collect_edge_features: bool = False,
                         ef_shard_mode: str = 'mod',
                         tiered: bool = False,
                         gns_boost: Optional[float] = None,
                         book_spec=None, cache_local: bool = False,
                         ewma_caps=None):
  """Build the jitted SPMD LINK sample step: per-device seed edges +
  collective strict negatives + the shared expansion body.

  The device analog of the reference's `_sample_from_edges`
  (`distributed/dist_neighbor_sampler.py:327-453`) — with the key
  difference that negatives are strict against the GLOBAL sharded
  graph (one `dist_edge_exists` exchange), where the reference settles
  for local-partition rejection.  ``gns_boost``: as `_make_dist_step`
  (non-None adds the bitmask input + the edge-weight output; the
  negative draws stay uniform — only the endpoint EXPANSION biases).
  """
  from .shard_map_compat import shard_map
  gns = gns_boost is not None

  def per_device(indptr_s, indices_s, eids_s, bounds, pairs_s, fshard_s,
                 lshard_s, cids_s, crows_s, efshard_s, ebounds, hcounts,
                 *rest):
    gns_bits = rest[0] if gns else None
    key = rest[-1]
    indptr = indptr_s[0]
    indices = indices_s[0]
    pairs = pairs_s[0]                       # [B, 2|3]
    src, dst = pairs[:, 0], pairs[:, 1]
    my_idx = jax.lax.axis_index(axis)
    neg_key = jax.random.fold_in(jax.random.fold_in(key, my_idx), 977)
    cap = _slack_cap(num_neg * NEG_TRIALS, num_parts,
                     exchange_slack, exchange_layout)
    neg_ok = None
    if neg_mode == 'binary':
      nrows, ncols, neg_ok = dist_sample_negative(
          indptr, indices, bounds, num_nodes, num_nodes, num_neg,
          neg_key, axis, num_parts, exchange_capacity=cap,
          book_spec=book_spec)
      seeds = jnp.concatenate([src, dst, nrows, ncols])
    elif neg_mode == 'triplet':
      amount = num_neg // batch
      srcs_rep = jnp.repeat(jnp.where(src >= 0, src, 0), amount)
      _, negs, neg_ok = dist_sample_negative(
          indptr, indices, bounds, num_nodes, num_nodes, num_neg,
          neg_key, axis, num_parts, exchange_capacity=cap,
          rows_fixed=srcs_rep.astype(jnp.int32),
          book_spec=book_spec)
      seeds = jnp.concatenate([src, dst, negs])
    else:
      seeds = jnp.concatenate([src, dst])
    seeds = jnp.where(seeds >= 0, seeds, INVALID_ID).astype(jnp.int32)

    (state, row, col, edge, seed_local, x, y, ef, nsn, stats,
     ew) = _expand_and_collect(
        indptr, indices, eids_s[0] if with_edge else None, bounds,
        seeds, key,
        fanouts=fanouts, node_cap=node_cap, with_edge=with_edge,
        collect_features=collect_features, collect_labels=collect_labels,
        with_cache=with_cache,
        fshard=fshard_s[0] if collect_features else None,
        lshard=lshard_s[0] if collect_labels else None,
        cids=cids_s[0] if with_cache else None,
        crows=crows_s[0] if with_cache else None,
        axis=axis, num_parts=num_parts, exchange_slack=exchange_slack,
        exchange_layout=exchange_layout,
        collect_edge_features=collect_edge_features,
        efshard=efshard_s[0] if collect_edge_features else None,
        ebounds=ebounds, ef_shard_mode=ef_shard_mode,
        hot_counts=hcounts if tiered else None,
        gns_bits=gns_bits, gns_boost=gns_boost, book_spec=book_spec,
        cache_local=cache_local,
        fr_caps=ewma_caps.get('frontier') if ewma_caps else None,
        ft_caps=ewma_caps.get('feature') if ewma_caps else None)

    b = batch
    sl = seed_local
    pair_valid = (src >= 0) & (dst >= 0)
    pos_label = jnp.where(
        pair_valid,
        pairs[:, 2] if pairs.shape[1] > 2 else jnp.ones((b,), jnp.int32),
        0)
    if neg_mode == 'binary':
      eli = jnp.stack([jnp.concatenate([sl[:b], sl[2 * b:2 * b + num_neg]]),
                       jnp.concatenate([sl[b:2 * b], sl[2 * b + num_neg:]])])
      elab = jnp.concatenate([pos_label,
                              jnp.zeros((num_neg,), jnp.int32)])
      # exhausted-trials slots may be REAL edges, and padded tail
      # batches keep the neg_amount-per-positive contract: negatives
      # beyond ceil(valid_pairs * amount) are masked out
      quota = jnp.ceil(jnp.sum(pair_valid)
                       * jnp.float32(neg_amount)).astype(jnp.int32)
      neg_keep = neg_ok & (jnp.arange(num_neg) < quota)
      emask_lab = jnp.concatenate([pair_valid, neg_keep])
      md = (eli, elab, emask_lab, jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b, 1), jnp.int32))
    elif neg_mode == 'triplet':
      amount = num_neg // batch
      dn = jnp.where(neg_ok, sl[2 * b:], -1).reshape(b, amount)
      md = (jnp.zeros((2, 1), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), bool), sl[:b], sl[b:2 * b], dn)
    else:
      eli = jnp.stack([sl[:b], sl[b:2 * b]])
      md = (eli, pos_label, pair_valid, jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b, 1), jnp.int32))

    if neg_ok is not None:
      with layer_scope('telemetry', 'negative'):
        stats = stats.at[6].add(
            jnp.sum((~neg_ok).astype(jnp.int32)))

    def lead(v):
      return None if v is None else v[None]
    out = ((lead(state.nodes), lead(state.count[None]), lead(row),
            lead(col), lead(edge), lead(seed_local), lead(x), lead(y),
            lead(ef), lead(nsn), lead(stats))
           + ((lead(ew),) if gns else ())
           + tuple(lead(m) for m in md))
    return out

  specs_in = (P(axis), P(axis), P(axis), P(), P(axis), P(axis), P(axis),
              P(axis), P(axis), P(axis), P(), P()) \
      + ((P(),) if gns else ()) + (P(),)
  specs_out = tuple(P(axis) for _ in range(18 if gns else 17))
  sharded = shard_map(per_device, mesh=mesh, in_specs=specs_in,
                      out_specs=specs_out)

  @jax.jit
  def step(indptr_s, indices_s, eids_s, bounds, pairs_s, fshard_s,
           lshard_s, cids_s, crows_s, efshard_s, ebounds, hcounts,
           *rest):
    return sharded(indptr_s, indices_s, eids_s, bounds, pairs_s,
                   fshard_s, lshard_s, cids_s, crows_s, efshard_s,
                   ebounds, hcounts, *rest)

  return step


def _make_dist_subgraph_step(mesh: Mesh, num_parts: int,
                             fanouts: Tuple[int, ...], node_cap: int,
                             max_degree: int, with_edge: bool,
                             collect_features: bool, collect_labels: bool,
                             axis: str = 'data',
                             with_cache: bool = False,
                             exchange_slack: Optional[float] = None,
                             exchange_layout: Optional[str] = None,
                             tiered: bool = False,
                             hop_chunk: Optional[int] = None,
                             book_spec=None):
  """Build the jitted SPMD INDUCED-SUBGRAPH step — the device-mesh
  analog of reference ``DistNeighborSampler._subgraph``
  (`distributed/dist_neighbor_sampler.py:456-516`).

  Per device: multihop closure over the sharded CSR (the shared
  expansion body), then ONE full-window distributed hop with
  ``k = max_degree`` — each owner returns every out-neighbor of the
  closure nodes it owns (no sampling: the Gumbel top-k window is exact
  when ``deg <= k``) — and a LOCAL sort-based membership test +
  relabel against this device's closure set.  The membership test runs
  at the requester, which owns its closure, so no closure-set
  all_gather is needed; edge (u, v) is emitted exactly once, by u's
  window, in natural (source, dest) direction like the single-chip
  `ops.subgraph.induced_subgraph`.

  ``hop_chunk`` bounds the full-window exchange: the node table is
  scanned in chunks of that many closure nodes, so every all_to_all
  buffer is ``[P, chunk]`` requests / ``[P, chunk, max_degree]``
  replies instead of ``[P, node_cap]`` — the SEAL-at-scale envelope
: peak exchange width becomes
  ``chunk * P * max_degree`` regardless of closure size, at the cost
  of ``ceil(node_cap / chunk)`` serialized exchanges.  Results are
  EXACT either way (each chunk's window is still unsampled).
  """
  from .shard_map_compat import shard_map
  chunk = node_cap if hop_chunk is None else max(int(hop_chunk), 1)
  chunk = min(chunk, node_cap)
  n_chunks = -(-node_cap // chunk)
  pad_cap = n_chunks * chunk

  def per_device(indptr_s, indices_s, eids_s, bounds, seeds_s, fshard_s,
                 lshard_s, cids_s, crows_s, hcounts, key):
    (state, _row, _col, _edge, seed_local, x, y, _ef, nsn, stats,
     _ew) = _expand_and_collect(
        indptr_s[0], indices_s[0], None, bounds, seeds_s[0], key,
        fanouts=fanouts, node_cap=node_cap, with_edge=False,
        collect_features=collect_features, collect_labels=collect_labels,
        with_cache=with_cache,
        fshard=fshard_s[0] if collect_features else None,
        lshard=lshard_s[0] if collect_labels else None,
        cids=cids_s[0] if with_cache else None,
        crows=crows_s[0] if with_cache else None,
        axis=axis, num_parts=num_parts, exchange_slack=exchange_slack,
        exchange_layout=exchange_layout,
        hot_counts=hcounts if tiered else None, book_spec=book_spec)

    nodes = state.nodes                              # [node_cap]
    nodes_pad = jnp.concatenate(
        [nodes, jnp.full((pad_cap - node_cap,), INVALID_ID,
                         nodes.dtype)]) if pad_cap > node_cap else nodes
    nbrs_parts, mask_parts, eids_parts = [], [], []
    for ci in range(n_chunks):
      frontier_c = jax.lax.dynamic_slice_in_dim(nodes_pad, ci * chunk,
                                                chunk)
      nb, mk, ei, _w, hstats = _dist_one_hop(
          indptr_s[0], indices_s[0], eids_s[0] if with_edge else None,
          bounds, frontier_c, max_degree,
          # per-chunk fold: with a truncating max_degree the window
          # draws must stay independent across chunks
          jax.random.fold_in(key, ci), axis, num_parts,
          with_edge,
          exchange_capacity=_slack_cap(chunk, num_parts,
                                       exchange_slack,
                                       exchange_layout),
          book_spec=book_spec)
      with layer_scope('telemetry', 'exchange'):
        stats = stats.at[:3].add(jnp.stack(hstats))
      # full-window hops are frontier traffic too: extend this
      # device's src->dst attribution row (stats[7:7+P])
      with layer_scope('telemetry', 'attribution'):
        stats = stats.at[7:7 + num_parts].add(
            dest_histogram(frontier_c, range_owner_fn(bounds),
                           num_parts))
      nbrs_parts.append(nb)
      mask_parts.append(mk)
      if with_edge:
        eids_parts.append(ei)
    nbrs = jnp.concatenate(nbrs_parts)[:node_cap]
    mask = jnp.concatenate(mask_parts)[:node_cap]
    eids = (jnp.concatenate(eids_parts)[:node_cap] if with_edge
            else None)
    big = jnp.iinfo(jnp.int32).max
    keyed = jnp.where(nodes >= 0, nodes, big)
    order = jnp.argsort(keyed)
    sorted_nodes = keyed[order]
    flat = nbrs.reshape(-1)
    loc = jnp.clip(jnp.searchsorted(sorted_nodes, flat), 0,
                   node_cap - 1).astype(jnp.int32)
    hit = (sorted_nodes[loc] == flat) & (flat >= 0) & mask.reshape(-1)
    col = jnp.where(hit, order[loc], INVALID_ID).astype(jnp.int32)
    row = jnp.where(
        hit,
        jnp.repeat(jnp.arange(node_cap, dtype=jnp.int32), max_degree),
        INVALID_ID)
    edge = (jnp.where(hit, eids.reshape(-1), INVALID_ID)
            if with_edge else None)

    def lead(v):
      return None if v is None else v[None]
    return (lead(nodes), lead(state.count[None]), lead(row), lead(col),
            lead(edge), lead(seed_local), lead(x), lead(y), lead(nsn),
            lead(stats))

  specs_in = (P(axis), P(axis), P(axis), P(), P(axis), P(axis), P(axis),
              P(axis), P(axis), P(), P())
  specs_out = tuple(P(axis) for _ in range(10))
  sharded = shard_map(per_device, mesh=mesh, in_specs=specs_in,
                      out_specs=specs_out)

  @jax.jit
  def step(indptr_s, indices_s, eids_s, bounds, seeds_s, fshard_s,
           lshard_s, cids_s, crows_s, hcounts, key):
    return sharded(indptr_s, indices_s, eids_s, bounds, seeds_s,
                   fshard_s, lshard_s, cids_s, crows_s, hcounts, key)

  return step


class ExchangeTelemetry:
  """Device-resident exchange-overflow telemetry shared by the mesh
  samplers: adding each step's stacked ``[P, 7]`` stats stays async
  (no per-batch host sync); `exchange_stats` materializes totals at
  epoch/bench boundaries and ticks the global metrics registry."""

  #: auto-drain interval: the device counter is int32 (x64 disabled)
  #: and the biggest per-step count (exchange SLOTS at the reference
  #: workload) is ~2e7, so 64 steps stay safely under 2^31.  Draining
  #: costs one [7]-scalar transfer at the tail of an already-dispatched
  #: chain — negligible against a training step.
  STATS_DRAIN_INTERVAL = 64

  def _init_stats(self) -> None:
    import threading
    # prefetch workers (`loader.prefetch`) call the sampler from a
    # second thread; the read-modify-write on the accumulators must
    # not interleave with an exchange_stats() drain
    self._stats_lock = threading.Lock()
    self._stats_acc = jnp.zeros((len(EXCHANGE_STAT_NAMES),), jnp.int32)
    self._stats_total = np.zeros(len(EXCHANGE_STAT_NAMES), np.int64)
    self._stats_pending = 0
    # per-(src device -> dst range) traffic attribution (ISSUE 16):
    # the step's stats vector carries [2P] histogram tails (frontier
    # dests, feature dests) per device; they accumulate UN-summed —
    # row = src device — into the device-resident [P, 2P] matrix
    self._attr_acc = None
    self._attr_total: Optional[np.ndarray] = None
    self._attr_reported = (0, 0)
    # host-side cold-tier counters (tiered feature stores only).
    # Definitions:
    #   lookups      = valid node-table feature lookups;
    #   cold_lookups = lookups past the owner's hot count (the cold
    #                  tier's demand — the cache denominator);
    #   cold_misses  = cold lookups the HOST tier served (cache
    #                  misses; each one is host-gather work);
    #   cache_*      = dynamic HBM victim-cache traffic
    #                  (`data.cold_cache`).
    self._feat_lookups = 0
    self._cold_lookups = 0
    self._cold_misses = 0
    self._cache_hits = 0
    self._cache_admits = 0
    self._cache_evicts = 0
    self._cold_reported = (0,) * 6

  def _accumulate_stats(self, stats_stacked) -> None:
    n = len(EXCHANGE_STAT_NAMES)
    base = stats_stacked[:, :n]
    attr = stats_stacked[:, n:]
    with self._stats_lock:
      self._stats_acc = self._stats_acc + jnp.sum(base, axis=0)
      if attr.shape[1]:
        self._attr_acc = (attr if self._attr_acc is None
                          else self._attr_acc + attr)
      self._stats_pending += 1
      drain = self._stats_pending >= self.STATS_DRAIN_INTERVAL
    if drain:
      self.exchange_stats()

  def _stats_state(self) -> np.ndarray:
    """Cumulative counter snapshot (exchange totals + cold-tier host
    counters) as ONE int64 leaf — saved with each chunk snapshot so a
    degraded-mode rollback (`parallel.fused._rollback_to_snapshot`)
    can rewind the counters a re-dispatched chunk would otherwise
    double-count."""
    self.exchange_stats(tick_metrics=False)     # drain the device acc
    with self._stats_lock:
      cold = (self._feat_lookups, self._cold_lookups,
              self._cold_misses, self._cache_hits, self._cache_admits,
              self._cache_evicts)
      parts = [self._stats_total, np.asarray(cold, np.int64)]
      if self._attr_total is not None:
        # the [P, 2P] attribution matrix rides flattened at the tail;
        # shape reconstructs from the size (2P^2) alone
        parts.append(self._attr_total.reshape(-1))
      return np.concatenate(parts)

  def _load_stats_state(self, packed) -> None:
    arr = np.asarray(packed, np.int64)
    n = len(EXCHANGE_STAT_NAMES)
    with self._stats_lock:
      self._stats_acc = jnp.zeros_like(self._stats_acc)
      self._attr_acc = None
      self._stats_pending = 0
      self._stats_total = arr[:n].copy()
      (self._feat_lookups, self._cold_lookups, self._cold_misses,
       self._cache_hits, self._cache_admits,
       self._cache_evicts) = (int(v) for v in arr[n:n + 6])
      tail = arr[n + 6:]
      if tail.size:
        # rows = device count, cols = 2P+1 (frontier dests, feature
        # dests, replica-hit count — ISSUE 20) or 2P for pre-replica
        # snapshots; prefer the sampler's own num_parts (rows ==
        # cols/2 only when mesh size == P)
        p = getattr(self, 'num_parts',
                    int(round(np.sqrt(tail.size / 2))))
        cols = (2 * p + 1) if tail.size % (2 * p + 1) == 0 else 2 * p
        self._attr_total = tail.reshape(-1, cols).copy()
      else:
        # pre-attribution snapshot: counters restore, the matrix
        # restarts cold (documented fallback)
        self._attr_total = None
      # the registry watermark must never exceed the rewound counters
      # (a negative delta would tick the global metrics backwards)
      self._cold_reported = tuple(
          min(r, int(v)) for r, v in zip(self._cold_reported,
                                         arr[n:n + 6]))

  def exchange_stats(self, tick_metrics: bool = True):
    """Materialize cumulative exchange telemetry (one device sync).

    Returns ``{'dist.frontier.offered': n, ...}`` totals since
    construction; the delta since the previous call is also ticked
    into the global `utils.profiling.metrics` registry so overflow
    drops are never invisible.
    """
    # the WHOLE drain runs under the lock (a prefetch worker's
    # interval drain may race a caller's): totals and the reported-
    # watermark are read-modify-write shared state too.  Only the
    # registry ticks happen outside, on snapshot values.
    with self._stats_lock:
      acc = self._stats_acc
      self._stats_acc = jnp.zeros_like(acc)
      attr_acc = self._attr_acc
      self._attr_acc = None
      self._stats_pending = 0
      delta = np.asarray(jax.device_get(acc), np.int64)
      self._stats_total += delta
      if attr_acc is not None:
        a = np.asarray(jax.device_get(attr_acc), np.int64)
        if (self._attr_total is None
            or self._attr_total.shape != a.shape):
          self._attr_total = np.zeros_like(a)
        self._attr_total += a
      totals = self._stats_total.copy()
      cold_now = (self._feat_lookups, self._cold_lookups,
                  self._cold_misses, self._cache_hits,
                  self._cache_admits, self._cache_evicts)
      cold_delta = (0,) * 6
      if tick_metrics:
        cold_delta = tuple(n - p for n, p
                           in zip(cold_now, self._cold_reported))
        self._cold_reported = cold_now
    out = {f'dist.{n}': int(v)
           for n, v in zip(EXCHANGE_STAT_NAMES, totals)}
    lookups, cold_lookups, cold_misses, hits, admits, evicts = cold_now
    out['dist.feature.lookups'] = lookups
    out['dist.feature.cold_lookups'] = cold_lookups
    out['dist.feature.cold_misses'] = cold_misses
    out['dist.feature.cache_hits'] = hits
    out['dist.feature.cache_admits'] = admits
    out['dist.feature.cache_evicts'] = evicts
    # hot_hit_rate: fraction of feature lookups the HBM hot tier
    # served (what r5's "cold_hit_rate" actually measured);
    # cache/cold_hit_rate: fraction of COLD lookups served on-device
    # by the victim cache — each miss is host-gather work.
    out['dist.feature.hot_hit_rate'] = (
        1.0 - cold_lookups / lookups if lookups else 1.0)
    out['dist.feature.cache_hit_rate'] = (
        1.0 - cold_misses / cold_lookups if cold_lookups else 0.0)
    out['dist.feature.cold_hit_rate'] = out[
        'dist.feature.cache_hit_rate']
    if tick_metrics:
      from ..telemetry.recorder import recorder
      from ..utils.profiling import metrics
      for n, d in zip(EXCHANGE_STAT_NAMES, delta):
        if d:
          metrics.inc(f'dist.{n}', float(d))
      for n, d in zip(('lookups', 'cold_lookups', 'cold_misses',
                       'cache_hits', 'cache_admits', 'cache_evicts'),
                      cold_delta):
        if d > 0:
          metrics.inc(f'dist.feature.{n}', float(d))
      if delta.any():
        # one flight-recorder event per drain window: the since-last
        # deltas, so a JSONL reader sees the exchange trajectory
        # without diffing cumulative totals
        recorder.emit(
            'dist.exchange',
            **{n.replace('.', '_'): int(d)
               for n, d in zip(EXCHANGE_STAT_NAMES, delta)})
      if cold_delta[1] > 0:
        recorder.emit('dist.cold_tier',
                      lookups=int(cold_delta[0]),
                      cold_lookups=int(cold_delta[1]),
                      misses=int(cold_delta[2]),
                      cache_hits=int(cold_delta[3]),
                      hit_rate=round(
                          1.0 - cold_delta[2] / cold_delta[1], 6))
    return out

  def attribution_matrices(self) -> Tuple[np.ndarray, np.ndarray]:
    """``(frontier, feature)`` — two ``[P, P]`` int64 id-count
    matrices, row = SRC device, column = DST range (`PartitionBook`
    identity ranges, so columns keep meaning "range r" under adopted
    books).  Drains the device accumulator (one sync)."""
    self.exchange_stats(tick_metrics=False)
    with self._stats_lock:
      tot = self._attr_total
      if tot is None:
        p = int(getattr(self, 'num_parts', 0) or 0)
        z = np.zeros((p, p), np.int64)
        return z, z.copy()
      # cols = 2P (pre-replica) or 2P+1 (trailing replica-hit count)
      p = tot.shape[1] // 2
      return tot[:, :p].copy(), tot[:, p:2 * p].copy()

  def replica_hits(self) -> int:
    """Cumulative feature lookups served WITHOUT riding the exchange
    (ISSUE 20b): replica-set hits plus the owner bypass's self-owned
    rows — everything the masked gather kept OFF the wire.  0 when
    the stats tail predates the replica slot or no replicas exist."""
    self.exchange_stats(tick_metrics=False)
    with self._stats_lock:
      tot = self._attr_total
      if tot is None or tot.shape[1] % 2 == 0:
        return 0
      return int(tot[:, -1].sum())

  def attribution_stats(self, top_k: Optional[int] = None,
                        feature_row_bytes: Optional[int] = None,
                        tick_metrics: bool = True) -> dict:
    """Traffic attribution rollup (`report.py --attribution` input).

    Bytes: frontier ids weigh 4 B (int32 on the wire), feature ids
    weigh one feature row (inferred from the node-feature store when
    not given).  ``hot_ranges`` prefers the GNS sketches' decayed
    range mass (the learned hotness); without an active sketch it
    falls back to the attribution matrix's column mass — measured
    demand per range.
    """
    fr, ft = self.attribution_matrices()
    p = int(fr.shape[0])
    if feature_row_bytes is None:
      feature_row_bytes = 4
      try:
        sh = self.ds.node_features.shards
        feature_row_bytes = int(sh.shape[-1]) * int(
            np.dtype(sh.dtype).itemsize)
      except Exception:               # noqa: BLE001 — no feature
        pass                          # store on this sampler
    ids = fr + ft
    bytes_m = fr * 4 + ft * int(feature_row_bytes)
    # "local" is BOOK-OWNER-aware: cell (src device, dst range) costs
    # no wire bytes when the book routes range dst to device src —
    # under the identity book this is exactly the diagonal, and after
    # an adoption/rebalance the migrated range's column flips local on
    # its new owner's row (the matrices stay range-keyed).
    local_mask = np.eye(ids.shape[0], ids.shape[1], dtype=bool)
    book = getattr(self, 'book', None)
    if book is not None:
      try:
        owners = np.asarray(book.view().owners)
        if owners.shape[0] == ids.shape[1]:
          local_mask = (owners[None, :]
                        == np.arange(ids.shape[0])[:, None])
      except Exception:               # noqa: BLE001 — identity
        pass                          # fallback (no live view)
    # locally-served hits (ISSUE 20b) are feature rows the masked
    # gather served device-locally (replica copies + the owner
    # bypass's self-owned rows): they never reach the wire-truth
    # matrices, so credit them back as LOCAL demand.
    rep = self.replica_hits()
    total_ids = int(ids.sum()) + rep
    local_ids = int(ids[local_mask].sum()) + rep
    cross_ids = total_ids - local_ids
    rep_bytes = rep * int(feature_row_bytes)
    total_bytes = int(bytes_m.sum()) + rep_bytes
    cross_bytes = total_bytes - (int(bytes_m[local_mask].sum())
                                 + rep_bytes)

    mass = None
    source = 'exchange'
    cache = getattr(self, '_cold_cache', None)
    if cache is not None and getattr(cache, 'shards', None):
      ms = [sh.sketch.range_mass for sh in cache.shards
            if sh.sketch.range_mass is not None]
      if ms:
        agg = np.sum(ms, axis=0)
        if float(agg.sum()) > 0 and len(agg) == p:
          mass, source = agg.astype(np.float64), 'gns_sketch'
    if mass is None:
      mass = ids.sum(axis=0).astype(np.float64)   # demand per range
    total_mass = float(mass.sum())
    k = min(max(1, p // 4) if top_k is None else max(int(top_k), 1),
            max(p, 1))
    hot = []
    coverage = 0.0
    if p and total_mass > 0:
      order = np.argsort(-mass, kind='stable')[:k]
      hot = [{'partition': int(r),
              'share': round(float(mass[r] / total_mass), 6)}
             for r in order]
      coverage = round(float(mass[order].sum() / total_mass), 6)

    if tick_metrics:
      from ..telemetry.live import live
      d_local = max(local_ids - self._attr_reported[0], 0)
      d_cross = max(cross_ids - self._attr_reported[1], 0)
      self._attr_reported = (local_ids, cross_ids)
      if d_local:
        live.counter('exchange.local_ids_total').inc(d_local)
      if d_cross:
        live.counter('exchange.cross_ids_total').inc(d_cross)

    return {
        'num_parts': p,
        'feature_row_bytes': int(feature_row_bytes),
        'frontier_ids': fr.tolist(),
        'feature_ids': ft.tolist(),
        'bytes_matrix': bytes_m.tolist(),
        'local_ids': local_ids,
        'locally_served_ids': rep,
        'cross_ids': cross_ids,
        'cross_partition_ids_frac': (
            round(cross_ids / total_ids, 6) if total_ids else 0.0),
        'total_bytes': total_bytes,
        'cross_partition_bytes': cross_bytes,
        'cross_partition_bytes_frac': (
            round(cross_bytes / total_bytes, 6) if total_bytes
            else 0.0),
        'hotness_source': source,
        'top_k': k if p else 0,
        'hot_ranges': hot,
        'hot_range_coverage': coverage,
    }

  def _ewma_caps(self):
    """Per-channel ``(dest_cap, traffic_cap)`` dict for the step
    builders, or None when the EWMA model is off (the default — the
    compiled programs are then byte-identical to uniform shares)."""
    m = getattr(self, '_ewma_model', None)
    if m is None:
      return None
    caps = {c: m.caps(c) for c in m.CHANNELS}
    return caps if any(v != (None, None) for v in caps.values()) else None

  def capacity_retune(self) -> bool:
    """Epoch-end seam for the EWMA capacity co-design (ISSUE 20c):
    feed the attribution-matrix delta since the last retune into the
    `EwmaCapacityModel`; when a quantized cap moves, clear the step
    cache so the next dispatch compiles `capacity_spec(dest_cap=...)`
    sized to the OBSERVED per-destination traffic instead of uniform
    shares.  Returns True when the caps (and hence the programs)
    changed.  No-op unless GLT_EXCHANGE_EWMA is on."""
    m = getattr(self, '_ewma_model', None)
    if m is None:
      return False
    steps = int(self._step_cnt)
    d_steps = steps - self._ewma_last_steps
    if d_steps <= 0:
      return False
    fr, ft = self.attribution_matrices()
    last = self._ewma_last
    d_fr = fr - last[0] if last is not None else fr
    d_ft = ft - last[1] if last is not None else ft
    self._ewma_last = (fr, ft)
    self._ewma_last_steps = steps
    changed = m.observe('frontier', d_fr, d_steps)
    changed = m.observe('feature', d_ft, d_steps) or changed
    if changed:
      from ..telemetry.recorder import recorder
      caps = {c: m.caps(c) for c in m.CHANNELS}
      self._steps.clear()
      recorder.emit(
          'exchange.retune', steps=d_steps,
          frontier_dest_cap=caps['frontier'][0],
          frontier_traffic_cap=caps['frontier'][1],
          feature_dest_cap=caps['feature'][0],
          feature_traffic_cap=caps['feature'][1])
    return changed

  def cluster_exchange_stats(self) -> dict:
    """CLUSTER-wide exchange health: raw totals plus the derived
    padding-waste / drop-rate numbers the bench rounds track.

    The device-side counters are already global — each step's
    ``[P, 7]`` stats vector is summed over the sharded mesh axis
    before the host drains it, so every process reads the same
    cluster totals.  The HOST-side cold-tier counters are
    per-process; under multiple controllers they are summed over
    hosts via `telemetry.aggregate.allgather_sum_int`.  On a single
    controller (including the virtual CPU mesh) this is exactly
    `exchange_stats` plus the derived keys.
    """
    from ..telemetry.aggregate import allgather_sum_int, exchange_summary
    st = dict(self.exchange_stats())
    num_hosts = jax.process_count()
    if num_hosts > 1:
      keys = ('lookups', 'cold_lookups', 'cold_misses', 'cache_hits',
              'cache_admits', 'cache_evicts')
      summed = allgather_sum_int(
          [st[f'dist.feature.{k}'] for k in keys])
      for k, v in zip(keys, summed):
        st[f'dist.feature.{k}'] = v
      lookups, cold_lookups, cold_misses = summed[:3]
      st['dist.feature.hot_hit_rate'] = (
          1.0 - cold_lookups / lookups if lookups else 1.0)
      st['dist.feature.cache_hit_rate'] = (
          1.0 - cold_misses / cold_lookups if cold_lookups else 0.0)
      st['dist.feature.cold_hit_rate'] = st[
          'dist.feature.cache_hit_rate']
    st['num_hosts'] = num_hosts
    st.update(exchange_summary(st))
    return st


def put_stacked_host_local(mesh: Mesh, axis: str, num_parts: int,
                           host_parts, arr_local: np.ndarray) -> jax.Array:
  """Host-local put: this process holds only its partitions' slices
  (`DistDataset.host_parts`); assemble the GLOBAL ``[P, ...]`` array
  from per-device single-shard puts — no host ever materializes
  another host's tensors (the multi-host RAM story)."""
  from .multihost import host_partition_ids
  flat = mesh.devices.reshape(-1)
  mine = host_partition_ids(mesh).tolist()
  hp = list(np.asarray(host_parts))
  if mine != hp:
    raise ValueError(
        f'host_parts {hp} != this process\'s mesh positions {mine} '
        '— load with multihost.host_partition_ids(mesh)')
  assert arr_local.shape[0] == len(mine), (arr_local.shape, mine)
  shards = [jax.device_put(arr_local[j:j + 1], flat[i])
            for j, i in enumerate(mine)]
  return jax.make_array_from_single_device_arrays(
      (num_parts,) + tuple(arr_local.shape[1:]),
      NamedSharding(mesh, P(axis)), shards)


class DistNeighborSampler(ExchangeTelemetry):
  """Device-mesh distributed sampler (+ feature/label collection).

  The public analog of reference ``DistNeighborSampler``
  (`distributed/dist_neighbor_sampler.py:88-174`) — but synchronous
  SPMD: every call samples P per-device seed batches in one program.

  Args:
    dataset: `DistDataset` (sharded layout).
    num_neighbors: per-hop fanouts.
    mesh: mesh whose ``axis`` dimension matches the partition count.
  """

  def __init__(self, dataset: DistDataset, num_neighbors,
               mesh: Optional[Mesh] = None, axis: str = 'data',
               with_edge: bool = False, collect_features: bool = True,
               seed: int = 0, exchange_slack: Optional[float] = None,
               exchange_layout: Optional[str] = None,
               cold_cache_rows='auto', gns=None):
    from .dp import make_mesh
    self.ds = dataset
    self.fanouts = tuple(int(k) for k in num_neighbors)
    self.num_parts = dataset.num_partitions
    self.mesh = mesh or make_mesh(self.num_parts, axis)
    self.axis = axis
    self.with_edge = with_edge
    if with_edge and dataset.graph.edge_ids is None:
      raise ValueError(
          'with_edge=True needs edge ids, and this dataset was built '
          'without them (DistDataset.from_device_coo builds none)')
    self.collect_features = (collect_features
                             and dataset.node_features is not None)
    self.collect_labels = dataset.node_labels is not None
    # edge features need the sampled eids to gather by — implied
    # with_edge, like the reference's `with_edge=True` efeats contract
    self.collect_edge_features = (collect_features and with_edge
                                  and dataset.edge_features is not None)
    self._ef_shard_mode = (
        'mod' if (self.collect_edge_features
                  and dataset.edge_features.mod_sharded) else 'range')
    self.with_cache = (self.collect_features
                       and dataset.node_features.has_cache)
    # ISSUE 20 replica set (`from_full_graph(replica_frac=)`): the
    # cached rows are exact copies of remote rows, so the gather can
    # MASK them out of the exchange (served by the overlay) instead of
    # fetching them twice.  Offline cache plans (`cache_local=False`)
    # keep the historical overlay-after-gather semantics byte-for-byte.
    # Label collection shares the gathered id vector, so masking is
    # only sound when labels aren't gathered alongside.
    self.cache_local = bool(
        self.with_cache
        and getattr(dataset.node_features, 'cache_local', False)
        and self.collect_features and not self.collect_labels)
    # tiered store: HBM shards hold only each partition's hot rows;
    # cold rows live in host DRAM and are overlaid post-step
    # (`_maybe_overlay_cold`) — reference
    # `data/feature.py:174-206` + `csrc/cuda/unified_tensor.cu:202+`.
    self.tiered = (self.collect_features
                   and dataset.node_features.is_tiered)
    # dynamic HBM victim cache over cold rows (`data.cold_cache`):
    # built lazily on the first cold overlay; 'auto' sizes it to
    # GLT_COLD_CACHE_ROWS or 15% of the largest partition's cold rows
    self._cold_cache_spec = cold_cache_rows
    self._cold_cache = None
    self._cold_cache_built = False
    # cache-aware Global Neighbor Sampling (ops.gns, r11): bias
    # neighbor selection toward the device-servable set (hot split ∪
    # cold-cache residents) with a 1/q unbiasedness correction.  Only
    # meaningful on tiered feature stores (a fully-HBM table has no
    # cold tier to steer away from); `GLT_GNS=1` / gns=True enables,
    # off is byte-identical to the unbiased sampler.
    from ..ops.gns import gns_enabled, resolve_boost
    self.gns = bool(gns_enabled(gns) and self.tiered
                    and self.collect_features)
    self.gns_boost = resolve_boost() if self.gns else None
    self._gns_bits = None
    self._gns_hot_bits = None
    self._gns_ver = -1
    # SURVEY §7 "partition-aware capacity tuning": e.g. 2.0 sends
    # 2x the balanced share per destination instead of the full
    # frontier (P/2 x fewer exchanged bytes); overflowed ids lose
    # their neighbors/features that hop (counted by the telemetry).
    # None = exact; the loaders resolve 'auto' to
    # DEFAULT_EXCHANGE_SLACK when shuffling, exact otherwise.
    self.exchange_slack = exchange_slack
    # exchange LAYOUT (parallel.exchange): None/'auto' keeps dense on
    # small meshes and compacts at P >= 16; 'dense'/'compact'/'hier'/
    # 'ragged' select explicitly (env GLT_EXCHANGE_LAYOUT overrides
    # 'auto' only).  Exact exchanges (slack None) always run dense.
    self.exchange_layout = exchange_layout or 'auto'
    # ISSUE 20 exchange co-design: per-destination capacity from an
    # EWMA of the attribution matrices (GLT_EXCHANGE_EWMA=1).  The
    # model observes matrix deltas at `capacity_retune()` (epoch end)
    # and its power-of-two caps feed `capacity_spec(dest_cap=...)`;
    # off (default) compiles exactly the uniform-share programs.
    from .exchange import EwmaCapacityModel, ewma_enabled
    self._ewma_model = (EwmaCapacityModel(self.num_parts)
                        if ewma_enabled() else None)
    self._ewma_last = None
    self._ewma_last_steps = 0
    self._base_key = jax.random.key(seed)
    self._step_cnt = 0
    self._steps = {}
    self._device_arrays = None
    #: ISSUE 15 — the single routing authority.  The sampler compiles
    #: its steps against one pinned `BookView` per dispatch and fences
    #: at the `_arrays()` seam: a version bump (adoption) rebuilds the
    #: device arrays lane-stacked and recompiles the exchange plans
    #: for the new routing.  The identity book (version 0) compiles
    #: EXACTLY the pre-book program.
    self.book = dataset.partition_book
    self._book_ver = self.book.version
    self._shard_store = None
    # degraded write-offs are DATASET state (the stacks are zeroed in
    # place): the set is shared so every sampler over this dataset
    # classifies the loss identically, and `maybe_refresh_book` fences
    # on its size so siblings rebuild from the emptied stacks instead
    # of serving a stale full view
    if not hasattr(dataset, 'degraded_partitions'):
      dataset.degraded_partitions = set()
    self._degraded_partitions = dataset.degraded_partitions
    self._degraded_seen = len(self._degraded_partitions)
    # the load-time durable copy: with GLT_SHARD_DIR set, the shards
    # are written NOW (idempotent across samplers over one dataset) —
    # an owner lost later adopts from this copy, and recovery never
    # pays (or depends on) a serialize of the dead owner's memory
    self._resolve_shard_store()
    #: streaming ingestion (ISSUE 14): last `graph_version` this
    #: sampler's stacks were (re)built from.  Seeded from the version
    #: `attach_stream` restacked ds.graph at, so the first dispatch
    #: doesn't repeat that restack on an identical graph (None =
    #: static dataset).
    self._stream_ver = getattr(dataset, 'stream_version', None)
    self._init_stats()

  def _put_stacked(self, arr_local: np.ndarray) -> jax.Array:
    return put_stacked_host_local(self.mesh, self.axis, self.num_parts,
                                  self.ds.host_parts, arr_local)

  def _put_shard(self, a: np.ndarray) -> jax.Array:
    """One ``[P, ...]`` stack onto the mesh — the same placement
    `_arrays` uses (host-local stacks on multi-host, a sharded
    `device_put` under a single controller)."""
    if getattr(self.ds, 'host_parts', None) is not None:
      return self._put_stacked(a)
    return jax.device_put(a, NamedSharding(self.mesh, P(self.axis)))

  def maybe_refresh_stream(self):
    """Version fence for streaming ingestion (ISSUE 14): when the
    dataset carries a `streaming.StreamingGraph` handle
    (`DistDataset.attach_stream`), re-pin the newest published view
    at this dispatch seam — restack the per-partition CSR by the
    FROZEN partition book (`restack_stream_view`) and RCU-swap the
    device-arrays dict, so the dispatch that called `_arrays()` works
    against exactly one ``graph_version`` end to end.  The cached-set
    bitmask is invalidated at the same seam (``_gns_ver`` reset):
    derived structures refresh with the graph they derive from.
    Returns the pinned version (None without a stream)."""
    stream = getattr(self.ds, 'stream', None)
    if stream is None:
      return None
    view = stream.pin()
    if view.version == self._stream_ver:
      return self._stream_ver
    from .dist_data import DistGraph, restack_stream_view
    g = self.ds.graph
    indptr_s, indices_s, eids_s = restack_stream_view(
        view, self.ds.old2new, g.bounds,
        min_edge_width=int(g.indices.shape[1]))
    # a degraded write-off stays written off: the restack rebuilds
    # every partition from the stream, which would resurrect the dead
    # owner's topology against its zeroed feature shard
    for p in self._degraded_partitions:
      indptr_s[p] = 0
      indices_s[p] = -1
      eids_s[p] = -1
    self.ds.graph = DistGraph(indptr_s, indices_s, eids_s, g.bounds)
    # adopted lanes track the restacked topology too: the stream owns
    # the full graph — the parked durable payload was only the
    # bootstrap copy (feature/label fields stay: topology-only stream)
    adopted = getattr(self.ds, 'adopted_shards', None)
    if adopted:
      for r in list(adopted):
        adopted[r] = dict(adopted[r], indptr=np.asarray(indptr_s[r]),
                          indices=np.asarray(indices_s[r]),
                          eids=np.asarray(eids_s[r]))
    if self._device_arrays is not None:
      if self.book.version or self._degraded_partitions:
        # lane-stacked arrays (post-adoption) — the in-place [P, W]
        # patch would drop the lane axis the compiled book steps
        # expect; rebuild at the seam instead
        self._device_arrays = None
        self._steps.clear()
      else:
        arrs = dict(self._device_arrays)  # RCU: in-flight dicts frozen
        arrs['indptr'] = self._put_shard(indptr_s)
        arrs['indices'] = self._put_shard(indices_s)
        if self.with_edge:
          arrs['eids'] = self._put_shard(eids_s)
        self._device_arrays = arrs
    self._gns_ver = -1                   # version-fenced invalidation
    self._stream_ver = view.version
    self.ds.stream_version = view.version  # later samplers seed here
    return self._stream_ver

  # -- elastic partition failover (ISSUE 15) -------------------------------
  def _resolve_shard_store(self):
    """The durable `failover.ShardStore` under ``GLT_SHARD_DIR``
    (None = failover off, degraded semantics unchanged).  First
    resolution WRITES the dataset's shards (the load-time durable
    copy the tentpole requires) unless the store already covers this
    partition count — single-controller only; host-local layouts
    would write other hosts' shards from placeholders."""
    if self._shard_store is not None:
      return self._shard_store
    from .failover import ShardStore, shard_dir_from_env
    d = shard_dir_from_env()
    if d is None or getattr(self.ds, 'host_parts', None) is not None:
      return None
    store = ShardStore(d)
    written = getattr(self.ds, '_shards_written', False)
    meta = store.meta()
    g = self.ds.graph
    # a stale store (different graph under the same dir) must be
    # overwritten, not trusted: shape alone can collide (a regenerated
    # same-config dataset), so the content fingerprint is checked too;
    # edge-width growth (streaming reserve) is allowed since adoption
    # pads narrower durable rows to the live width
    from .failover import dataset_fingerprint
    stale = (meta is None
             or meta.get('num_parts') != self.num_parts
             or meta.get('num_nodes') != int(g.num_nodes)
             or meta.get('node_width') != int(g.indptr.shape[1])
             or int(meta.get('edge_width', 0)) > int(g.indices.shape[1])
             or meta.get('fingerprint') not in
             (None, dataset_fingerprint(self.ds)))
    if not written and stale:
      store.write_dataset_shards(self.ds)
    self.ds._shards_written = True
    self._shard_store = store
    return store

  def _partition_supervision(self) -> None:
    """Chaos-seam owner supervision, run at every dispatch seam
    BEFORE the step counter advances: a planned ``partition.owner``
    kill classifies that owner dead (the in-process stand-in for the
    PR 13 heartbeat-miss discriminator; ``delay`` models a slow-but-
    alive owner and only costs wall clock) and recovery runs the
    documented ladder — adopt (durable shard present) → degraded
    (``GLT_DEGRADED_OK=1``) → typed `PartitionLostError`.  After a
    successful adoption the SAME dispatch proceeds: the key stream
    never advanced, so the recovered batch is byte-identical to the
    fault-free one."""
    from ..testing import chaos
    from .failover import PartitionLostError
    try:
      chaos.partition_owner_check(step=self._step_cnt + 1)
    except PartitionLostError as e:
      self._on_partition_lost(e)

  def _on_partition_lost(self, err) -> None:
    """One owner classified dead: run the fallback ladder."""
    import time as _time
    from ..distributed.resilience import degraded_ok
    from ..telemetry.recorder import recorder
    from .failover import NoDurableShardError, adopt_shard
    from .partition_book import AdoptionRefusedError
    p = int(err.partition or 0)
    if p in self._degraded_partitions:
      return                      # already written off (degraded)
    view = self.book.view()
    if int(view.owners[p]) != p:
      return                      # already adopted — reader just fences
    t0 = _time.monotonic()
    try:
      info = adopt_shard(self.ds, self._resolve_shard_store(), p)
    except (NoDurableShardError, AdoptionRefusedError) as e:
      # the documented ladder: adoption unavailable (no durable
      # shard, no eligible survivor, foreign store, adopt timeout) →
      # degraded when the operator opted in, typed otherwise
      if not degraded_ok():
        raise type(err)(
            f'partition {p} lost and adoption is unavailable '
            f'({e}); set GLT_SHARD_DIR for elastic failover or '
            f'GLT_DEGRADED_OK=1 for reduced completion',
            partition=p) from e
      self._enter_degraded(p)
      return
    self._adopt_pending_t0 = (t0, p, info['survivor'])
    recorder.emit('peer.lost', peer=p, peer_kind='partition',
                  degraded=False, adopted=True,
                  survivor=info['survivor'])

  def _enter_degraded(self, p: int) -> None:
    """Documented ``GLT_DEGRADED_OK`` fallback: the orphaned shard's
    nodes VANISH from the epoch (its CSR row and feature shard are
    emptied) — reduced data, exact accounting, flagged typed in the
    flight recorder, never a silent wrong answer."""
    from ..telemetry.recorder import recorder
    self._degraded_partitions.add(p)
    g = self.ds.graph
    g.indptr[p] = 0
    g.indices[p] = -1
    g.edge_ids[p] = -1
    nf = self.ds.node_features
    if nf is not None:
      nf.shards[p] = 0
      if nf.cold_host is not None:
        b = np.asarray(g.bounds, np.int64)
        nf.cold_host[b[p]:b[p + 1]] = 0
    self._device_arrays = None       # rebuild from the emptied stacks
    self._steps.clear()
    self._gns_ver = -1
    self._degraded_seen = len(self._degraded_partitions)
    recorder.emit('peer.lost', peer=p, peer_kind='partition',
                  degraded=True, adopted=False)

  def _complete_recovery(self) -> None:
    """First successful dispatch after an adoption: close the
    recovery clock (classification → served batch) into the
    ``partition.recovery_secs`` gauge."""
    pending = getattr(self, '_adopt_pending_t0', None)
    if pending is None:
      return
    import time as _time
    from ..telemetry.live import live
    from ..telemetry.recorder import recorder
    t0, p, survivor = pending
    self._adopt_pending_t0 = None
    secs = _time.monotonic() - t0
    live.gauge('partition.recovery_secs').set(float(secs))
    recorder.emit('partition.adopt', partition=p, survivor=survivor,
                  version=self.book.version, phase='recovered',
                  secs=round(secs, 6))

  def maybe_refresh_book(self):
    """Version fence for partition ownership (ISSUE 15) — the same
    RCU discipline as `maybe_refresh_stream`: when the shared
    `PartitionBook` published a newer view (an adoption), rebuild the
    owner-side device arrays LANE-STACKED for the new routing, clear
    the step cache (the `BookSpec` is a trace-time constant — new
    routing = new exchange plans and capacity specs) and invalidate
    the GNS bitmask (derived structures refresh with the placement
    they derive from).  Readers hold one view per dispatch; a bump
    mid-dispatch swaps the attribute, never the arrays in flight."""
    ver = self.book.version
    ndeg = len(self._degraded_partitions)
    if ver == self._book_ver and ndeg == self._degraded_seen:
      return ver
    self._book_ver = ver
    self._book_view = self.book.view()
    self._degraded_seen = ndeg
    self._device_arrays = None
    self._steps.clear()
    self._gns_ver = -1
    return ver

  @property
  def book_spec(self):
    """Hashable static routing tables of the PINNED view (None =
    identity book: every step compiles the pre-book program)."""
    view = getattr(self, '_book_view', None)
    if view is None or view.version != self._book_ver:
      self._book_view = view = self.book.view()
    return view.spec()

  def _lane_source(self, r: int) -> dict:
    """Shard payload serving range ``r``: the durably re-loaded copy
    for adopted ranges (`failover.adopt_shard` parked it), the live
    stacks otherwise."""
    adopted = getattr(self.ds, 'adopted_shards', {})
    if r in adopted:
      return adopted[r]
    g = self.ds.graph
    out = {'indptr': g.indptr[r], 'indices': g.indices[r],
           'eids': None if g.edge_ids is None else g.edge_ids[r]}
    nf = self.ds.node_features
    if self.collect_features and nf is not None:
      out['fshard'] = nf.shards[r]
    if self.collect_labels and self.ds.node_labels is not None:
      out['lshard'] = np.asarray(self.ds.node_labels)[r]
    if self.collect_edge_features:
      out['efshard'] = self.ds.edge_features.shards[r]
    return out

  def _lane_stacked(self, key: str, template: np.ndarray, fill):
    """``[P, ...]`` owner-side stack → ``[P, S, ...]`` lane stack:
    device ``d``'s lane ``j`` holds the shard of range
    ``slot_ranges[d, j]`` (unassigned lanes hold ``fill``)."""
    view = self._book_view
    p, s = view.num_partitions, int(view.num_lanes)
    out = np.full((p, s) + tuple(template.shape[1:]), fill,
                  template.dtype)
    for d in range(p):
      for j in range(s):
        r = int(view.slot_ranges[d, j])
        if r < 0:
          continue
        src = self._lane_source(r).get(key)
        if src is None:
          continue
        src = np.asarray(src, template.dtype)
        sl = tuple(slice(0, n) for n in src.shape)
        out[(d, j) + sl] = src
    return out

  def _arrays(self):
    # book fence FIRST: a version bump (adoption) drops the cached
    # dict and the compiled steps, so this dispatch rebuilds against
    # exactly one pinned BookView (`maybe_refresh_book`)
    self.maybe_refresh_book()
    if self._device_arrays is None:
      shard = NamedSharding(self.mesh, P(self.axis))
      repl = NamedSharding(self.mesh, P())
      g = self.ds.graph
      put = jax.device_put
      fshards = (self.ds.node_features.shards if self.collect_features
                 else np.zeros((self.num_parts, 1, 1), np.float32))
      lshards = (self.ds.node_labels if self.collect_labels
                 else np.zeros((self.num_parts, 1), np.int32))
      if self.with_cache:
        cids = self.ds.node_features.cache_ids
        crows = self.ds.node_features.cache_rows
      else:
        from .dist_data import CACHE_PAD_ID
        cids = np.full((self.num_parts, 1), CACHE_PAD_ID, np.int32)
        crows = np.zeros((self.num_parts, 1, 1), np.float32)
      if self.collect_edge_features:
        efshards = self.ds.edge_features.shards
        ebounds = self.ds.edge_features.bounds
      else:
        efshards = np.zeros((self.num_parts, 1, 1), np.float32)
        ebounds = np.zeros(self.num_parts + 1, np.int64)
      hcounts = (self.ds.node_features.hot_counts
                 if self.collect_features
                 else np.zeros(self.num_parts, np.int32))
      # edge ids go to the devices only where a step reads them
      # (`with_edge`); every other step takes a placeholder
      eids = (g.edge_ids if self.with_edge
              else np.full((self.num_parts, 1), -1, np.int32))
      if getattr(self.ds, 'host_parts', None) is not None:
        # stacked arrays hold ONLY this host's partitions: assemble
        # the global sharded arrays shard-by-shard.  Placeholder
        # tables must match the LOCAL stack height.
        pl = len(self.ds.host_parts)
        if not self.collect_features:
          fshards = np.zeros((pl, 1, 1), np.float32)
        if not self.collect_labels:
          lshards = np.zeros((pl, 1), np.int32)
        if not self.with_cache:
          cids = cids[:pl]
          crows = crows[:pl]
        if not self.collect_edge_features:
          efshards = efshards[:pl]
        if not self.with_edge:
          eids = eids[:pl]
        putS = self._put_stacked
      else:
        def putS(a):
          # a stack that is already a device array sharded over the
          # mesh axis (`DistDataset.from_device_coo`) is handed on as
          # it is: it never visits the host
          if isinstance(a, jax.Array) and a.sharding.is_equivalent_to(
              shard, a.ndim):
            return a
          return put(a, shard)
      spec = self.book_spec
      if spec is None:
        # identity book: EXACTLY the pre-book arrays (the fault-free
        # byte-identity contract — failover compiled in costs nothing)
        self._device_arrays = dict(
            indptr=putS(g.indptr), indices=putS(g.indices),
            eids=putS(eids), bounds=put(g.bounds, repl),
            fshards=putS(fshards), lshards=putS(lshards),
            cids=putS(cids), crows=putS(crows),
            efshards=putS(efshards), ebounds=put(ebounds, repl),
            hcounts=put(np.asarray(hcounts, np.int32), repl))
      else:
        # adopted book: owner-side stacks grow a lane axis — device
        # ``d`` lane ``j`` serves range ``slot_ranges[d, j]``, adopted
        # lanes built from the DURABLE shard payload.  Requester-side
        # arrays (the offline remote-hot cache) keep their shape.
        self._device_arrays = dict(
            indptr=putS(self._lane_stacked('indptr', g.indptr, 0)),
            indices=putS(self._lane_stacked('indices', g.indices, -1)),
            eids=putS(self._lane_stacked('eids', g.edge_ids, -1)
                      if self.with_edge else eids),
            bounds=put(g.bounds, repl),
            fshards=putS(self._lane_stacked('fshard',
                                            np.asarray(fshards), 0)),
            lshards=putS(self._lane_stacked('lshard',
                                            np.asarray(lshards), 0)),
            cids=putS(cids), crows=putS(crows),
            efshards=putS(self._lane_stacked('efshard', efshards, 0)),
            ebounds=put(ebounds, repl),
            hcounts=put(np.asarray(hcounts, np.int32), repl))
    # streaming fence: re-pin the newest published graph version at
    # the dispatch seam (no-op for static datasets).  Callers hold
    # the RETURNED dict for the whole dispatch — a publish landing
    # mid-dispatch swaps the attribute, never the dict in flight.
    self.maybe_refresh_stream()
    return self._device_arrays

  def node_capacity(self, batch_size: int) -> int:
    cap = max_sampled_nodes(batch_size, self.fanouts)
    cap = min(cap, batch_size + self.ds.graph.num_nodes)
    return round_up(cap, 8)

  def step_for_batch(self, batch_size: int):
    """The compiled SPMD step for per-device batches of ``batch_size``
    (built once per size).  Signature: ``step(indptr, indices, eids,
    bounds, seeds, fshards, lshards, cids, crows, efshards, ebounds,
    hcounts, key)`` — also the scan body of `FusedDistEpoch`."""
    cfg = (int(batch_size),)
    if cfg not in self._steps:
      with self._layout_span(batch=int(batch_size)):
        self._steps[cfg] = _make_dist_step(
            self.mesh, self.num_parts, self.fanouts,
            self.node_capacity(int(batch_size)),
            self.with_edge, self.collect_features, self.collect_labels,
            self.axis, with_cache=self.with_cache,
            exchange_slack=self.exchange_slack,
            exchange_layout=self.exchange_layout,
            collect_edge_features=self.collect_edge_features,
            ef_shard_mode=self._ef_shard_mode, tiered=self.tiered,
            gns_boost=self.gns_boost, book_spec=self.book_spec,
            cache_local=self.cache_local, ewma_caps=self._ewma_caps())
      if self.gns:
        from ..telemetry.recorder import recorder
        from ..utils.profiling import metrics
        metrics.inc('gns.bias_steps_total')
        recorder.emit('gns.bias', batch=int(batch_size),
                      boost=float(self.gns_boost),
                      num_parts=self.num_parts)
    return self._steps[cfg]

  def _layout_span(self, **fields):
    """Build-time `exchange.layout` span around step construction: the
    resolved layout + slack land in the flight recorder once per
    compiled program (the runtime path stays span-free)."""
    from ..telemetry.spans import span
    return span('exchange.layout',
                layout=resolve_layout(self.exchange_layout,
                                      self.num_parts),
                num_parts=self.num_parts,
                slack=self.exchange_slack, **fields)

  def sample_from_nodes(self, seeds_stacked: np.ndarray, key=None):
    """``seeds_stacked``: ``[P, B]`` per-device seed batches (relabeled
    id space, -1 padded).  Returns stacked pytree pieces.  ``key``
    overrides the internal key stream (the fused-vs-per-batch parity
    tests drive both engines with identical keys)."""
    return self._finish_nodes(self._dispatch_nodes(seeds_stacked, key))

  def _dispatch_nodes(self, seeds_stacked: np.ndarray, key=None):
    """Dispatch the SPMD sample+collect step WITHOUT the cold-tier
    finish: the returned dict's arrays are in flight on device.  With
    `_finish_nodes` this is the loaders' double-buffered cold
    pipeline — batch k+1's sampling runs on device while batch k's
    cold overlay does its host work (`PrefetchingLoader._pipelined`).
    """
    from ..telemetry.spans import span
    b = seeds_stacked.shape[1]
    # supervision + fence BEFORE step resolution: an adoption here
    # clears the step cache and the step must compile for the new
    # routing, with the key stream still un-advanced (byte-identity)
    self._partition_supervision()
    arrs = self._arrays()
    step = self.step_for_batch(b)
    self._step_cnt += 1
    if key is None:
      key = jax.random.fold_in(self._base_key, self._step_cnt)
    # 'sample.exchange': the fused sample+exchange SPMD dispatch —
    # async, so its duration is dispatch latency; sync time (the
    # stage-attribution signal) lands in the feature.lookup child
    # whenever a cold overlay forces the host to wait
    with span('sample.exchange', step=self._step_cnt, batch=b):
      seeds_dev = jax.device_put(
          np.asarray(seeds_stacked, dtype=np.int32),
          NamedSharding(self.mesh, P(self.axis)))
      extra = (self._gns_arrays(),) if self.gns else ()
      outs = step(arrs['indptr'], arrs['indices'], arrs['eids'],
                  arrs['bounds'], seeds_dev, arrs['fshards'],
                  arrs['lshards'], arrs['cids'], arrs['crows'],
                  arrs['efshards'], arrs['ebounds'],
                  arrs['hcounts'], *extra, key)
      (nodes, count, row, col, edge, seed_local, x, y, ef, nsn,
       stats) = outs[:11]
      ew = outs[11] if self.gns else None
    # outside the span: the every-64th-call drain blocks on the
    # device, and that sync must not masquerade as dispatch latency
    self._complete_recovery()
    self._accumulate_stats(stats)
    out = dict(node=nodes, node_count=count[..., 0], row=row, col=col,
               edge=edge, seed_local=seed_local, x=x, y=y, ef=ef,
               num_sampled_nodes=nsn, batch=seeds_dev,
               overlay_step=self._step_cnt)
    if ew is not None:
      out['edge_weight'] = ew
    return out

  def _finish_nodes(self, out: dict) -> dict:
    """The host half of a dispatched step: the cold-tier overlay
    (no-op for untiered stores).  ``overlay_step`` pins the span to
    the step that DISPATCHED this batch — under the cold pipeline
    batch k+1's dispatch has already advanced ``_step_cnt`` by the
    time batch k's overlay runs."""
    out['x'] = self._maybe_overlay_cold(out['x'], out['node'],
                                        step=out.pop('overlay_step',
                                                     None))
    return out

  def _maybe_overlay_cold(self, x, nodes, step=None):
    """Overlay host-DRAM cold-tier rows onto the exchanged features
    (requester-side `overlay_cold_host` for single-controller
    ``cold_host`` tables; owner-served `overlay_cold_owner` for
    host-local ``cold_local`` stacks) and tick the cold telemetry."""
    if not self.tiered or x is None:
      return x
    from ..telemetry.spans import span
    with span('feature.lookup',
              step=self._step_cnt if step is None else step):
      return self._overlay_cold_traced(x, nodes)

  def _ensure_cold_cache(self):
    """Build the `MeshColdCache` on first use (the budget needs the
    feature dim and the partitions' cold-row counts, both known only
    for tiered stores)."""
    if self._cold_cache_built:
      return self._cold_cache
    self._cold_cache_built = True
    if not self.tiered:
      return None
    from ..data.cold_cache import MeshColdCache, resolve_cache_rows
    nf = self.ds.node_features
    counts = np.diff(self.ds.graph.bounds)
    cold_rows = int(np.maximum(counts - nf.hot_counts, 0).max(
        initial=0))
    cap = resolve_cache_rows(self._cold_cache_spec, cold_rows)
    if cap > 0:
      num_local = (len(self.ds.host_parts)
                   if self.ds.host_parts is not None
                   else self.num_parts)
      shard = NamedSharding(self.mesh, P(self.axis))
      putS = (self._put_stacked
              if self.ds.host_parts is not None
              else (lambda a: jax.device_put(a, shard)))
      self._cold_cache = MeshColdCache(
          cap, nf.shards.shape[-1], nf.shards.dtype, num_local,
          self.mesh, self.axis, putS, bounds=self.ds.graph.bounds)
    return self._cold_cache

  def _gns_arrays(self) -> jax.Array:
    """The replicated cached-set bitmask (`ops.gns.cached_set_bits`)
    for the GNS step's ``gns_bits`` input, rebuilt ONLY when the cold
    cache's residency actually changed (its version counter) — the
    refresh is one N/8-byte host build + replicated transfer, paid
    per admission wave, never per step.

    Staleness is harmless by construction: the importance weights
    correct ANY membership mask exactly, so a mask lagging one batch
    behind the ring costs a little bias-placement efficiency, zero
    estimator bias (`ops.gns` module docstring).
    """
    cache = self._ensure_cold_cache()
    ver = cache.version if cache is not None else 0
    if self._gns_bits is None or ver != self._gns_ver:
      from ..ops.gns import cached_set_bits, dedup_requester_bits
      n = self.ds.graph.num_nodes
      if self._gns_hot_bits is None:
        # the static half, packed once: refreshes pay O(bytes) copy
        # + O(residents), not the O(num_nodes) bool rebuild
        self._gns_hot_bits = cached_set_bits(
            n, self.ds.graph.bounds,
            self.ds.node_features.hot_counts, np.empty(0, np.int64))
      # PER-REQUESTER masks (ISSUE 15, the PR 10 known-limit fix):
      # row d = hot split ∪ device d's OWN cache residents, last row
      # = hot-only fallback for unattributable recv rows.  The union
      # mask over-boosted rows resident only on another device's ring
      # — a remote-only resident now gets no boost locally.  Devices
      # outside this host (host_parts) stay hot-only: unknown
      # residency must never over-boost (weights keep ANY mask
      # unbiased; a conservative mask costs placement, not bias).
      residents_by_dev = {}
      n_res = 0
      if cache is not None:
        hp = (self.ds.host_parts if self.ds.host_parts is not None
              else np.arange(self.num_parts))
        for j, sh in enumerate(cache.shards):
          res = sh.resident_ids()
          residents_by_dev[int(hp[j])] = res
          n_res += len(res)
      # r19 dedup: devices sharing a mask row (no residents of their
      # own, plus the fallback) point at ONE shared row through the
      # int32 indirection map — [T, N/8] + [R+1] instead of the
      # [R+1, N/8] replication, consumed identically by the XLA and
      # Pallas bias paths (equivalence pinned in
      # tests/test_pallas_sample.py)
      table, row_index = dedup_requester_bits(
          n, self.ds.graph.bounds,
          self.ds.node_features.hot_counts, residents_by_dev,
          base_bits=self._gns_hot_bits)
      repl = NamedSharding(self.mesh, P())
      self._gns_bits = (jax.device_put(table, repl),
                        jax.device_put(row_index, repl))
      self._gns_ver = ver
      mask_bytes = int(table.nbytes) + int(row_index.nbytes)
      # memory accounting (ISSUE 17): the replicated bitmask is the
      # GNS tier's whole bill; re-registered on each rebuild so the
      # gauge tracks the live arrays
      from ..telemetry.memaccount import register_tier
      register_tier(
          'gns', lambda b=self._gns_bits: sum(
              int(getattr(a, 'nbytes', 0)) for a in b))
      from ..utils.profiling import metrics
      metrics.inc('gns.sketch_updates_total')
      from ..telemetry.recorder import recorder
      if recorder.enabled:
        recorder.emit('gns.sketch_update', scope='dist',
                      residents=int(n_res), version=int(ver),
                      mask_bytes=mask_bytes)
    return self._gns_bits

  def _overlay_cold_traced(self, x, nodes):
    """The overlay body, under `_maybe_overlay_cold`'s span — the
    span exists only for tiered stores, where this is the per-batch
    host sync worth attributing.

    Order of service per batch: (1) hits in the dynamic HBM victim
    cache are overlaid by a purely local device gather (no host
    bytes); (2) residual misses ride the host cold tier
    (requester-side `overlay_cold_host` or owner-served
    `overlay_cold_owner`); (3) the now-corrected miss rows are
    admitted into the cache (device→device `at[].set`), so the next
    batch's repeats hit — the cross-batch cold-id dedup.
    """
    from ..data.cold_cache import emit_cache_events
    from ..testing import chaos
    # chaos seam: the host cold tier can die mid-epoch; a planned
    # 'fail' surfaces here, before any host gather
    chaos.cold_service_check('dist')
    nf = self.ds.node_features
    g = self.ds.graph
    cache = self._ensure_cold_cache()
    hits = admits = evicts = 0
    if nf.cold_host is not None:
      # single-controller table: every shard addressable
      nodes_l = np.asarray(jax.device_get(nodes)).astype(np.int64)
      valid = nodes_l >= 0
      # placement reads through the book's frozen-range rule (ISSUE
      # 15): the hot/cold split keys on the RANGE — adoption moves the
      # serving device, never a row's tier
      _rng, local, cold = hot_split_host(g.bounds, nf.hot_counts,
                                         nodes_l, valid)
      lookups, cold_n = int(valid.sum()), int(cold.sum())
      miss = cold
      if cache is not None:
        hit, slot = cache.lookup(nodes_l, cold)
        hits = int(hit.sum())
        x = cache.serve(x, hit, slot)
        miss = cold & ~hit
      x, _, served = overlay_cold_host(
          x, nodes, g.bounds, nf.hot_counts, nf.cold_host, self.mesh,
          self.axis, self.num_parts, nodes_host=nodes_l,
          cold_mask=miss)
      if cache is not None and miss.any():
        plans = cache.plan_admissions(nodes_l, miss)
        admits, evicts = cache.commit_admissions(
            x, plans, cache.admit_width(plans))
    else:
      hp = (self.ds.host_parts if self.ds.host_parts is not None
            else np.arange(self.num_parts))
      plan = plan_cold_requests(nodes, g.bounds, nf.hot_counts, hp,
                                cache_ids=nf.cache_ids)
      hp_, nodes_l, valid, owner, cold, counts, lookups = plan
      cold_n = int(cold.sum())
      if cache is not None:
        hit, slot = cache.lookup(nodes_l, cold)
        hits = int(hit.sum())
        # serve runs UNCONDITIONALLY under multiple controllers: every
        # process must dispatch the same programs on the global arrays
        x = cache.serve(x, hit, slot)
        miss = cold & ~hit
        counts = np.zeros_like(counts)
        sel_j, sel_pos = np.nonzero(miss)
        if len(sel_j):
          np.add.at(counts, (sel_j, owner[sel_j, sel_pos]), 1)
        plan = (hp_, nodes_l, valid, owner, miss, counts, lookups)
        adm_plans = cache.plan_admissions(nodes_l, miss)
        # ONE handshake agrees on both per-batch program widths
        caps = _global_max_vec([int(counts.max(initial=0)),
                                cache.admit_width(adm_plans)])
        x, _, served = overlay_cold_owner(
            x, nodes, g.bounds, nf.hot_counts, nf.cold_local,
            self.mesh, self.axis, self.num_parts, hp, plan_=plan,
            agreed_capacity=caps[0])
        admits, evicts = cache.commit_admissions(x, adm_plans,
                                                 caps[1])
      else:
        x, _, served = overlay_cold_owner(
            x, nodes, g.bounds, nf.hot_counts, nf.cold_local,
            self.mesh, self.axis, self.num_parts, hp, plan_=plan)
    with self._stats_lock:
      self._feat_lookups += lookups
      self._cold_lookups += cold_n
      self._cold_misses += served
      self._cache_hits += hits
      self._cache_admits += admits
      self._cache_evicts += evicts
    if cache is not None:
      # cache-off runs (GLT_COLD_CACHE_ROWS=0, the static split)
      # must not record phantom cache.miss traffic — cold
      # service without a cache is already visible as cold_misses
      emit_cache_events('dist', hits, served, admits, evicts)
    return x

  # -- DataPlaneState (utils.checkpoint) ----------------------------------
  def data_plane_state(self) -> dict:
    """Key-stream cursor + cold-cache rings.  ``step_cnt`` positions
    the per-batch sampling keys (``fold_in(base_key, step_cnt)``) —
    restoring it is what makes resumed batches byte-identical."""
    state = {'step_cnt': self._step_cnt}
    cache = self._ensure_cold_cache()
    if cache is not None:
      state['cache'] = cache.state_dict()
    return state

  def load_data_plane_state(self, state: dict) -> None:
    self._step_cnt = int(np.asarray(state['step_cnt']))
    if 'cache' in state:
      cache = self._ensure_cold_cache()
      if cache is not None:
        cache.load_state_dict(state['cache'])


@jax.jit
def _overlay_cold_rows(x, mask, rank, compact):
  """``x[p, i] = compact[rank[p, i]] where mask`` — the device half of
  the cold-tier overlay (`overlay_cold_host`)."""
  return jnp.where(mask[..., None], compact[rank], x)


def overlay_cold_host(x, nodes, bounds, hot_counts, cold_host, mesh,
                      axis: str, num_parts: int, nodes_host=None,
                      cold_mask=None):
  """Serve cold-tier rows (host DRAM) for node-table entries the HBM
  exchange zeroed — shared by the homo and hetero mesh engines.

  Tiered stores serve only HBM-hot rows through the all_to_all
  (owners zero rows past their hot count); the cold remainder is
  host-gathered into a COMPACT replicated buffer and expanded on
  device by a rank map — the same compact-transfer trade as the
  single-chip mixed path (`data/feature.py.__getitem__`), stacked.
  The explicit, per-batch analog of the reference's UVA reads
  (`csrc/cuda/unified_tensor.cu:202+`).  Costs one device sync for
  the node table — the honest price of exceeding HBM.

  Returns ``(x', lookups, misses)`` for the caller's telemetry.
  ``nodes_host`` skips the device_get when the caller already fetched
  the table (the hetero engine batches ONE sync over all node types).
  ``cold_mask`` overrides the cold-row predicate with a precomputed
  mask (the cache-aware caller passes ``cold & ~cache_hit`` so served
  rows skip the host gather).
  """
  from ..utils.padding import next_power_of_two
  nodes_h = np.asarray(nodes_host if nodes_host is not None
                       else jax.device_get(nodes)).astype(np.int64)
  valid = nodes_h >= 0
  if cold_mask is not None:
    cold = cold_mask
  else:
    _rng, _local, cold = hot_split_host(bounds, hot_counts, nodes_h,
                                        valid)
  lookups = int(valid.sum())
  n_cold = int(cold.sum())
  if n_cold == 0:
    return x, lookups, 0
  cold_pad = next_power_of_two(n_cold)
  compact = np.zeros((cold_pad, cold_host.shape[1]), cold_host.dtype)
  compact[:n_cold] = cold_host[nodes_h[cold]]
  flat = cold.reshape(-1)
  rank = np.where(flat, np.cumsum(flat) - 1,
                  0).astype(np.int32).reshape(cold.shape)
  shard = NamedSharding(mesh, P(axis))
  repl = NamedSharding(mesh, P())
  out = _overlay_cold_rows(x, jax.device_put(cold, shard),
                           jax.device_put(rank, shard),
                           jax.device_put(compact, repl))
  return out, lookups, n_cold


def _local_shards_stacked(arr, host_parts) -> np.ndarray:
  """This process's shards of a dim-0-sharded global array, stacked
  ``[len(host_parts), ...]`` in ``host_parts`` order — the read half
  of `put_stacked_host_local` (multi-host safe: only addressable
  shards are touched)."""
  by_part = {}
  for s in arr.addressable_shards:
    by_part[int(s.index[0].start or 0)] = np.asarray(s.data)[0]
  return np.stack([by_part[int(p)] for p in host_parts])


def _global_max_int(v: int) -> int:
  """Agree on ``max(v)`` across processes — the request-capacity
  handshake of the owner-served cold overlay (every process must
  compile/run identical [P, P, C] programs or the collectives
  deadlock).  Single-process: the local value."""
  return _global_max_vec([v])[0]


def _global_max_vec(vs) -> list:
  """Vector form of `_global_max_int`: ONE allgather agrees on the
  element-wise max of a whole list — hetero batches with many tiered
  node types pay one DCN round trip instead of one per type
  (the per-(type, batch) handshake can dominate batch time
  at large P)."""
  if jax.process_count() == 1:
    return [int(v) for v in vs]
  from jax.experimental import multihost_utils
  return [int(x) for x in multihost_utils.process_allgather(
      np.asarray(vs, np.int64)).max(axis=0)]


@functools.lru_cache(maxsize=None)
def _cold_overlay_programs(mesh: Mesh, axis: str, num_parts: int):
  """The two tiny collectives of the owner-served cold overlay
  (`overlay_cold_owner`), cached per mesh: request-id all_to_all and
  reply all_to_all + scatter."""
  from .shard_map_compat import shard_map
  s3 = P(axis, None, None)
  s2 = P(axis, None)
  s4 = P(axis, None, None, None)

  def _exch(req):                                  # [1, P, C]
    return jax.lax.all_to_all(req[0], axis, 0, 0, tiled=True)[None]

  exchange_requests = jax.jit(shard_map(
      _exch, mesh=mesh, in_specs=(s3,), out_specs=s3))

  def _scatter(x, replies, mask, owner_idx, slot_idx):
    rep = jax.lax.all_to_all(replies[0], axis, 0, 0,
                             tiled=True)           # [P, C, D] by owner
    rows = rep[owner_idx[0], slot_idx[0]]          # [cap, D]
    return jnp.where(mask[0][:, None], rows, x[0])[None]

  scatter_replies = jax.jit(shard_map(
      _scatter, mesh=mesh, in_specs=(s3, s4, s2, s2, s2),
      out_specs=s3))
  return exchange_requests, scatter_replies


def plan_cold_requests(nodes, bounds, hot_counts, host_parts,
                       cache_ids=None, nodes_host=None):
  """Requester-side analysis half of `overlay_cold_owner`: which
  sampled rows are cold, who owns them, and the per-owner counts.
  Callers overlaying SEVERAL tiered stores in one batch (the hetero
  engine) run this per store, agree on all capacities in ONE
  `_global_max_vec` handshake, then execute each overlay with
  ``agreed_capacity`` — one DCN round trip per batch instead of one
  per store."""
  hp = [int(p) for p in host_parts]
  num_parts = len(hot_counts)
  nodes_l = (nodes_host if nodes_host is not None
             else _local_shards_stacked(nodes, hp)).astype(np.int64)
  valid = nodes_l >= 0
  owner, local, cold = hot_split_host(bounds, hot_counts, nodes_l,
                                      valid)
  if cache_ids is not None:
    # cache-served rows already carry correct values — skip them
    for j in range(nodes_l.shape[0]):
      cid = np.asarray(cache_ids[j])
      pos = np.clip(np.searchsorted(cid, nodes_l[j]), 0, len(cid) - 1)
      cold[j] &= ~((cid[pos] == nodes_l[j]) & valid[j])
  counts = np.zeros((nodes_l.shape[0], num_parts), np.int64)
  if cold.any():
    sel_j, sel_pos = np.nonzero(cold)
    np.add.at(counts, (sel_j, owner[sel_j, sel_pos]), 1)
  return (hp, nodes_l, valid, owner, cold, counts, int(valid.sum()))


def overlay_cold_owner(x, nodes, bounds, hot_counts, cold_local, mesh,
                       axis: str, num_parts: int, host_parts,
                       cache_ids=None, nodes_host=None, plan_=None,
                       agreed_capacity=None):
  """OWNER-served cold-tier overlay — the multi-host form
  (`DistFeature.cold_local`): each host holds only its own
  partitions' cold rows, so a requester cannot gather them locally
  (the `overlay_cold_host` path needs the full ``[N, D]`` table).
  Instead the cold rows ride a second per-batch gather, the
  collective analog of the reference's RPC feature fan-out against
  per-host UVA tables (`distributed/dist_feature.py:134-269` +
  `data/feature.py:174-206`):

    1. each process reads ITS devices' sampled-node shards and marks
       rows the HBM exchange zeroed (past the owner's hot count and
       not served by the local remote-hot cache);
    2. processes agree on a power-of-two request capacity ``C``
       (`_global_max_int` — all processes must run identical
       programs);
    3. one all_to_all ships the ``[P, P, C]`` request ids to owners;
    4. each owner host gathers the requested rows from its DRAM stack
       (this is THE host round trip — the honest price of exceeding
       HBM, same as the requester-side path);
    5. one all_to_all ships replies back; a scatter overlays them.

  Works identically under a single controller (every partition is
  addressable) — the virtual-mesh tests drive the same code path the
  multi-host deployment runs.  Returns ``(x', lookups, misses)``.
  """
  plan = (plan_ if plan_ is not None
          else plan_cold_requests(nodes, bounds, hot_counts, host_parts,
                                  cache_ids=cache_ids,
                                  nodes_host=nodes_host))
  hp, nodes_l, valid, owner, cold, counts, lookups = plan
  pl, cap = nodes_l.shape
  from ..utils.padding import next_power_of_two
  c_req = (agreed_capacity if agreed_capacity is not None
           else _global_max_int(int(counts.max(initial=0))))
  if c_req == 0:
    return x, lookups, 0
  n_cold = int(cold.sum())
  c_pad = next_power_of_two(c_req)
  # vectorized (requester, owner) bucketing (the nested
  # pl x P python loops were per-batch host work): stable-sort the
  # cold rows by their (j, owner) group; slot-in-group = rank minus
  # the group's first rank
  req = np.full((pl, num_parts, c_pad), -1, np.int32)
  owner_idx = np.zeros((pl, cap), np.int32)
  slot_idx = np.zeros((pl, cap), np.int32)
  sel_j, sel_pos = np.nonzero(cold)
  if len(sel_j):
    own = owner[sel_j, sel_pos]
    ids = nodes_l[sel_j, sel_pos]
    gkey = sel_j * num_parts + own
    order = np.argsort(gkey, kind='stable')
    ks = gkey[order]
    starts = np.r_[0, np.nonzero(np.diff(ks))[0] + 1]
    sizes = np.diff(np.r_[starts, len(ks)])
    slots = (np.arange(len(ks))
             - np.repeat(starts, sizes)).astype(np.int32)
    req[sel_j[order], own[order], slots] = ids[order]
    owner_idx[sel_j, sel_pos] = own
    slot_idx[sel_j[order], sel_pos[order]] = slots

  exchange_requests, scatter_replies = _cold_overlay_programs(
      mesh, axis, num_parts)
  putS = functools.partial(put_stacked_host_local, mesh, axis,
                           num_parts, hp)
  req_at_owner = exchange_requests(putS(req))
  ro = _local_shards_stacked(req_at_owner, hp)     # [pl, P, C]
  d = cold_local.shape[-1]
  replies = np.zeros((pl, num_parts, c_pad, d), cold_local.dtype)
  for j, p in enumerate(hp):
    ids = ro[j].astype(np.int64)
    loc = np.where(ids >= 0, ids - bounds[p], 0)
    loc = np.clip(loc, 0, cold_local.shape[1] - 1)
    replies[j] = np.where((ids >= 0)[..., None], cold_local[j][loc], 0)
  x2 = scatter_replies(x, putS(replies), putS(cold),
                       putS(owner_idx), putS(slot_idx))
  return x2, lookups, n_cold


def _make_dist_walk_step(mesh: Mesh, num_parts: int, walk_length: int,
                         axis: str = 'data',
                         exchange_slack: Optional[float] = None,
                         exchange_layout: Optional[str] = None,
                         book_spec=None):
  """Jitted SPMD uniform random walk over the sharded CSR: each step
  is one `_dist_one_hop` with fanout 1 (a uniform neighbor draw
  through the owner exchange) — the distributed arm of
  `ops.random_walk` (beyond reference parity; the reference only
  reserves ``SamplingType.RANDOM_WALK``)."""
  from .shard_map_compat import shard_map

  def per_device(indptr_s, indices_s, bounds, starts_s, key):
    cur = starts_s[0].astype(jnp.int32)
    path = [cur]
    stats = jnp.zeros((3,), jnp.int32)
    attr_owner = range_owner_fn(bounds)
    attr_fr = jnp.zeros((num_parts,), jnp.int32)
    for h in range(walk_length):
      with layer_scope('telemetry', 'attribution'):
        attr_fr = attr_fr + dest_histogram(cur, attr_owner, num_parts)
      nbrs, mask, _, _w, hstats = _dist_one_hop(
          indptr_s[0], indices_s[0], None, bounds, cur, 1,
          jax.random.fold_in(key, h), axis, num_parts, False,
          exchange_capacity=_slack_cap(cur.shape[0], num_parts,
                                       exchange_slack,
                                       exchange_layout),
          book_spec=book_spec)
      with layer_scope('telemetry', 'exchange'):
        stats = stats + jnp.stack(hstats)
      cur = jnp.where(mask[:, 0], nbrs[:, 0], INVALID_ID).astype(
          jnp.int32)
      path.append(cur)
    walks = jnp.stack(path, axis=1)             # [B, L+1]
    with layer_scope('telemetry', 'exchange'):
      full = jnp.concatenate(
          [stats, jnp.zeros((4,), jnp.int32), attr_fr,
           jnp.zeros((num_parts + 1,), jnp.int32)])
    return walks[None], full[None]

  specs_in = (P(axis), P(axis), P(), P(axis), P())
  sharded = shard_map(per_device, mesh=mesh, in_specs=specs_in,
                      out_specs=(P(axis), P(axis)))
  return jax.jit(sharded)


#: `hop_chunk='auto'` engages chunking once one full-window reply
#: buffer (``node_cap * max_degree`` int32 per destination device)
#: would exceed this many elements — 16M = 64 MB, comfortably inside
#: HBM while keeping the all_to_all rendezvous bounded at any P.
SUBGRAPH_WINDOW_BUDGET = 1 << 24


def resolve_hop_chunk(hop_chunk, node_cap: int,
                      max_degree: int) -> Optional[int]:
  """Resolve the subgraph samplers' ``'auto'``: chunk only when the
  full-window exchange would exceed `SUBGRAPH_WINDOW_BUDGET` elements
  (results are EXACT either way; chunking costs serialized exchanges,
  so small configs keep the single wide one)."""
  if isinstance(hop_chunk, str):
    if hop_chunk != 'auto':
      raise ValueError(f'unknown hop_chunk {hop_chunk!r}')
    if node_cap * max_degree <= SUBGRAPH_WINDOW_BUDGET:
      return None
    # round DOWN so chunk * max_degree never exceeds the budget (the
    # MIN_EXCHANGE_CAP floor may for degenerate max_degree — a floor,
    # not a violation of intent)
    return max(SUBGRAPH_WINDOW_BUDGET // max_degree // 8 * 8,
               MIN_EXCHANGE_CAP)
  return hop_chunk


class DistSubGraphSampler(DistNeighborSampler):
  """Device-mesh induced-subgraph sampler: multihop closure + one
  full-window distributed hop + local membership/relabel (SEAL at pod
  scale; reference `distributed/dist_neighbor_sampler.py:456-516`).

  Args:
    max_degree: static per-node neighbor window for the induced scan;
      None = the sharded graph's true max degree (exact results).
    hop_chunk: closure nodes per full-window exchange — bounds the
      all_to_all to ``[P, chunk, max_degree]`` (SEAL-at-scale
      envelope; see `_make_dist_subgraph_step`).  ``'auto'`` (default)
      chunks only past `SUBGRAPH_WINDOW_BUDGET`; None = always one
      node_cap-wide exchange.
  """

  def __init__(self, dataset: DistDataset, num_neighbors,
               max_degree: Optional[int] = None,
               hop_chunk='auto', **kwargs):
    super().__init__(dataset, num_neighbors, **kwargs)
    # induced subgraphs are EXACT by contract (a biased closure
    # corrupts SEAL/DRNL labels the way a capacity drop would), so a
    # global GLT_GNS=1 must not flip this sampler's flag: the step
    # never biases, and the flag must not report otherwise
    self.gns = False
    self.gns_boost = None
    if max_degree is None:
      g = dataset.graph
      max_degree = int(np.diff(g.indptr, axis=1).max())
    self.max_degree = max(int(max_degree), 1)
    self.hop_chunk = hop_chunk

  def sample_subgraph(self, seeds_stacked: np.ndarray):
    """``seeds_stacked``: ``[P, B]`` per-device seed batches (relabeled
    space, -1 padded).  Returns the induced-subgraph pieces; edges in
    natural (source, dest) direction; ``seed_local`` doubles as the
    reference's ``mapping`` metadata."""
    b = seeds_stacked.shape[1]
    node_cap = self.node_capacity(b)
    self._partition_supervision()
    arrs = self._arrays()
    cfg = ('subgraph', b)
    if cfg not in self._steps:
      with self._layout_span(batch=b, mode='subgraph'):
        self._steps[cfg] = _make_dist_subgraph_step(
            self.mesh, self.num_parts, self.fanouts, node_cap,
            self.max_degree, self.with_edge, self.collect_features,
            self.collect_labels, self.axis, with_cache=self.with_cache,
            exchange_slack=self.exchange_slack,
            exchange_layout=self.exchange_layout, tiered=self.tiered,
            hop_chunk=resolve_hop_chunk(self.hop_chunk, node_cap,
                                        self.max_degree),
            book_spec=self.book_spec)
    from ..telemetry.spans import span
    self._step_cnt += 1
    key = jax.random.fold_in(self._base_key, self._step_cnt)
    with span('sample.exchange', step=self._step_cnt,
              mode='subgraph'):
      seeds_dev = jax.device_put(
          np.asarray(seeds_stacked, dtype=np.int32),
          NamedSharding(self.mesh, P(self.axis)))
      (nodes, count, row, col, edge, seed_local, x, y, nsn, stats) = \
          self._steps[cfg](arrs['indptr'], arrs['indices'],
                           arrs['eids'], arrs['bounds'], seeds_dev,
                           arrs['fshards'], arrs['lshards'],
                           arrs['cids'], arrs['crows'],
                           arrs['hcounts'], key)
    self._complete_recovery()
    self._accumulate_stats(stats)
    x = self._maybe_overlay_cold(x, nodes)
    return dict(node=nodes, node_count=count[..., 0], row=row, col=col,
                edge=edge, seed_local=seed_local, x=x, y=y,
                num_sampled_nodes=nsn, batch=seeds_dev)


class DistRandomWalker(DistNeighborSampler):
  """Device-mesh uniform random walks (DeepWalk-corpus generation over
  a graph larger than one chip) — see `_make_dist_walk_step`.
  Subclasses `DistNeighborSampler` for the shared scaffolding (mesh,
  key stream, device-array cache, step cache, telemetry).

  Args:
    dataset: `DistDataset`.
    walk_length: steps per walk (output is ``[P, B, L+1]``).
    exchange_slack: default EXACT — a dropped frontier id does not
      under-sample one hop here, it truncates the walk's whole
      remainder, and walk frontiers are degree-biased (hotness
      partitioners concentrate them on few owners), so the loaders'
      capped default would silently empty the corpus.  Pass a float to
      opt in where partition balance is known.
  """

  def __init__(self, dataset: DistDataset, walk_length: int,
               exchange_slack=None, **kwargs):
    if exchange_slack == 'adaptive':
      raise ValueError(
          "exchange_slack='adaptive' is not supported for random "
          'walks: a dropped frontier id truncates the whole walk '
          'remainder, so the walker stays exact (pass a float to opt '
          'into a cap where partition balance is known)')
    super().__init__(
        dataset, [], collect_features=False, with_edge=False,
        # 'auto' resolves to exact here (see class docstring)
        exchange_slack=resolve_exchange_slack(exchange_slack, False),
        **kwargs)
    self.walk_length = int(walk_length)

  def walk(self, starts_stacked: np.ndarray) -> jax.Array:
    """``starts_stacked``: ``[P, B]`` per-device start nodes (relabeled
    space, -1 padded).  Returns ``[P, B, walk_length + 1]``."""
    b = starts_stacked.shape[1]
    self._partition_supervision()
    arrs = self._arrays()
    cfg = ('walk', b)
    if cfg not in self._steps:
      with self._layout_span(batch=b, mode='walk'):
        self._steps[cfg] = _make_dist_walk_step(
            self.mesh, self.num_parts, self.walk_length, self.axis,
            self.exchange_slack, self.exchange_layout,
            book_spec=self.book_spec)
    self._step_cnt += 1
    key = jax.random.fold_in(self._base_key, self._step_cnt)
    starts = jax.device_put(
        np.asarray(starts_stacked, np.int32),
        NamedSharding(self.mesh, P(self.axis)))
    walks, stats = self._steps[cfg](arrs['indptr'], arrs['indices'],
                                    arrs['bounds'], starts, key)
    self._complete_recovery()
    self._accumulate_stats(stats)
    return walks


class DistSubGraphLoader(PrefetchingLoader):
  """Distributed induced-subgraph loader over the device mesh — the
  mesh-engine arm of reference ``DistSubGraphLoader``
  (`distributed/dist_subgraph_loader.py:28-89`); the host-runtime arm
  lives in `graphlearn_tpu.distributed`.  Yields stacked `Batch`
  pytrees with ``metadata['mapping']`` locating each seed in the node
  table (the SEAL contract, `loader/subgraph_loader.py:88-97`).
  """

  def __init__(self, dataset: DistDataset, num_neighbors, input_nodes,
               batch_size: int = 1, shuffle: bool = False,
               drop_last: bool = False, mesh: Optional[Mesh] = None,
               with_edge: bool = False, collect_features: bool = True,
               max_degree: Optional[int] = None, seed: int = 0,
               input_space: str = 'old', exchange_slack='auto',
               exchange_layout: Optional[str] = None,
               hop_chunk='auto', prefetch: int = 0):
    from ..loader.node_loader import SeedBatcher
    self.prefetch = int(prefetch)
    # 'auto' resolves to EXACT here, shuffled or not: a dropped
    # closure node under a capacity cap loses its whole neighbor
    # window, making the "induced subgraph" silently wrong (for
    # neighbor sampling a drop is a statistical under-sample; for
    # SEAL/DRNL it corrupts labels).  An explicit float still opts in.
    # `hop_chunk` is the scale lever that keeps exact affordable: it
    # bounds every full-window exchange to [P, chunk, max_degree].
    if exchange_slack == 'adaptive':
      raise ValueError(
          "exchange_slack='adaptive' is not supported for induced "
          'subgraphs: any capacity drop corrupts SEAL/DRNL labels, so '
          'the loader stays exact (hop_chunk bounds the exchange '
          'instead)')
    if exchange_slack == 'auto':
      exchange_slack = None
    self.sampler = DistSubGraphSampler(
        dataset, num_neighbors, max_degree=max_degree, mesh=mesh,
        with_edge=with_edge, collect_features=collect_features,
        seed=seed,
        exchange_slack=resolve_exchange_slack(exchange_slack, shuffle),
        exchange_layout=exchange_layout,
        hop_chunk=hop_chunk)
    self.ds = dataset
    seeds = np.asarray(input_nodes).reshape(-1)
    if input_space == 'old' and dataset.old2new is not None:
      seeds = dataset.old2new[seeds]
    self.num_parts = dataset.num_partitions
    self.batch_size = int(batch_size)
    self._batcher = SeedBatcher(seeds, batch_size * self.num_parts,
                                shuffle, drop_last, seed)

  def __len__(self):
    return len(self._batcher)

  def _produce(self, seed_iter):
    from ..loader.transform import Batch
    from ..telemetry.spans import span
    flat = next(seed_iter)
    with span('batch', scope='DistSubGraphLoader'):
      seeds = flat.reshape(self.num_parts, self.batch_size)
      out = self.sampler.sample_subgraph(seeds)
      with span('stitch'):
        edge_index = jnp.stack([out['row'], out['col']], axis=1)
        return Batch(
            x=out['x'], y=out['y'], edge_index=edge_index,
            node=out['node'], node_mask=out['node'] >= 0,
            edge_mask=out['row'] >= 0, edge=out['edge'],
            batch=out['batch'], batch_size=self.batch_size,
            num_sampled_nodes=out['num_sampled_nodes'],
            metadata={'seed_local': out['seed_local'],
                      'mapping': out['seed_local']})


class _ResumableEpochMixin:
  """Mid-epoch snapshot/resume for the mesh loaders (the
  `utils.checkpoint` DataPlaneState protocol, loader-shaped).

  ``state_dict()`` captures the epoch cursor: the batcher's RNG (the
  interrupted epoch's permutation is RE-DRAWN on resume, not stored),
  the number of batches already handed out, the sampler key-stream
  position those batches consumed, and the cold-cache rings.
  ``load_state_dict()`` + ``resume_epoch()`` then continue the epoch
  in a fresh loader with byte-identical remaining batches: same
  permutation, same per-batch sampling keys (``step_cnt`` excludes
  any lost dispatch-ahead overshoot — the in-flight batch k+1 a kill
  destroys is re-dispatched with the same key).
  """

  def _start_epoch(self, seed_iter):
    self._epoch_start_steps = self.sampler._step_cnt
    self._consumed = 0
    return super()._start_epoch(seed_iter)

  def state_dict(self) -> dict:
    if getattr(self, '_active_prefetch', None) is not None:
      # the worker thread runs _produce ahead of the consumer, so
      # `_consumed` counts batches the trainer may never have seen —
      # a snapshot here would skip them on resume (silent batch loss)
      raise ValueError(
          'mid-epoch snapshots need a synchronous epoch (prefetch=0): '
          'a prefetch worker produces ahead of the trainer, so the '
          'durable cursor would overcount delivered batches')
    c = int(getattr(self, '_consumed', 0))
    start = getattr(self, '_epoch_start_steps',
                    self.sampler._step_cnt)
    sampler_state = self.sampler.data_plane_state()
    # the CONSUMED-batch key position, not the live counter: under the
    # dispatch-ahead overlay batch k+1's dispatch has already advanced
    # the counter while batch k is the newest durable batch
    sampler_state['step_cnt'] = start + c
    out = {'batcher': self._batcher.state_dict(), 'consumed': c,
           'epoch_count': int(getattr(self, '_epoch_count', 0)),
           'sampler': sampler_state}
    ctl = getattr(self, '_adaptive', None)
    if ctl is not None:
      out['slack'] = ctl.state_dict()
    return out

  def load_state_dict(self, state: dict) -> None:
    self._batcher.load_state_dict(state['batcher'], mid_epoch=True)
    self.sampler.load_data_plane_state(state['sampler'])
    # the ladder's rung/pin survive the restart (ISSUE 6: AdaptiveSlack
    # is one of the stateful components a restart would silently reset)
    ctl = getattr(self, '_adaptive', None)
    if ctl is not None and 'slack' in state:
      ctl.load_state_dict(state['slack'])
    self._epoch_count = int(np.asarray(state.get('epoch_count', 0)))
    self._resume_consumed = int(np.asarray(state['consumed']))

  def resume_epoch(self):
    """Iterator over the interrupted epoch's REMAINING batches (call
    after `load_state_dict`); `iter(loader)` afterwards starts the
    next epoch exactly where an uninterrupted run would."""
    consumed = getattr(self, '_resume_consumed', None)
    if consumed is None:
      raise ValueError('resume_epoch() needs load_state_dict() first')
    self._resume_consumed = None
    it = iter(self._batcher)       # re-draws the interrupted epoch's perm
    for _ in range(consumed):
      next(it)                     # skip what the trainer already has
    ep = PrefetchingLoader._start_epoch(self, it)
    self._consumed = consumed
    self._epoch_start_steps = self.sampler._step_cnt - consumed
    return ep


class DistNeighborLoader(_ResumableEpochMixin, PrefetchingLoader):
  """Distributed loader facade (reference ``DistNeighborLoader``,
  `distributed/dist_neighbor_loader.py:27-94`).

  Splits the (relabeled) seed set across the mesh, yields stacked
  `Batch` pytrees ready for the DP train step: leading axis = device.
  ``prefetch=N`` runs the host side of the NEXT batch (seed prep, the
  collective dispatch, the tiered store's cold overlay) on a worker
  thread while the current step trains — the overlap tiered stores
  need, since their overlay syncs on the node table per batch.
  """

  def __init__(self, dataset: DistDataset, num_neighbors, input_nodes,
               batch_size: int = 1, shuffle: bool = False,
               drop_last: bool = False, mesh: Optional[Mesh] = None,
               with_edge: bool = False, collect_features: bool = True,
               seed: int = 0, input_space: str = 'old',
               exchange_slack='auto',
               exchange_layout: Optional[str] = None,
               prefetch: int = 0, cold_cache_rows='auto', gns=None):
    from ..loader.node_loader import SeedBatcher
    self.prefetch = int(prefetch)
    slack = resolve_exchange_slack(exchange_slack, shuffle)
    self.sampler = DistNeighborSampler(
        dataset, num_neighbors, mesh=mesh, with_edge=with_edge,
        collect_features=collect_features, seed=seed,
        exchange_slack=(DEFAULT_EXCHANGE_SLACK if slack == 'adaptive'
                        else slack),
        exchange_layout=exchange_layout,
        cold_cache_rows=cold_cache_rows, gns=gns)
    self._adaptive = (AdaptiveSlack(self.sampler)
                      if slack == 'adaptive' else None)
    self._epoch_count = 0
    import os
    # tiered stores default to the double-buffered cold overlay
    # (GLT_COLD_PREFETCH=0 opts out; batches are byte-identical)
    self._cold_pipeline = (self.sampler.tiered
                           and os.environ.get('GLT_COLD_PREFETCH',
                                              '1') != '0')
    self.ds = dataset
    seeds = np.asarray(input_nodes).reshape(-1)
    if input_space == 'old' and dataset.old2new is not None:
      seeds = dataset.old2new[seeds]
    self.num_parts = dataset.num_partitions
    self.batch_size = int(batch_size)
    # one batcher per device slice, all consuming a common shuffled pool
    self._batcher = SeedBatcher(seeds, batch_size * self.num_parts,
                                shuffle, drop_last, seed)

  def __len__(self):
    return len(self._batcher)

  def _maybe_emit_hop_events(self, nsn) -> None:
    """Per-hop frontier-size / padding-fill flight-recorder events for
    one batch.  Only when the recorder is on: reading the stacked
    ``num_sampled_nodes`` is a device sync, which the hot path must
    never pay by default."""
    from ..telemetry.recorder import recorder
    if not recorder.enabled:
      return
    from ..telemetry.aggregate import per_hop_padding
    self._batch_idx = getattr(self, '_batch_idx', 0) + 1
    if getattr(nsn, 'is_fully_addressable', True):
      arr = np.asarray(nsn)
    else:
      # multi-controller mesh: only this host's shards are readable —
      # emit the HOST-LOCAL per-hop fill (capacities scale by the
      # local shard count inside per_hop_padding), instead of
      # crashing the job the recorder is meant to diagnose
      arr = np.concatenate(
          [np.asarray(s.data) for s in nsn.addressable_shards])
    rows = per_hop_padding(arr, self.batch_size, self.sampler.fanouts)
    for row in rows:
      recorder.emit('hop.padding', scope='dist_loader',
                    batch=self._batch_idx, **row)

  def _dispatch_flat(self, flat):
    seeds = flat.reshape(self.num_parts, self.batch_size)  # [P * B]
    return self.sampler._dispatch_nodes(seeds)

  def _produce(self, seed_iter):
    from ..loader.transform import Batch
    from ..telemetry.spans import span
    # acquire BEFORE the span: epoch end (StopIteration) must not
    # emit an empty `batch` root span
    if self._cold_pipeline:
      acquired = self._pipeline_acquire(seed_iter)
    else:
      flat = next(seed_iter)                       # [P * B]
    # 'batch' is the per-batch ROOT span; the sampler's
    # sample.exchange / feature.lookup spans nest under it, and
    # 'stitch' covers the Batch assembly — the causal tree stage
    # attribution reads
    with span('batch', scope='DistNeighborLoader',
              batch=getattr(self, '_batch_idx', 0) + 1):
      if self._cold_pipeline:
        # tiered stores: double-buffered cold overlay — batch k+1's
        # sampling is dispatched before batch k's overlay syncs
        # (`PrefetchingLoader._pipelined`; GLT_COLD_PREFETCH=0 off)
        out = self._pipelined(acquired, seed_iter,
                              self._dispatch_flat,
                              self.sampler._finish_nodes)
      else:
        seeds = flat.reshape(self.num_parts, self.batch_size)
        out = self.sampler.sample_from_nodes(seeds)
      self._maybe_emit_hop_events(out['num_sampled_nodes'])
      with span('stitch'):
        edge_index = jnp.stack([out['row'], out['col']],
                               axis=1)             # [P, 2, E]
        md = {'seed_local': out['seed_local']}
        if 'edge_weight' in out:
          # GNS importance weights, aligned with the [P, E] edge list
          # — consumers weight aggregation by them to stay unbiased
          md['edge_weight'] = out['edge_weight']
        batch = Batch(
            x=out['x'], y=out['y'], edge_index=edge_index,
            edge_attr=out['ef'],
            node=out['node'], node_mask=out['node'] >= 0,
            edge_mask=out['row'] >= 0, edge=out['edge'],
            batch=out['batch'], batch_size=self.batch_size,
            num_sampled_nodes=out['num_sampled_nodes'],
            metadata=md)
      self._consumed = getattr(self, '_consumed', 0) + 1
      return batch


def pack_link_seeds(edge_label_index, edge_label,
                    neg_mode: Optional[str]):
  """Pack seed edges (+optional integer labels, binary +1-shifted) into
  the ``[E, 2|3]`` tensor both mesh link loaders batch over — ONE
  definition of the label contract (`link_loader.py:146-186`)."""
  if isinstance(edge_label_index, (tuple, list)):
    rows, cols = edge_label_index
  else:
    ei = np.asarray(edge_label_index)
    rows, cols = ei[0], ei[1]
  rows = np.asarray(rows, np.int64)
  cols = np.asarray(cols, np.int64)
  colsarr = [rows, cols]
  if edge_label is not None:
    lab = np.asarray(edge_label)
    if not np.issubdtype(lab.dtype, np.integer):
      raise ValueError(
          'mesh link loaders carry integer edge labels in their packed '
          'seed tensor; for float labels use the host-runtime '
          'DistLinkNeighborLoader (graphlearn_tpu.distributed)')
    lab = lab.astype(np.int64)
    if neg_mode == 'binary':
      lab = lab + 1     # reference +1 shift (`link_loader.py:146-186`)
    colsarr.append(lab)
  return rows, cols, colsarr


def pack_link_seeds_relabeled(edge_label_index, edge_label,
                              neg_mode: Optional[str], dataset,
                              input_space: str) -> np.ndarray:
  """`pack_link_seeds` + the ``input_space`` old→new endpoint remap —
  the one constructor-side contract shared by `DistLinkNeighborLoader`
  and `FusedDistLinkEpoch`.  Returns the packed ``[E, 2|3]`` pairs."""
  rows, cols, colsarr = pack_link_seeds(edge_label_index, edge_label,
                                        neg_mode)
  if input_space == 'old' and dataset.old2new is not None:
    colsarr[0] = dataset.old2new[rows]
    colsarr[1] = dataset.old2new[cols]
  return np.stack(colsarr, axis=1)


def link_step_metadata(neg_mode: Optional[str], seed_local, eli, elab,
                       elab_mask, src_idx, dst_pos, dst_neg) -> dict:
  """Map a link step's label outputs to the metadata dict
  `link_loss_from_metadata` dispatches on — ONE definition for the
  per-batch sampler and the fused epoch twin."""
  md = {'seed_local': seed_local}
  if neg_mode == 'triplet':
    md.update(src_index=src_idx, dst_pos_index=dst_pos,
              dst_neg_index=dst_neg, pair_mask=src_idx >= 0)
  else:
    md.update(edge_label_index=eli, edge_label=elab,
              edge_label_mask=elab_mask)
  return md


class DistLinkNeighborSampler(DistNeighborSampler):
  """Device-mesh LINK sampler: per-device seed edges + collective
  strict negatives + endpoint expansion — the SPMD analog of the
  reference's link path (`distributed/dist_neighbor_sampler.py:
  327-453`), with negatives strict against the GLOBAL sharded graph
  via `dist_edge_exists` (the reference rejects only locally).

  Args:
    neg_sampling: ``None`` / ``'binary'`` / ``('triplet', amount)``.
  """

  def __init__(self, dataset: DistDataset, num_neighbors,
               neg_sampling=None, **kwargs):
    super().__init__(dataset, num_neighbors, **kwargs)
    from ..sampler.base import NegativeSampling
    ns = (NegativeSampling.cast(neg_sampling)
          if neg_sampling is not None else None)
    # NegativeSampling validates the mode/amount; unknown strings raise
    # instead of silently sampling no negatives
    self.neg_mode = ns.mode if ns is not None else None
    self.neg_amount = float(ns.amount) if ns is not None else 1.0

  def _expansion_seeds(self, b: int) -> Tuple[int, int]:
    """(total expansion seeds, negative count) per device batch —
    negative counts come from the ONE shared definition
    (`distributed.dist_options.binary_num_negatives`)."""
    from ..distributed.dist_options import binary_num_negatives
    if self.neg_mode == 'binary':
      nn = binary_num_negatives(b, self.neg_amount)
      return 2 * b + 2 * nn, nn
    if self.neg_mode == 'triplet':
      amount = int(np.ceil(self.neg_amount))
      return 2 * b + b * amount, b * amount
    return 2 * b, 0

  def step_for_pairs(self, batch_size: int, width: int):
    """The compiled SPMD link step for ``[P, batch_size, width]`` seed
    edges (built once per (batch, width)) — also the scan body of
    `FusedDistLinkEpoch`."""
    b = int(batch_size)
    exp_seeds, num_neg = self._expansion_seeds(b)
    cfg = ('link', b, int(width))
    if cfg not in self._steps:
      with self._layout_span(batch=b, mode='link'):
        self._steps[cfg] = _make_dist_link_step(
            self.mesh, self.num_parts, self.fanouts,
            self.node_capacity(exp_seeds), b,
            self.ds.graph.num_nodes, self.neg_mode, num_neg,
            self.neg_amount,
            self.with_edge, self.collect_features, self.collect_labels,
            self.axis, with_cache=self.with_cache,
            exchange_slack=self.exchange_slack,
            exchange_layout=self.exchange_layout,
            collect_edge_features=self.collect_edge_features,
            ef_shard_mode=self._ef_shard_mode, tiered=self.tiered,
            gns_boost=self.gns_boost, book_spec=self.book_spec,
            cache_local=self.cache_local, ewma_caps=self._ewma_caps())
      if self.gns:
        from ..telemetry.recorder import recorder
        from ..utils.profiling import metrics
        metrics.inc('gns.bias_steps_total')
        recorder.emit('gns.bias', batch=b, mode='link',
                      boost=float(self.gns_boost),
                      num_parts=self.num_parts)
    return self._steps[cfg]

  def sample_from_edges(self, pairs_stacked: np.ndarray, key=None):
    """``pairs_stacked``: ``[P, B, 2|3]`` per-device (src, dst[, label])
    seed edges in the relabeled id space, -1 padded."""
    return self._finish_edges(self._dispatch_edges(pairs_stacked, key))

  def _dispatch_edges(self, pairs_stacked: np.ndarray, key=None):
    """Link twin of `_dispatch_nodes` (the cold pipeline's dispatch
    half)."""
    from ..telemetry.spans import span
    p, b = pairs_stacked.shape[:2]
    self._partition_supervision()
    arrs = self._arrays()
    step = self.step_for_pairs(b, pairs_stacked.shape[2])
    self._step_cnt += 1
    if key is None:
      key = jax.random.fold_in(self._base_key, self._step_cnt)
    with span('sample.exchange', step=self._step_cnt, batch=b,
              mode='link'):
      pairs_dev = jax.device_put(
          np.asarray(pairs_stacked, dtype=np.int32),
          NamedSharding(self.mesh, P(self.axis)))
      extra = (self._gns_arrays(),) if self.gns else ()
      outs = step(arrs['indptr'], arrs['indices'], arrs['eids'],
                  arrs['bounds'], pairs_dev, arrs['fshards'],
                  arrs['lshards'], arrs['cids'], arrs['crows'],
                  arrs['efshards'], arrs['ebounds'],
                  arrs['hcounts'], *extra, key)
      (nodes, count, row, col, edge, seed_local, x, y, ef, nsn,
       stats) = outs[:11]
      ew = outs[11] if self.gns else None
      (eli, elab, elab_mask, src_idx, dst_pos, dst_neg) = \
          outs[12:] if self.gns else outs[11:]
    self._complete_recovery()
    self._accumulate_stats(stats)
    md = link_step_metadata(self.neg_mode, seed_local, eli, elab,
                            elab_mask, src_idx, dst_pos, dst_neg)
    if ew is not None:
      md['edge_weight'] = ew
    return dict(node=nodes, node_count=count[..., 0], row=row, col=col,
                edge=edge, x=x, y=y, ef=ef, num_sampled_nodes=nsn,
                batch=pairs_dev[:, :, 0], metadata=md,
                overlay_step=self._step_cnt)

  def _finish_edges(self, out: dict) -> dict:
    out['x'] = self._maybe_overlay_cold(out['x'], out['node'],
                                        step=out.pop('overlay_step',
                                                     None))
    return out


class DistLinkNeighborLoader(_ResumableEpochMixin, PrefetchingLoader):
  """Distributed link-prediction loader over the device mesh
  (reference ``DistLinkNeighborLoader``,
  `distributed/dist_link_neighbor_loader.py:30-153`): seed edges split
  across devices, negatives drawn collectively, stacked `Batch`
  pytrees with link-label metadata ready for the DP unsupervised step.

  Args:
    edge_label_index: ``[2, E]`` (or ``(rows, cols)``) seed edges.
    edge_label: optional labels (binary mode applies the reference's
      +1 shift).
    neg_sampling: ``'binary'`` / ``('triplet', amount)`` / None.
    input_space: ``'old'`` runs seeds through ``dataset.old2new``.
  """

  def __init__(self, dataset: DistDataset, num_neighbors,
               edge_label_index, edge_label=None, neg_sampling=None,
               batch_size: int = 1, shuffle: bool = False,
               drop_last: bool = False, mesh: Optional[Mesh] = None,
               with_edge: bool = False, collect_features: bool = True,
               seed: int = 0, input_space: str = 'old',
               exchange_slack='auto',
               exchange_layout: Optional[str] = None,
               prefetch: int = 0, cold_cache_rows='auto', gns=None):
    from ..loader.node_loader import SeedBatcher
    self.prefetch = int(prefetch)
    slack = resolve_exchange_slack(exchange_slack, shuffle)
    self.sampler = DistLinkNeighborSampler(
        dataset, num_neighbors, neg_sampling=neg_sampling, mesh=mesh,
        with_edge=with_edge, collect_features=collect_features,
        seed=seed,
        exchange_slack=(DEFAULT_EXCHANGE_SLACK if slack == 'adaptive'
                        else slack),
        exchange_layout=exchange_layout,
        cold_cache_rows=cold_cache_rows, gns=gns)
    self._adaptive = (AdaptiveSlack(self.sampler)
                      if slack == 'adaptive' else None)
    self._epoch_count = 0
    import os
    self._cold_pipeline = (self.sampler.tiered
                           and os.environ.get('GLT_COLD_PREFETCH',
                                              '1') != '0')
    self.pairs = pack_link_seeds_relabeled(
        edge_label_index, edge_label, self.sampler.neg_mode, dataset,
        input_space)
    self.num_parts = dataset.num_partitions
    self.batch_size = int(batch_size)
    self._batcher = SeedBatcher(self.pairs,
                                batch_size * self.num_parts, shuffle,
                                drop_last, seed)

  def __len__(self):
    return len(self._batcher)

  def _dispatch_flat(self, flat):
    pairs = flat.reshape(self.num_parts, self.batch_size, -1)
    return self.sampler._dispatch_edges(pairs)

  def _produce(self, seed_iter):
    from ..loader.transform import Batch
    from ..telemetry.spans import span
    # acquire BEFORE the span (see DistNeighborLoader._produce)
    if self._cold_pipeline:
      acquired = self._pipeline_acquire(seed_iter)
    else:
      flat = next(seed_iter)                       # [P * B, 2|3]
    with span('batch', scope='DistLinkNeighborLoader'):
      if self._cold_pipeline:
        out = self._pipelined(acquired, seed_iter,
                              self._dispatch_flat,
                              self.sampler._finish_edges)
      else:
        pairs = flat.reshape(self.num_parts, self.batch_size, -1)
        out = self.sampler.sample_from_edges(pairs)
      with span('stitch'):
        edge_index = jnp.stack([out['row'], out['col']], axis=1)
        batch = Batch(
            x=out['x'], y=out['y'], edge_index=edge_index,
            edge_attr=out['ef'],
            node=out['node'], node_mask=out['node'] >= 0,
            edge_mask=out['row'] >= 0, edge=out['edge'],
            batch=out['batch'], batch_size=self.batch_size,
            num_sampled_nodes=out['num_sampled_nodes'],
            metadata=out['metadata'])
      self._consumed = getattr(self, '_consumed', 0) + 1
      return batch
