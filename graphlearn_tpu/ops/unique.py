"""Capacity-bounded, order-preserving unique & relabel.

TPU-native replacement for the reference's GPU hash-table "inducer"
(`csrc/cuda/inducer.cu:94-141`, `csrc/cuda/hash_table.cu`,
`include/hash_table.cuh:24-150`): the CUDA code deduplicates node ids
and assigns local indices with atomicCAS open addressing.  TPUs have no
device-atomics idiom, so we use a sort-based unique instead — fully
static shapes, no data-dependent sizes, jit/vmap/shard_map friendly.

Semantics match the inducer contract: the *first occurrence order* of
ids is preserved (seeds keep local indices ``0..B-1``, newly discovered
nodes are appended in arrival order), which PyG-style batches rely on.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..telemetry.recorder import recorder
from ..utils.padding import INVALID_ID


class UniqueResult(NamedTuple):
  """Result of a capacity-bounded unique.

  Attributes:
    values: ``[capacity]`` unique ids in first-occurrence order, padded
      with ``fill_value``.
    inverse: ``[n]`` local index of each input element in ``values``
      (-1 for invalid/padded inputs or overflow past capacity).
    count: scalar — number of valid unique ids (clamped to capacity).
  """
  values: jax.Array
  inverse: jax.Array
  count: jax.Array


#: permutation gathers ``a[perm]`` over all ``n`` sorted elements that
#: one `unique_stable` makes (it made five before its sorts carried
#: their payloads); `emit_dedup` reports ``gathered`` from it.
GATHERS_PER_DEDUP = 0


def _copy_from_heads(head: jax.Array, vals: jax.Array) -> jax.Array:
  """``vals`` at each element's segment head, handed along the sorted
  order without a gather: ``head`` flags the first element of every
  segment, ``vals`` lie in ``[0, n)``.

  A running max over keys that pack (position of the head, a chunk of
  the head's value) into 31 bits: positions rise along the order, so
  the max at an element is its own head's key.  One pass where
  position and value fit 31 bits together (``n <= 2**15``), else one
  per chunk of the value (two up to ``2**20`` elements).  Elements
  before the first head read garbage; the caller masks them.
  """
  n = head.shape[0]
  pos_bits = max(int(n - 1).bit_length(), 1)
  chunk = 31 - pos_bits
  low = (1 << chunk) - 1
  pos = jnp.arange(n, dtype=jnp.int32) << chunk
  out = jnp.zeros((n,), jnp.int32)
  for shift in range(0, pos_bits, chunk):
    key = jnp.where(head, pos | ((vals >> shift) & low), -1)
    out = out | ((jax.lax.cummax(key) & low) << shift)
  return out


@functools.partial(jax.jit, static_argnames=('capacity', 'fill_value'))
def unique_stable(
    x: jax.Array,
    capacity: int,
    fill_value: int = INVALID_ID,
    valid: Optional[jax.Array] = None,
) -> UniqueResult:
  """Order-preserving unique with a static output capacity.

  Algorithm (all O(n log n), static shapes): four sorts, each carrying
  as operands what the next step reads, and no gather or scatter over
  the ``n`` elements —
    1. stable-sort ``(id, position)`` by id (invalid ids mapped to a
       +inf sentinel) — within an equal-value segment the original
       positions stay ascending, so each segment HEAD already sits at
       its value's first occurrence (no segment-min scatters needed),
    2. rank segments in appearance order by sorting the heads' original
       positions, with the sorted ids and the sorted positions as
       payload: the ids come out in appearance order (``values`` is a
       static slice of them),
    3. invert that permutation with a third sort, which puts each
       head's appearance rank at its sorted position, and hand it to
       the rest of its segment by a running max (`_copy_from_heads`),
    4. sort ``(position, rank)`` by position: ``inverse``.
  On the v5e a permutation gather ``a[perm]`` over 937,984 elements
  costs 6.69 ms, a sort of them 0.86 ms with two operands (1.04 asked
  to be stable) and 1.30 with three: the form that fetched these five
  values with gathers spent 33.4 of its 38.2 ms at that size in them,
  this one takes 5.2 (PERF.md section 6, PR 35).  A three-operand sort
  compiles about half again as long as a two-operand one.
  """
  n = x.shape[0]
  if n == 0:
    return UniqueResult(
        values=jnp.full((capacity,), fill_value, x.dtype),
        inverse=jnp.zeros((0,), jnp.int32),
        count=jnp.zeros((), jnp.int32))
  if valid is None:
    valid = x != fill_value
  else:
    valid = valid & (x != fill_value)
  big = jnp.iinfo(x.dtype).max
  xv = jnp.where(valid, x, big)
  iota = jnp.arange(n, dtype=jnp.int32)

  # ids sorted by value, each with its original position
  xs, order = jax.lax.sort((xv, iota), num_keys=1, is_stable=True)
  live = xs != big
  head = jnp.concatenate([jnp.ones((1,), bool), xs[1:] != xs[:-1]]) & live
  # Up to n distinct segments exist; overflow past `capacity` must drop
  # the *latest-appearing* ids (preserving earlier local indices), so
  # ranking happens over all n segments before truncation.
  count = jnp.minimum(jnp.sum(head), capacity)

  # appearance order: stable sort -> the head of each segment carries
  # that value's first original position; sorting those positions gives
  # the appearance ranking directly.  Non-heads sink to the tail, tied:
  # nothing reads their order, so the sort need not be stable.
  first_pos = jnp.where(head, order, jnp.iinfo(jnp.int32).max)
  _, vals_by_rank, rank_to_sorted = jax.lax.sort(
      (first_pos, xs, iota), num_keys=1, is_stable=False)
  vals_by_rank = jnp.pad(vals_by_rank[:capacity],
                         (0, max(capacity - n, 0)))
  values = jnp.where(jnp.arange(capacity) < count, vals_by_rank, fill_value)

  # Each element's appearance rank: the keys of the last two sorts are
  # permutations, so they ask for no stability (asking adds an operand).
  _, sorted_to_rank = jax.lax.sort((rank_to_sorted, iota), num_keys=1,
                                   is_stable=False)
  rank = _copy_from_heads(head, sorted_to_rank)
  inv_sorted = jnp.where(live & (rank < capacity), rank, -1)
  _, inverse = jax.lax.sort((order, inv_sorted), num_keys=1,
                            is_stable=False)
  return UniqueResult(values=values, inverse=inverse, count=count)


class InducerState(NamedTuple):
  """Functional inducer state: the node table accumulated across hops.

  Attributes:
    nodes: ``[capacity]`` global node ids in insertion order (padded).
    count: scalar number of valid entries.
  """
  nodes: jax.Array
  count: jax.Array


def init_node(seeds: jax.Array, capacity: int) -> Tuple[InducerState,
                                                        jax.Array]:
  """Seed the inducer table; counterpart of ``InitNode``
  (`csrc/cuda/inducer.cu:74`).  Seeds are deduplicated preserving order
  (reference seeds are assumed unique per batch; we dedup defensively).

  Returns the state and the seeds' local indices.
  """
  res = unique_stable(seeds, capacity)
  return InducerState(nodes=res.values, count=res.count), res.inverse


def induce_next(
    state: InducerState,
    src_local: jax.Array,
    nbrs: jax.Array,
    nbr_mask: jax.Array,
    capacity: Optional[int] = None,
) -> Tuple[InducerState, jax.Array, jax.Array, jax.Array]:
  """Insert newly sampled neighbors; counterpart of ``InduceNext``
  (`csrc/cuda/inducer.cu:94-141`).

  What is sorted is the table AS HANDED IN plus the candidates,
  ``state.nodes.shape[0] + B*k`` elements (four sorts that carry
  their payloads and a running max, no gather over them: 5.2 ms for
  937,984 elements on the v5e, 0.7 for 169,984); what comes back is a
  table of ``capacity`` rows.  The two are apart so that a caller can
  grow its table insertion by insertion: handed a table of
  the rows filled so far and asked for ``min(rows + B*k, final)``, it
  gets the ids, counts and local indices a table held at ``final`` from
  the start would give (the result depends on the valid elements and
  their order only; overflow past ``capacity`` drops the
  latest-appearing ids) from a sort that never covers the padding.

  Args:
    state: current node table.
    src_local: ``[B]`` local indices of the source nodes (-1 invalid).
    nbrs: ``[B, k]`` sampled neighbor global ids (-1 invalid).
    nbr_mask: ``[B, k]`` validity of each sampled neighbor.
    capacity: static row count of the returned table; default the
      incoming table's.

  Returns:
    ``(new_state, rows, cols, frontier_start)`` where ``rows``/``cols``
    are the ``[B*k]`` local COO of the sampled edges — ``rows`` is the
    *neighbor* local index and ``cols`` the *source* local index,
    matching the reference's transposed emission for PyG message
    passing (`sampler/neighbor_sampler.py:159-166`) — and
    ``frontier_start`` is the previous node count (new frontier =
    ``state.nodes[frontier_start:new_count]``).
  """
  held = state.nodes.shape[0]
  if capacity is None:
    capacity = held
  b, k = nbrs.shape
  flat_nbrs = nbrs.reshape(-1)
  flat_mask = nbr_mask.reshape(-1)

  # Combined table: existing nodes first (so their indices are stable),
  # then the new candidates in arrival order.
  combined = jnp.concatenate([state.nodes, flat_nbrs])
  valid = jnp.concatenate(
      [jnp.arange(held) < state.count, flat_mask])
  res = unique_stable(combined, capacity, valid=valid)

  new_state = InducerState(nodes=res.values, count=res.count)
  nbr_local = res.inverse[held:]                # [B*k]
  src_flat = jnp.broadcast_to(src_local[:, None], (b, k)).reshape(-1)
  edge_valid = flat_mask & (src_flat >= 0) & (nbr_local >= 0)
  rows = jnp.where(edge_valid, nbr_local, -1)
  cols = jnp.where(edge_valid, src_flat, -1)
  return new_state, rows, cols, state.count


def emit_dedup(insertions) -> None:
  """The trace-time record of a sampler program's `induce_next` calls:
  one ``sample.dedup`` flight-recorder event per compiled program,
  listing per insertion ``(name, sorted, table_rows, candidates)`` —
  the elements its sort covers, the capacity it returns and the
  ``B*k`` it inserts — and ``gathered``, the elements that dedup moves
  through a permutation gather (`GATHERS_PER_DEDUP` times ``sorted``;
  an event without the field is of the form that gathered five times
  ``sorted``).  A program whose tables start at their final size (the
  mesh samplers) emits none."""
  if insertions:
    names, n_sorted, rows, candidates = zip(*insertions)
    recorder.emit('sample.dedup', insertions=len(insertions),
                  scope=list(names), sorted=list(n_sorted),
                  table_rows=list(rows), candidates=list(candidates),
                  gathered=[GATHERS_PER_DEDUP * n for n in n_sorted])
