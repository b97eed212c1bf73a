"""Pallas delta-CSR merge — dirty-row shift+insert on device (r19).

`streaming/delta.py::merge_delta_csr` keeps the clean bulk vectorized
but re-sorts every DIRTY row with a per-row python ``np.argsort``
loop; under steady-state ingest (ISSUE 14's freshness loop) that loop
is the merge's serial tail and it sits on the publish critical path.
This module replaces the loop with ONE kernel launch: a stable
MERGE-BY-RANK over all dirty rows at once.

Both inputs of a dirty row are already ordered — the base slice is
sorted CSR, the segment slice is event-ordered — so the stable sort
is really a two-way merge, and a merge needs no sort network: each
element's output position is its RANK,

  * base element ``i`` (column ``b_i``):   ``i + #{j: s_j <  b_i}``
  * seg  element ``j`` (column ``s_j``):   ``#{i: b_i <= s_j}
                                             + #{m < j: s_m <= s_j}
                                             + #{m > j: s_m <  s_j}``

which reproduces `coo_to_csr`'s stable lexsort tie-breaking exactly:
equal columns land base-first, then in event order (pinned
byte-identical in tests/test_pallas_sample.py).  Rows are padded to
the batch's max widths with an int32-max sentinel, so no per-row
control flow and no length scalars reach the kernel — sentinel
columns rank past every real column and fall off the cropped tail.

The host keeps what it is good at: the new ``indptr`` prefix sum and
the one-scatter clean-bulk shift (`merge_delta_csr`'s vectorized
half).  Dispatch discipline matches the other r19 kernels:
``GLT_PALLAS_DELTA`` (default OFF) is re-read per merge, any
disqualified shape raises `DeltaMergeUnsupported` and the caller
(`StreamingGraph.apply_events`) falls back to the host merge at byte
parity, stamping a ``pallas.fallback`` event.

Roofline note (r19): the rank kernel is compare-bound, O(L^2) per
row over VMEM-resident tiles vs the host loop's O(L log L) serial
passes + interpreter overhead per row; the win is batching every
dirty row into one launch, not asymptotics — not measured on the
chip; measure it there before defaulting it on (ROADMAP D2).
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import numpy as np

DELTA_ENV = 'GLT_PALLAS_DELTA'

#: per-row width cap (base or segment side): [L, L] compare tiles
#: must stay VMEM-plausible; wider rows fall back to the host merge.
_MAX_WIDTH = 2048

_TILE = 8


class DeltaMergeUnsupported(Exception):
  """Shape/dtype disqualifies the merge kernel; fall back to host."""


def delta_merge_enabled() -> bool:
  """Re-read ``GLT_PALLAS_DELTA`` on every merge (kill switch)."""
  return os.environ.get(DELTA_ENV, '').strip().lower() in (
      '1', 'true', 'on', 'yes')


def _rank_kernel(Lb: int, Ls: int, tile: int):
  import jax
  import jax.numpy as jnp
  from jax.experimental import pallas as pl

  def kernel(bc_ref, sc_ref, pb_ref, ps_ref):
    for i in range(tile):
      bc = bc_ref[pl.ds(i, 1), :]                       # [1, Lb]
      sc = sc_ref[pl.ds(i, 1), :]                       # [1, Ls]
      # base ranks: i + #{seg strictly below b_i}
      lt = sc < bc.reshape(Lb, 1)                       # [Lb, Ls]
      bi = jax.lax.broadcasted_iota(jnp.int32, (1, Lb), 1)
      pb_ref[pl.ds(i, 1), :] = bi + jnp.sum(
          lt.astype(jnp.int32), axis=1).reshape(1, Lb)
      # seg ranks: #{base <= s_j} + #{earlier seg <= s_j}
      #                           + #{later seg < s_j}
      le = bc <= sc.reshape(Ls, 1)                      # [Ls, Lb]
      c_base = jnp.sum(le.astype(jnp.int32), axis=1)
      mj = jax.lax.broadcasted_iota(jnp.int32, (Ls, Ls), 1)
      jj = jax.lax.broadcasted_iota(jnp.int32, (Ls, Ls), 0)
      scm = sc                                          # row of m
      scj = sc.reshape(Ls, 1)
      before = (scm < scj) | ((scm == scj) & (mj < jj))
      c_seg = jnp.sum(before.astype(jnp.int32), axis=1)
      ps_ref[pl.ds(i, 1), :] = (c_base + c_seg).reshape(1, Ls)

  return kernel


@functools.lru_cache(maxsize=32)
def _rank_call(Lb: int, Ls: int, rp: int, tile: int,
               interpret: bool):
  import jax
  from jax.experimental import pallas as pl
  import jax.numpy as jnp

  def blk(width):
    return pl.BlockSpec((tile, width), lambda t: (t, 0))

  return jax.jit(pl.pallas_call(
      _rank_kernel(Lb, Ls, tile),
      grid=(rp // tile,),
      in_specs=[blk(Lb), blk(Ls)],
      out_specs=[blk(Lb), blk(Ls)],
      out_shape=(jax.ShapeDtypeStruct((rp, Lb), jnp.int32),
                 jax.ShapeDtypeStruct((rp, Ls), jnp.int32)),
      interpret=interpret,
  ))


def merge_ranks(bc: np.ndarray, sc: np.ndarray, *,
                interpret: Optional[bool] = None,
                tile: int = _TILE
                ) -> Tuple[np.ndarray, np.ndarray]:
  """Stable two-way merge ranks for a batch of (base, seg) column
  rows, both ascending-sorted per row, int32-max sentinel padded.
  Returns ``(pos_b [R, Lb], pos_s [R, Ls])`` int32 output positions
  within each merged row."""
  import jax
  if interpret is None:
    interpret = jax.default_backend() != 'tpu'
  r, lb = bc.shape
  ls = sc.shape[1]
  rp = -(-r // tile) * tile
  sent = np.iinfo(np.int32).max
  if rp != r:
    pad = np.full((rp - r, lb), sent, np.int32)
    bc = np.concatenate([bc, pad])
    sc = np.concatenate([sc, np.full((rp - r, ls), sent, np.int32)])
  pos_b, pos_s = _rank_call(int(lb), int(ls), int(rp), int(tile),
                            bool(interpret))(bc, sc)
  return np.asarray(pos_b)[:r], np.asarray(pos_s)[:r]


def merge_delta_csr_device(indptr: np.ndarray, indices: np.ndarray,
                           eids: np.ndarray, seg,
                           *, interpret: Optional[bool] = None
                           ) -> Tuple[np.ndarray, np.ndarray,
                                      np.ndarray]:
  """Kernel-backed twin of `streaming.delta.merge_delta_csr` — same
  byte-identity contract (result equals ``coo_to_csr`` over the full
  event-ordered edge list).  Host does the indptr prefix sum and the
  clean-bulk shift; the dirty rows are merged by the rank kernel in
  one launch instead of the per-row python sort loop.

  Raises `DeltaMergeUnsupported` when the shape disqualifies the
  kernel (caller falls back to the host merge)."""
  from ..utils.topo import ptr2ind
  num_nodes = len(indptr) - 1
  src = np.asarray(seg.src, np.int64)
  if src.size and (src.min() < 0 or src.max() >= num_nodes):
    raise ValueError(
        f'delta source ids out of range for num_nodes={num_nodes}')
  sent = np.iinfo(np.int32).max
  if num_nodes >= sent:
    raise DeltaMergeUnsupported('num_nodes >= int32 sentinel')
  add = np.bincount(src, minlength=num_nodes).astype(np.int64)
  new_indptr = np.zeros(num_nodes + 1, np.int64)
  np.cumsum(np.diff(indptr) + add, out=new_indptr[1:])
  e_new = int(new_indptr[-1])
  new_indices = np.empty(e_new, indices.dtype)
  new_eids = np.empty(e_new, eids.dtype)
  if len(indices):
    rows_of = ptr2ind(indptr)
    pos = np.arange(len(indices)) + (new_indptr[:-1] - indptr[:-1]
                                     )[rows_of]
    new_indices[pos] = indices
    new_eids[pos] = eids
  dirty = np.unique(src)
  if dirty.size:
    dst = np.asarray(seg.dst)
    seg_eids = np.asarray(seg.eids)
    order = np.argsort(src, kind='stable')
    s_src = src[order]
    s_dst = dst[order]
    s_eids = seg_eids[order]
    seg_lo = np.searchsorted(s_src, dirty, side='left')
    seg_cnt = (np.searchsorted(s_src, dirty, side='right')
               - seg_lo).astype(np.int64)
    base_cnt = (indptr[dirty + 1] - indptr[dirty]).astype(np.int64)
    lb = max(1, int(base_cnt.max()))
    ls = max(1, int(seg_cnt.max()))
    if lb > _MAX_WIDTH or ls > _MAX_WIDTH:
      raise DeltaMergeUnsupported(f'dirty row wider than {_MAX_WIDTH}')
    rd = int(dirty.size)
    bi = np.arange(lb)
    bmask = bi[None, :] < base_cnt[:, None]
    bpos = np.asarray(indptr)[dirty][:, None] + bi     # base edge pos
    bc = np.full((rd, lb), sent, np.int32)
    bc[bmask] = np.asarray(indices)[bpos[bmask]].astype(np.int32)
    si = np.arange(ls)
    smask = si[None, :] < seg_cnt[:, None]
    spos = seg_lo[:, None] + si
    sc = np.full((rd, ls), sent, np.int32)
    sc[smask] = s_dst[spos[smask]].astype(np.int32)
    pos_b, pos_s = merge_ranks(bc, sc, interpret=interpret)
    tgt = (new_indptr[dirty][:, None] + pos_b)[bmask]
    srcpos = bpos[bmask]
    new_indices[tgt] = np.asarray(indices)[srcpos]
    new_eids[tgt] = np.asarray(eids)[srcpos]
    tgt = (new_indptr[dirty][:, None] + pos_s)[smask]
    sflat = spos[smask]
    new_indices[tgt] = s_dst[sflat].astype(new_indices.dtype)
    new_eids[tgt] = s_eids[sflat].astype(new_eids.dtype)
  return new_indptr, new_indices, new_eids
