"""Pallas fused one-kernel sample+compact — the r19 sampling tentpole.

`ops/neighbor.py::sample_one_hop`'s medium-degree arm (``k < deg <=
W``) materializes a ``[B, W]`` gathered window, a ``[B, W]`` Gumbel
tensor and a full ``top_k`` sort network per hop; the GNS twin
(`ops/gns.py`) adds a ``[B, W]`` membership gather, a ``[B, W]``
cumulative-weight vector and a vmapped ``searchsorted`` on top.  This module fuses the whole
medium arm into ONE Pallas kernel:

  * the seed's CSR window arrives by aligned-overfetch DMA
    (`pallas_window.py` layout: two 4 KB units per seed, lane+sublane
    rotates cut the exact ``[w]`` slice — never a general gather);
  * the draw happens IN REGISTERS against the VMEM window — Gumbel
    rank-select for the uniform kernel, the GNS inverse-CDF biased
    draw ``q(v) ∝ 1 + boost·cached(v)`` with the per-requester
    bitmask lookup (the dedup table of `ops.gns.dedup_requester_bits`)
    read straight from a VMEM-resident bits block for the biased one;
  * compacted neighbor values, window offsets and (GNS) ``1/q``
    importance weights stream out in one pass — the ``[B, W]``
    window, sort and cumsum intermediates never reach HBM.

**Value parity is exact, not approximate.**  All randomness is drawn
OUTSIDE the kernel with the identical `jax.random` key discipline the
XLA kernels use (``k_rand, k_win = split(key)``; same shapes, same
order), so the fused kernel consumes the very same uniforms/Gumbels
and reproduces the XLA outputs bit-for-bit:

  * Gumbel top-k is computed as a rank-select (count of strictly
    greater entries with index tie-break) — the same total order
    `jax.lax.top_k` sorts by;
  * the inverse-CDF draw counts ``cum <= draw`` — exactly
    ``searchsorted(side='right')`` on a sorted vector;
  * the ``deg > W`` with-replacement arm and the ``deg <= k``
    take-all arm are selected from the same precomputed offsets the
    XLA path uses (the beyond-window gather stays an XLA gather: it
    is O(B·k), not O(B·W), and keeps the kernel's DMA footprint at
    two units per seed).

`tests/test_pallas_sample.py` pins nbrs/mask/eids/weights equality
against `sample_one_hop` / `sample_one_hop_gns` in interpret mode on
CPU tier-1 for every arm.

**On a chip (one v5e, PR 21 bring-up, nothing timed).**  The UNIFORM
kernel compiles non-interpreted and is value-identical to
`sample_one_hop` on the products-scale CSR at B=1024/k=15 and
B=15360/k=10, called directly and through `sample_one_hop_auto` under
`jit`.  The GNS kernel does NOT lower: Mosaic answers
``Unimplemented primitive in Pallas TPU lowering for KernelType.TC:
dynamic_slice`` (the per-requester row select,
`dynamic_index_in_dim` over the bits block) — so on a TPU, a GNS
sampler with ``GLT_PALLAS_SAMPLE=1`` raises that message.

**Dispatch discipline** (the `pallas_gather.py` precedent): default
OFF; ``GLT_PALLAS_SAMPLE`` is re-read at every dispatch (kill
switch), `sample_one_hop_auto` takes the XLA kernels — at value
parity — whenever `fused_sample_supported` disqualifies the shape or
dtype (a documented rule, stamped into a ``pallas.fallback`` event),
and emits ``pallas.dispatch`` at trace time so the chosen path is
visible in traces without taxing the steady state.  A qualified
kernel that then fails to trace or compile raises; it never silently
becomes the twin.

**Roofline note (r19, from shapes — not a measurement).**  The medium
arm moves ``8 KB`` of window DMA + ``k`` compacted outputs per seed
where the XLA path moves the ``[B, W]`` window plus the sort's
O(W log W) compare network through HBM; at the bench shapes (B=4096,
k=8, W=64) that is ~6x less HBM traffic on the draw path.  Whether
that is a win is for a chip measurement to say (ROADMAP S1).  The beyond-window hub arm and the
O(E) `prepare_window_table` repack stay outside the kernel — pass a
prebuilt ``table`` on repeated calls (the `NeighborSampler` caches
one per graph version) or the repack lands on the per-call path.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.padding import INVALID_ID
from .neighbor import OneHopResult, default_window, sample_one_hop
from .gns import (bits_table, is_per_requester, resolve_boost,
                  sample_one_hop_gns)
from .pallas_window import (LANES, MAX_W, SUBLANES, UNIT, _TILE,
                            prepare_window_table)

SAMPLE_ENV = 'GLT_PALLAS_SAMPLE'

#: scalar-prefetch budget — same bound as `pallas_gather._MAX_DMA_IDS`.
#: At the flagship shape (batch 1024, fanout [15, 10, 5]) the third
#: hop's frontier is 153,600 seeds, so the largest hop never takes the
#: kernel.  The bound is also loose: this kernel prefetches 3 (uniform)
#: or 4 (GNS) ``[B]`` int32 vectors into the v5e's 1 MB SMEM, and the
#: compiler refused the 2-vector window kernel at B=153,600 ("Used
#: 1.17M of 1.00M smem", PR 21 chip run) — a B between ~80k and this
#: bound is expected to be refused the same way (it raises; it is not
#: caught).  Left for ROADMAP S1 to settle with a measurement.
_MAX_IDS = 1 << 17

#: VMEM budget for the replicated per-requester bits block (the dedup
#: table keeps this at O(distinct caches), not O(P)).
_MAX_BITS_BYTES = 4 << 20


def fused_sample_enabled() -> bool:
  """Re-read ``GLT_PALLAS_SAMPLE`` on every dispatch (kill switch —
  the `pallas_gather.pallas_enabled` discipline)."""
  return os.environ.get(SAMPLE_ENV, '').strip().lower() in (
      '1', 'true', 'on', 'yes')


def _interpret_default() -> bool:
  return jax.default_backend() != 'tpu'


def fused_sample_supported(b: int, k: int, window: Optional[int],
                           indices_dtype,
                           bits=None,
                           replace: bool = False,
                           num_edges: Optional[int] = None
                           ) -> Optional[str]:
  """None when the fused kernel can run this shape; else the
  fallback-reason string (stamped into the ``pallas.fallback``
  event)."""
  w = window if window is not None else default_window(k)
  if replace:
    return 'replace-arm'          # no window arm to fuse
  if b < 1 or k < 1 or num_edges == 0:
    return 'empty'
  if k > w:
    return 'k>window'
  if w > MAX_W:
    return f'window>{MAX_W}'      # two-unit overfetch no longer covers
  if b > _MAX_IDS:
    return 'batch>smem-budget'
  if jnp.dtype(indices_dtype) != jnp.int32:
    return 'indices-dtype'
  if bits is not None:
    tbl = bits_table(bits)
    if int(tbl.shape[0]) * int(tbl.shape[1]) > _MAX_BITS_BYTES:
      return 'bits>vmem-budget'
  return None


def _emit(kind: str, **fields) -> None:
  from ..telemetry.recorder import recorder
  if recorder.enabled:
    recorder.emit(kind, **fields)


def _make_kernel(*, k: int, w: int, tile: int, boost: float,
                 gns: bool, nbytes: int):
  """Kernel factory.  Scalar-prefetch refs: per-seed DMA row, intra-
  unit offset, degree, (GNS) bits-table row.  Tensor inputs: the
  ``[R, 128]`` window table (ANY -> manual DMA), the precomputed
  draws, the with-replacement offsets, the beyond-window values and
  (GNS) the bits table block."""

  def kernel(row_ref, off_ref, deg_ref, *rest):
    if gns:
      (req_ref, tbl_ref, draw_ref, rand_ref, large_ref, bits_ref,
       val_ref, out_off_ref, iw_ref, scratch, sems) = rest
    else:
      (tbl_ref, draw_ref, rand_ref, large_ref,
       val_ref, out_off_ref, scratch, sems) = rest
    t = pl.program_id(0)
    for i in range(tile):
      r = row_ref[t * tile + i]
      pltpu.make_async_copy(tbl_ref.at[pl.ds(r, 2 * SUBLANES)],
                            scratch.at[i], sems.at[i]).start()
    for i in range(tile):
      g = t * tile + i
      r = row_ref[g]
      pltpu.make_async_copy(tbl_ref.at[pl.ds(r, 2 * SUBLANES)],
                            scratch.at[i], sems.at[i]).wait()
      off = off_ref[g]
      r0 = off // LANES
      c0 = off % LANES
      val = scratch[i]                       # [16, 128]
      rot = pltpu.roll(val, -c0, 1)
      rot = pltpu.roll(rot, -r0, 0)
      lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
      take0 = lane < (LANES - c0)
      win = jnp.where(take0, rot[0:1, :w], rot[1:2, :w])   # [1, w]

      deg_i = deg_ref[g]
      in_deg = lane < deg_i                                # [1, w]
      slot_k = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
      ee = jax.lax.broadcasted_iota(jnp.int32, (k, w), 1)

      if gns:
        # membership bits for the window ids, straight from VMEM —
        # identical math to `bitmask_lookup` (invalid slots read
        # byte 0 and are zeroed by in_deg, exactly like the XLA
        # path's where(in_deg, win_ids, -1) masking)
        ids = jnp.where(in_deg, win, 0)
        rowv = jax.lax.dynamic_index_in_dim(
            bits_ref[...], req_ref[g], axis=0, keepdims=False)
        byte = jnp.take(rowv, jnp.clip(ids >> 3, 0, nbytes - 1)
                        .reshape(-1)).reshape(1, w)
        bit = (byte >> (ids & 7).astype(jnp.uint8)) & jnp.uint8(1)
        cached = jnp.where(in_deg, bit, jnp.uint8(0))
        wgt = jnp.where(
            in_deg,
            1.0 + jnp.float32(boost) * cached.astype(jnp.float32),
            0.0)                                           # [1, w]
        cum = jnp.cumsum(wgt, axis=1)
        total = cum[0, w - 1]
        draw = draw_ref[pl.ds(i, 1), :] * jnp.maximum(total, 1e-9)
        # searchsorted(side='right') == count of cum <= draw
        cmp = cum <= draw.reshape(k, 1)                    # [k, w]
        off_med = jnp.sum(cmp.astype(jnp.int32),
                          axis=1).reshape(1, k)
        off_med = jnp.minimum(off_med, jnp.maximum(deg_i - 1, 0))
        hot = ee == off_med.reshape(k, 1)                  # one-hot
        w_drawn = jnp.sum(jnp.where(hot, wgt, 0.0),
                          axis=1).reshape(1, k)
        iw = (total / jnp.maximum(deg_i, 1)) \
            / jnp.maximum(w_drawn, 1e-9)
        iw_ref[pl.ds(i, 1), :] = iw
      else:
        # Gumbel top-k as a rank select: rank(e) = #{f beating e}
        # under the (value desc, index asc) total order lax.top_k
        # sorts by — bit-identical winners, no sort network
        gmb = jnp.where(in_deg, draw_ref[pl.ds(i, 1), :], -jnp.inf)
        colv = gmb.reshape(w, 1)
        fidx = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
        eidx = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
        beats = (gmb > colv) | ((gmb == colv) & (fidx < eidx))
        rank = jnp.sum(beats.astype(jnp.int32),
                       axis=1).reshape(1, w)
        sel = rank == jax.lax.broadcasted_iota(jnp.int32, (k, w), 0)
        off_med = jnp.sum(
            jnp.where(sel,
                      jax.lax.broadcasted_iota(jnp.int32, (k, w), 1),
                      0), axis=1).reshape(1, k)

      take_all = deg_i <= k
      med = (deg_i > k) & (deg_i <= w)
      off_sel = jnp.where(take_all, slot_k,
                          jnp.where(med, off_med,
                                    rand_ref[pl.ds(i, 1), :]))
      # compact: value = window one-hot for in-window offsets, the
      # precomputed beyond-window gather for the hub arm
      onehot = ee == off_sel.reshape(k, 1)                 # [k, w]
      win_val = jnp.sum(jnp.where(onehot, win, 0),
                        axis=1).reshape(1, k)
      val_out = jnp.where(deg_i > w, large_ref[pl.ds(i, 1), :],
                          win_val)
      val_ref[pl.ds(i, 1), :] = val_out
      out_off_ref[pl.ds(i, 1), :] = off_sel

  return kernel


@functools.partial(
    jax.jit,
    static_argnames=('e', 'k', 'w', 'tile', 'boost', 'gns',
                     'interpret'))
def _fused_draw(ind2d, starts, deg, draws, rand_off, large_vals,
                reqrow, bits2d, *, e: int, k: int, w: int, tile: int,
                boost: float, gns: bool, interpret: bool):
  """Run the fused kernel over padded tiles; returns ``(val, off[,
  iw])`` each ``[b, k]``."""
  b = starts.shape[0]
  bp = -(-b // tile) * tile
  starts_p = jnp.zeros((bp,), jnp.int32).at[:b].set(
      jnp.clip(starts.astype(jnp.int32), 0, max(int(e) - 1, 0)))
  deg_p = jnp.zeros((bp,), jnp.int32).at[:b].set(deg)
  unit_row = starts_p // UNIT * SUBLANES
  offm = starts_p % UNIT

  def pad2(x, dtype):
    return jnp.zeros((bp, x.shape[1]), dtype).at[:b].set(
        x.astype(dtype))

  draws_p = pad2(draws, jnp.float32)
  rand_p = pad2(rand_off, jnp.int32)
  large_p = pad2(large_vals, jnp.int32)

  nbytes = int(bits2d.shape[1]) if gns else 0
  kernel = _make_kernel(k=k, w=w, tile=tile, boost=boost, gns=gns,
                        nbytes=nbytes)
  n_scalar = 4 if gns else 3
  dw = draws.shape[1]

  def blk(width):
    return pl.BlockSpec((tile, width),
                        lambda t, *refs: (t, 0),
                        memory_space=pltpu.VMEM)

  in_specs = [pl.BlockSpec(memory_space=pl.ANY),   # window table
              blk(dw), blk(k), blk(k)]
  inputs = [ind2d, draws_p, rand_p, large_p]
  out_shape = [jax.ShapeDtypeStruct((bp, k), jnp.int32),
               jax.ShapeDtypeStruct((bp, k), jnp.int32)]
  out_specs = [blk(k), blk(k)]
  scalars = [unit_row, offm, deg_p]
  if gns:
    scalars.append(jnp.zeros((bp,), jnp.int32).at[:b].set(reqrow))
    in_specs.append(pl.BlockSpec(bits2d.shape,
                                 lambda t, *refs: (0, 0),
                                 memory_space=pltpu.VMEM))
    inputs.append(bits2d)
    out_shape.append(jax.ShapeDtypeStruct((bp, k), jnp.float32))
    out_specs.append(blk(k))

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=n_scalar,
      grid=(bp // tile,),
      in_specs=in_specs,
      out_specs=out_specs,
      scratch_shapes=[pltpu.VMEM((tile, 2 * SUBLANES, LANES),
                                 ind2d.dtype),
                      pltpu.SemaphoreType.DMA((tile,))],
  )
  outs = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=tuple(out_shape),
      interpret=interpret,
  )(*scalars, *inputs)
  return tuple(o[:b] for o in outs)


def sample_one_hop_fused(
    indptr: jax.Array,
    indices: jax.Array,
    seeds: jax.Array,
    k: int,
    key: jax.Array,
    edge_ids: Optional[jax.Array] = None,
    *,
    bits=None,
    boost: float = 0.0,
    req: Optional[jax.Array] = None,
    window: Optional[int] = None,
    with_edge_ids: bool = False,
    sort_locality: bool = True,
    interpret: Optional[bool] = None,
    tile: int = _TILE,
    table: Optional[Tuple[jax.Array, int]] = None,
) -> OneHopResult:
  """Fused-kernel twin of `sample_one_hop` (``bits=None``) /
  `sample_one_hop_gns` (``bits`` set) — same contract, bit-identical
  outputs.  Callers qualify the shape with `fused_sample_supported`
  first; this function assumes a qualified call.

  Args:
    table: prebuilt `prepare_window_table(indices)` — pass it on
      repeated calls so the O(E) repack is paid once per graph.
  """
  if interpret is None:
    interpret = _interpret_default()
  gns = bits is not None
  if sort_locality and seeds.shape[0] > 1:
    big = jnp.iinfo(seeds.dtype).max
    order = jnp.argsort(jnp.where(seeds >= 0, seeds, big))
    res = sample_one_hop_fused(
        indptr, indices, seeds[order], k, key, edge_ids, bits=bits,
        boost=boost,
        req=(req[order] if req is not None else None),
        window=window, with_edge_ids=with_edge_ids,
        sort_locality=False, interpret=interpret, tile=tile,
        table=table)
    inv = jnp.argsort(order)
    return OneHopResult(
        nbrs=res.nbrs[inv], mask=res.mask[inv],
        eids=res.eids[inv] if res.eids is not None else None,
        weights=(res.weights[inv] if res.weights is not None
                 else None))

  num_edges = indices.shape[0]
  b = seeds.shape[0]
  w = window if window is not None else default_window(k)
  slot = jnp.arange(k, dtype=jnp.int32)

  valid_seed = seeds >= 0
  s = jnp.where(valid_seed, seeds, 0)
  start = indptr[s]
  deg = (indptr[s + 1] - start).astype(jnp.int32)
  deg = jnp.where(valid_seed, deg, 0)
  mask = slot[None, :] < jnp.minimum(deg, k)[:, None]

  # identical key discipline to the XLA kernels: k_rand feeds the
  # with-replacement arm, k_win the window arm — same shapes, same
  # order, so the fused path consumes the very same draws
  k_rand, k_win = jax.random.split(key)
  u = jax.random.uniform(k_rand, (b, k))
  rand_off = jnp.minimum((u * deg[:, None]).astype(jnp.int32),
                         jnp.maximum(deg - 1, 0)[:, None])
  if gns:
    draws = jax.random.uniform(k_win, (b, k))
  else:
    draws = jax.random.gumbel(k_win, (b, w), dtype=jnp.float32)

  # the deg > W hub arm reads beyond the two DMA'd units; its O(B·k)
  # gather stays XLA (compacted positions, not the window)
  large_pos = jnp.clip(start[:, None] + rand_off, 0,
                       max(num_edges - 1, 0))
  large_vals = indices[large_pos].astype(jnp.int32)

  ind2d, e = table if table is not None else prepare_window_table(
      indices)
  if gns:
    tbl2d = bits_table(bits)
    if is_per_requester(bits):
      if req is None:
        raise ValueError('per-requester bitmask needs req')
      reqrow = _bits_row(bits, req)
    else:
      reqrow = jnp.zeros((b,), jnp.int32)
  else:
    tbl2d = jnp.zeros((1, 1), jnp.uint8)
    reqrow = jnp.zeros((b,), jnp.int32)

  outs = _fused_draw(ind2d, start, deg, draws, rand_off,
                     large_vals, reqrow, tbl2d, e=int(e), k=int(k),
                     w=int(w), tile=int(tile), boost=float(boost),
                     gns=gns, interpret=bool(interpret))
  if gns:
    val, off, iw = outs
  else:
    val, off = outs
    iw = None

  pos = jnp.clip(start[:, None] + off, 0, max(num_edges - 1, 0))
  nbrs = jnp.where(mask, val, INVALID_ID)
  eids = None
  if with_edge_ids:
    if edge_ids is None:
      eids = jnp.where(mask, pos, INVALID_ID)
    else:
      eids = jnp.where(mask, edge_ids[pos], INVALID_ID)
  weights = None
  if gns:
    medium = ((deg > k) & (deg <= w))[:, None]
    weights = jnp.where(mask,
                        jnp.where(medium, iw, 1.0).astype(jnp.float32),
                        0.0)
  return OneHopResult(nbrs=nbrs, mask=mask, eids=eids,
                      weights=weights)


def _bits_row(bits, req: jax.Array) -> jax.Array:
  """Resolve per-seed table rows for the kernel: the dedup tuple maps
  requester -> shared row; a replicated 2-D stack maps identically."""
  if isinstance(bits, tuple):
    tbl, row_index = bits
    row = jnp.clip(req, 0, row_index.shape[0] - 1).astype(jnp.int32)
    return row_index[row].astype(jnp.int32)
  return jnp.clip(req, 0, bits.shape[0] - 1).astype(jnp.int32)


def sample_one_hop_auto(
    indptr: jax.Array,
    indices: jax.Array,
    seeds: jax.Array,
    k: int,
    key: jax.Array,
    edge_ids: Optional[jax.Array] = None,
    *,
    bits=None,
    boost: Optional[float] = None,
    req: Optional[jax.Array] = None,
    window: Optional[int] = None,
    with_edge_ids: bool = False,
    replace: bool = False,
    sort_locality: bool = True,
    table: Optional[Tuple[jax.Array, int]] = None,
    use_fused: Optional[bool] = None,
) -> OneHopResult:
  """THE sampling dispatcher: fused Pallas kernel when
  ``GLT_PALLAS_SAMPLE`` is on and the shape qualifies, else the XLA
  kernels — value-identical either way, so flipping the knob never
  changes results, only the lowering.  Dispatch resolves at trace
  time (jitted callers bake the choice per compile — the
  ``pallas.dispatch``/``pallas.fallback`` event marks which, once
  per compile, the `gns.bias` build-time-event precedent).

  ``bits=None`` selects the uniform kernel; otherwise the GNS-biased
  kernel with ``boost`` (env-resolved when None) and the optional
  per-requester ``req`` rows.
  """
  gns = bits is not None
  bst = resolve_boost(boost) if gns else 0.0
  fused = fused_sample_enabled() if use_fused is None else bool(
      use_fused)
  reason = None
  if fused:
    reason = fused_sample_supported(
        int(seeds.shape[0]), int(k), window, indices.dtype,
        bits=bits, replace=replace,
        num_edges=int(indices.shape[0]))
    if reason is None:
      # an opted-in, shape-qualified kernel that fails to trace or
      # compile RAISES: a caught failure would turn every
      # kernel-vs-twin comparison into twin-vs-twin without a word
      out = sample_one_hop_fused(
          indptr, indices, seeds, k, key, edge_ids, bits=bits,
          boost=bst, req=req, window=window,
          with_edge_ids=with_edge_ids,
          sort_locality=sort_locality, table=table)
      _emit('pallas.dispatch', kernel='fused_sample',
            mode=('gns' if gns else 'uniform'),
            batch=int(seeds.shape[0]), k=int(k))
      return out
  if fused:
    _emit('pallas.fallback', kernel='fused_sample', reason=reason,
          batch=int(seeds.shape[0]), k=int(k))
  if gns:
    return sample_one_hop_gns(
        indptr, indices, seeds, k, key, bits, bst, edge_ids,
        req=req, window=window, with_edge_ids=with_edge_ids,
        sort_locality=sort_locality)
  return sample_one_hop(
      indptr, indices, seeds, k, key, edge_ids, window=window,
      with_edge_ids=with_edge_ids, replace=replace,
      sort_locality=sort_locality)
