"""Pallas CSR neighbor-window gather — the aligned-overfetch experiment.

The neighbor sampler's hot memory access is the ``[B, W]`` window
gather ``indices[indptr[seed] + 0..W)`` feeding Gumbel top-k
(`ops/neighbor.py` medium-degree path; the role of the reference's
reservoir read loop, `csrc/cuda/random_sampler.cu:58-108`).  XLA
lowers it to a general element gather.  Mosaic cannot DMA-slice a 1-D
array at arbitrary offsets, and HBM slices must respect the int32
(8, 128) tiling — so the DMA alternative is an ALIGNED OVERFETCH:
view ``indices`` as ``[R, 128]`` lanes, DMA the TWO 4 KB-aligned
(8, 128) units covering each seed's window into VMEM (8 KB per seed),
and cut the exact ``[w]`` slice with lane+sublane rotates (dynamic
slice does not lower in Mosaic; dynamic rotates do).

On one v5e (PR 21 bring-up, nothing timed) the kernel compiles and
matches `xla_window_gather` on the products-scale CSR at B=1024/w=120
and B=15360/w=80; at B=153600/w=64 the compiler refuses it — its two
``[B]`` scalar-prefetch vectors need 1.17 MB of the 1 MB SMEM — and
this function has no guard for that, so the compiler's message is what
the caller sees.  Its speed against `xla_window_gather` is not
measured on the chip (ROADMAP S3, D2).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: int32 HBM tiling unit: 8 sublanes x 128 lanes = 1024 elems = 4 KB.
UNIT = 1024
LANES = 128
SUBLANES = 8

_TILE = 16

#: max window width: a w <= 128 window spans <= 2 sublane rows, always
#: inside the two DMA'd units.
MAX_W = LANES


def prepare_window_table(indices: jax.Array) -> Tuple[jax.Array, int]:
  """One-time repack of a 1-D CSR column array into the ``[R, 128]``
  DMA-able layout (padded so the 2-unit window always fits).  Build it
  ONCE per graph: the repack touches all E elements and must never sit
  on the per-batch path (or in a kernel timing loop).
  Returns ``(ind2d, e)``."""
  e = indices.shape[0]
  rows = (-(-e // UNIT) + 2) * SUBLANES
  fill = indices[-1] if e else jnp.zeros((), indices.dtype)
  ind2d = jnp.concatenate(
      [indices, jnp.full((rows * LANES - e,), fill,
                         indices.dtype)]).reshape(rows, LANES)
  return ind2d, e


def csr_window_gather(indices: jax.Array, starts: jax.Array, w: int, *,
                      tile: int = _TILE,
                      interpret: Optional[bool] = None,
                      table: Optional[Tuple[jax.Array, int]] = None
                      ) -> jax.Array:
  """``out[i, j] = indices[starts[i] + j]`` for ``j < w`` via aligned
  unit DMA (positions past the array read the pad tail; callers mask
  by degree exactly like the XLA path).

  Args:
    indices: ``[E]`` int32 CSR column array.
    starts: ``[B]`` window start positions (``indptr[seeds]``).
    w: static window width, ``<= 128``.
    table: prebuilt `prepare_window_table` output — pass it on
      repeated calls so the O(E) repack is paid once per graph.
  """
  assert w <= MAX_W, (w, MAX_W)
  if interpret is None:
    interpret = jax.default_backend() != 'tpu'
  ind2d, e = table if table is not None else prepare_window_table(indices)
  starts = jnp.clip(starts.astype(jnp.int32), 0, max(e - 1, 0))
  return _window_dma(ind2d, starts, w=int(w), tile=int(tile),
                     interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=('w', 'tile', 'interpret'))
def _window_dma(ind2d: jax.Array, starts: jax.Array, *, w: int,
                tile: int, interpret: bool) -> jax.Array:
  b = starts.shape[0]
  bp = -(-b // tile) * tile
  starts_p = jnp.zeros((bp,), jnp.int32).at[:b].set(starts)
  unit_row = starts_p // UNIT * SUBLANES    # 8-aligned DMA start row
  offm = starts_p % UNIT                    # flat offset inside 2 units

  def kernel(row_ref, off_ref, tbl_ref, out_ref, scratch, sems):
    t = pl.program_id(0)
    for i in range(tile):
      r = row_ref[t * tile + i]
      pltpu.make_async_copy(tbl_ref.at[pl.ds(r, 2 * SUBLANES)],
                            scratch.at[i], sems.at[i]).start()
    for i in range(tile):
      r = row_ref[t * tile + i]
      pltpu.make_async_copy(tbl_ref.at[pl.ds(r, 2 * SUBLANES)],
                            scratch.at[i], sems.at[i]).wait()
      off = off_ref[t * tile + i]
      r0 = off // LANES
      c0 = off % LANES
      val = scratch[i]                       # [16, 128]
      rot = pltpu.roll(val, -c0, 1)          # lane rotate
      rot = pltpu.roll(rot, -r0, 0)          # sublane rotate
      # out[j] = val[r0 + (j >= 128 - c0), (c0 + j) % 128]
      lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
      take0 = lane < (LANES - c0)
      out_ref[pl.ds(i, 1), :] = jnp.where(take0, rot[0:1, :w],
                                          rot[1:2, :w])

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=2,
      grid=(bp // tile,),
      in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
      out_specs=pl.BlockSpec(
          (tile, w), lambda t, row_ref, off_ref: (t, 0),
          memory_space=pltpu.VMEM),
      scratch_shapes=[pltpu.VMEM((tile, 2 * SUBLANES, LANES),
                                 ind2d.dtype),
                      pltpu.SemaphoreType.DMA((tile,))],
  )
  out = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((bp, w), ind2d.dtype),
      interpret=interpret,
  )(unit_row, offm, ind2d)
  return out[:b]


@functools.partial(jax.jit, static_argnames=('w',))
def xla_window_gather(indices: jax.Array, starts: jax.Array,
                      w: int) -> jax.Array:
  """The sampler's current window access, isolated: the kernel's
  reference twin."""
  e = indices.shape[0]
  pos = jnp.clip(starts[:, None].astype(jnp.int32)
                 + jnp.arange(w, dtype=jnp.int32)[None, :],
                 0, max(e - 1, 0))
  return indices[pos]
