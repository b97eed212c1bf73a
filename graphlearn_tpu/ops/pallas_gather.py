"""Pallas TPU gather kernel — the feature-store HBM row-gather primitive.

TPU-native counterpart of the reference's per-row warp gather
``GatherTensorKernel`` (`csrc/cuda/unified_tensor.cu:35-96`): on GPU one
32-lane warp copies one feature row from wherever it lives (HBM / peer
GPU / pinned host); on TPU the analog is a per-row **async DMA**
HBM→VMEM issued from a Pallas kernel, ``tile`` copies in flight per
grid step.  The table stays in HBM (``memory_space=ANY``), row ids are
scalar-prefetched into SMEM so the DMA addresses are known before the
body runs, and rows stream straight into the VMEM output block.

STATUS.  Off by default (``GLT_PALLAS=1`` opts in).  On one v5e (PR 21
bring-up, nothing timed) the kernel compiles non-interpreted and is
value-identical to ``jnp.take`` on a lane-aligned ``[400k, 128]`` f32
table at 1,024 / 15,360 / 131,072 ids.  The feature store keeps its
device tier at a lane-multiple row width (`data.feature`'s layout
rule, `utils.padding.lane_width`), so a padded 32-bit tier — the
flagship's 100 columns are stored as 128 — is DMA-eligible under the
alignment rule below, and `data.feature._device_gather` takes the
kernel's rows back to the table's width.
The last speed comparison on record (round 5, one v5e, deleted with
the round's logs in PR 21) had XLA's row gather ahead of this per-row
DMA; nothing has been measured on today's code, and ROADMAP S2/D2
decide whether the kernel stays.  The remote-chip variant of the
per-row DMA — owners pushing requested rows straight into requester
buffers via `make_async_remote_copy` — is `parallel/rdma_gather.py`
(interpret-validated only; it needs >= 2 chips).

A streaming-select kernel (stream the covering range, extract wanted
rows in VMEM) would be the way past a per-row issue bound, but Mosaic
rejected every extraction formulation tried: `jnp.take` on a VMEM
block (shape-mismatch on lowering), `take_along_axis` (internal
compiler error), per-row dynamic VMEM load/store in a fori_loop
(internal compiler error).

Constraints discovered on real hardware (Mosaic tiling rules):
  * Row DMA slices must be lane-aligned: ``D % 128 == 0`` for f32/i32.
    Unaligned tables take the XLA gather (a documented shape rule, so
    no padding is forced on callers).
  * bf16 rows cannot be row-sliced at all (packed (16,128)(2,1)
    sublane tiling) — bf16 tables always take the XLA path.
  * 1-D arrays tile at 1024 elements, so *CSR neighbor-window* gathers
    at arbitrary ``indptr`` offsets are not DMA-able without a 4KB+
    aligned overfetch per seed (`ops/pallas_window.py`: two (8,128)
    units = 8 KB per seed, lane+sublane-rotate extraction — a 16x
    inherent overfetch, 8 KB moved per 512 B used).  Sampling stays on
    XLA until a kernel wins a chip measurement; a sub-4KB-aligned DMA
    primitive would be the thing to revisit.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.padding import round_up

# Rows gathered per grid step == async copies in flight.
_TILE = 32

#: Max ids the DMA kernel accepts: the id vector is SCALAR-PREFETCHED
#: into SMEM (1 MB on v5e), so ``4 * B`` bytes must fit with headroom
#: for the grid machinery — discovered the hard way at B=2^20 ids
#: ("Allocation (size=4194304) would exceed memory (size=1048576)",
#: space=smem).  Products-scale collation gathers ~938k ids, so ANY
#: lane-aligned table would have crashed here without this guard;
#: bigger gathers take the XLA take.  B = 2^17 compiles and runs on a
#: v5e (PR 21).
_MAX_DMA_IDS = 1 << 17


def pallas_enabled() -> bool:
  """Use the Pallas per-row DMA gather?  Default: NO — XLA's gather
  is the default everywhere (module docstring, STATUS);
  ``GLT_PALLAS=1`` opts the DMA kernel in (on-TPU, or interpret-mode
  off-TPU for debugging).
  """
  return os.environ.get('GLT_PALLAS', '').strip().lower() in (
      '1', 'true', 'on', 'yes')


def _interpret_default() -> bool:
  return jax.default_backend() != 'tpu'


def _dma_supported(dtype) -> bool:
  """Row-sliceable dtypes: 32-bit (tiling (8,128), 1-row slices OK)."""
  return jnp.dtype(dtype).itemsize == 4


def gather_rows(table: jax.Array, idx: jax.Array, *,
                tile: int = _TILE,
                interpret: Optional[bool] = None) -> jax.Array:
  """Gather ``table[idx]`` rows via per-row async DMA.

  Callers use it unconditionally: it falls back to ``jnp.take`` when
  Pallas is disabled (:func:`pallas_enabled`), the table layout is
  not DMA-able (unaligned ``D``, sub-32-bit dtype), or the id vector
  exceeds the SMEM scalar-prefetch budget (`_MAX_DMA_IDS`).
  Out-of-range ids are clamped to the last row, matching
  ``jnp.take``'s TPU semantics.

  The env flag is re-read on every call (this plain wrapper dispatches
  to jitted implementations, so ``GLT_PALLAS=0`` works mid-process as
  the kill-switch it documents).

  Args:
    table: ``[N, D]`` HBM-resident array.
    idx: ``[B]`` int32 row ids (callers mask invalid rows after).
    tile: rows per grid step (DMAs in flight).
    interpret: force the kernel through the Pallas interpreter
      (tests); ``None`` = auto (off-TPU backends interpret).
  Returns:
    ``[B, D]`` gathered rows.
  """
  if interpret is None:
    if not pallas_enabled():
      return _xla_take(table, idx)
    interpret = _interpret_default()
  d = table.shape[1]
  if not interpret and (d % 128 != 0 or not _dma_supported(table.dtype)
                        or idx.shape[0] > _MAX_DMA_IDS):
    return _xla_take(table, idx)
  return _gather_rows_dma(table, idx, tile=tile, interpret=interpret)


@jax.jit
def _xla_take(table: jax.Array, idx: jax.Array) -> jax.Array:
  return jnp.take(table, idx.astype(jnp.int32), axis=0)


@functools.partial(jax.jit, static_argnames=('tile', 'interpret'))
def _gather_rows_dma(table: jax.Array, idx: jax.Array, *,
                     tile: int, interpret: bool) -> jax.Array:
  b = idx.shape[0]
  d = table.shape[1]
  bp = round_up(b, tile)
  idx_c = jnp.clip(idx.astype(jnp.int32), 0, table.shape[0] - 1)
  idx_p = jnp.zeros((bp,), jnp.int32).at[:b].set(idx_c)

  def kernel(idx_ref, table_ref, out_ref, sems):
    t = pl.program_id(0)
    for i in range(tile):
      r = idx_ref[t * tile + i]
      pltpu.make_async_copy(
          table_ref.at[r], out_ref.at[i], sems.at[i]).start()
    for i in range(tile):
      r = idx_ref[t * tile + i]
      pltpu.make_async_copy(
          table_ref.at[r], out_ref.at[i], sems.at[i]).wait()

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=1,
      grid=(bp // tile,),
      in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
      out_specs=pl.BlockSpec(
          (tile, d), lambda t, idx_ref: (t, 0), memory_space=pltpu.VMEM),
      scratch_shapes=[pltpu.SemaphoreType.DMA((tile,))],
  )
  out = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((bp, d), table.dtype),
      interpret=interpret,
  )(idx_p, table)
  return out[:b]
