"""Static-shape padding helpers — the backbone of the TPU design.

XLA traces a program once per shape; the reference's ragged outputs
(variable neighbor counts, growing unique-node sets) become fixed
capacities with validity masks here.  These helpers centralize the
pad/mask/bucket conventions used by every op.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

#: Sentinel for an invalid/padded node or edge id.
INVALID_ID = -1


#: Lanes of a TPU vector register: the minor tile of every 2-D layout.
LANES = 128

#: The widest row a TPU row gather reads from a large table's default
#: (column-major) layout without copying the table first: compiled for
#: a described v5e, float32, bfloat16 and int32 tables of 9.8 M rows
#: are gathered in place at 1-56 columns (50 M rows too) and copied
#: whole at 57-127 (`tests/test_feature_layout.py` holds 56, 57, 63
#: and 100).  A table of 100 k rows is copied at any width, which costs
#: little at that size.
GATHERED_IN_PLACE = 56


def round_up(x: int, multiple: int) -> int:
  return -(-int(x) // int(multiple)) * int(multiple)


def lane_width(d: int, dtype) -> int:
  """The row width a ``d``-column device table of ``dtype`` is stored
  at: ``d`` rounded up to a lane multiple for 32- and 16-bit dtypes
  wider than `GATHERED_IN_PLACE` columns, else ``d`` (so ``d`` itself
  wherever it already is a multiple).

  Why: a TPU lays a 2-D array whose row width is no lane multiple out
  column-major, and a row gather then copies the whole table into a
  row-major temporary, rows padded to 128 lanes, on every call
  (``f32[9796116,100]``: 14 ms and 5.5 GB of temporaries a call on a
  v5e).  A table stored ``[N, lane_width(D)]`` is row-major by default,
  and its first ``D`` columns are a bitcast of it, which the gather
  reads in place.  Narrower rows are left alone: the compiler gathers
  them from the column-major layout without a table copy, and padding
  would multiply their bytes by more than two."""
  d = int(d)
  if np.dtype(dtype).itemsize in (2, 4) and d > GATHERED_IN_PLACE:
    return round_up(d, LANES)
  return d


def next_power_of_two(x: int) -> int:
  if x <= 1:
    return 1
  return 1 << (int(x) - 1).bit_length()


def pad_1d(arr: np.ndarray, size: int, fill=INVALID_ID,
           strict: Optional[bool] = None) -> np.ndarray:
  """Pad (or truncate) a host 1-D array to a static size.

  Truncation that cuts NON-fill entries is a capacity bug in the
  caller, not routine padding — it emits a ``padding.truncate``
  flight-recorder event so the loss surfaces instead of vanishing,
  and raises when ``strict`` is True (default: env
  ``GLT_STRICT_PADDING=1``).
  """
  import os
  arr = np.asarray(arr)
  if len(arr) > size:
    tail = arr[size:]
    dropped = int((tail != fill).sum()) if tail.size else 0
    if dropped:
      from ..telemetry.recorder import recorder
      recorder.emit('padding.truncate', requested=int(len(arr)),
                    size=int(size), dropped=dropped)
      if strict or (strict is None
                    and os.environ.get('GLT_STRICT_PADDING') == '1'):
        raise ValueError(
            f'pad_1d would truncate {dropped} valid entries '
            f'({len(arr)} -> {size}); the caller undersized a static '
            'capacity')
  out = np.full((size,), fill, dtype=arr.dtype)
  n = min(len(arr), size)
  out[:n] = arr[:n]
  return out


def bucket_size(n: int, buckets: Optional[Sequence[int]] = None,
                multiple: int = 128) -> int:
  """Pick a padded size for `n`: smallest bucket >= n, or round up to a
  lane multiple.  Bucketing bounds the number of distinct compiled
  programs when batch tails vary."""
  if buckets:
    for b in sorted(buckets):
      if n <= b:
        return int(b)
  return round_up(max(n, 1), multiple)


def max_sampled_nodes(batch_size: int, num_neighbors: Sequence[int]) -> int:
  """Worst-case unique-node capacity of a multi-hop sample.

  The reference computes the same bound to size its inducer
  (`sampler/neighbor_sampler.py:595-612`); here it fixes the static
  shape of the relabeled node set.
  """
  total = batch_size
  frontier = batch_size
  for k in num_neighbors:
    frontier = frontier * int(k)
    total += frontier
  return total


def max_sampled_edges(batch_size: int, num_neighbors: Sequence[int]) -> int:
  """Worst-case sampled-edge capacity of a multi-hop sample."""
  total = 0
  frontier = batch_size
  for k in num_neighbors:
    frontier = frontier * int(k)
    total += frontier
  return total
