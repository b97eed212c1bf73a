"""Peaks, and the work a step needs, counted from shapes and counts.

Nothing here looks at which kernel does the work: a later PR that
swaps a kernel keeps the same numerator.  Matmul FLOPs only (2 per
multiply-add); the mean aggregation's adds are left out, as the usual
model-FLOPs convention does.
"""
from __future__ import annotations

#: published peaks per chip, keyed by `jax.Device.device_kind`
PEAKS = {
    'TPU v5 lite': dict(
        flops_per_s=197e12,        # bf16
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
               'bf16, 16 GB HBM2e at 819 GB/s'),
}


def peaks(device_kind: str) -> dict:
  if device_kind not in PEAKS:
    raise KeyError(
        f'chipbench: no published peak for device kind {device_kind!r}; '
        f'known: {sorted(PEAKS)}')
  return PEAKS[device_kind]


def _layer_flops(rows_per_layer, dims):
  """Forward + backward matmul FLOPs of a GraphSAGE stack: each layer
  multiplies ``rows`` by a self and a neighbour ``[din, dout]``
  weight.  Backward needs the weight gradient in every layer and the
  input gradient in every layer but the first (features take none)."""
  total = 0
  for l, (rows, (din, dout)) in enumerate(zip(rows_per_layer, dims)):
    fwd = 2 * 2 * rows * din * dout
    total += fwd * (2 if l == 0 else 3)
  return total


def tree_step_flops(level_counts, dims) -> int:
  """``level_counts[t]``: valid slots of tree level ``t`` (seeds
  first).  Layer ``l`` of an L-layer stack computes levels
  ``0..L-1-l``."""
  depth = len(dims)
  rows = [sum(level_counts[:depth - l]) for l in range(depth)]
  return _layer_flops(rows, dims)


def subgraph_step_flops(hop_counts, dims) -> int:
  """``hop_counts[h]``: nodes first reached at hop ``h`` (seeds
  first).  Only the seeds' logits are trained on, so layer ``l``
  needs the nodes within ``L-1-l`` hops — what a program computes
  beyond that is not counted."""
  depth = len(dims)
  rows = [sum(hop_counts[:depth - l]) for l in range(depth)]
  return _layer_flops(rows, dims)


def sample_bytes(frontier_counts, drawn_counts, id_bytes: int = 4) -> int:
  """Bytes a multi-hop draw has to move: per frontier node its two
  row pointers, per drawn neighbour its id read and written."""
  return (2 * id_bytes * sum(frontier_counts)
          + 2 * id_bytes * sum(drawn_counts))


def gather_bytes(rows: int, row_bytes: int, id_bytes: int = 4) -> int:
  """Bytes a feature gather has to move: each id read, each row read
  and written once."""
  return rows * (id_bytes + 2 * row_bytes)


def share(work: float, seconds: float, peak_per_s: float) -> float:
  """Percent of a peak rate: ``work`` done in ``seconds``."""
  return 100.0 * work / seconds / peak_per_s
