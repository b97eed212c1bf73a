"""Node-wise mini-batch loader.

Counterpart of reference `loader/node_loader.py:27-113` (``NodeLoader``):
iterate seed ids in (optionally shuffled) batches, run the sampler, and
collate features/labels into a `Batch` pytree.  Where the reference
leans on `torch.utils.data.DataLoader` for seed batching, the TPU
version batches on the host with numpy and **pads the tail batch to the
static batch size** so every step reuses one compiled program.
"""
from __future__ import annotations

from typing import Iterator, Optional

import numpy as np

from ..data.dataset import Dataset
from ..sampler.base import BaseSampler, NodeSamplerInput
from ..utils.padding import INVALID_ID, pad_1d
from ..telemetry.spans import span
from ..utils.profiling import metrics
from .prefetch import PrefetchingLoader
from .transform import Batch, collate


class SeedBatcher:
  """Host-side seed iterator: shuffle, slice, pad to static size.

  ``seeds`` may be ``[E]`` node ids or ``[E, K]`` rows (link-mode
  (src, dst[, label]) triples); shuffling/slicing is along axis 0 and
  padding fills whole rows with INVALID_ID."""

  def __init__(self, seeds: np.ndarray, batch_size: int,
               shuffle: bool = False, drop_last: bool = False,
               seed: Optional[int] = None):
    seeds = np.asarray(seeds)
    self.seeds = seeds if seeds.ndim > 1 else seeds.reshape(-1)
    self.batch_size = int(batch_size)
    self.shuffle = shuffle
    self.drop_last = drop_last
    self._rng = np.random.default_rng(seed)
    self.epochs_started = 0
    self._epoch_start_rng = None   # packed rng state at last __iter__

  def __len__(self) -> int:
    n = len(self.seeds)
    if self.drop_last:
      return n // self.batch_size
    return -(-n // self.batch_size)

  def __iter__(self):
    """Each epoch is a PRIVATE iterator (own order, own position):
    an abandoned consumer — e.g. an orphaned prefetch worker — can
    never steal batches from a later epoch."""
    from ..utils.checkpoint import pack_rng_state
    # epoch-START rng snapshot: a mid-epoch resume must re-draw THIS
    # epoch's permutation, which requires the state BEFORE the draw
    self._epoch_start_rng = pack_rng_state(self._rng)
    self.epochs_started += 1
    n = len(self.seeds)
    order = (self._rng.permutation(n) if self.shuffle
             else np.arange(n))
    return self._epoch(order)

  # -- DataPlaneState (utils.checkpoint) ----------------------------------
  def state_dict(self) -> dict:
    """Cursor + RNG capture: ``rng`` is the CURRENT stream (epoch-
    boundary resume point) and ``epoch_rng`` the state at the last
    epoch's start (mid-epoch resume re-draws that epoch's permutation
    byte-identically)."""
    from ..utils.checkpoint import pack_rng_state
    return {'rng': pack_rng_state(self._rng),
            'epoch_rng': (self._epoch_start_rng
                          if self._epoch_start_rng is not None
                          else pack_rng_state(self._rng)),
            'epochs_started': self.epochs_started}

  def load_state_dict(self, state: dict, mid_epoch: bool = False
                      ) -> None:
    """``mid_epoch=True`` rewinds the RNG to the interrupted epoch's
    START (the next ``__iter__`` re-draws the same permutation) and
    rolls the epoch counter back so that re-draw is not double-
    counted; False resumes at the epoch boundary."""
    from ..utils.checkpoint import restore_rng_state
    self.epochs_started = int(np.asarray(state['epochs_started']))
    if mid_epoch:
      restore_rng_state(self._rng, state['epoch_rng'])
      self.epochs_started = max(self.epochs_started - 1, 0)
    else:
      restore_rng_state(self._rng, state['rng'])

  def _epoch(self, order: np.ndarray):
    n = len(self.seeds)
    pos = 0
    while pos < n:
      end = pos + self.batch_size
      if end > n and self.drop_last:
        return
      batch = self.seeds[order[pos:end]].astype(np.int32)
      pos = end
      if len(batch) < self.batch_size:
        if batch.ndim > 1:
          pad = np.full((self.batch_size - len(batch),) + batch.shape[1:],
                        INVALID_ID, batch.dtype)
          batch = np.concatenate([batch, pad])
        else:
          batch = pad_1d(batch, self.batch_size, INVALID_ID)
      yield batch


class NodeLoader(PrefetchingLoader):
  """Base loader: seeds → sampler → collate.

  Args:
    data: the `Dataset` (graph + features + labels).
    sampler: any `BaseSampler` with ``sample_from_nodes``.
    input_nodes: ``[N]`` seed ids (e.g. the train split).
    batch_size / shuffle / drop_last: epoch iteration controls.
    seed: shuffling seed.
    prefetch: batches prepared ahead on a worker thread (0 = off;
      2 = double buffering — overlaps the next batch's host-side
      sampling + cold-tier gather + transfer dispatch with the current
      device step; see `loader.prefetch.PrefetchIterator`).
  """

  def __init__(self, data: Dataset, sampler: BaseSampler, input_nodes,
               batch_size: int = 1, shuffle: bool = False,
               drop_last: bool = False, seed: Optional[int] = None,
               prefetch: int = 0, **kwargs):
    self.prefetch = int(prefetch)
    self.data = data
    self.sampler = sampler
    self.input_type = None
    if isinstance(input_nodes, tuple) and isinstance(input_nodes[0], str):
      # Hetero seeds: (node_type, ids) — reference `InputNodes`
      # (`typing.py:83`).
      self.input_type, input_nodes = input_nodes
    input_nodes = np.asarray(input_nodes)
    if input_nodes.dtype == np.bool_:
      input_nodes = np.nonzero(input_nodes)[0]
    self._batcher = SeedBatcher(input_nodes, batch_size, shuffle, drop_last,
                                seed)
    self.batch_size = int(batch_size)

  def __len__(self) -> int:
    return len(self._batcher)

  def _produce(self, seed_iter) -> Batch:
    seeds = next(seed_iter)
    with span('loader.sample'):
      out = self.sampler.sample_from_nodes(
          NodeSamplerInput(node=seeds, input_type=self.input_type))
    with span('loader.collate'):
      batch = self._collate_fn(out)
    metrics.inc('loader.batches')
    metrics.inc('loader.seeds', int((seeds >= 0).sum()))
    return batch

  def _collate_fn(self, out):
    """Gather features/labels for sampled nodes and build the batch
    (reference `loader/node_loader.py:85-113`)."""
    return collate(self.data, out)
