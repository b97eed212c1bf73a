"""Two-tier feature store: HBM-resident hot rows + host-DRAM cold rows.

TPU-native replacement for the reference's ``UnifiedTensor``/``Feature``
stack (`csrc/cuda/unified_tensor.cu:29-96` — per-row warp gather across
{local HBM, peer-GPU HBM via NVLink, pinned host via UVA};
`data/feature.py:31-280` — split_ratio hot/cold split + DeviceGroup
sharding).  TPUs have no UVA and no per-warp gather kernel to write: the
idiomatic mapping is

  * **hot tier**: the first ``split_ratio`` fraction of rows (callers
    pre-sort by hotness, see :func:`~graphlearn_tpu.data.reorder.
    sort_by_in_degree`) lives as a `jax.Array` in device HBM; lookups
    are a single fused XLA gather feeding the MXU directly.
  * **cold tier**: remaining rows stay in TPU-VM host DRAM (numpy);
    misses are gathered on host and `device_put` once per batch —
    the explicit, async analog of the reference's UVA reads.

The reference's ``DeviceGroup`` replication/sharding across NVLink
cliques maps to sharding the hot tier over a `jax.sharding.Mesh` (see
:mod:`graphlearn_tpu.parallel`); single-device behavior is here.

**Layout rule.**  The hot tier is stored in the layout its row gather
reads: ``[rows, lane_width(D, dtype)]`` (`utils.padding.lane_width`),
the ``D`` columns zero-padded to a lane multiple once, when the tier is
placed (`_store_hot`; a ``feature.layout`` flight-recorder event says
what was stored).  A TPU lays out a 2-D array whose row width is no
lane multiple column-major, and a gather program handed one copies
the whole table into a row-major one, rows padded to 128 lanes, on
every call.  A ``[N, 128]`` array is row-major by default, and its
first 100 columns are a bitcast of it: `_device_gather`, the one place
that takes the ``D`` columns back, reads the stored rows in place, in
a per-batch program and inside a fused epoch's scan alike.  At a lane
multiple (and at rows the gather reads in place without a copy,
`utils.padding.GATHERED_IN_PLACE` columns or fewer) the tier is the
caller's own buffer.
Nothing outside this module sees the stored width (`HotTier`).  A
padded 32-bit tier is DMA-eligible for the Pallas gather under
``GLT_PALLAS=1`` (``D % 128 == 0`` after padding).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas_gather import gather_rows, pallas_enabled
from ..telemetry.recorder import recorder
from ..utils.padding import lane_width, next_power_of_two
from ..utils.profiling import layer_scope
from ..utils.tensor import convert_to_array


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=['rows'], meta_fields=['width'])
@dataclasses.dataclass(frozen=True)
class HotTier:
  """A device tier as stored: ``rows`` is ``[N, lane_width(width)]``,
  whose first ``width`` columns are the table and the rest zeros.  A
  pytree whose ``width`` is static, so it crosses a ``jit`` boundary as
  one array argument and `_device_gather` takes the table's columns
  back inside the same program."""
  rows: jax.Array
  width: int


def _first_columns(x: jax.Array, width: int) -> jax.Array:
  return x if x.shape[1] == width else x[:, :width]


@functools.partial(jax.jit, static_argnums=(1,))
def _pad_columns(x: jax.Array, width: int) -> jax.Array:
  return jnp.pad(x, ((0, 0), (0, width - x.shape[1])))


def _store_hot(x: jax.Array) -> HotTier:
  """``[N, D]`` device rows -> the stored tier (module docstring,
  layout rule): ``x`` itself where ``lane_width`` keeps ``D``, else one
  zero-padded copy.  Emits ``feature.layout``."""
  d = int(x.shape[1])
  width = lane_width(d, x.dtype)
  rows = x if width == d else _pad_columns(x, width)
  recorder.emit('feature.layout', width=d, stored_width=width,
                dtype=str(x.dtype), rows=int(x.shape[0]),
                stored_bytes=int(rows.nbytes), padded=width != d)
  return HotTier(rows, d)


@functools.partial(jax.jit, static_argnames=('use_pallas', 'part'))
def _device_gather(hot: Union[HotTier, jax.Array], ids: jax.Array,
                   id2index, *, use_pallas: bool,
                   part: Optional[str] = None) -> jax.Array:
  # `use_pallas` is part of the jit cache key so the GLT_PALLAS
  # kill-switch keeps working mid-process (resolved per call outside).
  # ``part``: which table this is where a dataset has several (the
  # node type), in the ops' scope and nowhere else.  A bare ``[N, D]``
  # table is gathered as it is: the benchmark's real-size compile of
  # the fused epoch (`tests/chipbench/test_real_size_compile.py`) hands
  # the epoch one.
  if not isinstance(hot, HotTier):
    hot = HotTier(hot, hot.shape[1])
  with layer_scope('gather', part):
    valid = ids >= 0
    idx = jnp.where(valid, ids, 0).astype(jnp.int32)
    if id2index is not None:
      idx = id2index[idx].astype(jnp.int32)
      valid = valid & (idx >= 0)
      idx = jnp.where(valid, idx, 0)
    if use_pallas:
      # whole stored rows, which a padded 32-bit tier lets the DMA
      # kernel take
      out = _first_columns(gather_rows(hot.rows, idx), hot.width)
    else:
      # the table's columns before the gather, not the gathered rows'
      # after it: the former is a bitcast, the latter a pass of its own
      # that keeps the select below out of the rows' consumer
      out = jnp.take(_first_columns(hot.rows, hot.width), idx, axis=0)
    return jnp.where(valid[:, None], out, 0)


class _DeviceFeatsShim:
  """Stand-in for ``_host_feats`` when the table was constructed from
  a device array: shape/dtype metadata come from the device tier;
  element access (rare — `host_get` and test assertions) pulls the
  table to host ONCE and caches it."""

  def __init__(self, tier: HotTier):
    self._tier = tier
    self._np = None

  shape = property(lambda self: (self._tier.rows.shape[0],
                                 self._tier.width))
  dtype = property(lambda self: self._tier.rows.dtype)
  ndim = property(lambda self: 2)

  def _pull(self) -> np.ndarray:
    if self._np is None:
      self._np = _first_columns(np.asarray(self._tier.rows),
                                self._tier.width)
    return self._np

  def __getitem__(self, key):
    return self._pull()[key]

  def __array__(self, dtype=None):
    a = self._pull()
    return a if dtype is None else a.astype(dtype)


class Feature:
  """Hot/cold split feature table addressed by global ids.

  Args:
    feature_array: ``[N, D]`` host array, rows assumed ordered
      hottest-first when ``split_ratio < 1`` (use ``sort_by_in_degree``).
    id2index: optional ``[max_id+1]`` map from global id to storage row
      (produced by hotness reordering); identity when ``None``.
    split_ratio: fraction of rows resident in device HBM.  ``1.0`` pins
      everything on device (DMA mode analog), ``0.0`` keeps everything
      on host (CPU mode analog).  The HBM tier costs ``hot_rows x
      lane_width(D, dtype) x itemsize`` bytes (module docstring, layout
      rule; `hot_bytes`): at 100 float32 columns 28 % more than the
      values, since each row is stored 128 wide.
    device: optional explicit device for the hot tier.
    dtype: optional storage dtype for the hot tier (e.g. ``bfloat16`` —
      halves HBM footprint and feeds the MXU natively).
    cold_cache_rows: HBM victim-cache budget over the cold tier
      (`data.cold_cache`): ``'auto'`` (default) sizes it to
      ``GLT_COLD_CACHE_ROWS`` or 15% of the cold rows, an int pins it,
      0 disables.  Cache hits are served by a device gather (the cold
      bytes stay in HBM across batches); only misses pay the host
      gather + transfer.  Values are byte-identical either way.
  """

  def __init__(self, feature_array, id2index: Optional[np.ndarray] = None,
               split_ratio: float = 1.0,
               device: Optional[jax.Device] = None,
               dtype=None, cold_cache_rows='auto'):
    if isinstance(feature_array, jax.Array):
      # device-native construction (tables produced on device — e.g.
      # `chip_smoke.build_dataset`): the array IS the hot tier;
      # pulling it to host just to re-upload would cost a full
      # d2h + h2d round trip of the table.
      if float(split_ratio) != 1.0:
        raise ValueError('device-resident feature input requires '
                         'split_ratio == 1.0 (a cold tier lives on '
                         'host by definition)')
      feats = feature_array if feature_array.ndim > 1 \
          else feature_array[:, None]
      self._id2index_host = (np.asarray(id2index, dtype=np.int64)
                             if id2index is not None
                             and not isinstance(id2index, jax.Array)
                             else None)
      self.split_ratio = 1.0
      self._device = device
      self._dtype = dtype
      hot = feats if dtype is None else feats.astype(dtype)
      if device is not None and device not in feats.devices():
        # an explicit device that differs from where the table lives
        # must move it — silently keeping the old placement made the
        # `device=` argument a no-op on the device-native path
        hot = jax.device_put(hot, device)
      self._hot = _store_hot(hot)
      # host reads come from the stored tier where it holds the
      # caller's values, so a caller that hands over its only reference
      # keeps one table on the device, not two
      self._host_feats = _DeviceFeatsShim(
          self._hot if hot.dtype == feats.dtype
          else HotTier(feats, feats.shape[1]))
      self._id2index_dev = (None if id2index is None
                            else jnp.asarray(id2index, jnp.int32))
      self.hot_rows = feats.shape[0]
      self._cache_rows = 0
      self._cold_cache = None
      self._pinned_cold = None
      self.cold_stats = {'lookups': 0, 'cold_lookups': 0}
      return
    feats = convert_to_array(feature_array)
    if feats.ndim == 1:
      feats = feats[:, None]
    self._host_feats = feats
    self._id2index_host = (np.asarray(id2index, dtype=np.int64)
                           if id2index is not None else None)
    self.split_ratio = float(split_ratio)
    self._device = device
    self._dtype = dtype
    self._hot = None            # HotTier of rows [0, hot_rows) (lazy)
    self._id2index_dev = None   # jax.Array (lazy)
    n = feats.shape[0]
    self.hot_rows = int(round(n * self.split_ratio))
    self.hot_rows = max(0, min(self.hot_rows, n))
    from .cold_cache import resolve_cache_rows
    # the cache only bites on the MIXED path (0 < hot_rows < n): the
    # fully-host path ships whole batches and the fully-HBM path has
    # no cold tier to cache
    self._cache_rows = (
        resolve_cache_rows(cold_cache_rows, n - self.hot_rows)
        if 0 < self.hot_rows < n else 0)
    self._cold_cache = None     # DeviceColdCache (lazy, see lazy_init)
    self._pinned_cold = None    # PinnedColdBuffer (lazy, env-gated)
    #: host-side cold accounting: lookups = valid ids per __getitem__,
    #: cold_lookups = ids past the hot tier (the cache denominator)
    self.cold_stats = {'lookups': 0, 'cold_lookups': 0}

  # -- lazy device residency (reference `Feature.lazy_init*`,
  # `data/feature.py:208-258`) -------------------------------------------
  def lazy_init(self):
    if self._hot is not None or self.hot_rows == 0:
      return
    dev = self._device or jax.devices()[0]
    hot = self._host_feats[:self.hot_rows]
    if self._dtype is not None:
      hot = hot.astype(self._dtype)
    self._hot = _store_hot(jax.device_put(hot, dev))
    if self._id2index_host is not None:
      self._id2index_dev = jax.device_put(self._id2index_host, dev)
    if self._cache_rows and self._cold_cache is None:
      from .cold_cache import DeviceColdCache
      self._cold_cache = DeviceColdCache(
          self._cache_rows, self.feature_dim, self.dtype, dev)

  @property
  def shape(self):
    return self._host_feats.shape

  @property
  def dtype(self):
    return self._dtype or self._host_feats.dtype

  @property
  def feature_dim(self) -> int:
    return self._host_feats.shape[1]

  def size(self, dim: int = 0) -> int:
    return self._host_feats.shape[dim]

  @property
  def hot_tier(self) -> Optional[HotTier]:
    """The device-resident block (rows ``[0, hot_rows)``), for callers
    that gather inside jit when the whole table is HBM-resident.

    It is the `HotTier` as stored — rows ``[hot_rows, lane_width(D)]``
    with the table's width ``D`` beside them — and not a bare array, so
    no caller is handed padded columns as if they were the table: pass
    it to `_device_gather`, which returns ``[B, D]`` rows."""
    self.lazy_init()
    return self._hot

  @property
  def hot_bytes(self) -> int:
    """Device bytes the hot tier takes as stored, ``hot_rows x
    lane_width(D, dtype) x itemsize`` — before `lazy_init` places it
    too."""
    if self._hot is not None:
      return int(self._hot.rows.nbytes)
    return (self.hot_rows * lane_width(self.feature_dim, self.dtype)
            * np.dtype(self.dtype).itemsize)

  # -- lookup -------------------------------------------------------------
  def __getitem__(self, ids) -> jax.Array:
    """Gather rows by global id onto the device (see :meth:`get`)."""
    return self.get(ids)

  def get(self, ids, scope: str = 'feature',
          part: Optional[str] = None) -> jax.Array:
    """Gather rows by global id onto the device.

    Counterpart of reference `Feature.__getitem__`
    (`data/feature.py:141-154`) → `GatherTensorKernel`.  Invalid ids
    (< 0, the padding sentinel) return zero rows, so padded batches
    flow straight into the model.

    Device-resident ids with a fully-HBM table take an all-device
    path: the reference's ids are already on-GPU likewise; a host
    round-trip here would serialize every batch on transfer latency.

    ``scope`` tags this lookup's cold-cache telemetry
    (``cache.hit``/``cache.miss``/... events): the epoch loaders use
    the default ``'feature'``; the online serving plane's per-request
    tiered path passes ``'serving'`` so a dashboard can split
    training-epoch from inference-traffic cache behavior out of one
    event stream.  Values are scope-independent (byte-identical).

    ``part`` names this table among a dataset's (a typed loader passes
    the node type): the all-device gather's ops then carry
    ``glt.gather/<part>``.
    """
    self.lazy_init()
    if (isinstance(ids, jax.Array)
        and self.hot_rows >= self._host_feats.shape[0]):
      return self._device_get(ids, part)
    if self._id2index_dev is not None and self._id2index_host is None:
      # device-native table with a device-only id2index: the host
      # remap below would silently SKIP the mapping — route host ids
      # through the all-device path instead (table is fully hot by
      # the device-native constructor's contract)
      return self._device_get(jnp.asarray(np.asarray(ids),
                                          dtype=jnp.int32), part)
    ids_host = np.asarray(ids)
    valid = ids_host >= 0
    idx = np.where(valid, ids_host, 0)
    if self._id2index_host is not None:
      idx = self._id2index_host[idx]
      valid &= idx >= 0  # partial maps hold -1 for unmapped ids
      idx = np.where(valid, idx, 0)
    d = self.feature_dim

    if self.hot_rows >= self._host_feats.shape[0]:
      # Fully HBM-resident: one device gather — per-row DMA kernel on
      # TPU under GLT_PALLAS=1 (`ops/pallas_gather.py`), fused XLA
      # gather otherwise.
      return self._hot_rows(np.where(valid, idx, -1))

    cold_sel = valid & (idx >= self.hot_rows)
    self.cold_stats['lookups'] += int(valid.sum())
    self.cold_stats['cold_lookups'] += int(cold_sel.sum())
    if self.hot_rows == 0:
      # Fully host-resident: gather on host, one transfer.
      out = np.zeros((len(ids_host), d), dtype=self._host_feats.dtype)
      out[valid] = self._host_feats[idx[valid]]
      return jnp.asarray(out if self._dtype is None
                         else out.astype(self._dtype))
    if not cold_sel.any():
      return self._hot_rows(np.where(valid, idx, -1))

    # chaos seam: the host cold tier is a service that can die
    # mid-epoch; a planned 'fail' raises here, on the batch that
    # needed it (the snapshot/resume layer turns it into a finished
    # epoch instead of a lost one)
    from ..testing import chaos
    chaos.cold_service_check('feature')
    # Mixed: device gather for hot rows; cold rows first checked
    # against the HBM victim cache (`data.cold_cache` — hits are a
    # device gather, the bytes never leave HBM); residual misses are
    # host-gathered into a COMPACT [n_miss_pad, D] buffer
    # (power-of-two padded so the number of compiled variants stays
    # logarithmic) and expanded on device by a per-row rank map.
    # Ships only the miss bytes — a full-[B, D] staging buffer or a
    # dynamic scatter is 10-200x slower (the former in transfer, the
    # latter recompiling on every batch's cold count).
    out = self._hot_rows(np.where(valid & ~cold_sel, idx, -1))
    cache = self._cold_cache
    if cache is not None:
      hit, slot = cache.lookup(idx, cold_sel)
      miss_sel = cold_sel & ~hit
    else:
      hit = slot = None
      miss_sel = cold_sel
    n_miss = int(miss_sel.sum())
    pinned = self._pinned_buffer()
    if pinned is not None:
      # r19 pinned path (ISSUE 18): the cold block already lives in
      # pinned host memory; one compiled host-compute gather replaces
      # Python np.take + per-batch transfer.  Same rows, same dtype
      # cast (paid once at build) — the output is byte-identical to
      # the compact path below.
      rel = np.where(miss_sel, idx - self.hot_rows, 0).astype(np.int32)
      cold_rows = pinned.gather(rel)
    else:
      cold_pad = next_power_of_two(n_miss)
      compact = np.zeros((cold_pad, d), dtype=self._host_feats.dtype)
      compact[:n_miss] = self._host_feats[idx[miss_sel]]
      if self._dtype is not None:
        compact = compact.astype(self._dtype)
      # rank[i] = position of row i's value in the compact buffer
      rank = np.cumsum(miss_sel) - 1
      rank = np.where(miss_sel, rank, 0).astype(np.int32)
      cold_rows = jnp.take(jnp.asarray(compact), jnp.asarray(rank),
                           axis=0)
    hot_ok = jnp.asarray(valid & ~cold_sel)[:, None]
    cold_ok = jnp.asarray(miss_sel)[:, None]
    x = jnp.where(hot_ok, out, jnp.where(cold_ok, cold_rows, 0))
    if cache is not None:
      x = cache.serve_hits(x, hit, slot)
      admits, evicts = cache.admit(x, idx, miss_sel)
      from .cold_cache import emit_cache_events
      emit_cache_events(scope, int(hit.sum()), n_miss, admits,
                        evicts)
    return x

  def _device_get(self, ids: jax.Array,
                  part: Optional[str] = None) -> jax.Array:
    """All-device gather (fully-hot tables, device ids): no host sync."""
    return _device_gather(self._hot, ids, self._id2index_dev,
                          use_pallas=pallas_enabled(), part=part)

  def _hot_rows(self, rows: np.ndarray) -> jax.Array:
    """``[B, D]`` hot-tier rows by host storage row (-1 = a zero row)."""
    return _device_gather(self._hot, jnp.asarray(rows.astype(np.int32)),
                          None, use_pallas=pallas_enabled())

  def _pinned_buffer(self):
    """The lazily built `data.cold_cache.PinnedColdBuffer` over the
    cold block, or None with the knob off — ``GLT_PALLAS_COLD`` is
    re-read per batch (kill switch), the build runs once, and a build
    that fails raises (`make_pinned_cold_buffer`)."""
    from .cold_cache import make_pinned_cold_buffer, pinned_cold_enabled
    if not pinned_cold_enabled():
      return None
    if self._pinned_cold is None:
      self._pinned_cold = make_pinned_cold_buffer(
          self._host_feats[self.hot_rows:], self.feature_dim,
          self._dtype, self._device or jax.devices()[0])
    return self._pinned_cold

  # -- DataPlaneState (utils.checkpoint): the dynamic cache only ----------
  # (the hot tier and host table are reconstructed from the dataset —
  # snapshotting gigabytes of static rows would be pure dead weight)
  def state_dict(self) -> dict:
    self.lazy_init()
    if self._cold_cache is None:
      return {'has_cache': 0}
    return {'has_cache': 1, 'cache': self._cold_cache.state_dict()}

  def load_state_dict(self, state: dict) -> None:
    self.lazy_init()
    if not int(np.asarray(state.get('has_cache', 0))):
      return
    if self._cold_cache is None:
      return                       # cache disabled this run: warmth lost
    self._cold_cache.load_state_dict(state['cache'])

  def host_get(self, ids=None) -> np.ndarray:
    """Host-side gather (reference ``Feature.cpu_get``,
    `data/feature.py:156`); full table when ``ids`` is None."""
    if ids is None:
      return self._host_feats
    ids = np.asarray(ids)
    valid = ids >= 0
    idx = np.where(valid, ids, 0)
    if self._id2index_host is not None:
      idx = self._id2index_host[idx]
      valid &= idx >= 0
      idx = np.where(valid, idx, 0)
    out = np.zeros((len(ids), self.feature_dim),
                   dtype=self._host_feats.dtype)
    out[valid] = self._host_feats[idx[valid]]
    return out

  def __repr__(self):
    return (f'Feature(shape={self._host_feats.shape}, '
            f'split_ratio={self.split_ratio}, hot_rows={self.hot_rows})')
