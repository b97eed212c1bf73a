"""Adaptive HBM victim cache over cold-tier feature rows.

The tiered store's static ``split_ratio`` slice (`sort_by_in_degree`
hot prefix) leaves every cold lookup a synchronous host gather on the
batch critical path.  PyTorch-Direct and Global Neighbor Sampling
(PAPERS.md) both show that a small dynamically-maintained device cache
plus overlapped cold access recovers most of the fully-resident
throughput.  This module is that cache, TPU-shaped:

  * **rows live in HBM** as a fixed-budget ``[C, D]`` ring; admissions
    update them with batched ``at[].set`` from rows that are already
    on device post-overlay — cached bytes NEVER round-trip through the
    host, and a hit is served by a device gather;
  * **policy lives on the host** as a CLOCK (second-chance) ring over
    the id tags: the per-batch cold-id multiset is analyzed where it
    already exists (the cold-overlay planning is host-side), so hit
    detection costs one vectorized ``searchsorted`` against a sorted
    mirror and no device sync of its own;
  * **admission is frequency-based**: candidates are ranked by their
    multiplicity in the batch's cold-id multiset (ids a batch touches
    many times are worth a slot most), and residents touched since the
    last sweep survive one eviction pass (the second-chance bit) — so
    a scan-like burst of one-touch ids cannot flush the reused set.

Three consumers share it: the single-chip `data.feature.Feature`
mixed path (`DeviceColdCache`), the mesh engines' cold overlay
(`MeshColdCache`, per-device shards), and the tiered fused epochs
(same `MeshColdCache`, served between chunk dispatches).

Knobs: ``GLT_COLD_CACHE_ROWS`` (rows per device; 0 disables,
unset/'auto' = `DEFAULT_BUDGET_FRACTION` of the cold rows).
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: 'auto' budget: fraction of the (per-partition max) cold rows kept
#: in the HBM ring.  15% matches the bench sweep's upper point and
#: keeps the cache an order of magnitude below the hot tier's spend.
DEFAULT_BUDGET_FRACTION = 0.15

#: per-admission-wave cap, as a fraction of capacity.  When the
#: batch's miss set exceeds the cache (the common steady state for a
#: beyond-HBM working set), admitting EVERY miss would churn the whole
#: ring each batch — residents never live long enough to earn hits and
#: the admission scatter dominates the overlay.  Capping the wave
#: keeps turnover bounded (a resident survives >= 1/frac waves even
#: untouched), lets the second-chance bit actually protect reused
#: rows, and cuts the per-batch plan/scatter cost by the same factor.
ADMIT_WAVE_FRACTION = 0.25

_ENV_ROWS = 'GLT_COLD_CACHE_ROWS'


def resolve_cache_rows(spec, cold_rows: int) -> int:
  """Resolve a ``cold_cache_rows`` knob: int = rows per device
  (0 disables), None/'auto' = ``GLT_COLD_CACHE_ROWS`` when set, else
  `DEFAULT_BUDGET_FRACTION` of ``cold_rows``."""
  if spec in (None, 'auto'):
    env = os.environ.get(_ENV_ROWS)
    if env is not None:
      try:
        return max(int(env), 0)
      except ValueError:
        pass
    if cold_rows <= 0:
      return 0
    return int(np.ceil(cold_rows * DEFAULT_BUDGET_FRACTION))
  return max(int(spec), 0)


class ClockShardCache:
  """CLOCK second-chance id→slot policy for ONE device shard.

  Holds only host-side metadata (tags, reference bits, the hand, the
  decayed visit-frequency sketch); the cached ROWS live in the owning
  cache's device array, addressed by the slot indices this class
  assigns.  All operations are vectorized over the batch's id arrays
  — no per-id python on the hot path.

  Admission ranking (r11): candidates are scored by the shard's
  `ops.gns.DecayedSketch` — the batch's cold-id multiset folded into
  an exponentially-decayed cross-batch visit count — instead of the
  per-batch multiset alone.  An id the stream revisits every few
  batches now outranks a one-batch burst, and the SAME sketch-selected
  residents feed the GNS sampling bias (`ops.gns.cached_set_bits`),
  so admission and sampling share one notion of "hot".  Cache
  contents never change batch bytes (PR 5's byte-identity contract),
  so the ranking change is invisible outside hit rates.
  """

  def __init__(self, capacity: int, bounds=None):
    from ..ops.gns import DecayedSketch
    self.capacity = int(capacity)
    self.ids = np.full(self.capacity, -1, np.int64)
    self.ref = np.zeros(self.capacity, np.uint8)
    self.hand = 0
    # with PartitionBook bounds attached the sketch also keeps the
    # decayed per-range visit histogram (gns.range_hotness export)
    self.sketch = DecayedSketch(bounds=bounds)
    #: bumped on every committed admission wave — consumers (the GNS
    #: bitmask refresh) rebuild derived state only when this moved
    self.version = 0
    self._sorted_ids = np.empty(0, np.int64)
    self._sorted_slots = np.empty(0, np.int32)

  @property
  def size(self) -> int:
    return len(self._sorted_ids)

  def _rebuild(self) -> None:
    occ = np.nonzero(self.ids >= 0)[0]
    order = np.argsort(self.ids[occ], kind='stable')
    self._sorted_ids = self.ids[occ][order]
    self._sorted_slots = occ[order].astype(np.int32)

  def lookup(self, ids: np.ndarray, active: Optional[np.ndarray] = None
             ) -> Tuple[np.ndarray, np.ndarray]:
    """``(hit, slot)`` for an id array of any shape; ``active`` masks
    which entries participate (e.g. the batch's cold mask).  Hits set
    the second-chance bit (the CLOCK "touch")."""
    ids = np.asarray(ids, np.int64)
    hit = np.zeros(ids.shape, bool)
    slot = np.zeros(ids.shape, np.int32)
    if self.size == 0:
      return hit, slot
    if active is not None:
      # probe only the active (cold) positions: the node table is
      # mostly hot/padding, and the searchsorted is the per-batch
      # host cost of every overlay
      sel = np.nonzero(active)
      sub = ids[sel]
      pos = np.clip(np.searchsorted(self._sorted_ids, sub), 0,
                    self.size - 1)
      h = self._sorted_ids[pos] == sub
      s = self._sorted_slots[pos]
      hit[sel] = h
      slot[sel] = np.where(h, s, 0)
      if h.any():
        self.ref[s[h]] = 1
      return hit, slot
    pos = np.clip(np.searchsorted(self._sorted_ids, ids), 0,
                  self.size - 1)
    hit = self._sorted_ids[pos] == ids
    slot = np.where(hit, self._sorted_slots[pos], 0).astype(np.int32)
    if hit.any():
      self.ref[slot[hit]] = 1
    return hit, slot

  def plan_admissions(self, cand_ids: np.ndarray,
                      cand_counts: Optional[np.ndarray] = None
                      ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Assign ring slots to (unique, not-resident) candidate ids.

    The batch's cold-id multiset (``cand_counts``) is folded into the
    shard's decayed visit-frequency sketch, and candidates are ranked
    by SKETCH score (descending) — cross-batch reuse outranks a
    one-batch burst; on a fresh sketch the ranking reduces exactly to
    the old per-batch multiset order.  Free slots fill first; the
    remainder comes from one batched CLOCK sweep: residents with a
    clear reference bit are victims in hand order, residents touched
    since the last sweep survive it (their bit is cleared — the
    second chance).  Returns ``(admitted_ids, slots, evicted)``; call
    `commit` after the device rows were written.
    """
    cand_ids = np.asarray(cand_ids, np.int64)
    if cand_ids.size == 0 or self.capacity == 0:
      return (np.empty(0, np.int64), np.empty(0, np.int32), 0)
    if cand_counts is None:
      cand_counts = np.ones(len(cand_ids), np.int64)
    self.sketch.update(cand_ids, cand_counts)
    order = np.lexsort((cand_ids, -self.sketch.score(cand_ids)))
    # bounded wave: empty slots may always fill, but EVICTING
    # admissions are capped at `ADMIT_WAVE_FRACTION` of the ring (see
    # the constant's rationale — full-ring churn earns no hits)
    n_free = int(np.count_nonzero(self.ids < 0))
    wave = max(int(self.capacity * ADMIT_WAVE_FRACTION), 1)
    cand = cand_ids[order][:min(self.capacity, n_free + wave)]
    free = np.nonzero(self.ids < 0)[0]
    n_free = min(len(free), len(cand))
    slots = [free[:n_free].astype(np.int32)]
    need = len(cand) - n_free
    evicted = 0
    if need > 0:
      sweep = (self.hand + np.arange(self.capacity)) % self.capacity
      occ = self.ids[sweep] >= 0
      fresh = self.ref[sweep] == 0
      clear = occ & fresh
      cand_pos = np.nonzero(clear)[0]
      if len(cand_pos) >= need:
        # batched CLOCK: victims are the first `need` clear-bit slots
        # in hand order; slots the hand passed over keep residency but
        # lose their bit (the second chance) — slots BEYOND the hand's
        # stop keep their bit, so reuse is only re-asserted where the
        # hand actually swept
        stop = cand_pos[need - 1]
        victims = sweep[cand_pos[:need]]
        self.ref[sweep[:stop + 1]] = 0
        self.hand = (int(sweep[stop]) + 1) % self.capacity
      else:
        # not enough clear bits in a full revolution: every slot ages
        # (the hand swept the whole ring), remainder comes from the
        # touched residents in hand order
        victims = np.concatenate([sweep[clear],
                                  sweep[occ & ~fresh]])[:need]
        self.ref[:] = 0
        if len(victims):
          self.hand = (int(victims[-1]) + 1) % self.capacity
      evicted = len(victims)
      if evicted:
        slots.append(victims.astype(np.int32))
    out_slots = np.concatenate(slots)
    return cand[:len(out_slots)], out_slots, evicted

  def commit(self, ids: np.ndarray, slots: np.ndarray) -> None:
    if len(ids):
      self.ids[slots] = ids
      self.ref[slots] = 0
      self.version += 1
    self._rebuild()

  def resident_ids(self) -> np.ndarray:
    """The current residents (sorted) — the dynamic half of the GNS
    cached set (`ops.gns.cached_set_bits`)."""
    return self._sorted_ids

  # -- DataPlaneState (utils.checkpoint): rings + the visit sketch --------
  def state_dict(self) -> dict:
    return {'ids': self.ids.copy(), 'ref': self.ref.copy(),
            'hand': self.hand, 'sketch': self.sketch.state_dict()}

  def load_state_dict(self, state: dict) -> None:
    ids = np.asarray(state['ids'], np.int64)
    if ids.shape != self.ids.shape:
      raise ValueError(
          f'cold-cache snapshot capacity {ids.shape[0]} does not match '
          f'this cache ({self.capacity}); resume with the same '
          f'GLT_COLD_CACHE_ROWS the snapshot was taken under')
    self.ids = ids
    self.ref = np.asarray(state['ref'], np.uint8).copy()
    self.hand = int(np.asarray(state['hand']))
    if 'sketch' in state:
      # pre-r11 snapshots carry no sketch: residency restores, the
      # learned visit frequencies restart cold (documented fallback)
      self.sketch.load_state_dict(state['sketch'])
    self.version += 1
    self._rebuild()


class CacheStats:
  """Flat counters shared by every cache flavor; consumers fold them
  into their own telemetry planes (the mesh samplers into
  ``exchange_stats``, the single-chip Feature into the global metrics
  registry)."""

  __slots__ = ('hits', 'misses', 'admits', 'evicts')

  def __init__(self):
    self.hits = self.misses = self.admits = self.evicts = 0

  def snapshot(self) -> dict:
    return {'hits': self.hits, 'misses': self.misses,
            'admits': self.admits, 'evicts': self.evicts}


#: scope -> backing-store keys of the four labeled live counters,
#: resolved (and registered for the /metrics rendering) once per scope
_CACHE_METRIC_KEYS: dict = {}


def _cache_metric_keys(scope: str):
  keys = _CACHE_METRIC_KEYS.get(scope)
  if keys is None:
    from ..telemetry.live import live
    labels = {'scope': scope}
    keys = _CACHE_METRIC_KEYS[scope] = (
        live.counter('cache.hits_total', labels=labels).key,
        live.counter('cache.misses_total', labels=labels).key,
        live.counter('cache.admits_total', labels=labels).key,
        live.counter('cache.evicts_total', labels=labels).key)
  return keys


def emit_cache_events(scope: str, hits: int, misses: int, admits: int,
                      evicts: int) -> None:
  """Per-overlay-batch flight-recorder events (only when the recorder
  is on; zero-count kinds are skipped so the JSONL stays signal).

  Always mirrors the counts into the live metrics vocabulary
  (``cache.*_total{scope=...}``, one lock acquisition) — the scrape
  must see cache economics even when the flight recorder is off.
  Registration goes through the live registry so the labeled
  per-scope instances render on ``/metrics`` (an instance the
  registry never saw would exist only in ``/varz``); the typed
  handles are resolved ONCE per scope (`_cache_metric_keys`), so the
  per-overlay-batch tick is a plain multi-key increment."""
  from ..utils.profiling import metrics
  hk, mk, ak, ek = _cache_metric_keys(scope)
  pairs = [(k, float(v)) for k, v in
           ((hk, hits), (mk, misses), (ak, admits), (ek, evicts))
           if v]
  if pairs:
    metrics.inc_many(pairs)
  from ..telemetry.recorder import recorder
  if not recorder.enabled:
    return
  if hits:
    recorder.emit('cache.hit', scope=scope, count=int(hits))
  if misses:
    recorder.emit('cache.miss', scope=scope, count=int(misses))
  if admits:
    recorder.emit('cache.admit', scope=scope, count=int(admits))
  if evicts:
    recorder.emit('cache.evict', scope=scope, count=int(evicts))


# -- single-device flavor (data.feature.Feature) ---------------------------

@jax.jit
def _serve_rows(x, rows_cache, hit, slot):
  """``x[i] = rows_cache[slot[i]] where hit`` — the device half of a
  cache hit (rows never leave HBM)."""
  return jnp.where(hit[:, None], rows_cache[slot], x)


@functools.partial(jax.jit, donate_argnums=(0,))
def _admit_rows(rows_cache, x, src, dst):
  """``rows_cache[dst[j]] = x[src[j]]`` — batched admission from rows
  already on device; padded entries carry ``dst == capacity`` and are
  dropped by the scatter."""
  return rows_cache.at[dst].set(x[src], mode='drop')


def _pad_pow2(n: int) -> int:
  from ..utils.padding import next_power_of_two
  return next_power_of_two(max(int(n), 1))


class DeviceColdCache:
  """Single-device victim cache: one `ClockShardCache` policy + a
  ``[C, D]`` HBM row ring + the jitted serve/admit programs.  Keys are
  the caller's choice (the Feature uses storage row indices, so the
  cache composes with ``id2index`` remaps for free)."""

  def __init__(self, capacity: int, dim: int, dtype,
               device: Optional[jax.Device] = None):
    self.policy = ClockShardCache(capacity)
    rows = jnp.zeros((max(int(capacity), 1), int(dim)), dtype)
    self.rows = (jax.device_put(rows, device) if device is not None
                 else rows)
    self.stats = CacheStats()
    # memory accounting (ISSUE 17): the row ring is the cache's whole
    # HBM bill (policy state is host-side numpy, negligible)
    from ..telemetry.memaccount import register_tier
    register_tier('cold_cache',
                  lambda r=self.rows: int(getattr(r, 'nbytes', 0)))

  @property
  def capacity(self) -> int:
    return self.policy.capacity

  def lookup(self, ids: np.ndarray,
             active: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``(hit, slot)`` over ``ids`` (ticks the hit counter); callers
    drop hits from their host gather and then `serve_hits`."""
    hit, slot = self.policy.lookup(ids, active)
    self.stats.hits += int(hit.sum())
    return hit, slot

  def serve_hits(self, x: jax.Array, hit: np.ndarray,
                 slot: np.ndarray) -> jax.Array:
    if not hit.any():
      return x
    return _serve_rows(x, self.rows, jnp.asarray(hit),
                       jnp.asarray(slot))

  def admit(self, x: jax.Array, ids: np.ndarray,
            miss: np.ndarray) -> Tuple[int, int]:
    """Admit this batch's (corrected, on-device) miss rows: dedup the
    miss multiset, rank by multiplicity, write winners into the ring
    with one padded ``at[].set``.  Returns ``(admits, evicts)``."""
    self.stats.misses += int(miss.sum())
    if not miss.any() or self.capacity == 0:
      return 0, 0
    uniq, first, counts = np.unique(np.asarray(ids)[miss],
                                    return_index=True,
                                    return_counts=True)
    adm_ids, slots, evicted = self.policy.plan_admissions(uniq, counts)
    if not len(adm_ids):
      return 0, 0
    # src = position in x of the FIRST occurrence of each admitted id
    pos_of = dict(zip(uniq.tolist(),
                      np.nonzero(miss)[0][first].tolist()))
    src = np.asarray([pos_of[i] for i in adm_ids.tolist()], np.int32)
    a_pad = _pad_pow2(len(adm_ids))
    src_p = np.zeros(a_pad, np.int32)
    dst_p = np.full(a_pad, self.capacity, np.int32)    # dropped
    src_p[:len(src)] = src
    dst_p[:len(slots)] = slots
    self.rows = _admit_rows(self.rows, x, jnp.asarray(src_p),
                            jnp.asarray(dst_p))
    self.policy.commit(adm_ids, slots)
    self.stats.admits += len(adm_ids)
    self.stats.evicts += evicted
    return len(adm_ids), evicted

  # -- DataPlaneState: tag ring + clock hand + the HBM row ring -----------
  def state_dict(self) -> dict:
    return {'policy': self.policy.state_dict(),
            'rows': np.asarray(self.rows)}

  def load_state_dict(self, state: dict) -> None:
    self.policy.load_state_dict(state['policy'])
    self.rows = jax.device_put(
        np.asarray(state['rows'], self.rows.dtype),
        next(iter(self.rows.devices())))


# -- pinned-host cold gather (r19, ISSUE 18) --------------------------------

_PINNED_ENV = 'GLT_PALLAS_COLD'


def pinned_cold_enabled() -> bool:
  """Re-read ``GLT_PALLAS_COLD`` on every mixed-path build (kill
  switch, the `pallas_gather.pallas_enabled` discipline)."""
  return os.environ.get(_PINNED_ENV, '').strip().lower() in (
      '1', 'true', 'on', 'yes')


def _host_take(rows: jax.Array, idx: jax.Array) -> jax.Array:
  """``rows[idx]`` with BOTH operands in host memory: the gather runs
  as XLA host compute and only its ``[B, D]`` result crosses to device
  memory.  This is the one formulation that lowers on jax 0.9.0 on
  both backends (v5e run, PR 21): a gather whose operands sit in
  different memory spaces is refused at trace time, a host/host
  gather outside ``compute_on`` fails in XLA:TPU, and XLA:CPU refuses
  a host->device ``out_shardings`` but accepts the in-program
  `device_put`.  A raw `lax.gather`, because `jnp.take`'s index
  normalisation compares against device-space constants."""
  from jax.experimental.compute_on import compute_on
  dnums = jax.lax.GatherDimensionNumbers(
      offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,))
  with compute_on('device_host'):
    out = jax.lax.gather(
        rows, idx[:, None], dnums, slice_sizes=(1, rows.shape[1]),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
  return jax.device_put(out, jax.memory.Space.Device)


class PinnedColdBuffer:
  """Cold-tier feature rows resident in pinned HOST memory, served by
  one jitted gather per batch (PyTorch-Direct / GIDS in spirit —
  PAPERS.md arXiv 2101.07956, 2306.16384).

  The PR 5 overlay's cold fill is ``np.take`` in Python followed by a
  full-batch transfer — the interpreter touches every cold byte twice
  (gather + copy into the transfer buffer).  Here the cold rows are
  device_put ONCE into ``pinned_host`` memory and every per-batch fill
  is one compiled program (`_host_take`): the take runs as XLA host
  compute next to the rows and its output lands in device memory.
  It is NOT a device-initiated DMA over the host buffer — no such
  gather lowers on the installed JAX.  Byte parity with the
  ``np.take`` path is exact — same rows, same dtype cast (applied once
  at build instead of per batch) — pinned by
  tests/test_pallas_sample.py and re-checked on a v5e (PR 21).

  Owns the ``pinned_host`` memaccount tier: the buffer is that
  tier's whole bill, so ``memory.tier_bytes{tier=pinned_host}``
  tracks it live on /metrics.

  Whether this beats ``np.take`` on a chip is not measured (ROADMAP
  S5); the knob stays opt-in until it is."""

  memory_kind = 'pinned_host'

  def __init__(self, rows_np: np.ndarray, dim: int, dtype,
               device: Optional[jax.Device] = None):
    from jax.sharding import SingleDeviceSharding
    dev = device if device is not None else jax.devices()[0]
    arr = np.ascontiguousarray(rows_np)
    if dtype is not None:
      arr = arr.astype(dtype, copy=False)
    if arr.ndim != 2 or arr.shape[1] != int(dim):
      raise ValueError(f'expected [rows, {dim}] cold block, got '
                       f'{arr.shape}')
    self._host = SingleDeviceSharding(dev, memory_kind=self.memory_kind)
    self.rows = jax.device_put(arr, self._host)
    self._gather = jax.jit(_host_take)
    # run one tiny gather end-to-end NOW so a backend that cannot
    # lower it fails here, at build, with the compiler's message —
    # never on some later batch
    np.asarray(self.gather(np.zeros((1,), np.int32)))
    from ..telemetry.memaccount import register_tier
    register_tier('pinned_host',
                  lambda r=self.rows: int(getattr(r, 'nbytes', 0)))

  def gather(self, idx: np.ndarray) -> jax.Array:
    """``[B] -> [B, D]`` device rows; indices are buffer-relative
    (caller subtracts the hot-row base) and must be in range."""
    return self._gather(self.rows, jax.device_put(
        np.ascontiguousarray(idx, np.int32), self._host))


def make_pinned_cold_buffer(rows_np, dim: int, dtype,
                            device=None) -> Optional[PinnedColdBuffer]:
  """`PinnedColdBuffer` when ``GLT_PALLAS_COLD`` is on, else None (the
  caller keeps the host ``np.take`` path).  An opted-in build that
  fails RAISES: it never quietly becomes the host path.  Emits the
  kernel dispatch event once, at build."""
  from ..telemetry.recorder import recorder
  if not pinned_cold_enabled():
    return None
  buf = PinnedColdBuffer(rows_np, dim, dtype, device=device)
  if recorder.enabled:
    recorder.emit('pallas.dispatch', kernel='cold_gather',
                  rows=int(buf.rows.shape[0]),
                  memory_kind=buf.memory_kind)
  return buf


# -- mesh flavor (dist samplers + tiered fused epochs) ---------------------

@functools.lru_cache(maxsize=None)
def _mesh_cache_programs(mesh, axis: str):
  """Per-mesh jitted serve/admit programs over ``[P, ...]`` sharded
  stacks (cached like `_cold_overlay_programs`)."""
  from ..parallel.shard_map_compat import shard_map
  from jax.sharding import PartitionSpec as P
  s2, s3 = P(axis, None), P(axis, None, None)

  def _serve(x, rows, hit, slot):
    return jnp.where(hit[0][:, None], rows[0][slot[0]], x[0])[None]

  serve = jax.jit(shard_map(_serve, mesh=mesh,
                            in_specs=(s3, s3, s2, s2), out_specs=s3))

  def _admit(rows, x, src, dst):
    return rows[0].at[dst[0]].set(x[0][src[0]], mode='drop')[None]

  admit = jax.jit(shard_map(_admit, mesh=mesh,
                            in_specs=(s3, s3, s2, s2), out_specs=s3),
                  donate_argnums=(0,))
  return serve, admit


class MeshColdCache:
  """Per-device victim caches for the mesh engines: ``P`` (locally:
  ``len(host_parts)``) independent `ClockShardCache` policies over a
  ``[P, C, D]`` sharded HBM row stack.  Each device caches the cold
  rows *it* requested (requester-side, like PyTorch-Direct's per-GPU
  cache) — hits are served by a purely local gather, no collective.

  The host-side plan/commit calls take the same ``[pl, cap]`` stacked
  id/mask layout the cold-overlay planners already produce, and the
  device calls take the put function the sampler already owns
  (`put_stacked_host_local` on multi-host, a sharded `device_put`
  under a single controller) — so one cache implementation serves the
  per-batch loaders, the pipelined overlay, and the fused chunk path.
  """

  def __init__(self, capacity: int, dim: int, dtype, num_local: int,
               mesh, axis: str, put_stacked, bounds=None):
    self.capacity = int(capacity)
    self.mesh, self.axis = mesh, axis
    self._put = put_stacked
    self.shards = [ClockShardCache(capacity, bounds=bounds)
                   for _ in range(num_local)]
    self.rows = put_stacked(
        np.zeros((num_local, max(self.capacity, 1), int(dim)), dtype))
    self.stats = CacheStats()
    from ..telemetry.memaccount import register_tier
    register_tier('cold_cache',
                  lambda r=self.rows: int(getattr(r, 'nbytes', 0)))
    self._hotness_fns = ()
    if bounds is not None:
      # the sketches' decayed range mass becomes the live top-K
      # gns.range_hotness{partition=} gauges (evaluated at scrape)
      from ..ops.gns import register_hotness_gauges
      self._hotness_fns = register_hotness_gauges(
          lambda: [sh.sketch for sh in self.shards],
          max(len(np.asarray(bounds)) - 1, 1))

  @property
  def enabled(self) -> bool:
    return self.capacity > 0

  @property
  def version(self) -> int:
    """Sum of the shard ring versions — moved iff any shard's
    residency changed (the GNS bitmask refresh trigger)."""
    return sum(sh.version for sh in self.shards)

  def resident_ids(self) -> np.ndarray:
    """Union of every local shard's residents (global ids) — the
    dynamic half of the GNS cached set."""
    if not self.shards:
      return np.empty(0, np.int64)
    return np.unique(np.concatenate(
        [sh.resident_ids() for sh in self.shards]))

  def lookup(self, ids_l: np.ndarray, active: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized per-shard lookup over the stacked ``[pl, cap]`` id
    table.  Returns ``(hit [pl, cap], slot [pl, cap])``."""
    hit = np.zeros(ids_l.shape, bool)
    slot = np.zeros(ids_l.shape, np.int32)
    for j, sh in enumerate(self.shards):
      hit[j], slot[j] = sh.lookup(ids_l[j], active[j])
    self.stats.hits += int(hit.sum())
    return hit, slot

  def serve(self, x: jax.Array, hit: np.ndarray,
            slot: np.ndarray) -> jax.Array:
    # only a SINGLE controller may skip the dispatch on a locally
    # empty hit set — multiple controllers must all run the same
    # programs on the global arrays or they diverge
    if not hit.any() and jax.process_count() == 1:
      return x
    serve, _ = _mesh_cache_programs(self.mesh, self.axis)
    return serve(x, self.rows, self._put(hit), self._put(slot))

  def admit(self, x: jax.Array, ids_l: np.ndarray,
            miss: np.ndarray) -> Tuple[int, int]:
    """Admit the batch's miss rows (already corrected on device in
    ``x``).  The padded admission width is the max over LOCAL shards;
    multi-controller callers must agree on it globally — pass the
    agreed value through `admit_width` / `admit_planned`."""
    plans = self.plan_admissions(ids_l, miss)
    return self.commit_admissions(x, plans, self.admit_width(plans))

  def plan_admissions(self, ids_l: np.ndarray, miss: np.ndarray):
    self.stats.misses += int(miss.sum())
    plans = []
    for j, sh in enumerate(self.shards):
      m = miss[j]
      if not m.any() or self.capacity == 0:
        plans.append((np.empty(0, np.int64), np.empty(0, np.int32),
                      np.empty(0, np.int32), 0))
        continue
      uniq, first, counts = np.unique(ids_l[j][m], return_index=True,
                                      return_counts=True)
      adm, slots, ev = sh.plan_admissions(uniq, counts)
      pos_of = dict(zip(uniq.tolist(),
                        np.nonzero(m)[0][first].tolist()))
      src = np.asarray([pos_of[i] for i in adm.tolist()], np.int32)
      plans.append((adm, slots, src, ev))
    return plans

  def admit_width(self, plans) -> int:
    """Local padded admission width (power of two); multi-controller
    callers fold this into their capacity handshake."""
    n = max((len(p[0]) for p in plans), default=0)
    return _pad_pow2(n) if n else 0

  def commit_admissions(self, x: jax.Array, plans,
                        width: int) -> Tuple[int, int]:
    """Execute planned admissions at the (globally agreed) padded
    ``width``.  Returns ``(admits, evicts)``."""
    if width == 0:
      return 0, 0
    pl = len(self.shards)
    src_p = np.zeros((pl, width), np.int32)
    dst_p = np.full((pl, width), self.capacity, np.int32)  # dropped
    admits = evicts = 0
    for j, (adm, slots, src, ev) in enumerate(plans):
      src_p[j, :len(src)] = src
      dst_p[j, :len(slots)] = slots
      admits += len(adm)
      evicts += ev
    _, admit = _mesh_cache_programs(self.mesh, self.axis)
    self.rows = admit(self.rows, x, self._put(src_p),
                      self._put(dst_p))
    for sh, (adm, slots, _src, _ev) in zip(self.shards, plans):
      sh.commit(adm, slots)
    self.stats.admits += admits
    self.stats.evicts += evicts
    return admits, evicts

  # -- DataPlaneState: per-shard tag rings + the sharded HBM row stack ----
  def state_dict(self) -> dict:
    return {'shards': [sh.state_dict() for sh in self.shards],
            'rows': np.asarray(jax.device_get(self.rows))}

  def load_state_dict(self, state: dict) -> None:
    shard_states = state['shards']
    if len(shard_states) != len(self.shards):
      raise ValueError(
          f'cold-cache snapshot has {len(shard_states)} shards, this '
          f'mesh cache holds {len(self.shards)}')
    for sh, st in zip(self.shards, shard_states):
      sh.load_state_dict(st)
    self.rows = self._put(np.asarray(state['rows']))
