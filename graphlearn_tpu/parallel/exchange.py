"""Pluggable all-to-all exchange layouts for the ICI data plane.

Every distributed engine in this package moves ids (and feature/reply
payloads) through the same request/reply pattern: bucket ids by owner
partition, ship buckets to owners, compute locally, ship replies back,
stitch into request order.  The r5 scale envelope showed the naive
uniform ``[P, C]`` bucketing blowing up at scale: the per-destination
capacity ``C`` is floor-bounded (`MIN_EXCHANGE_CAP`, worst-case skew),
so send slots grow as ``P * C`` while the real traffic stays ~the
frontier size — 81.5% padding waste at P=16 and 96.9% at P=64.

This module makes the layout a pluggable choice behind one API
(`capacity_spec` + `plan_exchange`), with three selectable layouts:

``dense``
    The original layout: ``[P, C]`` send buffer, one
    ``jax.lax.all_to_all`` each way, per-destination capacity
    ``max(ceil(n/P * slack), MIN_EXCHANGE_CAP)``.  Zero-risk default
    for small meshes; the floor is paid P times.

``compact``
    Tight per-destination base (``ceil(n/P * slack)``, NO floor) plus
    one lane-aligned globally-shared overflow pool: ids past their
    owner's base capacity ride a compact ``[V]`` buffer that is
    all-gathered, so skew headroom is paid ONCE per exchange instead
    of once per destination.  When the balanced share is tiny
    (``n/P * slack < POOL_ONLY_MAX_SHARE``) the base collapses to the
    pool alone — for frontiers much smaller than the mesh,
    replicating the whole (tiny) request vector costs less than any
    per-destination layout.  This is the GNNSampler / PyTorch-Direct
    lesson applied to the ICI plane: align layout to the transfer
    granularity of the hardware, not to per-logical-bucket bounds.

``hier``
    Two-stage hierarchical routing over a ``[rows, cols]`` factoring
    of the mesh (``rows * cols == P``, both ~sqrt(P)): stage 1 routes
    each id to its owner's COLUMN (an all_to_all within each mesh
    row), stage 2 routes within the column to the owner's row.  The
    per-destination floor is paid ``rows + cols`` ~ ``2 * sqrt(P)``
    times instead of ``P`` times, and every collective has ~sqrt(P)
    participants (bounded rendezvous at large P).  Stage-2 drops are
    shipped back to the requester as a delivered bit so capacity
    overflow is never silent.

``ragged``
    ``jax.lax.ragged_all_to_all``: per-destination send sizes are
    runtime values, so there is no capacity waste at all.  NOT
    SELECTABLE: `_RaggedPlan` has never executed on any device (and
    carries a known receive-overflow hole), so `resolve_layout`
    raises `NotImplementedError` for it rather than let a user be the
    first to run it.  Whether it is qualified on a multi-chip slice
    or deleted is ROADMAP D5's decision.

Selection: pass ``exchange_layout=`` to the samplers/loaders, or set
``GLT_EXCHANGE_LAYOUT`` (wins over the built-in ``'auto'`` rule, loses
to an explicit per-sampler layout).  ``'auto'`` keeps ``dense`` below
`AUTO_COMPACT_MIN_PARTS` devices (bit-identical with the pre-layout
engines) and switches to ``compact`` at P >= 16 where the floor waste
dominates.

Capacity knobs, all tuned by `dist_sampler.AdaptiveSlack` through the
single slack ladder: the per-destination base multiplier (``slack``),
the global overflow budget (``POOL_FRAC`` of the request width, env
``GLT_EXCHANGE_POOL_FRAC``), and the per-stage capacities of the
hierarchical layout (slack times the per-stage balanced share, floored
at `MIN_STAGE_CAP`).

Accounting contract (the telemetry triple every plan exposes):
``offered`` counts valid ids entering each wire stage (an id crossing
both hierarchical stages counts twice — the triple measures per-wire
fill, i.e. the fraction of exchanged slots carrying payload);
``dropped`` counts valid ids that lost their slot; ``slots`` is the
static send-buffer footprint.  Invariant: ``offered - dropped <=
slots`` (what was actually sent fits in the slots).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.padding import INVALID_ID, round_up
from ..utils.profiling import layer_scope

#: per-destination capacity floor of the DENSE layout: exchanges this
#: small gain nothing from capping (the buffer is a few KB) but would
#: drop ids on ANY ownership skew, so they stay exact.  This floor —
#: paid per destination, P times — is exactly the waste the compacted
#: and hierarchical layouts exist to reclaim.
MIN_EXCHANGE_CAP = 64

#: hierarchical per-STAGE bucket floor (paid ~2*sqrt(P) times).
MIN_STAGE_CAP = 16

#: minimum compacted overflow-pool width (absolute skew headroom that
#: the tight per-destination base no longer carries).
MIN_POOL = 32

#: compacted overflow pool as a fraction of the request width — the
#: GLOBAL skew budget, paid once per exchange instead of once per
#: destination.  Default; ``GLT_EXCHANGE_POOL_FRAC`` overrides at
#: capacity-planning time (read per call, like the layout env knob,
#: so late exports and monkeypatched tests take effect).
POOL_FRAC = 0.25


def _pool_frac() -> float:
  try:
    return float(os.environ.get('GLT_EXCHANGE_POOL_FRAC', POOL_FRAC))
  except ValueError:
    return POOL_FRAC

#: below this per-destination share (``n/P * slack``) the compacted
#: base is dropped entirely and the whole request rides the pool: a
#: frontier much smaller than the mesh is cheaper to replicate than to
#: bucket (the all_gather is ~n elements; any per-destination layout
#: pays >= P slots).
POOL_ONLY_MAX_SHARE = 2.0

#: ``'auto'`` switches dense -> compact at this mesh size: below it
#: the dense floor waste is bounded (P * MIN_EXCHANGE_CAP is small)
#: and bit-compatibility with the original engines wins.
AUTO_COMPACT_MIN_PARTS = 16

#: hierarchical needs a non-trivial factoring.
HIER_MIN_PARTS = 4

LAYOUTS = ('dense', 'compact', 'hier', 'ragged')

_ENV_LAYOUT = 'GLT_EXCHANGE_LAYOUT'


def resolve_layout(layout: Optional[str], num_parts: int) -> str:
  """Resolve a requested layout name to the one that will run.

  ``None``/``'auto'`` consults ``GLT_EXCHANGE_LAYOUT`` then the
  built-in rule (dense below `AUTO_COMPACT_MIN_PARTS`, compact at or
  above).  ``'ragged'`` raises `NotImplementedError` (module
  docstring); ``'hier'`` falls back to ``'dense'`` when the mesh is
  too small to factor.
  """
  name = layout or 'auto'
  if name == 'auto':
    name = os.environ.get(_ENV_LAYOUT, '') or 'auto'
  if name == 'auto':
    name = ('compact' if num_parts >= AUTO_COMPACT_MIN_PARTS
            else 'dense')
  if name not in LAYOUTS:
    raise ValueError(
        f'unknown exchange layout {name!r}; expected one of '
        f"{LAYOUTS + ('auto',)}")
  if name == 'ragged':
    raise NotImplementedError(
        "exchange layout 'ragged' has never executed on a device and "
        'is not selectable (parallel.exchange docstring; ROADMAP D5)')
  if name == 'hier':
    if num_parts < HIER_MIN_PARTS:
      name = 'dense'
    elif mesh_factors(num_parts)[1] < 2:
      name = 'compact'            # prime P: no useful factoring
  return name


def mesh_factors(num_parts: int) -> Tuple[int, int]:
  """``(rows, cols)`` with ``rows * cols == num_parts``, both as close
  to sqrt(P) as the factorization allows (rows >= cols)."""
  c = max(int(np.floor(np.sqrt(num_parts))), 1)
  while num_parts % c:
    c -= 1
  return num_parts // c, c


@dataclasses.dataclass(frozen=True)
class ExchangeSpec:
  """Static capacity plan for one bucketed exchange (trace-time
  constant — part of the compiled program's shape)."""
  layout: str
  num_parts: int
  #: per-destination width: dense cap / compacted base (0 = pool-only).
  capacity: int = 0
  #: compacted global overflow budget (send-slot width of the pool).
  pool: int = 0
  #: hierarchical mesh factoring and per-stage bucket widths.
  rows: int = 0
  cols: int = 0
  stage_caps: Tuple[int, int] = (0, 0)

  @property
  def slots(self) -> int:
    """Static send-buffer footprint (the ``slots`` telemetry term)."""
    if self.layout == 'hier':
      return self.cols * self.stage_caps[0] + self.rows * self.stage_caps[1]
    if self.layout == 'compact':
      return self.num_parts * self.capacity + self.pool
    return self.num_parts * self.capacity


def capacity_spec(n: int, num_parts: int, slack: Optional[float],
                  layout: Optional[str] = None,
                  floor: int = MIN_EXCHANGE_CAP,
                  dest_cap: Optional[int] = None,
                  traffic_cap: Optional[int] = None
                  ) -> Optional[ExchangeSpec]:
  """Plan the static capacities of one ``n``-id exchange.

  ``slack`` is the per-destination capacity multiplier over the
  balanced share ``n / P`` (the `AdaptiveSlack` ladder value); None
  means EXACT — per-destination width ``n`` under the dense layout,
  which can never drop an id (callers needing exactness — walkers,
  induced subgraphs — rely on this returning None unchanged).

  ``dest_cap`` / ``traffic_cap`` (ISSUE 20, exchange co-design): the
  `EwmaCapacityModel`'s measured per-step demand — ``dest_cap``
  replaces the UNIFORM balanced share ``n / P`` with the measured
  busiest-destination id count, and ``traffic_cap`` bounds the total
  per-step wire traffic so the compact layout's overflow pool shrinks
  when locality/replication skews traffic local.  Both are quantized
  by the model (powers of two) so recompiles stay logarithmic.  None
  keeps the uniform plan bit-for-bit.  The hierarchical layout keeps
  uniform stage shares (its buckets aggregate destinations, so a
  per-destination measurement does not map onto its caps).
  """
  if slack is None:
    return None
  n = int(n)
  num_parts = int(num_parts)
  name = resolve_layout(layout, num_parts)
  lam = n / num_parts * float(slack)
  if dest_cap is not None and name != 'hier':
    lam = min(n, int(dest_cap)) * float(slack)
  if name == 'hier':
    rows, cols = mesh_factors(num_parts)
    # per-stage caps: slack times the stage's balanced share PLUS an
    # additive fluctuation margin (max of the stage floor and 25% of
    # the share) — a pure multiplier leaves no absolute headroom at
    # small shares, where Poisson noise routinely exceeds slack * lam
    lam1 = n / cols
    lam2 = n / rows
    c1 = int(np.ceil(lam1 * float(slack))) + max(
        MIN_STAGE_CAP, int(np.ceil(lam1 / 4)))
    # stage-2 buckets are single partitions (full ownership skew where
    # stage 1 averaged over a column) — extra 1.5x skew headroom
    c2 = int(np.ceil(lam2 * float(slack) * 1.5)) + max(
        MIN_STAGE_CAP, int(np.ceil(lam2 / 4)))
    c1 = int(round_up(min(c1, n), 4))
    c2 = int(round_up(min(c2, n), 4))
    from ..telemetry.spans import span
    with span('exchange.stage', layout='hier', rows=rows, cols=cols,
              stage1_cap=c1, stage2_cap=c2, n=n):
      pass          # build-time marker: one per compiled stage pair
    return ExchangeSpec('hier', num_parts, rows=rows, cols=cols,
                        stage_caps=(c1, c2))
  dense = ExchangeSpec(
      'dense', num_parts,
      capacity=int(round_up(min(n, max(int(np.ceil(lam)),
                                       int(floor))), 8)))
  if name in ('compact', 'ragged'):
    if name == 'ragged':
      budget = int(round_up(max(n, 1), 8))
      return ExchangeSpec('ragged', num_parts, capacity=budget,
                          pool=2 * budget)
    if lam < POOL_ONLY_MAX_SHARE:
      # pool-only: the whole request vector is the pool — exact (every
      # id fits by construction), slots == round_up(n, 8)
      return ExchangeSpec('compact', num_parts, capacity=0,
                          pool=int(round_up(max(n, 1), 8)))
    base = int(np.ceil(lam))
    # the pool absorbs GLOBAL skew: its budget scales with the ids
    # that actually ride the wire per step (measured `traffic_cap`
    # when the EWMA model supplies one) rather than the request width
    wire = n if traffic_cap is None else min(n, int(traffic_cap))
    pool = int(round_up(
        min(n, max(MIN_POOL, int(np.ceil(wire * _pool_frac())))), 8))
    compact = ExchangeSpec('compact', num_parts,
                           capacity=min(base, n), pool=pool)
    # compact's whole win is reclaiming the dense FLOOR padding; when
    # the share is large enough that the floor never bound, the tight
    # base equals the dense cap and the pool is pure overhead — keep
    # the dense program (also skew-safer: floor >= base + pool/P)
    return compact if compact.slots < dense.slots else dense
  return dense


#: elements of the ``[P, chunk]`` compare one `dest_histogram` pass
#: covers; longer id vectors are counted chunk by chunk (16 M elements
#: = 64 MB as int32, were the compare ever materialised)
HISTOGRAM_CHUNK_ELEMS = 1 << 24


def dest_histogram(ids: jax.Array, owner_fn: Callable,
                   num_parts: int, valid=None) -> jax.Array:
  """[P] int32 count of valid ids per destination partition — the
  attribution row one device contributes to the fleet's P×P src→dst
  traffic matrix (`ExchangeTelemetry.attribution_matrices`).

  Keyed by ``owner_fn`` — callers pass the `PartitionBook` RANGE owner
  (`partition_book.range_owner_fn`), so a row means "ids in range r"
  even after an adopted book remaps which physical device serves r.
  Traceable (runs inside the compiled step); invalid ids and owners
  past ``P`` go to a dropped bin, a negative owner counts to bin 0.

  Counted as a compare of each id's owner against ``arange(P)`` and a
  dense sum over the ids (chunk by chunk past
  `HISTOGRAM_CHUNK_ELEMS`): a reduction that reads the owners once.
  A ``segment_sum`` into P + 1 bins lowers to a scatter whose updates
  all land on a handful of addresses, which the TPU applies one after
  another: 8.2 ms of a 196 ms step on a four-chip TPU v5e host for the
  feature gather's 937,984 ids at P = 4.  Callers trace it under
  ``layer_scope('telemetry', 'attribution')``.
  """
  if valid is None:
    valid = ids >= 0
  owner = jnp.where(valid, owner_fn(ids).astype(jnp.int32),
                    jnp.int32(num_parts))
  owner = jnp.clip(owner, 0, num_parts).reshape(-1)
  bins = jnp.arange(num_parts, dtype=jnp.int32)[:, None]
  chunk = max(HISTOGRAM_CHUNK_ELEMS // max(num_parts, 1), 1)
  count = jnp.zeros((num_parts,), jnp.int32)
  for lo in range(0, owner.shape[0], chunk):
    count = count + jnp.sum(owner[None, lo:lo + chunk] == bins, axis=1,
                            dtype=jnp.int32)
  return count


_ENV_EWMA = 'GLT_EXCHANGE_EWMA'


def ewma_enabled(flag=None) -> bool:
  """``GLT_EXCHANGE_EWMA=1`` turns on measured (EWMA) capacity sizing;
  default OFF — the uniform-share plans stay byte-identical."""
  if flag is not None:
    return bool(flag)
  return os.environ.get(_ENV_EWMA, '').lower() in ('1', 'true', 'on')


def _quantize_pow2(x: float) -> int:
  """Next power of two >= x (>= 1): the capacity ladder that bounds
  recompiles to log2 steps over any traffic trajectory."""
  v = max(int(np.ceil(x)), 1)
  return int(1 << (v - 1).bit_length())


class EwmaCapacityModel:
  """EWMA of measured exchange demand -> quantized capacity caps
  (ISSUE 20 exchange co-design).

  Fed per-channel (``'frontier'`` / ``'feature'``) attribution-matrix
  DELTAS at epoch boundaries: the busiest (src, dst) cell per step
  becomes the per-destination demand (replacing the uniform ``n / P``
  share in `capacity_spec`), and the busiest src row per step bounds
  total wire traffic (shrinking the compact pool).  Both are EWMA'd
  (``GLT_EXCHANGE_EWMA_ALPHA``), padded by a headroom multiplier
  (``GLT_EXCHANGE_EWMA_HEADROOM``) and quantized to powers of two so a
  capacity change — and therefore a recompile — happens at most
  logarithmically often.  `AdaptiveSlack` keeps guarding drops on top:
  an under-measured epoch that drops ids widens the slack rung the
  usual way.
  """

  CHANNELS = ('frontier', 'feature')

  def __init__(self, num_parts: int, alpha: Optional[float] = None,
               headroom: Optional[float] = None):
    def _f(env: str, default: float) -> float:
      try:
        return float(os.environ.get(env, default))
      except ValueError:
        return default
    self.num_parts = int(num_parts)
    self.alpha = (_f('GLT_EXCHANGE_EWMA_ALPHA', 0.5)
                  if alpha is None else float(alpha))
    self.headroom = (_f('GLT_EXCHANGE_EWMA_HEADROOM', 1.3)
                     if headroom is None else float(headroom))
    self._dest: dict = {}
    self._traffic: dict = {}
    self._caps: dict = {}

  def observe(self, channel: str, matrix_delta, steps: int) -> bool:
    """Fold one epoch's [P, P] id-count matrix delta (``steps`` step
    dispatches) into the model.  Returns True when the QUANTIZED caps
    moved — the caller must recompile (clear its step cache)."""
    if steps <= 0:
      return False
    m = np.asarray(matrix_delta, np.float64)
    if m.size == 0 or m.sum() <= 0:
      return False
    dest = float(m.max()) / steps
    traffic = float(m.sum(axis=1).max()) / steps
    a = self.alpha
    self._dest[channel] = (a * dest + (1 - a) * self._dest[channel]
                           if channel in self._dest else dest)
    self._traffic[channel] = (
        a * traffic + (1 - a) * self._traffic[channel]
        if channel in self._traffic else traffic)
    caps = (_quantize_pow2(self._dest[channel] * self.headroom),
            _quantize_pow2(self._traffic[channel] * self.headroom))
    changed = self._caps.get(channel) != caps
    self._caps[channel] = caps
    return changed

  def caps(self, channel: str):
    """``(dest_cap, traffic_cap)`` for `capacity_spec`, or
    ``(None, None)`` before the first observation (uniform plan)."""
    return self._caps.get(channel, (None, None))

  def state_dict(self) -> dict:
    return {f'{c}_{k}': float(d[c])
            for k, d in (('dest', self._dest), ('traffic', self._traffic))
            for c in d}

  def load_state_dict(self, state: dict) -> None:
    for key, val in state.items():
      c, k = key.rsplit('_', 1)
      (self._dest if k == 'dest' else self._traffic)[c] = float(
          np.asarray(val))
    for c in set(self._dest) & set(self._traffic):
      self._caps[c] = (
          _quantize_pow2(self._dest[c] * self.headroom),
          _quantize_pow2(self._traffic[c] * self.headroom))


def offered_dropped(ids: jax.Array, kept: jax.Array):
  """``(offered, dropped)`` of one exchange: the valid ids offered, and
  those of them that found no slot — the first two of a plan's
  ``stats`` triple, counted under ``glt.telemetry/exchange``
  (`scoped_plan`)."""
  valid = ids >= 0
  return (jnp.sum(valid.astype(jnp.int32)),
          jnp.sum((valid & ~kept).astype(jnp.int32)))


def _bcast(mask: jax.Array, values: jax.Array) -> jax.Array:
  """Broadcast a [F] mask over the trailing dims of [F, ...]."""
  return mask.reshape(mask.shape + (1,) * (values.ndim - 1))


def _row_groups(rows: int, cols: int):
  return [[r * cols + c for c in range(cols)] for r in range(rows)]


def _col_groups(rows: int, cols: int):
  return [[r * cols + c for r in range(rows)] for c in range(cols)]


class _SubExchange:
  """One bucketed all_to_all over ``nbuckets`` destinations — the
  shared machinery of the dense layout and each hierarchical stage
  (``groups`` routes the collective within mesh sub-groups)."""

  def __init__(self, ids, owner, nbuckets: int, axis: str,
               capacity: Optional[int], groups=None, payload=None):
    from .dist_sampler import bucket_by_owner, bucket_with_payload
    self.axis = axis
    self.nbuckets = nbuckets
    self.groups = groups
    if payload is None:
      send, self.slot_p, self.slot_j = bucket_by_owner(
          ids, owner, nbuckets, None, capacity)
      recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=True,
                                axis_index_groups=groups)
    else:
      send, send_pl, self.slot_p, self.slot_j = bucket_with_payload(
          ids, payload, owner, nbuckets, None, capacity)
      c = send.shape[1]
      # ONE fused [G, 2C] exchange for ids + payload (these buffers
      # are small and latency-bound on ICI)
      both = jax.lax.all_to_all(
          jnp.concatenate([send, send_pl], axis=1), axis, 0, 0,
          tiled=True, axis_index_groups=groups)
      recv, recv_pl = both[:, :c], both[:, c:]
      self.recv_payload = recv_pl.reshape(-1)
    self.cap = send.shape[1]
    self.recv = recv.reshape(-1)                  # [nbuckets * cap]
    self.kept = self.slot_j >= 0
    self._ids = ids

  def count(self):
    return offered_dropped(self._ids, self.kept)

  def reply(self, values, fill):
    """[nbuckets * cap, ...] owner-side values -> [F, ...] in request
    order; un-kept positions get ``fill``."""
    v = values.reshape((self.nbuckets, self.cap) + values.shape[1:])
    back = jax.lax.all_to_all(v, self.axis, 0, 0, tiled=True,
                              axis_index_groups=self.groups)
    out = back[self.slot_p, jnp.where(self.kept, self.slot_j, 0)]
    return jnp.where(_bcast(self.kept, out), out,
                     jnp.asarray(fill, out.dtype))


class _DensePlan:
  """The original ``[P, C]`` layout behind the plan API."""

  layout = 'dense'

  def __init__(self, ids, owner_fn, num_parts: int, axis: str,
               capacity: Optional[int], payload=None):
    owner = owner_fn(ids).astype(jnp.int32)
    self._sub = _SubExchange(ids, owner, num_parts, axis, capacity,
                             payload=payload)
    self.recv = self._sub.recv
    if payload is not None:
      self.recv_payload = self._sub.recv_payload
    self.kept = self._sub.kept
    self.delivered = self._sub.kept
    #: source device of each recv row (``recv`` is the flattened
    #: [P_src, cap] buffer) — the per-requester GNS mask attribution
    #: (ISSUE 15): owners bias each request by what ITS requester can
    #: serve locally, not by the union of every device's cache
    self.requester_of_recv = jnp.repeat(
        jnp.arange(num_parts, dtype=jnp.int32), self._sub.cap)
    self._slots = num_parts * self._sub.cap

  def count(self):
    return self._sub.count() + (jnp.int32(self._slots),)

  def reply(self, values, fill=0):
    return self._sub.reply(values, fill)


class _CompactPlan:
  """Tight per-destination base + globally-shared overflow pool.

  Base: ``[P, cap]`` all_to_all (cap may be 0 — pool-only mode).
  Pool: ``[V]`` all_gather — every owner sees every device's overflow
  ids, answers the ones it owns; replies ride a ``[P, V]`` all_to_all
  and the requester selects each id's reply row by its owner.  The
  pool is the skew budget paid ONCE per exchange.
  """

  layout = 'compact'

  def __init__(self, ids, owner_fn, num_parts: int, axis: str,
               spec: ExchangeSpec, payload=None):
    f = ids.shape[0]
    p = num_parts
    cap = int(spec.capacity)
    v = int(spec.pool)
    self._p, self._cap, self._pool, self._axis = p, cap, v, axis
    valid = ids >= 0
    owner = jnp.where(valid, owner_fn(ids).astype(jnp.int32), p)
    perm = jnp.argsort(owner, stable=True)
    owner_s = owner[perm]
    ids_s = ids[perm]
    counts = jax.ops.segment_sum(jnp.ones((f,), jnp.int32), owner_s,
                                 num_segments=p + 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(f, dtype=jnp.int32) - offsets[owner_s]
    real = owner_s < p
    in_base = real & (rank < cap)
    want_pool = real & ~in_base
    pool_rank = jnp.cumsum(want_pool.astype(jnp.int32)) - 1
    in_pool = want_pool & (pool_rank < v)

    def scatter_pool(vals, dtype):
      buf = jnp.full((v,), INVALID_ID, dtype)
      return buf.at[jnp.where(in_pool, pool_rank, v)].set(vals,
                                                          mode='drop')

    def scatter_base(vals, dtype):
      buf = jnp.full((p, max(cap, 1)), INVALID_ID, dtype)
      return buf.at[jnp.where(in_base, owner_s, p),
                    jnp.where(in_base, rank, 0)].set(vals, mode='drop')

    pool_send = scatter_pool(ids_s, ids.dtype)
    sends = [pool_send]
    if payload is not None:
      payload_s = payload[perm]
      sends.append(scatter_pool(payload_s, payload.dtype))
    pool_all = jax.lax.all_gather(
        jnp.stack(sends) if len(sends) > 1 else sends[0][None],
        axis, tiled=False)                        # [P, 1|2, V]
    if cap > 0:
      base_send = scatter_base(ids_s, ids.dtype)
      if payload is not None:
        base_pl = scatter_base(payload_s, payload.dtype)
        both = jax.lax.all_to_all(
            jnp.concatenate([base_send, base_pl], axis=1), axis, 0, 0,
            tiled=True)
        base_recv, base_recv_pl = both[:, :cap], both[:, cap:]
      else:
        base_recv = jax.lax.all_to_all(base_send, axis, 0, 0,
                                       tiled=True)
      self.recv = jnp.concatenate([base_recv.reshape(-1),
                                   pool_all[:, 0].reshape(-1)])
      if payload is not None:
        self.recv_payload = jnp.concatenate(
            [base_recv_pl.reshape(-1), pool_all[:, 1].reshape(-1)])
    else:
      self.recv = pool_all[:, 0].reshape(-1)      # [P * V]
      if payload is not None:
        self.recv_payload = pool_all[:, 1].reshape(-1)

    # requester attribution (per-requester GNS masks, ISSUE 15): base
    # recv is the flattened [P_src, cap] buffer; the pool is an
    # all_gather whose row p holds device p's overflow ids verbatim
    src = jnp.arange(p, dtype=jnp.int32)
    if cap > 0:
      self.requester_of_recv = jnp.concatenate(
          [jnp.repeat(src, cap), jnp.repeat(src, v)])
    else:
      self.requester_of_recv = jnp.repeat(src, v)

    # inverse maps back to request order
    inv = lambda x, fill: jnp.full((f,), fill, jnp.int32).at[perm].set(x)
    self._owner = inv(jnp.where(real, owner_s, 0), 0)
    self._slot_j = inv(jnp.where(in_base, rank, -1), -1)
    self._pool_slot = inv(jnp.where(in_pool, pool_rank, -1), -1)
    self.kept = (self._slot_j >= 0) | (self._pool_slot >= 0)
    self.delivered = self.kept
    self._ids = ids

  def count(self):
    return offered_dropped(self._ids, self.kept) + (
        jnp.int32(self._p * self._cap + self._pool),)

  def reply(self, values, fill=0):
    p, cap, v = self._p, self._cap, self._pool
    base_n = p * cap
    pool_part = values[base_n:].reshape((p, v) + values.shape[1:])
    # row o of the replied stack = owner o's answers for MY pool ids
    pool_back = jax.lax.all_to_all(pool_part, self._axis, 0, 0,
                                   tiled=True)
    out_pool = pool_back[self._owner,
                         jnp.where(self._pool_slot >= 0,
                                   self._pool_slot, 0)]
    fillv = jnp.asarray(fill, out_pool.dtype)
    out = jnp.where(_bcast(self._pool_slot >= 0, out_pool), out_pool,
                    fillv)
    if cap > 0:
      base_part = values[:base_n].reshape((p, cap) + values.shape[1:])
      base_back = jax.lax.all_to_all(base_part, self._axis, 0, 0,
                                     tiled=True)
      out_base = base_back[self._owner,
                           jnp.where(self._slot_j >= 0,
                                     self._slot_j, 0)]
      out = jnp.where(_bcast(self._slot_j >= 0, out_base), out_base,
                      out)
    return out


class _HierPlan:
  """Two-stage hierarchical exchange over a [rows, cols] mesh
  factoring: stage 1 within mesh rows (bucket by owner COLUMN), stage
  2 within mesh columns (bucket by owner ROW).  Owners are recomputed
  from the ids at the intermediate device, so no routing metadata
  travels.  Stage-2 drops are shipped back as a delivered bit (one
  int8 reply through stage 1) — multi-stage overflow is never silent.
  """

  layout = 'hier'

  def __init__(self, ids, owner_fn, num_parts: int, axis: str,
               spec: ExchangeSpec, payload=None):
    rows, cols = spec.rows, spec.cols
    c1, c2 = spec.stage_caps
    self._owner_fn = owner_fn
    owner = owner_fn(ids).astype(jnp.int32)
    st1 = _SubExchange(ids, owner % cols, cols, axis, c1,
                       groups=_row_groups(rows, cols), payload=payload)
    ids1 = st1.recv                                  # [cols * c1]
    owner1 = owner_fn(ids1).astype(jnp.int32)
    st2 = _SubExchange(ids1, owner1 // cols, rows, axis, c2,
                       groups=_col_groups(rows, cols),
                       payload=(st1.recv_payload
                                if payload is not None else None))
    self.recv = st2.recv                             # [rows * c2]
    if payload is not None:
      self.recv_payload = st2.recv_payload
    self._st1, self._st2 = st1, st2
    self.kept = st1.kept
    # a kept id may still have been dropped at stage 2 — reply the
    # intermediate's kept bits back through stage 1 (one int8 [cols,
    # c1] exchange) so the requester can mask undelivered results
    bits = st1.reply(st2.kept.astype(jnp.int8), fill=0)
    self.delivered = st1.kept & (bits > 0)
    self._slots = cols * c1 + rows * c2

  def count(self):
    (o1, d1), (o2, d2) = self._st1.count(), self._st2.count()
    return o1 + o2, d1 + d2, jnp.int32(self._slots)

  def reply(self, values, fill=0):
    mid = self._st2.reply(values, fill)              # [cols * c1, ...]
    out = self._st1.reply(mid, fill)                 # [F, ...]
    return jnp.where(_bcast(self.delivered, out), out,
                     jnp.asarray(fill, out.dtype))


class _RaggedPlan:  # pragma: no cover — unreachable, see below
  """`jax.lax.ragged_all_to_all` backend: runtime per-destination send
  sizes, no capacity waste.  UNREACHABLE today: `resolve_layout`
  refuses ``'ragged'`` because this class has never executed on a
  device (ROADMAP D5 qualifies or deletes it).

  KNOWN LIMIT (pre-hardware-validation): the receive buffer is a
  static 2x the send budget, but total arrivals at one device are
  bounded only by ``P * n`` — extreme ownership skew can exceed the
  buffer, and `ragged_all_to_all`'s behavior past it is undefined
  while ``stats`` still reads dropped=0.  Before promoting this
  backend on a real slice, gate it on measured skew (or clamp
  ``recv_sizes`` against remaining space and count the clamp as
  drops); the dense-family layouts bound this by construction.
  """

  layout = 'ragged'

  def __init__(self, ids, owner_fn, num_parts: int, axis: str,
               spec: ExchangeSpec, payload=None):
    if payload is not None:
      raise NotImplementedError(
          'ragged exchange does not carry forward payloads yet; use '
          'compact/dense for paired exchanges')
    f = ids.shape[0]
    p = num_parts
    budget = int(spec.capacity)            # compacted send budget
    out_budget = int(spec.pool)            # receive budget (2x send)
    valid = ids >= 0
    owner = jnp.where(valid, owner_fn(ids).astype(jnp.int32), p)
    perm = jnp.argsort(owner, stable=True)
    owner_s = owner[perm]
    ids_s = jnp.where(owner_s < p, ids[perm], INVALID_ID)
    counts = jax.ops.segment_sum(jnp.ones((f,), jnp.int32), owner_s,
                                 num_segments=p + 1)[:p]
    send_sizes = counts
    input_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    recv_sizes = jax.lax.all_to_all(send_sizes[:, None], axis, 0, 0,
                                    tiled=True)[:, 0]
    output_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(recv_sizes)[:-1]])
    operand = jnp.full((budget,), INVALID_ID, ids.dtype)
    operand = operand.at[jnp.arange(f)].set(ids_s, mode='drop')
    out_buf = jnp.full((out_budget,), INVALID_ID, ids.dtype)
    self.recv = jax.lax.ragged_all_to_all(
        operand, out_buf, input_offsets, send_sizes,
        output_offsets, recv_sizes, axis_name=axis)
    self._perm = perm
    self._axis = axis
    self._io = (input_offsets, send_sizes, output_offsets, recv_sizes)
    self._rank = jnp.arange(f, dtype=jnp.int32) - input_offsets[
        jnp.clip(owner_s, 0, p - 1)]
    self.kept = valid
    self.delivered = valid
    self._budget = budget

  def count(self):
    return (jnp.sum(self.kept.astype(jnp.int32)), jnp.int32(0),
            jnp.int32(self._budget))

  def reply(self, values, fill=0):
    input_offsets, send_sizes, output_offsets, recv_sizes = self._io
    out = jnp.full(self._perm.shape + values.shape[1:],
                   jnp.asarray(fill, values.dtype), values.dtype)
    # roles swap: the owner's received layout becomes the send layout
    back = jax.lax.ragged_all_to_all(
        values, out, output_offsets, recv_sizes, input_offsets,
        send_sizes, axis_name=self._axis)
    # back is in compacted (sorted-by-owner) order; undo the sort
    inv = jnp.zeros_like(self._perm).at[self._perm].set(
        jnp.arange(self._perm.shape[0]))
    return back[inv]


class scoped_plan:
  """A plan under ``glt.exchange/<what>``: ``build()`` (pack + the
  request collective) and every ``reply`` (the reply collective +
  unpack) trace inside the scope, so a profiler trace books them to
  the exchange and what the owners do in between to its own layer.
  The plan's ``stats`` triple (offered, dropped, slots), which only
  reporting reads, is counted after the build, under
  ``glt.telemetry/exchange``."""

  def __init__(self, what: Optional[str], build: Callable):
    self._what = what
    with layer_scope('exchange', what):
      self._plan = build()
    with layer_scope('telemetry', 'exchange'):
      self.stats = self._plan.count()

  def reply(self, values, fill=0):
    with layer_scope('exchange', self._what):
      return self._plan.reply(values, fill)

  def __getattr__(self, name):
    return getattr(self._plan, name)


def plan_exchange(ids: jax.Array, owner_fn: Callable, num_parts: int,
                  axis: str, spec=None, payload=None,
                  what: Optional[str] = None):
  """Build the exchange plan for one request vector.

  Args:
    ids: [F] int ids (-1 padded invalid).
    owner_fn: maps an id array to owner partition indices (the range
      ``searchsorted`` or the mod rule) — called again at the
      hierarchical intermediate, so it must be position-independent.
    spec: None (exact dense), a legacy int per-destination cap, or an
      `ExchangeSpec` from `capacity_spec`.
    payload: optional [F] companion array delivered alongside each id
      (the (row, col) pair shipping of the distributed edge test).
    what: what travels (``frontier`` / ``feature`` / ``pairs``) — the
      part of the ``glt.exchange`` scope the plan traces under.

  Returns a plan with ``recv`` (flat ids this device must answer),
  ``recv_payload`` (when ``payload`` given), ``kept``/``delivered``
  [F] masks, ``stats`` (offered, dropped, slots) and
  ``reply(values, fill)`` mapping owner-side [R, ...] results back to
  request order.
  """
  return scoped_plan(what, lambda: _plan(ids, owner_fn, num_parts, axis,
                                         spec, payload))


def _plan(ids, owner_fn, num_parts: int, axis: str, spec, payload):
  if spec is None or isinstance(spec, (int, np.integer)):
    return _DensePlan(ids, owner_fn, num_parts, axis,
                      None if spec is None else int(spec),
                      payload=payload)
  if spec.layout == 'dense':
    return _DensePlan(ids, owner_fn, num_parts, axis, spec.capacity,
                      payload=payload)
  if spec.layout == 'compact':
    return _CompactPlan(ids, owner_fn, num_parts, axis, spec,
                        payload=payload)
  if spec.layout == 'hier':
    return _HierPlan(ids, owner_fn, num_parts, axis, spec,
                     payload=payload)
  if spec.layout == 'ragged':  # pragma: no cover — see _RaggedPlan
    if payload is not None:
      # the ragged backend has no forward-payload support yet: paired
      # exchanges (edge-existence tests shipping (row, col)) degrade
      # to the exact pool-only compact plan instead of crashing the
      # step trace
      fb = ExchangeSpec('compact', num_parts, capacity=0,
                        pool=int(round_up(max(ids.shape[0], 1), 8)))
      return _CompactPlan(ids, owner_fn, num_parts, axis, fb,
                          payload=payload)
    return _RaggedPlan(ids, owner_fn, num_parts, axis, spec)
  raise ValueError(f'unknown layout {spec.layout!r}')


# ---------------------------------------------------------------------------
# host-side simulation (property tests at any P without a device mesh)

def simulate_assignment(ids: np.ndarray, owner: np.ndarray,
                        spec) -> dict:
  """Pure-numpy twin of the plan slot assignment: which ids keep a
  slot under ``spec``, and the (offered, dropped, slots) triple.
  Mirrors the traced bucketing exactly (stable sort by owner, rank
  against base capacity, overflow pool, per-stage hierarchical caps)
  so capacity properties can be tested at P=64 without 64 devices.
  """
  ids = np.asarray(ids)
  owner = np.asarray(owner)
  valid = ids >= 0
  offered = int(valid.sum())

  def bucket_kept(own, nbuckets, cap):
    own = np.where(valid_cur, own, nbuckets)
    order = np.argsort(own, kind='stable')
    own_s = own[order]
    rank = np.zeros(len(own), np.int64)
    counts = {}
    for pos, o in zip(order, own_s):
      rank[pos] = counts.get(o, 0)
      counts[o] = counts.get(o, 0) + 1
    return (own < nbuckets) & (rank < cap), rank

  if spec is None:
    return {'kept': valid.copy(), 'offered': offered, 'dropped': 0,
            'slots': len(ids) * int(owner.max(initial=0) + 1)}
  if isinstance(spec, (int, np.integer)):
    num_parts = int(owner.max(initial=0) + 1)
    spec = ExchangeSpec('dense', num_parts, capacity=int(spec))
  p = spec.num_parts
  valid_cur = valid
  if spec.layout == 'dense':
    kept, _ = bucket_kept(owner, p, spec.capacity)
    kept &= valid
  elif spec.layout == 'compact':
    in_base, _ = bucket_kept(owner, p, spec.capacity)
    in_base &= valid
    want_pool = valid & ~in_base
    pool_rank = np.cumsum(want_pool) - 1
    kept = in_base | (want_pool & (pool_rank < spec.pool))
  elif spec.layout == 'hier':
    rows, cols = spec.rows, spec.cols
    c1, c2 = spec.stage_caps
    kept1, _ = bucket_kept(owner % cols, cols, c1)
    kept1 &= valid
    # stage 2 runs at the intermediate on the arrived ids; worst-case
    # host model: all of THIS device's kept ids land on one
    # intermediate with nothing else — per-row rank against c2
    valid_cur = kept1
    kept2, _ = bucket_kept(owner // cols, rows, c2)
    kept = kept1 & kept2
  else:
    kept = valid.copy()
  dropped = int((valid & ~kept).sum())
  return {'kept': kept, 'offered': offered, 'dropped': dropped,
          'slots': int(spec.slots)}
