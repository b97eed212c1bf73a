"""Whole-epoch fused DISTRIBUTED training: one SPMD program per epoch.

The mesh twin of `loader.fused.FusedEpoch`: a `lax.scan` over the
epoch's ``[S, P, B]`` seed batches whose body is the full distributed
step — per-hop owner exchange (`all_to_all` over ICI), feature/label
collection, and the data-parallel optax update (`pmean` gradients) —
so the host enqueues ONE XLA program per epoch instead of S sampler
dispatches + S train dispatches.

The reference cannot express this at all: its distributed loader is an
asyncio RPC pipeline feeding a separate DDP step per batch
(`distributed/dist_loader.py`, `dist_neighbor_sampler.py`); fusing an
epoch into one compiled collective program is mesh-native territory.

Exchange telemetry is NOT lost: the scan stacks each step's device-side
counters and `run()` folds the epoch's totals back into the sampler's
accumulator, so `exchange_stats()` reads the same numbers the per-batch
path would produce.

Constraints (checked at construction):
  * static exchange slack — ``'adaptive'`` retunes between batches on
    the host, which a single fused program precludes by design
    (``'auto'`` resolves to the capacity default, as in the loaders).

TIERED stores (``split_ratio < 1``) run as **tiered fused epochs**
(ISSUE 5): the epoch splits into chunks of ``GLT_FUSED_COLD_CHUNK``
steps and each chunk runs THREE dispatches instead of one —

  1. a compiled sample+collect scan (the same SPMD step the per-batch
     sampler dispatches; cold rows come back zeroed past the owner's
     hot count);
  2. the host cold service BETWEEN dispatches: hits in the dynamic
     HBM victim cache (`data.cold_cache`) are overlaid by a local
     device gather, residual misses ride the bounded per-chunk host
     overlay (`overlay_cold_host` / `overlay_cold_owner`), and the
     corrected rows are admitted back into the cache;
  3. a compiled train scan over the chunk's corrected batches.

Batches are byte-identical to the per-batch tiered loader driven with
the same keys; the fused dispatch structure (O(S/chunk) programs, not
O(S) sampler+train dispatches) survives tiering.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..loader.fused import (_COMPILED_ATTRS, _SnapshotHooks,
                            _counted_jit, driver_compile_count,
                            resolve_cold_chunk)
from ..models.train import TrainState
from ..utils.profiling import layer_scope
from .dist_data import DistDataset
from .dist_sampler import (DistLinkNeighborSampler, DistNeighborSampler,
                           link_step_metadata, pack_link_seeds_relabeled,
                           resolve_exchange_slack)
from .dp import (make_dp_eval_step, make_dp_supervised_step,
                 make_dp_unsupervised_step)


class _MeshEpochDriver(_SnapshotHooks):
  """Host-driver pieces shared by the three fused mesh classes, so
  the seed/key/device-put contracts cannot drift between them.

  Preemption tolerance (`_SnapshotHooks`): with a `SnapshotManager`
  attached, tiered epochs snapshot at every chunk boundary (the
  `GLT_FUSED_COLD_CHUNK` seams — the natural recovery points) and
  untiered epochs at epoch boundaries; `restore_from_snapshot` +
  `run()` then finish an interrupted epoch byte-identically.  Every
  dispatch additionally runs under the `GLT_DISPATCH_DEADLINE`
  watchdog: a collective hung by a dead mesh participant surfaces as
  a typed `MeshStallError` instead of wedging the epoch forever, and
  — with ``GLT_DEGRADED_OK=1`` and snapshots attached — the tiered
  driver rolls back to the last snapshot and finishes the epoch on
  the surviving hosts."""

  #: True = tiered store: run()/evaluate() take the chunked
  #: collect → cold-service → consume path (module docstring)
  _tiered = False

  def _chunk_arrs(self) -> dict:
    """The sampler's device arrays, plus — under cache-aware GNS —
    the freshly refreshed cached-set bitmask.  Called per dispatch so
    a chunk's sampling bias sees the admissions the previous chunk's
    cold service made (`ops.gns`: staleness costs placement, never
    estimator bias).

    Streaming ingestion (ISSUE 14) rides the same seam: `_arrays()`
    re-pins the newest published ``graph_version`` at each chunk
    boundary (`DistNeighborSampler.maybe_refresh_stream`), so a
    whole chunk's scan samples exactly one graph version and the
    GNS bitmask is invalidated with the graph it derives from.

    Partition failover (ISSUE 15) fences here too: owner supervision
    runs before the dispatch, and a book-version bump (adoption)
    rebuilds the lane-stacked arrays inside `_arrays()` and
    re-resolves the driver's captured dist step — the changed array
    shapes retrace the compiled scan against the new routing."""
    # the previous chunk's dispatch has been consumed by the time the
    # NEXT chunk asks for arrays — close a pending adoption's recovery
    # clock at this boundary (an adoption in the final chunk closes at
    # the next epoch's first boundary)
    self.sampler._complete_recovery()
    self.sampler._partition_supervision()
    arrs = self.sampler._arrays()
    ver = self.sampler._book_ver
    if getattr(self, '_driver_book_ver', 0) != ver:
      self._driver_book_ver = ver
      if hasattr(self, '_dist_step'):
        self._dist_step = self._resolve_dist_step()
      # the outer scan programs bake `book_spec` as a trace-time
      # closure constant, and jax.jit keys executables on avals only:
      # a bump that keeps every aval unchanged (a SECOND adoption at
      # the same lane count) would hit the stale in-memory executable
      # and route through the old owners — drop the program caches so
      # the next dispatch retraces against the new routing
      for name in _COMPILED_ATTRS:
        jitted = getattr(getattr(self, name, None), 'jitted', None)
        if jitted is not None and hasattr(jitted, 'clear_cache'):
          jitted.clear_cache()
    if getattr(self.sampler, 'gns', False):
      arrs = dict(arrs, gns=self.sampler._gns_arrays())
    return arrs

  def _resolve_dist_step(self):
    """Re-resolve the captured SPMD step after a book bump (the link
    driver overrides with its pair-step resolver)."""
    return self.sampler.step_for_batch(self.batch_size)

  # -- snapshot hooks (mesh-shaped overrides of _SnapshotHooks) -----------
  def data_plane_state(self) -> dict:
    return {'epoch_idx': self._epoch_idx,
            'batcher': self._batcher.state_dict(),
            'sampler': self.sampler.data_plane_state()}

  def load_data_plane_state(self, plane: dict) -> None:
    self._epoch_idx = int(np.asarray(plane['epoch_idx'])) - 1
    self._batcher.load_state_dict(plane['batcher'], mid_epoch=True)
    self.sampler.load_data_plane_state(plane['sampler'])

  def _state_to_device(self, train_host):
    from .dp import replicate
    return replicate(jax.tree_util.tree_map(np.asarray, train_host),
                     self.mesh)

  def _next_epoch_key(self):
    self._epoch_idx += 1
    return jax.random.fold_in(self._base_key, self._epoch_idx)

  def _eval_key(self):
    """Eval keys live in their own fold DOMAIN (base -> 0 -> 1);
    train keys are base -> epoch with epoch >= 1, so no epoch-counter
    value can alias a train sampling key (the loader.fused
    contract)."""
    return jax.random.fold_in(jax.random.fold_in(self._base_key, 0), 1)

  def _put_batches(self, arr: np.ndarray) -> jax.Array:
    """``[S, P, ...]`` host batches → device, sharded over the mesh
    axis on dim 1."""
    return jax.device_put(
        arr.astype(np.int32),
        NamedSharding(self.mesh, P(None, self.axis)))

  def _stack_eval_seeds(self, input_nodes, input_space: str):
    """Relabel + batch an eval split into ``[S, P, B]``."""
    from ..loader.node_loader import SeedBatcher
    ids = np.asarray(input_nodes).reshape(-1)
    if ids.dtype == np.bool_:
      ids = np.nonzero(ids)[0]
    if ids.size == 0:
      raise ValueError('evaluate() got an empty split')
    if input_space == 'old' and self.ds.old2new is not None:
      ids = self.ds.old2new[ids]
    ev = SeedBatcher(ids, self.batch_size * self.num_parts,
                     shuffle=False)
    return np.stack(list(ev)).reshape(-1, self.num_parts,
                                      self.batch_size)

  def run(self, state: TrainState) -> Tuple[TrainState, 'EpochStats']:
    """Run one epoch; ``state`` must be mesh-replicated and is
    DONATED — thread the returned state forward.  ``stats`` is LAZY
    (`loader.fused.EpochStats`)."""
    from ..distributed.resilience import run_with_deadline
    from ..loader.fused import EpochStats
    from ..telemetry.spans import span
    from ..testing import chaos
    from ..utils.profiling import step_annotation
    with span('fused.seeds'):
      flat = np.stack(list(self._batcher))         # [S, P*B]
      seeds = flat.reshape(-1, self.num_parts, self.batch_size)
      s = seeds.shape[0]
      key = self._next_epoch_key()
    with span('fused.epoch', scope=type(self).__name__,
              epoch=self._epoch_idx, steps=seeds.shape[0],
              tiered=self._tiered):
      with step_annotation('fused_dist_epoch', self._epoch_idx):
        if self._tiered:
          state, losses, correct, valid, hops = self._run_tiered(
              state, seeds, key)
        else:
          # untiered = ONE program: snapshots land at epoch
          # boundaries only (there is no mid-epoch seam to save at)
          skip, l_saved, c_saved, v_saved, extra = self._take_resume(s)
          if skip >= s and l_saved:
            losses, correct, valid = l_saved[0], c_saved, v_saved
            hops = extra.get('hops')
          else:
            with span('fused.dispatch'):
              def _epoch_dispatch():
                chaos.fused_dispatch_check(chunk=0,
                                           epoch=self._epoch_idx)
                return self._compiled(state, self._put_batches(seeds),
                                      key, self._chunk_arrs())
              (state, losses, correct, valid, stats,
               hops) = run_with_deadline(_epoch_dispatch,
                                         scope='fused.dispatch')
            self.sampler._accumulate_stats(stats)
            self._save_chunk_snapshot(state, s, s, [losses], correct,
                                      valid, force=True, hops=hops)
      self._emit_hop_events(hops, seeds.shape[0])
    return state, EpochStats(losses, correct, valid)

  # -- tiered fused epochs (module docstring) -------------------------------

  def _chunk_key_stack(self, key, c0: int, n: int):
    """Per-step keys for one chunk, in the GLOBAL step index domain —
    the same ``fold_in(epoch_key, i)`` schedule the single-program
    scan uses, so tiered and untiered epochs draw identically."""
    return jnp.stack([jax.random.fold_in(key, i)
                      for i in range(c0, c0 + n)])

  def _cold_chunk_steps(self, total_steps: int) -> int:
    return resolve_cold_chunk(self._collect_step_bytes(), total_steps)

  def _tiered_chunks(self, stacked: np.ndarray, key, chunk: int):
    """Yield ``(c0, real_steps, [chunk, ...] piece, [chunk] keys)``:
    tail chunks pad with INVALID_ID seed rows (the loader twin's
    `_chunks` convention — every epoch length reuses ONE compile per
    collect/train/eval program; padded steps sample nothing and
    contribute no valid seeds).  Consumers must slice per-step
    outputs (losses, stats) to ``real_steps``."""
    s = stacked.shape[0]
    for c0 in range(0, s, chunk):
      part = stacked[c0:c0 + chunk]
      real = part.shape[0]
      if real < chunk:
        pad = np.full((chunk - real,) + stacked.shape[1:], -1,
                      stacked.dtype)
        part = np.concatenate([part, pad])
      yield c0, real, part, self._chunk_key_stack(key, c0, chunk)

  def _overlay_stacked(self, x_all, nodes_all):
    """Between-dispatch cold service for one chunk's stacked
    ``[c, ...]`` features/ids: per step, the sampler's cache-aware
    overlay (cache hits device-served, misses host-overlaid, corrected
    rows admitted)."""
    from ..telemetry.spans import span
    c = x_all.shape[0]
    with span('feature.cold_overlay', scope=type(self).__name__,
              steps=c):
      fixed = [self.sampler._overlay_cold_traced(x_all[i], nodes_all[i])
               for i in range(c)]
    return jnp.stack(fixed)

  def _run_tiered(self, state, seeds: np.ndarray, key):
    """Chunked collect → cold-service → train epoch (tiered stores).
    Returns ``(state, losses, correct, valid, hops)``.

    With snapshots attached, every chunk boundary is a durable
    recovery point, and a `MeshStallError` (hung collective under
    `GLT_DISPATCH_DEADLINE`) rolls back to the last snapshot and
    retries on the surviving hosts when ``GLT_DEGRADED_OK=1`` —
    instead of wedging or losing the epoch."""
    from ..distributed.resilience import MeshStallError, degraded_ok
    s = seeds.shape[0]
    chunk = self._cold_chunk_steps(s)
    skip, losses, correct, valid, extra = self._take_resume(chunk)
    hops = extra.get('hops')
    if 'sampler_stats' in extra:
      # a fresh-process resume continues the interrupted epoch's
      # cumulative exchange/cold telemetry, not a zeroed ledger
      self.sampler._load_stats_state(extra['sampler_stats'])
    stats_fn = lambda: {'sampler_stats': self.sampler._stats_state()}
    if self._snap is not None and skip == 0 and not losses:
      # epoch-entry save: the rollback target a chunk-0 stall needs
      self._save_chunk_snapshot(state, 0, chunk, losses, correct,
                                valid, force=True, extra_fn=stats_fn)
    parts = list(self._tiered_chunks(seeds, key, chunk))
    i = rollbacks = 0
    while i < len(parts):
      c0, real, part, keys = parts[i]
      if c0 < skip:
        i += 1
        continue
      try:
        state, ls, cor, val, hop = self._dispatch_tiered_chunk(
            state, part, keys, real, c0)
      except MeshStallError:
        if (not degraded_ok() or self._snap is None
            or rollbacks >= 3):
          raise
        rollback = self._rollback_to_snapshot(state)
        if rollback is None:
          raise     # nothing durable to roll back to: stay typed
        rollbacks += 1
        (state, skip, losses, correct, valid, hops) = rollback
        i = 0
        continue
      losses.append(ls[:real])
      correct = cor if correct is None else correct + cor
      valid = val if valid is None else valid + val
      hops = hop if hops is None else hops + hop
      self._save_chunk_snapshot(state, c0 + chunk, chunk, losses,
                                correct, valid, hops=hops,
                                extra_fn=stats_fn)
      i += 1
    return state, jnp.concatenate(losses), correct, valid, hops

  def _dispatch_tiered_chunk(self, state, part, keys, real: int,
                             c0: int):
    """One chunk's collect → overlay → train, every dispatch under
    the stall watchdog and the ``fused.dispatch`` chaos seam."""
    from ..distributed.resilience import run_with_deadline
    from ..telemetry.spans import span
    from ..testing import chaos
    with span('fused.dispatch', chunk=c0, phase='collect'):
      def _collect():
        chaos.fused_dispatch_check(chunk=int(c0),
                                   epoch=self._epoch_idx,
                                   phase='collect')
        return self._compiled_collect(self._put_batches(part), keys,
                                      self._chunk_arrs())
      data, stats = run_with_deadline(_collect, scope='fused.dispatch')
    # stats sliced to the real steps: padded tail steps still carry
    # static exchange SLOTS, which would inflate padding waste
    chunk_stats = jnp.sum(stats[:real], axis=0)
    data = self._overlay_chunk(data)
    with span('fused.dispatch', chunk=c0, phase='train'):
      out = run_with_deadline(self._train_chunk, state, data,
                              scope='fused.dispatch')
    # banked only after BOTH dispatches land: a train-phase stall
    # rolls back and re-runs the chunk, and stats accumulated at
    # collect time would then double-count
    self.sampler._accumulate_stats(chunk_stats)
    return out

  def _train_chunk(self, state, data):
    """Train dispatch for one tiered chunk -> ``(state, losses,
    correct, valid, hops)`` (the link driver overrides: no accuracy,
    no hop gauges)."""
    return self._compiled_train(state, data)

  def _rollback_to_snapshot(self, cur_state):
    """Degraded stall recovery: reload the last snapshot's train
    state + progress (NOT the full data plane — the epoch counters
    and batcher are live and correct mid-run) and hand back the
    accumulators to continue from.  ``None`` when no snapshot was
    ever published (every save failed): the caller re-raises the
    stall."""
    payload = self._snap.restore_latest()
    if payload is None:
      return None
    prog = payload['progress']
    train = payload.get('train')
    state = (self._state_to_device(train) if train is not None
             else cur_state)
    saved = np.asarray(prog['losses'])
    losses = [saved] if saved.size else []
    if 'sampler_stats' in prog:
      # re-dispatched chunks re-accumulate exchange/cold counters;
      # rewinding them to the snapshot keeps AdaptiveSlack and the
      # padding-waste metrics honest through a degraded recovery
      self.sampler._load_stats_state(prog['sampler_stats'])
    return (state, int(np.asarray(prog['next_chunk'])), losses,
            prog.get('correct'), prog.get('valid'), prog.get('hops'))

  def _emit_hop_events(self, hop_counts, steps: int) -> None:
    """Per-hop padding-fill flight-recorder events for one fused
    epoch.  ``hop_counts`` is the epoch's ``[H+1]`` per-hop node
    totals (summed over steps and devices inside the program — free
    in the scan); reading it is a device sync, so this only runs when
    the recorder is on (`EpochStats` laziness stays intact
    otherwise)."""
    from ..telemetry.recorder import recorder
    if not recorder.enabled:
      return
    from ..telemetry.aggregate import per_hop_padding
    fanouts = getattr(self, 'fanouts', None) or self.sampler.fanouts
    rows = per_hop_padding(
        np.asarray(hop_counts),
        self.batch_size * self.num_parts * max(int(steps), 1), fanouts)
    for row in rows:
      recorder.emit('hop.padding', scope=type(self).__name__,
                    epoch=self._epoch_idx, steps=int(steps), **row)

  def compile_count(self) -> int:
    """Total XLA compiles across this driver's `_counted_jit`
    programs (`loader.fused.driver_compile_count`) — the mesh twin of
    the serving engine's zero-recompile pin.  A serving fleet that
    co-hosts training warms its epoch programs once and watches this
    stay flat, exactly like the bucket ladder."""
    return driver_compile_count(self)

  def cluster_exchange_stats(self) -> dict:
    """Cluster-wide padding-waste / drop-rate / cold-tier report for
    this epoch driver (delegates to the sampler's telemetry — see
    `ExchangeTelemetry.cluster_exchange_stats`)."""
    return self.sampler.cluster_exchange_stats()

  def evaluate(self, params, input_nodes,
               input_space: str = 'old') -> float:
    """Accuracy over ``input_nodes`` (e.g. the test split) as ONE
    SPMD scan program — or, for tiered stores, the
    chunked collect → cold-service → eval path."""
    seeds = self._stack_eval_seeds(input_nodes, input_space)
    if self._tiered:
      return self._evaluate_tiered(params, seeds)
    correct, total, stats = self._compiled_eval(
        params, self._put_batches(seeds), self._eval_key(),
        self._chunk_arrs())
    self.sampler._accumulate_stats(stats)
    return float(int(correct) / max(int(total), 1))

  def _evaluate_tiered(self, params, seeds: np.ndarray) -> float:
    key = self._eval_key()
    s = seeds.shape[0]
    chunk = self._cold_chunk_steps(s)
    correct = total = 0
    for c0, real, part, keys in self._tiered_chunks(seeds, key, chunk):
      data, stats = self._compiled_collect(
          self._put_batches(part), keys, self._chunk_arrs())
      self.sampler._accumulate_stats(jnp.sum(stats[:real], axis=0))
      data = self._overlay_chunk(data)
      c, t = self._compiled_eval_consume(params, data)
      correct += int(c)
      total += int(t)
    return correct / max(total, 1)


class FusedDistEpoch(_MeshEpochDriver):
  """One-program data-parallel training epochs on the mesh engine.

  Example::

      fused = FusedDistEpoch(dist_ds, [15, 10, 5], train_idx, apply_fn,
                             tx, batch_size=1024, mesh=mesh, seed=0)
      state = replicate(state, mesh)
      for epoch in range(10):
        state, stats = fused.run(state)

  Args:
    dataset: `DistDataset` (sharded layout).  Tiered stores
      (``split_ratio < 1``) run as chunked tiered fused epochs with
      the cold-cache service between dispatches (module docstring).
    num_neighbors: per-hop fanouts.
    input_nodes: global seed ids (``input_space`` semantics as in
      `DistNeighborLoader`).
    apply_fn / tx: model apply function and optax transformation.
    batch_size: PER-DEVICE seed batch size.
    mesh / axis: device mesh; its ``axis`` size must equal the
      partition count.
    shuffle / drop_last / seed: epoch iteration controls.
    exchange_slack: static capacity factor (``'auto'`` → the shuffled
      default; ``'adaptive'`` is rejected, see module docstring).
    remat: checkpoint the model forward (`jax.checkpoint`) — the fused
      program holds sampler buffers and training activations live
      together, and at large batch x fanout that joint peak can exceed
      per-chip HBM where the separate per-batch programs fit (see
      `loader.fused.FusedEpoch`).
    fast_compile: compile the epoch program with the expensive LLVM
      passes OFF (`loader.fused._FAST_COMPILE_OPTIONS`) — measured on
      the 8-device CPU mesh at the headline shape: ~38% off the scan
      compile wall for a modest runtime cost; for dev iteration and
      CPU-mesh validation.
  """

  def __init__(self, dataset: DistDataset, num_neighbors, input_nodes,
               apply_fn: Callable, tx: optax.GradientTransformation,
               batch_size: int, mesh: Optional[Mesh] = None,
               axis: str = 'data', shuffle: bool = True,
               drop_last: bool = False, seed: int = 0,
               input_space: str = 'old',
               exchange_slack='auto', exchange_layout=None,
               remat: bool = False,
               fast_compile: bool = False, gns=None):
    from ..loader.node_loader import SeedBatcher
    if dataset.node_features is None or dataset.node_labels is None:
      raise ValueError('FusedDistEpoch needs node features and labels')
    if exchange_slack == 'adaptive':
      raise ValueError(
          "exchange_slack='adaptive' retunes between batches on the "
          "host; FusedDistEpoch takes a static slack ('auto' or a "
          'number) — or use DistNeighborLoader for adaptive tuning')
    # 'adaptive' was rejected above, so the resolved slack is static
    slack = resolve_exchange_slack(exchange_slack, shuffle)
    self.sampler = DistNeighborSampler(
        dataset, num_neighbors, mesh=mesh, axis=axis,
        collect_features=True, seed=seed, exchange_slack=slack,
        exchange_layout=exchange_layout, gns=gns)
    self.ds = dataset
    self.mesh = self.sampler.mesh
    self.axis = axis
    self.num_parts = dataset.num_partitions
    self.batch_size = int(batch_size)

    seeds = np.asarray(input_nodes).reshape(-1)
    if input_space == 'old' and dataset.old2new is not None:
      seeds = dataset.old2new[seeds]
    self._batcher = SeedBatcher(seeds, self.batch_size * self.num_parts,
                                shuffle, drop_last, seed)
    self._base_key = jax.random.key(seed)
    self._epoch_idx = 0
    step_apply = jax.checkpoint(apply_fn) if remat else apply_fn
    self._dp_step = make_dp_supervised_step(step_apply, tx,
                                            self.batch_size, self.mesh,
                                            axis)
    # un-remat'd: evaluate() is forward-only
    self._dp_eval = make_dp_eval_step(apply_fn, self.batch_size,
                                      self.mesh, axis)
    self._dist_step = self.sampler.step_for_batch(self.batch_size)
    self._compiled = _counted_jit(self._epoch_fn, donate_argnums=(0,),
                                   fast_compile=fast_compile)
    self._compiled_eval = _counted_jit(self._eval_fn,
                                        fast_compile=fast_compile)
    # tiered store: chunked collect → cold-service → train programs
    # (module docstring, "tiered fused epochs")
    self._tiered = dataset.node_features.is_tiered
    if self._tiered:
      self._compiled_collect = _counted_jit(self._collect_fn,
                                             fast_compile=fast_compile)
      self._compiled_train = _counted_jit(self._train_fn,
                                           donate_argnums=(0,),
                                           fast_compile=fast_compile)
      self._compiled_eval_consume = _counted_jit(
          self._eval_consume_fn, fast_compile=fast_compile)

  def __len__(self) -> int:
    return len(self._batcher)

  # -- the one program ------------------------------------------------------

  def _collate(self, seeds: jax.Array, key_i: jax.Array, arrs: dict):
    """One fused distributed sample+collect: shared front half of the
    train and eval scan bodies (the same program `DistNeighborSampler`
    dispatches per batch).  Under GNS (``'gns'`` in ``arrs``) the step
    takes the cached-set bitmask and the per-edge importance weights
    land in the batch metadata."""
    from ..loader.transform import Batch
    extra = (arrs['gns'],) if 'gns' in arrs else ()
    outs = self._dist_step(
        arrs['indptr'], arrs['indices'], arrs['eids'], arrs['bounds'],
        seeds, arrs['fshards'], arrs['lshards'], arrs['cids'],
        arrs['crows'], arrs['efshards'], arrs['ebounds'],
        arrs['hcounts'], *extra, key_i)
    (nodes, _count, row, col, edge, seed_local, x, y, ef, nsn,
     stats) = outs[:11]
    md = {'seed_local': seed_local}
    if 'gns' in arrs:
      md['edge_weight'] = outs[11]
    batch = Batch(
        x=x, y=y, edge_index=jnp.stack([row, col], axis=1),
        edge_attr=ef, node=nodes, node_mask=nodes >= 0,
        edge_mask=row >= 0, edge=edge, batch=seeds,
        batch_size=self.batch_size,
        num_sampled_nodes=nsn, metadata=md)
    return batch, stats

  def _epoch_fn(self, state: TrainState, seeds_all: jax.Array,
                key: jax.Array, arrs: dict):
    """``[S, P, B]`` seed batches → S fused exchange+collect+train
    steps; outputs per-step losses, the summed telemetry and the
    per-hop new-node totals (for the padding-fill gauges)."""

    def body(state, xs):
      i, seeds = xs
      batch, stats = self._collate(seeds, jax.random.fold_in(key, i),
                                   arrs)
      state, loss, correct = self._dp_step(state, batch)
      # [P, H+1] new-node counts -> [H+1]: per-hop padding fill rides
      # the scan for free instead of a per-batch host sync
      hop = jnp.sum(batch.num_sampled_nodes, axis=0)
      return state, (loss, correct, jnp.sum(seeds >= 0), stats, hop)

    steps = jnp.arange(seeds_all.shape[0], dtype=jnp.int32)
    state, (losses, corrects, valids, stats, hops) = jax.lax.scan(
        body, state, (steps, seeds_all))
    return (state, losses, jnp.sum(corrects), jnp.sum(valids),
            jnp.sum(stats, axis=0), jnp.sum(hops, axis=0))

  def _eval_fn(self, params, seeds_all: jax.Array, key: jax.Array,
               arrs: dict):
    """Scan twin of an eval loop over ``[S, P, B]`` seeds — accuracy
    on the seed slots, psum'd over the mesh (`make_dp_eval_step`)."""

    def body(carry, xs):
      i, seeds = xs
      batch, stats = self._collate(seeds, jax.random.fold_in(key, i),
                                   arrs)
      correct, total = self._dp_eval(params, batch)
      return carry, (correct, total, stats)

    steps = jnp.arange(seeds_all.shape[0], dtype=jnp.int32)
    _, (correct, total, stats) = jax.lax.scan(
        body, 0, (steps, seeds_all))
    return jnp.sum(correct), jnp.sum(total), jnp.sum(stats, axis=0)

  # -- tiered fused epochs (chunked collect/train twins) --------------------

  def _collect_step_bytes(self) -> int:
    cap = self.sampler.node_capacity(self.batch_size)
    nf = self.ds.node_features
    return (self.num_parts * cap * nf.feature_dim
            * np.dtype(nf.shards.dtype).itemsize)

  def _collect_fn(self, seeds_all: jax.Array, keys: jax.Array,
                  arrs: dict):
    """``[c, P, B]`` seeds → the chunk's stacked sample+collect
    batches (cold rows zeroed, corrected between dispatches) + the
    stacked telemetry."""

    def body(_, xs):
      key_i, seeds = xs
      batch, stats = self._collate(seeds, key_i, arrs)
      return 0, (batch, stats)

    _, (batches, stats) = jax.lax.scan(body, 0, (keys, seeds_all))
    return batches, stats

  def _overlay_chunk(self, batches):
    batches.x = self._overlay_stacked(batches.x, batches.node)
    return batches

  def _train_fn(self, state: TrainState, batches):
    """Train scan over one chunk's corrected batches — the back half
    of the untiered `_epoch_fn` body."""

    def body(state, batch):
      state, loss, correct = self._dp_step(state, batch)
      hop = jnp.sum(batch.num_sampled_nodes, axis=0)
      return state, (loss, correct, jnp.sum(batch.batch >= 0), hop)

    state, (losses, corrects, valids, hops) = jax.lax.scan(
        body, state, batches)
    return (state, losses, jnp.sum(corrects), jnp.sum(valids),
            jnp.sum(hops, axis=0))

  def _eval_consume_fn(self, params, batches):
    def body(carry, batch):
      correct, total = self._dp_eval(params, batch)
      return carry, (correct, total)

    _, (c, t) = jax.lax.scan(body, 0, batches)
    return jnp.sum(c), jnp.sum(t)

  # run()/evaluate() come from `_MeshEpochDriver` — one host driver
  # for the supervised mesh twins


class FusedDistTreeEpoch(_MeshEpochDriver):
  """One-program TREE-LAYOUT data-parallel epochs over the mesh.

  The distributed twin of `loader.fused_tree.FusedTreeEpoch` — the
  flagship scatter-free/sort-free path running against a graph
  SHARDED over the devices: each hop exchanges the per-device level
  frontier to its owners (`_dist_one_hop` — windows come back in the
  tree layout, no dedup/induce step exists at all), all levels'
  features + the seed labels ride ONE capacity-capped
  `dist_gather_multi` exchange, `models.tree.TreeSAGE` aggregates by
  reshape + masked mean, and the optax update pmean-averages
  gradients — the whole epoch as one `lax.scan` SPMD program.

  Measured motivation (r5, single chip): the tree layout runs
  12.4x the subgraph fused step; this class carries the same design
  to the mesh, where the reference has no fused counterpart at all.

  Capacity semantics: level ids past the feature exchange's
  per-owner capacity return ZERO rows (counted in
  ``dist.feature.dropped``) while staying valid in the mean's count
  — the same explicit-overflow contract as the subgraph engines
  (`dist_gather_multi`); ``exchange_slack`` tunes it.

  Args:
    dataset: `DistDataset` (sharded; features + labels).  Tiered
      stores run as chunked tiered fused epochs (module docstring).
    num_neighbors: per-hop fanouts; ``len == model.num_layers``.
    input_nodes: global seed ids (``input_space`` as in the loaders).
    model: a `TreeSAGE`-shaped flax module.
    tx: optax transformation.
    batch_size: PER-DEVICE seed batch size.
    mesh / axis / shuffle / drop_last / seed / exchange_slack /
    remat / fast_compile: as `FusedDistEpoch`.
  """

  def __init__(self, dataset: DistDataset, num_neighbors, input_nodes,
               model, tx: optax.GradientTransformation,
               batch_size: int, mesh: Optional[Mesh] = None,
               axis: str = 'data', shuffle: bool = True,
               drop_last: bool = False, seed: int = 0,
               input_space: str = 'old', exchange_slack='auto',
               exchange_layout=None,
               remat: bool = False, fast_compile: bool = False,
               gns=None):
    from ..loader.node_loader import SeedBatcher
    if dataset.node_features is None or dataset.node_labels is None:
      raise ValueError('FusedDistTreeEpoch needs node features and '
                       'labels')
    if exchange_slack == 'adaptive':
      raise ValueError(
          "exchange_slack='adaptive' retunes on the host between "
          "batches; FusedDistTreeEpoch takes a static slack")
    self.fanouts = tuple(int(k) for k in num_neighbors)
    if getattr(model, 'num_layers', len(self.fanouts)) != \
        len(self.fanouts):
      raise ValueError(
          f'model.num_layers={model.num_layers} must equal '
          f'len(num_neighbors)={len(self.fanouts)}')
    # reuse the sampler scaffolding (mesh, device arrays, telemetry)
    # with no induce machinery — the DistRandomWalker pattern
    self.sampler = DistNeighborSampler(
        dataset, [], mesh=mesh, axis=axis, collect_features=True,
        seed=seed,
        exchange_slack=resolve_exchange_slack(exchange_slack, shuffle),
        exchange_layout=exchange_layout, gns=gns)
    self.ds = dataset
    self.model = model
    self.tx = tx
    self.mesh = self.sampler.mesh
    self.axis = axis
    self.num_parts = dataset.num_partitions
    self.batch_size = int(batch_size)
    seeds = np.asarray(input_nodes).reshape(-1)
    if input_space == 'old' and dataset.old2new is not None:
      seeds = dataset.old2new[seeds]
    self._batcher = SeedBatcher(seeds, self.batch_size * self.num_parts,
                                shuffle, drop_last, seed)
    self._base_key = jax.random.key(seed)
    self._epoch_idx = 0
    apply = model.apply
    self._apply = jax.checkpoint(apply) if remat else apply
    self._eval_apply = apply
    self._sharded_step = self._make_sharded(train=True)
    self._sharded_eval = self._make_sharded(train=False)
    self._compiled = _counted_jit(self._epoch_fn, donate_argnums=(0,),
                                   fast_compile=fast_compile)
    self._compiled_eval = _counted_jit(self._eval_fn,
                                        fast_compile=fast_compile)
    self._tiered = dataset.node_features.is_tiered
    if self._tiered:
      self._sharded_collect = self._make_collect_sharded()
      self._sharded_consume = self._make_consume_sharded(train=True)
      self._sharded_consume_eval = self._make_consume_sharded(
          train=False)
      self._compiled_collect = _counted_jit(self._collect_fn,
                                             fast_compile=fast_compile)
      self._compiled_train = _counted_jit(self._train_fn,
                                           donate_argnums=(0,),
                                           fast_compile=fast_compile)
      self._compiled_eval_consume = _counted_jit(
          self._eval_consume_fn, fast_compile=fast_compile)

  def __len__(self) -> int:
    return len(self._batcher)

  def init_state(self, rng) -> TrainState:
    from ..models.tree import tree_level_sizes
    d = self.ds.node_features.feature_dim
    sizes = tree_level_sizes(self.batch_size, self.fanouts)
    xs = [jnp.zeros((s, d), jnp.float32) for s in sizes]
    masks = [jnp.ones((s,), jnp.bool_) for s in sizes]
    params = self.model.init(rng, xs, masks)
    from .dp import replicate
    return replicate(
        TrainState(params, self.tx.init(params),
                   jnp.zeros((), jnp.int32)), self.mesh)

  # -- per-device body ------------------------------------------------------

  def _level_sizes(self):
    sizes = [self.batch_size]
    for k in self.fanouts:
      sizes.append(sizes[-1] * int(k))
    return sizes

  def _expand_collect(self, seeds, key, indptr_s, indices_s, bounds,
                      fshards_s, lshards_s, hcounts=None,
                      concat: bool = False, gns_bits=None):
    """Tree expansion + one fused feature/label exchange for one
    device's ``[B]`` seed slice.  Returns
    ``(xs, masks, y, stats7, hop_counts)`` — ``hop_counts[h]`` is the
    number of VALID ids in level ``h`` (the tree analog of the
    dedup path's per-hop new-node count, for the padding gauges).

    ``hcounts`` (tiered stores) zeroes feature rows past each owner's
    hot count — the caller overlays the cold tier; ``concat=True``
    returns ``(all_ids, feats, y, stats7, hop_counts)`` in the
    concatenated level layout instead of the split lists (the tiered
    collect phase's shape — the overlay machinery addresses one
    ``[L]`` id table, the consume phase re-splits).

    ``gns_bits`` (cache-aware GNS, tiered path only): hops sample
    through `ops.gns.sample_one_hop_gns` and a CUMULATIVE per-slot
    importance weight (the product of a slot's ancestor edge weights
    — the tree estimator's 1/q correction, GNS §3) rides back with
    the level layout; the consume phase multiplies each level's
    features by it so TreeSAGE's masked means stay unbiased."""
    from .dist_sampler import (_dist_one_hop, _slack_cap,
                               dist_gather_multi)
    from .exchange import dest_histogram
    from .partition_book import range_owner_fn
    slack = self.sampler.exchange_slack
    layout = self.sampler.exchange_layout
    gns = gns_bits is not None
    boost = self.sampler.gns_boost if gns else None
    levels, frontier = [seeds], seeds
    w_levels = [jnp.ones(seeds.shape, jnp.float32)]
    fstats = jnp.zeros((3,), jnp.int32)
    book_spec = self.sampler.book_spec   # trace-time routing constant
    # src->dst range attribution (ISSUE 16/20): the fused tree path
    # must tick the SAME [2P + 1] tail as the dedup sampler — this was
    # the dead feature counter (frontier_ids populated, feature_ids
    # all-zero) on every tiered envelope epoch
    attr_owner = range_owner_fn(bounds)
    attr_fr = jnp.zeros((self.num_parts,), jnp.int32)
    hop_plans = []
    # the exchange and the owners' work carry their own scopes
    # (`dist_sampler`); a scope around them here would claim them, so
    # only what this function does itself is scoped
    for h, k in enumerate(self.fanouts):
      with layer_scope('exchange', 'stats'):
        attr_fr = attr_fr + dest_histogram(frontier, attr_owner,
                                           self.num_parts)
      hop_plans.append((frontier.shape[0], _slack_cap(
          frontier.shape[0], self.num_parts, slack, layout)))
      nbrs, mask, _, hw, st = _dist_one_hop(
          indptr_s, indices_s, None, bounds, frontier, int(k),
          jax.random.fold_in(key, h), self.axis, self.num_parts,
          False, sort_locality=False,
          exchange_capacity=hop_plans[-1][1],
          gns_bits=gns_bits, gns_boost=boost, book_spec=book_spec)
      with layer_scope('sample', f'hop{h}'):
        fstats = fstats + jnp.stack(st)
        nxt = jnp.where(mask, nbrs, -1).reshape(-1)
        levels.append(nxt)
        if gns:
          w_levels.append((w_levels[-1][:, None] * hw).reshape(-1))
        frontier = nxt
    with layer_scope('gather', 'ids'):
      all_ids = jnp.concatenate(levels)
    feature_plan = (all_ids.shape[0], _slack_cap(
        all_ids.shape[0], self.num_parts, slack, layout))
    self._emit_exchange_plan(hop_plans, feature_plan)
    (feats, labels), gst = dist_gather_multi(
        (fshards_s, lshards_s), bounds, all_ids, self.axis,
        self.num_parts, exchange_capacity=feature_plan[1],
        hot_counts=hcounts, book_spec=book_spec)
    with layer_scope('exchange', 'stats'):
      attr_ft = dest_histogram(all_ids, attr_owner, self.num_parts)
      stats7 = jnp.concatenate(
          [fstats, jnp.stack(gst), jnp.zeros((1,), jnp.int32),
           attr_fr, attr_ft, jnp.zeros((1,), jnp.int32)])
      hop_counts = jnp.stack(
          [jnp.sum((lvl >= 0).astype(jnp.int32)) for lvl in levels])
    with layer_scope('gather', 'split'):
      y = labels[:self.batch_size]
      if concat:
        out = (all_ids, feats, y, stats7, hop_counts)
        return out + (jnp.concatenate(w_levels),) if gns else out
      sizes = [lvl.shape[0] for lvl in levels]
      xs, off = [], 0
      for s in sizes:
        xs.append(feats[off:off + s])
        off += s
      masks = [lvl >= 0 for lvl in levels]
    return xs, masks, y, stats7, hop_counts

  def _emit_exchange_plan(self, hop_plans, feature_plan) -> None:
    """Trace time, once per compiled mesh program: the exchange
    layout that was chosen, its slack, and per exchange (each hop's
    frontier, then the one feature and label gather) the ids a device
    offers and the send slots its buffer holds for them — ``slots``
    over ``ids`` is the padding the owners draw and gather over
    (`exchange_stats()` counts it at run time).  An exact exchange
    (no slack) holds ``P * ids`` slots."""
    from ..telemetry.recorder import recorder
    from .exchange import resolve_layout
    slots = lambda n, spec: (self.num_parts * n if spec is None
                             else spec.slots)
    recorder.emit(
        'exchange.plan', scope=type(self).__name__,
        layout=resolve_layout(self.sampler.exchange_layout,
                              self.num_parts),
        slack=self.sampler.exchange_slack, num_parts=self.num_parts,
        batch=self.batch_size,
        frontier_ids=[n for n, _ in hop_plans],
        frontier_slots=[slots(n, spec) for n, spec in hop_plans],
        feature_ids=feature_plan[0],
        feature_slots=slots(*feature_plan))

  def _eval_tail(self, params, xs, masks, y, valid):
    axis = self.axis
    logits = self._eval_apply(params, xs, masks)
    with layer_scope('model', 'metrics'):
      correct = jax.lax.psum(
          jnp.sum((jnp.argmax(logits, -1) == y) & valid), axis)
      total = jax.lax.psum(jnp.sum(valid), axis)
    return correct, total

  def _train_tail(self, state, xs, masks, y, valid, hop_counts):
    """The DP update half of the tree step — shared by the fused
    single-program path and the tiered consume scan."""
    axis, b = self.axis, self.batch_size
    with layer_scope('exchange', 'stats'):
      hop_g = jax.lax.psum(hop_counts, axis)       # global [H+1]
      n_valid = jax.lax.psum(jnp.sum(valid), axis)

    def loss_fn(params):
      logits = self._apply(params, xs, masks)
      with layer_scope('model', 'loss'):
        vf = valid.astype(logits.dtype)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, y.astype(jnp.int32))
        return (ce * vf).sum() / jnp.maximum(vf.sum(), 1.0), logits

    (loss, logits), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(state.params)
    with layer_scope('exchange', 'grads'):
      grads = jax.lax.pmean(grads, axis)
      loss = jax.lax.pmean(loss, axis)
    with layer_scope('optimizer'):
      updates, opt_state = self.tx.update(grads, state.opt_state,
                                          state.params)
      params = optax.apply_updates(state.params, updates)
      new_state = TrainState(params, opt_state, state.step + 1)
      any_valid = n_valid > 0
      state = jax.tree_util.tree_map(
          lambda new, old: jnp.where(any_valid, new, old),
          new_state, state)
    with layer_scope('model', 'metrics'):
      correct = jax.lax.psum(
          jnp.sum((jnp.argmax(logits[:b], -1) == y) & valid), axis)
    return state, loss, correct, n_valid, hop_g

  def _make_sharded(self, train: bool):
    from .shard_map_compat import shard_map
    axis = self.axis

    def per_device(state_or_params, seeds_s, key, indptr_s, indices_s,
                   bounds, fshards_s, lshards_s):
      seeds = seeds_s[0]
      xs, masks, y, stats7, hop_counts = self._expand_collect(
          seeds, key, indptr_s[0], indices_s[0], bounds, fshards_s[0],
          lshards_s[0])
      valid = seeds >= 0
      if not train:
        correct, total = self._eval_tail(state_or_params, xs, masks, y,
                                         valid)
        return correct, total, stats7[None]
      state, loss, correct, n_valid, hop_g = self._train_tail(
          state_or_params, xs, masks, y, valid, hop_counts)
      return state, loss, correct, n_valid, stats7[None], hop_g

    ax = self.axis
    if train:
      out_specs = (P(), P(), P(), P(), P(ax), P())
    else:
      out_specs = (P(), P(), P(ax))
    return shard_map(
        per_device, mesh=self.mesh,
        in_specs=(P(), P(ax), P(), P(ax), P(ax), P(), P(ax), P(ax)),
        out_specs=out_specs)

  # -- tiered fused epochs: collect / consume twins -------------------------

  def _collect_step_bytes(self) -> int:
    nf = self.ds.node_features
    return (self.num_parts * sum(self._level_sizes()) * nf.feature_dim
            * np.dtype(nf.shards.dtype).itemsize)

  def _make_collect_sharded(self):
    """Per-device tree expansion + hot-masked feature/label exchange,
    returning the CONCATENATED level ids + features (the overlay
    machinery's addressing) instead of the split lists.  Under GNS the
    program takes the replicated cached-set bitmask and also returns
    the cumulative per-slot importance weights."""
    from .shard_map_compat import shard_map
    ax = self.axis
    gns = self.sampler.gns

    def per_device(seeds_s, key, indptr_s, indices_s, bounds,
                   fshards_s, lshards_s, hcounts, *rest):
      seeds = seeds_s[0]
      out = self._expand_collect(
          seeds, key, indptr_s[0], indices_s[0], bounds, fshards_s[0],
          lshards_s[0], hcounts=hcounts, concat=True,
          gns_bits=rest[0] if gns else None)
      all_ids, feats, y, stats7, hop_counts = out[:5]
      lead = (all_ids[None], feats[None], y[None], stats7[None],
              hop_counts[None])
      return lead + (out[5][None],) if gns else lead

    n_out = 6 if gns else 5
    return shard_map(
        per_device, mesh=self.mesh,
        in_specs=(P(ax), P(), P(ax), P(ax), P(), P(ax), P(ax), P())
        + ((P(),) if gns else ()),
        out_specs=tuple(P(ax) for _ in range(n_out)))

  def _make_consume_sharded(self, train: bool):
    """Per-device split of the corrected level features + the train or
    eval tail (the back half of `_make_sharded`'s per_device).  Under
    GNS each level's features are scaled by the cumulative importance
    weights BEFORE the model's masked means — the tree form of the
    1/q correction (weight 1 everywhere when the boost never bit)."""
    from .shard_map_compat import shard_map
    ax = self.axis
    sizes = self._level_sizes()
    gns = self.sampler.gns

    def per_device(state_or_params, seeds_s, ids_s, feats_s, y_s,
                   hop_s, *rest):
      seeds = seeds_s[0]
      ids, feats, y = ids_s[0], feats_s[0], y_s[0]
      w = rest[0][0] if gns else None
      xs, masks, off = [], [], 0
      for s in sizes:
        lvl = feats[off:off + s]
        if gns:
          lvl = lvl * w[off:off + s][:, None].astype(lvl.dtype)
        xs.append(lvl)
        masks.append(ids[off:off + s] >= 0)
        off += s
      valid = seeds >= 0
      if not train:
        correct, total = self._eval_tail(state_or_params, xs, masks, y,
                                         valid)
        return correct, total
      return self._train_tail(state_or_params, xs, masks, y, valid,
                              hop_s[0])

    if train:
      out_specs = (P(), P(), P(), P(), P())
    else:
      out_specs = (P(), P())
    return shard_map(
        per_device, mesh=self.mesh,
        in_specs=(P(), P(ax), P(ax), P(ax), P(ax), P(ax))
        + ((P(ax),) if gns else ()),
        out_specs=out_specs)

  def _collect_fn(self, seeds_all: jax.Array, keys: jax.Array,
                  arrs: dict):
    gns = 'gns' in arrs

    def body(_, xs):
      key_i, seeds = xs
      outs = self._sharded_collect(
          seeds, key_i, arrs['indptr'], arrs['indices'],
          arrs['bounds'], arrs['fshards'], arrs['lshards'],
          arrs['hcounts'], *((arrs['gns'],) if gns else ()))
      ids, feats, y, stats, hops = outs[:5]
      d = dict(seeds=seeds, ids=ids, feats=feats, y=y, hops=hops)
      if gns:
        d['w'] = outs[5]
      return 0, (d, stats)

    _, (data, stats) = jax.lax.scan(body, 0, (keys, seeds_all))
    return data, stats

  def _overlay_chunk(self, data):
    data['feats'] = self._overlay_stacked(data['feats'], data['ids'])
    return data

  def _consume_args(self, d):
    return ((d['w'],) if 'w' in d else ())

  def _train_fn(self, state: TrainState, data):
    def body(state, d):
      state, loss, correct, n_valid, hop_g = self._sharded_consume(
          state, d['seeds'], d['ids'], d['feats'], d['y'], d['hops'],
          *self._consume_args(d))
      return state, (loss, correct, n_valid, hop_g)

    state, (losses, corrects, valids, hops) = jax.lax.scan(
        body, state, data)
    return (state, losses, jnp.sum(corrects), jnp.sum(valids),
            jnp.sum(hops, axis=0))

  def _eval_consume_fn(self, params, data):
    def body(carry, d):
      correct, total = self._sharded_consume_eval(
          params, d['seeds'], d['ids'], d['feats'], d['y'], d['hops'],
          *self._consume_args(d))
      return carry, (correct, total)

    _, (c, t) = jax.lax.scan(body, 0, data)
    return jnp.sum(c), jnp.sum(t)

  # -- the one program ------------------------------------------------------

  def _epoch_fn(self, state: TrainState, seeds_all: jax.Array,
                key: jax.Array, arrs: dict):
    def body(state, xs_in):
      i, seeds = xs_in
      state, loss, correct, valid, stats, hop = self._sharded_step(
          state, seeds, jax.random.fold_in(key, i), arrs['indptr'],
          arrs['indices'], arrs['bounds'], arrs['fshards'],
          arrs['lshards'])
      return state, (loss, correct, valid, stats, hop)

    steps = jnp.arange(seeds_all.shape[0], dtype=jnp.int32)
    state, (losses, corrects, valids, stats, hops) = jax.lax.scan(
        body, state, (steps, seeds_all))
    return (state, losses, jnp.sum(corrects), jnp.sum(valids),
            jnp.sum(stats, axis=0), jnp.sum(hops, axis=0))

  def _eval_fn(self, params, seeds_all: jax.Array, key: jax.Array,
               arrs: dict):
    def body(carry, xs_in):
      i, seeds = xs_in
      correct, total, stats = self._sharded_eval(
          params, seeds, jax.random.fold_in(key, i), arrs['indptr'],
          arrs['indices'], arrs['bounds'], arrs['fshards'],
          arrs['lshards'])
      return carry, (correct, total, stats)

    steps = jnp.arange(seeds_all.shape[0], dtype=jnp.int32)
    _, (correct, total, stats) = jax.lax.scan(
        body, 0, (steps, seeds_all))
    return jnp.sum(correct), jnp.sum(total), jnp.sum(stats, axis=0)

  # run()/evaluate() come from `_MeshEpochDriver`


class FusedDistLinkEpoch(_MeshEpochDriver):
  """One-program data-parallel LINK-PREDICTION epochs on the mesh.

  The link member of the fused mesh family: the scan body runs the
  full distributed link step (per-device seed edges + collective
  strict negatives against the GLOBAL sharded graph + endpoint
  expansion + feature collection — the same program
  `DistLinkNeighborSampler` dispatches per batch) followed by the DP
  unsupervised update (`make_dp_unsupervised_step`: binary sigmoid or
  max-margin triplet link loss by the metadata keys, pmean gradients).

  Same constraints as `FusedDistEpoch`: a static exchange slack;
  tiered stores run as chunked tiered fused epochs (module
  docstring).

  Args:
    dataset: `DistDataset` (sharded layout).
    num_neighbors: per-hop fanouts for the endpoint expansion.
    edge_label_index: ``[2, E]`` (or ``(rows, cols)``) seed edges.
    apply_fn / tx: embedding model apply + optax transform.
    batch_size: PER-DEVICE seed-edge batch size.
    neg_sampling: ``'binary'`` / ``('triplet', amount)``.
    edge_label: optional labels (binary mode applies the reference's
      +1 shift via `pack_link_seeds`).
    remat: checkpoint the model forward (see `FusedDistEpoch`).
  """

  def __init__(self, dataset: DistDataset, num_neighbors,
               edge_label_index, apply_fn: Callable,
               tx: optax.GradientTransformation, batch_size: int,
               neg_sampling='binary', edge_label=None,
               mesh: Optional[Mesh] = None, axis: str = 'data',
               shuffle: bool = True, drop_last: bool = False,
               seed: int = 0, input_space: str = 'old',
               exchange_slack='auto', exchange_layout=None,
               remat: bool = False,
               fast_compile: bool = False, gns=None):
    from ..loader.node_loader import SeedBatcher
    if dataset.node_features is None:
      raise ValueError('FusedDistLinkEpoch needs node features')
    if exchange_slack == 'adaptive':
      raise ValueError(
          "exchange_slack='adaptive' retunes between batches on the "
          "host; FusedDistLinkEpoch takes a static slack ('auto' or "
          'a number) — or use DistLinkNeighborLoader')
    slack = resolve_exchange_slack(exchange_slack, shuffle)
    self.sampler = DistLinkNeighborSampler(
        dataset, num_neighbors, neg_sampling=neg_sampling, mesh=mesh,
        axis=axis, collect_features=True, seed=seed,
        exchange_slack=slack, exchange_layout=exchange_layout, gns=gns)
    self.ds = dataset
    self.mesh = self.sampler.mesh
    self.axis = axis
    self.num_parts = dataset.num_partitions
    self.batch_size = int(batch_size)

    self.pairs = pack_link_seeds_relabeled(        # [E, 2|3]
        edge_label_index, edge_label, self.sampler.neg_mode, dataset,
        input_space)
    self._batcher = SeedBatcher(self.pairs,
                                self.batch_size * self.num_parts,
                                shuffle, drop_last, seed)
    self._base_key = jax.random.key(seed)
    self._epoch_idx = 0
    step_apply = jax.checkpoint(apply_fn) if remat else apply_fn
    self._dp_step = make_dp_unsupervised_step(step_apply, tx, self.mesh,
                                              axis)
    self._dist_step = self.sampler.step_for_pairs(
        self.batch_size, self.pairs.shape[1])
    self._resolve_dist_step = lambda: self.sampler.step_for_pairs(
        self.batch_size, self.pairs.shape[1])
    self._apply = apply_fn            # un-remat'd: evaluate() is fwd-only
    self._compiled = _counted_jit(       # see FusedDistEpoch note
        self._epoch_fn, donate_argnums=(0,), fast_compile=fast_compile)
    self._compiled_eval = _counted_jit(self._auc_fn,
                                        fast_compile=fast_compile)
    self._tiered = dataset.node_features.is_tiered
    if self._tiered:
      self._compiled_collect = _counted_jit(self._collect_fn,
                                             fast_compile=fast_compile)
      self._compiled_train = _counted_jit(self._train_fn,
                                           donate_argnums=(0,),
                                           fast_compile=fast_compile)
      self._compiled_auc_consume = _counted_jit(
          self._auc_consume_fn, fast_compile=fast_compile)

  def __len__(self) -> int:
    return len(self._batcher)

  # -- the one program ------------------------------------------------------

  def _epoch_fn(self, state: TrainState, pairs_all: jax.Array,
                key: jax.Array, arrs: dict):
    """``[S, P, B, 2|3]`` seed-edge batches → S fused
    negatives+exchange+collect+train steps."""

    def body(state, xs):
      i, pairs = xs
      batch, stats = self._link_batch(pairs, jax.random.fold_in(key, i),
                                      arrs)
      state, loss = self._dp_step(state, batch)
      valid = jnp.sum((pairs[:, :, 0] >= 0) & (pairs[:, :, 1] >= 0))
      return state, (loss, valid, stats)

    steps = jnp.arange(pairs_all.shape[0], dtype=jnp.int32)
    state, (losses, valids, stats) = jax.lax.scan(
        body, state, (steps, pairs_all))
    return state, losses, jnp.sum(valids), jnp.sum(stats, axis=0)

  def _link_batch(self, pairs: jax.Array, key_i: jax.Array, arrs: dict):
    """One fused distributed link sample+collect (negatives +
    endpoint expansion + features): shared front half of the train
    and eval scan bodies."""
    from ..loader.transform import Batch
    extra = (arrs['gns'],) if 'gns' in arrs else ()
    outs = self._dist_step(
        arrs['indptr'], arrs['indices'], arrs['eids'],
        arrs['bounds'], pairs, arrs['fshards'], arrs['lshards'],
        arrs['cids'], arrs['crows'], arrs['efshards'],
        arrs['ebounds'], arrs['hcounts'], *extra, key_i)
    (nodes, _count, row, col, edge, seed_local, x, y, ef, nsn,
     stats) = outs[:11]
    ew = outs[11] if 'gns' in arrs else None
    (eli, elab, elab_mask, src_idx, dst_pos, dst_neg) = \
        outs[12:] if 'gns' in arrs else outs[11:]
    md = link_step_metadata(self.sampler.neg_mode, seed_local, eli,
                            elab, elab_mask, src_idx, dst_pos, dst_neg)
    if ew is not None:
      md['edge_weight'] = ew
    batch = Batch(
        x=x, y=y, edge_index=jnp.stack([row, col], axis=1),
        edge_attr=ef, node=nodes, node_mask=nodes >= 0,
        edge_mask=row >= 0, edge=edge, batch=pairs[:, :, 0],
        batch_size=self.batch_size, num_sampled_nodes=nsn, metadata=md)
    return batch, stats

  # -- tiered fused epochs (chunked collect/train twins) --------------------

  def _collect_step_bytes(self) -> int:
    exp_seeds, _ = self.sampler._expansion_seeds(self.batch_size)
    cap = self.sampler.node_capacity(exp_seeds)
    nf = self.ds.node_features
    return (self.num_parts * cap * nf.feature_dim
            * np.dtype(nf.shards.dtype).itemsize)

  def _collect_fn(self, pairs_all: jax.Array, keys: jax.Array,
                  arrs: dict):
    def body(_, xs):
      key_i, pairs = xs
      batch, stats = self._link_batch(pairs, key_i, arrs)
      return 0, (batch, stats)

    _, (batches, stats) = jax.lax.scan(body, 0, (keys, pairs_all))
    return batches, stats

  def _overlay_chunk(self, batches):
    batches.x = self._overlay_stacked(batches.x, batches.node)
    return batches

  def _train_fn(self, state: TrainState, batches):
    def body(state, batch):
      state, loss = self._dp_step(state, batch)
      # SeedBatcher pads whole rows, so a valid src implies the pair
      return state, (loss, jnp.sum(batch.batch >= 0))

    state, (losses, valids) = jax.lax.scan(body, state, batches)
    return state, losses, jnp.sum(valids)

  def _auc_consume_fn(self, params, batches):
    auc_step = self._make_auc_step()

    def body(carry, batch):
      wins, total = auc_step(params, batch)
      return carry, (wins, total)

    _, (wins, totals) = jax.lax.scan(body, 0, batches)
    return jnp.sum(wins), jnp.sum(totals)

  def _make_auc_step(self):
    """Per-device embedding + pairwise (pos > neg) win counts, psum'd
    over the mesh — shared by the single-program `_auc_fn` and the
    tiered `_auc_consume_fn`."""
    from .shard_map_compat import shard_map
    b, axis = self.batch_size, self.axis

    def per_device(params, batch):
      batch = jax.tree_util.tree_map(lambda v: v[0], batch)
      emb = self._apply(params, batch.x, batch.edge_index,
                        batch.edge_mask)
      eli = batch.metadata['edge_label_index']      # [2, b + nn]
      mask = batch.metadata['edge_label_mask']
      score = (emb[eli[0]] * emb[eli[1]]).sum(-1)
      ps, ns = score[:b], score[b:]
      pv, nv = mask[:b], mask[b:]
      pair_ok = pv[:, None] & nv[None, :]
      # float32 accumulation: int32 pair counts overflow past ~2k
      # products-scale batches
      wins = (jnp.sum((ps[:, None] > ns[None, :]) & pair_ok,
                      dtype=jnp.float32)
              + 0.5 * jnp.sum((ps[:, None] == ns[None, :]) & pair_ok,
                              dtype=jnp.float32))
      wins = jax.lax.psum(wins, axis)
      total = jax.lax.psum(jnp.sum(pair_ok, dtype=jnp.float32), axis)
      return wins, total

    return shard_map(per_device, mesh=self.mesh,
                     in_specs=(P(), P(self.axis)),
                     out_specs=(P(), P()))

  def _auc_fn(self, params, pairs_all: jax.Array, key: jax.Array,
              arrs: dict):
    """Scan body of `evaluate`: per batch, the full distributed link
    step (fresh strict negatives), per-device embedding + pairwise
    (pos > neg) win counts, psum'd over the mesh — the SPMD twin of
    `loader.fused.FusedLinkEpoch._auc_fn` (batched rank-sum AUC,
    per-device positive/negative blocks)."""
    auc_step = self._make_auc_step()

    def body(carry, xs):
      i, pairs = xs
      batch, stats = self._link_batch(pairs, jax.random.fold_in(key, i),
                                      arrs)
      wins, total = auc_step(params, batch)
      return carry, (wins, total, stats)

    steps = jnp.arange(pairs_all.shape[0], dtype=jnp.int32)
    _, (wins, totals, stats) = jax.lax.scan(body, 0, (steps, pairs_all))
    return jnp.sum(wins), jnp.sum(totals), jnp.sum(stats, axis=0)

  def evaluate(self, params, edge_label_index,
               input_space: str = 'old') -> float:
    """Held-out link AUC over ``edge_label_index`` as ONE SPMD scan
    program — the mesh twin of `loader.fused.FusedLinkEpoch.evaluate`
.  Binary negative-sampling mode only (triplet
    mode's per-src negatives make precision@rank the right metric)."""
    from ..loader.node_loader import SeedBatcher
    if self.sampler.neg_mode != 'binary':
      raise ValueError('evaluate() needs binary negative sampling')
    pairs = pack_link_seeds_relabeled(edge_label_index, None, 'binary',
                                      self.ds, input_space)
    if pairs.shape[0] == 0:
      raise ValueError('evaluate() got an empty split')
    # eval batches must carry the SAME pair width the compiled dist
    # step was built for
    if pairs.shape[1] != self.pairs.shape[1]:
      pad = np.ones((pairs.shape[0],
                     self.pairs.shape[1] - pairs.shape[1]), np.int64)
      pairs = np.concatenate([pairs, pad], axis=1)
    ev = SeedBatcher(pairs, self.batch_size * self.num_parts,
                     shuffle=False)
    stacked = np.stack(list(ev)).reshape(-1, self.num_parts,
                                         self.batch_size,
                                         pairs.shape[1])
    if self._tiered:
      key = self._eval_key()
      s = stacked.shape[0]
      chunk = self._cold_chunk_steps(s)
      wins = total = 0.0
      for c0, real, part, keys in self._tiered_chunks(stacked, key,
                                                      chunk):
        batches, stats = self._compiled_collect(
            self._put_batches(part), keys, self._chunk_arrs())
        self.sampler._accumulate_stats(jnp.sum(stats[:real], axis=0))
        batches = self._overlay_chunk(batches)
        w, t = self._compiled_auc_consume(params, batches)
        wins += float(w)
        total += float(t)
      return wins / max(total, 1.0)
    wins, total, stats = self._compiled_eval(
        params, self._put_batches(stacked), self._eval_key(),
        self._chunk_arrs())
    self.sampler._accumulate_stats(stats)
    return float(wins) / max(float(total), 1.0)

  # -- host driver ----------------------------------------------------------

  def _train_chunk(self, state, data):
    # link train has no accuracy and no hop gauges — adapt to the
    # shared _run_tiered 5-tuple (None accumulators stay None)
    state, ls, val = self._compiled_train(state, data)
    return state, ls, None, val, None

  def run(self, state: TrainState) -> Tuple[TrainState, 'EpochStats']:
    """One epoch; ``state`` must be mesh-replicated and is DONATED.
    ``stats.seeds`` counts valid seed EDGES; accuracy reads 0 (the
    unsupervised objective has no accuracy)."""
    from ..loader.fused import EpochStats
    from ..utils.profiling import step_annotation
    flat = np.stack(list(self._batcher))           # [S, P*B, 2|3]
    pairs = flat.reshape(-1, self.num_parts, self.batch_size,
                         flat.shape[-1])
    key = self._next_epoch_key()
    with step_annotation('fused_dist_link_epoch', self._epoch_idx):
      if self._tiered:
        # the shared chunked driver: snapshot seams, stall watchdog
        # AND degraded rollback — the link driver must honor the same
        # preemption contract as the node twins (link stats carry
        # valid-pair counts; no accuracy, no hop gauges)
        state, losses, _corr, valid, _hops = self._run_tiered(
            state, pairs, key)
        return state, EpochStats(losses, jnp.zeros((), jnp.int32),
                                 valid)
      state, losses, valid, stats = self._compiled(
          state, self._put_batches(pairs), key, self._chunk_arrs())
    self.sampler._accumulate_stats(stats)
    return state, EpochStats(losses, jnp.zeros((), jnp.int32), valid)
