"""Whole-epoch fused training: sample → collate → train in ONE program.

The per-batch path (`NeighborLoader` + `make_supervised_step`) dispatches
several XLA programs per step — sample, label gather, feature gather,
train step — each ~1 ms of device work on the headline config, so host
dispatch latency is a visible fraction of the epoch.  The reference has
the same shape (its loader feeds a separate DDP step per batch,
`examples/train_sage_ogbn_products.py:90-130`) and eats the overhead in
CUDA-stream pipelining; the TPU-idiomatic answer is stronger: put the
WHOLE epoch under one `jax.jit` as a `lax.scan` over seed batches.

  * seeds for all steps upload once per epoch as a ``[S, B]`` array;
  * the scan body = multi-hop sample → device collate → optax update,
    compiled once and reused for every epoch of the same length;
  * no host↔device chatter inside the epoch at all — the host enqueues
    one program and blocks on the final state.

Constraints (checked at construction):
  * homogeneous graphs (the hetero per-type dict collation is
    per-batch territory).

TIERED Features (``split_ratio < 1``) run as **tiered fused epochs**
(ISSUE 5): each chunk of ``max_steps_per_program`` (or the auto
``GLT_FUSED_COLD_CHUNK`` bound) dispatches a sample-only collect
scan, then the host cold service fills ``x`` per step through the
cache-aware tiered `Feature` lookup (HBM victim-cache hits are a
device gather; misses host-gather + admit — `data.cold_cache`), then
a train scan consumes the corrected batches.  The fused dispatch
structure survives tiering at O(S/chunk) programs.

This is a TPU-first capability with no reference counterpart: the
torch loader cannot fuse Python-loop epochs into one graph.
"""
from __future__ import annotations

import inspect
import weakref
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..data.dataset import Dataset
from ..data.feature import _device_gather
from ..models.train import (TrainState, make_extracted_eval_step,
                            make_extracted_supervised_step)
from ..ops.pallas_gather import pallas_enabled
from ..ops.pallas_sample import fused_sample_enabled
from ..ops.pallas_window import prepare_window_table
from ..sampler.base import NegativeSampling
from ..sampler.neighbor_sampler import (NeighborSampler, _multihop_sample,
                                        hop_capacities, hop_windows,
                                        link_metadata, link_plan,
                                        link_seeds)
from ..utils.profiling import metrics, step_annotation
from .link_loader import EdgeSeedBatcher
from .node_loader import SeedBatcher
from .transform import Batch, _gather_labels


#: `fast_compile` option: skip the EXPENSIVE LLVM passes for a big
#: scan program whose COMPILE wall, not runtime, is the cost — dev
#: iteration and CPU-mesh validation.  Measured at the bench shape
#: (P=8, fanout [15,10,5], 3-layer 256-hidden SAGE): ~38% off the
#: scan compile.  Deliberately NOT `xla_backend_optimization_level=0`:
#: that leaves the graph so unfused that CPU codegen gets SLOWER at
#: big shapes (measured: the B=512 compile blew past 2x baseline).
_FAST_COMPILE_OPTIONS = {'xla_llvm_disable_expensive_passes': True}


def _counted_jit(fn, fast_compile: bool = False, **jit_kwargs):
  """`jax.jit` plus PER-CALLABLE counters — ``call.calls`` and
  ``call.compiles`` — so a caller can pin "this program never
  recompiled" without diffing the process-global metrics registry
  (`driver_compile_count`, and the serving plane's
  zero-recompile-after-warmup assertion in `serving.engine`).
  ``fast_compile`` trades runtime for compile wall (see
  `_FAST_COMPILE_OPTIONS`).

  A dispatch "compiled" when it grew the jit's in-memory executable
  table; whether XLA built that executable or JAX's persistent
  compilation cache supplied it is the process's business
  (`utils.compile_cache`), not this wrapper's.

  Every dispatch also feeds the telemetry plane: an in-memory
  executable hit ticks ``fused.compile.hits``; a dispatch that
  compiled ticks ``fused.compile.misses`` + ``fused.compile.secs`` and
  emits a ``fused.compile`` flight-recorder event whose ``secs`` is
  the wall of that dispatch (compile + first execution).

  A bound method is held weakly: a driver keeps its programs, so a
  program that kept the driver would make a cycle, and the tables the
  driver holds (a feature tier of gigabytes) would outlive the last
  reference to the driver until a collection."""
  import time as _time
  from ..telemetry.recorder import recorder
  if fast_compile:
    jit_kwargs = dict(jit_kwargs,
                      compiler_options=_FAST_COMPILE_OPTIONS)
  name = getattr(fn, '__qualname__', None) or getattr(
      fn, '__name__', 'jit_fn')
  if inspect.ismethod(fn):
    method = weakref.WeakMethod(fn)

    def target(*args, **kwargs):
      return method()(*args, **kwargs)

    # the method's name (the program's) and signature (its static and
    # donated arguments), without `functools.wraps`' strong
    # ``__wrapped__``
    target.__name__, target.__qualname__ = fn.__name__, name
    target.__module__ = fn.__module__
    target.__signature__ = inspect.signature(fn)
    fn = target
  compiled = jax.jit(fn, **jit_kwargs)

  def call(*args, **kwargs):
    before = compiled._cache_size()
    t0 = _time.perf_counter()
    call.calls += 1
    out = compiled(*args, **kwargs)
    if compiled._cache_size() > before:
      dt = _time.perf_counter() - t0
      call.compiles += 1
      metrics.inc('fused.compile.misses')
      metrics.inc('fused.compile.secs', dt)
      recorder.emit('fused.compile', fn=name, secs=round(dt, 3))
    else:
      metrics.inc('fused.compile.hits')
    return out

  call.jitted = compiled         # escape hatch for lower()/inspection
  call.calls = 0
  call.compiles = 0
  return call


#: every `_counted_jit` program attribute a fused epoch driver (this
#: module, `loader.fused_tree`, `parallel.fused`) may hold — the scan
#: set of `driver_compile_count`
_COMPILED_ATTRS = ('_compiled', '_compiled_eval', '_compiled_collect',
                   '_compiled_train', '_compiled_eval_consume',
                   '_compiled_auc_consume')


def driver_compile_count(driver) -> int:
  """Total XLA compiles across a fused driver's `_counted_jit`
  programs (the per-callable counters) — the epoch-driver twin of
  `serving.engine.ServingEngine.compile_count`.  Snapshot it before a
  steady-state window and compare after: a nonzero delta means an
  epoch shape escaped chunking/bucketing and silently paid a compile
  (the exact failure `max_steps_per_program` and the serving bucket
  ladder exist to prevent)."""
  return sum(getattr(driver, a).compiles for a in _COMPILED_ATTRS
             if getattr(driver, a, None) is not None
             and hasattr(getattr(driver, a), 'compiles'))


#: default steps per tiered-fused chunk when the auto budget does not
#: bind (override with GLT_FUSED_COLD_CHUNK)
DEFAULT_COLD_CHUNK = 8
#: auto chunk budget: bytes of stacked collect output per chunk the
#: host cold-service phase holds live (the stacked feature tensor
#: dominates)
COLD_CHUNK_BYTES = 1 << 30


def resolve_cold_chunk(per_step_bytes: int, total_steps: int) -> int:
  """Steps per tiered-fused chunk: ``GLT_FUSED_COLD_CHUNK`` wins;
  otherwise `DEFAULT_COLD_CHUNK` clamped so one chunk's stacked
  collect output stays under `COLD_CHUNK_BYTES`."""
  import os as _os
  env = _os.environ.get('GLT_FUSED_COLD_CHUNK')
  if env:
    try:
      return max(min(int(env), total_steps), 1)
    except ValueError:
      pass
  by_mem = max(COLD_CHUNK_BYTES // max(per_step_bytes, 1), 1)
  return max(min(DEFAULT_COLD_CHUNK, by_mem, total_steps), 1)


class _SnapshotHooks:
  """Chunk-boundary snapshot/resume for the fused epoch drivers (the
  `utils.checkpoint` DataPlaneState protocol, driver-shaped) — shared
  by the single-chip classes here and the mesh drivers in
  `parallel.fused`, so the save/restore contracts cannot drift.

  Also hosts `_init_fused_sampling`, the r19 Pallas fused-sampler
  resolution shared by the homo/link drivers (hetero stays on the
  XLA path).

  Lifecycle::

      snap = fused.attach_snapshots()        # GLT_SNAPSHOT_DIR, or
      fused.attach_snapshots(SnapshotManager(dir, every=2))
      state, stats = fused.run(state)        # saves at chunk seams
      # ... preemption; in a fresh process, same constructor args:
      fused.attach_snapshots(snap_dir_manager)
      state = fused.restore_from_snapshot(state)   # mid-epoch rewind
      state, stats = fused.run(state)              # finishes the epoch

  The snapshot payload holds (a) the DATA-PLANE state — epoch
  counter, batcher RNG (epoch-start capture: resume RE-DRAWS the
  interrupted epoch's permutation), cold-cache rings — (b) the epoch
  PROGRESS (next chunk offset + per-step losses/correct/valid
  accumulated so far), and (c) the TrainState as host copies.  Resume
  is byte-identical: same permutation, same ``fold_in(epoch_key,
  chunk_offset)`` key schedule, partial stats stitched back in front
  of the freshly computed remainder.
  """

  _snap = None
  _resume_progress = None
  _use_fused = False
  _win_e = 0

  def _init_fused_sampling(self, graph) -> None:
    """Resolve GLT_PALLAS_SAMPLE once per driver (the epoch programs
    compile once, so the dispatch is baked per driver — value-
    identical either way) and stage the O(E) window repack into the
    jit-argument dict so the kernel's DMA table rides the same
    no-closure discipline as the other big tables."""
    self._use_fused = fused_sample_enabled()
    self._win_e = 0
    self._dev['win2d'] = None
    if self._use_fused:
      win2d, e = prepare_window_table(graph.indices)
      self._dev['win2d'] = win2d
      self._win_e = int(e)

  def attach_snapshots(self, manager=None):
    """Attach a `SnapshotManager` (``None`` builds one from
    ``GLT_SNAPSHOT_DIR`` when set; returns the manager or None)."""
    if manager is None:
      from ..utils.checkpoint import (SnapshotManager,
                                      snapshot_dir_from_env)
      if snapshot_dir_from_env() is None:
        return None
      manager = SnapshotManager()
    self._snap = manager
    return manager

  # -- per-driver state hooks (overridden by the mesh drivers) ------------
  def data_plane_state(self) -> dict:
    st = {'epoch_idx': self._epoch_idx,
          'dispatch_idx': getattr(self, '_dispatch_idx', 0),
          'batcher': self._batcher.state_dict()}
    feat = getattr(self, '_feat', None)
    if feat is not None and getattr(self, '_tiered', False):
      st['feat'] = feat.state_dict()
    return st

  def load_data_plane_state(self, plane: dict) -> None:
    # run() pre-increments the epoch counter, so the rewound value is
    # "one before the interrupted epoch"; the batcher rewinds its RNG
    # to that epoch's start so run() re-draws the same permutation
    self._epoch_idx = int(np.asarray(plane['epoch_idx'])) - 1
    self._dispatch_idx = int(np.asarray(plane.get('dispatch_idx', 0)))
    self._batcher.load_state_dict(plane['batcher'], mid_epoch=True)
    feat = getattr(self, '_feat', None)
    if feat is not None and 'feat' in plane:
      feat.load_state_dict(plane['feat'])

  def _state_to_device(self, train_host):
    """Host TrainState pytree → device, driver-appropriately (the
    mesh drivers replicate over their mesh instead)."""
    return jax.tree_util.tree_map(jnp.asarray, train_host)

  def restore_from_snapshot(self, state_template):
    """Load the newest snapshot: rewind the data plane and return the
    TrainState to continue from (validated against
    ``state_template``'s structure/dtypes/shapes —
    `CheckpointMismatchError` on a stale snapshot).  ``None`` when the
    directory holds no snapshot; the caller keeps its fresh state."""
    if self._snap is None:
      raise ValueError('restore_from_snapshot() needs '
                       'attach_snapshots() first')
    payload = self._snap.restore_latest()
    if payload is None:
      return None
    from ..utils.checkpoint import validate_tree
    self.load_data_plane_state(payload['plane'])
    self._resume_progress = payload['progress']
    train = payload.get('train')
    if train is None:
      return None
    validate_tree(train,
                  jax.tree_util.tree_map(np.asarray, state_template))
    return self._state_to_device(train)

  # -- run()-side helpers -------------------------------------------------
  def _take_resume(self, chunk_steps: int):
    """Pop the pending resume progress (one epoch continuation per
    restore).  Returns ``(skip_before, losses_list, correct, valid,
    extra)`` — ``extra`` carries driver-specific partials (the mesh
    tree driver's hop counts)."""
    prog = self._resume_progress
    if prog is None:
      return 0, [], None, None, {}
    self._resume_progress = None
    saved_chunk = int(np.asarray(prog.get('chunk_steps', chunk_steps)))
    if saved_chunk != chunk_steps:
      from ..utils.checkpoint import CheckpointMismatchError
      raise CheckpointMismatchError(
          f'snapshot was taken with chunk size {saved_chunk}, this '
          f'process resolves {chunk_steps} — resume with the same '
          f'GLT_FUSED_COLD_CHUNK / max_steps_per_program',
          path='progress.chunk_steps')
    losses = np.asarray(prog['losses'])
    losses_list = [losses] if losses.size else []
    correct = prog.get('correct')
    valid = prog.get('valid')
    extra = {k: v for k, v in prog.items()
             if k not in ('losses', 'correct', 'valid', 'epoch',
                          'next_chunk', 'chunk_steps')}
    return (int(np.asarray(prog['next_chunk'])), losses_list, correct,
            valid, extra)

  def _save_chunk_snapshot(self, state, next_chunk: int,
                           chunk_steps: int, losses, correct, valid,
                           force: bool = False, extra_fn=None,
                           **extra) -> None:
    """One chunk-boundary save when due (``force`` bypasses the
    cadence — epoch-entry rollback targets and epoch-end saves).
    ``extra_fn`` defers expensive extras (a device sync) to the saves
    that actually happen."""
    if self._snap is None:
      return
    if not force and not self._snap.due():
      return
    if extra_fn is not None:
      extra = {**extra, **extra_fn()}
    progress = {
        'epoch': self._epoch_idx, 'next_chunk': int(next_chunk),
        'chunk_steps': int(chunk_steps),
        'losses': (np.concatenate([np.asarray(l) for l in losses])
                   if losses else np.zeros((0,), np.float32)),
    }
    if correct is not None:
      progress['correct'] = np.asarray(correct)
    if valid is not None:
      progress['valid'] = np.asarray(valid)
    for k, v in extra.items():
      if v is not None:
        progress[k] = np.asarray(v)
    self._snap.save(self.data_plane_state(), progress, train=state)


class EpochStats:
  """Lazy epoch statistics: holds DEVICE arrays; any numeric access
  syncs.  Epoch loops that don't read stats dispatch epochs back to
  back with zero host↔device round trips; an eager ``float()`` per
  epoch would block the host on every dispatch."""

  def __init__(self, losses: jax.Array, correct: jax.Array,
               valid: jax.Array):
    self.losses = losses

    self._correct = correct
    self._valid = valid

  @property
  def loss(self) -> float:
    return float(self.losses.mean())

  @property
  def correct(self) -> int:
    return int(self._correct)

  @property
  def seeds(self) -> int:
    return int(self._valid)

  @property
  def accuracy(self) -> float:
    return self.correct / max(self.seeds, 1)

  def __getitem__(self, key: str):
    return getattr(self, key)

  def __repr__(self):
    return f'EpochStats(steps={self.losses.shape[0]}, <lazy>)'


class _SupervisedScanEpoch(_SnapshotHooks):
  """Shared epoch driver for the supervised fused twins: subclasses
  supply ``_sample_collate(seeds, key, dev, use_pallas) -> batch`` and
  ``_step(state, batch) -> (state, loss, correct)`` plus the
  ``_batcher`` / ``_base_key`` / ``_dev`` / ``_compiled`` state; this
  mixin owns the scan body and the host driver so the donation and
  stats contracts cannot drift between the homo and hetero paths."""

  def __len__(self) -> int:
    return len(self._batcher)

  def _epoch_fn(self, state: TrainState, seeds_all: jax.Array,
                key: jax.Array, dev: dict, use_pallas: bool):
    """``[S, B]`` seed batches → S fused sample+collate+train steps."""

    def body(state, xs):
      i, seeds = xs
      batch = self._sample_collate(seeds, jax.random.fold_in(key, i),
                                   dev, use_pallas)
      new_state, loss, correct = self._step(state, batch)
      # fully-padded steps (epoch-length chunking) must be state
      # no-ops: zero grads still move adam's moments/bias correction
      any_valid = jnp.any(seeds >= 0)
      state = jax.tree_util.tree_map(
          lambda new, old: jnp.where(any_valid, new, old),
          new_state, state)
      return state, (loss, correct, jnp.sum(seeds >= 0))

    steps = jnp.arange(seeds_all.shape[0], dtype=jnp.int32)
    state, (losses, corrects, valids) = jax.lax.scan(
        body, state, (steps, seeds_all))
    return state, losses, jnp.sum(corrects), jnp.sum(valids)

  def _chunks(self, seeds: np.ndarray):
    """Yield ``(chunk_offset, real_steps, [chunk, B] piece)``: the
    epoch split into fixed-size dispatches of ONE compiled program
    (every epoch length reuses one compile; the
    tail pads with INVALID_ID rows, which the scan body no-ops).
    Tiered epochs without an explicit ``max_steps_per_program`` get
    the auto cold-chunk bound (`resolve_cold_chunk`) — each chunk's
    stacked collect output must fit the host cold-service budget."""
    s = seeds.shape[0]
    chunk = getattr(self, '_chunk', None)
    if chunk is None and getattr(self, '_tiered', False):
      chunk = resolve_cold_chunk(self._collect_step_bytes(), s)
    chunk = chunk or s
    for c0 in range(0, s, chunk):
      part = seeds[c0:c0 + chunk]
      real = part.shape[0]
      if real < chunk:
        pad = np.full((chunk - real,) + seeds.shape[1:], -1,
                      seeds.dtype)
        part = np.concatenate([part, pad])
      yield c0, real, part

  def run(self, state: TrainState) -> Tuple[TrainState, 'EpochStats']:
    """Run one epoch; returns ``(state, stats)``.

    The input ``state`` is DONATED to the epoch program (its buffers
    are reused for the output state) — thread the returned state
    forward and don't touch the argument again, exactly as with a
    donated jitted train step.  ``stats`` is LAZY (`EpochStats`):
    reading ``.loss`` etc. syncs on the epoch; a loop that ignores it
    never blocks.  With ``max_steps_per_program`` set, per-chunk keys
    derive from (epoch, chunk offset): same draw distribution as the
    single-program epoch, different stream."""
    from ..telemetry.spans import span
    from ..testing import chaos
    with span('fused.seeds'):
      seeds = np.stack(list(self._batcher))        # [S, B], host shuffle
      parts = list(self._chunks(seeds))
      self._epoch_idx += 1
      key = jax.random.fold_in(self._base_key, self._epoch_idx)
    chunk_steps = parts[0][2].shape[0] if parts else 0
    # mid-epoch resume (attach_snapshots/restore_from_snapshot):
    # chunks before `skip` already ran pre-preemption — their stats
    # come from the snapshot, the permutation and key schedule are
    # re-derived identically, and only the remainder dispatches
    skip, losses, correct, valid, _ = self._take_resume(chunk_steps)
    with span('fused.epoch', scope=type(self).__name__,
              epoch=self._epoch_idx, steps=seeds.shape[0],
              tiered=getattr(self, '_tiered', False)):
      for c0, real, part in parts:
        if c0 < skip:
          continue
        # single-program epochs keep the r4 key schedule exactly
        ck = key if len(parts) == 1 else jax.random.fold_in(key, c0)
        # chaos seam: a planned kill dies here, between chunk
        # dispatches — exactly what a preemption hits
        chaos.fused_dispatch_check(chunk=c0, epoch=self._epoch_idx)
        with span('fused.dispatch', chunk=c0):
          with step_annotation('fused_epoch', self._next_dispatch()):
            if getattr(self, '_tiered', False):
              state, ls, c, v = self._run_tiered_chunk(state, part, ck)
            else:
              state, ls, c, v = self._compiled(
                  state, jnp.asarray(part), ck, self._dev,
                  pallas_enabled())
        losses.append(ls[:real])
        correct = c if correct is None else correct + c
        valid = v if valid is None else valid + v
        self._save_chunk_snapshot(state, c0 + part.shape[0],
                                  chunk_steps, losses, correct, valid)
    metrics.inc('loader.batches', seeds.shape[0])
    return state, EpochStats(jnp.concatenate(losses), correct, valid)

  def _next_dispatch(self) -> int:
    """Monotone per-loader dispatch counter — the xprof step number of
    each fused program dispatch (one per chunk)."""
    self._dispatch_idx = getattr(self, '_dispatch_idx', 0) + 1
    return self._dispatch_idx

  def compile_count(self) -> int:
    """Total compiles across this driver's programs (see
    `driver_compile_count`)."""
    return driver_compile_count(self)

  # -- tiered fused epochs (cold-cache service between dispatches) ----------

  def _run_tiered_chunk(self, state, part: np.ndarray, ck):
    """One tiered chunk: compiled sample-only collect scan → host
    cold service (the Feature's cache-aware mixed lookup fills x) →
    compiled train scan.  Returns ``(state, losses, correct,
    valid)`` matching the untiered chunk program."""
    batches = self._compiled_collect(jnp.asarray(part), ck, self._dev)
    batches = self._fill_cold_x(batches)
    return self._compiled_train(state, batches)

  def _fill_cold_x(self, batches):
    """The between-dispatch cold service: per step, one cache-aware
    tiered Feature lookup (`data.feature.Feature.__getitem__` — cache
    hits device-served, misses host-gathered + admitted)."""
    from ..telemetry.spans import span
    nodes_h = np.asarray(batches.node)             # [c, cap], one sync
    with span('feature.cold_overlay', scope=type(self).__name__,
              steps=nodes_h.shape[0]):
      xs = [self._feat[nodes_h[i]] for i in range(nodes_h.shape[0])]
    batches.x = jnp.stack(xs)
    return batches

  def _collect_fn(self, seeds_all: jax.Array, key: jax.Array,
                  dev: dict):
    """Sample-only scan: the chunk's batches WITHOUT x (the cold
    service fills it between dispatches)."""

    def body(_, xs):
      i, seeds = xs
      return 0, self._collect_batch(seeds, jax.random.fold_in(key, i),
                                    dev)

    steps = jnp.arange(seeds_all.shape[0], dtype=jnp.int32)
    _, batches = jax.lax.scan(body, 0, (steps, seeds_all))
    return batches

  def _train_chunk_fn(self, state: TrainState, batches):
    def body(state, batch):
      new_state, loss, correct = self._step(state, batch)
      any_valid = jnp.any(batch.batch >= 0)
      state = jax.tree_util.tree_map(
          lambda new, old: jnp.where(any_valid, new, old),
          new_state, state)
      return state, (loss, correct, jnp.sum(batch.batch >= 0))

    state, (losses, corrects, valids) = jax.lax.scan(
        body, state, batches)
    return state, losses, jnp.sum(corrects), jnp.sum(valids)

  def _eval_consume_fn(self, params, batches):
    def body(carry, batch):
      correct, total = self._eval_step(params, batch)
      return carry, (correct, total)

    _, (c, t) = jax.lax.scan(body, 0, batches)
    return jnp.sum(c), jnp.sum(t)

  def _eval_fn(self, params, seeds_all: jax.Array, key: jax.Array,
               dev: dict, use_pallas: bool):
    """Scan twin of a `make_eval_step` loop over ``[S, B]`` seeds —
    accuracy on the seed slots via the subclass's ``_eval_step``."""

    def body(carry, xs):
      i, seeds = xs
      batch = self._sample_collate(seeds, jax.random.fold_in(key, i),
                                   dev, use_pallas)
      correct, total = self._eval_step(params, batch)
      return carry, (correct, total)

    steps = jnp.arange(seeds_all.shape[0], dtype=jnp.int32)
    _, (correct, total) = jax.lax.scan(body, 0, (steps, seeds_all))
    return jnp.sum(correct), jnp.sum(total)

  def evaluate(self, params, input_nodes) -> float:
    """Accuracy over ``input_nodes`` (e.g. the test split) as one scan
    program — the fused counterpart of a `make_eval_step` loop."""
    ids = np.asarray(input_nodes)
    if ids.dtype == np.bool_:
      ids = np.nonzero(ids)[0]
    if ids.size == 0:
      raise ValueError('evaluate() got an empty split')
    ev = SeedBatcher(ids, self.batch_size, shuffle=False)
    seeds = np.stack(list(ev))
    # eval keys live in their own fold DOMAIN (base -> 0 -> 1); train
    # keys are base -> epoch with epoch >= 1, so no epoch-counter
    # value (wraparound included) can alias a train sampling key
    key = jax.random.fold_in(jax.random.fold_in(self._base_key, 0), 1)
    parts = list(self._chunks(seeds))
    correct = total = 0
    for c0, _real, part in parts:
      ck = key if len(parts) == 1 else jax.random.fold_in(key, c0)
      if getattr(self, '_tiered', False):
        batches = self._compiled_collect(jnp.asarray(part), ck,
                                         self._dev)
        batches = self._fill_cold_x(batches)
        c, t = self._compiled_eval_consume(params, batches)
      else:
        c, t = self._compiled_eval(params, jnp.asarray(part), ck,
                                   self._dev, pallas_enabled())
      correct += int(c)
      total += int(t)
    return correct / max(total, 1)


class FusedEpoch(_SupervisedScanEpoch):
  """One-program supervised training epochs over neighbor sampling.

  Example::

      fused = FusedEpoch(dataset, [15, 10, 5], train_idx, apply_fn, tx,
                         batch_size=1024, shuffle=True, seed=0)
      for epoch in range(10):
        state, stats = fused.run(state)
        print(stats['loss'], stats['accuracy'])

  Args:
    data: `Dataset` with a homogeneous graph, fully device-resident
      features (``split_ratio == 1.0``) and integer labels.
    num_neighbors: per-hop fanouts.
    input_nodes: seed ids (or boolean mask) — e.g. the train split.
    apply_fn / tx: model apply function and optax transformation, the
      same pair `make_supervised_step` takes.
    batch_size / shuffle / drop_last / seed: epoch iteration controls
      (`SeedBatcher` semantics — the tail batch is INVALID_ID-padded).
    sort_locality: forwarded to the sampler's hop kernel.
    remat: rematerialize the model forward in the backward pass
      (`jax.checkpoint`).  The fused program holds the sampler's
      buffers AND the training activations live together; at large
      ``batch_size x fanout`` products that joint peak can exceed HBM
      where the separate per-batch programs fit — remat trades the
      recompute FLOPs for that headroom.
    max_steps_per_program: run each epoch as ceil(S/chunk) dispatches
      of ONE compiled ``[chunk, B]`` program instead of one
      ``[S, B]`` program per epoch length (a changed epoch length
      otherwise reuses nothing and recompiles).  Tail steps pad with
      INVALID_ID and are state no-ops.
  """

  def __init__(self, data: Dataset, num_neighbors: Sequence[int],
               input_nodes, apply_fn: Callable,
               tx: optax.GradientTransformation, batch_size: int,
               shuffle: bool = True, drop_last: bool = False,
               seed: Optional[int] = None, sort_locality: bool = True,
               remat: bool = False,
               max_steps_per_program: Optional[int] = None):
    if data.is_hetero:
      raise ValueError('FusedEpoch is homogeneous-only; use the '
                       'per-batch NeighborLoader for hetero graphs')
    self._chunk = (int(max_steps_per_program)
                   if max_steps_per_program else None)
    feat = data.node_features
    if feat is None:
      raise ValueError('FusedEpoch needs node features')
    # tiered Feature (split_ratio < 1): the epoch runs as a tiered
    # fused epoch — sample-only collect scans, the cache-aware cold
    # service between dispatches, train scans (module docstring)
    self._tiered = feat.hot_rows < feat.size(0)
    self._feat = feat
    labels = data.get_node_label_device()
    if labels is None:
      raise ValueError('FusedEpoch needs node labels')

    self.data = data
    self.batch_size = int(batch_size)
    self.fanouts = tuple(int(k) for k in num_neighbors)
    self.sort_locality = bool(sort_locality)

    graph = data.get_graph()
    # The big tables go through the jit boundary as ARGUMENTS, never
    # closures: a closed-over device array becomes a jaxpr CONSTANT
    # bundled with the program (a ~1 GB feature table serialized into
    # the compile); as parameters the already-resident buffers are
    # just referenced.
    self._dev = dict(indptr=graph.indptr, indices=graph.indices,
                     hot=None if self._tiered else feat.hot_tier,
                     id2index=(None if self._tiered
                               else feat._id2index_dev),
                     labels=labels)
    self._init_fused_sampling(graph)

    # identical capacity arithmetic to the per-batch sampler, so fused
    # and per-batch programs see the same static shapes
    ref = NeighborSampler(graph, self.fanouts, seed=0)
    self._node_cap = ref.node_capacity(self.batch_size)

    input_nodes = np.asarray(input_nodes)
    if input_nodes.dtype == np.bool_:
      input_nodes = np.nonzero(input_nodes)[0]
    self._batcher = SeedBatcher(input_nodes, self.batch_size, shuffle,
                                drop_last, seed)
    self._base_key = jax.random.key(seed or 0)
    self._epoch_idx = 0
    step_apply = jax.checkpoint(apply_fn) if remat else apply_fn
    # ONE extract per apply variant pins the train and eval paths to
    # the same batch-field contract
    self._step = make_extracted_supervised_step(
        self._extract_with(step_apply), tx, self.batch_size)
    self._eval_step = make_extracted_eval_step(
        self._extract_with(apply_fn), self.batch_size)
    self._compiled = _counted_jit(self._epoch_fn, donate_argnums=(0,),
                                  static_argnums=(4,))
    self._compiled_eval = _counted_jit(self._eval_fn,
                                       static_argnums=(4,))
    if self._tiered:
      self._compiled_collect = _counted_jit(self._collect_fn)
      self._compiled_train = _counted_jit(self._train_chunk_fn,
                                          donate_argnums=(0,))
      self._compiled_eval_consume = _counted_jit(self._eval_consume_fn)

  def _collect_step_bytes(self) -> int:
    return (self._node_cap * self._feat.feature_dim
            * np.dtype(self._feat.dtype).itemsize)

  def _collect_batch(self, seeds: jax.Array, key: jax.Array,
                     dev: dict) -> Batch:
    """Sample-only scan-body front half for tiered stores: everything
    `_sample_collate` produces EXCEPT x (the cold service fills it
    from the cache-aware Feature between dispatches)."""
    (nodes, _count, row, col, _edge, emask, seed_local, _nsn,
     _nse) = _multihop_sample(
         dev['indptr'], dev['indices'], None, seeds, key, dev['win2d'],
         fanouts=self.fanouts, node_cap=self._node_cap,
         with_edge=False, sort_locality=self.sort_locality,
         use_fused=self._use_fused, win_e=self._win_e)
    return Batch(
        x=None,
        y=_gather_labels(dev['labels'], nodes),
        edge_index=jnp.stack([row, col]),
        node=nodes, node_mask=nodes >= 0, edge_mask=emask,
        batch=seeds, batch_size=self.batch_size,
        metadata={'seed_local': seed_local})

  @staticmethod
  def _extract_with(apply):
    def extract(params, batch):
      logits = apply(params, batch.x, batch.edge_index, batch.edge_mask)
      return logits, batch.y, batch.batch
    return extract

  # __len__ / _epoch_fn / run come from _SupervisedScanEpoch

  def _sample_collate(self, seeds: jax.Array, key: jax.Array,
                      dev: dict, use_pallas: bool) -> Batch:
    """The shared scan-body front half: one fused multi-hop sample +
    all-device collation (same programs as the per-batch path).
    ``use_pallas`` comes from the host driver so the GLT_PALLAS
    kill-switch keeps working between epochs (the per-batch contract,
    `data/feature.py:39-40`)."""
    (nodes, _count, row, col, _edge, emask, seed_local, _nsn,
     _nse) = _multihop_sample(
         dev['indptr'], dev['indices'], None, seeds, key, dev['win2d'],
         fanouts=self.fanouts, node_cap=self._node_cap,
         with_edge=False, sort_locality=self.sort_locality,
         use_fused=self._use_fused, win_e=self._win_e)
    return Batch(
        x=_device_gather(dev['hot'], nodes, dev['id2index'],
                         use_pallas=use_pallas),
        y=_gather_labels(dev['labels'], nodes),
        edge_index=jnp.stack([row, col]),
        node=nodes, node_mask=nodes >= 0, edge_mask=emask,
        batch=seeds, batch_size=self.batch_size,
        metadata={'seed_local': seed_local})

class FusedHeteroEpoch(_SupervisedScanEpoch):
  """One-program supervised training epochs on a HETERO graph.

  The hetero twin of `FusedEpoch`: the scan body runs the fused
  per-type multi-hop program (`sampler.hetero_neighbor_sampler.
  _hetero_multihop` — the same program the per-batch
  `HeteroNeighborSampler` dispatches), collates per-type feature
  dicts on device, and applies a supervised step whose loss lives on
  the seed type's slots — the objective of the reference's HGT / RGNN
  examples (`examples/hetero/train_hgt_mag.py:90-130`,
  `examples/igbh/train_rgnn.py`).

  ``apply_fn(params, x_dict, edge_index_dict, edge_mask_dict)`` must
  return the TARGET type's logits (the `HGT`/`RGCN`/`HeteroConv`
  model contract).

  Args:
    data: hetero `Dataset`; every node type's features fully
      device-resident, labels present for the seed type.
    num_neighbors: per-hop fanouts (list or ``{EdgeType: list}``).
    input_nodes: ``(node_type, ids)`` seed spec.
    apply_fn / tx: model apply + optax transform.
    batch_size / shuffle / drop_last / seed: epoch controls.
    remat: checkpoint the model forward (see `FusedEpoch`).
  """

  def __init__(self, data: Dataset, num_neighbors, input_nodes,
               apply_fn: Callable, tx: optax.GradientTransformation,
               batch_size: int, shuffle: bool = True,
               drop_last: bool = False, seed: Optional[int] = None,
               sort_locality: bool = True, remat: bool = False,
               max_steps_per_program: Optional[int] = None):
    self._chunk = (int(max_steps_per_program)
                   if max_steps_per_program else None)
    from ..sampler.hetero_neighbor_sampler import (HeteroNeighborSampler,
                                                   _plan_capacities)
    if not data.is_hetero:
      raise ValueError('FusedHeteroEpoch needs a hetero Dataset; use '
                       'FusedEpoch for homogeneous graphs')
    if (not isinstance(input_nodes, tuple)
        or not isinstance(input_nodes[0], str)):
      raise ValueError('input_nodes must be (node_type, ids)')
    self.input_type, ids = input_nodes
    feats = data.node_features
    if not isinstance(feats, dict) or not feats:
      raise ValueError('FusedHeteroEpoch needs per-type node features')
    for nt, f in feats.items():
      if f.hot_rows < f.size(0):
        raise ValueError(
            f'feature table for {nt!r} keeps rows on host; '
            f'FusedHeteroEpoch needs split_ratio == 1.0 everywhere '
            f'(use NeighborLoader(prefetch=2) for tiered tables)')
    labels = data.get_node_label_device(self.input_type)
    if labels is None:
      raise ValueError(
          f'FusedHeteroEpoch needs labels for {self.input_type!r}')

    self.data = data
    self.batch_size = int(batch_size)
    self.sort_locality = bool(sort_locality)

    graphs = {et: data.get_graph(et) for et in data.get_edge_types()}
    # reuse the per-batch sampler's planning so fused and per-batch
    # programs share static shapes and the same _hetero_multihop
    ref = HeteroNeighborSampler(graphs, num_neighbors,
                                num_nodes=data.num_nodes_dict(), seed=0,
                                sort_locality=sort_locality)
    self._etypes = ref.etypes
    self._fanouts_t = tuple(ref.fanouts[et] for et in ref.etypes)
    self._num_hops = ref.num_hops
    ntypes, table_cap, frontier_caps, _ = _plan_capacities(
        ref.etypes, ref.fanouts, {self.input_type: self.batch_size},
        ref.num_hops, ref._num_nodes)
    self._table_caps = tuple(sorted(table_cap.items()))
    self._frontier_caps_t = tuple(
        tuple(sorted(fc.items())) for fc in frontier_caps)

    # big tables as jit arguments, not closures (see FusedEpoch note)
    self._dev = dict(
        graphs={et: (g.indptr, g.indices, None)
                for et, g in graphs.items()},
        hot={nt: f.hot_tier for nt, f in feats.items()},
        id2index={nt: f._id2index_dev for nt, f in feats.items()},
        labels=labels)

    ids = np.asarray(ids)
    if ids.dtype == np.bool_:
      ids = np.nonzero(ids)[0]
    self._batcher = SeedBatcher(ids, self.batch_size, shuffle,
                                drop_last, seed)
    self._base_key = jax.random.key(seed or 0)
    self._epoch_idx = 0
    step_apply = jax.checkpoint(apply_fn) if remat else apply_fn
    self._step = make_extracted_supervised_step(
        self._extract_with(step_apply), tx, self.batch_size)
    self._eval_step = make_extracted_eval_step(
        self._extract_with(apply_fn), self.batch_size)
    self._compiled = _counted_jit(self._epoch_fn, donate_argnums=(0,),
                                  static_argnums=(4,))
    self._compiled_eval = _counted_jit(self._eval_fn,
                                       static_argnums=(4,))

  def _extract_with(self, apply):
    it = self.input_type

    def extract(params, batch):
      logits = apply(params, batch.x_dict, batch.edge_index_dict,
                     batch.edge_mask_dict)
      return logits, batch.y_dict[it], batch.batch_dict[it]

    return extract

  def _sample_collate(self, seeds: jax.Array, key: jax.Array,
                      dev: dict, use_pallas: bool):
    from ..sampler.hetero_neighbor_sampler import _hetero_multihop
    from .transform import HeteroBatch
    (node, _cnt, row, col, _eid, emask, seed_locals, _nsn) = \
        _hetero_multihop(
            dev['graphs'], (seeds,), key,
            etypes=self._etypes, fanouts_t=self._fanouts_t,
            seed_types=(self.input_type,), num_hops=self._num_hops,
            table_caps=self._table_caps,
            frontier_caps_t=self._frontier_caps_t,
            with_edge=False, sort_locality=self.sort_locality)
    x_dict = {nt: _device_gather(dev['hot'][nt], ids,
                                 dev['id2index'][nt],
                                 use_pallas=use_pallas)
              for nt, ids in node.items() if nt in dev['hot']}
    y = _gather_labels(dev['labels'], node[self.input_type])
    ei_dict = {et: jnp.stack([row[et], col[et]]) for et in row}
    return HeteroBatch(
        x_dict=x_dict, y_dict={self.input_type: y},
        edge_index_dict=ei_dict,
        edge_attr_dict={},
        node_dict=dict(node),
        node_mask_dict={nt: ids >= 0 for nt, ids in node.items()},
        edge_mask_dict=dict(emask),
        batch_dict={self.input_type: seeds},
        batch_size=self.batch_size,
        metadata={'seed_local': seed_locals[self.input_type]})


def _as_edge_pairs(edge_label_index):
  """Normalize ``(rows, cols)`` / ``[2, E]`` seed-edge forms — one
  definition for `FusedLinkEpoch.__init__` and its `evaluate`."""
  if isinstance(edge_label_index, (tuple, list)):
    rows, cols = edge_label_index
    return rows, cols
  ei = np.asarray(edge_label_index)
  return ei[0], ei[1]


class FusedLinkEpoch(_SnapshotHooks):
  """One-program link-prediction (unsupervised) training epochs.

  The link twin of `FusedEpoch`, fusing the `LinkNeighborLoader` +
  unsupervised-step loop: the scan body draws negatives, expands
  multi-hop neighborhoods around the positive + negative endpoints,
  collates, and applies the binary (sigmoid) or triplet (max-margin)
  link loss — the objective of the reference's unsupervised SAGE
  (`examples/graph_sage_unsup_ppi.py:41-45`).

  The seeds, the strict negative draw and the metadata are
  `sampler.neighbor_sampler.link_seeds` / `link_metadata`, the
  functions `NeighborSampler.sample_from_edges` calls (keys passed
  in, not held).  A batch states its expansion's static hop layout
  (``hop_capacities`` / ``hop_windows`` for the seed width ``2B +
  2 * negatives`` binary, ``2B + B * amount`` triplet) and the step
  applies the model through `models.train.apply_to_batch`, so a
  `BasicGNN` computes each layer over the hops it feeds only and
  aggregates by fanout window; the link loss reads the seed rows,
  which lie below ``C_0``.  No labels are gathered: the link loss
  reads none.  `batch_fill` counts the valid rows and edge slots of
  the batches every dispatch ran, on the device.

  Args:
    data: `Dataset` with fully device-resident features (labels
      optional — link training is label-free unless ``edge_label``).
    num_neighbors: per-hop fanouts.
    edge_label_index: ``[2, E]`` (or ``(rows, cols)``) seed edges.
    apply_fn / tx: model apply fn (emits embeddings) + optax transform.
    batch_size: seed-EDGE batch size.
    neg_sampling: `NegativeSampling` spec or mode string
      (default binary, amount 1).
    edge_label: optional ``[E]`` positive labels (binary mode gets the
      reference's +1 shift: 0 = sampled negative).
    remat: checkpoint the model forward — same merged-program HBM
      hazard as `FusedEpoch` (and the link seed width is LARGER:
      ``2B + negatives`` endpoints per batch).
  """

  def __init__(self, data: Dataset, num_neighbors, edge_label_index,
               apply_fn: Callable, tx: optax.GradientTransformation,
               batch_size: int, neg_sampling='binary', edge_label=None,
               shuffle: bool = True, drop_last: bool = False,
               seed: Optional[int] = None, sort_locality: bool = True,
               remat: bool = False,
               max_steps_per_program: Optional[int] = None):
    if data.is_hetero:
      raise ValueError('FusedLinkEpoch is homogeneous-only')
    self._chunk = (int(max_steps_per_program)
                   if max_steps_per_program else None)
    feat = data.node_features
    if feat is None:
      raise ValueError('FusedLinkEpoch needs node features')
    # tiered Feature: tiered fused epochs (see FusedEpoch)
    self._tiered = feat.hot_rows < feat.size(0)
    self._feat = feat
    self.data = data
    self.batch_size = int(batch_size)
    self.fanouts = tuple(int(k) for k in num_neighbors)
    self.sort_locality = bool(sort_locality)
    self.neg = NegativeSampling.cast(neg_sampling)

    graph = data.get_graph()
    self._num_nodes = graph.num_nodes
    # big tables as jit arguments, not closures (see FusedEpoch note)
    self._dev = dict(indptr=graph.indptr, indices=graph.indices,
                     hot=None if self._tiered else feat.hot_tier,
                     id2index=(None if self._tiered
                               else feat._id2index_dev))
    self._init_fused_sampling(graph)

    rows, cols = _as_edge_pairs(edge_label_index)
    self._batcher = EdgeSeedBatcher(rows, cols, edge_label,
                                    self.batch_size, shuffle, drop_last,
                                    seed)

    (self._mode, self._num_neg, self._amount,
     seed_width) = link_plan(self.neg, self.batch_size)
    ref = NeighborSampler(graph, self.fanouts, seed=0)
    self._node_cap = ref.node_capacity(seed_width)
    self._layout = (hop_capacities(seed_width, self.fanouts,
                                   self._node_cap),
                    hop_windows(seed_width, self.fanouts))

    self._base_key = jax.random.key(seed or 0)
    self._epoch_idx = 0
    self._fill = []                   # per dispatch [4] int32, on device
    self._filled = np.zeros(4, np.int64)
    from ..models.train import make_unsupervised_step
    self._apply = apply_fn            # evaluate() is fwd-only: no remat
    self._step = make_unsupervised_step(apply_fn, tx, remat=remat)
    self._compiled = _counted_jit(self._epoch_fn, donate_argnums=(0,),
                                  static_argnums=(6,))
    self._compiled_eval = _counted_jit(self._auc_fn,
                                       static_argnums=(5,))
    # the sample-only scan: the tiered epochs' front half, and the
    # epoch's own draw for whoever wants the batches a dispatch trained
    # on again (`epoch_key`)
    self._compiled_collect = _counted_jit(
        self._link_collect_fn, static_argnames=('collect_x',))
    if self._tiered:
      self._compiled_train = _counted_jit(self._link_train_fn,
                                          donate_argnums=(0,))
      self._compiled_auc_consume = _counted_jit(self._auc_consume_fn)

  def __len__(self) -> int:
    return len(self._batcher)

  # -- tiered fused epochs (see FusedEpoch): the cold-service and
  # chunk-budget helpers are shared with the supervised twins via
  # `_SupervisedScanEpoch` — one body, so a fix cannot miss a twin
  _collect_step_bytes = FusedEpoch._collect_step_bytes
  _fill_cold_x = _SupervisedScanEpoch._fill_cold_x
  compile_count = _SupervisedScanEpoch.compile_count

  def epoch_key(self, epoch_idx: int) -> jax.Array:
    """The key of epoch ``epoch_idx`` (`run` numbers them from 1);
    step ``i`` of a one-dispatch epoch draws with ``fold_in(key, i)``,
    as `_link_collect_fn` does — the one statement of the schedule."""
    return jax.random.fold_in(self._base_key, epoch_idx)

  def batch_fill(self) -> dict:
    """Valid node rows and valid edge slots of the batches that
    every dispatch so far ran, beside the padded extents they were
    laid out over (``rows_valid`` / ``rows``, ``edges_valid`` /
    ``edge_slots``).  Counted on the device inside each program; this
    call pulls them."""
    for fill in self._fill:
      self._filled += np.asarray(fill, np.int64)
    self._fill = []
    rows_valid, rows, edges_valid, slots = (int(v) for v in self._filled)
    return dict(rows_valid=rows_valid, rows=rows, edges_valid=edges_valid,
                edge_slots=slots)

  @staticmethod
  def _fill_of(batch: Batch) -> jax.Array:
    """``[rows valid, rows, edge slots valid, edge slots]`` of one
    batch."""
    return jnp.stack([
        jnp.sum(batch.node_mask, dtype=jnp.int32),
        jnp.int32(batch.node_mask.shape[0]),
        jnp.sum(batch.edge_mask, dtype=jnp.int32),
        jnp.int32(batch.edge_mask.shape[0])])

  def _link_collect_fn(self, srcs: jax.Array, dsts: jax.Array,
                       labs: jax.Array, key: jax.Array, dev: dict,
                       collect_x: bool = False):
    """Sample-only link scan (negatives + expansion + metadata; the
    feature gather only with ``collect_x``) for one chunk."""

    def body(_, xs):
      i, src, dst, lab = xs
      return 0, self._link_batch(src, dst, lab,
                                 jax.random.fold_in(key, i), dev,
                                 False, collect_x=collect_x)

    steps = jnp.arange(srcs.shape[0], dtype=jnp.int32)
    _, batches = jax.lax.scan(body, 0, (steps, srcs, dsts, labs))
    return batches

  def _link_train_fn(self, state: TrainState, batches,
                     srcs: jax.Array, dsts: jax.Array):
    def body(state, xs):
      batch, src, dst = xs
      new_state, loss = self._step(state, batch)
      any_valid = jnp.any((src >= 0) & (dst >= 0))
      state = jax.tree_util.tree_map(
          lambda new, old: jnp.where(any_valid, new, old),
          new_state, state)
      return state, (loss, jnp.sum((src >= 0) & (dst >= 0)),
                     self._fill_of(batch))

    state, (losses, valids, fill) = jax.lax.scan(body, state,
                                                 (batches, srcs, dsts))
    return state, losses, jnp.sum(valids), jnp.sum(fill, axis=0)

  def _auc_consume_fn(self, params, batches):
    def body(carry, batch):
      return carry, self._auc_score(params, batch)

    _, (wins, totals) = jax.lax.scan(body, 0, batches)
    return jnp.sum(wins), jnp.sum(totals)

  def _auc_score(self, params, batch):
    """Embed one batch and accumulate the pairwise (pos > neg) win
    counts — the batched rank-sum AUC body, shared by the
    single-program `_auc_fn` and the tiered `_auc_consume_fn`."""
    from ..models.train import apply_to_batch
    b = self.batch_size
    emb = apply_to_batch(self._apply, params, batch)
    eli = batch.metadata['edge_label_index']        # [2, b + nn]
    mask = batch.metadata['edge_label_mask']
    score = (emb[eli[0]] * emb[eli[1]]).sum(-1)
    # binary layout is static: first b slots positive, rest negative
    ps, ns = score[:b], score[b:]
    pv, nv = mask[:b], mask[b:]
    pair_ok = pv[:, None] & nv[None, :]
    # float32 accumulation: int32 pair counts overflow past ~2k
    # products-scale batches (b * nn pairs each)
    wins = (jnp.sum((ps[:, None] > ns[None, :]) & pair_ok,
                    dtype=jnp.float32)
            + 0.5 * jnp.sum((ps[:, None] == ns[None, :]) & pair_ok,
                            dtype=jnp.float32))
    return wins, jnp.sum(pair_ok, dtype=jnp.float32)

  def _auc_fn(self, params, srcs: jax.Array, dsts: jax.Array,
              key: jax.Array, dev: dict, use_pallas: bool):
    """Scan body of `evaluate`: per batch, draw strict negatives,
    expand + embed, score endpoint pairs, and accumulate the
    pairwise (pos > neg) win counts — the batched rank-sum AUC."""

    def body(carry, xs):
      i, src, dst = xs
      batch = self._link_batch(src, dst, None,
                               jax.random.fold_in(key, i), dev,
                               use_pallas)
      return carry, self._auc_score(params, batch)

    steps = jnp.arange(srcs.shape[0], dtype=jnp.int32)
    _, (wins, totals) = jax.lax.scan(body, 0, (steps, srcs, dsts))
    return jnp.sum(wins), jnp.sum(totals)

  def evaluate(self, params, edge_label_index, seed: int = 0) -> float:
    """Held-out link AUC over ``edge_label_index`` as ONE scan
    program — the fused counterpart of the reference's unsupervised
    eval loop (score held-out positives against freshly drawn strict
    negatives; `examples/graph_sage_unsup_ppi.py` computes the same
    ranking metric on host).  Scores are embedding dot products (the
    binary link objective's logit); the batched rank-sum estimator
    averages all pos x neg comparisons per batch.  Binary mode only
    (triplet mode's per-src negatives make precision@rank the right
    metric instead)."""
    if not self.neg.is_binary():
      raise ValueError('evaluate() needs binary negative sampling')
    rows, cols = _as_edge_pairs(edge_label_index)
    if len(np.asarray(rows)) == 0:
      raise ValueError('evaluate() got an empty split')
    ev = EdgeSeedBatcher(rows, cols, None, self.batch_size,
                         shuffle=False)
    srcs, dsts = [], []
    for r, c, _ in ev:
      srcs.append(r)
      dsts.append(c)
    # eval fold domain disjoint from train epochs (see
    # _SupervisedScanEpoch.evaluate)
    key = jax.random.fold_in(jax.random.fold_in(self._base_key, 0),
                             1 + seed)
    srcs, dsts = np.stack(srcs), np.stack(dsts)
    if self._tiered:
      s = srcs.shape[0]
      chunk = self._chunk or resolve_cold_chunk(
          self._collect_step_bytes(), s)
      wins = total = 0.0
      for c0 in range(0, s, chunk):
        sp = jnp.asarray(srcs[c0:c0 + chunk])
        dp = jnp.asarray(dsts[c0:c0 + chunk])
        ck = (key if s <= chunk else jax.random.fold_in(key, c0))
        batches = self._compiled_collect(sp, dp, jnp.ones_like(sp),
                                         ck, self._dev)
        batches = self._fill_cold_x(batches)
        w, t = self._compiled_auc_consume(params, batches)
        wins += float(w)
        total += float(t)
      return wins / max(total, 1.0)
    wins, total = self._compiled_eval(
        params, jnp.asarray(srcs), jnp.asarray(dsts),
        key, self._dev, pallas_enabled())
    return float(wins) / max(float(total), 1.0)

  def _link_batch(self, src: jax.Array, dst: jax.Array,
                  label: Optional[jax.Array], key: jax.Array,
                  dev: dict, use_pallas: bool,
                  collect_x: bool = True) -> Batch:
    """One link batch: `link_seeds` (the strict negatives), the
    expansion, `link_metadata` (see class doc).  ``collect_x=False``
    skips the feature gather (tiered collect scans — the cold service
    fills x between dispatches)."""
    b = self.batch_size
    pair_valid = (src >= 0) & (dst >= 0)
    pos_label = (label if label is not None
                 else jnp.ones((b,), jnp.int32))
    seeds = link_seeds(dev['indptr'], dev['indices'], src, dst,
                       jax.random.fold_in(key, 0), mode=self._mode,
                       num_neg=self._num_neg, amount=self._amount,
                       num_nodes=self._num_nodes, layout=self._layout)
    (nodes, _count, row, col, _edge, emask, seed_local, nsn,
     nse) = _multihop_sample(
         dev['indptr'], dev['indices'], None, seeds,
         jax.random.fold_in(key, 1), dev['win2d'],
         fanouts=self.fanouts, node_cap=self._node_cap,
         with_edge=False, sort_locality=self.sort_locality,
         use_fused=self._use_fused, win_e=self._win_e)
    return Batch(
        x=(_device_gather(dev['hot'], nodes, dev['id2index'],
                          use_pallas=use_pallas) if collect_x
           else None),
        edge_index=jnp.stack([row, col]),
        node=nodes, node_mask=nodes >= 0, edge_mask=emask,
        batch=seeds, batch_size=b, num_sampled_nodes=nsn,
        num_sampled_edges=nse,
        metadata=link_metadata(seed_local, b, self._mode, self._num_neg,
                               self._amount, pair_valid, pos_label,
                               self._layout))

  def _epoch_fn(self, state: TrainState, srcs: jax.Array,
                dsts: jax.Array, labels: Optional[jax.Array],
                key: jax.Array, dev: dict, use_pallas: bool):
    def body(state, xs):
      i, src, dst, lab = xs
      batch = self._link_batch(src, dst, lab,
                               jax.random.fold_in(key, i), dev,
                               use_pallas)
      new_state, loss = self._step(state, batch)
      # padded chunk-tail steps are state no-ops (see FusedEpoch)
      any_valid = jnp.any((src >= 0) & (dst >= 0))
      state = jax.tree_util.tree_map(
          lambda new, old: jnp.where(any_valid, new, old),
          new_state, state)
      return state, (loss, jnp.sum((src >= 0) & (dst >= 0)),
                     self._fill_of(batch))

    steps = jnp.arange(srcs.shape[0], dtype=jnp.int32)
    labs = (labels if labels is not None
            else jnp.ones_like(srcs))             # constant positive label
    state, (losses, valids, fill) = jax.lax.scan(
        body, state, (steps, srcs, dsts, labs))
    return state, losses, jnp.sum(valids), jnp.sum(fill, axis=0)

  def run(self, state: TrainState) -> Tuple[TrainState, 'EpochStats']:
    """One epoch; ``state`` is DONATED (thread the returned one).
    ``stats.seeds`` counts valid seed EDGES; accuracy is meaningless
    for the unsupervised objective and reads 0."""
    srcs, dsts, labs = [], [], []
    for r, c, lab in self._batcher:
      srcs.append(r)
      dsts.append(c)
      if lab is not None:
        # reference +1 shift (loader/link_loader.py:146-186): user
        # labels move up so 0 means "sampled negative"; only VALID
        # pair slots shift — the batcher zero-pads the tail, and a
        # padded slot must not read as a phantom positive to metadata
        # consumers that skip edge_label_mask
        labs.append(np.where((r >= 0) & (c >= 0), lab + 1, 0)
                    if self.neg.is_binary() else lab)
    srcs = np.stack(srcs)
    dsts = np.stack(dsts)
    labels = np.stack(labs).astype(np.int32) if labs else None
    self._epoch_idx += 1
    key = self.epoch_key(self._epoch_idx)
    s = srcs.shape[0]
    chunk = self._chunk or s
    losses, valid = [], None

    def piece(a, c0, fill=-1):
      part = a[c0:c0 + chunk]
      if part.shape[0] < chunk:
        part = np.concatenate([
            part, np.full((chunk - part.shape[0], a.shape[1]), fill,
                          a.dtype)])
      return jnp.asarray(part)

    if self._tiered and self._chunk is None:
      chunk = resolve_cold_chunk(self._collect_step_bytes(), s)
    n_chunks = (s + chunk - 1) // chunk
    from ..testing import chaos
    # mid-epoch resume: see _SupervisedScanEpoch.run (same contract,
    # link stats carry valid-pair counts instead of correct)
    skip, losses, _corr, valid, _ = self._take_resume(chunk)
    for c0 in range(0, s, chunk):
      if c0 < skip:
        continue
      real = min(chunk, s - c0)
      ck = key if n_chunks == 1 else jax.random.fold_in(key, c0)
      chaos.fused_dispatch_check(chunk=c0, epoch=self._epoch_idx)
      self._dispatch_idx = getattr(self, '_dispatch_idx', 0) + 1
      with step_annotation('fused_link_epoch', self._dispatch_idx):
        # chunk-tail label padding uses the established invalid
        # sentinel 0 ("sampled negative"/masked), NOT -1: a -1
        # label reaching a metadata consumer that skips
        # edge_label_mask would index class tables out of range
        lab_piece = (piece(labels, c0, fill=0)
                     if labels is not None else None)
        if self._tiered:
          sp, dp = piece(srcs, c0), piece(dsts, c0)
          batches = self._compiled_collect(
              sp, dp, lab_piece if lab_piece is not None
              else jnp.ones_like(sp), ck, self._dev)
          batches = self._fill_cold_x(batches)
          state, ls, v, fill = self._compiled_train(state, batches, sp,
                                                    dp)
        else:
          state, ls, v, fill = self._compiled(
              state, piece(srcs, c0), piece(dsts, c0), lab_piece,
              ck, self._dev, pallas_enabled())
      self._fill.append(fill)
      if len(self._fill) > 64:
        # the runtime keeps at most 32 programs in flight: this one has
        # long finished, and pulling it waits for nothing
        self._filled += np.asarray(self._fill.pop(0), np.int64)
      losses.append(ls[:real])
      valid = v if valid is None else valid + v
      self._save_chunk_snapshot(state, c0 + chunk, chunk, losses,
                                None, valid)
    metrics.inc('loader.batches', s)
    return state, EpochStats(jnp.concatenate(losses),
                             jnp.zeros((), jnp.int32), valid)
