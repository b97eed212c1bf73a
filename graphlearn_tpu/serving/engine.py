"""Bucketed warm-executable inference engine (ISSUE 9 tentpole).

The training data plane compiles ONE program per epoch shape and
amortizes it over thousands of steps; online traffic arrives as
single-seed (or few-seed) queries whose natural shapes are all
different — compiled naively, every request is a 60 s compile.  The
engine applies the PR 5 INVALID_ID idiom to the traffic envelope
instead: a small ladder of **shape buckets** (``GLT_SERVING_BUCKETS``,
seed capacities), each served by ONE warm fused sample+gather(+model-
forward) executable; a coalesced batch pads its tail with INVALID_ID
up to the smallest bucket that fits.  `warmup` AOT-compiles every
bucket at server start, and after it NOTHING recompiles across the
whole envelope (pinned by the `_counted_jit` per-callable compile
counters — the zero-recompile acceptance assertion).

**Per-seed determinism (the coalescing contract).**  A batch-keyed
sampler draws per *slot*, so a seed's neighborhood would change with
whoever it shares a bucket with — coalescing would alter answers.
The serving program instead vmaps the single-shot tree expansion
(`loader.fused_tree.expand_tree_levels`) per seed under a key folded
from ``(serve_key, seed_id)``: a seed's sampled tree is a pure
function of the engine seed and the node id — independent of bucket
capacity, slot position, and co-batched traffic.  That is what makes
the de-multiplexed per-request results byte-identical to the per-seed
offline reference (`offline_reference`) across bucket boundaries, and
what makes an RPC retry's re-execution indistinguishable from the
first run.

Identity fine print (pinned by tests/test_serving.py): ``nodes`` and
gathered ``x`` are byte-identical across EVERY bucket shape and any
co-batched traffic.  Fused-forward ``logits`` are byte-identical
within a bucket shape whatever the request rode with (each row's
matmul reads only its own row), and agree across DIFFERENT bucket
shapes only to float tolerance (XLA retiles the matmul reduction per
shape; no compiler grants cross-shape bitwise equality): ~1e-6 on the
CPU, and on a TPU — where f32 matmuls run as bf16 passes by default —
2.4e-3 absolute at hidden 256 on one v5e (`chip_smoke.py`, PR 21),
the same size as the error against a float64 forward.  Per-request answers are therefore bitwise-reproducible
given (engine seed, bucket shape) — retries and replicas agree —
while cross-bucket logit identity is numerical, not bitwise.

**Tiered tables.**  With ``split_ratio < 1`` the device program emits
the sampled node ids only; features fill through the per-request
tiered `Feature.get` path — hot split gather + HBM cold-cache hits +
host-served misses with admission (`data.cold_cache`) — under the
``'serving'`` telemetry scope.  Zipf-skewed inference traffic is
exactly the workload that cache was built for (ROADMAP item 2
grounding: GNS, arXiv 2106.06150).
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.dataset import Dataset
from ..data.feature import _device_gather
from ..loader.fused import _counted_jit
from ..loader.fused_tree import expand_tree_levels
from ..data.cold_cache import pinned_cold_enabled
from ..ops.pallas_gather import pallas_enabled
from ..ops.pallas_sample import fused_sample_enabled
from ..utils.padding import INVALID_ID

BUCKETS_ENV = 'GLT_SERVING_BUCKETS'
DEFAULT_BUCKETS = (1, 2, 4, 8, 16)


def resolve_buckets(spec=None) -> Tuple[int, ...]:
  """The seed-capacity ladder: an explicit sequence wins, else
  ``GLT_SERVING_BUCKETS`` (comma-separated ints), else the default.
  Returned sorted ascending, deduplicated, all positive."""
  if spec is None:
    env = os.environ.get(BUCKETS_ENV)
    if env:
      try:
        spec = [int(tok) for tok in env.split(',') if tok.strip()]
      except ValueError:
        spec = None
  if not spec:
    spec = DEFAULT_BUCKETS
  caps = sorted({int(c) for c in spec if int(c) > 0})
  if not caps:
    raise ValueError(f'no positive bucket capacities in {spec!r}')
  return tuple(caps)


@dataclass
class ServingResult:
  """De-multiplexed per-request inference output.

  ``nodes`` is ``[k, W]`` — each seed's sampled tree, all levels
  concatenated (widths ``1, k1, k1*k2, ...``; INVALID_ID where
  masked).  Exactly one of ``x`` (``[k, W, D]`` gathered features,
  model-less engines) and ``logits`` (``[k, C]``, engines with a
  model) is set."""
  nodes: np.ndarray
  x: Optional[np.ndarray] = None
  logits: Optional[np.ndarray] = None

  def slice(self, lo: int, hi: int) -> 'ServingResult':
    return ServingResult(
        nodes=self.nodes[lo:hi],
        x=None if self.x is None else self.x[lo:hi],
        logits=None if self.logits is None else self.logits[lo:hi])


class ServingEngine:
  """Warm bucketed single-shot inference over a `Dataset`.

  Args:
    data: homogeneous `Dataset`; the `Feature` may be tiered
      (``split_ratio < 1`` routes cold rows through the cache-aware
      host path) — the serving twin of the fused epoch drivers'
      tiered contract.
    num_neighbors: per-hop fanouts of the sampling tree.
    model: optional tree-layout model (`models.tree.TreeSAGE`
      signature: ``(xs, masks) -> [B, C]``); fused into the bucket
      program when the table is fully HBM-resident, run as a warm
      consume program after the host feature fill when tiered.
    params: model params (required with ``model``; see
      `init_params`).
    seed: the serve key — per-seed sampling derives from
      ``fold_in(key(seed), node_id)``, so two engines with one seed
      answer identically (replica consistency for free).
    buckets: seed-capacity ladder override (else
      ``GLT_SERVING_BUCKETS``).
  """

  def __init__(self, data: Dataset, num_neighbors: Sequence[int],
               model=None, params=None, seed: int = 0, buckets=None,
               stream=None):
    import threading
    if data.is_hetero:
      raise ValueError('ServingEngine is homogeneous-only (hetero '
                       'serving parity is ROADMAP item 4)')
    feat = data.node_features
    if feat is None:
      raise ValueError('ServingEngine needs node features')
    self.data = data
    self.fanouts = tuple(int(k) for k in num_neighbors)
    self.model = model
    self.params = params
    self.buckets = resolve_buckets(buckets)
    self._tiered = feat.hot_rows < feat.size(0)
    self._feat = feat
    # memory accounting (ISSUE 17): the hot tier is the engine's HBM
    # bill — resident bytes when materialised, the would-be bill before
    # lazy_init (`Feature.hot_bytes`)
    from ..telemetry.memaccount import register_tier
    self._unregister_hot_tier = register_tier('hot',
                                              lambda: feat.hot_bytes)
    #: streaming ingestion (ISSUE 14): with a `StreamingGraph`
    #: attached (explicitly or via `Dataset.attach_stream`), every
    #: dispatch re-pins the newest published `GraphView` FIRST and
    #: reads topology only through that pinned view — one
    #: `graph_version` end to end per coalesced run, the
    #: `model_version`-style accounting the fleet heartbeats carry
    self._stream = (stream if stream is not None
                    else getattr(data, 'stream', None))
    self._pin_lock = threading.Lock()
    self._pin_holds = 0        # guarded-by: self._pin_lock
    if self._stream is not None:
      view = self._stream.pin()
      self.num_nodes = int(view.num_nodes)
      self.graph_version = int(view.version)
      indptr, indices = view.indptr_dev, view.indices_dev
    else:
      graph = data.get_graph()
      self.num_nodes = int(graph.num_nodes)
      self.graph_version = 0
      indptr, indices = graph.indptr, graph.indices
    # big tables as jit ARGUMENTS, never closures (`loader.fused`)
    self._dev = dict(indptr=indptr, indices=indices,
                     hot=None if self._tiered else feat.hot_tier,
                     id2index=(None if self._tiered
                               else feat._id2index_dev))
    self._key = jax.random.key(int(seed))
    self._seed = int(seed)
    self.level_widths = self._level_widths()
    self.tree_width = sum(self.level_widths)
    #: bumped by `set_params` (the hot-swap commit); surfaced in
    #: `compile_status` / heartbeats so fleet routing and swap
    #: validation can tell which version a replica answers with
    self.model_version = 0
    #: AOT executables `warmup` restored from (or published to) the
    #: persistent cache under GLT_AOT_CACHE_DIR: (program, cap) ->
    #: callable over the program's dynamic args.  `_dispatch` prefers
    #: these; empty without a cache dir (the default path unchanged).
    self._aot = {}
    self._aot_compiles = 0
    self._aot_restores = 0
    #: bucket capacity -> True once `warmup` compiled it
    self.warm = {cap: False for cap in self.buckets}
    self._compiled_collect = _counted_jit(self._collect_fn)
    self._compiled_gather = _counted_jit(self._gather_fn,
                                         static_argnums=(2,))
    self._compiled_forward = _counted_jit(self._forward_fn,
                                          static_argnums=(3,))
    self._compiled_consume = _counted_jit(self._consume_fn)

  # -- static layout --------------------------------------------------------
  def _level_widths(self) -> Tuple[int, ...]:
    widths = [1]
    for k in self.fanouts:
      widths.append(widths[-1] * k)
    return tuple(widths)

  def max_request_seeds(self) -> int:
    return self.buckets[-1]

  def bucket_for(self, n_seeds: int) -> int:
    """Smallest capacity holding ``n_seeds`` (ValueError past the
    ladder — admission refuses those with a typed error instead)."""
    for cap in self.buckets:
      if n_seeds <= cap:
        return cap
    raise ValueError(f'{n_seeds} seeds exceed the largest bucket '
                     f'{self.buckets[-1]}')

  # -- traced programs ------------------------------------------------------
  def _seed_tree(self, indptr, indices, seed):
    """One seed's sampled tree: ``[W]`` concatenated level node ids,
    keyed by (serve_key, seed id) ONLY — the per-seed determinism the
    whole coalescing contract rests on."""
    valid = seed >= 0
    skey = jax.random.fold_in(self._key, jnp.where(valid, seed, 0))
    s1 = jnp.where(valid, seed, INVALID_ID).astype(jnp.int32)[None]
    levels, _masks = expand_tree_levels(indptr, indices, s1, skey,
                                        self.fanouts)
    return jnp.concatenate(levels)

  def _collect_fn(self, seeds: jax.Array, dev: dict) -> jax.Array:
    """``[cap]`` seeds -> ``[cap, W]`` sampled trees (no features) —
    the tiered path's device half."""
    return jax.vmap(
        lambda s: self._seed_tree(dev['indptr'], dev['indices'], s)
    )(seeds)

  def _split_levels(self, flat: jax.Array) -> List[jax.Array]:
    """``[cap, W, ...]`` -> per-level ``[cap * w_t, ...]`` tensors in
    the tree-layout order `models.tree.TreeSAGE` consumes (parent-
    major within each seed block — the same layout
    `expand_tree_levels` emits)."""
    out, off = [], 0
    cap = flat.shape[0]
    for w in self.level_widths:
      lvl = flat[:, off:off + w]
      out.append(lvl.reshape((cap * w,) + flat.shape[2:]))
      off += w
    return out

  def _gather_fn(self, seeds: jax.Array, dev: dict,
                 use_pallas: bool):
    """Fully-hot, model-less bucket program: sample + feature gather
    in ONE executable.  Returns ``(nodes [cap, W], x [cap, W, D])``."""
    nodes = self._collect_fn(seeds, dev)
    x = _device_gather(dev['hot'], nodes.reshape(-1), dev['id2index'],
                       use_pallas=use_pallas)
    return nodes, x.reshape(nodes.shape + (x.shape[-1],))

  def _forward_fn(self, seeds: jax.Array, params, dev: dict,
                  use_pallas: bool):
    """Fully-hot bucket program WITH the model forward fused in:
    sample + gather + tree-layout apply.  ``(nodes, logits)``."""
    nodes = self._collect_fn(seeds, dev)
    xs = [_device_gather(dev['hot'], lvl, dev['id2index'],
                         use_pallas=use_pallas)
          for lvl in self._split_levels(nodes)]
    masks = [lvl >= 0 for lvl in self._split_levels(nodes)]
    return nodes, self.model.apply(params, xs, masks)

  def _consume_fn(self, nodes: jax.Array, x: jax.Array, params):
    """Tiered consume program: host-filled ``[cap, W, D]`` features ->
    logits (the warm second half of a tiered bucket)."""
    xs = self._split_levels(x)
    masks = [lvl >= 0 for lvl in self._split_levels(nodes)]
    return self.model.apply(params, xs, masks)

  # -- host driver ----------------------------------------------------------
  def init_params(self, rng):
    """Init model params from the level shapes (host-cheap, shapes
    only) — the serving twin of `FusedTreeEpoch.init_state`."""
    if self.model is None:
      raise ValueError('init_params() needs a model')
    d = self._feat.feature_dim
    xs = [jnp.zeros((w, d), self._feat.dtype)
          for w in self.level_widths]
    masks = [jnp.ones((w,), jnp.bool_) for w in self.level_widths]
    self.params = self.model.init(rng, xs, masks)
    return self.params

  def _pad(self, seeds: np.ndarray, cap: int) -> jax.Array:
    out = np.full((cap,), INVALID_ID, np.int32)
    out[:len(seeds)] = np.asarray(seeds, np.int32)
    return jnp.asarray(out)

  def _run_prog(self, name: str, cap: int, jit_fn, dyn_args,
                call_args, statics=()):
    """Dispatch one bucket program: the AOT-restored executable when
    `warmup` installed one, else the `_counted_jit` path.  A restored
    executable that fails AT CALL TIME (foreign device set, moved jax
    internals) is dropped and the dispatch falls back to the compile
    path — skip-to-recompile extends to runtime, not just load.
    ``statics`` are the CURRENT static-arg values: an AOT executable
    baked different ones at warmup (GLT_PALLAS toggled since) is
    bypassed for this call — env knobs keep their documented
    dispatch-time semantics (`_counted_jit`)."""
    entry = self._aot.get((name, cap))
    if entry is not None:
      fn, baked = entry
      if baked != tuple(statics):
        return jit_fn(*call_args)    # toggle may flip back: keep the
        # entry, just don't serve this call from it
      try:
        return fn(*dyn_args)
      except Exception:             # noqa: BLE001 — recompile, never
        # fail the request on a bad cached executable
        self._aot.pop((name, cap), None)
        from ..telemetry.recorder import recorder
        recorder.emit('aot.cache_miss', program=name, bucket=cap,
                      reason='error')
    return jit_fn(*call_args)

  def _repin_graph(self) -> None:
    """Streaming fence: swap in the newest published `GraphView`
    BEFORE a dispatch starts.  RCU on the `_dev` dict — a dispatch
    already in flight keeps the dict (and the immutable view arrays)
    it captured; the swap is one reference assignment, so no reader
    ever sees half a graph.  Same-shape publishes (the steady state
    under `reserve_edges`) keep every warm executable warm — topology
    rides as program ARGUMENTS; a capacity growth changes the aval
    and recompiles once per doubling."""
    if self._stream is None:
      return
    view = self._stream.pin()
    if view.version == self.graph_version:
      return
    with self._pin_lock:
      if self._pin_holds > 0:      # hold_graph(): multi-dispatch
        return                     # comparison in flight, keep the
      view = self._stream.pin()    # version it started on
      if view.version == self.graph_version:
        return
      dev = dict(self._dev)
      dev['indptr'] = view.indptr_dev
      dev['indices'] = view.indices_dev
      self._dev = dev
      self.graph_version = int(view.version)

  @contextmanager
  def hold_graph(self):
    """Freeze the pinned ``graph_version`` across SEVERAL dispatches.
    A single dispatch is always torn-read-safe on its own; use this
    when comparing dispatches against each other — the swap parity
    probe runs one coalesced candidate against per-seed references,
    and a publish landing between them would make the byte-identity
    check span two graphs (a spurious rollback, not a caught bug)."""
    self._repin_graph()            # newest version, then freeze
    with self._pin_lock:
      self._pin_holds += 1
    try:
      yield self.graph_version
    finally:
      with self._pin_lock:
        self._pin_holds -= 1

  def _dispatch(self, padded: jax.Array,
                params=None) -> ServingResult:
    """One bucket dispatch (``padded`` already at a bucket capacity).
    Warm after `warmup`: every call is an in-memory executable hit.
    ``params`` overrides the installed model version for THIS dispatch
    (the hot-swap parity probe validates a candidate this way without
    admitting traffic to it).  The graph is PINNED once here (`dev`):
    a concurrent ingest publish lands in the next dispatch, never
    mid-run — the no-torn-reads contract."""
    params = self.params if params is None else params
    if self.model is not None and params is None:
      raise ValueError(
          'ServingEngine has a model but no params — call '
          'init_params(rng) (or set .params) before serving/warmup')
    self._repin_graph()
    dev = self._dev
    cap = int(padded.shape[0])
    if self._tiered:
      import time as _time
      _sc0 = _time.monotonic()
      nodes = self._run_prog('collect', cap, self._compiled_collect,
                             (padded, dev), (padded, dev))
      #: (monotonic t0, dur) of THIS dispatch's neighbor-sampling
      #: collect program — the frontend reads it to attach a
      #: `serving.sample_collect` span under each traced rider's
      #: dispatch slice (sampling vs feature-fill cost split)
      self.last_collect = (_sc0, _time.monotonic() - _sc0)
      nodes_h = np.asarray(nodes)
      # cross-request cold-id dedup (r11): one coalesced dispatch
      # carries several riders whose trees overlap heavily under
      # skewed traffic — fetch each DISTINCT id once per run, then
      # expand by the inverse map on device.  Every rider's rows are
      # byte-identical to the undeduped lookup; the host cold tier is
      # paid per unique id instead of per (rider, occurrence).
      flat = nodes_h.reshape(-1)
      uniq, inverse = np.unique(flat, return_inverse=True)
      # power-of-two padding (INVALID_ID rows read zero) keeps the
      # number of distinct gather shapes logarithmic — a raw uniq
      # length is content-dependent and would defeat the warm-
      # executable story one compile at a time
      from ..utils.padding import next_power_of_two
      upad = next_power_of_two(max(len(uniq), 1))
      uniq_p = np.full(upad, INVALID_ID, np.int64)
      uniq_p[:len(uniq)] = uniq
      # the per-request tiered lookup: hot split + HBM cold-cache +
      # host-served misses, 'serving' telemetry scope
      import time as _time
      _cf0 = _time.monotonic()
      x_u = self._feat.get(uniq_p, scope='serving')
      #: (monotonic t0, dur) of THIS dispatch's tiered fill — the
      #: frontend reads it to attach a `serving.cold_fill` span under
      #: each traced rider's dispatch slice
      self.last_cold_fill = (_cf0, _time.monotonic() - _cf0)
      x = jnp.take(x_u, jnp.asarray(inverse.astype(np.int32)), axis=0)
      x = x.reshape(nodes_h.shape + (x.shape[-1],))
      if self.model is None:
        return ServingResult(nodes=nodes_h, x=np.asarray(x))
      xj = jnp.asarray(x)
      logits = self._run_prog('consume', cap, self._compiled_consume,
                              (nodes, xj, params),
                              (nodes, xj, params))
      return ServingResult(nodes=nodes_h, logits=np.asarray(logits))
    if self.model is None:
      nodes, x = self._run_prog(
          'gather', cap, self._compiled_gather, (padded, dev),
          (padded, dev, pallas_enabled()),
          statics=(bool(pallas_enabled()),))
      return ServingResult(nodes=np.asarray(nodes), x=np.asarray(x))
    nodes, logits = self._run_prog(
        'forward', cap, self._compiled_forward,
        (padded, params, dev),
        (padded, params, dev, pallas_enabled()),
        statics=(bool(pallas_enabled()),))
    return ServingResult(nodes=np.asarray(nodes),
                         logits=np.asarray(logits))

  def infer(self, seeds, cap: Optional[int] = None,
            params=None) -> ServingResult:
    """Serve one (possibly coalesced) seed batch; results sliced back
    to ``len(seeds)``.  ``cap`` pins the bucket (the frontend picks it
    once per coalesced dispatch); default = smallest fitting.
    ``params`` overrides the installed model version for this call
    (hot-swap validation)."""
    seeds = np.asarray(seeds).reshape(-1)
    cap = self.bucket_for(len(seeds)) if cap is None else cap
    return self._dispatch(self._pad(seeds, cap),
                          params=params).slice(0, len(seeds))

  def offline_reference(self, seeds, cap: Optional[int] = None,
                        params=None) -> ServingResult:
    """The per-seed offline loader twin: every seed served ALONE —
    through the smallest bucket by default, or a pinned ``cap`` —
    the byte-identity reference the coalesced path is tested against
    (and what a non-coalescing baseline deployment would compute).
    See the class docstring's identity fine print for which outputs
    are bitwise vs float-tolerance equal across bucket shapes."""
    parts = [self.infer(np.asarray([s]), cap=cap, params=params)
             for s in np.asarray(seeds).reshape(-1)]
    return ServingResult(
        nodes=np.concatenate([p.nodes for p in parts]),
        x=(None if parts[0].x is None
           else np.concatenate([p.x for p in parts])),
        logits=(None if parts[0].logits is None
                else np.concatenate([p.logits for p in parts])))

  def validate_params(self, params) -> None:
    """Refuse a candidate param tree that cannot ride the warm bucket
    executables: structure/shape/dtype must match the installed tree
    leaf-for-leaf (params are program ARGUMENTS, so a conforming tree
    swaps with zero recompiles and a drifted one would silently
    recompile every bucket).  Raises ValueError naming the first
    diverging leaf."""
    if self.model is None:
      raise ValueError('validate_params on a model-less engine')
    if self.params is None:
      return
    old_s = jax.tree_util.tree_structure(self.params)
    new_s = jax.tree_util.tree_structure(params)
    if old_s != new_s:
      raise ValueError(
          f'param tree structure changed ({new_s} vs installed '
          f'{old_s}) — a hot swap must keep the architecture; '
          'deploy a new engine for a new architecture')
    def _dt(x):
      # dtype off the aval — no device-to-host copy for jax leaves
      d = getattr(x, 'dtype', None)
      return d if d is not None else np.asarray(x).dtype
    for (path, old_leaf), (_, new_leaf) in zip(
        jax.tree_util.tree_leaves_with_path(self.params),
        jax.tree_util.tree_leaves_with_path(params)):
      if (tuple(np.shape(old_leaf)) != tuple(np.shape(new_leaf))
          or _dt(old_leaf) != _dt(new_leaf)):
        raise ValueError(
            f'param leaf {jax.tree_util.keystr(path)} changed '
            f'shape/dtype ({np.shape(new_leaf)} vs '
            f'{np.shape(old_leaf)}) — refused (would recompile '
            'every warm bucket)')

  def set_params(self, params, version: Optional[int] = None) -> int:
    """Install a new model version (the hot-swap COMMIT — callers go
    through `serving.swap.hot_swap`, which quiesces and parity-checks
    first).  Validates via `validate_params`; returns the new
    ``model_version``."""
    self.validate_params(params)
    self.params = params
    self.model_version = (int(version) if version is not None
                          else self.model_version + 1)
    return self.model_version

  # -- persistent AOT executables (ISSUE 13) --------------------------------
  def _aot_fingerprint(self, program: str, cap: int, dyn_args,
                       static_args) -> dict:
    """The cache key material: everything that shapes the compiled
    bucket program.  The engine seed is included because the serve
    key is a traced CLOSURE constant — two engines with different
    seeds compile different programs that would answer differently."""
    leaves = jax.tree_util.tree_leaves(dyn_args)
    return {
        'program': program, 'cap': int(cap),
        'fanouts': list(self.fanouts),
        'num_nodes': int(self.num_nodes),
        # graph SHAPE + ingest version (ISSUE 14 satellite): the
        # padded edge capacity is what the executable's avals bake,
        # and the graph_version pins which published graph this
        # entry was warmed against — a mutated graph skips a stale
        # disk executable into a fresh compile instead of serving
        # against mismatched statics.  Deliberately conservative:
        # topology rides as program ARGUMENTS, so a same-capacity
        # executable would in fact be reusable across versions — the
        # version key trades warm-restores during LIVE ingest (each
        # replica warming at a moved version recompiles) for the
        # guarantee that no entry ever outlives the graph it was
        # validated against
        'num_edges': int(self._dev['indices'].shape[0]),
        'graph_version': int(self.graph_version),
        'feature': [int(self._feat.feature_dim), str(self._feat.dtype)],
        'tiered': bool(self._tiered),
        'model': repr(self.model),
        'seed': self._seed,
        'statics': [repr(s) for s in static_args],
        # .shape/.dtype read the aval — NEVER np.asarray, which would
        # pull the full graph/feature tables device-to-host just to
        # name their dtypes (per program per bucket, on the exact
        # warm-start path the cache exists to make fast)
        'avals': [f'{tuple(x.shape)}:{x.dtype}' for x in leaves],
        # r19 kernel toggles: dispatch resolves at trace time, so a
        # program compiled with a kernel ON must never be restored
        # into a process running with it OFF (same avals, different
        # lowering)
        'kernels': [bool(pallas_enabled()),
                    bool(fused_sample_enabled()),
                    bool(pinned_cold_enabled())],
        'jax': jax.__version__,
        'backend': jax.default_backend(),
        'devices': [str(d) for d in jax.devices()],
    }

  def _aot_install(self, cache, name: str, cap: int, jit_fn,
                   dyn_args, static_args) -> None:
    """Restore one bucket program from the persistent cache, or AOT
    lower+compile it and publish the executable for the next replica."""
    fp = self._aot_fingerprint(name, cap, dyn_args, static_args)
    fn = cache.load(fp)
    if fn is None:
      compiled = jit_fn.jitted.lower(*dyn_args, *static_args).compile()
      self._aot_compiles += 1
      cache.save(fp, compiled)
      fn = compiled
    else:
      self._aot_restores += 1
    self._aot[(name, cap)] = (fn, tuple(static_args))

  def _aot_warm_bucket(self, cache, cap: int,
                       padded: jax.Array) -> None:
    """Install every program this engine mode needs at capacity
    ``cap`` (hot: gather|forward; tiered: collect[+consume])."""
    use_pallas = bool(pallas_enabled())
    if self._tiered:
      self._aot_install(cache, 'collect', cap, self._compiled_collect,
                        (padded, self._dev), ())
      if self.model is not None:
        # consume's avals hang off collect's output: run the (now
        # AOT) collect once to shape them
        nodes = self._run_prog('collect', cap, self._compiled_collect,
                               (padded, self._dev),
                               (padded, self._dev))
        x0 = jnp.zeros(tuple(nodes.shape) + (self._feat.feature_dim,),
                       self._feat.dtype)
        self._aot_install(cache, 'consume', cap,
                          self._compiled_consume,
                          (nodes, x0, self.params), ())
    elif self.model is None:
      self._aot_install(cache, 'gather', cap, self._compiled_gather,
                        (padded, self._dev), (use_pallas,))
    else:
      self._aot_install(cache, 'forward', cap, self._compiled_forward,
                        (padded, self.params, self._dev),
                        (use_pallas,))

  def warmup(self, aot_cache='env') -> dict:
    """AOT-compile every bucket program at server start (the tiered
    host fill + consume included), so the first real request — and
    every one after — hits a warm executable.  With
    ``GLT_AOT_CACHE_DIR`` set (or an `AotExecutableCache` passed),
    bucket executables are restored from the persistent cache instead
    of recompiling — the warm-from-disk replica-replacement path —
    and fresh compiles are published back for the next replica.
    Returns ``{'buckets': {...}, 'compiles': n, 'secs': wall,
    'aot_restored': k}``."""
    import time
    from ..utils.profiling import metrics
    if aot_cache == 'env':
      from . import aot_cache as _aot_mod
      cache = _aot_mod.from_env()
    else:
      cache = aot_cache
    t0 = time.perf_counter()
    self._repin_graph()               # warm against the newest version
    n = min(self.num_nodes, 8)
    before = self.compile_count()
    restores_before = self._aot_restores
    for cap in self.buckets:
      # valid ids (0..n-1 cycled) + one INVALID tail slot when the
      # bucket has room: both the masked and unmasked arms warm up
      seeds = np.arange(cap, dtype=np.int32) % n
      if cap > 1:
        seeds[-1] = INVALID_ID
      padded = jnp.asarray(seeds)
      if cache is not None:
        self._aot_warm_bucket(cache, cap, padded)
      self._dispatch(padded)
      self.warm[cap] = True
    secs = time.perf_counter() - t0
    compiles = self.compile_count() - before
    metrics.inc('serving.warmup.secs', secs)
    return {'buckets': dict(self.warm), 'compiles': compiles,
            'secs': round(secs, 3),
            # restores counted by THIS warmup (not a lifetime delta —
            # a re-warm that restores over a prior compile still
            # reports its restores)
            'aot_restored': self._aot_restores - restores_before}

  def compile_count(self) -> int:
    """Total compiles across the engine's programs (the
    `_counted_jit` per-callable counters, plus AOT lower+compiles
    the persistent cache could not serve) — snapshot before traffic,
    compare after: a nonzero delta after `warmup` means a shape
    escaped the bucket ladder.  Zero after a warmup that restored
    every bucket from ``GLT_AOT_CACHE_DIR`` — the warm-start pin."""
    return self._aot_compiles + sum(fn.compiles for fn in (
        self._compiled_collect, self._compiled_gather,
        self._compiled_forward, self._compiled_consume))

  def compile_status(self) -> dict:
    """Per-bucket warm status + compile counters (the heartbeat's
    serving block)."""
    return {'buckets': {str(c): bool(w) for c, w in self.warm.items()},
            'compiles': self.compile_count(),
            'aot_programs': len(self._aot),
            'model_version': self.model_version,
            'graph_version': self.graph_version,
            'tiered': self._tiered}
