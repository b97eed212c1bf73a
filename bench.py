"""Headline benchmark: GraphSAGE epoch time + sampling throughput
+ feature-gather roofline + distributed (virtual-mesh) loader section
+ fused whole-epoch number.

PRIMARY metric (BASELINE.json: "GraphSAGE epoch time on
ogbn-products"): wall-clock of one full training epoch — seed shuffle
-> multi-hop sampling (fanout [15, 10, 5], batch 1024,
`examples/train_sage_ogbn_products.py:16`) -> feature/label collation
-> fused train step — on an ogbn-products-scale synthetic graph (2.45M
nodes, ~61M directed edges, 100-dim features, ~8% train split).
The HEADLINE `value` is the whole-epoch `FusedEpoch` time (the same
epoch as ONE XLA program); the per-batch epoch median is always
reported alongside.

MEASUREMENT PROTOCOL.  Dispatch is asynchronous, so every timed
number here:
  * derives a SCALAR from the computation and pulls it via float()
    (a d2h value dependency: the wall ends when the work has);
  * uses distinct arguments per timed call;
  * is cross-checked against an analytic HBM floor
    (`*_floor_secs`, from the chip's peak in `DEVICE_PEAKS`); a wall
    below its floor is physically impossible, is flagged
    `suspect_elision` and is excluded from the headline.

SETUP COST: the graph + features + labels are generated ON DEVICE
(`benchmarks/common.build_graph_csr_device`, device-native Dataset
paths) — zero host↔device upload.

SECONDARY: the reference's "Sampled Edges per secs" definition
(`benchmarks/api/bench_sampler.py:46-54`), a feature-gather roofline
phase (achieved vs ACHIEVABLE: the best row-granular rate XLA's gather
reaches this session, and the streaming bound for context), and a
`dist` section — a P=8 virtual-CPU-mesh distributed
loader run with >= 2 epochs so `exchange_slack='adaptive'` shows its
padding-waste trajectory.

``vs_baseline`` divides a NOMINAL single-A100 epoch time of 2.0 s into
the headline (the reference publishes figures, not numbers — 2.0 s is
a mid-range read of public GLT-class A100 pipelines on this workload;
BASELINE.md documents the absence of published values).  > 1.0 means
faster than that nominal A100.

ARTIFACT CONTRACT (r6): the FULL aggregate JSON is written to
`BENCH_ARTIFACT.json` (`GLT_BENCH_ARTIFACT` overrides the path) after
every completed phase — atomic replace, so a kill at any point leaves
the newest complete artifact on disk.  Stdout carries only a SHORT
summary line (<= 2000 chars, `telemetry.sink.summary_line`) naming the
artifact file (the full aggregate outgrows a 2000-char stdout tail).
The dist section also runs with the flight recorder
on, writing per-hop padding / slack-transition / exchange events to
`BENCH_TELEMETRY.jsonl` (`GLT_TELEMETRY_JSONL` overrides).

`--trace-dir DIR` captures an xprof trace (TensorBoard profile plugin
format) around the fused session's epoch dispatches, which carry
`StepTraceAnnotation` step markers.

`--check-regression` runs the bench regression gate after the final
artifact lands (`telemetry/regress.py`, loaded by path like the sink):
the artifact's headline metrics are compared against
`BENCH_BASELINE.json` (`GLT_BENCH_BASELINE` / `--baseline` override;
created FROM this artifact on the first run) and the driver exits
nonzero with a per-metric report when any metric slows more than the
threshold (default 20%; `--regress-threshold 0.1` /
`GLT_REGRESS_THRESHOLD`).  The compact verdict is stamped into the
artifact summary line under `regression`.
"""
import json
import os
import statistics
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchmarks.common import (NUM_NODES, build_graph,  # noqa: E402
                               cpu_mesh_env)

#: nominal single-A100 epoch seconds (see module docstring)
BASELINE_EPOCH_SECS = 2.0
#: round-1 normalization constant for the secondary sampling metric
BASELINE_EDGES_PER_SEC = 100e6
#: per-chip peaks keyed by ``jax.devices()[0].device_kind``:
#: (HBM bytes/s — the floor and `*_hbm_frac` denominator; f32 FLOP/s —
#: the `train_step_mfu` denominator, model runs f32).  v5e: Google
#: Cloud "TPU v5e" — 819 GB/s HBM, 197 TFLOP/s bf16 (f32 taken as 1/4).
#: A device that is not in the table is an error (`_device_peaks`):
#: a missing peak used to zero the floor and switch the
#: `suspect_elision` check off without a word.
DEVICE_PEAKS = {'TPU v5 lite': (819e9, 49.2e12)}

FANOUT = (15, 10, 5)
BATCH = 1024
DIM = 100
CLASSES = 47
SAMPLE_ITERS = 30
EPOCHS_PER_SESSION = 2

#: dist section: smaller graph (CPU mesh), reference bench workload
#: shape at half batch — r5 shrank it (batch 1024, 4 batches/epoch,
#: 500k nodes needed ~100 s/batch on the 8x-oversubscribed virtual
#: mesh and could not finish 3 adaptive epochs inside any budget);
#: numbers remain RELATIVE, the config is in the artifact
DIST_PARTS = 8
DIST_NODES = 200_000
DIST_DIM = 64
DIST_BATCH = 512
DIST_BATCHES_PER_EPOCH = 2


def _arg_after(flag: str):
  """Value following ``flag`` on argv (None when absent)."""
  if flag in sys.argv:
    i = sys.argv.index(flag)
    if i + 1 < len(sys.argv):
      return sys.argv[i + 1]
  return None


def _device_peaks(jax):
  """``(hbm_bytes_per_s, f32_flops)`` of the chip this worker runs on;
  an unknown ``device_kind`` raises instead of defaulting."""
  kind = jax.devices()[0].device_kind
  if kind not in DEVICE_PEAKS:
    raise SystemExit(
        f'bench.py: no peaks for device_kind {kind!r} (known: '
        f'{sorted(DEVICE_PEAKS)}); add its published HBM bandwidth and '
        'f32 FLOP/s to DEVICE_PEAKS — a measurement needs its chip')
  return DEVICE_PEAKS[kind]


def _pull(x) -> float:
  """Force REAL completion: a scalar d2h value dependency ends the
  timed window when the work has ended (module docstring)."""
  import jax.numpy as jnp
  return float(jnp.sum(x))


def _pull_state(state) -> float:
  import jax
  return _pull(jax.tree_util.tree_leaves(state.params)[0])


def _sample_window_bytes(batch, fanouts):
  """See `benchmarks.common.sample_window_bytes` (one definition)."""
  from benchmarks.common import sample_window_bytes
  return sample_window_bytes(batch, fanouts)


def _tree_step_flops(batch, fanouts, dim, hidden, classes):
  """Analytic fwd+bwd matmul FLOPs of one tree-layout SAGE step
  (`models.tree.TreeSAGE`): layer ``l`` applies its self+neighbor
  matmul pair to every level that still matters."""
  sizes = [batch]
  for k in fanouts:
    sizes.append(sizes[-1] * int(k))
  num_layers = len(fanouts)
  dims = [dim] + [hidden] * (num_layers - 1) + [classes]
  fwd = 0
  for l in range(num_layers):
    rows = sum(sizes[t] for t in range(num_layers - l))
    fwd += 2 * rows * dims[l] * dims[l + 1] * 2
  return 3 * fwd


def _sage_step_flops(node_cap, fanouts, batch, dim, hidden, classes,
                     num_layers=3):
  """Analytic forward+backward FLOPs of one supervised SAGE step on
  the padded static shapes (matmuls only; the segment mean/sum and
  elementwise tails are bandwidth, not FLOPs).  Each SAGE layer runs
  two [rows, in]x[in, out] matmuls (self + aggregated neighbor); the
  backward pass costs ~2x the forward's matmul FLOPs."""
  rows = node_cap
  dims = [dim] + [hidden] * (num_layers - 1) + [classes]
  fwd = 0
  for lin, lout in zip(dims[:-1], dims[1:]):
    fwd += 2 * rows * lin * lout * 2        # 2 matmuls per layer
  return 3 * fwd                            # fwd + ~2x bwd


def _build_device_dataset(jax, jnp, feat_dtype=None):
  """Products-scale synthetic dataset generated entirely on device
  (zero upload — module docstring, SETUP COST)."""
  from benchmarks.common import build_graph_csr_device
  from graphlearn_tpu.data import Dataset
  n = int(os.environ.get('GLT_BENCH_NODES', NUM_NODES))  # smoke knob
  indptr, indices, _ = build_graph_csr_device(n)
  kf, kl = jax.random.split(jax.random.key(7))
  feats = jax.random.uniform(kf, (n, DIM), jnp.float32)
  if feat_dtype is not None:
    feats = feats.astype(feat_dtype)
  labels = jax.random.randint(kl, (n,), 0, CLASSES, jnp.int32)
  ds = (Dataset()
        .init_graph((indptr, indices), layout='CSR', num_nodes=n)
        .init_node_features(feats)
        .init_node_labels(labels))
  return ds, n


def worker(fused_only: bool = False):
  """One fresh-session measurement under the r5 pull-protocol: the
  per-batch epoch (x EPOCHS_PER_SESSION), then sampling throughput,
  then the feature-gather roofline.  ``fused_only`` is the DEDICATED
  fused session: same setup, then the whole-epoch `FusedEpoch`
  measured as a first-class program (compile walls reported, steady
  state = median of 3 pulled runs with distinct epoch keys)."""
  import jax
  if '--cpu' in sys.argv:
    jax.config.update('jax_platforms', 'cpu')
  from graphlearn_tpu.utils.compile_cache import enable_compile_cache
  enable_compile_cache()
  import jax.numpy as jnp
  import optax
  from graphlearn_tpu.loader import NeighborLoader
  from graphlearn_tpu.models import (GraphSAGE, create_train_state,
                                     make_supervised_step)
  from graphlearn_tpu.sampler import NeighborSampler

  t_setup = time.perf_counter()
  ds, n = _build_device_dataset(jax, jnp)
  _pull(ds.get_graph().indptr[-8:])        # sync: graph build done
  _pull(ds.node_features.hot_tier[0])
  setup_secs = round(time.perf_counter() - t_setup, 1)
  platform = jax.devices()[0].platform
  peak, f32_peak = _device_peaks(jax)
  train_idx = np.random.default_rng(0).permutation(n)[:max(n // 12, 1)]
  loader = NeighborLoader(ds, list(FANOUT), train_idx, batch_size=BATCH,
                          shuffle=True, seed=0)
  node_cap = NeighborSampler(ds.get_graph(), FANOUT,
                             seed=0).node_capacity(BATCH)
  steps = len(loader)
  # analytic per-epoch HBM floor: the feature gather's table reads
  # alone (node_cap rows x DIM f32 per step) — everything else
  # (windows, labels, model) only raises it, so a wall BELOW this is
  # physically impossible and flags a broken measurement
  epoch_floor = steps * node_cap * DIM * 4 / peak
  step_flops = _sage_step_flops(node_cap, FANOUT, BATCH, DIM, 256,
                                CLASSES)

  # sampler-pipeline compile = wall of the very first batch
  t0 = time.perf_counter()
  it0 = iter(loader)
  first_batch = next(it0)
  _pull(first_batch.x)
  sampler_compile = time.perf_counter() - t0
  model = GraphSAGE(hidden_features=256, out_features=CLASSES,
                    num_layers=3)
  tx = optax.adam(3e-3)
  state, apply_fn = create_train_state(
      model, jax.random.key(0), first_batch, tx)

  if fused_only:
    # the fused HEADLINE is the TREE-LAYOUT epoch (`FusedTreeEpoch` —
    # scatter-free, sort-free).  The subgraph fused path (the reference's
    # dedup estimator) is measured after it when budget remains.
    tree_flops = _tree_step_flops(BATCH, FANOUT, DIM, 256, CLASSES)
    result = {'mode': 'fused-session', 'platform': platform,
              'epoch_floor_secs': round(epoch_floor, 4),
              'fused_layout': 'tree',
              'tree_step_flops': tree_flops,
              'setup_secs': setup_secs, 'steps': steps}
    try:
      from graphlearn_tpu.loader import FusedEpoch, FusedTreeEpoch
      from graphlearn_tpu.models import TreeSAGE
      tree = TreeSAGE(hidden_features=256, out_features=CLASSES,
                      num_layers=3)
      fused = FusedTreeEpoch(ds, list(FANOUT), train_idx, tree, tx,
                             batch_size=BATCH, shuffle=True, seed=0,
                             max_steps_per_program=100)
      tstate = fused.init_state(jax.random.key(0))
      # --trace-dir: xprof capture around the headline epochs (the
      # fused drivers wrap each dispatch in a StepTraceAnnotation, so
      # the timeline segments by chunk).  The finally covers the
      # COMPILE dispatch too — jax materializes the trace only on
      # stop_trace, and the compile is the most expensive thing the
      # flag exists to profile.
      trace_dir = _arg_after('--trace-dir')
      runs = []
      try:
        if trace_dir:
          from graphlearn_tpu.utils.profiling import start_trace
          start_trace(trace_dir)
          result['trace_dir'] = trace_dir
        t0 = time.perf_counter()
        tstate, _ = fused.run(tstate)
        _pull_state(tstate)
        result['fused_compile_secs'] = round(time.perf_counter() - t0,
                                             1)
        print(json.dumps(result), flush=True)
        for _ in range(3):          # distinct epoch keys per run
          t0 = time.perf_counter()
          tstate, _ = fused.run(tstate)
          _pull_state(tstate)
          runs.append(round(time.perf_counter() - t0, 4))
      finally:
        if trace_dir:
          from graphlearn_tpu.utils.profiling import stop_trace
          stop_trace()
      result['fused_epoch_runs'] = runs
      med = statistics.median(runs)
      result['epoch_secs_fused'] = med
      result['suspect_elision'] = bool(med < epoch_floor)
      result['train_step_mfu'] = (
          round(tree_flops / (med / steps) / f32_peak, 4)
          if med >= epoch_floor else None)
      print(json.dumps(result), flush=True)
      # bf16 compute variant (MXU half precision, f32 params)
      tree16 = TreeSAGE(hidden_features=256, out_features=CLASSES,
                        num_layers=3, dtype=jnp.bfloat16)
      fused16 = FusedTreeEpoch(ds, list(FANOUT), train_idx, tree16, tx,
                               batch_size=BATCH, shuffle=True, seed=0,
                               max_steps_per_program=100)
      state16 = fused16.init_state(jax.random.key(0))
      t0 = time.perf_counter()
      state16, _ = fused16.run(state16)
      _pull_state(state16)
      result['fused_bf16_compile_secs'] = round(
          time.perf_counter() - t0, 1)
      runs16 = []
      for _ in range(2):
        t0 = time.perf_counter()
        state16, _ = fused16.run(state16)
        _pull_state(state16)
        runs16.append(round(time.perf_counter() - t0, 4))
      result['fused_epoch_runs_bf16'] = runs16
      med16 = statistics.median(runs16)
      # same floor as f32: only the COMPUTE dtype is bf16 here — the
      # feature table (the floor's byte source) stays f32
      result['fused_epoch_secs_bf16'] = (
          med16 if med16 >= epoch_floor else None)
      print(json.dumps(result), flush=True)
      # subgraph fused path (the reference's dedup estimator).
      # Measured on a 96-step SUBSET (one chunk): its step is
      # scatter-bound, the very thing the tree layout removes, and a
      # full epoch would not fit the session budget — the artifact
      # reports ms/step instead.
      if os.environ.get('GLT_BENCH_SUBGRAPH_FUSED', '1') != '0':
        sub_steps = 96
        sub = FusedEpoch(ds, list(FANOUT), train_idx[:BATCH * sub_steps],
                         apply_fn, tx, batch_size=BATCH, shuffle=True,
                         seed=0, remat=True,
                         max_steps_per_program=sub_steps)
        t0 = time.perf_counter()
        state, _ = sub.run(state)
        _pull_state(state)
        result['fused_subgraph_compile_secs'] = round(
            time.perf_counter() - t0, 1)       # compile + first run
        t0 = time.perf_counter()
        state, _ = sub.run(state)
        _pull_state(state)
        sub_dt = time.perf_counter() - t0
        result['fused_subgraph_ms_per_step'] = round(
            1000 * sub_dt / sub_steps, 1)
        result['fused_subgraph_epoch_secs_est'] = round(
            sub_dt / sub_steps * steps, 2)
    except Exception as e:          # noqa: BLE001
      result['fused_error'] = f'{type(e).__name__}: {e}'[:200]
    print(json.dumps(result), flush=True)
    return

  step = make_supervised_step(apply_fn, tx, BATCH)

  # step compile = wall of the first train-step call; together with
  # the sampler compile above this is the per-batch pipeline's full
  # compile cost
  t0 = time.perf_counter()
  state, loss, _ = step(state, first_batch)
  _pull_state(state)
  compile_secs = sampler_compile + time.perf_counter() - t0
  # two more batches cover the donated-layout recompile
  for i, batch in enumerate(it0):
    state, loss, _ = step(state, batch)
    if i >= 1:
      break
  _pull_state(state)

  epochs = []
  for _ in range(EPOCHS_PER_SESSION):
    t0 = time.perf_counter()
    for batch in loader:
      state, loss, _ = step(state, batch)
    _pull_state(state)
    epochs.append(round(time.perf_counter() - t0, 4))
  valid = [e for e in epochs if e >= epoch_floor]
  result = {'epoch_runs': epochs,
            'epoch_secs': (statistics.median(valid) if valid else None),
            'epoch_floor_secs': round(epoch_floor, 4),
            'suspect_elision': len(valid) < len(epochs),
            'compile_secs': round(compile_secs, 1),
            'sampler_compile_secs': round(sampler_compile, 1),
            'steps': steps, 'mode': 'primary',
            'node_cap': int(node_cap),
            'train_step_flops': step_flops,
            'setup_secs': setup_secs,
            'platform': platform}
  if valid:
    result['train_step_mfu'] = round(
        step_flops / (statistics.median(valid) / steps) / f32_peak, 4)
  # CHECKPOINT the line after every phase: a timeout mid-sampling or
  # mid-roofline must not cost the already-measured PRIMARY number
  print(json.dumps(result), flush=True)

  # secondary: sampling-only DEVICE throughput, reference metric
  # definition ("Sampled Edges per secs").  The whole burst runs as
  # ONE scan program over [iters, B] seed batches — a per-batch
  # dispatch loop here would time host dispatch, not the sampler.
  # AOT-compiled, first execution, value pull.
  iters = SAMPLE_ITERS
  from benchmarks.common import make_sample_burst
  g = ds.get_graph()
  srng = np.random.default_rng(1)
  seeds_all = jnp.asarray(
      srng.integers(0, n, (iters, BATCH)).astype(np.int32))
  sample_burst = make_sample_burst(FANOUT, node_cap, iters)
  comp = jax.jit(sample_burst).lower(
      g.indptr, g.indices, seeds_all, jax.random.key(11)).compile()
  t0 = time.perf_counter()
  edges = int(comp(g.indptr, g.indices, seeds_all, jax.random.key(12)))
  dt = time.perf_counter() - t0
  window_bytes = iters * _sample_window_bytes(BATCH, FANOUT)
  result.update(edges_per_sec=edges / dt,
                sample_secs=round(dt, 4),
                sample_floor_secs=round(window_bytes / peak, 4),
                sample_hbm_frac=round(window_bytes / dt / peak, 4))
  print(json.dumps(result), flush=True)

  # roofline phase: achieved vs ACHIEVABLE for the feature-row
  # gather.  Three AOT-compiled programs, each timed on its
  # FIRST execution with a value pull:
  #   gather      — the real pattern (sorted ~50%-dense ids, D=100)
  #   gather_128  — same ids on a lane-padded [n,128] table (rules
  #                 out alignment as the limiter)
  #   stream      — contiguous block copy of the same byte volume
  #                 (the extraction-free streaming bound)
  # The ACHIEVABLE bound for a row-granular gather is taken as the
  # best row rate measured this session (`ops/pallas_gather.py`
  # documents the kernel attempts at beating it).
  if n > (1 << 21) + 8:
    # (the n guard keeps the GLT_BENCH_NODES smoke knob from driving
    # randint maxval negative — ids span [start, start + 2*grows) —
    # and measuring clamped garbage accesses)
    grows = 1 << 20
    from jax import lax

    def make_prog(kind, d, giters):
      def run(table, key):
        def body(i, acc):
          k = jax.random.fold_in(key, i)
          start = jax.random.randint(k, (), 0,
                                     table.shape[0] - 2 * grows)
          if kind == 'stream':
            rows = lax.dynamic_slice(table, (start, 0), (grows, d))
          else:
            ids = start + 2 * jnp.arange(grows, dtype=jnp.int32)
            rows = jnp.take(table, ids, axis=0)
          rows = lax.optimization_barrier(rows)
          return acc + rows.sum(dtype=jnp.float32)
        return lax.fori_loop(0, giters, body, jnp.float32(0))
      return run

    def timed(kind, table, giters):
      d = table.shape[1]
      fn = jax.jit(make_prog(kind, d, giters))
      comp = fn.lower(table, jax.random.key(3)).compile()
      t0 = time.perf_counter()
      float(comp(table, jax.random.key(4)))
      dt = time.perf_counter() - t0
      gb = giters * grows * d * 4 / 1e9
      return gb / dt, dt

    # volumes sized for >= 2 s of device time per program, so the
    # dispatch + value-pull constant is a small part of each wall
    hot = ds.node_features.hot_tier
    g100, _ = timed('gather', hot, 240)
    hot128 = jnp.pad(hot, ((0, 0), (0, 28)))
    g128, _ = timed('gather', hot128, 240)
    stream, _ = timed('stream', hot128, 1200)
    del hot128
    rows_per_s = max(g100 * 1e9 / (DIM * 4), g128 * 1e9 / (128 * 4))
    achievable = rows_per_s * DIM * 4 / 1e9       # GB/s at D=100 rows
    result.update(
        gather_gbps=round(g100, 1),
        gather_gbps_d128=round(g128, 1),
        stream_gbps=round(stream, 1),
        gather_rows_per_sec_M=round(rows_per_s / 1e6, 1),
        gather_achievable_gbps=round(achievable, 1),
        gather_hbm_frac=round(g100 * 1e9 / peak, 4),
        gather_achievable_frac=round(achievable * 1e9 / peak, 4),
        gather_achieved_vs_achievable=round(g100 / achievable, 3),
        stream_hbm_frac=round(stream * 1e9 / peak, 4))
  print(json.dumps(result), flush=True)


#: hetero session: ogbn-mag-scale synthetic (reference workload:
#: `examples/hetero/train_hgt_mag.py:102-121` — paper/author/cites/
#: writes, 349 classes)
MAG_PAPER, MAG_AUTHOR, MAG_CLASSES, MAG_DIM = 736_389, 1_134_649, 349, 128


def hetero_worker():
  """On-chip `FusedHeteroEpoch` measurement: RGCN
  training epochs on a device-built MAG-scale hetero graph as one
  scan program per chunk, pull-protocol timed."""
  import jax
  if '--cpu' in sys.argv:
    jax.config.update('jax_platforms', 'cpu')
  from graphlearn_tpu.utils.compile_cache import enable_compile_cache
  enable_compile_cache()
  import jax.numpy as jnp
  import optax
  from benchmarks.common import build_bipartite_csr_device
  from graphlearn_tpu.data import Dataset
  from graphlearn_tpu.loader import FusedHeteroEpoch, NeighborLoader  # noqa: F401
  from graphlearn_tpu.models import RGCN
  from graphlearn_tpu.models.train import TrainState

  t_setup = time.perf_counter()
  np_, na = MAG_PAPER, MAG_AUTHOR
  if os.environ.get('GLT_BENCH_NODES'):          # smoke knob
    np_ = int(os.environ['GLT_BENCH_NODES'])
    na = np_ * 3 // 2
  P_, A = 'paper', 'author'
  cites = build_bipartite_csr_device(np_, np_, 7, seed=1)
  writes = build_bipartite_csr_device(na, np_, 7, seed=2)
  rev = build_bipartite_csr_device(np_, na, 4, seed=3)
  kf1, kf2, kl = jax.random.split(jax.random.key(9), 3)
  etypes = {(P_, 'cites', P_): cites, (A, 'writes', P_): writes,
            (P_, 'rev_writes', A): rev}
  ds = (Dataset()
        .init_graph(etypes, layout='CSR',
                    num_nodes={P_: np_, A: na})
        .init_node_features(
            {P_: jax.random.uniform(kf1, (np_, MAG_DIM), jnp.float32),
             A: jax.random.uniform(kf2, (na, MAG_DIM), jnp.float32)})
        .init_node_labels(
            {P_: jax.random.randint(kl, (np_,), 0, MAG_CLASSES,
                                    jnp.int32)}))
  _pull(ds.node_features[P_].hot_tier[0])
  result = {'mode': 'hetero-session',
            'platform': jax.devices()[0].platform,
            'setup_secs': round(time.perf_counter() - t_setup, 1),
            'paper': np_, 'author': na, 'classes': MAG_CLASSES}
  batch, fanouts, steps = 512, [10, 10], 64
  train_idx = np.random.default_rng(0).permutation(np_)[:batch * steps]
  model = RGCN(etypes=tuple(etypes.keys()), hidden_features=128,
               out_features=MAG_CLASSES, num_layers=2,
               target_ntype=P_)
  tx = optax.adam(1e-3)
  fused = FusedHeteroEpoch(ds, fanouts, (P_, train_idx), model.apply,
                           tx, batch_size=batch, shuffle=True, seed=0,
                           max_steps_per_program=steps)
  result.update(batch=batch, fanouts=fanouts, steps=steps)
  # init params from one tiny traced batch via the fused machinery's
  # own collation (shapes only)
  seeds0 = jnp.asarray(train_idx[:batch].astype(np.int32))
  b0 = fused._sample_collate(seeds0, jax.random.key(0), fused._dev,
                             False)
  params = model.init(jax.random.key(0), b0.x_dict,
                      b0.edge_index_dict, b0.edge_mask_dict)
  state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
  t0 = time.perf_counter()
  state, _ = fused.run(state)
  _pull_state(state)
  result['fused_hetero_compile_secs'] = round(time.perf_counter() - t0,
                                              1)
  print(json.dumps(result), flush=True)
  runs = []
  for _ in range(2):
    t0 = time.perf_counter()
    state, stats = fused.run(state)
    _pull_state(state)
    runs.append(round(time.perf_counter() - t0, 4))
  result['fused_hetero_epoch_runs'] = runs
  result['fused_hetero_epoch_secs'] = statistics.median(runs)
  result['fused_hetero_ms_per_step'] = round(
      1000 * statistics.median(runs) / steps, 1)
  print(json.dumps(result), flush=True)


def dist_worker():
  """P=8 virtual-mesh distributed loader run: the
  reference dist-bench workload (batch 1024, fanout [15,10,5]) on the
  mesh engine, run for MULTIPLE epochs with ``exchange_slack=
  'adaptive'`` so the artifact records the padding-waste trajectory
  as the capacity ladder converges (r4 shipped only the static
  slack-2.0 floor, 58.9%).  CPU-mesh numbers are RELATIVE (no ICI);
  the label says so.  A complete JSON line is printed after every
  phase (adaptive / tiered / fused-mesh) so the harness can salvage
  whatever finished."""
  import jax
  # NOTE: deliberately NOT enabling the /tmp compilation cache here —
  # XLA:CPU AOT cache entries recorded with different target-feature
  # sets load with "could lead to SIGILL" errors on this box and
  # killed the worker mid-phase when tried.
  from graphlearn_tpu.parallel import (DistDataset, DistNeighborLoader,
                                       make_mesh)
  from graphlearn_tpu.telemetry import recorder
  # flight recorder ON for the dist section: per-hop padding fill,
  # slack-ladder transitions, exchange/cold-tier deltas land in a
  # JSONL next to the artifact (costs one nsn sync per batch — this
  # section measures exchange accounting, not dispatch latency)
  jsonl_path = os.environ.get('GLT_TELEMETRY_JSONL',
                              'BENCH_TELEMETRY.jsonl')
  # fresh flight log per bench run: close any import-time file handle
  # FIRST (with GLT_TELEMETRY_JSONL set, the recorder enabled at
  # import holding this very path — unlinking under it would orphan
  # the inode and lose every event), then unlink, then (re)open
  recorder.disable()
  try:
    os.unlink(jsonl_path)
  except OSError:
    pass
  recorder.enable(jsonl_path)
  assert len(jax.devices()) == DIST_PARTS, jax.devices()
  rows, cols = build_graph(DIST_NODES)
  rng = np.random.default_rng(0)
  feats = rng.random((DIST_NODES, DIST_DIM), dtype=np.float32)
  labels = rng.integers(0, CLASSES, DIST_NODES).astype(np.int32)
  ds = DistDataset.from_full_graph(DIST_PARTS, rows, cols,
                                   node_feat=feats, node_label=labels,
                                   num_nodes=DIST_NODES)
  seeds = rng.permutation(DIST_NODES)[
      :DIST_BATCH * DIST_PARTS * DIST_BATCHES_PER_EPOCH]
  mesh = make_mesh(DIST_PARTS)
  loader = DistNeighborLoader(ds, list(FANOUT), seeds,
                              batch_size=DIST_BATCH,
                              shuffle=True, mesh=mesh, seed=0,
                              exchange_slack='adaptive')
  epochs = int(os.environ.get('GLT_BENCH_DIST_EPOCHS', 3))
  t0 = time.perf_counter()
  waste_by_epoch, compile_secs, edges, n_batches = [], None, 0, 0
  t_epoch = time.perf_counter()
  for ep in range(epochs):
    prev = loader.sampler.exchange_stats(tick_metrics=False)
    for i, b in enumerate(iter(loader)):
      if ep == 0 and i == 0:
        compile_secs = time.perf_counter() - t_epoch
      edges += int(np.asarray(b.edge_mask.sum()))
      n_batches += 1
    st = loader.sampler.exchange_stats(tick_metrics=False)
    sent = ((st['dist.frontier.offered'] - prev['dist.frontier.offered'])
            - (st['dist.frontier.dropped'] - prev['dist.frontier.dropped']))
    slots = st['dist.frontier.slots'] - prev['dist.frontier.slots']
    waste_by_epoch.append(round(100.0 * (1 - sent / max(slots, 1)), 2))
  dt = time.perf_counter() - t0
  st = loader.sampler.exchange_stats(tick_metrics=False)
  drop = 100.0 * st['dist.frontier.dropped'] / max(
      st['dist.frontier.offered'], 1)
  out = {
      'label': 'virtual CPU mesh - relative only',
      'num_parts': DIST_PARTS, 'batch': DIST_BATCH,
      'fanout': list(FANOUT),
      'num_nodes': DIST_NODES, 'batches': n_batches, 'epochs': epochs,
      'compile_secs': round(compile_secs or 0.0, 1),
      'edges_per_sec_per_chip': round(
          edges / max(dt - (compile_secs or 0), 1e-9) / DIST_PARTS, 1),
      'seeds_per_sec': round(
          n_batches * DIST_BATCH * DIST_PARTS
          / max(dt - (compile_secs or 0), 1e-9), 1),
      'exchange_slack': 'adaptive',
      'padding_waste_pct_by_epoch': waste_by_epoch,
      'padding_waste_pct': waste_by_epoch[-1] if waste_by_epoch else None,
      'drop_rate_pct': round(drop, 3),
      # cluster-wide derived aggregates (== host-local on this
      # single-controller mesh; sums host cold counters at multi-host)
      'cluster': loader.sampler.cluster_exchange_stats(),
      'flight_recorder': jsonl_path,
      'slack_transitions': len(recorder.events('slack.transition')),
      # the adaptive phase runs recorder-ON (it IS the attribution
      # phase); its seeds/edges rates carry the per-batch nsn sync +
      # JSONL writes.  All later timed windows run recorder-off.
      'recorder_on_during_adaptive': True,
  }
  # adaptive-phase numbers are safe NOW: if the later phases time out,
  # the harness takes the last printed JSON line
  print(json.dumps(out), flush=True)
  # recorder OFF for the remaining TIMED windows (README: attribution
  # on, throughput off — the per-batch nsn sync + JSONL writes must
  # not ride inside a measured loop); re-enabled briefly around the
  # fused warm run below so its hop events still land in the JSONL
  recorder.disable()
  # tiered store in the MEASURED path: same workload, 30% of each
  # partition's rows in "HBM", the rest served by the r10 cold-cache +
  # pipelined overlay (benchmarks/README "Cold-tier cache").  The
  # cache gets the EQUAL-HBM-BUDGET size (one hot shard's rows per
  # device) so the dynamic-vs-static comparison is spend-for-spend.
  ds_t = DistDataset.from_full_graph(DIST_PARTS, rows, cols,
                                     node_feat=feats, node_label=labels,
                                     num_nodes=DIST_NODES,
                                     split_ratio=0.3)
  # prefetch=2: the next batch's cold-tier overlay (a host sync) runs
  # on a worker thread while the current batch computes
  cache_rows = int(np.max(ds_t.node_features.hot_counts))
  lt = DistNeighborLoader(ds_t, list(FANOUT), seeds,
                          batch_size=DIST_BATCH, shuffle=True,
                          mesh=mesh, seed=0, prefetch=2,
                          cold_cache_rows=cache_rows)
  # r05-PROTOCOL window (the comparison target for the guarded
  # `dist.tiered.seeds_per_sec`): first batch warms the compiles, the
  # REMAINDER OF THE EPOCH is timed — identical to the r5 measurement
  # that scored the static split 250.6, so the delta is machinery, not
  # protocol.  With prefetch + the dispatch-ahead pipeline, the timed
  # batches' sampling and cold service largely overlap the warm
  # window — which is the point being measured.
  it = iter(lt)
  b = next(it)
  b.x.block_until_ready()
  t0 = time.perf_counter()
  nt = 0
  for b in it:
    b.x.block_until_ready()
    nt += 1
  dt_t = time.perf_counter() - t0
  # STEADY-STATE window: epochs 2..n timed whole (every dispatch and
  # every cold service inside the timer) — the conservative number,
  # and the denominator window for the hit rates (cache warm)
  st_w = lt.sampler.exchange_stats(tick_metrics=False)
  t0 = time.perf_counter()
  ns = 0
  for _ in range(max(epochs - 1, 1)):
    for b in iter(lt):
      b.x.block_until_ready()
      ns += 1
  dt_s = time.perf_counter() - t0
  st_t = lt.sampler.exchange_stats(tick_metrics=False)
  d = {k: st_t[k] - st_w[k] for k in
       ('dist.feature.lookups', 'dist.feature.cold_lookups',
        'dist.feature.cold_misses', 'dist.feature.cache_hits')}
  lk = max(d['dist.feature.lookups'], 1)
  cl = max(d['dist.feature.cold_lookups'], 1)
  out['tiered'] = {
      'split_ratio': 0.3, 'prefetch': 2,
      'cold_cache_rows': cache_rows,
      'cold_pipeline': lt._cold_pipeline,
      'seeds_per_sec': round(
          nt * DIST_BATCH * DIST_PARTS / max(dt_t, 1e-9), 1),
      'steady_state_seeds_per_sec': round(
          ns * DIST_BATCH * DIST_PARTS / max(dt_s, 1e-9), 1),
      'steady_state_epochs': max(epochs - 1, 1),
      # r10 vocabulary (benchmarks/README "Cold-tier metrics"):
      # lookups/cold_lookups are the DENOMINATORS the two hit rates
      # divide by — r5 printed cold_misses with no denominator.
      # Steady-state (post-warm-epoch) deltas.
      'lookups': d['dist.feature.lookups'],
      'cold_lookups': d['dist.feature.cold_lookups'],
      'cold_misses': d['dist.feature.cold_misses'],
      'cache_hits': d['dist.feature.cache_hits'],
      'hot_hit_rate': round(1.0 - cl / lk, 4),
      'cache_hit_rate': round(
          1.0 - d['dist.feature.cold_misses'] / cl, 4),
      # the DIRECT successor of r5's (misnamed) "cold_hit_rate 0.329":
      # the fraction of ALL feature lookups served on-device — static
      # hot tier + dynamic cache together vs the host
      'hbm_served_rate': round(
          1.0 - d['dist.feature.cold_misses'] / lk, 4),
  }
  out['tiered']['cold_hit_rate'] = out['tiered']['cache_hit_rate']
  # nested twin of the guarded dotted keys: `dist.feature.cache_hit_rate`
  # resolves here (regress._get walks dict levels, not literal dots)
  out['feature'] = {
      'cache_hit_rate': out['tiered']['cache_hit_rate'],
      'hot_hit_rate': out['tiered']['hot_hit_rate'],
      'hbm_served_rate': out['tiered']['hbm_served_rate'],
      'cold_lookups': out['tiered']['cold_lookups'],
  }
  print(json.dumps(out), flush=True)

  # -- cache-aware GNS row (r11): same tiered store, sampler-side bias --
  # Identical workload/protocol as the tiered row, with Global
  # Neighbor Sampling on: neighbor selection biased toward hot split ∪
  # cache residents with the 1/q correction (benchmarks/README
  # "Cache-aware sampling").  Feeds the guarded
  # `dist.gns.cache_hit_rate` / `dist.gns.seeds_per_sec` keys; the
  # ceiling being broken is `budget_over_universe` (the r10 honesty
  # note's 0.056).
  lg = DistNeighborLoader(ds_t, list(FANOUT), seeds,
                          batch_size=DIST_BATCH, shuffle=True,
                          mesh=mesh, seed=0, prefetch=2,
                          cold_cache_rows=cache_rows, gns=True)
  it = iter(lg)
  b = next(it)
  b.x.block_until_ready()
  t0 = time.perf_counter()
  ng = 0
  for b in it:
    b.x.block_until_ready()
    ng += 1
  dt_g = time.perf_counter() - t0
  st_w = lg.sampler.exchange_stats(tick_metrics=False)
  t0 = time.perf_counter()
  ngs = 0
  for b in iter(lg):
    b.x.block_until_ready()
    ngs += 1
  dt_gs = time.perf_counter() - t0
  st_g = lg.sampler.exchange_stats(tick_metrics=False)
  dg = {k: st_g[k] - st_w[k] for k in
        ('dist.feature.lookups', 'dist.feature.cold_lookups',
         'dist.feature.cold_misses', 'dist.feature.cache_hits')}
  clg = max(dg['dist.feature.cold_lookups'], 1)
  counts = np.diff(ds_t.graph.bounds)
  cold_universe = int(np.maximum(
      counts - ds_t.node_features.hot_counts, 0).sum())
  out['gns'] = {
      'split_ratio': 0.3, 'boost': float(lg.sampler.gns_boost),
      'cold_cache_rows': cache_rows,
      'budget_over_universe': round(
          cache_rows / max(cold_universe, 1), 4),
      'seeds_per_sec': round(
          ng * DIST_BATCH * DIST_PARTS / max(dt_g, 1e-9), 1),
      'steady_state_seeds_per_sec': round(
          ngs * DIST_BATCH * DIST_PARTS / max(dt_gs, 1e-9), 1),
      'lookups': dg['dist.feature.lookups'],
      'cold_lookups': dg['dist.feature.cold_lookups'],
      'cold_misses': dg['dist.feature.cold_misses'],
      'cache_hits': dg['dist.feature.cache_hits'],
      'cache_hit_rate': round(
          1.0 - dg['dist.feature.cold_misses'] / clg, 4),
      'hot_hit_rate': round(
          1.0 - clg / max(dg['dist.feature.lookups'], 1), 4),
      'vs_gns_off_cache_hit_rate': out['tiered']['cache_hit_rate'],
  }
  print(json.dumps(out), flush=True)

  # fused mesh epoch vs per-batch DP loop, SAME shape; the fused
  # program now also runs its evaluate() pass
  import optax
  from graphlearn_tpu.models import GraphSAGE, create_train_state
  from graphlearn_tpu.parallel import (FusedDistEpoch,
                                       local_batch_piece,
                                       make_dp_supervised_step,
                                       replicate)
  b2, fan2 = 512, [10, 5]
  seeds2 = rng.permutation(DIST_NODES)[:b2 * DIST_PARTS * 4]
  it2 = iter(DistNeighborLoader(ds, fan2, seeds2, batch_size=b2,
                                shuffle=True, mesh=mesh, seed=0))
  t0 = time.perf_counter()
  b0 = next(it2)
  b0.x.block_until_ready()
  pb_sampler_compile = time.perf_counter() - t0
  b0_local = local_batch_piece(b0, DIST_PARTS)
  model = GraphSAGE(hidden_features=64, out_features=CLASSES,
                    num_layers=2)
  tx = optax.adam(3e-3)
  state, apply_fn = create_train_state(
      model, jax.random.key(0), b0_local, tx)
  step = make_dp_supervised_step(apply_fn, tx, b2, mesh)
  state = replicate(state, mesh)
  t0 = time.perf_counter()
  state, _, _ = step(state, b0)
  jax.tree_util.tree_leaves(state.params)[0].block_until_ready()
  pb_compile = pb_sampler_compile + time.perf_counter() - t0
  npb = 0
  t0 = time.perf_counter()
  for b in it2:
    state, _, _ = step(state, b)
    npb += 1
  jax.tree_util.tree_leaves(state.params)[0].block_until_ready()
  pb_dt = time.perf_counter() - t0
  fused = FusedDistEpoch(ds, fan2, seeds2, apply_fn, tx, batch_size=b2,
                         mesh=mesh, shuffle=True, seed=0)
  fstate, _ = create_train_state(model, jax.random.key(1), b0_local, tx)
  fstate = replicate(fstate, mesh)
  t0 = time.perf_counter()
  fstate, _ = fused.run(fstate)
  jax.tree_util.tree_leaves(fstate.params)[0].block_until_ready()
  f_compile = time.perf_counter() - t0
  # warm run with the recorder ON: the fused epoch's per-hop
  # padding-fill events land in the JSONL without touching the timed
  # window below
  recorder.enable(jsonl_path)
  fstate, _ = fused.run(fstate)         # donated-layout recompile
  jax.tree_util.tree_leaves(fstate.params)[0].block_until_ready()
  recorder.disable()
  t0 = time.perf_counter()
  fstate, _ = fused.run(fstate)
  jax.tree_util.tree_leaves(fstate.params)[0].block_until_ready()
  f_dt = time.perf_counter() - t0
  pb_rate = npb * b2 * DIST_PARTS / max(pb_dt, 1e-9)
  f_rate = len(fused) * b2 * DIST_PARTS / max(f_dt, 1e-9)
  out['fused_mesh'] = {
      'batch': b2, 'fanout': fan2,
      'per_batch_seeds_per_sec': round(pb_rate, 1),
      'fused_seeds_per_sec': round(f_rate, 1),
      'fused_vs_per_batch': round(f_rate / max(pb_rate, 1e-9), 2),
      'per_batch_compile_secs': round(pb_compile, 1),
      'fused_compile_secs': round(f_compile, 1),
  }
  try:
    acc = fused.evaluate(fstate.params, seeds2[:b2 * DIST_PARTS])
    out['fused_mesh']['eval_acc'] = round(float(acc), 4)
  except Exception as e:            # noqa: BLE001
    out['fused_mesh']['eval_error'] = f'{type(e).__name__}: {e}'[:160]
  print(json.dumps(out), flush=True)

  # TREE-layout mesh epochs (r5 flagship, distributed form): same
  # shape as the fused_mesh comparison above
  try:
    from graphlearn_tpu.models import TreeSAGE
    from graphlearn_tpu.parallel import FusedDistTreeEpoch
    tmodel = TreeSAGE(hidden_features=64, out_features=CLASSES,
                      num_layers=2)
    tfused = FusedDistTreeEpoch(ds, fan2, seeds2, tmodel, tx,
                                batch_size=b2, mesh=mesh,
                                shuffle=True, seed=0)
    tstate = tfused.init_state(jax.random.key(2))
    t0 = time.perf_counter()
    tstate, _ = tfused.run(tstate)
    jax.tree_util.tree_leaves(tstate.params)[0].block_until_ready()
    t_compile = time.perf_counter() - t0
    tstate, _ = tfused.run(tstate)       # donated-layout recompile
    jax.tree_util.tree_leaves(tstate.params)[0].block_until_ready()
    t0 = time.perf_counter()
    tstate, _ = tfused.run(tstate)
    jax.tree_util.tree_leaves(tstate.params)[0].block_until_ready()
    t_dt = time.perf_counter() - t0
    out['fused_mesh']['tree_seeds_per_sec'] = round(
        len(tfused) * b2 * DIST_PARTS / max(t_dt, 1e-9), 1)
    out['fused_mesh']['tree_compile_secs'] = round(t_compile, 1)
  except Exception as e:            # noqa: BLE001
    out['fused_mesh']['tree_error'] = f'{type(e).__name__}: {e}'[:160]
  print(json.dumps(out), flush=True)


def _run_session(timeout: int, fused: bool = False):
  cmd = [sys.executable, os.path.abspath(__file__),
         '--fused-session' if fused else '--bench-worker']
  cmd += [a for a in sys.argv[1:]
          if a not in ('--bench-worker', '--fused-session')]
  try:
    out = subprocess.run(cmd, capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.abspath(__file__)),
                         timeout=timeout)
    stdout = out.stdout or ''
    stderr = out.stderr or ''
  except subprocess.TimeoutExpired as e:
    # each session prints one complete JSON line as soon as its
    # numbers exist — salvage whatever made it out before the kill
    print(f'session timed out after {timeout}s (parsing partial '
          f'output)', file=sys.stderr)
    stdout = e.stdout or b''
    if isinstance(stdout, bytes):
      stdout = stdout.decode(errors='replace')
    stderr = e.stderr or b''
    if isinstance(stderr, bytes):
      stderr = stderr.decode(errors='replace')
  for ln in reversed(stdout.strip().splitlines()):
    if ln.startswith('{'):
      try:
        return json.loads(ln)
      except json.JSONDecodeError:
        continue      # truncated mid-print: fall through to the
                      # previous (complete) line
  print(f'session failed:\n{stdout[-2000:]}\n{stderr[-2000:]}',
        file=sys.stderr)
  return None


def _run_dist_section(timeout: int):
  cmd = [sys.executable, os.path.abspath(__file__), '--dist-worker']
  timed_out = False
  try:
    out = subprocess.run(cmd, capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.abspath(__file__)),
                         env=cpu_mesh_env(DIST_PARTS), timeout=timeout)
    stdout, stderr = out.stdout or '', out.stderr or ''
  except subprocess.TimeoutExpired as e:
    # the worker prints a complete JSON line after EVERY phase —
    # salvage the last one
    timed_out = True
    stdout = e.stdout or b''
    if isinstance(stdout, bytes):
      stdout = stdout.decode(errors='replace')
    stderr = e.stderr or b''
    if isinstance(stderr, bytes):
      stderr = stderr.decode(errors='replace')
  for ln in reversed(stdout.strip().splitlines()):
    if ln.startswith('{'):
      try:
        r = json.loads(ln)
      except json.JSONDecodeError:
        continue
      if timed_out:
        r['note'] = f'partial: dist worker hit the {timeout}s budget'
      return r
  cause = (f'timed out after {timeout}s with no JSON'
           if timed_out else 'failed')
  return {'error': f'dist section {cause}: {stderr[-500:]}'}


def _run_hetero_session(timeout: int):
  """Spawn the hetero fused session; parse its last JSON line."""
  cmd = [sys.executable, os.path.abspath(__file__), '--hetero-session']
  try:
    out = subprocess.run(cmd, capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.abspath(__file__)),
                         timeout=timeout)
    stdout = out.stdout or ''
  except subprocess.TimeoutExpired as e:
    stdout = e.stdout or b''
    if isinstance(stdout, bytes):
      stdout = stdout.decode(errors='replace')
  for ln in reversed(stdout.strip().splitlines()):
    if ln.startswith('{'):
      try:
        return json.loads(ln)
      except json.JSONDecodeError:
        continue
  return None


def _run_envelope_row(num_parts: int, batch: int, timeout: int):
  """One P-row of the scale envelope: spawn the tiny
  `bench_dist_loader.py --envelope-worker` config on a ``num_parts``
  virtual mesh and parse its JSON line (None on failure/timeout)."""
  script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'benchmarks', 'bench_dist_loader.py')
  cmd = [sys.executable, script, '--envelope-worker', '--num-parts',
         str(num_parts), '--mode', 'homo', '--batch', str(batch),
         '--nodes', '20000', '--epochs', '5']
  try:
    out = subprocess.run(cmd, capture_output=True, text=True,
                         env=cpu_mesh_env(num_parts), timeout=timeout)
  except subprocess.TimeoutExpired:
    return None
  for ln in reversed((out.stdout or '').strip().splitlines()):
    if ln.startswith('{'):
      try:
        return json.loads(ln)
      except json.JSONDecodeError:
        continue
  return None


def _run_dist_loader_row(flags, timeout: int, env=None, pin_key=None):
  """Shared `benchmarks/bench_dist_loader.py` subprocess harness for
  the chaos / resume / failover rows: spawn with ``flags``, scan
  stdout bottom-up for the last JSON line, return the parsed row
  (None on timeout / no parseable output).  With ``pin_key`` the
  worker's exit verdict is stamped into that key ('ok'/'FAILED') so
  the pin survives in the artifact, not only in a discarded code."""
  script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'benchmarks', 'bench_dist_loader.py')
  cmd = [sys.executable, script, *flags]
  try:
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=timeout)
  except subprocess.TimeoutExpired:
    return None
  for ln in reversed((out.stdout or '').strip().splitlines()):
    if ln.startswith('{'):
      try:
        r = json.loads(ln)
      except json.JSONDecodeError:
        continue
      if pin_key is not None:
        r[pin_key] = 'ok' if out.returncode == 0 else 'FAILED'
      return r
  return None


def _run_chaos_row(timeout: int):
  """The `bench_dist_loader.py --chaos` resilience smoke in a
  subprocess; returns its JSON row (None on failure/timeout)."""
  return _run_dist_loader_row(('--chaos',), timeout)


def _run_resume_row(timeout: int):
  """The `bench_dist_loader.py --resume` preemption-resume smoke in a
  subprocess; returns its JSON row (None on failure/timeout)."""
  return _run_dist_loader_row(('--resume',), timeout)


def _run_failover_row(timeout: int):
  """The `bench_dist_loader.py --failover` elastic-failover smoke
  (ISSUE 15) on the 8-device virtual mesh: one partition owner killed
  mid-epoch with a durable shard under GLT_SHARD_DIR — a survivor
  adopts, the epoch must complete EXACTLY (completed_ratio 1.0,
  batches byte-identical to the fault-free run, ONE adoption).  The
  worker exits nonzero unless the pin holds — stamped into
  ``failover_pin``.  Feeds the dist.failover.recovery_secs /
  dist.failover.completed_ratio regression guards."""
  r = _run_dist_loader_row(('--failover', '--nodes', '5000'), timeout,
                           env=cpu_mesh_env(8),
                           pin_key='failover_pin')
  if r is not None and r['failover_pin'] != 'ok':
    print('failover phase: epoch not exactly complete / not '
          'byte-identical / adoption count wrong (see dist.failover)',
          file=sys.stderr)
  return r


def _run_bench_serving(timeout: int, extra_args=(),
                       script_name='bench_serving.py', env=None):
  """Shared benchmarks/ subprocess harness for the serving, fleet,
  ingest and autoscale phases: spawn with forced-CPU env (optionally a
  caller-supplied one, e.g. cpu_mesh_env for phases that need a
  virtual device mesh), scan stdout bottom-up for the last JSON line,
  return (row, returncode) — or None on timeout/no-parseable-output."""
  script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'benchmarks', script_name)
  cmd = [sys.executable, script, '--cpu', *extra_args]
  env = dict(env if env is not None else os.environ)
  env.setdefault('JAX_PLATFORMS', 'cpu')
  try:
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=timeout)
  except subprocess.TimeoutExpired:
    return None
  for ln in reversed((out.stdout or '').strip().splitlines()):
    if ln.startswith('{'):
      try:
        return json.loads(ln), out.returncode
      except json.JSONDecodeError:
        continue
  return None


def _run_serving_row(timeout: int):
  """The `bench_serving.py` online-serving phase (ISSUE 9) in a
  subprocess: Zipf open-loop traffic against the coalescing tier on a
  single CPU device — p50/p95/p99 + sustained QPS + shed rate feed
  the dist.serving.p99_ms / dist.serving.qps regression guards, and
  the worker exits nonzero if any shape recompiled after warmup.
  Returns its last JSON row (None on failure/timeout)."""
  got = _run_bench_serving(timeout)
  if got is None:
    return None
  r, returncode = got
  # the worker exits nonzero when ANY phase recompiled after
  # warmup OR the mid-run live-ops scrape failed validation
  # (r13: bench_serving runs with the ops endpoint on and
  # strictly parses /metrics during traffic) — stamp the verdict
  # into the artifact row so the pin is visible there, not only
  # in a discarded exit code
  r['recompile_pin'] = 'ok' if returncode == 0 else 'FAILED'
  if returncode != 0:
    print('serving phase: recompile after warmup or failed '
          'live-ops scrape (see dist.serving rows / the ops '
          'block)', file=sys.stderr)
  return r


def _run_fleet_row(timeout: int):
  """`bench_serving.py --fleet 3` (ISSUE 13): the Zipf open loop
  spread over 3 in-process replicas behind the `FleetRouter`, with a
  chaos stall-then-kill on one replica mid-run.  The worker exits
  nonzero when any request failed/dropped across the failover or the
  fleet qps recovered to < 0.6x pre-kill — stamped into
  ``failover_pin`` so the verdict survives in the artifact.  Returns
  the fleet keys (``fleet_qps`` / ``failover_failed_requests`` /
  ``recovery_ratio`` / ``redriven`` / ``evictions`` + the full
  ``fleet`` row) to merge into the dist.serving block."""
  got = _run_bench_serving(timeout, extra_args=('--fleet', '3'))
  if got is None or 'fleet' not in got[0]:
    return None
  r, returncode = got
  keys = ('fleet_qps', 'failover_failed_requests',
          'recovery_ratio', 'redriven', 'evictions',
          'traced_tail_count', 'traced_tail_max_spans',
          'fleet_headroom_qps')
  row = {k: r[k] for k in keys if k in r}
  row['fleet'] = r['fleet']
  row['failover_pin'] = 'ok' if returncode == 0 else 'FAILED'
  if returncode != 0:
    print('fleet phase: failed/dropped requests, qps recovery below '
          '0.6x across the mid-run replica kill, or the tracing '
          'acceptance (>=1 slow-tail trace with >=5 spans + a live '
          'headroom gauge) failed (see dist.serving.fleet)',
          file=sys.stderr)
  return row


def _run_ingest_row(timeout: int):
  """`benchmarks/bench_ingest.py` (ISSUE 14): the freshness-vs-
  throughput open loop — events/s ingested through the WAL-backed
  delta-CSR pipeline while the Zipf serving load holds its p99.  The
  worker exits nonzero on ANY shed/errored request during
  steady-state ingest, a recompile after warmup, or unapplied lag at
  the end — stamped into ``ingest_pin``.  Feeds
  dist.ingest.events_per_sec / dist.ingest.p99_during_ingest_ms."""
  got = _run_bench_serving(timeout, script_name='bench_ingest.py')
  if got is None:
    return None
  r, returncode = got
  if 'events_per_sec' not in r:        # died before the final row
    return None
  r['ingest_pin'] = 'ok' if returncode == 0 else 'FAILED'
  if returncode != 0:
    print('ingest phase: shed/error during steady-state ingest, '
          'recompile after warmup, or unapplied lag (see '
          'dist.ingest)', file=sys.stderr)
  return r


def _run_autoscale_row(timeout: int):
  """`benchmarks/bench_autoscale.py` (ISSUE 19): the diurnal open
  loop against the `ElasticController` — sinusoidal arrivals over a
  1→3-replica fleet with a chaos-failed first spawn (typed rollback)
  and a mid-epoch planned partition handoff on the 8-device virtual
  mesh.  The worker exits nonzero unless the fleet scaled out AND
  back in, every request completed, the burn stayed < 1 outside the
  chaos incident, the elastic p99 held vs the static baseline, and
  the handoff produced zero degraded batches with exactly one
  PartitionBook bump — stamped into ``autoscale_pin``.  Feeds
  dist.autoscale.p99_held_ms / .burn_max /
  .handoff_degraded_batches."""
  got = _run_bench_serving(timeout, script_name='bench_autoscale.py',
                           env=cpu_mesh_env(8))
  if got is None:
    return None
  r, returncode = got
  if 'p99_held_ms' not in r:           # died before the final row
    return None
  r['autoscale_pin'] = 'ok' if returncode == 0 else 'FAILED'
  if returncode != 0:
    print('autoscale phase: fleet failed to scale out+in, a request '
          'failed, burn >= 1 outside the chaos incident, elastic p99 '
          'regressed vs static, or the handoff degraded a batch (see '
          'dist.autoscale)', file=sys.stderr)
  return r


def _run_pallas_row(timeout: int):
  """`benchmarks/bench_pallas_sample.py` (ISSUE 18): FusedEpoch step
  time through the r19 `sample_one_hop_auto` dispatcher with the knob
  OFF (the threading must cost the default path nothing), the
  pinned-host cold gather at split<1 against the FIXED 1.355 GB/s
  untiered XLA line, and the delta-CSR merge rate.  Runs on whatever
  accelerator the driver sees — the kernel-ON rows are hardware-only
  and skip cleanly on CPU (interpret-mode walls measure the
  interpreter, not the lowering).  Feeds pallas.fused_step_ms /
  pallas.feature_lookup_gbps / pallas.delta_merge_events_per_sec."""
  script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'benchmarks', 'bench_pallas_sample.py')
  cmd = [sys.executable, script, '--quick']
  try:
    out = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=timeout)
  except subprocess.TimeoutExpired:
    return None
  for ln in reversed((out.stdout or '').strip().splitlines()):
    if ln.startswith('{'):
      try:
        r = json.loads(ln)
      except json.JSONDecodeError:
        continue
      if r.get('metric') == 'pallas_sample':   # per-row emit lines
        return r                               # also start with '{'
  return None


def _aggregate(results, fused_res, dist, hetero=None, pallas=None):
  """The full artifact schema from whatever phases have completed so
  far.  The HEADLINE `value` is the fused whole-epoch time when the
  fused session has landed (and passed its floor check), else the
  per-batch epoch median.  Printed after EVERY completed phase —
  the last JSON line on stdout is always the newest complete
  aggregate, so a kill at ANY point leaves a parseable artifact."""
  ep = sorted(r['epoch_secs'] for r in results
              if r.get('epoch_secs') is not None)
  # spread over FLOOR-VALID runs only: an elision-flagged wall must
  # not reappear as the series min (the r5 protocol's whole point);
  # salvaged sessions without per-run lists contribute their median
  all_runs = []
  for r in results:
    runs = r.get('epoch_runs') or (
        [r['epoch_secs']] if r.get('epoch_secs') is not None else [])
    floor = r.get('epoch_floor_secs', 0.0)
    all_runs += [e for e in runs if e >= floor]
  es = sorted(r['edges_per_sec'] for r in results
              if 'edges_per_sec' in r)
  cs = sorted(r['compile_secs'] for r in results if 'compile_secs' in r)
  fused_ok = (fused_res and fused_res.get('epoch_secs_fused') is not None
              and not fused_res.get('suspect_elision'))
  fu = [fused_res['epoch_secs_fused']] if fused_ok else []
  med_ep = statistics.median(ep) if ep else None
  med_es = statistics.median(es) if es else None
  platform = (results[0]['platform'] if results
              else (fused_res or {}).get('platform', '?'))
  shape = (f'products-scale synthetic, fanout {list(FANOUT)}, '
           f'batch {BATCH}, {platform}')
  if fu:
    metric = f'graphsage_fused_epoch_secs ({shape})'
    value = round(fu[0], 4)
  else:
    metric = f'graphsage_epoch_secs ({shape})'
    value = round(med_ep, 4) if med_ep is not None else None
  mfu = [r['train_step_mfu'] for r in results
         if r.get('train_step_mfu') is not None]
  if fused_res and fused_res.get('train_step_mfu') is not None:
    mfu.append(fused_res['train_step_mfu'])
  gather = {}
  for k in ('gather_gbps', 'gather_gbps_d128', 'stream_gbps',
            'gather_rows_per_sec_M', 'gather_achievable_gbps',
            'gather_hbm_frac', 'gather_achievable_frac',
            'gather_achieved_vs_achievable', 'stream_hbm_frac'):
    v = [r[k] for r in results if r.get(k) is not None]
    if v:
      gather[k] = round(statistics.median(v), 4)
  hbm = {}
  sf = [r['sample_hbm_frac'] for r in results
        if r.get('sample_hbm_frac') is not None]
  if sf:
    hbm['sample'] = round(statistics.median(sf), 4)
  if 'gather_hbm_frac' in gather:
    hbm['gather'] = gather['gather_hbm_frac']
  floors = [r['epoch_floor_secs'] for r in results
            if r.get('epoch_floor_secs') is not None]
  return {
      'metric': metric,
      'value': value,
      'unit': 's',
      'vs_baseline': (round(BASELINE_EPOCH_SECS / value, 4)
                      if value else None),
      'protocol': 'r5 pull+floor (r2-r4 walls not comparable)',
      'epoch_secs_min_med_max': (
          [round(min(all_runs), 4), round(med_ep, 4),
           round(max(all_runs), 4)] if ep and all_runs else None),
      'epoch_floor_secs': (round(statistics.median(floors), 4)
                           if floors else None),
      'epoch_vs_baseline': (round(BASELINE_EPOCH_SECS / med_ep, 4)
                            if med_ep else None),
      'sampled_edges_per_sec_M_min_med_max': (
          [round(es[0] / 1e6, 1), round(med_es / 1e6, 1),
           round(es[-1] / 1e6, 1)] if es else None),
      'sampling_vs_a100_nominal': (round(med_es / BASELINE_EDGES_PER_SEC,
                                         2) if med_es else None),
      'fused_epoch_secs': round(fu[0], 4) if fu else None,
      'fused_layout': (fused_res or {}).get('fused_layout'),
      'fused_epoch_runs': (fused_res or {}).get('fused_epoch_runs'),
      'fused_vs_baseline': (round(BASELINE_EPOCH_SECS / fu[0], 4)
                            if fu else None),
      'fused_epoch_secs_bf16': (fused_res or {}).get(
          'fused_epoch_secs_bf16'),
      'fused_subgraph_ms_per_step': (fused_res or {}).get(
          'fused_subgraph_ms_per_step'),
      'fused_subgraph_epoch_secs_est': (fused_res or {}).get(
          'fused_subgraph_epoch_secs_est'),
      'fused_compile_secs': (fused_res or {}).get('fused_compile_secs'),
      'fused_bf16_compile_secs': (fused_res or {}).get(
          'fused_bf16_compile_secs'),
      'fused_error': (fused_res or {}).get('fused_error'),
      'fused_suspect_elision': (fused_res or {}).get('suspect_elision'),
      'train_step_mfu': (round(statistics.median(mfu), 4)
                         if mfu else None),
      'compile_secs_med': (round(statistics.median(cs), 1)
                           if cs else None),
      'achieved_hbm_frac': hbm or None,
      'gather_roofline': gather or None,
      'fused_hetero_epoch_secs': (hetero or {}).get(
          'fused_hetero_epoch_secs'),
      'fused_hetero_ms_per_step': (hetero or {}).get(
          'fused_hetero_ms_per_step'),
      'hetero': hetero,
      'sessions': len(results),
      'session_modes': [r['mode'] for r in results],
      'steps_per_epoch': results[0]['steps'] if results else None,
      'dist': dist,
      'pallas': pallas,
  }


_SINK = None
_REGRESS = None


def _light_module(name: str, cache: str):
  """Load a json-only telemetry module directly by file path, keeping
  the driver process free of the full package (and jax) import
  chain."""
  import importlib.util
  p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   'graphlearn_tpu', 'telemetry', f'{name}.py')
  spec = importlib.util.spec_from_file_location(cache, p)
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def _sink_module():
  """Load `telemetry/sink.py` directly by file path: the sink is
  json/os/tempfile-only, and loading it this way keeps the driver
  process free of the full package (and jax) import chain."""
  global _SINK
  if _SINK is None:
    _SINK = _light_module('sink', '_bench_sink')
  return _SINK


def _regress_module():
  """Load `telemetry/regress.py` by file path (json/os-only, like the
  sink)."""
  global _REGRESS
  if _REGRESS is None:
    _REGRESS = _light_module('regress', '_bench_regress')
  return _REGRESS


def _run_regression_gate(art) -> int:
  """The `--check-regression` gate (telemetry.regress): compare the
  just-written artifact against BENCH_BASELINE.json (created from this
  artifact on the first run, since the trajectory starts empty), print
  the per-metric report, stamp the compact verdict into the artifact's
  summary, and return the exit code: 0 = PASS/baseline created, 1 = a
  headline metric slowed past the threshold, 2 = the gate itself could
  not run (which must NOT fail a completed bench — main() exits
  nonzero only on rc 1)."""
  try:
    regress = _regress_module()
    sink = _sink_module()
    thr = _arg_after('--regress-threshold')
    try:
      thr = float(thr) if thr else None
    except ValueError:
      # a typo'd flag must not crash the gate AFTER the whole bench
      # ran: degrade to the env/default threshold like regress does
      print(f'--regress-threshold {thr!r} is not a number; using the '
            'default', file=sys.stderr)
      thr = None
    # gate the IN-MEMORY aggregate when we have it: if the artifact
    # sink degraded to stdout this run, the file on disk may be a
    # STALE previous run's — it must never be what gets gated
    verdict, rc = regress.check(
        art if art is not None else sink.artifact_path(),
        baseline=_arg_after('--baseline'),
        threshold=thr)
    print(regress.format_report(verdict), flush=True)
    if art is not None:
      # re-emit with the verdict so the artifact file + the bounded
      # summary line both carry it ('regression' sits near the front
      # of sink._SUMMARY_KEYS — a FAIL survives line degradation).
      # Best-effort: a re-emit failure must not downgrade an rc-1
      # verdict to the non-fatal rc 2 (CI would miss the regression).
      try:
        art = dict(art)
        art['regression'] = regress.summary(verdict)
        art['regression_report'] = verdict
        print(_emit_artifact(art), flush=True)
      except Exception as e:      # noqa: BLE001
        print(f'could not stamp the regression verdict into the '
              f'artifact ({type(e).__name__}: {e})', file=sys.stderr)
    return rc
  except Exception as e:          # noqa: BLE001 — the gate must
    # report, never traceback-crash a driver whose bench phases all
    # completed (missing artifact, unreadable baseline, ...)
    print(f'regression gate could not run '
          f'({type(e).__name__}: {e})', file=sys.stderr)
    return 2


def _emit_artifact(art):
  """The r6 artifact sink contract: write the FULL aggregate to the
  artifact file (atomic) and return the short stdout summary line —
  always <= 2000 chars, always naming the artifact file.  The driver's
  last-JSON-line salvage parses the summary; the evidence lives in the
  file.

  Degrades, never dies: if the sink cannot write (read-only cwd, disk
  full), the FULL aggregate JSON goes to stdout exactly as before r6 —
  a sink failure must not cost the measurement (the indestructible-
  artifact contract this sink exists to strengthen)."""
  try:
    sink = _sink_module()
    path = sink.write_artifact(art)
    return sink.summary_line(art, artifact=path)
  except Exception as e:            # noqa: BLE001 — degrade to stdout
    print(f'artifact sink failed ({type(e).__name__}: {e}); '
          f'falling back to full JSON on stdout', file=sys.stderr)
    return json.dumps(art)


def main():
  sessions = int(os.environ.get('GLT_BENCH_SESSIONS', 4))
  session_timeout = int(os.environ.get('GLT_BENCH_SESSION_TIMEOUT', 420))
  # hard wall for the whole harness, sized INSIDE the driver's wall:
  # with the zero-upload setup a primary session costs ~2-4 min and
  # the fused session ~4-6 min (compile-dominated); slow days degrade
  # phase by phase, each one leaving a fresh cumulative artifact line
  total_budget = float(os.environ.get('GLT_BENCH_TOTAL_BUDGET', 1200))
  dist_timeout = int(os.environ.get('GLT_BENCH_DIST_TIMEOUT', 600))
  fused_timeout = int(os.environ.get('GLT_BENCH_FUSED_TIMEOUT', 600))
  t_start = time.monotonic()

  def budget_left():
    return total_budget - (time.monotonic() - t_start)

  results, fused_res, dist, hetero = [], None, None, None
  pallas_row = [None]
  last_art = [None]

  def emit():
    """The indestructible-artifact contract: full cumulative
    aggregate to the artifact FILE after every completed phase;
    stdout gets only the bounded summary line."""
    if results or fused_res or dist or hetero or pallas_row[0]:
      last_art[0] = _aggregate(results, fused_res, dist, hetero,
                               pallas_row[0])
      print(_emit_artifact(last_art[0]), flush=True)

  # phase 1 — one primary session (epochs + sampling + roofline).
  attempts = 0
  while not results and attempts < 3:
    tmo = int(min(session_timeout, max(budget_left() - 60, 120)))
    if budget_left() < 180:
      print(f'budget: giving up on primary after {attempts} attempts',
            file=sys.stderr)
      break
    r = _run_session(tmo)
    attempts += 1
    if r is not None:
      results.append(r)
      emit()

  # phase 2 — dedicated fused session (whole-epoch FusedEpoch,
  # always fresh compiles): lands the HEADLINE number
  if budget_left() > 150:
    fused_res = _run_session(
        int(min(fused_timeout, max(budget_left() - 10, 120))),
        fused=True)
    emit()
  else:
    print(f'budget: skipping the fused session '
          f'({budget_left():.0f}s left)', file=sys.stderr)

  # phase 3 — dist section (virtual CPU mesh; emits a
  # complete JSON line after EVERY internal phase)
  if budget_left() > 90:
    dist = _run_dist_section(
        int(min(dist_timeout, max(budget_left() - 30, 60))))
    emit()
  else:
    print(f'budget: skipping dist ({budget_left():.0f}s left)',
          file=sys.stderr)

  # phase 3b — hetero fused session.  ~100-150 s with
  # a warm compile cache (the MAG-scale graph builders and the RGCN
  # scan all cache); it outranks extra primary sessions — a unique
  # datum beats another sample of an existing one
  if budget_left() > 200:
    hetero = _run_hetero_session(
        int(min(480, max(budget_left() - 20, 120))))
    emit()
  else:
    print(f'budget: skipping hetero ({budget_left():.0f}s left)',
          file=sys.stderr)

  # phase 3c — per-P scale-envelope rows for the dist section (each
  # ~60-120 s; a new datum, so it outranks extra primary samples —
  # the r5 runs where this sat after phase 4 never reached it)
  if not (isinstance(dist, dict) and 'error' not in dist):
    print('skipping envelope rows: no dist section to attach to',
          file=sys.stderr)
  elif budget_left() <= 160:
    print(f'budget: skipping envelope rows ({budget_left():.0f}s left)',
          file=sys.stderr)
  else:
    env_rows = []
    for p_, bsz in ((16, 64), (64, 32)):
      # rows now include the per-layout comparison epochs (3 extra
      # compiles) and the 5-epoch adaptive walk: up to ~7 min worst
      # case, typically 2-3 — don't launch with less than ~3 min left
      # (a timed-out row burns the budget AND leaves the guarded
      # dist.scale_envelope.pNN metrics unwatched)
      if budget_left() < 200:
        break
      r = _run_envelope_row(p_, bsz,
                            int(min(420, max(budget_left() - 30, 170))))
      if r is not None:
        env_rows.append(r)
    if env_rows:
      dist['scale_envelope'] = env_rows
      # lift the P=16 row's traffic attribution to a stable dotted
      # address (ISSUE 16): the regress gate guards
      # dist.attribution.cross_partition_bytes_frac (lower) and
      # dist.attribution.hot_range_coverage (higher)
      att = next((r['attribution'] for r in env_rows
                  if r.get('num_parts') == 16
                  and isinstance(r.get('attribution'), dict)), None)
      if att:
        dist['attribution'] = {
            'num_parts': att.get('num_parts'),
            'cross_partition_bytes_frac': att.get(
                'cross_partition_bytes_frac'),
            'cross_partition_ids_frac': att.get(
                'cross_partition_ids_frac'),
            'hot_range_coverage': att.get('hot_range_coverage'),
            'hotness_source': att.get('hotness_source'),
        }
      # lift the P=16 row's locality comparison (ISSUE 20) the same
      # way: dist.locality.cross_partition_bytes_frac (lower) and
      # dist.locality.seeds_per_sec (higher) are regression-guarded,
      # each with `same: dist.locality.partitioner` so a partitioner
      # change resets the baseline instead of tripping the gate
      loc = next((r['locality'] for r in env_rows
                  if r.get('num_parts') == 16
                  and isinstance(r.get('locality'), dict)
                  and isinstance(r['locality'].get('locality'), dict)),
                 None)
      if loc:
        arm = loc['locality']
        dist['locality'] = {
            'num_parts': 16,
            'partitioner': arm.get('partitioner'),
            'cross_partition_bytes_frac': arm.get(
                'cross_partition_bytes_frac'),
            'cross_partition_ids_frac': arm.get(
                'cross_partition_ids_frac'),
            'locally_served_ids': arm.get('locally_served_ids'),
            'seeds_per_sec': arm.get('seeds_per_sec'),
            'drop_rate_pct': arm.get('drop_rate_pct'),
            'range_cross_partition_bytes_frac': loc.get(
                'range', {}).get('cross_partition_bytes_frac'),
            'locality_over_range_speedup': loc.get(
                'locality_over_range_speedup'),
            'rename_equivalent': loc.get('rename_equivalent'),
        }
      emit()

  # phase 3d — resilience smoke (ISSUE 4): the host server->client
  # path with the retry/idempotency layer on — fault-free throughput
  # feeds the dist.chaos.fault_free_seeds_per_sec regression guard,
  # and one chaos epoch proves exact accounting under faults
  if not (isinstance(dist, dict) and 'error' not in dist):
    print('skipping chaos smoke: no dist section to attach to',
          file=sys.stderr)
  elif budget_left() <= 150:
    print(f'budget: skipping chaos smoke ({budget_left():.0f}s left)',
          file=sys.stderr)
  else:
    r = _run_chaos_row(int(min(300, max(budget_left() - 30, 120))))
    if r is not None:
      dist['chaos'] = r
      emit()

  # phase 3e — preemption-resume smoke (ISSUE 6): snapshot-overhead
  # epoch timing vs the no-snapshot line + kill -> durable restore ->
  # finish; feeds the dist.resume.restore_secs / replayed_batches
  # regression guards
  if isinstance(dist, dict) and 'error' not in dist and \
      budget_left() > 150:
    r = _run_resume_row(int(min(300, max(budget_left() - 30, 120))))
    if r is not None:
      dist['resume'] = r
      emit()

  # phase 3f — online serving (ISSUE 9): Zipf open-loop traffic
  # against the coalescing tier; feeds dist.serving.p99_ms /
  # dist.serving.qps (+ shed_rate reported) and pins zero recompiles
  # after warmup
  if isinstance(dist, dict) and 'error' not in dist and \
      budget_left() > 120:
    r = _run_serving_row(int(min(300, max(budget_left() - 30, 90))))
    if r is not None:
      dist['serving'] = r
      emit()
    # fleet failover acceptance (ISSUE 13): same Zipf open loop across
    # 3 replicas behind the FleetRouter with a stall-then-kill on one
    # — feeds dist.serving.fleet_qps / .failover_failed_requests (the
    # worker exits nonzero on ANY failed/dropped request or a <0.6x
    # qps recovery, stamped into failover_pin)
    if budget_left() > 90:
      fr = _run_fleet_row(int(min(300, max(budget_left() - 30, 90))))
      if fr is not None and isinstance(dist.get('serving'), dict):
        dist['serving'].update(fr)
        emit()
      elif fr is not None:
        dist['serving'] = fr
        emit()
  elif isinstance(dist, dict) and 'error' not in dist:
    print(f'budget: skipping serving phase ({budget_left():.0f}s left)',
          file=sys.stderr)

  # phase 3g — streaming ingestion (ISSUE 14): the freshness-vs-
  # throughput open loop (events/s through the WAL-backed delta-CSR
  # pipeline while the Zipf serving p99 holds); feeds
  # dist.ingest.events_per_sec / .p99_during_ingest_ms, and the
  # worker's nonzero exit (any shed during ingest / recompile /
  # unapplied lag) lands in ingest_pin
  if isinstance(dist, dict) and 'error' not in dist and \
      budget_left() > 90:
    r = _run_ingest_row(int(min(300, max(budget_left() - 30, 90))))
    if r is not None:
      dist['ingest'] = r
      emit()
  elif isinstance(dist, dict) and 'error' not in dist:
    print(f'budget: skipping ingest phase ({budget_left():.0f}s left)',
          file=sys.stderr)

  # phase 3h — elastic partition failover (ISSUE 15): one owner
  # killed mid-epoch with a durable shard present — adoption, exact
  # completion, byte-identity; feeds dist.failover.recovery_secs /
  # .completed_ratio, and the worker's nonzero exit (any completion
  # or identity violation) lands in failover_pin
  if isinstance(dist, dict) and 'error' not in dist and \
      budget_left() > 90:
    r = _run_failover_row(int(min(300, max(budget_left() - 30, 90))))
    if r is not None:
      dist['failover'] = r
      emit()
  elif isinstance(dist, dict) and 'error' not in dist:
    print(f'budget: skipping failover phase ({budget_left():.0f}s '
          f'left)', file=sys.stderr)

  # phase 3i — Pallas fused-pipeline rows (ISSUE 18): dispatcher-
  # threaded FusedEpoch step time (knob OFF), pinned-host cold-gather
  # GB/s at split<1 (hardware-only, 1.355 GB/s pin), delta-merge
  # events/s; feeds the pallas.* regression guards.  Unlike the dist
  # phases this row does NOT need the dist section — it measures
  # single-process paths and attaches at the artifact top level
  if budget_left() > 120:
    r = _run_pallas_row(int(min(420, max(budget_left() - 30, 90))))
    if r is not None:
      pallas_row[0] = r
      emit()
  else:
    print(f'budget: skipping pallas rows ({budget_left():.0f}s left)',
          file=sys.stderr)

  # phase 3j — closed-loop elastic autoscaling + planned handoff
  # (ISSUE 19): the diurnal open loop drives ElasticController
  # scale-out/in with a chaos-faulted first spawn, then a planned
  # mid-epoch partition handoff; feeds dist.autoscale.p99_held_ms /
  # .burn_max / .handoff_degraded_batches, and the worker's nonzero
  # exit (missed scale event, failed request, burn >= 1 outside the
  # incident, degraded handoff batch) lands in autoscale_pin
  if isinstance(dist, dict) and 'error' not in dist and \
      budget_left() > 90:
    r = _run_autoscale_row(int(min(300, max(budget_left() - 30, 90))))
    if r is not None:
      dist['autoscale'] = r
      emit()
  elif isinstance(dist, dict) and 'error' not in dist:
    print(f'budget: skipping autoscale phase ({budget_left():.0f}s '
          f'left)', file=sys.stderr)

  # phase 4 — extra primary sessions stabilize the per-batch median
  while (len(results) < sessions and attempts < sessions + 3
         and budget_left() > session_timeout * 0.75):
    r = _run_session(int(min(session_timeout, budget_left())))
    attempts += 1
    if r is not None:
      results.append(r)
      emit()

  if not (results or fused_res or dist):
    raise SystemExit('all bench phases failed')
  emit()                            # final (possibly repeated) line

  # phase 5 — the bench regression gate (--check-regression): fail
  # the run ONLY on a genuine regression (rc 1).  A gate that could
  # not run at all (rc 2: missing artifact, unwritable baseline dir)
  # is reported but must not fail a bench whose measurement phases
  # all completed.
  if '--check-regression' in sys.argv:
    rc = _run_regression_gate(last_art[0])
    if rc == 1:
      raise SystemExit(1)


if __name__ == '__main__':
  if '--dist-worker' in sys.argv:
    dist_worker()
  elif '--hetero-session' in sys.argv:
    hetero_worker()
  elif '--fused-session' in sys.argv:
    worker(fused_only=True)
  elif '--bench-worker' in sys.argv:
    worker()
  else:
    main()
