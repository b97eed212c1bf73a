"""One run of one cell:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's data and weights from the seed, drives the program
through its first steps and a warm-up (set-up), measures for
``--seconds`` (or, with ``--trace 1``, traces a short window and the
probes), holds what the first steps produced against the plain
reference, and prints one JSON line last.  It needs a TPU with the
chips the cell asks for: without one it exits non-zero and prints no
result.  Everything about a cell is looked up by name under the root
that holds `BENCHMARK.json` (`PERF.md`, "How to add a cell / a
metric").
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
  with open(path) as f:
    return json.load(f)


def load_cell(root: str, workload: str) -> dict:
  """Everything the files say about one cell."""
  bench = _load(os.path.join(root, 'BENCHMARK.json'))
  cells = {w['name']: w for w in bench['workloads']}
  if workload not in cells:
    raise SystemExit(f'chipbench: no workload {workload!r}; '
                     f'known: {sorted(cells)}')
  cell = cells[workload]
  conf = {c['name']: c for c in bench['configs']}[cell['config']]
  home = os.path.join(root, bench['paths'][0])
  metrics_dir = os.path.join(home, 'layer_metrics')
  layer = []
  for m in bench['per_layer']:
    if workload in m.get('workloads', [workload]):
      spec = _load(os.path.join(metrics_dir, m['name'] + '.json'))
      layer.append(dict(spec, name=m['name'], unit=m['unit']))
  cfg = _load(os.path.join(root, conf['file']))
  traffic = _load(os.path.join(home, 'traffic', cell['traffic'] + '.json'))
  # a configuration may size a mix for itself (its "traffic" key)
  traffic.update(cfg.get('traffic', {}).get(cell['traffic'], {}))
  return dict(
      bench=bench, cell=cell, metrics_dir=metrics_dir,
      builders_dir=os.path.join(home, 'builders'), cfg=cfg,
      traffic=traffic,
      limits=_load(os.path.join(home, 'cells',
                                workload + '.json'))['limits'],
      end_to_end=[m for m in bench['end_to_end']
                  if workload in m.get('workloads', [workload])],
      per_layer=layer)


def find_device(chips: int) -> dict:
  """What JAX found; exit unless it is a TPU with ``chips`` chips.
  ``runtime_init_s`` is how long the accelerator's runtime took to
  start (the first `jax.devices()`): no code of this repository runs
  in it, it swings by seconds between runs of the same code, and
  `setup_s` leaves it out."""
  import jax
  t0 = time.perf_counter()
  devs = jax.devices()
  info = dict(platform=devs[0].platform, kind=devs[0].device_kind,
              count=len(devs),
              runtime_init_s=time.perf_counter() - t0)
  print(f'chipbench: jax {jax.__version__} {info}', file=sys.stderr)
  if info['platform'] != 'tpu' or info['count'] < chips:
    raise SystemExit(f'chipbench: needs {chips} TPU chip(s), JAX found '
                     f'{info}')
  return info


def enable_cache():
  """`<checkout>/.jax_cache` (or where JAX_COMPILATION_CACHE_DIR
  says), every program kept: the small ones too, so that a second run
  compiles nothing."""
  import jax
  from graphlearn_tpu.utils.compile_cache import enable_compile_cache
  where = enable_compile_cache()
  if where:
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
  return where


class CompileEvents:
  """JAX's own count of executables built or loaded from the cache.
  JAX keeps a listener for the life of the process, so there is one
  (`compile_events`) and callers take differences."""

  def __init__(self):
    import jax
    self.n = 0
    jax.monitoring.register_event_duration_secs_listener(self._on)

  def _on(self, event, duration, **_kw):
    if event == '/jax/core/compile/backend_compile_duration':
      self.n += 1


_EVENTS = []


def compile_events() -> CompileEvents:
  if not _EVENTS:
    _EVENTS.append(CompileEvents())
  return _EVENTS[0]


def memory_peak_bytes() -> int:
  import jax
  peak = 0
  for d in jax.local_devices():
    stats = d.memory_stats() or {}
    peak = max(peak, int(stats.get('peak_bytes_in_use', 0)))
  return peak


def traced(fn, where=None):
  """Run ``fn`` in a profiler session of its own; returns
  ``(fn's result, the loaded profile)``.  The trace goes to ``where``
  and stays there, or under TMPDIR and is deleted once read."""
  import jax
  from . import trace
  keep = where is not None
  where = where or tempfile.mkdtemp(prefix='chipbench_trace_')
  try:
    jax.profiler.start_trace(where)
    try:
      out = fn()
    finally:
      jax.profiler.stop_trace()
    return out, trace.load(where)
  finally:
    if not keep:
      shutil.rmtree(where, ignore_errors=True)


def window_trace_dir(trace_on: bool):
  """A context: where a traced run keeps its window's trace until the
  readers have run (``ctx['trace_dir']``: a reader that wants more
  than the reduction, the ops' scopes say, opens it); ``None`` without
  ``--trace 1``."""
  if trace_on:
    return tempfile.TemporaryDirectory(prefix='chipbench_trace_')
  return contextlib.nullcontext()


def run_probes(drv, reps: int) -> dict:
  """Device seconds of one call of each of the driver's probes: warm
  call first, then ``reps`` calls in a session of their own."""
  import jax
  from . import trace
  out = {}
  for name, call in drv.probes().items():
    jax.block_until_ready(call())

    def go():
      for _ in range(reps):
        jax.block_until_ready(call())
    _, prof = traced(go)
    ops = trace.device_ops(prof)
    if ops:
      busy = max(trace.busy_ns(ev) for ev in ops.values())
      out[name] = dict(device_s=busy / 1e9 / reps, reps=reps)
  return out


def exact_counts(drv, first) -> dict:
  """The exchange's counts, which only the live program can give; then,
  the program freed, what the first steps drew against the data."""
  numbers = dict(drv.exchange_checks())
  drv.free()
  numbers.update(drv.draw_counts(first['steps']))
  return numbers


def compare(drv, first) -> dict:
  """The numbers compared: the exact counts, then — with the program
  freed — what the driver's plain reference, following the first
  steps, says of the record the program left (`drivers._Driver`)."""
  numbers = exact_counts(drv, first)
  numbers.update(drv.gaps(first['prog'], drv.follow(first['steps'])))
  return numbers


def verdict(numbers: dict, limits: dict):
  """``(correct, checks)``: every limit needs its number, and every
  number its limit; ``checks`` maps a name to ``[value, limit]``."""
  checks, ok = {}, True
  for name in sorted(set(numbers) | set(limits)):
    value, limit = numbers.get(name), limits.get(name)
    checks[name] = [value, limit]
    if value is None or limit is None or not value <= limit:
      ok = False
  return ok, checks


def matmul_precision(cfg: dict):
  """The matmul precision the configuration states, as a context in
  which the program is built, run and compiled: a configuration that
  states float32 is run in float32 (`PERF.md`, "correct")."""
  import jax
  return jax.default_matmul_precision(cfg['precision']['matmul'])


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace_on: bool, device: dict, t_start: float) -> dict:
  spec = load_cell(root, workload)
  with matmul_precision(spec['cfg']), \
      window_trace_dir(trace_on) as trace_dir:
    return _run_cell(spec, seed, seconds, trace_dir, device, t_start)


def _run_cell(spec: dict, seed: int, seconds: float, trace_dir,
              device: dict, t_start: float) -> dict:
  from . import drivers, readers, yardstick
  trace_on = trace_dir is not None
  cfg, traffic = spec['cfg'], spec['traffic']
  peaks = yardstick.peaks(device['kind'])
  events = compile_events()
  phases = [('start', time.perf_counter() - t_start)]
  mark = lambda name: phases.append((name, time.perf_counter() - t_start))
  drv = drivers.make(cfg, traffic, seed,
                     builders_dir=spec['builders_dir'])
  mark('built')
  first = drv.first_steps()
  mark('first_steps')
  drv.warm()
  mark('warm')
  device = dict(device)
  runtime_init_s = device.pop('runtime_init_s', 0.0)
  setup_s = time.perf_counter() - t_start - runtime_init_s
  print('chipbench: set-up, seconds since start: '
        + ' '.join(f'{n}={t:.1f}' for n, t in phases)
        + f'; of which the runtime took {runtime_init_s:.1f} to start '
        f'(not in setup_s = {setup_s:.1f})', file=sys.stderr)
  compiles0, events0 = drv.compile_count(), events.n
  exchange0 = drv.exchange_counts()
  ctx = dict(peaks=peaks, chips=int(spec['cell']['chips']))
  if trace_on:
    res, prof = traced(
        lambda: drv.window(min(seconds, traffic['trace_seconds'])),
        trace_dir)
  else:
    res, prof = drv.window(seconds), None
  in_window = max(drv.compile_count() - compiles0, events.n - events0)
  exchange1 = drv.exchange_counts()
  peak = memory_peak_bytes()
  out_device = dict(device, memory_peak_bytes=peak)
  metrics, breakdown = {}, None
  if trace_on:
    from . import trace
    print(f'chipbench: trace holds {trace.describe(prof)[:1500]}',
          file=sys.stderr)
    red = trace.reduce(prof, res['wall_s'])
    breakdown = red.pop('breakdown')
    out_device.update(busy_s=red['busy_s'], window_s=res['wall_s'])
    ctx.update(
        window=res, trace=red, trace_dir=trace_dir,
        memory_peak_bytes=peak,
        work=drv.work(first['steps']),
        probes=run_probes(drv, int(traffic['probe_reps'])),
        counters=dict(
            {k: exchange1[k] - exchange0[k] for k in exchange1},
            in_window_compiles=in_window))
    for m in spec['per_layer']:
      read = readers.resolve(m['reader'], spec['metrics_dir'])
      value = read(ctx, **m.get('params', {}))
      if value is not None:
        metrics[m['name']] = dict(value=float(value), unit=m['unit'])
  else:
    values = dict(train_seeds_per_s=res['seeds'] / res['wall_s'],
                  setup_s=setup_s)
    for m in spec['end_to_end']:
      metrics[m['name']] = dict(value=float(values[m['name']]),
                                unit=m['unit'])
  t_check = time.perf_counter()
  numbers = compare(drv, first)
  print(f'chipbench: reference and comparison took '
        f'{time.perf_counter() - t_check:.1f} s', file=sys.stderr)
  ok, checks = verdict(numbers, spec['limits'])
  line = dict(correct=bool(ok and res['failed'] == 0 and in_window == 0),
              attempted=int(res['steps']), failed=int(res['failed']),
              metrics=metrics, device=out_device)
  if breakdown is not None:
    line['breakdown'] = breakdown
  line['window'] = {k: v for k, v in res.items()
                    if not isinstance(v, list)}
  line['in_window_compiles'] = int(in_window)
  line['checks'] = checks
  return line


def report(line: dict) -> None:
  """Each number compared beside its limit, last on standard error;
  the result, last on standard output."""
  sys.stdout.flush()
  for name, (value, limit) in line['checks'].items():
    print(f'chipbench check: {name} {value} limit {limit}',
          file=sys.stderr)
  print(f'chipbench check: in_window_compiles '
        f'{line["in_window_compiles"]} limit 0', file=sys.stderr)
  sys.stderr.flush()
  print(json.dumps(line), flush=True)


def main(argv=None) -> None:
  ap = argparse.ArgumentParser()
  ap.add_argument('--workload', required=True)
  ap.add_argument('--seed', type=int, default=0)
  ap.add_argument('--seconds', type=float, default=10.0)
  ap.add_argument('--trace', type=int, default=0, choices=(0, 1))
  args = ap.parse_args(argv)
  spec = load_cell(ROOT, args.workload)
  device = find_device(int(spec['cell']['chips']))
  print(f'chipbench: compile cache at {enable_cache()}', file=sys.stderr)
  report(run_cell(ROOT, args.workload, args.seed, args.seconds,
                  bool(args.trace), device, T_START))


if __name__ == '__main__':
  main()
