"""Readers: one small function per kind of per-layer metric.

`chipbench/layer_metrics/<metric>.json` names a reader and its
parameters.  A reader takes the run's context (the window's record,
the reduced trace, the probes' device times, the work counts, the
counters, the peaks) and returns a number, or ``None`` where it finds
nothing to read — the harness then leaves the metric out of the line;
it never stands a 0 in for a share.  A metric whose reader is not
here is looked up as `chipbench/layer_metrics/<reader>.py` with a
``read(ctx, **params)`` of its own, so a later PR adds a reader by
adding a file.
"""
from __future__ import annotations

import os
import statistics

from . import load_file, yardstick


def window_ms_per_step(ctx, key):
  """Mean over all the window's steps of a per-step host time."""
  vals = ctx['window'].get(key)
  return 1e3 * sum(vals) / len(vals) if vals else None


def window_percentile_ms(ctx, key, percentile):
  """A percentile over all the window's steps; wants at least ten
  samples beyond it (`choosing-metrics` 1)."""
  vals = ctx['window'].get(key)
  if not vals or len(vals) * (100 - percentile) / 100.0 < 1:
    return None
  cuts = statistics.quantiles(vals, n=100, method='inclusive')
  return 1e3 * cuts[int(percentile) - 1]


def probe_ms(ctx, probe):
  p = ctx.get('probes', {}).get(probe)
  return 1e3 * p['device_s'] if p else None


def probe_peak_share(ctx, probe, work, peak):
  """Percent of a published peak rate a probe reached: the work one
  call needs (`yardstick`) over its device time."""
  p = ctx.get('probes', {}).get(probe)
  w = ctx.get('work', {}).get(work)
  if not p or not w or not p['device_s']:
    return None
  return yardstick.share(w, p['device_s'], ctx['peaks'][peak])


def step_mfu(ctx):
  """Model FLOPs the steps of the traced window needed, over its wall
  time, against every chip's published bf16 peak."""
  flops = ctx.get('work', {}).get('step_flops')
  win = ctx['window']
  if not flops or not win.get('steps'):
    return None
  return yardstick.share(flops * win['steps'], win['wall_s'],
                         ctx['chips'] * ctx['peaks']['flops_per_s'])


def trace_value(ctx, key):
  t = ctx.get('trace')
  return None if not t else t.get(key)


def counter(ctx, name):
  return ctx.get('counters', {}).get(name)


def counter_ratio_pct(ctx, numerator, denominator):
  c = ctx.get('counters', {})
  if not c.get(denominator):
    return None
  return 100.0 * c.get(numerator, 0) / c[denominator]


def memory_gb(ctx):
  b = ctx.get('memory_peak_bytes')
  return b / 1e9 if b else None


READERS = {f.__name__: f for f in (
    window_ms_per_step, window_percentile_ms, probe_ms, probe_peak_share,
    step_mfu, trace_value, counter, counter_ratio_pct, memory_gb)}


def resolve(name: str, metrics_dir: str):
  if name in READERS:
    return READERS[name]
  path = os.path.join(metrics_dir, name + '.py')
  if not os.path.exists(path):
    raise KeyError(f'chipbench: no reader {name!r} in readers.py or '
                   f'{metrics_dir}')
  return load_file(path).read
