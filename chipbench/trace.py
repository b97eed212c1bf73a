"""From a profiler trace to numbers.  One reduction, kept here.

Reads what `jax.profiler.ProfileData` exposes — planes, their lines,
events with ``start_ns``/``duration_ns`` — and nothing else, so the
tests feed it small hand-made planes.  Device planes are those whose
name starts with ``/device:``; on such a plane the op events sit on
the line named ``XLA Ops``.  Each traced region is a profiler session
of its own (`chipbench.run`), so everything on a device plane belongs
to the region and no clock has to be matched across planes for the
busy time; only the idle gaps' attribution to host spans assumes the
planes share a time base.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = 'XLA Ops'
#: a collective, by its HLO opcode (``... all-to-all(`` in the event's
#: HLO line) or, where an event carries only a name, by that name
COLLECTIVE = re.compile(
    r'(^| )%?(all[-_]to[-_]all|all[-_]reduce|all[-_]gather|'
    r'collective[-_]permute|reduce[-_]scatter|collective[-_]broadcast)'
    r'(-start|-done)?[.(\d]')
HOST_SPAN_PREFIX = 'chipbench.'


def load(trace_dir: str):
  import jax
  found = sorted(glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                           recursive=True))
  if not found:
    raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
  return jax.profiler.ProfileData.from_file(found[-1])


_HLO = re.compile(r'^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?')
CONTAINERS = re.compile(r'^(while|conditional|call)[.\d]*( |$)')


def op_name(text: str) -> str:
  """A device event is named by its whole HLO line; keep the op's
  name and the shape of its (first) result: ``fusion.533
  f32[768000,100]``."""
  m = _HLO.match(text)
  if not m:
    return text.lstrip('%')[:80]
  return m.group(1) + (' ' + m.group(2) if m.group(2) else '')


def describe(profile) -> str:
  """Planes, lines and event counts: what a trace holds, in a few
  lines (printed on standard error by a traced run)."""
  out = []
  for plane in profile.planes:
    lines = [f'{ln.name}:{sum(1 for _ in ln.events)}' for ln in plane.lines]
    out.append(f'{plane.name} [{", ".join(lines[:8])}]')
  return '; '.join(out)


def device_ops(profile) -> dict:
  """``{plane name: [(name, start_ns, end_ns, is_collective), ...]}``
  for every device plane that has an op line, sorted by start."""
  out = {}
  for plane in profile.planes:
    if not plane.name.startswith('/device:'):
      continue
    for line in plane.lines:
      if line.name != OPS_LINE:
        continue
      evs = [(op_name(e.name), float(e.start_ns),
              float(e.start_ns) + float(e.duration_ns),
              bool(COLLECTIVE.search(e.name)))
             for e in line.events]
      if evs:
        out[plane.name] = sorted(evs, key=lambda e: e[1])
  return out


def merged(intervals):
  """Union of ``(start, end)`` intervals as a sorted disjoint list."""
  out = []
  for s, e in sorted(intervals):
    if out and s <= out[-1][1]:
      out[-1][1] = max(out[-1][1], e)
    else:
      out.append([s, e])
  return out


def busy_ns(events) -> float:
  return sum(e - s for s, e in merged((s, e) for _, s, e, _ in events))


def collective_ns(events) -> float:
  """Time in which a collective ran (union, so overlapping start/done
  pairs count once)."""
  return sum(e - s for s, e in merged(
      (s, e) for _, s, e, coll in events if coll))


def top_ops(events, n=10):
  """Total time by op, control-flow containers (whose time is their
  bodies') left out."""
  tot = {}
  for name, s, e, _ in events:
    if CONTAINERS.match(name):
      continue
    tot[name] = tot.get(name, 0.0) + (e - s)
  top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
  return [[name, ns / 1e9] for name, ns in top]


def host_spans(profile):
  """The harness's own spans on the host planes."""
  spans = []
  for plane in profile.planes:
    if plane.name.startswith('/device:'):
      continue
    for line in plane.lines:
      for e in line.events:
        if e.name.startswith(HOST_SPAN_PREFIX):
          s = float(e.start_ns)
          spans.append((e.name, s, s + float(e.duration_ns)))
  return spans


def idle_gaps(events, spans, n=10):
  """The longest gaps between device ops, each named by the harness
  span that covers its middle (``unattributed`` where none does)."""
  m = merged((s, e) for _, s, e, _ in events)
  gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(m, m[1:])),
                reverse=True)[:n]
  out = []
  for length, s, e in gaps:
    mid = (s + e) / 2
    # the innermost span covering the middle
    covering = [sp for sp in spans if sp[1] <= mid <= sp[2]]
    name = (min(covering, key=lambda sp: sp[2] - sp[1])[0]
            if covering else 'unattributed')
    out.append([name, length / 1e9])
  return out


def reduce(profile, window_s: float) -> dict:
  """Busy seconds per device, the idle share on the busiest device,
  the collective share on the fullest one, and the breakdown."""
  per_dev = device_ops(profile)
  if not per_dev:
    raise ValueError('trace holds no device op: planes '
                     f'{[p.name for p in profile.planes]}')
  busy = {d: busy_ns(ev) / 1e9 for d, ev in per_dev.items()}
  busiest = max(busy, key=busy.get)
  coll = {d: collective_ns(ev) / 1e9 for d, ev in per_dev.items()}
  return dict(
      devices=len(per_dev),
      busy_s=sum(busy.values()) / len(busy),
      busy_by_device=busy,
      idle_share=100.0 * (1.0 - busy[busiest] / window_s),
      collective_share=100.0 * max(
          coll[d] / busy[d] for d in per_dev if busy[d] > 0),
      breakdown=dict(
          device_ops=top_ops(per_dev[busiest]),
          idle_gaps=idle_gaps(per_dev[busiest], host_spans(profile))))
