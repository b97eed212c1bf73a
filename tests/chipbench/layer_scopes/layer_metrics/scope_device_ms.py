"""Device time by layer, read from the timed window's own trace.

The program puts a scope ``glt.<layer>[/<part>]`` on every device op
it traces (`graphlearn_tpu.utils.profiling.layer_scope`); XLA carries
it as the op's ``op_name``.  The rule of attribution, the whole of it:

  * an op belongs to the FIRST ``glt.<layer>`` token of its
    ``op_name``, wherever it stands — bare, inside ``jvp(...)`` or
    inside ``transpose(jvp(...))``;
  * it is backward work (``<layer>.bwd``) where a ``transpose(``
    opens before that token ends its path element (JAX writes the
    backward pass as ``transpose(jvp(glt.model/loss))/...`` or, under
    a flax module, ``transpose(jvp(TreeSAGE))/glt.model/layer0/...``),
    and forward work (``<layer>.fwd``) otherwise;
  * an op with no token is ``unattributed``; a fusion is the one
    instruction the trace shows, so it has its root's name;
  * control-flow containers (``while`` / ``conditional`` / ``call``)
    are left out, as `chipbench.trace.top_ops` leaves them out: their
    time is their bodies'.

So the layers and ``unattributed`` add up to the leaf-op time of the
device exactly.  Nothing here imports the program: the token ``glt.``
is all the two share.

Where the ``op_name`` is (my chip runs, PR 25, `TPU v5 lite`, jax
0.9.0): not in the event's name (the HLO line is printed without its
metadata) and not among the event's own stats (device times only),
but in the stat ``tf_op`` of the event's METADATA, which
`jax.profiler.ProfileData` does not expose.  `xspace.load`, beside
this file, reads the ``.xplane.pb`` with the metadata's stats behind
each event's own; this reader walks either.  On a `ProfileData` of a
TPU trace it finds no scope and returns ``None``.

This reader WAITS in `tests/chipbench/layer_scopes/` (ROADMAP S0): it
reads the window's own trace, which `chipbench.run` keeps until the
readers have run and names in ``ctx['trace_dir']`` (PR 27), through
`xspace.load` beside this file; a test may hand it a loaded profile as
``ctx['profile']``.  The `benchmark` issue that retires the probes
moves these files into `chipbench/layer_metrics/` and the entries of
`entries.json` into `BENCHMARK.json`.
"""
from __future__ import annotations

import functools
import re
import sys

from chipbench import beside, trace

MODULES_LINE = 'XLA Modules'
UNATTRIBUTED = 'unattributed'
_TOKEN = re.compile(r'glt\.(\w+)')
_IN_HLO = re.compile(r'op_name="([^"]*)"')
#: stats of a device event (its own, or its metadata's) that may hold
#: the op's ``op_name``; the TPU runtime's is ``tf_op``
_STATS = ('tf_op', 'op_name', 'long_name')
_SAID = set()


def _say_once(what: str) -> None:
  if what not in _SAID:
    _SAID.add(what)
    print(f'scope_device_ms: op_name read from {what}', file=sys.stderr)


def scope_of(event) -> str:
  """The ``op_name`` of a device event, where this runtime keeps it:
  in the HLO line the event is named by (``metadata={op_name="..."}``)
  or, failing that, in one of the stats the loaded trace shows for it;
  ``''`` where neither holds one."""
  m = _IN_HLO.search(event.name)
  if m:
    _say_once('the event name (the HLO line\'s metadata)')
    return m.group(1)
  for key, value in getattr(event, 'stats', None) or ():
    if key in _STATS and isinstance(value, str) and value:
      m = _IN_HLO.search(value)
      _say_once(f'the event stat {key!r}')
      return m.group(1) if m else value
  return ''


def classify(scope: str) -> str:
  """``<layer>.fwd`` / ``<layer>.bwd`` / ``unattributed``."""
  m = _TOKEN.search(scope)
  if not m:
    return UNATTRIBUTED
  back = 'transpose(' in scope[:m.start()]
  return f'{m.group(1)}.{"bwd" if back else "fwd"}'


def _lines(profile, line_name):
  """``{device plane: [event, ...]}`` of the line ``line_name``."""
  out = {}
  for plane in profile.planes:
    if not plane.name.startswith('/device:'):
      continue
    for line in plane.lines:
      if line.name == line_name:
        evs = list(line.events)
        if evs:
          out[plane.name] = evs
  return out


def busiest(profile) -> str:
  """The device plane with the most busy time (as `trace.reduce`)."""
  ops = trace.device_ops(profile)
  if not ops:
    raise ValueError('trace holds no device op')
  return max(ops, key=lambda d: trace.busy_ns(ops[d]))


def leaf_events(profile, device=None):
  """The busiest device's op events without the containers."""
  device = device or busiest(profile)
  return [e for e in _lines(profile, trace.OPS_LINE)[device]
          if not trace.CONTAINERS.match(trace.op_name(e.name))]


def by_layer(profile, device=None) -> dict:
  """``{'<layer>.fwd' | '<layer>.bwd' | 'unattributed': ns}`` over the
  leaf ops of the busiest device; the values add up to `leaf_ns`."""
  out = {}
  for e in leaf_events(profile, device):
    key = classify(scope_of(e))
    out[key] = out.get(key, 0.0) + float(e.duration_ns)
  return out


def leaf_ns(profile, device=None) -> float:
  return sum(float(e.duration_ns) for e in leaf_events(profile, device))


def by_module(profile, device=None) -> dict:
  """``{program name: ns}`` over the device's ``XLA Modules`` line; a
  module event is named ``jit_<function>(<fingerprint>)``."""
  device = device or busiest(profile)
  out = {}
  for e in _lines(profile, MODULES_LINE).get(device, ()):
    name = re.sub(r'\(\d+\)$', '', e.name.strip())
    out[name] = out.get(name, 0.0) + float(e.duration_ns)
  return out


@functools.lru_cache(maxsize=1)
def _load(trace_dir: str):
  """One parse for all the metrics of a run."""
  return beside(__file__, 'xspace').load(trace_dir)


def _profile(ctx):
  if ctx.get('profile') is not None:
    return ctx['profile']
  return _load(ctx['trace_dir']) if ctx.get('trace_dir') else None


def _pick(totals: dict, layer: str) -> float:
  """``model`` takes both directions, ``model.bwd`` one."""
  return sum(ns for key, ns in totals.items()
             if key == layer or key.rsplit('.', 1)[0] == layer)


def read(ctx, layer, per='step', by='op'):
  """Device time of ``layer`` in the traced window.

  ``layer``: a layer of the program's vocabulary (both directions), a
  direction of one (``model.bwd``), or ``unattributed``; with
  ``by='module'``, a program name as the ``XLA Modules`` line has it
  (``jit__multihop_sample``).  ``per``: ``'step'`` — ms per step of
  the window; ``'window'`` — ms; ``'share'`` — percent of the leaf-op
  time (of all modules' time with ``by='module'``).  ``None`` where
  there is no profile, or the trace holds no scoped op at all: a
  program without scopes has no by-layer time, which is not a time of
  0."""
  profile = _profile(ctx)
  if profile is None:
    return None
  if by == 'module':
    totals = by_module(profile)
    if layer not in totals:
      return None
    value, whole = totals[layer], sum(totals.values())
  else:
    totals = by_layer(profile)
    if set(totals) <= {UNATTRIBUTED}:
      return None
    value, whole = _pick(totals, layer), sum(totals.values())
  if per == 'share':
    return 100.0 * value / whole if whole else None
  if per == 'window':
    return value / 1e6
  steps = ctx.get('window', {}).get('steps')
  return value / 1e6 / steps if steps else None
