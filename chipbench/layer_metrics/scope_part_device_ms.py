"""Reader: device time of one PART of a layer — the ops under
``glt.<layer>/<part>`` — in the timed window's own trace.

It reads with the by-layer reader that waits in
`tests/chipbench/layer_scopes/layer_metrics/scope_device_ms.py`
(loaded from where it waits, as `waiting_scope_device_ms.py` does, and
through it `xspace.py` beside it), so that the rule of attribution
exists once: an op belongs to the FIRST ``glt.<layer>`` token of its
``op_name``, on the busiest device, leaf ops only.  Of those, an op is
the part's where the path element right after that token is ``part``
(``glt.sample/negative/...``).  Parameters: ``layer``, ``part``,
``per`` (``'step'``: ms per step of the window; ``'window'``: ms).
Nothing where the window left no trace, the trace holds no device op
(a CPU run) or no scoped one."""
import os
import re

import chipbench

_WAITING = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(chipbench.__file__))),
    'tests', 'chipbench', 'layer_scopes', 'layer_metrics',
    'scope_device_ms.py')


def part_ns(ctx, layer, part):
  """Device ns of the part's ops, or ``None`` (see the module)."""
  waiting = chipbench.load_file(_WAITING)
  try:
    profile = waiting._profile(ctx)
    if profile is None:
      return None
    events = waiting.leaf_events(profile)
  except (FileNotFoundError, ValueError):   # no trace; no device op
    return None
  inside = re.compile(re.escape('/' + part) + r'(?![\w.])')
  total, scoped = 0.0, False
  for e in events:
    scope = waiting.scope_of(e)
    m = waiting._TOKEN.search(scope)
    if not m:
      continue
    scoped = True
    if m.group(1) == layer and inside.match(scope, m.end()):
      total += float(e.duration_ns)
  return total if scoped else None


def read(ctx, layer, part, per='step'):
  ns = part_ns(ctx, layer, part)
  if ns is None:
    return None
  if per == 'window':
    return ns / 1e6
  steps = ctx.get('window', {}).get('steps')
  return ns / 1e6 / steps if steps else None
