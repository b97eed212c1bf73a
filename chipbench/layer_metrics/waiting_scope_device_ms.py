"""Reader: device time of one layer in the timed window's own trace,
by the by-layer reader that waits in
`tests/chipbench/layer_scopes/layer_metrics/scope_device_ms.py`
(ROADMAP S0 moves it here and retires the probes) — loaded from where
it waits, so that its rule of attribution exists once.  The parameters
are that reader's (``layer``, ``per``, ``by``).  Nothing where the
window left no trace, the trace holds no device op (a CPU run) or no
scoped one."""
import os

import chipbench

_WAITING = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(chipbench.__file__))),
    'tests', 'chipbench', 'layer_scopes', 'layer_metrics',
    'scope_device_ms.py')


def read(ctx, **params):
  try:
    return chipbench.load_file(_WAITING).read(ctx, **params)
  except (FileNotFoundError, ValueError):   # no trace; no device op
    return None
