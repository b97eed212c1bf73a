"""Reader: a part's share of the HBM roofline — the bytes it must move
per step (``ctx['work'][work]``, counted by the cell's driver from
counts and shapes) over its device time per step in the window's own
trace (`scope_part_device_ms.py` beside this file), of the published
peak.  Nothing where either is missing or the time is 0."""
import chipbench
from chipbench import yardstick

_PART = chipbench.beside(__file__, 'scope_part_device_ms')


def read(ctx, layer, part, work):
  ms = _PART.read(ctx, layer, part)
  moved = ctx.get('work', {}).get(work)
  if not ms or not moved:
    return None
  return yardstick.share(moved, ms / 1e3, ctx['peaks']['hbm_bytes_per_s'])
