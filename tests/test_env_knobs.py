"""Env-knob documentation enforcement (ISSUE 6 satellite): every
``GLT_*`` knob referenced anywhere in the package must appear in the
``KNOBS.md`` knob tables — the same
drift-proofing contract `test_event_schema.py` applies to event kinds
(PR 4/5 both shipped knobs the docs never learned about)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / 'tools'))

from check_env_knobs import (documented_knobs, knob_references,
                             undocumented)


def test_every_knob_documented():
  missing = undocumented()
  assert not missing, (
      f'GLT_* knobs referenced in code but missing from '
      f'KNOBS.md: {missing} — add a row to the knob '
      'tables (an undocumented knob is a feature only its author can '
      'use)')


def test_scan_actually_sees_known_knobs():
  """The scanner must keep finding the long-standing knobs — an AST
  regression that finds nothing would make the drift test pass
  vacuously."""
  refs = knob_references()
  for knob in ('GLT_FAULT_PLAN', 'GLT_COLD_CACHE_ROWS',
               'GLT_SNAPSHOT_DIR', 'GLT_DISPATCH_DEADLINE'):
    assert knob in refs, f'{knob} not found by the AST scan'
  assert len(documented_knobs()) >= 20
