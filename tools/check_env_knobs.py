"""Env-knob documentation drift check (ISSUE 6 satellite) — now a
thin shim over glint's ``env-knob-drift`` pass (ISSUE 11).

The implementation lives in ``tools/glint/passes/env_knobs.py``; this
module keeps the original standalone CLI and the helper API
(`knob_references` / `documented_knobs` / `undocumented`) that
``tests/test_env_knobs.py`` and the docs reference::

    python tools/check_env_knobs.py          # exit 1 on drift

The full framework run (this pass plus five more) is::

    python -m tools.glint --baseline tools/glint/baseline.json
"""
from __future__ import annotations

import ast
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:               # standalone-script import
  sys.path.insert(0, str(REPO))

from tools.glint.passes.env_knobs import (documented_knobs as  # noqa: E402
                                          _documented, knob_constants)

#: scanned roots: the package.  The glint pass scans the driver's
#: wider root set (``examples/`` included).
SCAN_ROOTS = ('graphlearn_tpu',)
README = REPO / 'KNOBS.md'


def knob_references() -> dict:
  """``{knob: [relative file, ...]}`` for every GLT_* string constant
  in the scanned roots."""
  out: dict = {}
  files = []
  for root in SCAN_ROOTS:
    p = REPO / root
    if p.is_file():
      files.append(p)
    elif p.is_dir():
      files.extend(sorted(p.rglob('*.py')))
  for py in files:
    try:
      tree = ast.parse(py.read_text())
    except SyntaxError:             # pragma: no cover — broken file
      continue
    for knob, _line in knob_constants(tree):
      out.setdefault(knob, []).append(str(py.relative_to(REPO)))
  return out


def documented_knobs(readme_path: Path = README) -> set:
  return _documented(readme_path)


def undocumented(readme_path: Path = README) -> dict:
  """Knobs referenced in code but absent from the knob tables."""
  doc = documented_knobs(readme_path)
  return {k: sorted(set(files)) for k, files in knob_references().items()
          if k not in doc}


def main() -> int:
  missing = undocumented()
  if not missing:
    print(f'env knobs: OK ({len(knob_references())} GLT_* knobs, all '
          f'documented in {README.relative_to(REPO)})')
    return 0
  print('env knobs: DRIFT — knobs read in code but missing from '
        f'{README.relative_to(REPO)}:')
  for k, files in sorted(missing.items()):
    print(f'  {k}  ({", ".join(files)})')
  return 1


if __name__ == '__main__':
  sys.exit(main())
