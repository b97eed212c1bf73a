"""env-knob-drift: every ``GLT_*`` knob is documented.

Migrated from the standalone ``tools/check_env_knobs.py`` (ISSUE 6
satellite), which stays as a thin shim over the helpers here so its
documented CLI keeps working.  The contract is unchanged: every
``GLT_*`` string constant in the scanned surfaces — the knob
vocabulary: env reads go through ``os.environ.get('GLT_X')``,
``os.environ['GLT_X']`` or a ``FOO_ENV = 'GLT_X'`` constant, all of
which surface as a string literal — must appear in the ``KNOBS.md``
knob tables.  An undocumented knob is a feature only its author can
use.

As a glint pass the scan covers every file the driver scans (the
shim scans the package), so e.g. ``examples/`` knobs get
drift-checked for free.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Tuple

from ..findings import Finding
from ..registry import GlintPass, register

KNOB_RE = re.compile(r'^GLT_[A-Z0-9_]+$')


def knob_constants(tree: ast.AST) -> List[Tuple[str, int]]:
  """``(knob, lineno)`` for every GLT_* string constant in a tree."""
  out = []
  for node in ast.walk(tree):
    if (isinstance(node, ast.Constant) and isinstance(node.value, str)
        and KNOB_RE.match(node.value)):
      out.append((node.value, node.lineno))
  return out


def documented_knobs(readme_path: Path) -> set:
  return set(re.findall(r'GLT_[A-Z0-9_]+', Path(readme_path).read_text()))


@register
class EnvKnobDriftPass(GlintPass):
  name = 'env-knob-drift'
  description = ('every GLT_* knob referenced in code appears in the '
                 'KNOBS.md knob tables')

  def begin(self, run):
    self._readme = run.readme_path
    #: knob -> [(rel, line), ...]
    self._refs: Dict[str, List[Tuple[str, int]]] = {}

  def check_file(self, ctx):
    for knob, line in knob_constants(ctx.tree):
      self._refs.setdefault(knob, []).append((ctx.rel, line))
    return ()

  def finish(self, run):
    try:
      documented = documented_knobs(self._readme)
    except OSError:
      yield Finding(
          rule=self.name, path=str(self._readme), line=0,
          message=f'knob table {self._readme} is unreadable — the '
                  'drift check has nothing to check against')
      return
    for knob, refs in sorted(self._refs.items()):
      if knob in documented:
        continue
      rel, line = refs[0]
      others = ', '.join(sorted({r for r, _ in refs} - {rel}))
      yield Finding(
          rule=self.name, path=rel, line=line,
          message=f'{knob} is read in code but missing from the '
                  f'{self._readme.name} knob tables'
                  + (f' (also referenced in {others})' if others else '')
                  + ' — add a row (an undocumented knob is a feature '
                    'only its author can use)')
