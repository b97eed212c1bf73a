"""The glint driver: file discovery, pass execution, suppression and
baseline application, reporting, CLI.

Default scan roots are the data-plane surfaces the invariants govern:
the package and ``examples/``.
Tests (``tests/``) are deliberately out of scope — they exercise
ad-hoc event kinds and throwaway RNG on private objects by design.

Exit code contract: 0 when every finding is inline-suppressed or
baselined, 1 otherwise, 2 on usage errors.  This is the single entry
point the docs reference::

    python -m tools.glint --baseline tools/glint/baseline.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence

from .context import FileContext
from .findings import Finding
from .registry import all_passes

REPO = Path(__file__).resolve().parent.parent.parent
DEFAULT_ROOTS = ('graphlearn_tpu', 'examples')
DEFAULT_BASELINE = Path(__file__).resolve().parent / 'baseline.json'


@dataclasses.dataclass
class Run:
  """Run-level configuration handed to every pass (``begin``/
  ``finish``).  Tests override the resource paths to point passes at
  fixture registries instead of the live repo."""

  repo: Path = REPO
  #: knob table the env-knob-drift pass checks against
  readme_path: Path = REPO / 'KNOBS.md'
  #: telemetry schema registry the event-schema pass checks against
  schema_path: Path = REPO / 'graphlearn_tpu' / 'telemetry' / 'schema.py'
  #: repo-relative prefix of "the package" for package-only passes
  pkg_prefix: str = 'graphlearn_tpu'


def discover(paths: Sequence, repo: Path) -> List[Path]:
  files: List[Path] = []
  for p in paths:
    p = Path(p)
    if not p.is_absolute():
      p = repo / p
    if p.is_file() and p.suffix == '.py':
      files.append(p)
    elif p.is_dir():
      files.extend(sorted(p.rglob('*.py')))
  return files


def run_glint(paths: Optional[Sequence] = None,
              rules: Optional[Sequence[str]] = None,
              run: Optional[Run] = None,
              baseline: Optional[Path] = None) -> List[Finding]:
  """Run the selected passes over ``paths`` (default roots when None)
  and return EVERY finding — suppressed and baselined ones included,
  flagged as such (callers filter on ``Finding.live``)."""
  run = run or Run()
  table = all_passes()
  if rules is not None:
    unknown = set(rules) - set(table)
    if unknown:
      raise ValueError(f'unknown glint rule(s): {sorted(unknown)} — '
                       f'registered: {sorted(table)}')
    table = {k: v for k, v in table.items() if k in rules}
  files = discover(paths if paths is not None else DEFAULT_ROOTS, run.repo)

  contexts: List[FileContext] = []
  findings: List[Finding] = []
  for f in files:
    ctx = FileContext.from_path(f, run.repo)
    if ctx.parse_error is not None:
      findings.append(Finding(
          rule='parse', path=ctx.rel, line=ctx.parse_error.lineno or 0,
          message=f'syntax error: {ctx.parse_error.msg}'))
      continue
    contexts.append(ctx)

  passes = [cls() for cls in table.values()]
  for p in passes:
    p.begin(run)
  for ctx in contexts:
    for p in passes:
      findings.extend(p.check_file(ctx))
  for p in passes:
    findings.extend(p.finish(run))

  by_rel: Dict[str, FileContext] = {c.rel: c for c in contexts}
  for f in findings:
    ctx = by_rel.get(f.path)
    if ctx is None:
      continue
    if not f.snippet:
      f.snippet = ctx.line_text(f.line)
    if ctx.rule_disabled(f.rule, f.line):
      f.suppressed = True
  if baseline is not None:
    apply_baseline(findings, load_baseline(baseline))
  findings.sort(key=lambda f: (f.path, f.line, f.rule))
  return findings


def check_source(source: str, rule: str, rel: str = 'fixture.py',
                 run: Optional[Run] = None) -> List[Finding]:
  """Test helper: run ONE pass over in-memory source.  Repo-level
  passes still honor ``run`` resource overrides."""
  run = run or Run()
  cls = all_passes()[rule]
  ctx = FileContext(source, rel)
  if ctx.parse_error is not None:
    raise ctx.parse_error
  p = cls()
  p.begin(run)
  findings = list(p.check_file(ctx))
  findings.extend(p.finish(run))
  for f in findings:
    if not f.snippet and f.path == rel:
      f.snippet = ctx.line_text(f.line)
    if f.path == rel and ctx.rule_disabled(f.rule, f.line):
      f.suppressed = True
  return findings


# -- baseline ----------------------------------------------------------------
def load_baseline(path: Path) -> List[str]:
  if not Path(path).exists():
    return []
  data = json.loads(Path(path).read_text())
  return list(data.get('findings', []))


def write_baseline(path: Path, findings: Sequence[Finding]) -> None:
  """Grandfather every unsuppressed finding.  Sorted for stable
  diffs; the workflow is: shrink this file over time, never grow it
  silently (new code must come in clean)."""
  fps = sorted(f.fingerprint for f in findings if not f.suppressed)
  Path(path).write_text(json.dumps(
      {'version': 1, 'findings': fps}, indent=2) + '\n')


def apply_baseline(findings: Sequence[Finding], fps: Sequence[str]) -> None:
  """Multiset match: each baseline entry absolves at most one
  finding, so a second instance of a grandfathered pattern still
  fails the run."""
  pool: Dict[str, int] = {}
  for fp in fps:
    pool[fp] = pool.get(fp, 0) + 1
  for f in findings:
    if f.suppressed:
      continue
    n = pool.get(f.fingerprint, 0)
    if n > 0:
      pool[f.fingerprint] = n - 1
      f.baselined = True


# -- CLI ---------------------------------------------------------------------
def main(argv: Optional[Sequence[str]] = None) -> int:
  ap = argparse.ArgumentParser(
      prog='python -m tools.glint',
      description='repo-native static analysis for data-plane '
                  'invariants (host-sync, RNG discipline, guarded-by '
                  'locks, knob/schema drift)')
  ap.add_argument('paths', nargs='*',
                  help=f'files/dirs to scan (default: {DEFAULT_ROOTS})')
  ap.add_argument('--rules', help='comma-separated subset of passes')
  ap.add_argument('--baseline', type=Path, default=DEFAULT_BASELINE,
                  help='baseline JSON (default: tools/glint/baseline.json)')
  ap.add_argument('--no-baseline', action='store_true',
                  help='ignore the baseline (report grandfathered '
                       'findings as live)')
  ap.add_argument('--write-baseline', action='store_true',
                  help='rewrite the baseline from the current findings '
                       'and exit 0')
  ap.add_argument('--list-passes', action='store_true')
  ap.add_argument('-q', '--quiet', action='store_true',
                  help='summary line only')
  args = ap.parse_args(argv)

  if args.list_passes:
    for name, cls in sorted(all_passes().items()):
      print(f'{name:20s} {cls.description}')
    return 0

  rules = ([r.strip() for r in args.rules.split(',') if r.strip()]
           if args.rules else None)
  if args.write_baseline and (rules or args.paths):
    # a filtered run sees a SUBSET of findings; writing it out would
    # silently drop every grandfathered entry outside the filter
    print('glint: --write-baseline rewrites the whole baseline file — '
          'run it without --rules or explicit paths', file=sys.stderr)
    return 2
  try:
    findings = run_glint(
        paths=args.paths or None, rules=rules,
        baseline=None if (args.no_baseline or args.write_baseline)
        else args.baseline)
  except ValueError as e:
    print(f'glint: {e}', file=sys.stderr)
    return 2

  if args.write_baseline:
    write_baseline(args.baseline, findings)
    n = sum(1 for f in findings if not f.suppressed)
    print(f'glint: wrote {n} finding(s) to {args.baseline}')
    return 0

  live = [f for f in findings if f.live]
  if not args.quiet:
    for f in findings:
      print(f.render())
  n_sup = sum(1 for f in findings if f.suppressed)
  n_base = sum(1 for f in findings if f.baselined)
  print(f'glint: {len(findings)} finding(s) — {len(live)} live, '
        f'{n_sup} suppressed, {n_base} baselined '
        f'({len(all_passes() if rules is None else rules)} pass(es))')
  return 1 if live else 0


if __name__ == '__main__':              # pragma: no cover
  sys.exit(main())
