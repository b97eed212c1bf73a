"""The documents and docstrings describe the tree that is there.

Guards the rot PR 32 removed with the pre-chip benchmark stack: a
citation of a file that is gone, a README that names a benchmark other
than `BENCHMARK.json`'s, a knob table with rows nothing reads, an
`__all__` that exports a deleted name.
"""
import ast
import json
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.glint.driver import DEFAULT_ROOTS, discover  # noqa: E402
from tools.glint.passes.env_knobs import knob_constants  # noqa: E402

#: a repo-rooted path: one of the two root scripts, or anything under
#: a root directory.  A longer path that merely CONTAINS one of these
#: (`graphlearn_torch/csrc/...`, the reference's) is not repo-rooted.
_ROOTED = re.compile(
    r'(?<![\w/.\-])(?:bench\.py|chip_smoke\.py|'
    r'(?:benchmarks|graphlearn_tpu|tests|examples|tools|chipbench|csrc)'
    r'/[\w./\-]*)')

#: the reference's files that docstrings cite and that happen to sit
#: under a directory name this repo has too (its `csrc/` has `cuda/`
#: and `cpu/`, ours is flat; its `examples/` are what ours port).
#: Prefixes.  Anything else under a root's name must exist HERE.
REFERENCE_PATHS = (
    'csrc/cuda/', 'csrc/cpu/',
    'examples/train_sage_ogbn_products.py',
    'examples/graph_sage_unsup_ppi.py',
    'examples/igbh/rgnn.py', 'examples/igbh/dataset.py',
    'examples/igbh/partition.py',
    'examples/hetero/train_hgt_mag_mp.py',
    'examples/distributed/dist_train_sage_supervised',
    'examples/distributed/partition_ogbn_dataset.py',
    'examples/multi_gpu/', 'examples/pai/',
)

_PACKAGES = ('channel', 'data', 'distributed', 'loader', 'models',
             'native', 'ops', 'parallel', 'partition', 'sampler',
             'serving', 'streaming', 'telemetry', 'testing', 'utils')
PLACES = (['README.md', 'KNOBS.md', 'chip_smoke.py', 'examples', 'tools']
          + [f'graphlearn_tpu/{p}' for p in _PACKAGES])


def _texts(where: str):
  p = REPO / where
  files = [p] if p.is_file() else sorted(
      f for f in p.rglob('*') if f.suffix in ('.py', '.md'))
  assert files, f'{where}: nothing to read'
  return [(f, f.read_text()) for f in files]


def cited_paths(text: str):
  """Repo-rooted paths in ``text``, trailing sentence punctuation and
  `:line` suffixes dropped (a glob or a `<placeholder>` ends the path
  at the directory that holds it); the reference's namesakes left
  out."""
  found = {m.group(0).rstrip('.-') for m in _ROOTED.finditer(text)}
  return sorted(c for c in found if not c.startswith(REFERENCE_PATHS))


@pytest.mark.parametrize('where', PLACES)
def test_cited_paths_exist(where):
  missing = [f'{f.relative_to(REPO)}: {c}'
             for f, text in _texts(where) for c in cited_paths(text)
             if not (REPO / c).exists()]
  assert not missing, (
      'cited paths that do not exist (a file of the reference is '
      'written `graphlearn_torch/...` or listed in REFERENCE_PATHS):'
      '\n  ' + '\n  '.join(missing))


def test_the_path_pattern_sees_what_it_should():
  got = cited_paths('see `bench.py:92`, graphlearn_tpu/ops/unique.py. '
                    'and benchmarks/*.py; not graphlearn_torch/csrc/x.cc '
                    'nor csrc/cuda/inducer.cu:74 '
                    'nor a bare sampler/x.py:595 nor chipbench.run')
  assert got == ['bench.py', 'benchmarks/', 'graphlearn_tpu/ops/unique.py']


def test_readme_names_the_benchmark_that_exists():
  bench = json.loads((REPO / 'BENCHMARK.json').read_text())
  readme = (REPO / 'README.md').read_text()
  assert '`BENCHMARK.json`' in readme
  assert ' '.join(bench['command']) in readme
  cells = {w['name'] for w in bench['workloads']}
  metrics = {m['name'] for m in bench['end_to_end']}
  for name in sorted(cells | metrics):
    assert f'`{name}`' in readme, f'README does not name {name}'
  # and no cell the benchmark does not hold
  named = set(re.findall(r'`([\w\-]+\.(?:train|serve)-[\w\-]+)`', readme))
  assert named <= cells, f'README names cells not in BENCHMARK.json: ' \
                         f'{sorted(named - cells)}'


def test_knob_table_has_no_stale_rows():
  read = set()
  for py in discover(DEFAULT_ROOTS, REPO):
    read.update(k for k, _ in knob_constants(ast.parse(py.read_text())))
  rows = re.findall(r'^\| `(GLT_[A-Z0-9_]+)` \|',
                    (REPO / 'KNOBS.md').read_text(), re.M)
  assert len(rows) == len(set(rows)), 'a knob has two rows'
  stale = sorted(set(rows) - read)
  assert rows and not stale, f'KNOBS.md rows nothing reads: {stale}'


def test_telemetry_exports_resolve():
  import graphlearn_tpu.telemetry as t
  missing = [n for n in t.__all__ if not hasattr(t, n)]
  assert not missing, f'telemetry.__all__ names nothing: {missing}'
