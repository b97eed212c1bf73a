"""A benchmark root for the tests: `BENCHMARK.json` and the data files
of `chipbench/`, with the four-chip cell that waits in `mesh_cell/`
added the way a later PR would add it — new files and new entries."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MESH_CELL = os.path.join(REPO, 'tests', 'chipbench', 'mesh_cell')
DATA_DIRS = ('configs', 'traffic', 'cells', 'layer_metrics')


def make_root(root: str) -> str:
  os.makedirs(root, exist_ok=True)
  for sub in DATA_DIRS:
    shutil.copytree(os.path.join(REPO, 'chipbench', sub),
                    os.path.join(root, 'chipbench', sub))
    if os.path.isdir(os.path.join(MESH_CELL, sub)):
      shutil.copytree(os.path.join(MESH_CELL, sub),
                      os.path.join(root, 'chipbench', sub),
                      dirs_exist_ok=True)
  with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  with open(os.path.join(MESH_CELL, 'entries.json')) as f:
    for key, entries in json.load(f).items():
      bench[key].extend(entries)
  with open(os.path.join(root, 'BENCHMARK.json'), 'w') as f:
    json.dump(bench, f)
  return root
