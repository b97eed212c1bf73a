"""A benchmark root for the tests: `BENCHMARK.json` and the data files
of `chipbench/`, with the four-chip cell that waits in `mesh_cell/`
added the way a later PR would add it — new files and new entries."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MESH_CELL = os.path.join(REPO, 'tests', 'chipbench', 'mesh_cell')
FOREIGN_CELL = os.path.join(REPO, 'tests', 'chipbench', 'foreign_cell')
DATA_DIRS = ('configs', 'traffic', 'cells', 'layer_metrics', 'builders')
TINY = dict(num_nodes=3000, avg_degree=6, feature_dim=12, hidden=16,
            classes=5, fanout=[3, 2, 2])


def add_cell(root: str, waiting: str) -> None:
  """What a PR that adds a cell does, and nothing else: the files of
  ``waiting`` laid into the root's `chipbench/` — a file that is there
  already is an error —, the entries of its `entries.json` appended to
  `BENCHMARK.json`, and under ``reports`` the cell's name appended to
  the ``workloads`` list of each metric the benchmark already has and
  the cell reports."""
  for sub in DATA_DIRS:
    src = os.path.join(waiting, sub)
    if not os.path.isdir(src):
      continue
    dst = os.path.join(root, 'chipbench', sub)
    os.makedirs(dst, exist_ok=True)
    for name in os.listdir(src):
      if os.path.exists(os.path.join(dst, name)):
        raise FileExistsError(f'{sub}/{name} is there already')
      shutil.copy(os.path.join(src, name), os.path.join(dst, name))
  path = os.path.join(root, 'BENCHMARK.json')
  with open(path) as f:
    bench = json.load(f)
  with open(os.path.join(waiting, 'entries.json')) as f:
    entries = json.load(f)
  by_name = {m['name']: m for m in bench['per_layer']}
  for cell, names in entries.pop('reports', {}).items():
    for name in names:
      by_name[name]['workloads'].append(cell)
  for key, more in entries.items():
    bench[key].extend(more)
  with open(path, 'w') as f:
    json.dump(bench, f)


def make_root(root: str) -> str:
  os.makedirs(root, exist_ok=True)
  for sub in DATA_DIRS:
    src = os.path.join(REPO, 'chipbench', sub)
    if os.path.isdir(src):
      shutil.copytree(src, os.path.join(root, 'chipbench', sub))
  shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), root)
  add_cell(root, MESH_CELL)
  return root


def make_tiny_root(root: str) -> str:
  """`make_root`, cut to a size a CPU test can run."""
  make_root(root)
  for name in os.listdir(os.path.join(root, 'chipbench', 'configs')):
    path = os.path.join(root, 'chipbench', 'configs', name)
    with open(path) as f:
      cfg = json.load(f)
    cfg.update(TINY)
    if 'traffic' in cfg:
      cfg['traffic'] = {'train-fused': {'steps_per_dispatch': 3}}
    with open(path, 'w') as f:
      json.dump(cfg, f)
  for name in os.listdir(os.path.join(root, 'chipbench', 'traffic')):
    path = os.path.join(root, 'chipbench', 'traffic', name)
    with open(path) as f:
      t = json.load(f)
    t.update(batch=16, trace_seconds=0.3, probe_reps=1)
    t.update({k: 4 for k in ('steps_per_dispatch',) if k in t})
    t.update({k: 6 for k in ('steps_per_epoch',) if k in t})
    with open(path, 'w') as f:
      json.dump(t, f)
  return root
