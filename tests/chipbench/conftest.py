"""The waiting four-chip cell of `mesh_cell/`, as a PR would add it NOW.

`exchange_collective_share` and `exchange_padding_share` waited in
`mesh_cell/layer_metrics/` and are the benchmark's own since PR 34
(`chipbench/layer_metrics/`, the same bytes —
`test_mesh_sharded_cell.py` holds them to each other —, reported by
`sage-papers100m-p4.train-fused`).  `cellroot.add_cell` refuses a file
that is there already, and both `cellroot.py` and the waiting files
are under `BENCHMARK.json`'s `paths`, which the PR that brought the
metrics in may neither edit nor delete.  So for the length of a test
session `cellroot.MESH_CELL` names a copy of the waiting cell without
what the benchmark now has: a metric file that `chipbench/` holds
under the same name is left out with its `per_layer` entry, and the
cell's name goes onto that metric's ``workloads`` through the
``reports`` of `entries.json` — which is how a cell reports a metric
the benchmark already has.  A `benchmark` PR that deletes the two
waiting files deletes this file with them.
"""
import json
import os
import shutil

import pytest

import cellroot


def without_what_the_benchmark_has(waiting: str, copy: str) -> str:
  home = os.path.join(cellroot.REPO, 'chipbench', 'layer_metrics')
  shutil.copytree(waiting, copy)
  with open(os.path.join(copy, 'entries.json')) as f:
    entries = json.load(f)
  kept = []
  for m in entries['per_layer']:
    if not os.path.exists(os.path.join(home, m['name'] + '.json')):
      kept.append(m)
      continue
    os.remove(os.path.join(copy, 'layer_metrics', m['name'] + '.json'))
    for cell in m['workloads']:
      entries['reports'].setdefault(cell, []).append(m['name'])
  entries['per_layer'] = kept
  with open(os.path.join(copy, 'entries.json'), 'w') as f:
    json.dump(entries, f)
  return copy


@pytest.fixture(scope='session', autouse=True)
def mesh_cell_as_a_pr_would_add_it_now(tmp_path_factory):
  copy = without_what_the_benchmark_has(
      cellroot.MESH_CELL,
      str(tmp_path_factory.mktemp('waiting') / 'mesh_cell'))
  with pytest.MonkeyPatch.context() as patch:
    patch.setattr(cellroot, 'MESH_CELL', copy)
    yield
