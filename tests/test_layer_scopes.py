"""Layer scopes on the device ops of the timed programs, and the host
span primitive on the profiler's clock (ISSUE 25).

(a) the compiled HLO of the small flagship programs holds every layer
    of the vocabulary its path has, forward and backward, and every
    gather / scatter / dot / sort / all-to-all of it carries a
    ``glt.`` token;
(b) the lowered text of each program is the same with the helper
    turned into a null context: scopes change no program;
(c) `span` inside a profiler session leaves an event of its name on a
    host plane whose duration agrees with the host clock's (and the
    recorder's ``dur``): the clock check the repo never had.
"""
import contextlib
import functools
import glob
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from graphlearn_tpu.data import Dataset
from graphlearn_tpu.loader import FusedTreeEpoch, NeighborLoader
from graphlearn_tpu.models import (GraphSAGE, TreeSAGE,
                                   make_supervised_step)
from graphlearn_tpu.models.train import TrainState
from graphlearn_tpu.telemetry import recorder, span
from graphlearn_tpu.utils.profiling import LAYERS, layer_scope

N, D, CLASSES, B = 240, 8, 4, 16
FANOUT = [3, 2]
#: opcodes that do a layer's real work: none may go unnamed
HEAVY = ('gather', 'scatter', 'dot', 'sort', 'all-to-all')
_HEAVY = re.compile(r'[\]\)\}] (' + '|'.join(HEAVY) + r')\(')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
MARGIN_S = 5e-3


def _graph():
  rng = np.random.default_rng(0)
  rows = np.repeat(np.arange(N), 6)
  cols = rng.integers(0, N, rows.shape[0])
  feats = rng.normal(size=(N, D)).astype(np.float32)
  labels = (np.arange(N) % CLASSES).astype(np.int32)
  return rows, cols, feats, labels


def _dataset():
  rows, cols, feats, labels = _graph()
  return (Dataset()
          .init_graph((rows, cols), layout='COO', num_nodes=N)
          .init_node_features(feats).init_node_labels(labels))


def _fused_tree():
  """``lower()`` of the one program `FusedTreeEpoch.run` dispatches."""
  tx = optax.adam(1e-2)
  fused = FusedTreeEpoch(
      _dataset(), FANOUT, np.arange(N),
      TreeSAGE(hidden_features=16, out_features=CLASSES,
               num_layers=2), tx, batch_size=B, seed=0,
      max_steps_per_program=3)
  state = fused.init_state(jax.random.key(0))
  return fused._compiled.jitted.lower(
      state, jnp.zeros((3, B), jnp.int32), jax.random.key(0),
      fused._dev, False)


def _loader_batch(ds):
  loader = NeighborLoader(ds, FANOUT, np.arange(N), batch_size=B,
                          shuffle=True, seed=0)
  return loader, next(iter(loader))


def _sampler():
  """The per-batch sampler's program, as `sample_from_nodes` calls it."""
  from graphlearn_tpu.sampler.neighbor_sampler import _multihop_sample
  loader, _ = _loader_batch(_dataset())
  s, g = loader.sampler, loader.sampler.graph
  return _multihop_sample.lower(
      g.indptr, g.indices, None, jnp.zeros((B,), jnp.int32),
      jax.random.key(0), None, fanouts=tuple(FANOUT),
      node_cap=s.node_capacity(B), with_edge=False, sort_locality=True)


def _feature_get():
  from graphlearn_tpu.data.feature import _device_gather
  feat = _dataset().node_features
  feat.lazy_init()
  return _device_gather.lower(feat._hot, jnp.zeros((64,), jnp.int32),
                              feat._id2index_dev, use_pallas=False)


def _supervised_step():
  tx = optax.adam(1e-2)
  _, batch = _loader_batch(_dataset())
  model = GraphSAGE(hidden_features=16, out_features=CLASSES,
                    num_layers=2)
  params = model.init(jax.random.key(0), batch.x, batch.edge_index,
                      batch.edge_mask)
  state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
  return make_supervised_step(model.apply, tx, B).lower(state, batch)


def _mesh_tree():
  from graphlearn_tpu.parallel import (DistDataset, FusedDistTreeEpoch,
                                       make_mesh)
  rows, cols, feats, labels = _graph()
  ds = DistDataset.from_full_graph(4, rows, cols, node_feat=feats,
                                   node_label=labels, num_nodes=N)
  fused = FusedDistTreeEpoch(
      ds, FANOUT, np.arange(N),
      TreeSAGE(hidden_features=16, out_features=CLASSES,
               num_layers=2), optax.adam(1e-2), batch_size=8,
      mesh=make_mesh(4), shuffle=True, seed=0)
  state = fused.init_state(jax.random.key(0))
  seeds = np.stack(list(fused._batcher)).reshape(-1, 4, 8)
  return fused._compiled.jitted.lower(
      state, fused._put_batches(seeds), fused._next_epoch_key(),
      fused._chunk_arrs())


#: program -> (how to lower it, the scopes its compiled HLO must hold;
#: ``bwd:`` wants the token on a ``transpose(jvp(...))`` op)
PROGRAMS = {
    'fused_tree': (_fused_tree, [
        'glt.sample/hop0', 'glt.sample/hop1', 'glt.gather/level0',
        'glt.gather/level2', 'glt.gather/labels', 'glt.model/layer0',
        'glt.model/layer1', 'glt.model/loss', 'glt.optimizer',
        'bwd:glt.model/layer0', 'bwd:glt.model/loss']),
    'sampler': (_sampler, [
        'glt.sample/hop0', 'glt.sample/hop1', 'glt.sample/dedup']),
    'feature_get': (_feature_get, ['glt.gather']),
    'supervised_step': (_supervised_step, [
        'glt.model/layer0', 'glt.model/layer1', 'glt.model/loss',
        'glt.optimizer', 'bwd:glt.model/layer0', 'bwd:glt.model/loss']),
    'mesh_tree': (_mesh_tree, [
        'glt.exchange/frontier', 'glt.exchange/feature',
        'glt.exchange/grads', 'glt.sample/owner', 'glt.gather/owner',
        'glt.model/layer0', 'glt.optimizer', 'bwd:glt.model/layer0']),
}


@functools.lru_cache(maxsize=None)
def _compiled_hlo(program: str) -> str:
  """The program's optimized HLO text (compiled once per process)."""
  return PROGRAMS[program][0]().compile().as_text()


@pytest.mark.parametrize('program', sorted(PROGRAMS))
def test_compiled_program_carries_its_layers(program):
  wanted = PROGRAMS[program][1]
  hlo = _compiled_hlo(program)
  names = _OP_NAME.findall(hlo)
  for scope in wanted:
    back = scope.startswith('bwd:')
    token = scope[4:] if back else scope
    hits = [n for n in names if token in n
            and (not back or 'transpose(' in n[:n.index(token)])]
    assert hits, f'{program}: no op under {scope}'
  unnamed = []
  for line in hlo.splitlines():
    if _HEAVY.search(line):
      m = _OP_NAME.search(line)
      if not m or 'glt.' not in m.group(1):
        unnamed.append(line.strip()[:160])
  assert not unnamed, (f'{program}: {len(unnamed)} of the heavy ops '
                       'carry no glt. token:\n' + '\n'.join(unnamed[:8]))


def test_every_layer_of_the_vocabulary_is_compiled_somewhere():
  """The table holds no layer that no program uses (and the helper
  refuses a layer that is not in it)."""
  seen = set()
  for program in ('fused_tree', 'mesh_tree'):
    for name in _OP_NAME.findall(_compiled_hlo(program)):
      seen |= set(re.findall(r'glt\.(\w+)', name))
  assert seen == set(LAYERS)
  with pytest.raises(ValueError, match='unknown layer'):
    layer_scope('sampler')
  with layer_scope('sample', 'anything'):     # a part is free text
    pass


def _unscoped(monkeypatch):
  """Every module's binding of the helper becomes a null context."""
  null = lambda layer, part=None: contextlib.nullcontext()
  for name, mod in list(sys.modules.items()):
    if name.startswith('graphlearn_tpu') and hasattr(mod, 'layer_scope'):
      monkeypatch.setattr(mod, 'layer_scope', null)


@pytest.mark.parametrize('program', sorted(PROGRAMS))
def test_scopes_change_no_program(program, monkeypatch):
  """``as_text()`` prints no locations: with and without the scopes
  it is the same text, so the scopes are metadata and nothing else."""
  lower = PROGRAMS[program][0]
  jax.clear_caches()          # nested jits must trace again, not reuse
  scoped = lower()
  assert 'glt.' in scoped.as_text(debug_info=True)
  _unscoped(monkeypatch)
  jax.clear_caches()
  plain = lower()
  assert 'glt.' not in plain.as_text(debug_info=True)
  assert scoped.as_text() == plain.as_text()


# -- (c) the host span on the profiler's clock -------------------------------

def _host_events(trace_dir, name):
  found = sorted(glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                           recursive=True))
  assert found, f'no trace under {trace_dir}'
  prof = jax.profiler.ProfileData.from_file(found[-1])
  return [e for plane in prof.planes
          if not plane.name.startswith('/device:')
          for line in plane.lines for e in line.events
          if e.name == name]


@pytest.mark.parametrize('recorder_on', [False, True])
def test_span_is_on_the_profilers_clock(tmp_path, recorder_on):
  if recorder_on:
    recorder.enable(str(tmp_path / 'flight.jsonl'))
  try:
    jax.profiler.start_trace(str(tmp_path / 'trace'))
    try:
      with span('clock.check', batch=7):
        t0 = time.monotonic()
        time.sleep(0.05)
        jnp.ones(4).block_until_ready()
        inner_s = time.monotonic() - t0
    finally:
      jax.profiler.stop_trace()
    ends = [e for e in recorder.events('span.end')
            if e.get('name') == 'clock.check']
  finally:
    recorder.disable()
  events = _host_events(str(tmp_path / 'trace'), 'clock.check')
  assert len(events) == 1
  traced_s = events[0].duration_ns / 1e9
  # the annotation opens before and closes after what the block timed
  # (and what the recorder times); they agree within a millisecond on
  # a quiet machine, and the margin is what holds with six workers
  assert 0 <= traced_s - inner_s < MARGIN_S
  if recorder_on:
    assert len(ends) == 1
    assert abs(traced_s - ends[0]['dur']) < MARGIN_S
  else:
    assert not ends
