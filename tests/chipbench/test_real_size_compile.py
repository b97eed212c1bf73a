"""Real-size compile rehearsal for the chip that is not attached.

The two fused step programs the benchmark times, at the sizes of
`chipbench/configs/*.json`, compiled by the TPU's own compiler for a
described ``v5e:2x2`` topology: what it refuses here (memory, a
partitioning it cannot do) costs no chip time.  Nothing runs, so
nothing here is a measurement; `memory_analysis()` per device is what
`PERF.md`'s sizing paragraph quotes.

The topology is described inside a fixture, in this one file, so that
only the worker that runs these tests loads the TPU's library
(`on-chip-measurement` guide, section 2).
"""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
  sys.path.insert(0, REPO)

import cellroot
from chipbench import run

HBM_BYTES = 16e9


@pytest.fixture(scope='module')
def topo():
  import jax
  from jax.experimental import topologies
  os.environ.setdefault('TPU_LOG_DIR', 'disabled')
  try:
    desc = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
  except Exception as e:  # noqa: BLE001 — no TPU compiler here
    pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
  # the suite compiles its CPU programs unoptimised (conftest); the
  # rehearsal wants the production pipeline
  was = jax.config.read('jax_disable_most_optimizations')
  jax.config.update('jax_disable_most_optimizations', False)
  yield desc
  jax.config.update('jax_disable_most_optimizations', was)


def _tiny_driver(workload, root=REPO):
  """The cell's driver over a tiny graph at the cell's own widths,
  batch and fanout: the program object whose step is compiled."""
  from chipbench import drivers
  spec = run.load_cell(root, workload)
  cfg = dict(spec['cfg'], num_nodes=4000, avg_degree=4)
  with run.matmul_precision(cfg):
    return spec, drivers.make(cfg, spec['traffic'], 1)


def _report(workload, compiled):
  m = compiled.memory_analysis()
  out = dict(workload=workload,
             arguments_gb=m.argument_size_in_bytes / 1e9,
             temporaries_gb=m.temp_size_in_bytes / 1e9,
             outputs_gb=m.output_size_in_bytes / 1e9)
  print('real-size compile:', json.dumps(out))
  return out


def test_fused_step_compiles_for_one_v5e_chip(topo):
  import jax
  import jax.numpy as jnp
  from jax.sharding import SingleDeviceSharding
  workload = 'sage-products.train-fused'
  spec, drv = _tiny_driver(workload)
  cfg, traffic = spec['cfg'], spec['traffic']
  n, e = cfg['num_nodes'], cfg['num_nodes'] * cfg['avg_degree']
  one = SingleDeviceSharding(topo.devices[0])
  sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
  on = lambda tree: jax.tree_util.tree_map(
      lambda a: sd(a.shape, a.dtype), tree)
  dev = dict(indptr=sd((n + 1,), jnp.int32), indices=sd((e,), jnp.int32),
             hot=sd((n, cfg['feature_dim']), jnp.float32), id2index=None,
             labels=sd((n,), jnp.int32))
  with run.matmul_precision(cfg):
    compiled = jax.jit(
        drv.epoch._epoch_fn, static_argnums=(4,), donate_argnums=(0,)
    ).lower(on(jax.eval_shape(lambda: drv.state)),
            sd((traffic['steps_per_dispatch'], traffic['batch']),
               jnp.int32),
            on(jax.eval_shape(lambda: jax.random.key(0))), dev,
            False).compile()
  got = _report(workload, compiled)
  assert got['arguments_gb'] == pytest.approx(
      (4 * (n + 1) + 4 * e + 4 * n * cfg['feature_dim'] + 4 * n) / 1e9,
      rel=0.05)
  # fits the chip, and is no toy: over a quarter of its memory
  assert 0.25 * HBM_BYTES < (got['arguments_gb']
                             + got['temporaries_gb']) * 1e9 < HBM_BYTES


def test_mesh_step_compiles_for_four_v5e_chips(topo, tmp_path_factory):
  import jax
  import jax.numpy as jnp
  from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
  workload = 'sage-products-p4.train-fused'
  spec, drv = _tiny_driver(
      workload, cellroot.make_root(str(tmp_path_factory.mktemp('root'))))
  cfg, traffic = spec['cfg'], spec['traffic']
  p, ep = int(cfg['chips']), drv.epoch
  mesh = Mesh(np.asarray(topo.devices[:p]), ('data',))
  ep.mesh = mesh
  ep._sharded_step = ep._make_sharded(train=True)
  per = cfg['num_nodes'] // p + 8
  eper = int(cfg['num_nodes'] * cfg['avg_degree'] / p * 1.02)
  real = dict(indptr=(p, per + 1), indices=(p, eper), eids=(p, eper),
              fshards=(p, per, cfg['feature_dim']), lshards=(p, per))
  sh = lambda shape, dt, spec_: jax.ShapeDtypeStruct(
      shape, dt, sharding=NamedSharding(mesh, spec_))
  arrs = {k: sh(real.get(k, a.shape), a.dtype, a.sharding.spec)
          for k, a in ep.sampler._arrays().items()}
  rep = lambda tree: jax.tree_util.tree_map(
      lambda a: sh(a.shape, a.dtype, P()), tree)
  with run.matmul_precision(cfg):
    compiled = jax.jit(ep._epoch_fn, donate_argnums=(0,)).lower(
        rep(jax.eval_shape(lambda: drv.state)),
        sh((traffic['steps_per_dispatch'], p, traffic['batch']),
           jnp.int32, P(None, 'data')),
        rep(jax.eval_shape(lambda: jax.random.key(0))), arrs).compile()
  got = _report(workload, compiled)
  text = compiled.as_text()
  # the frontier and feature exchange are there, and the gradient mean
  assert 'all-to-all' in text and 'all-reduce' in text
  per_device = (got['arguments_gb'] + got['temporaries_gb']) * 1e9
  assert 0.25 * HBM_BYTES < per_device < HBM_BYTES
