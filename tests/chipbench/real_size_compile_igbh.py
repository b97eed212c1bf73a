"""Real-size compile rehearsal of `rgat-igbh.train-loader-ahead`, run by hand
(no test collects it: the compile takes a minute and a half):

    JAX_PLATFORMS=cpu python tests/chipbench/real_size_compile_igbh.py

The cell's train step at its own widths, batch and typed capacities
(those of the configuration's node counts), compiled by the TPU's
compiler for one chip of a described ``v5e:2x2``, as
`test_real_size_compile.py` does for the fused programs.  Nothing runs,
so nothing here is a measurement; the line it prints is what PERF.md's
sizing of the configuration quotes.  Exit code 1 if arguments and
temporaries do not fit 16 GB beside the tables and the CSRs.
"""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

CELL = 'rgat-igbh.train-loader-ahead'


def main():
  import jax
  import jax.numpy as jnp
  from jax.experimental import topologies
  from jax.sharding import SingleDeviceSharding
  from chipbench import drivers, run
  from graphlearn_tpu.loader.transform import HeteroBatch
  from graphlearn_tpu.sampler.hetero_neighbor_sampler import (
      _plan, typed_hop_capacities)
  os.environ.setdefault('TPU_LOG_DIR', 'disabled')
  topo = topologies.get_topology_desc(platform='tpu',
                                      topology_name='v5e:2x2')
  spec = run.load_cell(REPO, CELL)
  cfg, traffic = spec['cfg'], spec['traffic']
  b, d = int(traffic['batch']), int(cfg['feature_dim'])
  tiny = dict(cfg, num_nodes=dict(paper=4000, author=3000, institute=60,
                                  fos=300))
  with run.matmul_precision(cfg):
    drv = drivers.make(tiny, traffic, 1, builders_dir=spec['builders_dir'])
  s = drv.loader.sampler
  caps = typed_hop_capacities(s.etypes, _plan(
      s.etypes, s.fanouts, {cfg['target']: b}, s.num_hops,
      dict(cfg['num_nodes'])))
  node, edge = dict(caps[0]), dict(caps[1])
  one = SingleDeviceSharding(topo.devices[0])
  sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
  on = lambda tree: jax.tree_util.tree_map(
      lambda a: sd(a.shape, a.dtype), tree)
  target = cfg['target']
  batch = HeteroBatch(
      x_dict={t: sd((c[-1], d), jnp.bfloat16) for t, c in node.items()},
      y_dict={target: sd((node[target][-1],), jnp.int32)},
      edge_index_dict={r: sd((2, e[-1]), jnp.int32)
                       for r, e in edge.items()},
      node_dict={t: sd((c[-1],), jnp.int32) for t, c in node.items()},
      node_mask_dict={t: sd((c[-1],), jnp.bool_) for t, c in node.items()},
      edge_mask_dict={r: sd((e[-1],), jnp.bool_) for r, e in edge.items()},
      batch_dict={target: sd((b,), jnp.int32)}, batch_size=b,
      metadata={'seed_local': sd((b,), jnp.int32), 'input_type': target,
                'hop_capacities': caps})
  with run.matmul_precision(cfg):
    compiled = drv._train.lower(on(jax.eval_shape(lambda: drv.state)),
                                batch).compile()
  m = compiled.memory_analysis()
  step_gb = (m.argument_size_in_bytes + m.temp_size_in_bytes) / 1e9
  tables_gb = 2 * d * sum(cfg['num_nodes'].values()) / 1e9
  print('real-size compile:', json.dumps(dict(
      workload=CELL, table_rows={t: c[-1] for t, c in node.items()},
      edge_slots=sum(e[-1] for e in edge.values()),
      arguments_gb=m.argument_size_in_bytes / 1e9,
      temporaries_gb=m.temp_size_in_bytes / 1e9, tables_gb=tables_gb)))
  # the tables, about 0.25 GB of CSRs, the step's arguments and
  # temporaries: inside the chip, with room for the next batch
  return 0 if tables_gb + 0.25 + step_gb < 14.0 else 1


if __name__ == '__main__':
  sys.exit(main())
