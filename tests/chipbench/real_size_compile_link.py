"""Real-size compile rehearsal of `sage-products-link.train-fused`, run by
hand (no test collects it: the compiles take minutes):

    JAX_PLATFORMS=cpu python tests/chipbench/real_size_compile_link.py

The cell's programs at its own widths, batch, seed width and
capacities — the 32-step epoch (`FusedLinkEpoch._epoch_fn`), the
epoch's sample-only collect of one step with the gather, the check of
one drawn batch and the reference's step over the whole subgraph —
compiled by the TPU's compiler for one chip of a described
``v5e:2x2``, on two threads as the driver compiles them.  The
epoch is built over a stand-in graph with the configuration's static
sizes (enough nodes that no capacity is clamped) and lowered with the
real table's and CSR's shapes.  Nothing runs, so nothing here is a
measurement; the lines it prints are what PERF.md's sizing of the
configuration quotes.  Exit code 1 if a program's arguments and
temporaries do not fit 16 GB.
"""
import concurrent.futures
import functools
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

CELL = 'sage-products-link.train-fused'


def main():
  import jax
  import jax.numpy as jnp
  from jax.experimental import topologies
  from jax.sharding import SingleDeviceSharding
  from chipbench import beside, build as base, drivers, run
  from graphlearn_tpu.data import Dataset
  from graphlearn_tpu.loader import FusedLinkEpoch
  from graphlearn_tpu.models import GraphSAGE
  from graphlearn_tpu.sampler import NegativeSampling
  os.environ.setdefault('TPU_LOG_DIR', 'disabled')
  jax.config.update('jax_enable_compilation_cache', False)
  topo = topologies.get_topology_desc(platform='tpu',
                                      topology_name='v5e:2x2')
  spec = run.load_cell(REPO, CELL)
  cfg, traffic = spec['cfg'], spec['traffic']
  b, steps = int(traffic['batch']), int(traffic['steps_per_dispatch'])
  n, dim = int(cfg['num_nodes']), int(cfg['feature_dim'])
  edges = n * int(cfg['avg_degree'])
  link = os.path.join(spec['builders_dir'], 'link_fused.py')
  ref, build = beside(link, 'link_fused_reference'), beside(
      link, 'link_fused_build')
  stand_in = 940_000          # > every capacity: nothing is clamped
  rows = np.arange(stand_in, dtype=np.int64)
  ds = (Dataset()
        .init_graph((rows, (rows + 1) % stand_in), layout='COO',
                    num_nodes=stand_in)
        .init_node_features(np.zeros((stand_in, 1), np.float32)))
  model = GraphSAGE(hidden_features=cfg['hidden'],
                    out_features=cfg['hidden'],
                    num_layers=cfg['num_layers'])
  tx = drivers._tx(cfg)
  neg = cfg['negatives']
  epoch = FusedLinkEpoch(ds, list(cfg['fanout']), (rows[:b], rows[:b]),
                         model.apply, tx, batch_size=b,
                         neg_sampling=NegativeSampling(neg['mode'],
                                                       neg['amount']),
                         seed=1, max_steps_per_program=steps)
  one = SingleDeviceSharding(topo.devices[0])
  sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
  on = lambda tree: jax.tree_util.tree_map(
      lambda a: sd(a.shape, a.dtype), tree)
  dev = dict(epoch._dev, indptr=sd((n + 1,), jnp.int32),
             indices=sd((edges,), jnp.int32),
             hot=sd((n, dim), jnp.float32))
  layers = [(np.zeros((i, o), np.float32), np.zeros((o,), np.float32),
             np.zeros((i, o), np.float32))
            for i, o in build.layer_dims(cfg)]
  state = on(jax.eval_shape(lambda: drivers._state(
      base.program_params('subgraph', layers), tx)))
  (node_caps, slot_caps), _ = epoch._layout
  cap, slots, width = node_caps[-1], slot_caps[-1], node_caps[0]
  pairs = b + epoch._num_neg
  i32, ok = jnp.int32, jnp.bool_
  key = on(jax.eval_shape(lambda: jax.random.key(0)))
  step = dict(node=sd((cap,), i32), src=sd((slots,), i32),
              dst=sd((slots,), i32), edge_ok=sd((slots,), ok),
              eli=sd((2, pairs), i32), label=sd((pairs,), i32),
              mask=sd((pairs,), ok))
  programs = {
      'epoch': (epoch._compiled.jitted,
                (state, sd((steps, b), i32), sd((steps, b), i32), None,
                 key, dev, False)),
      'collect': (jax.jit(functools.partial(epoch._link_collect_fn,
                                            collect_x=True)),
                  (sd((1, b), i32), sd((1, b), i32), sd((1, b), i32), key,
                   dev)),
      'check': (jax.jit(functools.partial(
          ref.check_batch, batch=b, ends=tuple(slot_caps),
          fanouts=tuple(cfg['fanout']))),
                (dev['indptr'], dev['indices'], dev['hot'], sd((cap,), i32),
                 sd((slots,), i32), sd((slots,), i32), sd((slots,), ok),
                 sd((width,), i32), sd((2, pairs), i32), sd((pairs,), i32),
                 sd((pairs,), ok), sd((cap, dim), jnp.float32))),
      'reference_step': (jax.jit(ref.loss_and_grad),
                         (on(layers), step, dev['hot'])),
  }

  def compile_(name):
    jitted, args = programs[name]
    t0 = time.perf_counter()
    with run.matmul_precision(cfg):
      m = jitted.lower(*args).compile().memory_analysis()
    return dict(program=name, compile_s=round(time.perf_counter() - t0, 1),
                arguments_gb=m.argument_size_in_bytes / 1e9,
                temporaries_gb=m.temp_size_in_bytes / 1e9)

  # as the driver does (`builders/link_fused._Chain`): the epoch on one
  # thread, the comparison's three one after the other on another —
  # compiled all four at once, the TPU's compiler overflowed its stack
  # in one of its passes
  chains = [('epoch',), ('collect', 'check', 'reference_step')]
  with concurrent.futures.ThreadPoolExecutor(len(chains)) as pool:
    got = [r for rs in pool.map(lambda c: [compile_(p) for p in c], chains)
           for r in rs]
  for g in got:
    print('real-size compile:', json.dumps(dict(workload=CELL, **g)))
  return 0 if all(g['arguments_gb'] + g['temporaries_gb'] < 16.0
                  for g in got) else 1


if __name__ == '__main__':
  sys.exit(main())
