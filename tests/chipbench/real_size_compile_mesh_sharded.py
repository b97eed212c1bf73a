"""Real-size compile rehearsal of `sage-papers100m-p4.train-fused`, run by
hand before any chip time is spent (no test collects it: the compiles
take minutes):

    JAX_PLATFORMS=cpu python tests/chipbench/real_size_compile_mesh_sharded.py

The programs a run of the cell compiles, at the configuration's own
sizes, by the TPU's compiler for the four chips of a described
``v5e:2x2``: the shard build (`parallel.dist_data.coo_shard_program`:
relabel, sort, exchange, sort), the mesh fused epoch
(`FusedDistTreeEpoch._epoch_fn`) and the collect the comparison draws
the first steps' trees with.  Nothing runs, so nothing here is a
measurement; per program it prints the arguments and temporaries of one
device and the seconds the compile took HERE, which PERF.md's sizing of
the configuration quotes.  Exit code 1 if a program does not fit a
16 GB chip beside what else the device holds then.
"""
import json
import os
import sys
import time

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
os.environ.setdefault(
    'XLA_FLAGS', '--xla_force_host_platform_device_count=4')
os.environ.setdefault('TPU_LOG_DIR', 'disabled')

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

CELL = 'sage-papers100m-p4.train-fused'


def main():
  import jax
  import jax.numpy as jnp
  import numpy as np
  from jax.experimental import topologies
  from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
  from chipbench import beside, drivers, run
  from graphlearn_tpu.parallel.dist_data import coo_shard_program
  topo = topologies.get_topology_desc(platform='tpu',
                                      topology_name='v5e:2x2')
  spec = run.load_cell(REPO, CELL)
  cfg, traffic = spec['cfg'], spec['traffic']
  build = beside(os.path.join(spec['builders_dir'], 'mesh_sharded.py'),
                 'mesh_sharded_build')
  p, n, d = int(cfg['chips']), int(cfg['num_nodes']), cfg['feature_dim']
  e, cap = build.num_edges(cfg), build.edge_capacity(cfg)
  assert cap == cfg['edge_capacity']['per_device'], cap
  mesh = Mesh(np.asarray(topo.devices[:p]), (build.AXIS,))
  sh = lambda shape, dt, spec_: jax.ShapeDtypeStruct(
      shape, dt, sharding=NamedSharding(mesh, spec_))
  rep = lambda tree: jax.tree_util.tree_map(
      lambda a: sh(a.shape, a.dtype, P()), tree)
  out = []

  def report(name, lowered, held_gb):
    t0 = time.perf_counter()
    m = lowered.compile().memory_analysis()
    got = dict(program=name, compile_s=round(time.perf_counter() - t0, 1),
               arguments_gb=m.argument_size_in_bytes / 1e9,
               temporaries_gb=m.temp_size_in_bytes / 1e9,
               outputs_gb=m.output_size_in_bytes / 1e9,
               held_beside_gb=held_gb)
    print('real-size compile:', json.dumps(got), flush=True)
    out.append(got)

  ax = build.AXIS
  report('shard_build', coo_shard_program(
      mesh, ax, n // p, cap).lower(
          sh((e,), jnp.int32, P(ax)), sh((e,), jnp.int32, P(ax)),
          sh((n,), jnp.int32, P()), sh((p + 1,), jnp.int32, P())), 0.0)

  # the cell's driver over a tiny graph at the cell's own widths, batch
  # and fanout: the program objects whose steps are compiled
  tiny = dict(cfg, num_nodes=40000, avg_degree=4,
              edge_capacity=dict(margin=0.5, multiple=8))
  with run.matmul_precision(cfg):
    drv = drivers.make(tiny, traffic, 1, builders_dir=spec['builders_dir'])
  ep = drv.epoch
  per = n // p
  real = dict(indptr=(p, per + 1), indices=(p, cap),
              fshards=(p, per, d), lshards=(p, per))
  arrs = {k: sh(real.get(k, a.shape), a.dtype, a.sharding.spec)
          for k, a in ep.sampler._arrays().items()}
  ep.mesh = mesh
  ep._sharded_step = ep._make_sharded(train=True)
  table_gb = 4 * per * d / 1e9
  b = int(traffic['batch'])
  with run.matmul_precision(cfg):
    report('epoch', jax.jit(ep._epoch_fn, donate_argnums=(0,)).lower(
        rep(jax.eval_shape(lambda: drv.state)),
        sh((traffic['steps_per_dispatch'], p, b), jnp.int32, P(None, ax)),
        rep(jax.eval_shape(lambda: jax.random.key(0))), arrs), 0.0)
    report('collect', jax.jit(ep._make_collect_sharded()).lower(
        sh((p, b), jnp.int32, P(ax)),
        rep(jax.eval_shape(lambda: jax.random.key(0))), arrs['indptr'],
        arrs['indices'], arrs['bounds'], arrs['fshards'], arrs['lshards'],
        arrs['hcounts']), 0.0)
  print(f'real-size compile: a device holds {table_gb:.2f} GB of table, '
        f'{4 * cap / 1e9:.2f} GB of indices')
  fits = all(g['arguments_gb'] + g['temporaries_gb'] + g['held_beside_gb']
             < 15.5 for g in out)
  return 0 if fits else 1


if __name__ == '__main__':
  sys.exit(main())
