"""The `mesh_sharded` builder's driver: `FusedDistTreeEpoch.run` back to
back on a 4-device mesh (`chipbench.drivers.MeshFusedDriver`'s program,
window, counters and first-steps protocol) over a graph that one chip
cannot hold — so nothing here keeps a whole copy of anything:

  * the dataset is `DistDataset.from_device_coo`'s: the COO drawn
    block by block on the mesh, relabelled, exchanged and sorted there,
    the table and labels filled shard by shard from the seed
    (`mesh_sharded_build`), at the edge capacity the configuration
    states — one set of compiled programs serves every seed;
  * `correct` holds what `MeshFusedDriver` holds — the trees each
    device drew in the first steps by the program's own collect, every
    gathered row and label (the ones that crossed a shard among them)
    against the table, no slot dropped, losses, gradients and weights
    after three steps against the plain float32 reference — with the
    rows a tree names RECOMPUTED FROM THE SEED on the device that
    compares them (`_check_rows`: every column, exactly) and the
    reference computed in blocks, one device's batch on each device
    (`mesh_sharded_reference`), the trees held against the seed's COO
    block by block after the window;
  * the benchmark's own arrays live on the devices only while the
    first steps are compared (before the window) and after the program
    is freed (after it): no device's peak in the window is the check's.

The driver asks the program for `DistDataset.from_device_coo` before it
builds anything: a tree without it exits non-zero at once.

Compiling is most of a cold run (a program with a device sort takes a
minute of it, whatever its size), so the comparison's own programs —
the collect, the recount of the trees against the COO, the
reference's step — are compiled AHEAD (`_Ahead`): on threads of their
own, from shapes, beside the epoch program's own compile; the epoch
program is dispatched once all of them are loaded (`first_steps`).
Nothing compiles in the window.
"""
import concurrent.futures
import contextlib

import numpy as np

from chipbench import beside, build as base, drivers

build = beside(__file__, 'mesh_sharded_build')
ref = beside(__file__, 'mesh_sharded_reference')


class _Ahead:
  """A jitted program compiled on a thread of its own for ``args``
  (arrays, or shapes with their shardings), under the matmul precision
  in force where it was asked for (a thread inherits none); called, it
  waits for the compile and runs it.  ``keep=False`` compiles for the
  persistent cache's sake alone: the executable is dropped at once,
  and a failure is the later caller's to meet."""
  _pool = concurrent.futures.ThreadPoolExecutor(
      max_workers=5, thread_name_prefix='chipbench-compile')

  def __init__(self, jitted, *args, keep=True):
    import jax
    precision = jax.config.jax_default_matmul_precision
    self._keep = keep

    def compile_():
      ctx = (jax.default_matmul_precision(precision) if precision
             else contextlib.nullcontext())
      with ctx:
        compiled = jitted.lower(*args).compile()
      return compiled if keep else None
    self._compiled = self._pool.submit(compile_)

  def wait(self):
    if self._keep:
      return self._compiled.result()
    return self._compiled.exception()

  def __call__(self, *args):
    return self.wait()(*args)


class ShardedMeshFusedDriver(drivers.MeshFusedDriver):

  def __init__(self, cfg, traffic, seed, model_dtype=None, data=None):
    import jax
    from graphlearn_tpu import parallel
    if not hasattr(parallel.DistDataset, 'from_device_coo'):
      raise SystemExit(
          'chipbench: the mesh_sharded builder needs '
          'graphlearn_tpu.parallel.DistDataset.from_device_coo (shards '
          'built on the mesh at a stated capacity); this tree has none')
    from graphlearn_tpu.models import TreeSAGE
    drivers._Driver.__init__(self, cfg, traffic, seed, model_dtype)
    self.num_devices = p = int(cfg['chips'])
    self.steps = int(traffic['steps_per_dispatch'])
    self.n = n = int(cfg['num_nodes'])
    self.mesh = parallel.make_mesh(p, build.AXIS)
    self.data = data or (build.dataset(cfg, seed, self.mesh),)
    self.dds, = self.data
    self.new2old = self.dds.new2old
    self.shard_build = dict(self.dds.shard_build)
    self.layers0 = base.host_layers(cfg, seed)
    self.rows_of = build.feat_rows(cfg)
    self.labels_of = build.label_rows(cfg)
    self.keys = build.keys(seed)
    rng = np.random.default_rng(self.seed)
    self.ids = rng.choice(n, self.steps * p * self.batch, replace=False)
    tx = drivers._tx(cfg)
    self.epoch = parallel.FusedDistTreeEpoch(
        self.dds, list(self.fanout), self.ids,
        TreeSAGE(**self.model_kwargs()), tx, batch_size=self.batch,
        mesh=self.mesh, axis=build.AXIS, shuffle=True, seed=self.seed)
    self.state = parallel.replicate(
        drivers._state(base.program_params('tree', self.layers0), tx),
        self.mesh)
    self._rows_bad = 0
    self._compile_ahead()

  def _level_sizes(self):
    return [self.batch * int(np.prod(self.fanout[:t]))
            for t in range(len(self.fanout) + 1)]

  def _compile_ahead(self):
    """The comparison's programs, compiling from now on — and, where
    a persistent compile cache is on, the epoch program beside them,
    for the cache alone (`first_steps`)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    p, b, ax = self.num_devices, self.batch, build.AXIS
    sizes = self._level_sizes()
    shard = NamedSharding(self.mesh, P(ax))
    sds = lambda shape, dt, sharding=None: jax.ShapeDtypeStruct(
        shape, dt, sharding=sharding)
    arrs = self.epoch.sampler._arrays()
    self._collect = _Ahead(
        jax.jit(self.epoch._make_collect_sharded()),
        self._seeds_of(self.ids[:p * b]), jax.random.key(0),
        arrs['indptr'], arrs['indices'], arrs['bounds'], arrs['fshards'],
        arrs['lshards'], arrs['hcounts'])
    total, dim = sum(sizes), int(self.cfg['feature_dim'])
    self._check_rows = _Ahead(
        self._make_check_rows(), sds((p, total), jnp.int32, shard),
        sds((p, total, dim), jnp.float32, shard),
        sds((p, b), jnp.int32, shard), sds((p, b), jnp.int32, shard),
        self.keys)
    trees = 3 * p
    count = build.num_edges(self.cfg) // p
    self._tree_counts = _Ahead(
        ref.tree_counts(
            self.mesh, ax,
            lambda key, d: build.coo_block(key, d, count, self.n), self.n,
            tuple(self.fanout)),
        self.keys['graph'], [sds((trees * s,), jnp.int32) for s in sizes])
    blocks = dict(seeds=sds((p, b), jnp.int32, shard),
                  levels=[sds((p, s), jnp.int32, shard) for s in sizes],
                  owned=[sds((p, s), jnp.float32, shard) for s in sizes])
    self._step_fn = _Ahead(
        ref.step_loss_and_grad(self.mesh, ax, self.rows_of,
                               self.labels_of),
        jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                               self.layers0), blocks, self.keys)
    self._ahead = [self._collect, self._check_rows, self._tree_counts,
                   self._step_fn]
    if jax.config.jax_compilation_cache_dir:
      self._ahead.append(_Ahead(
          self.epoch._compiled.jitted, self.state,
          self.epoch._put_batches(np.zeros((self.steps, p, b), np.int32)),
          jax.random.key(0), self.epoch._chunk_arrs(), keep=False))

  def _seeds_of(self, old):
    return self.epoch._put_batches(self.dds.old2new[old].reshape(
        1, self.num_devices, self.batch))[0]

  def _make_check_rows(self):
    """``f(old_ids [P, L], x [P, L, D], old_seeds [P, B], y [P, B],
    keys) -> count``: per device, the rows and labels it was handed
    that differ from the seed's (every column, exactly; a masked slot
    must hold a zero row), summed over the mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    ax = build.AXIS

    rows_of, labels_of = self.rows_of, self.labels_of

    def per_device(ids, x, seeds, y, keys):
      want = ref.rows_at(rows_of, ids[0], keys['feats'])
      want_y = ref.rows_at(labels_of, seeds[0], keys['labels'])
      return jax.lax.psum(
          jnp.sum(jnp.any(x[0] != want, axis=-1), dtype=jnp.int32)
          + jnp.sum(y[0] != want_y, dtype=jnp.int32), ax)

    return jax.jit(jax.shard_map(
        per_device, mesh=self.mesh, in_specs=(P(ax),) * 4 + (P(),),
        out_specs=P(), check_vma=False))

  def _trees(self, epoch_idx, batches_old):
    """`MeshFusedDriver._trees` without a table to index: what each
    device drew and was sent, by the program's own collect body and
    key schedule; ids go back to the seed's numbering on the host, and
    every gathered row and label is compared on the device that holds
    it with the seed's, recomputed there."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    arrs = self.epoch.sampler._arrays()
    key = jax.random.fold_in(jax.random.key(self.seed), epoch_idx)
    p, b = self.num_devices, self.batch
    sizes = np.cumsum([0] + self._level_sizes())
    shard = NamedSharding(self.mesh, P(build.AXIS))
    bounds = np.asarray(self.dds.graph.bounds)
    out = []
    for i, old in enumerate(batches_old):
      ids, x, y = self._collect(
          self._seeds_of(old), jax.random.fold_in(key, i), arrs['indptr'],
          arrs['indices'], arrs['bounds'], arrs['fshards'],
          arrs['lshards'], arrs['hcounts'])[:3]
      ids = np.asarray(ids)
      ok = ids >= 0
      old_ids = np.where(ok, self.new2old[np.where(ok, ids, 0)],
                         -1).astype(np.int32)
      old_seeds = old.reshape(p, b).astype(np.int32)
      self._rows_bad += int(self._check_rows(
          jax.device_put(old_ids, shard), x,
          jax.device_put(old_seeds, shard), y, self.keys))
      owned = (ids >= bounds[:-1, None]) & (ids < bounds[1:, None])
      out.append([dict(seeds=old_seeds[d],
                       levels=[old_ids[d, s:e]
                               for s, e in zip(sizes, sizes[1:])],
                       owned=[owned[d, s:e].astype(np.float32)
                              for s, e in zip(sizes, sizes[1:])])
                  for d in range(p)])
    return out

  def first_steps(self):
    """`MeshFusedDriver.first_steps`, once every program compiled ahead
    is compiled and loaded: the epoch program is then the LAST a device
    loads, in a cold process and in a warm one alike, and no compile is
    left running when the window opens.  Why the order: three processes
    in twelve — both cold ones, where the epoch finishes compiling long
    before the collect — ran the same executable 11.3 % slower, its
    hop-2 draw 53.0 ms a step where the others read 34.75 (PERF.md
    sections 6 and 7).  A cold process meets the epoch in the cache the
    thread beside the others filled."""
    for ahead in self._ahead:
      ahead.wait()
    return super().first_steps()

  def window(self, seconds):
    """`MeshFusedDriver.window`; the seconds the program's
    ``dist.shard_build`` span took ride along for `shard_build_s`."""
    return dict(super().window(seconds),
                shard_build_s=float(self.shard_build['secs']))

  def free(self):
    import gc
    self.epoch = self.state = self.dds = self.data = None
    self.new2old = self._collect = self._check_rows = None
    self._ahead = ()
    gc.collect()

  def draw_counts(self, steps):
    """Every tree of the first steps against the seed's COO, drawn
    again block by block (`mesh_sharded_reference.tree_counts`)."""
    trees = [s['levels'] for shards in steps for s in shards]
    levels = [np.concatenate([t[h] for t in trees])
              for h in range(len(self.fanout) + 1)]
    bad_e, bad_f = self._tree_counts(self.keys['graph'], levels)
    return dict(bad_edges=int(bad_e), bad_fanout=int(bad_f))

  def follow(self, steps, rnd=None, half=False, local_only=False):
    """The plain reference following ``steps`` in blocks over the mesh;
    a control or fault is a program of its own, compiled when asked
    for (`chipbench.limits` only)."""
    step_fn = self._step_fn
    if rnd or half or local_only:
      step_fn = ref.step_loss_and_grad(
          self.mesh, build.AXIS, self.rows_of, self.labels_of, rnd=rnd,
          half=half, local_only=local_only)
    return ref.follow(self.layers0, steps, step_fn, self.keys, self.mesh,
                      build.AXIS, self.hyper)


DRIVERS = {'fused': ShardedMeshFusedDriver}
