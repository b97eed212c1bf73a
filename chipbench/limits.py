"""Read, on the chip and at a cell's own size, the numbers a limit is
set from (`PERF.md`, "correct"):

    python3 -m chipbench.limits --workload <name> --seeds 1,2,3,... \\
        --controls 3 [--variants bfloat16,highest]

For every seed: the program's first three steps against the plain
reference (the lower reading).  For the first ``--controls`` seeds
also, on the very same drawn steps, whatever the cell's driver names
under `controls()` (`drivers._Driver`) — for the GraphSAGE cells the
reference with its matmul operands rounded to bfloat16 and to
float8_e4m3 put in the program's place (the controls) and the
reference with half of every batch left out (a fault) — and the
program with its state left unchanged (a fault); and, per
``--variants``, the program itself rebuilt under another matmul
precision (``high``, ``default``) or with its own bfloat16 path
(``bfloat16``: the model's `dtype=bfloat16`).  No window
is measured.  One JSON line per reading, also appended to
`chiprun_out/limits_<workload>.jsonl`.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import time

from . import run


def read_seed(spec, seed, controls: bool, variants):
  import jax
  import jax.numpy as jnp
  from . import drivers
  make = functools.partial(drivers.make, spec['cfg'], spec['traffic'],
                           seed, builders_dir=spec['builders_dir'])
  out = {}
  drv = make()
  data = drv.data
  first = drv.first_steps()
  records = {}
  for v in variants if controls else ():
    ctx = (contextlib.nullcontext() if v == 'bfloat16'
           else jax.default_matmul_precision(v))
    with ctx:
      alt = make(data=data,
                 model_dtype=jnp.bfloat16 if v == 'bfloat16' else None)
      records[f'program_{v}'] = alt.first_steps()['prog']
      alt.free()
  del data
  numbers = run.exact_counts(drv, first)
  ref = drv.follow(first['steps'])
  out['program'] = dict(numbers, **drv.gaps(first['prog'], ref))
  if controls:
    for name, rec in records.items():
      out[name] = drv.gaps(rec, ref)
    for name, control in drv.controls().items():
      out[name] = drv.gaps(drv.follow(first['steps'], **control), ref)
    out['fault_state_unchanged'] = drv.gaps(
        drv.unchanged(first['prog']), ref)
  return out


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument('--workload', required=True)
  ap.add_argument('--seeds', required=True)
  ap.add_argument('--controls', type=int, default=3)
  ap.add_argument('--variants', default='high,default,bfloat16')
  ap.add_argument('--root', default=run.ROOT)
  ap.add_argument('--any-device', action='store_true')
  args = ap.parse_args(argv)
  spec = run.load_cell(args.root, args.workload)
  if not args.any_device:
    run.find_device(int(spec['cell']['chips']))
    run.enable_cache()
  variants = [v for v in args.variants.split(',') if v]
  os.makedirs(os.path.join(args.root, 'chiprun_out'), exist_ok=True)
  path = os.path.join(args.root, 'chiprun_out',
                      f'limits_{args.workload}.jsonl')
  for i, seed in enumerate(int(s) for s in args.seeds.split(',')):
    t0 = time.perf_counter()
    with run.matmul_precision(spec['cfg']):
      got = read_seed(spec, seed, i < args.controls, variants)
    for what, numbers in got.items():
      rec = dict(workload=args.workload, seed=seed, what=what,
                 secs=round(time.perf_counter() - t0, 1), **numbers)
      print(json.dumps(rec), flush=True)
      with open(path, 'a') as f:
        f.write(json.dumps(rec) + '\n')
    # a jitted closure keeps its driver, and so a seed's tables, alive
    import gc
    import jax
    jax.clear_caches()
    gc.collect()


if __name__ == '__main__':
  main()
