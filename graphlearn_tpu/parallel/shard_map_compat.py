"""`jax.shard_map` with ``check_vma=False``: every mesh program in
this package was written with the varying-manual-axes check off, so
the setting lives in one place."""
from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, mesh, in_specs, out_specs):
  return _shard_map(f, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False)
