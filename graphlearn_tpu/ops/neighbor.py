"""Uniform neighbor sampling on device (XLA).

TPU-native replacement for the reference's CUDA row-wise sampler
(`csrc/cuda/random_sampler.cu:39-108` — FillNbrsNum + reservoir
CSRRowWiseSampleKernel with curand Philox) and its CPU twin
(`csrc/cpu/random_sampler.cc:76-113`).

Design: the CUDA kernel emits *ragged* ``(nbrs, nbrs_num)``; XLA needs
static shapes, so we emit a dense ``[B, k]`` neighbor block plus a
validity mask.  Per-row strategy (fused into one vectorized program —
no per-row control flow):

  * ``deg <= k``       — take all neighbors (slots ``0..deg-1``).
  * ``k < deg <= W``   — exact uniform sampling *without* replacement
    via Gumbel top-k over a ``W``-wide gathered window (the TPU answer
    to reservoir sampling: no atomics, no sequential state).
  * ``deg > W``        — k independent uniform draws (*with*
    replacement).  With the default ``W = 8k`` the expected number of
    colliding slots is ``< k^2/2W = k/16``; duplicates are deduped by
    the inducer for the node table and are statistically harmless for
    GNN aggregation.

Randomness comes from `jax.random` (threefry), counter-based like
curand Philox, so sampling is reproducible and order-independent.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..utils.padding import INVALID_ID, round_up


class OneHopResult(NamedTuple):
  """Dense one-hop sample.

  Attributes:
    nbrs: ``[B, k]`` global neighbor ids (INVALID_ID where masked).
    mask: ``[B, k]`` slot validity (slot < min(deg, k)).
    eids: ``[B, k]`` global edge ids (INVALID_ID where masked) or None.
    weights: ``[B, k]`` per-edge importance weights (``p/q``
      inclusion-probability correction), or None.  Only the biased
      GNS kernel (`ops.gns.sample_one_hop_gns`) sets this; the
      uniform kernel's draws are already unbiased for the neighbor
      mean, so it leaves the field None and no consumer pays for it.
  """
  nbrs: jax.Array
  mask: jax.Array
  eids: Optional[jax.Array]
  weights: Optional[jax.Array] = None


def default_window(k: int) -> int:
  return round_up(max(8 * k, 64), 8)


@functools.partial(
    jax.jit, static_argnames=('k', 'window', 'with_edge_ids', 'replace',
                              'sort_locality'))
def sample_one_hop(
    indptr: jax.Array,
    indices: jax.Array,
    seeds: jax.Array,
    k: int,
    key: jax.Array,
    edge_ids: Optional[jax.Array] = None,
    *,
    window: Optional[int] = None,
    with_edge_ids: bool = False,
    replace: bool = False,
    sort_locality: bool = True,
) -> OneHopResult:
  """Sample up to ``k`` neighbors for each seed.

  Args:
    indptr: ``[N+1]`` CSR row pointers (device array).
    indices: ``[E]`` CSR column indices.
    seeds: ``[B]`` global seed ids; INVALID_ID entries produce empty
      rows (the masked analog of the reference's empty-fallback at
      `sampler/neighbor_sampler.py:118-136`).
    k: fanout (static).
    key: PRNG key.
    edge_ids: optional ``[E]`` global edge ids to emit alongside.
    window: static window size for the exact without-replacement path;
      defaults to ``8k``.
    with_edge_ids: emit ``eids`` (requires ``edge_ids``).
    replace: force with-replacement draws for every ``deg > k`` row
      (skips the window gather entirely — cheaper, more approximate).
    sort_locality: process seeds in sorted-id order internally (outputs
      restored to input order) — adjacent CSR rows share HBM pages;
      what that is worth to the window gathers is not measured on the
      chip (ROADMAP S3).  Distribution-identical; per-seed draws
      differ from the unsorted order.
  """
  if sort_locality and seeds.shape[0] > 1:
    big = jnp.iinfo(seeds.dtype).max
    order = jnp.argsort(jnp.where(seeds >= 0, seeds, big))
    res = sample_one_hop(indptr, indices, seeds[order], k, key, edge_ids,
                         window=window, with_edge_ids=with_edge_ids,
                         replace=replace, sort_locality=False)
    # restore input order with plain gathers by the inverse permutation
    # (scatters would lower to XLA's collision-safe form — slower)
    inv = jnp.argsort(order)
    return OneHopResult(
        nbrs=res.nbrs[inv], mask=res.mask[inv],
        eids=res.eids[inv] if res.eids is not None else None)
  num_edges = indices.shape[0]
  b = seeds.shape[0]
  slot = jnp.arange(k, dtype=jnp.int32)

  valid_seed = seeds >= 0
  s = jnp.where(valid_seed, seeds, 0)
  # Edge positions keep indptr's dtype (int64-safe for >2^31 edges when
  # x64 is enabled); degrees always fit int32.
  start = indptr[s]
  deg = (indptr[s + 1] - start).astype(jnp.int32)
  deg = jnp.where(valid_seed, deg, 0)

  mask = slot[None, :] < jnp.minimum(deg, k)[:, None]

  k_rand, k_win = jax.random.split(key)
  # --- with-replacement draws (large-degree path / replace=True) -----------
  u = jax.random.uniform(k_rand, (b, k))
  rand_off = jnp.minimum((u * deg[:, None]).astype(jnp.int32),
                         jnp.maximum(deg - 1, 0)[:, None])

  if replace:
    off = jnp.where((deg <= k)[:, None], slot[None, :], rand_off)
  else:
    w = window if window is not None else default_window(k)
    wslot = jnp.arange(w, dtype=jnp.int32)
    in_deg = wslot[None, :] < deg[:, None]          # [B, W]
    g = jax.random.gumbel(k_win, (b, w), dtype=jnp.float32)
    g = jnp.where(in_deg, g, -jnp.inf)
    _, top_idx = jax.lax.top_k(g, k)                # [B, k] window slots
    medium = ((deg > k) & (deg <= w))[:, None]
    off = jnp.where((deg <= k)[:, None], slot[None, :],
                    jnp.where(medium, top_idx.astype(jnp.int32), rand_off))

  pos = jnp.clip(start[:, None] + off, 0, max(num_edges - 1, 0))
  nbrs = jnp.where(mask, indices[pos].astype(jnp.int32), INVALID_ID)
  eids = None
  if with_edge_ids:
    if edge_ids is None:
      eids = jnp.where(mask, pos, INVALID_ID)
    else:
      eids = jnp.where(mask, edge_ids[pos], INVALID_ID)
  return OneHopResult(nbrs=nbrs, mask=mask, eids=eids)


@jax.jit
def lookup_degree(indptr: jax.Array, nodes: jax.Array) -> jax.Array:
  """Degree lookup; counterpart of the ``LookupDegree`` kernel
  (`csrc/cuda/graph.cu:30-68`)."""
  valid = nodes >= 0
  n = jnp.where(valid, nodes, 0)
  deg = indptr[n + 1] - indptr[n]
  return jnp.where(valid, deg, 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=('k',))
def cal_nbr_prob(
    indptr: jax.Array,
    indices: jax.Array,
    node_prob: jax.Array,
    k: int,
) -> jax.Array:
  """Propagate per-node sampling probability one hop.

  Counterpart of ``CalNbrProbKernel`` (`csrc/cuda/random_sampler.cu:
  166-208`), used by the frequency partitioner: each node ``u`` with
  hotness ``p_u`` contributes ``p_u * min(1, k/deg(u))`` to each of its
  neighbors.  Vectorized as a single edge-parallel scatter-add instead
  of a per-row kernel.
  """
  num_nodes = indptr.shape[0] - 1
  num_edges = indices.shape[0]
  edge_pos = jnp.arange(num_edges)
  rows = (jnp.searchsorted(indptr, edge_pos, side='right') - 1).astype(
      jnp.int32)
  deg = (indptr[rows + 1] - indptr[rows]).astype(node_prob.dtype)
  contrib = node_prob[rows] * jnp.minimum(1.0, k / jnp.maximum(deg, 1))
  return jax.ops.segment_sum(contrib, indices, num_segments=num_nodes)
