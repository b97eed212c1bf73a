"""Tree-layout GraphSAGE: scatter-free message passing on the
sampler's native window structure.

The subgraph path (`models.conv.SAGEConv` on a deduped node table)
matches the reference's PyG consumption model
(`examples/train_sage_ogbn_products.py` via PyG ``SAGEConv``), but its
aggregation is a `segment_sum` — an XLA scatter.  On one v5e at the
flagship's widths the per-batch step spends 45.3 of its 147.7 ms in
that model, 20.2 of them in layer 0's two scatter-adds over ~938k
edge slots; this layout's whole model is 7.1 ms of a 37.3 ms fused
step (PERF.md section 5).

TPUs want streams, not scatters.  Multi-hop sampling already produces
a STATIC tree: level ``t`` holds ``B * k_1 * ... * k_t`` slots, and
each parent owns a contiguous ``k_{t+1}``-slot window of children.  On
that layout mean-aggregation is a reshape + masked mean — pure VPU
streaming — and the backward is a broadcast.  No scatter exists
anywhere in the program (the only gathers are the per-level feature
lookups).

Estimator note: the tree does NOT dedup repeated nodes.  A node drawn
twice gets two independently-sampled expansions (the original
GraphSAGE formulation); the deduped subgraph path expands each unique
node once and re-drawn nodes alias one expansion (the reference's
estimator, `csrc/cpu/inducer.cc`).  Both are unbiased neighborhood
estimators; padded compute volume is IDENTICAL (level sizes equal the
subgraph path's per-hop capacity blocks), so the tree layout is a
strict compute-shape win on TPU.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..utils.profiling import layer_scope


def tree_level_sizes(batch_size: int, fanouts: Sequence[int]
                     ) -> Tuple[int, ...]:
  """Slot count per tree level: ``[B, B*k1, B*k1*k2, ...]``."""
  sizes = [batch_size]
  for k in fanouts:
    sizes.append(sizes[-1] * int(k))
  return tuple(sizes)


class TreeSAGE(nn.Module):
  """GraphSAGE (mean aggregator) over tree-layout level tensors.

  ``__call__(xs, masks)`` where ``xs[t]`` is the ``[F_t, D]`` feature
  tensor of level ``t`` (``F_t = B * k_1 * ... * k_t``) and
  ``masks[t]`` its ``[F_t]`` validity — the output is the seed level's
  ``[B, out_features]`` logits.  Layer ``l`` applies ONE weight pair
  (self + neighbor) across all levels that still matter, exactly like
  the subgraph ``SAGEConv`` stack shares weights across the node
  table.

  ``len(xs)`` must be ``num_layers + 1``.
  """
  hidden_features: int
  out_features: int
  num_layers: int = 2
  dtype: Optional[jnp.dtype] = None   # compute dtype (bf16 → MXU);
                                      # params stay f32

  @nn.compact
  def __call__(self, xs: Sequence[jax.Array],
               masks: Sequence[jax.Array]) -> jax.Array:
    if len(xs) != self.num_layers + 1:
      raise ValueError(
          f'TreeSAGE(num_layers={self.num_layers}) needs '
          f'{self.num_layers + 1} levels, got {len(xs)}')
    with layer_scope('model', 'input'):
      hs = [x.astype(self.dtype) if self.dtype is not None else x
            for x in xs]
      # zero out invalid slots once: they then contribute nothing as
      # self terms of masked-out rows or as masked children
      hs = [h * m[:, None].astype(h.dtype) for h, m in zip(hs, masks)]
    for layer in range(self.num_layers):
      with layer_scope('model', f'layer{layer}'):
        out = (self.hidden_features if layer < self.num_layers - 1
               else self.out_features)
        lin_self = nn.Dense(out, dtype=self.dtype,
                            name=f'layer{layer}_self')
        lin_neigh = nn.Dense(out, use_bias=False, dtype=self.dtype,
                             name=f'layer{layer}_neigh')
        new_hs = []
        for t in range(self.num_layers - layer):
          parent, child = hs[t], hs[t + 1]
          k = child.shape[0] // parent.shape[0]
          cm = masks[t + 1].reshape(parent.shape[0], k)
          cd = child.reshape(parent.shape[0], k, child.shape[1])
          # masked mean over the static child window — the whole
          # aggregation.  The mask must gate the SUM too: past layer 0
          # an invalid slot's activation is relu(bias) != 0 (the input
          # zeroing above only cleans the leaves), and an unmasked sum
          # would leak it into every window with degree < fanout.
          cnt = jnp.maximum(cm.sum(axis=1, dtype=jnp.float32), 1.0)
          mean = ((cd * cm[..., None].astype(cd.dtype)).sum(axis=1)
                  / cnt[:, None].astype(cd.dtype))
          h = lin_self(parent) + lin_neigh(mean)
          if layer < self.num_layers - 1:
            h = nn.relu(h)
          new_hs.append(h)
        hs = new_hs
    return hs[0].astype(jnp.float32)
