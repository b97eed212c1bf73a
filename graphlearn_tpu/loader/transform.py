"""SamplerOutput → model-ready batch pytrees.

Counterpart of reference `loader/transform.py:25-104` (``to_data`` /
``to_hetero_data`` building `torch_geometric.data.Data`/`HeteroData`).
The TPU analog of a PyG ``Data`` is a static-shape pytree of
`jax.Array`s that crosses `jit` boundaries unchanged: same field names
(``x / y / edge_index / edge_attr / batch``), plus the validity masks
the padding contract requires.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..typing import EdgeType, NodeType
from ..sampler.base import HeteroSamplerOutput, SamplerOutput
from ..telemetry.spans import span
from ..utils.profiling import layer_scope


def _contains_array(v) -> bool:
  if hasattr(v, 'shape') or hasattr(v, 'dtype'):
    return True
  if isinstance(v, dict):
    return any(_contains_array(x) for x in v.values())
  if isinstance(v, (list, tuple)):
    return any(_contains_array(x) for x in v)
  return False


def _split_metadata(metadata: Dict):
  """Split metadata into (dynamic array-valued, static hashable) parts
  so batches stay jit-compatible pytrees even when samplers attach
  strings (e.g. ``input_type``).  Containers holding arrays (the
  hetero ``seed_local`` per-type dict) count as dynamic."""
  dyn, static = {}, {}
  for k, v in metadata.items():
    if _contains_array(v):
      dyn[k] = v
    else:
      static[k] = v
  return dyn, tuple(sorted(static.items()))


class Batch:
  """PyG-``Data``-shaped mini-batch (homogeneous), as a pytree.

  Attributes:
    x: ``[node_cap, D]`` node features (zero rows where padded).
    y: ``[node_cap]`` node labels (0 where padded) or None.
    edge_index: ``[2, edge_cap]`` local COO, -1 where masked; transposed
      for message passing (row = neighbor/source, col = target) exactly
      as the reference emits it.
    edge_attr: ``[edge_cap, De]`` edge features or None.
    node: ``[node_cap]`` global node ids (INVALID_ID padded).
    node_mask: ``[node_cap]`` validity.
    edge_mask: ``[edge_cap]`` validity.
    edge: ``[edge_cap]`` global edge ids or None.
    batch: ``[B]`` global seed ids.
    batch_size: static seed count (padded slots included).
    metadata: link-prediction labels etc. (``edge_label`` /
      ``edge_label_index`` / ``edge_label_mask`` / triplet indices).
  """

  def __init__(self, x=None, y=None, edge_index=None, edge_attr=None,
               node=None, node_mask=None, edge_mask=None, edge=None,
               batch=None, batch_size: int = 0, num_sampled_nodes=None,
               num_sampled_edges=None, metadata=None):
    self.x = x
    self.y = y
    self.edge_index = edge_index
    self.edge_attr = edge_attr
    self.node = node
    self.node_mask = node_mask
    self.edge_mask = edge_mask
    self.edge = edge
    self.batch = batch
    self.batch_size = batch_size
    self.num_sampled_nodes = num_sampled_nodes
    self.num_sampled_edges = num_sampled_edges
    self.metadata = metadata if metadata is not None else {}

  def tree_flatten(self):
    dyn_md, static_md = _split_metadata(self.metadata)
    children = (self.x, self.y, self.edge_index, self.edge_attr, self.node,
                self.node_mask, self.edge_mask, self.edge, self.batch,
                self.num_sampled_nodes, self.num_sampled_edges, dyn_md)
    return children, (self.batch_size, static_md)

  @classmethod
  def tree_unflatten(cls, aux, children):
    (x, y, edge_index, edge_attr, node, node_mask, edge_mask, edge, batch,
     nsn, nse, metadata) = children
    metadata = dict(metadata)
    metadata.update(dict(aux[1]))
    return cls(x, y, edge_index, edge_attr, node, node_mask, edge_mask, edge,
               batch, aux[0], nsn, nse, metadata)

  def __repr__(self):
    shp = lambda a: getattr(a, 'shape', None)
    return (f'Batch(x={shp(self.x)}, edge_index={shp(self.edge_index)}, '
            f'batch_size={self.batch_size})')


jax.tree_util.register_pytree_node(
    Batch, lambda b: b.tree_flatten(), Batch.tree_unflatten)


class HeteroBatch:
  """PyG-``HeteroData``-shaped mini-batch: per-type dicts of arrays."""

  def __init__(self, x_dict=None, y_dict=None, edge_index_dict=None,
               edge_attr_dict=None, node_dict=None, node_mask_dict=None,
               edge_mask_dict=None, batch_dict=None, batch_size: int = 0,
               metadata=None):
    self.x_dict = x_dict or {}
    self.y_dict = y_dict or {}
    self.edge_index_dict = edge_index_dict or {}
    self.edge_attr_dict = edge_attr_dict or {}
    self.node_dict = node_dict or {}
    self.node_mask_dict = node_mask_dict or {}
    self.edge_mask_dict = edge_mask_dict or {}
    self.batch_dict = batch_dict or {}
    self.batch_size = batch_size
    self.metadata = metadata if metadata is not None else {}

  def tree_flatten(self):
    dyn_md, static_md = _split_metadata(self.metadata)
    children = (self.x_dict, self.y_dict, self.edge_index_dict,
                self.edge_attr_dict, self.node_dict, self.node_mask_dict,
                self.edge_mask_dict, self.batch_dict, dyn_md)
    return children, (self.batch_size, static_md)

  @classmethod
  def tree_unflatten(cls, aux, children):
    (x, y, ei, ea, node, nm, em, batch, metadata) = children
    metadata = dict(metadata)
    metadata.update(dict(aux[1]))
    return cls(x, y, ei, ea, node, nm, em, batch, aux[0], metadata)

  def __repr__(self):
    return (f'HeteroBatch(node_types={list(self.node_dict)}, '
            f'edge_types={list(self.edge_index_dict)})')


jax.tree_util.register_pytree_node(
    HeteroBatch, lambda b: b.tree_flatten(), HeteroBatch.tree_unflatten)


@jax.jit
def _gather_labels(labels: jax.Array, ids: jax.Array) -> jax.Array:
  with layer_scope('gather', 'labels'):
    valid = ids >= 0
    idx = jnp.where(valid, ids, 0)
    out = labels[idx]
    mask = valid.reshape(valid.shape + (1,) * (out.ndim - 1))
    return jnp.where(mask, out, 0)


def to_data(
    out: SamplerOutput,
    node_feature=None,
    node_label=None,
    edge_feature=None,
) -> Batch:
  """Assemble a `Batch` from a `SamplerOutput` + gathered features.

  Mirrors reference `loader/transform.py:25-53` (``to_data``):
  feature/label tensors are indexed by the sampled global node ids;
  metadata (link labels) is forwarded.
  """
  x = None
  if node_feature is not None:
    with span('feature.get'):
      x = node_feature[out.node]
  y = None
  if node_label is not None:
    if isinstance(node_label, jax.Array) and isinstance(out.node,
                                                       jax.Array):
      # all-device label gather: no host round trip per batch
      y = _gather_labels(node_label, out.node)
    else:
      import numpy as np
      ids = np.asarray(out.node)
      valid = ids >= 0
      lab = np.asarray(node_label)
      yv = np.zeros((len(ids),) + lab.shape[1:], dtype=lab.dtype)
      yv[valid] = lab[ids[valid]]
      y = jnp.asarray(yv)
  edge_attr = None
  if edge_feature is not None and out.edge is not None:
    edge_attr = edge_feature[out.edge]
  edge_index = jnp.stack([out.row, out.col])
  return Batch(
      x=x, y=y, edge_index=edge_index, edge_attr=edge_attr,
      node=out.node, node_mask=out.node >= 0, edge_mask=out.edge_mask,
      edge=out.edge, batch=out.batch, batch_size=out.batch_size,
      num_sampled_nodes=out.num_sampled_nodes,
      num_sampled_edges=out.num_sampled_edges,
      metadata=dict(out.metadata))


def collate(data, out) -> Any:
  """Dispatch a sampler output through the right collation against a
  `Dataset` — the one shared implementation behind every loader's
  ``_collate_fn`` (reference `loader/node_loader.py:85-113`)."""
  if isinstance(out, HeteroSamplerOutput):
    label_dict = None
    if isinstance(data.node_labels, dict):
      label_dict = {nt: data.get_node_label_device(nt)
                    for nt in data.node_labels}
    return to_hetero_data(
        out,
        node_feature_dict=data.node_features
        if isinstance(data.node_features, dict) else None,
        node_label_dict=label_dict,
        edge_feature_dict=data.edge_features
        if isinstance(data.edge_features, dict) else None)
  return to_data(
      out,
      node_feature=data.get_node_feature(),
      node_label=data.get_node_label_device(),
      edge_feature=(data.get_edge_feature()
                    if out.edge is not None else None))


def to_hetero_data(
    out: HeteroSamplerOutput,
    node_feature_dict: Optional[Dict[NodeType, Any]] = None,
    node_label_dict: Optional[Dict[NodeType, Any]] = None,
    edge_feature_dict: Optional[Dict[EdgeType, Any]] = None,
) -> HeteroBatch:
  """Assemble a `HeteroBatch` (reference `loader/transform.py:56-104`)."""
  import numpy as np
  x_dict, y_dict, nm_dict = {}, {}, {}
  for ntype, ids in out.node.items():
    nm_dict[ntype] = ids >= 0
    if node_feature_dict and ntype in node_feature_dict:
      with span('feature.get', ntype=ntype):
        x_dict[ntype] = node_feature_dict[ntype].get(ids, part=ntype)
    if node_label_dict and ntype in node_label_dict:
      lab = node_label_dict[ntype]
      if isinstance(lab, jax.Array) and isinstance(ids, jax.Array):
        y_dict[ntype] = _gather_labels(lab, ids)
      else:
        ids_h = np.asarray(ids)
        valid = ids_h >= 0
        lab = np.asarray(lab)
        yv = np.zeros((len(ids_h),) + lab.shape[1:], dtype=lab.dtype)
        yv[valid] = lab[ids_h[valid]]
        y_dict[ntype] = jnp.asarray(yv)
  ei_dict, em_dict, ea_dict = {}, {}, {}
  for etype in out.row:
    ei_dict[etype] = jnp.stack([out.row[etype], out.col[etype]])
    if out.edge_mask is not None and etype in out.edge_mask:
      em_dict[etype] = out.edge_mask[etype]
    if (edge_feature_dict and etype in edge_feature_dict
        and out.edge is not None and etype in out.edge):
      ea_dict[etype] = edge_feature_dict[etype][out.edge[etype]]
  batch_size = 0
  if out.batch:
    batch_size = max(int(v.shape[0]) for v in out.batch.values())
  return HeteroBatch(
      x_dict=x_dict, y_dict=y_dict, edge_index_dict=ei_dict,
      edge_attr_dict=ea_dict, node_dict=dict(out.node),
      node_mask_dict=nm_dict, edge_mask_dict=em_dict,
      batch_dict=dict(out.batch or {}), batch_size=batch_size,
      metadata=dict(out.metadata))
