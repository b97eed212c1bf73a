"""Device-resident graph handle.

TPU-native counterpart of reference `data/graph.py:125-239` + the
native CSR holder (`csrc/cuda/graph.cu`, `include/graph.h:36-130`).
The reference's three residency modes (CPU / ZERO_COPY UVA / CUDA HBM)
collapse into two on TPU: topology as `jax.Array`s in device HBM
(``'device'``, the fast path — what DMA mode is on GPU), or pinned on
the TPU-VM host (``'host'``, for graphs larger than HBM; gathers are
then staged per batch).  There is no UVA on TPU; the ZERO_COPY
equivalent is host-resident arrays + explicit async `device_put`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .topology import CSRTopo


class DeviceCSRTopo:
  """CSR topology whose arrays already live on device.

  The device-native construction path: graphs built *on* the TPU
  (synthetic benchmarks, on-device ETL, arrays produced by another jit
  program) wrap here without a host round trip — ``np.asarray`` on a
  1 GB device array would pull it to the host just to push it back.
  The caller guarantees canonical sorted-CSR form (the
  host-side :class:`~graphlearn_tpu.data.topology.CSRTopo` constructor
  is where un-canonical input gets fixed up).  Host-only consumers
  (``to_coo`` etc.) intentionally do not exist on this shim; accessing
  ``indptr``/``indices`` yields the device arrays.
  """

  def __init__(self, indptr, indices, edge_ids=None):
    self._indptr = indptr
    self._indices = indices
    self._edge_ids = edge_ids
    self._max_degree = None

  indptr = property(lambda self: self._indptr)
  indices = property(lambda self: self._indices)
  edge_ids = property(lambda self: self._edge_ids)

  @property
  def num_nodes(self) -> int:
    return self._indptr.shape[0] - 1

  @property
  def num_edges(self) -> int:
    return self._indices.shape[0]

  @property
  def degrees(self) -> jax.Array:
    return self._indptr[1:] - self._indptr[:-1]

  @property
  def max_degree(self) -> int:
    if self._max_degree is None:
      self._max_degree = int(jnp.max(self.degrees))   # one scalar pull
    return self._max_degree

  def __repr__(self):
    return (f'DeviceCSRTopo(num_nodes={self.num_nodes}, '
            f'num_edges={self.num_edges})')


class Graph:
  """A graph object holding topology ready for device sampling.

  Args:
    csr_topo: canonical CSR topology.
    mode: ``'device'`` (HBM-resident, default) or ``'host'``.
    device: optional explicit `jax.Device`.
    with_edge_ids: materialize edge ids on device (needed when
      downstream wants edge features / provenance).
  """

  def __init__(self, csr_topo: CSRTopo, mode: str = 'device',
               device: Optional[jax.Device] = None,
               with_edge_ids: bool = True):
    mode = mode.lower()
    if mode not in ('device', 'host'):
      raise ValueError(f'Unsupported graph mode {mode!r}')
    self.csr_topo = csr_topo
    self.mode = mode
    self._device = device
    self.with_edge_ids = with_edge_ids
    self._indptr = None
    self._indices = None
    self._edge_ids = None

  @classmethod
  def from_device_arrays(cls, indptr: jax.Array, indices: jax.Array,
                         edge_ids: Optional[jax.Array] = None) -> 'Graph':
    """Wrap device-resident sorted-CSR arrays without a host round
    trip (see :class:`DeviceCSRTopo`).  Dtypes are narrowed on device
    (indices/edge_ids to int32; indptr to int32 when the edge count
    allows), mirroring what `lazy_init` does for host input."""
    num_edges = indices.shape[0]
    ptr_dtype = (jnp.int32 if num_edges < np.iinfo(np.int32).max
                 else jnp.int64)
    g = cls.__new__(cls)
    g.csr_topo = DeviceCSRTopo(indptr.astype(ptr_dtype),
                               indices.astype(jnp.int32),
                               None if edge_ids is None
                               else edge_ids.astype(jnp.int32))
    g.mode = 'device'
    g._device = None
    g.with_edge_ids = edge_ids is not None
    g._indptr = g.csr_topo.indptr
    g._indices = g.csr_topo.indices
    g._edge_ids = g.csr_topo.edge_ids
    return g

  # Lazy init mirrors reference `data/graph.py:160-188` (`lazy_init`).
  def lazy_init(self):
    if self._indptr is not None:
      return
    if self.mode == 'host':
      dev = _host_device()
    else:
      dev = self._device or jax.devices()[0]
    # indptr entries index edges: narrow to int32 only when safe.
    ptr_dtype = (np.int32 if self.csr_topo.num_edges < np.iinfo(np.int32).max
                 else np.int64)
    self._indptr = jax.device_put(
        np.asarray(self.csr_topo.indptr, dtype=ptr_dtype), dev)
    self._indices = jax.device_put(
        np.asarray(self.csr_topo.indices, dtype=np.int32), dev)
    if self.with_edge_ids:
      eids = np.asarray(self.csr_topo.edge_ids)
      # int32 when the id space allows — halves HBM footprint.
      if eids.size == 0 or eids.max() < np.iinfo(np.int32).max:
        eids = eids.astype(np.int32)
      self._edge_ids = jax.device_put(eids, dev)

  @property
  def indptr(self) -> jax.Array:
    self.lazy_init()
    return self._indptr

  @property
  def indices(self) -> jax.Array:
    self.lazy_init()
    return self._indices

  @property
  def edge_ids(self) -> Optional[jax.Array]:
    self.lazy_init()
    return self._edge_ids

  @property
  def num_nodes(self) -> int:
    return self.csr_topo.num_nodes

  @property
  def num_edges(self) -> int:
    return self.csr_topo.num_edges

  @property
  def max_degree(self) -> int:
    return self.csr_topo.max_degree

  def __repr__(self):
    return (f'Graph(num_nodes={self.num_nodes}, num_edges={self.num_edges}, '
            f'mode={self.mode!r})')


def _host_device() -> jax.Device:
  """Best-effort host (CPU) device for host-resident topology."""
  for d in jax.devices():
    if d.platform == 'cpu':
      return d
  try:
    return jax.devices('cpu')[0]
  except RuntimeError:
    return jax.devices()[0]
