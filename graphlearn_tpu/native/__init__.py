"""ctypes bindings for the native host runtime (``csrc/``).

The reference exposes its C++/CUDA layer through a pybind11 module
(`python/py_export.cc:46-216`); this build uses a plain C ABI + ctypes
(no pybind11 in the image).  No binary is checked in: the library is
built with ``make`` from ``csrc/`` on first use (and rebuilt when a
source is newer) — the moral equivalent of the reference's
build-on-install `setup.py` extension.

Everything here is *host* runtime: cross-process shm queues and
serialization for the producer pipeline, and CPU twins of the sampling
ops.  The device plane lives in `graphlearn_tpu/ops` (XLA/Pallas).
"""
from __future__ import annotations

import ctypes
import os
import pickle
import subprocess
import threading
from typing import Dict, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_CSRC = os.path.join(os.path.dirname(os.path.dirname(_HERE)), 'csrc')
_SO = os.path.join(_HERE, 'libglt_native.so')

_lib = None
_lock = threading.Lock()

# numpy dtype <-> wire code (keep stable: messages cross processes).
_DTYPE_CODES = {
    np.dtype(np.float32): 0, np.dtype(np.float64): 1,
    np.dtype(np.int32): 2, np.dtype(np.int64): 3,
    np.dtype(np.uint8): 4, np.dtype(np.bool_): 5,
    np.dtype(np.int16): 6, np.dtype(np.uint16): 7,
    np.dtype(np.float16): 8,
}
try:  # bfloat16 ships with jax via ml_dtypes
  import ml_dtypes as _ml
  _DTYPE_CODES[np.dtype(_ml.bfloat16)] = 9
except ImportError:
  pass
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}


def _build(force: bool = False):
  srcs = [os.path.join(_CSRC, f) for f in
          ('shm_queue.cc', 'tensor_map.cc', 'cpu_ops.cc', 'inducer.cc',
           'common.h')]
  if not force and os.path.exists(_SO):
    so_mtime = os.path.getmtime(_SO)
    if all(os.path.getmtime(s) <= so_mtime for s in srcs if
           os.path.exists(s)):
      return
  # build beside the target and rename: producer subprocesses race to
  # first use, and a half-written library must never be loadable
  tmp = f'{_SO}.{os.getpid()}.tmp'
  try:
    subprocess.run(['make', '-s', f'OUT={tmp}'], cwd=_CSRC, check=True)
    os.replace(tmp, _SO)
  finally:
    if os.path.exists(tmp):
      os.unlink(tmp)


def lib() -> ctypes.CDLL:
  """The loaded native library (built on first use).  A binary that
  fails to *load* — a built tree copied from a host with a different
  libstdc++/glibc — is rebuilt from source and retried once, instead
  of poisoning every native-dependent path on this machine."""
  global _lib
  if _lib is None:
    with _lock:
      if _lib is None:
        _build()
        try:
          l = ctypes.CDLL(_SO)
        except OSError:
          _build(force=True)
          l = ctypes.CDLL(_SO)
        _declare(l)
        _lib = l
  return _lib


def available() -> bool:
  try:
    lib()
    return True
  except Exception:
    return False


def _declare(l):
  u64, i64, i32 = ctypes.c_uint64, ctypes.c_int64, ctypes.c_int32
  p = ctypes.c_void_p
  l.glt_queue_create.restype = p
  l.glt_queue_create.argtypes = [u64, u64]
  l.glt_queue_attach.restype = p
  l.glt_queue_attach.argtypes = [ctypes.c_int]
  l.glt_queue_shmid.restype = ctypes.c_int
  l.glt_queue_shmid.argtypes = [p]
  l.glt_queue_slot_bytes.restype = u64
  l.glt_queue_slot_bytes.argtypes = [p]
  l.glt_queue_num_slots.restype = u64
  l.glt_queue_num_slots.argtypes = [p]
  l.glt_queue_size.restype = u64
  l.glt_queue_size.argtypes = [p]
  l.glt_queue_put.restype = ctypes.c_int
  l.glt_queue_put.argtypes = [p, ctypes.c_char_p, u64]
  l.glt_queue_get.restype = i64
  l.glt_queue_get.argtypes = [p, p, u64]
  l.glt_queue_get_timed.restype = i64
  l.glt_queue_get_timed.argtypes = [p, p, u64, i64]
  l.glt_queue_empty.restype = ctypes.c_int
  l.glt_queue_empty.argtypes = [p]
  l.glt_queue_detach.argtypes = [p]
  l.glt_queue_detach.restype = None

  u16p = np.ctypeslib.ndpointer(np.uint16, flags='C')
  u8p = np.ctypeslib.ndpointer(np.uint8, flags='C')
  u64p = np.ctypeslib.ndpointer(np.uint64, flags='C')
  i64p = np.ctypeslib.ndpointer(np.int64, flags='C')
  i32p = np.ctypeslib.ndpointer(np.int32, flags='C')
  f32p = np.ctypeslib.ndpointer(np.float32, flags='C')

  l.glt_tmap_size.restype = u64
  l.glt_tmap_size.argtypes = [ctypes.c_uint32, u16p, u8p, u64p]
  l.glt_tmap_write.restype = u64
  l.glt_tmap_write.argtypes = [
      ctypes.c_uint32, u16p, ctypes.c_char_p, u8p, u8p, u64p, u64p,
      ctypes.POINTER(ctypes.c_void_p), p]
  l.glt_tmap_count.restype = ctypes.c_uint32
  l.glt_tmap_count.argtypes = [p, u64]
  l.glt_tmap_parse.restype = ctypes.c_int
  l.glt_tmap_parse.argtypes = [p, u64, u16p, p, u8p, u8p, u64p, u64p, u64p]

  l.glt_coo_to_csr.restype = None
  l.glt_coo_to_csr.argtypes = [i64p, i64p, i64, i64, i64p, i64p, i64p]
  l.glt_sample_one_hop.restype = None
  l.glt_sample_one_hop.argtypes = [i64p, i64p, p, i64p, i64, i64, i64,
                                   u64, i64p, u8p, p]
  l.glt_cal_nbr_prob.restype = None
  l.glt_cal_nbr_prob.argtypes = [i64p, i64p, f32p, i64, i64, f32p]
  l.glt_negative_sample.restype = i64
  l.glt_negative_sample.argtypes = [i64p, i64p, i64, i64, i64,
                                    ctypes.c_int, ctypes.c_int, u64,
                                    i64p, i64p]

  l.glt_inducer_create.restype = p
  l.glt_inducer_create.argtypes = [i64]
  l.glt_inducer_destroy.argtypes = [p]
  l.glt_inducer_destroy.restype = None
  l.glt_inducer_clear.argtypes = [p]
  l.glt_inducer_clear.restype = None
  l.glt_inducer_num_nodes.restype = i64
  l.glt_inducer_num_nodes.argtypes = [p]
  l.glt_inducer_init.restype = None
  l.glt_inducer_init.argtypes = [p, i64p, i64, i32p]
  l.glt_inducer_induce.restype = i64
  l.glt_inducer_induce.argtypes = [p, i64p, i64p, u8p, i64, i64, i32p, i32p]
  l.glt_inducer_nodes_since.restype = None
  l.glt_inducer_nodes_since.argtypes = [p, i64, i64, i64p]
  l.glt_inducer_induce_pair.restype = i64
  l.glt_inducer_induce_pair.argtypes = [p, i32p, i64p, u8p, i64, i64,
                                        i32p, i32p]


# ---------------------------------------------------------------------------
# Serialization: Dict[str, np.ndarray] <-> bytes
# ---------------------------------------------------------------------------
def serialize_tensor_map(msg: Dict[str, np.ndarray]) -> bytes:
  """Flat-binary serialize (reference `csrc/tensor_map.cc:28-85` twin)."""
  l = lib()
  def _contig(v):
    v = np.asarray(v)
    # NB: np.ascontiguousarray would promote 0-d to 1-d; preserve rank.
    return v if v.flags['C_CONTIGUOUS'] else np.ascontiguousarray(v)
  items = [(k, _contig(v)) for k, v in msg.items()]
  n = len(items)
  key_bytes = b''.join(k.encode() for k, _ in items)
  key_lens = np.array([len(k.encode()) for k, _ in items], np.uint16)
  dtypes = np.array([_DTYPE_CODES[v.dtype] for _, v in items], np.uint8)
  ndims = np.array([v.ndim for _, v in items], np.uint8)
  shapes = np.array([d for _, v in items for d in v.shape], np.uint64)
  if shapes.size == 0:
    shapes = np.zeros(1, np.uint64)
  nbytes = np.array([v.nbytes for _, v in items], np.uint64)
  datas = (ctypes.c_void_p * n)(
      *[v.ctypes.data_as(ctypes.c_void_p).value for _, v in items])
  size = l.glt_tmap_size(n, key_lens, ndims, nbytes)
  out = ctypes.create_string_buffer(int(size))
  written = l.glt_tmap_write(n, key_lens, key_bytes, dtypes, ndims,
                             shapes, nbytes, datas, out)
  assert written == size, (written, size)
  return out.raw


def parse_tensor_map(buf: bytes) -> Dict[str, np.ndarray]:
  """Inverse of :func:`serialize_tensor_map` (copies out of ``buf``)."""
  l = lib()
  raw = ctypes.create_string_buffer(buf, len(buf))
  base = ctypes.cast(raw, ctypes.c_void_p)
  n = l.glt_tmap_count(base, len(buf))
  if n == 0 and len(buf) >= 12:
    raise ValueError('bad tensor-map buffer')
  key_lens = np.zeros(max(n, 1), np.uint16)
  dtypes = np.zeros(max(n, 1), np.uint8)
  ndims = np.zeros(max(n, 1), np.uint8)
  # Generous caps: keys and shapes are tiny.
  keys_buf = ctypes.create_string_buffer(len(buf))
  shapes = np.zeros(max(len(buf) // 8, 8), np.uint64)
  nbytes = np.zeros(max(n, 1), np.uint64)
  offs = np.zeros(max(n, 1), np.uint64)
  rc = l.glt_tmap_parse(base, len(buf), key_lens, keys_buf, dtypes,
                        ndims, shapes, nbytes, offs)
  if rc != 0:
    raise ValueError('malformed tensor-map buffer')
  out: Dict[str, np.ndarray] = {}
  kpos = 0
  spos = 0
  arr = np.frombuffer(buf, np.uint8)
  for i in range(n):
    key = keys_buf.raw[kpos:kpos + key_lens[i]].decode()
    kpos += key_lens[i]
    shape = tuple(int(s) for s in shapes[spos:spos + ndims[i]])
    spos += ndims[i]
    dt = _CODE_DTYPES[int(dtypes[i])]
    start = int(offs[i])
    data = arr[start:start + int(nbytes[i])].tobytes()
    out[key] = np.frombuffer(data, dt).reshape(shape)
  return out


# ---------------------------------------------------------------------------
# ShmQueue: cross-process bounded message queue
# ---------------------------------------------------------------------------
class ShmQueue:
  """Fixed-slot MPMC ring in SysV shm (see `csrc/shm_queue.cc`).

  Picklable: pickling captures the shmid; unpickling re-attaches —
  the reference's `SampleQueue` pickling contract
  (`py_export.cc:132-140`).
  """

  def __init__(self, num_slots: int, slot_bytes: int,
               _shmid: Optional[int] = None):
    self._l = lib()
    if _shmid is None:
      self._h = self._l.glt_queue_create(num_slots, slot_bytes)
      if not self._h:
        raise OSError('shmget failed (check kernel.shmmax)')
    else:
      self._h = self._l.glt_queue_attach(_shmid)
      if not self._h:
        raise OSError(f'shmat({_shmid}) failed')

  @property
  def shmid(self) -> int:
    return self._l.glt_queue_shmid(self._h)

  @property
  def slot_bytes(self) -> int:
    return self._l.glt_queue_slot_bytes(self._h)

  def qsize(self) -> int:
    return self._l.glt_queue_size(self._h)

  def empty(self) -> bool:
    return bool(self._l.glt_queue_empty(self._h))

  def put_bytes(self, data: bytes):
    rc = self._l.glt_queue_put(self._h, data, len(data))
    if rc != 0:
      raise ValueError(
          f'message of {len(data)} bytes exceeds slot size '
          f'{self.slot_bytes}')

  def get_bytes(self) -> bytes:
    cap = self.slot_bytes
    buf = ctypes.create_string_buffer(int(cap))
    n = self._l.glt_queue_get(self._h, buf, cap)
    if n < 0:
      raise ValueError('message exceeded receive buffer')
    return buf.raw[:n]

  def get_bytes_timed(self, timeout: float):
    """Dequeue with a timeout (seconds); ``None`` when nothing arrived
    — consumers run liveness watchdogs between waits."""
    cap = self.slot_bytes
    buf = ctypes.create_string_buffer(cap)
    n = self._l.glt_queue_get_timed(self._h, buf, cap,
                                    int(timeout * 1000))
    if n == -2:
      return None
    if n < 0:
      raise ValueError('message exceeded receive buffer')
    return buf.raw[:n]

  def get_timed(self, timeout: float):
    b = self.get_bytes_timed(timeout)
    return None if b is None else parse_tensor_map(b)

  def put(self, msg: Dict[str, np.ndarray]):
    self.put_bytes(serialize_tensor_map(msg))

  def get(self) -> Dict[str, np.ndarray]:
    return parse_tensor_map(self.get_bytes())

  def close(self):
    if getattr(self, '_h', None):
      self._l.glt_queue_detach(self._h)
      self._h = None

  def __del__(self):
    try:
      self.close()
    except Exception:
      pass

  def __reduce__(self):
    return (ShmQueue, (0, 0, self.shmid))


# ---------------------------------------------------------------------------
# CPU op wrappers
# ---------------------------------------------------------------------------
def coo_to_csr(rows: np.ndarray, cols: np.ndarray, num_nodes: int
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
  """Counting-sort COO->CSR; returns (indptr, indices, edge_perm)."""
  l = lib()
  rows = np.ascontiguousarray(rows, np.int64)
  cols = np.ascontiguousarray(cols, np.int64)
  e = len(rows)
  indptr = np.zeros(num_nodes + 1, np.int64)
  indices = np.zeros(e, np.int64)
  perm = np.zeros(e, np.int64)
  l.glt_coo_to_csr(rows, cols, e, num_nodes, indptr, indices, perm)
  return indptr, indices, perm


def sample_one_hop(indptr: np.ndarray, indices: np.ndarray,
                   seeds: np.ndarray, k: int, seed: int = 0,
                   edge_ids: Optional[np.ndarray] = None,
                   with_edge_ids: bool = False):
  """Dense uniform one-hop sample — host twin of
  `graphlearn_tpu.ops.sample_one_hop` (same [B,k]+mask contract)."""
  if k > 256:
    raise ValueError('fanout must be <= 256')
  l = lib()
  indptr = np.ascontiguousarray(indptr, np.int64)
  indices = np.ascontiguousarray(indices, np.int64)
  seeds = np.ascontiguousarray(seeds, np.int64)
  b = len(seeds)
  nbrs = np.empty((b, k), np.int64)
  mask = np.empty((b, k), np.uint8)
  eids = np.empty((b, k), np.int64) if with_edge_ids else None
  eid_ptr = (eids.ctypes.data_as(ctypes.c_void_p) if with_edge_ids
             else None)
  src_eids = (np.ascontiguousarray(edge_ids, np.int64)
              .ctypes.data_as(ctypes.c_void_p)
              if edge_ids is not None else None)
  l.glt_sample_one_hop(indptr, indices, src_eids, seeds, b,
                       len(indptr) - 1, k, seed, nbrs, mask, eid_ptr)
  return nbrs, mask.astype(bool), eids


def cal_nbr_prob(indptr, indices, prob_in, k: int) -> np.ndarray:
  l = lib()
  indptr = np.ascontiguousarray(indptr, np.int64)
  indices = np.ascontiguousarray(indices, np.int64)
  prob_in = np.ascontiguousarray(prob_in, np.float32)
  n = len(indptr) - 1
  out = np.zeros(n, np.float32)
  l.glt_cal_nbr_prob(indptr, indices, prob_in, n, k, out)
  return out


def negative_sample(indptr, indices, req_num: int, trials: int = 5,
                    strict: bool = True, padding: bool = False,
                    seed: int = 0):
  l = lib()
  indptr = np.ascontiguousarray(indptr, np.int64)
  indices = np.ascontiguousarray(indices, np.int64)
  n = len(indptr) - 1
  rows = np.empty(req_num, np.int64)
  cols = np.empty(req_num, np.int64)
  cnt = l.glt_negative_sample(indptr, indices, n, req_num, trials,
                              int(strict), int(padding), seed, rows, cols)
  return rows[:cnt], cols[:cnt]


class CpuInducer:
  """Stateful dedup/relabel — host twin of the device inducer
  (`graphlearn_tpu/ops/unique.py`); see `csrc/inducer.cc`."""

  def __init__(self, capacity_hint: int = 1024):
    self._l = lib()
    self._h = self._l.glt_inducer_create(capacity_hint)

  def __del__(self):
    try:
      if getattr(self, '_h', None):
        self._l.glt_inducer_destroy(self._h)
        self._h = None
    except Exception:
      pass

  def clear(self):
    self._l.glt_inducer_clear(self._h)

  @property
  def num_nodes(self) -> int:
    return self._l.glt_inducer_num_nodes(self._h)

  def init_nodes(self, seeds: np.ndarray) -> np.ndarray:
    seeds = np.ascontiguousarray(seeds, np.int64)
    out = np.empty(len(seeds), np.int32)
    self._l.glt_inducer_init(self._h, seeds, len(seeds), out)
    return out

  def induce_next(self, srcs: np.ndarray, nbrs: np.ndarray,
                  mask: np.ndarray):
    """Returns (new_nodes, row_local, col_local); edges are
    neighbor->seed (message-passing direction)."""
    srcs = np.ascontiguousarray(srcs, np.int64)
    nbrs = np.ascontiguousarray(nbrs, np.int64)
    mask = np.ascontiguousarray(mask, np.uint8)
    b, k = nbrs.shape
    rows = np.empty((b, k), np.int32)
    cols = np.empty((b, k), np.int32)
    before = self.num_nodes
    n_new = self._l.glt_inducer_induce(self._h, srcs, nbrs, mask, b, k,
                                       rows, cols)
    new_nodes = np.empty(n_new, np.int64)
    if n_new:
      self._l.glt_inducer_nodes_since(self._h, before, n_new, new_nodes)
    return new_nodes, rows, cols

  def all_nodes(self) -> np.ndarray:
    return self.nodes_since(0)

  def nodes_since(self, start: int) -> np.ndarray:
    """Global ids of table slots ``[start, num_nodes)`` in local-id
    order — the nodes first discovered after a hop snapshot."""
    n = self.num_nodes - int(start)
    out = np.empty(max(n, 0), np.int64)
    if n > 0:
      self._l.glt_inducer_nodes_since(self._h, start, n, out)
    return out

  def induce_from(self, src_local: np.ndarray, nbrs: np.ndarray,
                  mask: np.ndarray):
    """Hetero hop: the frontier's local ids come from a *different*
    (source-type) inducer; neighbors insert into THIS table.  Returns
    (new_nodes, row_local, col_local), edges neighbor->seed like
    `induce_next`."""
    src_local = np.ascontiguousarray(src_local, np.int32)
    nbrs = np.ascontiguousarray(nbrs, np.int64)
    mask = np.ascontiguousarray(mask, np.uint8)
    b, k = nbrs.shape
    rows = np.empty((b, k), np.int32)
    cols = np.empty((b, k), np.int32)
    before = self.num_nodes
    n_new = self._l.glt_inducer_induce_pair(self._h, src_local, nbrs, mask,
                                            b, k, rows, cols)
    new_nodes = np.empty(n_new, np.int64)
    if n_new:
      self._l.glt_inducer_nodes_since(self._h, before, n_new, new_nodes)
    return new_nodes, rows, cols
