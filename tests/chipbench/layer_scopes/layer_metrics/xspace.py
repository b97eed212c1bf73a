"""A profiler trace with what `jax.profiler.ProfileData` leaves out.

`ProfileData` shows an event's own stats.  On the TPU the op's
``op_name`` (the scope the program put on it) is not among them: the
runtime keeps it once per HLO instruction, in the stats of the
event's *metadata* (``XEventMetadata.stats`` of the ``XSpace``
proto), which `ProfileData` does not expose (PERF.md, PR 25).  So
this module reads the ``.xplane.pb`` itself — the protobuf wire
format, the dozen fields named below, nothing imported — and hands
back the shape `chipbench.trace` and the readers already walk
(``planes`` / ``lines`` / ``events`` with ``name``, ``start_ns``,
``duration_ns``, ``stats``), an event's stats followed by its
metadata's.

Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``.
"""
from __future__ import annotations

import collections
import glob
import os
import struct

Event = collections.namedtuple('Event', 'name start_ns duration_ns stats')
Line = collections.namedtuple('Line', 'name events')
Plane = collections.namedtuple('Plane', 'name lines stats')
Space = collections.namedtuple('Space', 'planes')


def _varint(buf, i):
  out = shift = 0
  while True:
    b = buf[i]
    i += 1
    out |= (b & 0x7F) << shift
    if not b & 0x80:
      return out, i
    shift += 7


def _fields(buf):
  """``(field number, wire type, value)`` of one message; a
  length-delimited value is a `memoryview` of its bytes."""
  i, n = 0, len(buf)
  while i < n:
    key, i = _varint(buf, i)
    num, wire = key >> 3, key & 7
    if wire == 0:
      val, i = _varint(buf, i)
    elif wire == 1:
      val, i = bytes(buf[i:i + 8]), i + 8
    elif wire == 2:
      size, i = _varint(buf, i)
      val, i = buf[i:i + size], i + size
    elif wire == 5:
      val, i = bytes(buf[i:i + 4]), i + 4
    else:
      raise ValueError(f'wire type {wire} at byte {i}')
    yield num, wire, val


def _signed(v: int) -> int:
  return v - (1 << 64) if v >= 1 << 63 else v


def _text(view) -> str:
  return bytes(view).decode('utf-8', 'replace')


def _stat(buf, stat_names):
  """``(name, value)`` of one XStat; a ``ref_value`` names another
  stat-metadata entry whose name is the string."""
  name, value = None, None
  for num, wire, val in _fields(buf):
    if num == 1:
      name = stat_names.get(val, str(val))
    elif num == 2:
      value = struct.unpack('<d', val)[0]
    elif num == 3:
      value = val
    elif num == 4:
      value = _signed(val)
    elif num == 5:
      value = _text(val)
    elif num == 6:
      value = bytes(val)
    elif num == 7:
      value = stat_names.get(val, str(val))
  return name, value


def _map_entry(buf):
  key = value = None
  for num, _, val in _fields(buf):
    if num == 1:
      key = val
    elif num == 2:
      value = val
  return key, value


def _plane(buf) -> Plane:
  name, lines, event_meta, stat_meta, stats = '', [], {}, {}, []
  for num, _, val in _fields(buf):
    if num == 2:
      name = _text(val)
    elif num == 3:
      lines.append(val)
    elif num == 4:
      k, v = _map_entry(val)
      event_meta[k] = v
    elif num == 5:
      k, v = _map_entry(val)
      stat_meta[k] = v
    elif num == 6:
      stats.append(val)
  stat_names = {}
  for k, v in stat_meta.items():
    for num, _, val in _fields(v):
      if num == 2:
        stat_names[k] = _text(val)
  metas = {}
  for k, v in event_meta.items():
    ename, display, mstats = '', '', []
    for num, _, val in _fields(v):
      if num == 2:
        ename = _text(val)
      elif num == 4:
        display = _text(val)
      elif num == 5:
        mstats.append(_stat(val, stat_names))
    metas[k] = (ename or display, tuple(mstats))
  out = []
  for lbuf in lines:
    lname, display, t0_ns, events = '', '', 0, []
    for num, _, val in _fields(lbuf):
      if num == 2:
        lname = _text(val)
      elif num == 11:
        display = _text(val)
      elif num == 3:
        t0_ns = _signed(val)
      elif num == 4:
        events.append(val)
    evs = []
    for ebuf in events:
      meta_id, offset_ps, duration_ps, estats = 0, 0, 0, []
      for num, _, val in _fields(ebuf):
        if num == 1:
          meta_id = val
        elif num == 2:
          offset_ps = _signed(val)
        elif num == 3:
          duration_ps = _signed(val)
        elif num == 4:
          estats.append(_stat(val, stat_names))
      ename, mstats = metas.get(meta_id, (str(meta_id), ()))
      evs.append(Event(ename, t0_ns + offset_ps / 1e3, duration_ps / 1e3,
                       tuple(estats) + mstats))
    out.append(Line(lname or display, evs))
  return Plane(name, out, tuple(_stat(s, stat_names) for s in stats))


def parse(data: bytes) -> Space:
  """An ``XSpace``, serialized, as planes of lines of events."""
  return Space([_plane(val) for num, _, val in _fields(memoryview(data))
                if num == 1])


def load(trace_dir: str) -> Space:
  """The newest ``.xplane.pb`` under ``trace_dir``."""
  found = sorted(glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                           recursive=True))
  if not found:
    raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
  with open(found[-1], 'rb') as f:
    return parse(f.read())
