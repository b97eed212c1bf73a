"""Persistent AOT executable cache for the serving bucket ladder
(ISSUE 13 tentpole, ROADMAP item 2b).

Fused serve programs take tens of seconds each to compile and the
bucket ladder holds several of them — so the dominant cost of replacing a
lost replica, or scaling one out, is not process start but the warmup
recompile of executables that are BYTE-IDENTICAL to what every other
replica already runs.  This cache persists each bucket's compiled
executable to ``GLT_AOT_CACHE_DIR`` keyed by a full program
fingerprint — (program name, bucket capacity, graph/feature/model
signature, engine seed, abstract arg signature, device set, jax
version) — so a restarted or autoscaled replica deserializes the
ladder from disk in seconds.

Durability discipline (the `SnapshotManager` rules, PR 6):

  * **atomic publish** — entries are written to a same-directory tmp
    file and ``os.replace``'d into place, so a concurrent reader (or
    a second replica warming from the same shared directory) sees
    either the whole entry or none of it, never a torn write;
  * **corrupt-entry skip-to-recompile** — every entry carries a
    sha256 of its serialized-executable payload; an unpicklable file,
    a checksum mismatch, or a deserialization failure falls back to a
    recompile (one ``aot.cache_miss`` event with the reason), NEVER a
    crash and never a wrong executable;
  * **stale-entry skip** — the stored fingerprint is compared field-
    for-field against the requested one (a key collision, a jax
    upgrade, a changed graph) and a mismatch recompiles;
  * **write failures absorbed** — a failed save (disk full, chaos
    ``aot.cache:fail``) costs the NEXT process a compile, this one
    nothing.

Chaos site ``aot.cache`` (``op='save'``/``'load'``): ``fail`` raises
into the absorbing arms above; ``corrupt`` scrambles the payload
before publish, so a later load exercises the checksum path against a
real durable bad entry.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional

AOT_CACHE_DIR_ENV = 'GLT_AOT_CACHE_DIR'

#: entry format version — bumped on layout change, stale-skips old files
#: (2: entries name the devices their executable runs on)
_FORMAT = 2


def cache_dir_from_env() -> Optional[str]:
  d = os.environ.get(AOT_CACHE_DIR_ENV)
  return d if d else None


def from_env() -> Optional['AotExecutableCache']:
  """The process's cache, or None when ``GLT_AOT_CACHE_DIR`` is unset
  (the default: serving warmup compiles exactly as before)."""
  d = cache_dir_from_env()
  return AotExecutableCache(d) if d else None


def fingerprint_key(fingerprint: Dict[str, Any]) -> str:
  """Stable file-name key for one fingerprint dict (sha256 over its
  sorted-key JSON — the fingerprint itself is ALSO stored in the
  entry and compared field-for-field on load, so a hash collision
  degrades to a stale-skip, not a wrong executable)."""
  import json
  blob = json.dumps(fingerprint, sort_keys=True, default=repr)
  return hashlib.sha256(blob.encode()).hexdigest()[:32]


def _tick_hit() -> None:
  from ..telemetry.live import live
  live.counter('aot.cache_hits_total').inc()


def _tick_miss() -> None:
  from ..telemetry.live import live
  live.counter('aot.cache_misses_total').inc()


class AotExecutableCache:
  """Directory of serialized XLA executables, one file per
  (fingerprint) entry, shared safely between concurrent replicas."""

  def __init__(self, root):
    self.root = Path(root)
    self.root.mkdir(parents=True, exist_ok=True)
    # memory accounting (ISSUE 17): on-disk executable bytes,
    # re-walked at scrape time (entries come and go between scrapes)
    from ..telemetry.memaccount import register_tier

    def _aot_bytes():
      try:
        return sum(p.stat().st_size
                   for p in self.root.glob('*.aotx'))
      except OSError:
        return 0

    register_tier('aot', _aot_bytes)

  def _path(self, key: str) -> Path:
    return self.root / f'{key}.aotx'

  # -- read side ------------------------------------------------------------
  def load(self, fingerprint: Dict[str, Any]) -> Optional[Callable]:
    """Deserialize the executable for ``fingerprint``; None on any
    absent/stale/corrupt/unreadable entry (one ``aot.cache_miss``
    event with the reason — the caller recompiles)."""
    from ..telemetry.recorder import recorder
    from ..testing import chaos
    key = fingerprint_key(fingerprint)
    program = fingerprint.get('program')
    bucket = fingerprint.get('cap')
    path = self._path(key)
    t0 = time.perf_counter()

    def miss(reason: str) -> None:
      recorder.emit('aot.cache_miss', program=program, bucket=bucket,
                    key=key, reason=reason)
      _tick_miss()

    try:
      chaos.aot_cache_faults('load')
      if not path.exists():
        miss('absent')
        return None
      rec = pickle.loads(path.read_bytes())
    except chaos.InjectedFault:
      miss('unreadable')
      return None
    except Exception:               # noqa: BLE001 — torn/garbage file
      miss('corrupt')
      return None
    try:
      if (not isinstance(rec, dict) or rec.get('format') != _FORMAT
          or rec.get('fingerprint') != fingerprint):
        miss('stale')
        return None
      payload = rec['payload']
      if hashlib.sha256(payload).hexdigest() != rec.get('sha256'):
        miss('corrupt')
        return None
      import jax
      from jax.experimental import serialize_executable
      # load onto the devices the program was compiled for: the
      # default is EVERY visible device, and an executable loaded
      # over eight devices refuses a one-device call
      by_id = {d.id: d for d in jax.devices()}
      fn = serialize_executable.deserialize_and_load(
          payload, rec['in_tree'], rec['out_tree'],
          execution_devices=[by_id[i] for i in rec['device_ids']])
    except Exception:               # noqa: BLE001 — bad payload,
      # moved jax internals, foreign device set: recompile, never
      # crash the warmup (and never run a questionable executable)
      miss('corrupt')
      return None
    recorder.emit('aot.cache_hit', program=program, bucket=bucket,
                  key=key, secs=round(time.perf_counter() - t0, 3))
    _tick_hit()
    return fn

  # -- write side -----------------------------------------------------------
  def save(self, fingerprint: Dict[str, Any], compiled) -> bool:
    """Serialize + atomically publish one compiled executable.
    Returns False (absorbing the error) on any failure — a cache that
    cannot write costs the next replica a compile, not this one its
    serving tier."""
    from ..testing import chaos
    key = fingerprint_key(fingerprint)
    path = self._path(key)
    tmp = path.with_name(f'{path.name}.tmp.{os.getpid()}')
    try:
      actions = chaos.aot_cache_faults('save')
      from jax.experimental import serialize_executable
      payload, in_tree, out_tree = serialize_executable.serialize(
          compiled)
      if 'corrupt' in actions:
        # durable bad entry: scramble AFTER the checksum is taken so
        # a later load sees a real integrity failure
        buf = bytearray(payload)
        buf[::7] = bytes((b ^ 0xFF) for b in buf[::7])
        payload_out = bytes(buf)
      else:
        payload_out = payload
      rec = {'format': _FORMAT, 'fingerprint': fingerprint,
             'sha256': hashlib.sha256(payload).hexdigest(),
             'payload': payload_out,
             'in_tree': in_tree, 'out_tree': out_tree,
             'device_ids': [d.id for d in compiled
                            .runtime_executable().local_devices()],
             'saved_at': time.time()}
      tmp.write_bytes(pickle.dumps(rec, protocol=5))
      os.replace(tmp, path)
      return True
    except Exception:               # noqa: BLE001 — absorbed
      try:
        tmp.unlink(missing_ok=True)
      except OSError:
        pass
      return False

  def entries(self) -> list:
    return sorted(p.name for p in self.root.glob('*.aotx'))
