"""Tracing, profiling and lightweight metrics.

The reference has NO tracing/profiling subsystem (SURVEY §5: wall-clock
prints in benchmarks only) — this module is deliberately beyond parity:

  * :func:`layer_scope` — the package's one named scope:
    ``glt.<layer>[/<part>]`` on every device op a jitted program
    traces under it, so a profiler trace of the timed program reads
    by layer (:data:`LAYERS` is the whole vocabulary);
  * :func:`start_trace` / :func:`stop_trace` — capture an xprof trace
    directory viewable in TensorBoard's profile plugin;
  * :class:`Metrics` — process-local counters/timers the loaders and
    channels tick (batches produced, edges sampled, bytes moved), with
    a one-line JSON snapshot for logs and the bench harness.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from typing import Dict, Iterator, Optional, Tuple

import jax


class Metrics:
  """Thread-safe counter/timer registry.

  >>> metrics.inc('loader.batches')
  >>> with metrics.timer('sampler.one_hop'):
  ...   ...
  >>> metrics.snapshot()
  {'loader.batches': 1, 'sampler.one_hop.secs': 0.01, ...}
  """

  def __init__(self):
    self._lock = threading.Lock()
    self._counts: Dict[str, float] = {}

  def inc(self, name: str, value: float = 1.0) -> None:
    with self._lock:
      self._counts[name] = self._counts.get(name, 0) + value

  def inc_many(self, pairs) -> None:
    """Apply several increments under ONE lock acquisition, so a
    concurrent `snapshot` sees all of them or none.  This is what
    keeps a multi-key encoding (the log2 histogram's bucket + count +
    secs triple) tear-free under a live scrape: a snapshot taken
    between two plain `inc` calls would show ``count != sum(buckets)``.
    """
    with self._lock:
      for name, value in pairs:
        self._counts[name] = self._counts.get(name, 0) + value

  @contextlib.contextmanager
  def timer(self, name: str) -> Iterator[None]:
    t0 = time.perf_counter()
    try:
      yield
    finally:
      dt = time.perf_counter() - t0
      self.inc(f'{name}.secs', dt)
      self.inc(f'{name}.calls')

  def snapshot(self) -> Dict[str, float]:
    with self._lock:
      return dict(self._counts)

  def reset(self) -> None:
    with self._lock:
      self._counts.clear()

  def dump(self) -> str:
    return json.dumps(
        {k: round(v, 6) for k, v in sorted(self.snapshot().items())})


#: process-global registry (the reference has none; loaders tick this)
metrics = Metrics()


#: every layer a device op can belong to.  A trace is reduced by the
#: first ``glt.<layer>`` token of an op's ``op_name`` (bare, or inside
#: ``jvp(...)`` / ``transpose(jvp(...))`` for the backward pass), so a
#: layer that is not here cannot be read back: `layer_scope` refuses it.
#:
#: Parts, one spelling each (``<rel>`` is `typing.as_str` of a
#: relation, ``a__rel__b``; the typed programs name a relation or a
#: node type where the homogeneous ones have one of each):
#:
#:   sample    hop<i> (a hop's draw) | hop<i>/<rel> (typed: one
#:             relation's draw and its dedup into the found type's
#:             table) | hop<i>/frontier | dedup | pack | key | owner |
#:             negative
#:   gather    level<t> | <node type> (typed `Feature.get`) | labels |
#:             owner | mask | ids | split; bare in the homogeneous
#:             `Feature.get`
#:   model     input | layer<l> | layer<l>/<rel> (typed: one relation's
#:             convolution) | layer<l>/merge (the sum into the target
#:             types and the activation) | layer<l>/trim (typed: the
#:             prefixes a trimmed layer reads) | head | loss | metrics
#:   optimizer (none)
#:   exchange  frontier | feature | pairs | grads (the DP update's
#:             collectives: the gradient and loss means and the valid
#:             seed count that gates the update)
#:   telemetry attribution (`parallel.exchange.dest_histogram`) |
#:             exchange (a plan's offered / dropped sums and the stats
#:             tail they are stacked into) | hops (per-hop valid
#:             counts and their psum) | fill (`FusedLinkEpoch`'s batch
#:             fill) | cold (the replica cache's local-hit count) |
#:             negative (strict-negative slots lost)
#:
#: ``telemetry`` holds the counts a program computes only so that it
#: can report them; work the step itself reads stays in its layer.  A
#: telemetry scope is never opened inside another ``glt.*`` scope: the
#: first token claims the op, so a nested one would be booked to the
#: layer around it.
LAYERS: Tuple[str, ...] = ('sample', 'gather', 'model', 'optimizer',
                           'exchange', 'telemetry')


def layer_scope(layer: str, part: Optional[str] = None):
  """``jax.named_scope('glt.<layer>[/<part>]')``: HLO metadata on the
  ops traced inside, nothing at run time.  ``part`` is free text
  (``hop0``, ``level2``, ``dedup``); ``layer`` is one of `LAYERS`."""
  if layer not in LAYERS:
    raise ValueError(f'unknown layer {layer!r}; the vocabulary is '
                     f'{LAYERS}')
  return jax.named_scope(f'glt.{layer}/{part}' if part
                         else f'glt.{layer}')


def start_trace(log_dir: str) -> None:
  """Begin an xprof capture (TensorBoard profile plugin format)."""
  jax.profiler.start_trace(log_dir)


def stop_trace() -> None:
  jax.profiler.stop_trace()


@contextlib.contextmanager
def capture(log_dir: str) -> Iterator[None]:
  """Trace a whole block: ``with capture('/tmp/xprof'): train()``."""
  start_trace(log_dir)
  try:
    yield
  finally:
    stop_trace()


@contextlib.contextmanager
def step_annotation(name: str, step_num: int) -> Iterator[None]:
  """xprof STEP marker (`jax.profiler.StepTraceAnnotation`): dispatches
  wrapped in this show up as numbered steps on the TensorBoard profile
  timeline.  The fused epoch drivers wrap each program dispatch so a
  `--trace-dir` capture segments by epoch/chunk."""
  with jax.profiler.StepTraceAnnotation(name, step_num=int(step_num)):
    yield
