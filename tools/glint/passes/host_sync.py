"""host-sync: no host synchronization inside the hot dispatch path.

The fused epoch drivers win by enqueuing the next compiled program
*before* the previous one finishes (dispatch-ahead, `loader/fused.py`
/ `parallel/fused.py`); the serving tier's warm executables and the
mesh epoch drivers share the property.  One `jax.device_get`,
`.item()`, `.block_until_ready()`, `np.asarray`-on-a-device-value or
tracer-`bool` inside that path stalls the pipeline silently — the
code stays correct, throughput dies, and nothing fails (the
PyTorch-Direct lineage in PAPERS.md depends on the same never-sync
contract in its overlapped window).

Hot scope = the transitive closure, within one file, of:
  * functions handed to ``_counted_jit(...)`` / ``jax.jit(...)``
    (by local name or ``self.<method>`` reference);
  * ``jax.lax.scan`` body callables (named or lambda);
  * functions decorated ``@jax.jit`` (bare or ``partial(jax.jit,..)``);
  * same-file functions *called* from a hot function by simple name.

Banned inside a hot scope: ``jax.device_get``, ``.item()``,
``.block_until_ready()``, ``np.asarray`` / ``np.array`` /
``np.copy``, ``bool(...)`` on a traced value, and host clocks
(``time.time`` / ``time.monotonic`` / ``time.perf_counter`` — traced
ONCE at compile time, so the recorded "duration" is a compile-time
constant, a silent telemetry lie).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Set

from ..context import terminal_name as _terminal
from ..findings import Finding
from ..registry import GlintPass, register

#: calls that make a jitted/scanned function hot, keyed by the
#: terminal segment of the callee's qualname
_JIT_WRAPPERS = {'_counted_jit', 'jit'}
#: control-flow primitives -> positional indices of their traced
#: callables (scan(body, ...); while_loop(cond, body, ...);
#: fori_loop(lo, hi, body, ...))
_SCAN_CALLEES = {
    'jax.lax.scan': (0,), 'lax.scan': (0,),
    'jax.lax.while_loop': (0, 1), 'lax.while_loop': (0, 1),
    'jax.lax.fori_loop': (2,), 'lax.fori_loop': (2,),
}

_BANNED_QUAL = {
    'jax.device_get': 'forces a device→host transfer + sync',
    'numpy.asarray': 'materializes a device value on host (sync)',
    'numpy.array': 'materializes a device value on host (sync)',
    'numpy.copy': 'materializes a device value on host (sync)',
    'time.time': 'host clock is traced ONCE at compile time — the '
                 'value is a compile-time constant, not a timestamp',
    'time.monotonic': 'host clock is traced ONCE at compile time',
    'time.perf_counter': 'host clock is traced ONCE at compile time',
}
_BANNED_METHODS = {
    'item': '.item() blocks on the device value',
    'block_until_ready': 'explicit device sync',
    'tolist': '.tolist() blocks on the device value',
}


@register
class HostSyncPass(GlintPass):
  name = 'host-sync'
  description = ('no device_get/.item()/block_until_ready/np.asarray/'
                 'host clocks inside jitted or scanned (hot-path) '
                 'functions')

  def check_file(self, ctx):
    tree = ctx.tree
    defs: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(tree):
      if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        defs.setdefault(node.name, []).append(node)

    hot: Set[ast.AST] = set()

    def mark(name: str) -> None:
      for d in defs.get(name, ()):
        hot.add(d)

    for node in ast.walk(tree):
      if isinstance(node, ast.Call):
        qn = ctx.qualname(node.func)
        term = _terminal(node.func)
        if (term in _JIT_WRAPPERS
            and (term == '_counted_jit' or qn in ('jax.jit', 'jit'))
            and node.args):
          arg = node.args[0]
          if isinstance(arg, ast.Lambda):
            hot.add(arg)
          else:
            mark(_terminal(arg))
        elif qn in _SCAN_CALLEES:
          for idx in _SCAN_CALLEES[qn]:
            if idx >= len(node.args):
              continue
            arg = node.args[idx]
            if isinstance(arg, ast.Lambda):
              hot.add(arg)
            else:
              mark(_terminal(arg))
      elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        for dec in node.decorator_list:
          target = dec.func if isinstance(dec, ast.Call) else dec
          qn = ctx.qualname(target)
          if qn in ('jax.jit', 'jit'):
            hot.add(node)
          elif qn in ('functools.partial', 'partial') \
              and isinstance(dec, ast.Call) and dec.args \
              and ctx.qualname(dec.args[0]) in ('jax.jit', 'jit'):
            hot.add(node)

    # transitive closure: same-file functions called from a hot scope
    # are traced into the same program
    changed = True
    while changed:
      changed = False
      for fn in list(hot):
        for node in ast.walk(fn):
          if isinstance(node, ast.Call):
            callee = _terminal(node.func)
            for d in defs.get(callee, ()):
              if d not in hot:
                hot.add(d)
                changed = True

    # report banned operations inside any hot scope (dedup nodes that
    # sit inside several nested hot functions)
    seen: Set[ast.AST] = set()
    for fn in hot:
      for node in ast.walk(fn):
        if not isinstance(node, ast.Call) or node in seen:
          continue
        seen.add(node)
        qn = ctx.qualname(node.func)
        why = _BANNED_QUAL.get(qn)
        if why is None and isinstance(node.func, ast.Attribute) \
            and node.func.attr in _BANNED_METHODS and not node.args:
          why = _BANNED_METHODS[node.func.attr]
          qn = f'.{node.func.attr}()'
        if why is None and qn == 'bool' and node.args:
          why = ('bool() on a traced value concretizes (sync or '
                 'TracerBoolConversionError)')
        if why is None:
          continue
        host = fn.name if hasattr(fn, 'name') else '<lambda>'
        yield Finding(
            rule=self.name, path=ctx.rel, line=node.lineno,
            message=f'{qn} inside hot-path function {host!r} — {why}; '
                    'hoist it out of the jitted/scanned scope (or '
                    'return the value through scan outputs)')
