"""The benchmark: three training cells on the v5e, driven by data.

Everything a later PR may not move lives here: data generation, the
window, the plain reference and the comparison that decides
``correct``, the work counts, the peaks and the trace reduction.  See
`PERF.md` ("How to add a cell / a metric") for the lookup by name.
"""
import importlib.util
import os
import re
import sys


def load_file(path: str):
  """The module a python file of a benchmark root defines.  What a
  later PR brings as a new file — a reader
  (`layer_metrics/<reader>.py`), a driver (`builders/<builder>.py`) and
  what such a file keeps beside itself — is found by its path and
  loaded once per path, so that a file's jitted functions and caches
  live as long as an imported module's."""
  path = os.path.abspath(path)
  name = 'chipbench_file_' + re.sub(r'\W', '_', path)
  if name not in sys.modules:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
      spec.loader.exec_module(mod)
    except BaseException:
      del sys.modules[name]
      raise
  return sys.modules[name]


def beside(file: str, name: str):
  """`load_file` of ``<name>.py`` in the directory of ``file``: how a
  driver file reaches its data builder and its copy of the plain
  reference (``beside(__file__, 'typed_reference')``)."""
  return load_file(os.path.join(os.path.dirname(os.path.abspath(file)),
                                name + '.py'))
