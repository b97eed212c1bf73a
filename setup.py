"""Build hook: compile the native host runtime before packaging.

The reference builds a torch cpp_extension wheel (`setup.py:26-74`
there); here the native layer is a plain shared library (ctypes-bound,
no torch/pybind11 dependency) built by `csrc/Makefile` and shipped as
package data.  `pip install .` compiles it when a toolchain exists;
without one the package installs without the library, and
`graphlearn_tpu.native` builds it from `csrc/` on first use (device
paths never need it).  No binary is checked in.
"""
import subprocess
from pathlib import Path

from setuptools import setup
from setuptools.command.build_py import build_py


class BuildWithNative(build_py):
  def run(self):
    root = Path(__file__).resolve().parent
    try:
      subprocess.run(['make', '-C', str(root / 'csrc')], check=True)
    except (OSError, subprocess.CalledProcessError) as e:
      print(f'[graphlearn-tpu] native build skipped ({e}); '
            'graphlearn_tpu.native will build on first use')
    super().run()


setup(cmdclass={'build_py': BuildWithNative})
