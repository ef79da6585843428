"""Locate (and build) the C libraries under ``native/``.

``native/`` is a directory of sources beside the package, not a package
itself, and its ``.so`` files are not tracked by git — a checkout, or a
copy of the tree made for a run on the chip, builds them on first use.
"""

from __future__ import annotations

import importlib.util
import os
import subprocess

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")

# what a failed build raises (no compiler, compile error, unwritable dir)
BuildError = (OSError, RuntimeError, subprocess.CalledProcessError)


def ensure_lib(lib_name: str) -> str:
    """Path of ``native/<lib_name>``, rebuilt first when it is missing
    or does not match its ``.c`` source (``native/build.py`` ·
    ``ensure_lib``). Raises one of :data:`BuildError` when it cannot be
    built."""
    spec = importlib.util.spec_from_file_location(
        "_dk_native_build", os.path.join(_NATIVE_DIR, "build.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.ensure_lib(lib_name)
