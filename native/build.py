"""Build the native libraries: ``python native/build.py``.

Produces ``native/libdk_transport.so`` (framed-socket data plane used by
:mod:`distkeras_tpu.networking`) and ``native/libdk_dataio.so`` (shard IO
kernels used by :mod:`distkeras_tpu.data.shard_io`). The libraries are
not tracked by git: both consumers call :func:`ensure_lib` on first use,
which (re)builds whenever the library is missing or was not built from
the ``.c`` file that lies next to it now (a hash of the source is
stamped beside the library at build time). Without a compiler the
consumers warn and run their pure-Python implementations.
"""

import hashlib
import os
import shutil
import subprocess
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))

LIBS = {
    "libdk_transport.so": "dk_transport.c",
    "libdk_dataio.so": "dk_dataio.c",
}


def _cc():
    cc = os.environ.get("CC") or shutil.which("cc") or shutil.which("gcc") \
        or shutil.which("clang")
    if cc is None:
        raise RuntimeError("no C compiler found")
    return cc


def _source_hash(lib_name: str) -> str:
    with open(os.path.join(HERE, LIBS[lib_name]), "rb") as fh:
        return hashlib.sha256(fh.read()).hexdigest()


def build_lib(lib_name: str, quiet: bool = False) -> str:
    src = os.path.join(HERE, LIBS[lib_name])
    out = os.path.join(HERE, lib_name)
    # build beside the target and rename into place: several processes
    # (test workers) and several threads of one (a server's and its
    # client's first frames) may find the library stale at the same moment
    mine = f"{out}.{os.getpid()}.{threading.get_ident()}"
    tmp = mine + ".tmp"
    cmd = [_cc(), "-O2", "-shared", "-fPIC", "-o", tmp, src]
    try:
        subprocess.run(cmd, check=True, capture_output=quiet)
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    with open(mine + ".stamp", "w") as fh:
        fh.write(_source_hash(lib_name))
    os.replace(fh.name, out + ".sha256")
    return out


def ensure_lib(lib_name: str, quiet: bool = True) -> str:
    """Path of ``lib_name`` built from the source that is on disk now:
    a missing library, a missing stamp or a stamp that differs from the
    source's hash all rebuild. Raises when the build fails."""
    out = os.path.join(HERE, lib_name)
    try:
        with open(out + ".sha256") as fh:
            fresh = (os.path.exists(out)
                     and fh.read().strip() == _source_hash(lib_name))
    except OSError:
        fresh = False
    return out if fresh else build_lib(lib_name, quiet=quiet)


def build_all(quiet: bool = False):
    return [build_lib(name, quiet=quiet) for name in LIBS]


if __name__ == "__main__":
    for path in build_all():
        print(path)
    sys.exit(0)
