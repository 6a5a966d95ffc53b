"""Build the native IO runtime: g++ -O3 -shared -fPIC -> libdl4jtpu_io.so.

Run as `python -m deeplearning4j_tpu.native.build` or let
`deeplearning4j_tpu.native.load()` build lazily on first use.

The library is a build output, not a committed file (`.gitignore`): a fresh
checkout builds it. Whether an existing library is current is decided by a
hash of the source kept beside it — file times say nothing in a checkout,
where every file is as old as the moment it was unpacked.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import sys
import tempfile

from ..util.fs import atomic_write, publish_file

_HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(_HERE, "src", "dl4jtpu_io.cpp")
LIB = os.path.join(_HERE, "libdl4jtpu_io.so")
STAMP = LIB + ".srchash"


def _source_hash():
    with open(SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _built_from(digest):
    if not os.path.exists(LIB):
        return False
    try:
        with open(STAMP) as f:
            return f.read().strip() == digest
    except OSError:
        return False        # a library with no stamp was not built from here


def build(force=False):
    """Compile the shared library unless one built from this very source is
    already there. Returns the .so path, or None when no C++ toolchain is
    available. Library and stamp are renamed into place, so concurrent
    builders (test workers on a fresh checkout) never load a half-written
    file."""
    digest = _source_hash()
    if not force and _built_from(digest):
        return LIB
    fd, tmp = tempfile.mkstemp(prefix=".libdl4jtpu_io.", suffix=".so",
                               dir=_HERE)
    os.close(fd)
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        publish_file(tmp, LIB)
        atomic_write(STAMP, (digest + "\n").encode())
    except FileNotFoundError:
        return None  # no g++ on this machine; Python fallbacks stay active
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native build failed:\n{e.stderr.decode()}") from e
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return LIB


if __name__ == "__main__":
    out = build(force="--force" in sys.argv)
    print(out or "no C++ toolchain found; skipped")
