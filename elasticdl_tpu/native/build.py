"""Build the native library: ``python -m elasticdl_tpu.native.build``."""

import os
import subprocess
import sys
import tempfile

_DIR = os.path.dirname(os.path.abspath(__file__))


def build(verbose=True):
    """Compile the library and put it in place whole: g++ writes under
    a temporary name in the same directory and ``os.replace`` renames
    it, so a process that finds ``libedl_native.so`` (several test
    workers build and load at once on a fresh checkout) never loads a
    half-written one."""
    sources = [
        os.path.join(_DIR, "recordio_reader.cc"),
        os.path.join(_DIR, "recordio_writer.cc"),
    ]
    out = os.path.join(_DIR, "libedl_native.so")
    fd, tmp = tempfile.mkstemp(
        dir=_DIR, prefix="libedl_native.", suffix=".so"
    )
    os.close(fd)
    cmd = [
        "g++",
        "-O2",
        "-shared",
        "-fPIC",
        "-std=c++17",
        *sources,
        "-lz",
        "-o",
        tmp,
    ]
    if verbose:
        print(" ".join(cmd))
    try:
        subprocess.check_call(cmd)
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return out


if __name__ == "__main__":
    build()
    sys.exit(0)
