"""Build the native host runtime: ``python -m deeplearning4j_tpu.native.build``.

g++ -O3 shared library; no external deps.  The library is optional — all
call sites fall back to pure Python when it is absent.

The library's file name carries a hash of its source and compiler flags.
Whether a binary is fresh is therefore a question of content, never of
mtimes (a copied tree keeps none), and no ``-march=native``: a binary built
on one host must not fault on another.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).parent
SRC = HERE / "src" / "host_runtime.cpp"
FLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17")


def lib_path() -> Path:
    """Where the library built from the CURRENT source lives."""
    digest = hashlib.sha256(
        SRC.read_bytes() + " ".join(FLAGS).encode()).hexdigest()[:16]
    return HERE / f"libdl4jtpu_host.{digest}.so"


def build(verbose: bool = True) -> Path | None:
    lib = lib_path()
    # compile beside the target, then rename: concurrent builders (test
    # workers) each publish a complete file or nothing
    tmp = lib.with_name(f"{lib.stem}.tmp{os.getpid()}.so")
    cmd = ["g++", *FLAGS, str(SRC), "-o", str(tmp)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (FileNotFoundError, subprocess.TimeoutExpired) as e:
        if verbose:
            print(f"native build unavailable: {e}", file=sys.stderr)
        return None
    if proc.returncode != 0:
        tmp.unlink(missing_ok=True)
        if verbose:
            print(f"native build failed:\n{proc.stderr}", file=sys.stderr)
        return None
    os.replace(tmp, lib)
    for old in HERE.glob("libdl4jtpu_host*.so"):
        if old != lib and ".tmp" not in old.name:
            old.unlink(missing_ok=True)   # built from another source
    if verbose:
        print(f"built {lib}")
    return lib


if __name__ == "__main__":
    sys.exit(0 if build() else 1)
