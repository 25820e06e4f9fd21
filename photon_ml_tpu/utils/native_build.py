"""On-demand g++ builds of the ``native/*.cpp`` helpers, content-addressed.

Each library is compiled to ``native/build/lib<name>-<sha>.so`` where
``<sha>`` hashes the ``.cpp`` source (and the extra link arguments): a
library on disk is only ever loaded when it was built from exactly the
source in this checkout. ``native/build/`` is git-ignored, so a fresh
checkout builds on first use; a tree copied with a build directory from
other source cannot satisfy the name.

The three ctypes bindings (ops/tiled_sparse, io/native_avro,
utils/native_index) keep their own fallbacks for hosts without a
toolchain; :func:`report` says which way each went, so a run that must
not fall back (chip_smoke.py) can check.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import threading
from typing import Dict, Sequence

from photon_ml_tpu.utils.backend import REPO_ROOT

_SRC_DIR = os.path.join(REPO_ROOT, "native")
_LIB_DIR = os.path.join(_SRC_DIR, "build")
_LOCK = threading.Lock()
# name -> "built" | "cached" | "unavailable: <why>"
_OUTCOMES: Dict[str, str] = {}


def library_path(name: str, opt: str = "-O2", link: Sequence[str] = ()) -> str:
    """Path of the shared library for ``native/<name>.cpp``, compiling it
    first when no library built from this exact source exists. Raises
    (``OSError`` / ``CalledProcessError``) when the toolchain is missing
    or the compile fails; the failure is recorded for :func:`report`."""
    src = os.path.join(_SRC_DIR, f"{name}.cpp")
    with _LOCK:
        try:
            with open(src, "rb") as f:
                digest = hashlib.sha256(f.read())
            digest.update(" ".join((opt, *link)).encode())
            lib = os.path.join(
                _LIB_DIR, f"lib{name}-{digest.hexdigest()[:16]}.so"
            )
            if os.path.isfile(lib):
                _OUTCOMES.setdefault(name, "cached")
                return lib
            os.makedirs(_LIB_DIR, exist_ok=True)
            # compile to a temp path + atomic rename so another process
            # never dlopens a half-written .so
            tmp = f"{lib}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", opt, "-shared", "-fPIC", "-std=c++17", src,
                 "-o", tmp, *link],
                check=True,
                capture_output=True,
            )
            os.replace(tmp, lib)
            _OUTCOMES[name] = "built"
            return lib
        except (OSError, subprocess.CalledProcessError) as e:
            _OUTCOMES[name] = f"unavailable: {type(e).__name__}: {e}"[:300]
            raise


def report() -> Dict[str, str]:
    """``{name: "built" | "cached" | "unavailable: ..."}`` for every
    native library this process has asked for so far."""
    with _LOCK:
        return dict(_OUTCOMES)
