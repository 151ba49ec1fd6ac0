"""Native (C++) components, built on demand with g++ and bound via ctypes.

The reference's JVM-external native layer (netlib BLAS, libxgboost JNI —
SURVEY §2.8) maps here: host-side runtime pieces that don't belong on the
TPU compute path get real native implementations, compiled once into
``_build/`` next to this file and loaded with ctypes. Every binding must
keep a pure-Python fallback so the framework works where no toolchain
exists.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional

_BUILD_LOCK = threading.Lock()
_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_DIR, "_build")

#: library name -> source file (in this dir)
LIBRARIES = {"texthash": "text_hashing.cpp",
             "shist": "streaming_histogram.cpp",
             "dictenc": "dict_encode.cpp"}


def build_and_load(lib_name: str) -> Optional[ctypes.CDLL]:
    """Compile ``LIBRARIES[lib_name]`` and dlopen it. The output is
    ``_build/lib<name>-<sha256 of the source>.so``: the library loaded is
    always built from the source file as it stands — a git-ignored ``.so``
    left by another checkout state can never win over the committed
    ``.cpp``. Returns None when compilation fails (no toolchain, sandbox,
    ...) — callers fall back to Python."""
    src = os.path.join(_DIR, LIBRARIES[lib_name])
    with _BUILD_LOCK:
        try:
            with open(src, "rb") as fh:
                digest = hashlib.sha256(fh.read()).hexdigest()[:16]
            out = os.path.join(_BUILD_DIR, f"lib{lib_name}-{digest}.so")
            if not os.path.exists(out):
                os.makedirs(_BUILD_DIR, exist_ok=True)
                # build beside the target and rename: a concurrent process
                # (replica workers start together) never dlopens a
                # half-written file
                fd, tmp = tempfile.mkstemp(dir=_BUILD_DIR, suffix=".so.tmp")
                os.close(fd)
                try:
                    subprocess.run(
                        ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                         "-o", tmp, src],
                        check=True, capture_output=True, timeout=120)
                    os.replace(tmp, out)
                finally:
                    if os.path.exists(tmp):
                        os.remove(tmp)
            return ctypes.CDLL(out)
        except (OSError, subprocess.SubprocessError):
            # failure-ok: native lib is optional; python fallback
            return None


def library_states() -> dict[str, str]:
    """``{library: "native" | "python"}`` — builds/loads every library of
    :data:`LIBRARIES` so a smoke run can say which implementation each
    host-side component would take here."""
    return {name: "native" if build_and_load(name) is not None else "python"
            for name in LIBRARIES}
