"""Build helper for libmxtpu_predict.so (src/predict_api.cc).

The .so embeds CPython and calls mxnet_tpu.predictor — C/C++ applications
link against it plus include/mxtpu/c_predict_api.h, the reference's
c_predict_api surface. Compiled on demand with the system toolchain and
cached under build/ like the other native components.
"""
from __future__ import annotations

import os
import sys
import sysconfig
import threading

from ._native_build import build_lib, source_path

__all__ = ["build", "lib_path"]

_SRC = source_path("predict_api.cc")
_lock = threading.Lock()


def lib_path():
    from ._native_build import _BUILD_DIR

    return os.path.join(_BUILD_DIR, "libmxtpu_predict.so")


def build():
    """Compile (if stale) and return the .so path; None if no toolchain."""
    with _lock:
        inc = sysconfig.get_paths()["include"]
        libdir = sysconfig.get_config_var("LIBDIR")
        pyver = "python%d.%d" % sys.version_info[:2]
        return build_lib(_SRC, "libmxtpu_predict.so",
                         extra_flags=["-I", inc, "-L", libdir, "-l", pyver])
