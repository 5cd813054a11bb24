"""One compile-if-stale helper for every native component.

All the runtime's C++ pieces (src/engine_native.cc, io_native.cc,
image_native.cc, predict_api.cc) share the same lifecycle: compile on first
use with the system toolchain, cache under build/, rebuild when the source
or the compile command changed, degrade (return None, with one logged
warning carrying the compiler's message) when the toolchain or the compile
fails. The publish is atomic (temp file + os.replace) so concurrent
processes never dlopen a half-written .so.

Freshness is a hash of the source bytes and the compile command, stored
beside the library (``<lib>.key``) — not mtimes: ``build/`` is git-ignored
and gets copied between machines and checkouts, and a copied ``.so`` must
never stand in for a ``src/*.cc`` it was not built from.
"""
from __future__ import annotations

import hashlib
import logging
import os
import subprocess

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BUILD_DIR = os.path.join(_ROOT, "build")


def source_path(name):
    return os.path.join(_ROOT, "src", name)


def build_lib(src, libname, extra_flags=(), opt="-O2"):
    """Compile ``src`` (absolute path) into build/<libname> unless the
    library there was built from exactly this source and command. Returns
    the .so path, or None when the toolchain/compile fails."""
    out = os.path.join(_BUILD_DIR, libname)
    keyfile = out + ".key"
    cmd = ["g++", "-std=c++17", opt, "-shared", "-fPIC", "-pthread", src,
           *extra_flags]
    try:
        with open(src, "rb") as f:
            key = hashlib.sha256(
                f.read() + b"\0" + "\0".join(cmd).encode()).hexdigest()
        try:
            with open(keyfile) as f:
                if f.read() == key and os.path.isfile(out):
                    return out
        except FileNotFoundError:
            pass
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = "%s.%d.tmp" % (out, os.getpid())
        subprocess.run(cmd + ["-o", tmp], check=True, capture_output=True)
        os.replace(tmp, out)
        with open(tmp, "w") as f:
            f.write(key)
        os.replace(tmp, keyfile)
        return out
    except (OSError, subprocess.CalledProcessError) as exc:
        detail = getattr(exc, "stderr", b"") or b""
        logging.getLogger("mxnet_tpu").warning(
            "native build of %s failed (%s)%s — the pure-Python path runs "
            "instead", libname, exc,
            ": " + detail.decode(errors="replace")[-400:] if detail else "")
        return None
