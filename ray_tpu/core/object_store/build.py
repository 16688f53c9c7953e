"""Compile the shm store C++ extension on first use. The binary is never
committed; it is named after the hash of its source, so a copied or
checked-out tree (whose mtimes say nothing) rebuilds exactly when the
source differs from what the binary was built from.

``python -m ray_tpu.core.object_store.build --sanitize=thread`` (or
``address``) builds a sanitizer-instrumented variant next to the normal
one; the stress harness (tests/test_store_sanitize.py) loads it via
RTPU_STORE_LIB (reference practice: TSAN/ASAN CI jobs over the plasma
store, SURVEY §4.3)."""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess

from ray_tpu.util.debug_lock import make_lock

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "_shm_store.cc")
_lock = make_lock("object_store.build._lock")

_SAN_FLAGS = {
    "thread": ["-fsanitize=thread", "-O1", "-g"],
    "address": ["-fsanitize=address", "-O1", "-g",
                "-fno-omit-frame-pointer"],
}


def _compile(out: str, extra: list) -> None:
    tmp = out + f".tmp{os.getpid()}"
    cmd = (["g++", "-std=c++17", "-shared", "-fPIC"] + extra
           + ["-o", tmp, _SRC, "-lpthread", "-lrt"])
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, out)


def ensure_built(sanitize: str = "", force: bool = False) -> str:
    """Build the store library unless one built from this exact source
    exists; return its path.

    ``sanitize`` in {"thread", "address"} builds/returns the
    instrumented variant (separate .so — normal users never pay the
    sanitizer tax). ``force`` recompiles even when the binary matches
    the source — the loader uses it when a .so left by another host
    turns out to be ABI-incompatible with this one (e.g. built against
    a newer glibc than the one present)."""
    stem = f"_shm_store_{sanitize}" if sanitize else "_shm_store"
    flags = _SAN_FLAGS[sanitize] if sanitize else ["-O2"]
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    lib = os.path.join(_DIR, f"{stem}.{digest}.so")
    with _lock:
        if force or not os.path.exists(lib):
            _compile(lib, flags)
            for old in glob.glob(os.path.join(_DIR, stem + ".*.so")):
                if old != lib:   # built from a source that is gone
                    try:
                        os.unlink(old)
                    except OSError:
                        pass
        return lib


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--sanitize", choices=["thread", "address", ""],
                    default="")
    path = ensure_built(ap.parse_args().sanitize)
    print(path)
