"""Build the native components on demand.

The native library is compiled once per source CONTENT into
``ray_tpu/native/_build/`` and loaded via ctypes (no pybind11 in this image;
the C ABI + ctypes keeps the binding dependency-free).
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")
_LOCK = threading.Lock()

_SOURCES = {
    "shm_store": ["shm_store.cpp"],
    "mutable_channel": ["mutable_channel.cpp"],
}


def lib_path(name: str) -> str:
    return os.path.join(_BUILD_DIR, f"lib{name}.so")


def _content_key(srcs, flags) -> str:
    h = hashlib.sha256(" ".join(flags).encode())
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _compile(srcs, out, flags) -> str:
    """Compile srcs -> out unless out was built from these very bytes
    with these flags: the key is the sources' content, kept beside the
    output (mtimes mean nothing in a copied or checked-out tree). Atomic
    replace of both."""
    with _LOCK:
        key, key_path = _content_key(srcs, flags), f"{out}.key"
        try:
            with open(key_path) as f:
                if f.read() == key and os.path.exists(out):
                    return out
        except FileNotFoundError:
            pass
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = f"{out}.tmp.{os.getpid()}"  # per-process tmp; os.replace is atomic
        cmd = ["g++", "-std=c++17", *flags, "-o", tmp, *srcs, "-lpthread"]
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, out)
        with open(f"{key_path}.tmp.{os.getpid()}", "w") as f:
            f.write(key)
        os.replace(f.name, key_path)
    return out


def build(name: str) -> str:
    """Compile (if stale) and return the path to lib<name>.so."""
    srcs = [os.path.join(_HERE, s) for s in _SOURCES[name]]
    return _compile(srcs, lib_path(name),
                    ["-O2", "-g", "-shared", "-fPIC"])


# Standalone sanitizer harnesses (the reference's build:asan/build:ubsan
# CI story, .bazelrc:104-125): each entry is a main() program compiled
# WITH the component sources under -fsanitize and run as a subprocess by
# tests/test_sanitizers.py. The suite runs asan+ubsan plus a
# sanitize="thread" build of the shm store's concurrent sections: the
# off-loop put path (per-stripe allocator + rt_write_parallel copy pool)
# and the lock-striped arena's racy surfaces — lock-free seal CAS,
# seqlock stats reads, and concurrent create/seal/get/evict across >=4
# stripes. The seqlock's publication edge is explicitly annotated for
# tsan (RT_TSAN_ACQUIRE/RT_TSAN_RELEASE in shm_store.cpp, compiled in
# only under -fsanitize=thread), so the reader/writer pairing is checked
# at the protocol level, not just per-field. tsan runs single-process
# multi-thread only — the cross-process robust-mutex EOWNERDEAD repair
# path is exercised by the asan harness via a re-exec'd crash child.
_SELFTESTS = {
    "shm_store_selftest": ["shm_store_selftest.cpp", "shm_store.cpp"],
    "mutable_channel_selftest": ["mutable_channel_selftest.cpp",
                                 "mutable_channel.cpp"],
}


def build_selftest(name: str, sanitize: str = "address,undefined") -> str:
    """Compile (if stale) a sanitizer selftest binary; returns its path."""
    srcs = [os.path.join(_HERE, s) for s in _SELFTESTS[name]]
    out = os.path.join(_BUILD_DIR, f"{name}.{sanitize.replace(',', '_')}")
    # tsan's runtime slowdown (5-15x) is hostile at -O1 on 1-core CI
    # hosts; -O2 keeps the hammer sections inside their test timeouts
    opt = "-O2" if sanitize == "thread" else "-O1"
    return _compile(srcs, out,
                    [opt, "-g", f"-fsanitize={sanitize}",
                     "-fno-omit-frame-pointer"])
