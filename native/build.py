"""Build the native shared libraries (g++) from ``native/src/*.cc`` on first use.

Nothing built is tracked by git. A library lands in ``native/_build/`` under a
name keyed by the CONTENT of its sources and the compile command, so a
checkout builds exactly once per source state, a changed source never loads a
stale binary, and a run never rewrites a tracked file. Where there is no
compiler the callers say so and use their stated alternative (the Python
radix tree, ``dynamo_tpu/llm/kv_router/native_indexer.py``)."""

from __future__ import annotations

import hashlib
import os
import subprocess
from pathlib import Path

NATIVE_DIR = Path(__file__).parent
SRC = NATIVE_DIR / "src"
BUILD_DIR = NATIVE_DIR / "_build"
CXX = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC"]

LIBS = {
    "libdynamo_tpu_native": [SRC / "radix_tree.cc"],
    # engine-embeddable C ABI for KV event publication (llm_capi.cc docstring)
    "libdynamo_tpu_llm": [SRC / "llm_capi.cc"],
}


def _build_one(name: str) -> Path:
    sources = LIBS[name]
    key = hashlib.sha256(" ".join(CXX).encode())
    for src in sources:
        key.update(src.read_bytes())
    out = BUILD_DIR / f"{name}-{key.hexdigest()[:16]}.so"
    if out.exists():
        return out
    BUILD_DIR.mkdir(exist_ok=True)
    # several workers may start at once: build beside the target, then rename
    tmp = out.with_suffix(f".{os.getpid()}.tmp")
    subprocess.run(
        [*CXX, *[str(s) for s in sources], "-o", str(tmp)],
        check=True, capture_output=True,
    )
    os.replace(tmp, out)
    return out


def build() -> Path:
    """Build the radix-tree library; returns its path."""
    return _build_one("libdynamo_tpu_native")


def build_llm_capi() -> Path:
    return _build_one("libdynamo_tpu_llm")


if __name__ == "__main__":
    for name in LIBS:
        print(_build_one(name))
