"""ctypes bindings for the native C++ radix tree (native/src/radix_tree.cc).

Drop-in replacement for the pure-Python RadixTree used by KvIndexer when the
native library is available (DYNTPU_NATIVE=0 disables). Same event semantics;
hashes are computed in Python (xxh3 via the C-backed xxhash wheel) and passed
as u64 arrays. The library is built from native/src on first use
(native/build.py); where there is no compiler the Python tree is the stated
alternative, and which one was loaded is logged once.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional, Sequence

from dynamo_tpu.llm.kv_router.indexer import OverlapScores, RouterEvent, WorkerId
from dynamo_tpu.utils import get_logger

log = get_logger("kv_router.native")

_lib = None
_load_failed = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    if os.environ.get("DYNTPU_NATIVE", "1") == "0":
        _load_failed = True
        return None
    try:
        import sys
        from pathlib import Path

        repo_root = Path(__file__).resolve().parents[3]
        sys.path.insert(0, str(repo_root / "native"))
        try:
            import build as native_build  # native/build.py
        finally:
            sys.path.pop(0)
        path = native_build.build()
        lib = ctypes.CDLL(str(path))
        lib.rtree_new.restype = ctypes.c_void_p
        lib.rtree_free.argtypes = [ctypes.c_void_p]
        lib.rtree_apply_stored.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64, ctypes.c_int,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.rtree_apply_removed.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.rtree_remove_worker.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.rtree_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.rtree_find_matches.restype = ctypes.c_int64
        lib.rtree_find_matches.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ]
        _lib = lib
        log.info("radix index: native (%s)", path)
    except (OSError, subprocess.CalledProcessError) as e:
        # no compiler, a failed build, or a library that does not load
        log.warning("radix index: python (native library unavailable: %s)", e)
        _load_failed = True
    return _lib


def native_available() -> bool:
    return _load() is not None


def _u64_array(values: Sequence[int]):
    return (ctypes.c_uint64 * len(values))(*[v & 0xFFFFFFFFFFFFFFFF for v in values])


class NativeRadixTree:
    """Same interface as dynamo_tpu.llm.kv_router.indexer.RadixTree (minus
    frequency tracking, which stays Python-side when enabled)."""

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = ctypes.c_void_p(lib.rtree_new())

    def __del__(self):
        if getattr(self, "_handle", None) and self._lib is not None:
            self._lib.rtree_free(self._handle)
            self._handle = None

    def apply_event(self, event: RouterEvent) -> None:
        ev = event.event
        if ev.kind == "stored":
            blocks = ev.blocks
            self._lib.rtree_apply_stored(
                self._handle,
                event.worker_id,
                (ev.parent_hash or 0) & 0xFFFFFFFFFFFFFFFF,
                0 if ev.parent_hash is None else 1,
                len(blocks),
                _u64_array([b.block_hash for b in blocks]),
                _u64_array([b.tokens_hash for b in blocks]),
            )
        elif ev.kind == "removed":
            self._lib.rtree_apply_removed(
                self._handle, event.worker_id, len(ev.block_hashes), _u64_array(ev.block_hashes)
            )

    def remove_worker(self, worker: WorkerId) -> None:
        self._lib.rtree_remove_worker(self._handle, worker)

    def stats(self) -> tuple[int, int]:
        """(num_nodes, num_workers)."""
        nodes = ctypes.c_int64()
        workers = ctypes.c_int64()
        self._lib.rtree_stats(self._handle, ctypes.byref(nodes), ctypes.byref(workers))
        return nodes.value, workers.value

    def find_matches(self, sequence: Sequence[int], early_exit: bool = False) -> OverlapScores:
        max_out = 4096
        out_w = (ctypes.c_int64 * max_out)()
        out_s = (ctypes.c_int64 * max_out)()
        n = self._lib.rtree_find_matches(
            self._handle, len(sequence), _u64_array(sequence),
            1 if early_exit else 0, out_w, out_s, max_out,
        )
        if n < 0:
            raise RuntimeError("too many workers in match result")
        return OverlapScores(scores={out_w[i]: out_s[i] for i in range(n)})
