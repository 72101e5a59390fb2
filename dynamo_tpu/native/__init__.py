"""ctypes binding for the native core (csrc/dynamo_core.cpp).

Builds csrc/libdynamo_core.so with `make` on first use (tracked sources
only; the .so is gitignored) and loads it. Every entry point has a
pure-Python twin (llm/tokens.py, llm/kv_router/indexer.py); callers use
`native_available()` / the `NativeRadixTree` class and fall back when the
build or load fails — which one is active is logged at WARNING, once per
process. Disable with DYN_NATIVE=0.

Reference parity: lib/llm/src/tokens.rs compute_hash_v2 :36 and
kv_router/indexer.rs RadixTree :224 (Rust there; C++ + ctypes here).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import Dict, List, Optional, Sequence

import numpy as np

logger = logging.getLogger(__name__)

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(__file__))), "csrc")
_SO = os.path.join(_CSRC, "libdynamo_core.so")

_lib = None
_load_attempted = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    _load_attempted = True
    from ..runtime.config import env_bool

    if not env_bool("DYN_NATIVE", True):
        logger.warning("native core disabled (DYN_NATIVE=0): pure-Python twin in use")
        return None
    # always invoke make: a no-op when the .so is fresh, a rebuild when
    # csrc/ changed. The .so is gitignored and built only from the tracked
    # csrc/Makefile + csrc/dynamo_core.cpp; one that make could not
    # produce from them is never loaded (a stale binary must not win).
    try:
        subprocess.run(
            ["make", "-C", _CSRC],
            check=True,
            capture_output=True,
            timeout=120,
        )
        lib = ctypes.CDLL(_SO)
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning(
            "native core unavailable (%s): pure-Python twin in use", e
        )
        return None
    logger.warning("native core loaded: %s", _SO)
    u64, i64, p = ctypes.c_uint64, ctypes.c_int64, ctypes.c_void_p
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.dyn_block_hash.restype = u64
    lib.dyn_block_hash.argtypes = [u32p, u64, u64]
    lib.dyn_seq_hashes.restype = u64
    lib.dyn_seq_hashes.argtypes = [u32p, u64, u64, u64, u64p]
    lib.dyn_index_new.restype = p
    lib.dyn_index_free.argtypes = [p]
    lib.dyn_index_apply_stored.argtypes = [p, i64, u64p, u64]
    lib.dyn_index_apply_removed.argtypes = [p, i64, u64p, u64]
    lib.dyn_index_remove_worker.argtypes = [p, i64]
    lib.dyn_index_num_blocks.restype = u64
    lib.dyn_index_num_blocks.argtypes = [p]
    lib.dyn_index_worker_block_count.restype = u64
    lib.dyn_index_worker_block_count.argtypes = [p, i64]
    lib.dyn_index_find_matches.restype = u64
    lib.dyn_index_find_matches.argtypes = [
        p, u64p, u64, ctypes.c_int, i64p, u64p, u64, u64p, u64p,
    ]
    lib.dyn_index_dump.restype = u64
    lib.dyn_index_dump.argtypes = [p, i64p, u64p, u64]
    _lib = lib
    return _lib


def native_available() -> bool:
    return _load() is not None


def _as_u64_array(hashes: Sequence[int]) -> np.ndarray:
    # Python ints may exceed int64; hashes are u64 by construction
    return np.asarray([h & 0xFFFFFFFFFFFFFFFF for h in hashes], dtype=np.uint64)


def _as_u32_tokens(tokens: Sequence[int]) -> np.ndarray:
    """Match the pure-Python path's `tok & 0xFFFFFFFF` masking (tokens.py)
    instead of letting numpy raise OverflowError on out-of-range ids."""
    arr = np.asarray(tokens)
    if arr.dtype == np.uint32:
        return arr
    return (np.asarray(arr, dtype=np.int64) & 0xFFFFFFFF).astype(np.uint32)


def compute_block_hash(tokens: Sequence[int], parent_hash: int = 0) -> int:
    lib = _load()
    toks = _as_u32_tokens(tokens)
    return int(
        lib.dyn_block_hash(
            toks.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            len(toks),
            parent_hash & 0xFFFFFFFFFFFFFFFF,
        )
    )


def compute_seq_hashes(
    tokens: Sequence[int], block_size: int = 64, salt: int = 0
) -> List[int]:
    lib = _load()
    toks = _as_u32_tokens(tokens)
    out = np.empty(max(len(toks) // block_size, 1), dtype=np.uint64)
    n = lib.dyn_seq_hashes(
        toks.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        len(toks),
        block_size,
        salt & 0xFFFFFFFFFFFFFFFF,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return [int(h) for h in out[:n]]


class NativeRadixTree:
    """Drop-in for llm.kv_router.indexer.RadixTree backed by the C++ index."""

    MAX_WORKERS = 4096

    def __init__(self):
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError("native core unavailable")
        self._idx = self._lib.dyn_index_new()
        # per-instance scratch (find_matches is called from one scheduler
        # task at a time); avoids per-call allocation overhead
        self._workers_buf = np.empty(self.MAX_WORKERS, dtype=np.int64)
        self._scores_buf = np.empty(self.MAX_WORKERS, dtype=np.uint64)
        self._freqs_buf = np.empty(4096, dtype=np.uint64)
        self._hash_buf = np.empty(4096, dtype=np.uint64)

    def __del__(self):
        lib = getattr(self, "_lib", None)
        idx = getattr(self, "_idx", None)
        if lib is not None and idx:
            lib.dyn_index_free(idx)
            self._idx = None

    def apply_stored(self, worker_id: int, block_hashes: List[int],
                     chained: bool = True, parent=None):
        # chained/parent are the Python tree's bounded-eviction chain
        # metadata; the C++ index is unbounded and ignores them
        arr = _as_u64_array(block_hashes)
        self._lib.dyn_index_apply_stored(
            self._idx,
            worker_id,
            arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            len(arr),
        )

    def apply_removed(self, worker_id: int, block_hashes: List[int]):
        arr = _as_u64_array(block_hashes)
        self._lib.dyn_index_apply_removed(
            self._idx,
            worker_id,
            arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            len(arr),
        )

    def remove_worker(self, worker_id: int):
        self._lib.dyn_index_remove_worker(self._idx, worker_id)

    def clear_all_blocks(self, worker_id: int):
        self.remove_worker(worker_id)

    def find_matches(self, seq_hashes: List[int], early_exit: bool = False):
        from ..llm.kv_router.indexer import OverlapScores

        result = OverlapScores()
        if not seq_hashes:
            return result
        nh = len(seq_hashes)
        if nh > len(self._hash_buf):
            self._hash_buf = np.empty(nh, dtype=np.uint64)
            self._freqs_buf = np.empty(nh, dtype=np.uint64)
        self._hash_buf[:nh] = np.asarray(seq_hashes, dtype=np.uint64)
        workers, scores, freqs = self._workers_buf, self._scores_buf, self._freqs_buf
        freq_n = ctypes.c_uint64(0)
        n = self._lib.dyn_index_find_matches(
            self._idx,
            self._hash_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            nh,
            1 if early_exit else 0,
            workers.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            scores.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            self.MAX_WORKERS,
            freqs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            ctypes.byref(freq_n),
        )
        result.scores = {int(workers[i]): int(scores[i]) for i in range(n)}
        result.frequencies = freqs[: freq_n.value].tolist()
        return result

    @property
    def num_blocks(self) -> int:
        return int(self._lib.dyn_index_num_blocks(self._idx))

    def worker_block_count(self, worker_id: int) -> int:
        return int(self._lib.dyn_index_worker_block_count(self._idx, worker_id))

    def workers(self) -> List[int]:
        return [w for w, hs in self._dump_pairs().items() if hs]

    def _dump_pairs(self) -> Dict[int, List[int]]:
        total = int(self._lib.dyn_index_dump(self._idx, None, None, 0))
        if total == 0:
            return {}
        workers = np.empty(total, dtype=np.int64)
        hashes = np.empty(total, dtype=np.uint64)
        n = self._lib.dyn_index_dump(
            self._idx,
            workers.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            hashes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            total,
        )
        out: Dict[int, List[int]] = {}
        for i in range(n):
            out.setdefault(int(workers[i]), []).append(int(hashes[i]))
        return out

    def dump(self) -> dict:
        return {str(w): sorted(hs) for w, hs in self._dump_pairs().items()}

    def load(self, snapshot: dict):
        for w_str, hashes in snapshot.items():
            self.apply_stored(int(w_str), list(hashes))


def make_radix_tree(max_blocks=None):
    """Best tree available: native C++ index, else the Python one. A
    block-count cap (`max_blocks`, DYN_ROUTER_INDEX_MAX_BLOCKS) forces
    the Python tree — leaf-first eviction needs the chain bookkeeping the
    C++ index does not carry; a bounded index is chosen for memory, not
    match speed, so that is the right trade."""
    from ..llm.kv_router.indexer import RadixTree

    if max_blocks is not None and max_blocks > 0:
        return RadixTree(max_blocks=max_blocks)
    if native_available():
        return NativeRadixTree()
    return RadixTree()
