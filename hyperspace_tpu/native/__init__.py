"""ctypes bindings for the native host kernels (native/hs_native.cpp).

Builds libhs_native-<hash>.so next to this package once with the system
compiler, keyed on a hash of the committed source and the build flags, so
a library left on disk from other sources is never loaded; every entry
point has a numpy fallback so the framework works without a toolchain. Hash outputs are
bit-identical to ops/hashing.py (covered by a parity test) — bucket layout
is an on-disk contract.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess

import numpy as np

from ..staticcheck.concurrency import TrackedLock

logger = logging.getLogger(__name__)

_ABI_VERSION = 4

# named so the one-time compile/load critical section participates in the
# lock-order graph (it subprocesses the compiler while held — nothing else
# may nest inside it)
_lock = TrackedLock("native.load")
_lib: ctypes.CDLL | None = None
_tried = False


def _source_path() -> str:
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo_root, "native", "hs_native.cpp")


# the exact flags the .so was (or would be) built with — bench artifacts
# record these so host-tier numbers are reproducible
BUILD_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC"]
COMPILER = "g++"


def _lib_path() -> str:
    """The library built from the current source and flags: its name
    carries their hash, so an edit to either builds a new file."""
    h = hashlib.sha256()
    try:
        with open(_source_path(), "rb") as f:
            h.update(f.read())
    except OSError:
        pass  # hslint: HS402 — no source: the name matches no build, and _build() reports it
    h.update(" ".join([COMPILER, *BUILD_FLAGS]).encode())
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"libhs_native-{h.hexdigest()[:16]}.so",
    )


def build_facts() -> dict:
    """Self-description for benchmark artifacts: compiler, flags, and
    whether the native library is CURRENTLY loaded (vs numpy fallbacks).
    Reads load state without triggering a build — callers that want the
    library pay for it on their own hot path, not while collecting facts."""
    facts = {"compiler": COMPILER, "flags": list(BUILD_FLAGS), "abi": _ABI_VERSION}
    try:
        out = subprocess.run(
            [COMPILER, "--version"], capture_output=True, text=True, timeout=10
        )
        facts["compiler_version"] = out.stdout.splitlines()[0] if out.stdout else None
    except Exception:
        facts["compiler_version"] = None
    facts["loaded"] = _lib is not None
    return facts


def _build() -> bool:
    src = _source_path()
    if not os.path.exists(src):
        return False
    out = _lib_path()
    # concurrent processes (test workers) may build at once: each writes
    # its own file and renames it into place atomically
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = [COMPILER, *BUILD_FLAGS, src, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
        return True
    except Exception as e:  # missing compiler, sandbox, ... -> numpy fallback
        logger.info("native build skipped (%s); using numpy fallbacks", e)
        try:
            os.unlink(tmp)
        except OSError:
            pass  # hslint: HS402 — nothing was written
        return False


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        path = _lib_path()
        if not os.path.exists(path) and not _build():
            return None
        try:
            lib = ctypes.CDLL(path)
            if lib.hs_native_abi_version() != _ABI_VERSION:
                logger.warning("stale %s (ABI mismatch); rebuilding", path)
                os.unlink(path)
                if not _build():
                    return None
                lib = ctypes.CDLL(path)
            _configure(lib)
            _lib = lib
        except OSError as e:
            # corrupt or foreign-arch artifact: rebuild once from source
            logger.info("native load failed (%s); rebuilding", e)
            try:
                os.unlink(path)
            except OSError:
                pass  # hslint: HS402 — best-effort removal; the rebuild overwrites anyway
            if _build():
                try:
                    lib = ctypes.CDLL(path)
                    _configure(lib)
                    _lib = lib
                except OSError:
                    logger.info("native rebuild failed; using numpy fallbacks")
        return _lib


def _configure(lib: ctypes.CDLL) -> None:
    u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    lib.hs_hash32_i64.argtypes = [i64p, ctypes.c_int64, u32p]
    lib.hs_hash32_i32.argtypes = [i32p, ctypes.c_int64, u32p]
    lib.hs_hash32_words.argtypes = [u32p, ctypes.c_int64, ctypes.c_int32, u32p]
    lib.hs_bucket_partition.argtypes = [
        u32p, ctypes.c_int64, ctypes.c_int32, i32p, i64p, i64p,
    ]
    lib.hs_join_i64.argtypes = [
        i64p, ctypes.c_int64, i64p, ctypes.c_int64, i64p, i64p, ctypes.c_int64,
    ]
    lib.hs_join_i64.restype = ctypes.c_int64
    f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    lib.hs_probe_agg_i64.argtypes = [
        i64p, ctypes.c_int64, i64p, ctypes.c_int64,
        f64p, ctypes.c_int32, i64p, f64p,
    ]
    lib.hs_probe_agg_i64.restype = ctypes.c_int64
    lib.hs_radix_argsort_i64.argtypes = [i64p, ctypes.c_int64, i64p]
    lib.hs_radix_argsort_i32.argtypes = [i32p, ctypes.c_int64, i64p]


def radix_argsort(keys: np.ndarray) -> np.ndarray | None:
    """Stable O(n)-per-digit argsort for int64/int32 keys (index-build
    bucket sorts); None -> numpy stable argsort fallback."""
    lib = _load()
    if lib is None or len(keys) < 4096:  # numpy wins at tiny sizes
        return None
    out = np.empty(len(keys), dtype=np.int64)
    if keys.dtype == np.int64:
        lib.hs_radix_argsort_i64(np.ascontiguousarray(keys), len(keys), out)
        return out
    if keys.dtype == np.int32:
        lib.hs_radix_argsort_i32(np.ascontiguousarray(keys), len(keys), out)
        return out
    return None


def available() -> bool:
    return _load() is not None


def hash32(keys: np.ndarray) -> np.ndarray | None:
    """Native single-column hash for int32/int64 keys; None -> caller falls
    back to the numpy implementation."""
    lib = _load()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys)
    out = np.empty(len(keys), dtype=np.uint32)
    if keys.dtype == np.int64:
        lib.hs_hash32_i64(keys, len(keys), out)
        return out
    if keys.dtype == np.int32:
        lib.hs_hash32_i32(keys, len(keys), out)
        return out
    return None


def hash32_words(words: list[np.ndarray]) -> np.ndarray | None:
    lib = _load()
    if lib is None:
        return None
    n = len(words[0])
    stacked = np.ascontiguousarray(
        np.concatenate([np.ascontiguousarray(w, dtype=np.uint32) for w in words])
    )
    out = np.empty(n, dtype=np.uint32)
    lib.hs_hash32_words(stacked, n, len(words), out)
    return out


def bucket_partition(hashes: np.ndarray, num_buckets: int):
    """(bucket_ids, order, offsets) via counting sort; None on no native lib."""
    lib = _load()
    if lib is None:
        return None
    hashes = np.ascontiguousarray(hashes, dtype=np.uint32)
    n = len(hashes)
    bucket_ids = np.empty(n, dtype=np.int32)
    order = np.empty(n, dtype=np.int64)
    offsets = np.empty(num_buckets + 1, dtype=np.int64)
    lib.hs_bucket_partition(hashes, n, num_buckets, bucket_ids, order, offsets)
    return bucket_ids, order, offsets


def join_i64(lcodes: np.ndarray, rcodes: np.ndarray) -> "tuple[np.ndarray, np.ndarray] | None":
    """Native inner hash join of factorized int64 code arrays (negative
    codes never match). Pair order matches the numpy sort+searchsorted path
    (left-major, ascending right within a key). None -> numpy fallback."""
    lib = _load()
    if lib is None:
        return None
    lcodes = np.ascontiguousarray(lcodes, dtype=np.int64)
    rcodes = np.ascontiguousarray(rcodes, dtype=np.int64)
    cap = max(len(lcodes), len(rcodes), 1)
    while True:
        li = np.empty(cap, dtype=np.int64)
        ri = np.empty(cap, dtype=np.int64)
        total = lib.hs_join_i64(lcodes, len(lcodes), rcodes, len(rcodes), li, ri, cap)
        if total <= cap:
            return li[:total], ri[:total]
        cap = int(total)


def probe_agg_i64(lk: np.ndarray, rk_sorted: np.ndarray, weights: "list[np.ndarray]"):
    """Fused probe + per-key accumulation: counts[nr] and one float64 sum
    vector per weight array, over a sorted unique int64 right side.
    None -> numpy fallback."""
    lib = _load()
    if lib is None:
        return None
    lk = np.ascontiguousarray(lk, dtype=np.int64)
    rk = np.ascontiguousarray(rk_sorted, dtype=np.int64)
    w = len(weights)
    stacked = np.ascontiguousarray(
        np.stack([np.ascontiguousarray(x, dtype=np.float64) for x in weights])
        if w
        else np.zeros((0, len(lk)))
    ).reshape(-1)
    counts = np.empty(len(rk), dtype=np.int64)
    sums = np.empty((w, len(rk)), dtype=np.float64)
    lib.hs_probe_agg_i64(lk, len(lk), rk, len(rk), stacked, w, counts, sums.reshape(-1))
    return counts, [sums[i] for i in range(w)]
