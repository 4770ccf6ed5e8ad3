"""ctypes bindings for the native storage runtime (libgalaxystore).

The shared library is built with g++ from `galaxystore.cpp` on the machine
that runs it (no pybind11 in the image — plain C ABI + ctypes per the
environment constraints).  Its file name carries a hash of the source's
content and of the host CPU's ISA (`-march=native` code is only valid there),
so a stale or foreign `.so` that travelled with a copy of the tree is never
loaded and a fresh checkout without one builds its own.  Every entry point has
a numpy fallback so the engine runs without a compiler; `AVAILABLE` tells
callers which path is live, and a failed build says so on stderr.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional

import numpy as np

from galaxysql_tpu.runtime import host_isa_id

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "galaxystore.cpp")

_lib: Optional[ctypes.CDLL] = None
_lock = threading.Lock()
AVAILABLE = False


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read() + host_isa_id().encode()).hexdigest()[:16]
    return os.path.join(_DIR, f"libgalaxystore-{tag}.so")


def _build(so: str) -> bool:
    # build beside the target and rename: concurrent processes (test workers,
    # subprocess servers) must never load a half-written library
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", "-O3", "-march=native", "-shared", "-fPIC",
                        "-o", tmp, _SRC], check=True, capture_output=True,
                       timeout=120)
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        print(f"[galaxysql_tpu.native] building {os.path.basename(so)} "
              f"failed ({e!r}); numpy fallbacks engage. "
              f"{detail.decode(errors='replace')[-2000:]}", file=sys.stderr)
        if os.path.exists(tmp):
            os.remove(tmp)
        return False


def _load():
    global _lib, AVAILABLE
    with _lock:
        if _lib is not None or AVAILABLE:
            return
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            return
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            print(f"[galaxysql_tpu.native] loading {so} failed ({e!r}); "
                  "numpy fallbacks engage", file=sys.stderr)
            return
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        st = ctypes.c_size_t
        lib.gx_hash_partition.argtypes = [i64p, i32p, st, ctypes.c_int32]
        lib.gx_visible_mask.argtypes = [i64p, i64p, u8p, st, ctypes.c_int64,
                                        ctypes.c_int64]
        lib.gx_join_build.argtypes = [u64p, u8p, st, i32p, st, i32p]
        lib.gx_join_probe.argtypes = [u64p, u8p, st, u64p, i32p, st, i32p,
                                      i32p, i32p, st]
        lib.gx_join_probe.restype = st
        lib.gx_join_build_k1.argtypes = [i64p, u8p, st, i32p, st, i32p]
        lib.gx_join_probe_k1.argtypes = [i64p, u8p, st, i64p, i32p, st, i32p,
                                         i32p, i32p, st]
        lib.gx_join_probe_k1.restype = st
        lib.gx_join_probe_k1_idx.argtypes = [i64p, i32p, st, i64p, i32p, st,
                                             i32p, i32p, i32p, st]
        lib.gx_join_probe_k1_idx.restype = st
        lib.gx_hash_combine.argtypes = [u64p, i64p, u8p, st, ctypes.c_int32]
        lib.gx_bloom_build.argtypes = [i64p, st, u64p, st]
        lib.gx_bloom_query.argtypes = [i64p, st, u64p, st, u8p]
        lib.gx_crc32c.argtypes = [u8p, st, ctypes.c_uint32]
        lib.gx_crc32c.restype = ctypes.c_uint32
        lib.gx_encode_i64.argtypes = [i64p, st, u8p]
        lib.gx_encode_i64.restype = st
        lib.gx_decode_i64.argtypes = [u8p, st, i64p, st]
        lib.gx_decode_i64.restype = st
        _lib = lib
        AVAILABLE = True


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


_load()


# ---------------------------------------------------------------------------
# public API (native or numpy fallback)
# ---------------------------------------------------------------------------

def hash_partition(keys: np.ndarray, nparts: int) -> np.ndarray:
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    if AVAILABLE and keys.size:
        out = np.empty(keys.size, dtype=np.int32)
        _lib.gx_hash_partition(_ptr(keys, ctypes.c_int64), _ptr(out, ctypes.c_int32),
                               keys.size, nparts)
        return out
    with np.errstate(over="ignore"):
        h = keys.astype(np.uint64)
        h ^= h >> np.uint64(33)
        h *= np.uint64(0xff51afd7ed558ccd)
        h ^= h >> np.uint64(33)
        h *= np.uint64(0xc4ceb9fe1a85ec53)
        h ^= h >> np.uint64(33)
    return (h % np.uint64(nparts)).astype(np.int32)


def visible_mask(begin_ts: np.ndarray, end_ts: np.ndarray, snapshot_ts: Optional[int],
                 txn_id: int) -> np.ndarray:
    begin_ts = np.ascontiguousarray(begin_ts, dtype=np.int64)
    end_ts = np.ascontiguousarray(end_ts, dtype=np.int64)
    n = begin_ts.shape[0]
    if AVAILABLE and n and snapshot_ts is not None:
        out = np.empty(n, dtype=np.uint8)
        _lib.gx_visible_mask(_ptr(begin_ts, ctypes.c_int64),
                             _ptr(end_ts, ctypes.c_int64),
                             _ptr(out, ctypes.c_uint8), n, snapshot_ts, txn_id)
        return out.view(np.bool_)
    # numpy fallback (also the snapshot_ts=None path)
    b, e = begin_ts, end_ts
    if snapshot_ts is None:
        ins = b >= 0
        dele = e != np.iinfo(np.int64).max
    else:
        ins = (b >= 0) & (b <= snapshot_ts)
        dele = (e >= 0) & (e <= snapshot_ts)
    if txn_id:
        ins = ins | (b == -txn_id)
        dele = dele | (e == -txn_id)
    return ins & ~dele


def bloom_build(keys: np.ndarray, nwords: int) -> np.ndarray:
    """nwords MUST be a power of two; returns the u64 word array."""
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    words = np.zeros(nwords, dtype=np.uint64)
    if AVAILABLE and keys.size:
        _lib.gx_bloom_build(_ptr(keys, ctypes.c_int64), keys.size,
                            _ptr(words, ctypes.c_uint64), nwords)
        return words
    with np.errstate(over="ignore"):
        h = _mix_np(keys.astype(np.uint64))
    m = np.uint64(nwords - 1)
    w1 = (h >> np.uint64(6)) & m
    w2 = (h >> np.uint64(38)) & m
    np.bitwise_or.at(words, w1.astype(np.int64), np.uint64(1) << (h & np.uint64(63)))
    np.bitwise_or.at(words, w2.astype(np.int64),
                     np.uint64(1) << ((h >> np.uint64(32)) & np.uint64(63)))
    return words


def bloom_query(keys: np.ndarray, words: np.ndarray) -> np.ndarray:
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    if AVAILABLE and keys.size:
        out = np.empty(keys.size, dtype=np.uint8)
        _lib.gx_bloom_query(_ptr(keys, ctypes.c_int64), keys.size,
                            _ptr(words, ctypes.c_uint64), words.size,
                            _ptr(out, ctypes.c_uint8))
        return out.view(np.bool_)
    with np.errstate(over="ignore"):
        h = _mix_np(keys.astype(np.uint64))
    m = np.uint64(words.size - 1)
    w1 = words[((h >> np.uint64(6)) & m).astype(np.int64)]
    w2 = words[((h >> np.uint64(38)) & m).astype(np.int64)]
    hit1 = (w1 >> (h & np.uint64(63))) & np.uint64(1)
    hit2 = (w2 >> ((h >> np.uint64(32)) & np.uint64(63))) & np.uint64(1)
    return (hit1 & hit2).astype(np.bool_)


def hash_combine(h: Optional[np.ndarray], lane: np.ndarray,
                 valid: Optional[np.ndarray]) -> np.ndarray:
    """Fold one key lane into the running combined hash — the host twin of
    kernels/relational.py::hash_columns (identical constants; the two must agree
    or nothing, since build and probe both hash here)."""
    lane = np.ascontiguousarray(lane, dtype=np.int64)
    n = lane.shape[0]
    first = h is None
    if first:
        h = np.empty(n, dtype=np.uint64)
    if AVAILABLE and n:
        v = None if valid is None else \
            np.ascontiguousarray(valid, dtype=np.uint8)
        _lib.gx_hash_combine(_ptr(h, ctypes.c_uint64),
                             _ptr(lane, ctypes.c_int64),
                             None if v is None else _ptr(v, ctypes.c_uint8),
                             n, 1 if first else 0)
        return h
    with np.errstate(over="ignore"):
        l = _mix_np(lane.astype(np.uint64))
        if valid is not None:
            l = np.where(valid, l, np.uint64(0xDEADBEEFCAFEBABE))
        if first:
            return l
        return _mix_np(h * np.uint64(31) + l + np.uint64(0x9E3779B97F4A7C15))


def _as_u8(mask: np.ndarray) -> np.ndarray:
    """bool mask -> uint8 lane, as a zero-copy view when already contiguous."""
    if mask.dtype == np.bool_ and mask.flags["C_CONTIGUOUS"]:
        return mask.view(np.uint8)
    return np.ascontiguousarray(mask, dtype=np.uint8)


def join_build(hashes: np.ndarray, live: np.ndarray):
    """Chained hash table over build hashes -> (heads, next, M)."""
    nb = hashes.shape[0]
    M = 1 << max(4, int(max(nb, 1) * 2 - 1).bit_length())
    heads = np.full(M, -1, dtype=np.int32)
    nxt = np.empty(max(nb, 1), dtype=np.int32)
    live8 = _as_u8(live)
    if AVAILABLE and nb:
        _lib.gx_join_build(_ptr(hashes, ctypes.c_uint64),
                           _ptr(live8, ctypes.c_uint8), nb,
                           _ptr(heads, ctypes.c_int32), M,
                           _ptr(nxt, ctypes.c_int32))
        return heads, nxt, M
    # fallback marker: heads=None, nxt = LIVE row ids in hash-sorted order
    ids = np.nonzero(np.asarray(live))[0]
    order = ids[np.argsort(hashes[ids], kind="stable")]
    return None, order, M


def join_probe(probe_hashes: np.ndarray, probe_live: np.ndarray,
               build_hashes: np.ndarray, table) -> tuple:
    """Candidate pairs (b_idx, p_idx) for every probe row whose 64-bit hash
    matches a build row's; exact-key verification is the caller's."""
    heads, nxt, M = table
    npr = probe_hashes.shape[0]
    live8 = _as_u8(probe_live)
    if AVAILABLE and heads is not None:
        # start at npr/4: selective joins rarely exceed it, and buffer
        # allocation is the dominant cost at large npr (a miss re-probes at
        # the now-exact size — one extra pass over the lanes, ~1ms/M rows)
        cap = max(int(npr) // 4, 1024)
        while True:
            out_b = np.empty(cap, dtype=np.int32)
            out_p = np.empty(cap, dtype=np.int32)
            total = _lib.gx_join_probe(
                _ptr(probe_hashes, ctypes.c_uint64),
                _ptr(live8, ctypes.c_uint8), npr,
                _ptr(build_hashes, ctypes.c_uint64),
                _ptr(heads, ctypes.c_int32), M,
                _ptr(nxt, ctypes.c_int32),
                _ptr(out_b, ctypes.c_int32), _ptr(out_p, ctypes.c_int32), cap)
            if total <= cap:
                return out_b[:total], out_p[:total]
            cap = int(total)
    # fallback: sort/searchsorted over the LIVE build hashes (see join_build)
    order = nxt  # live build row ids in hash order
    sh = build_hashes[order]
    lo = np.searchsorted(sh, probe_hashes, side="left")
    hi = np.searchsorted(sh, probe_hashes, side="right")
    counts = np.where(probe_live, hi - lo, 0).astype(np.int64)
    total = int(counts.sum())
    p_of = np.repeat(np.arange(npr, dtype=np.int32), counts)
    offs = np.concatenate([[0], np.cumsum(counts)])[:-1]
    k = np.arange(total, dtype=np.int64) - np.repeat(offs, counts)
    b_of = order[(np.repeat(lo, counts) + k).astype(np.int64)].astype(np.int32)
    return b_of, p_of


def join_build_k1(keys: np.ndarray, live: np.ndarray):
    """Single-int64-key chained table; matching compares keys exactly (no
    verification pass needed).  Returns (keys, heads, next, M)."""
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    nb = keys.shape[0]
    M = 1 << max(4, int(max(nb, 1) * 2 - 1).bit_length())
    heads = np.full(M, -1, dtype=np.int32)
    nxt = np.empty(max(nb, 1), dtype=np.int32)
    live8 = _as_u8(live)
    if AVAILABLE and nb:
        _lib.gx_join_build_k1(_ptr(keys, ctypes.c_int64),
                              _ptr(live8, ctypes.c_uint8), nb,
                              _ptr(heads, ctypes.c_int32), M,
                              _ptr(nxt, ctypes.c_int32))
        return keys, heads, nxt, M
    # fallback marker: heads=None, nxt = LIVE row ids in key-sorted order
    ids = np.nonzero(np.asarray(live))[0]
    order = ids[np.argsort(keys[ids], kind="stable")]
    return keys, None, order, M


def join_probe_k1(probe_keys: np.ndarray, probe_live: np.ndarray,
                  table) -> tuple:
    """Exact (b_idx, p_idx) pairs for a single-int64-key join."""
    build_keys, heads, nxt, M = table
    probe_keys = np.ascontiguousarray(probe_keys, dtype=np.int64)
    npr = probe_keys.shape[0]
    if AVAILABLE and heads is not None:
        n_live = int(np.count_nonzero(probe_live))
        sparse = n_live * 2 < npr
        if sparse:
            # sparse live mask: random-pattern `if (!live)` branches mispredict
            # in the scalar loop; collect ids vectorized, probe dense
            ids = np.nonzero(probe_live)[0].astype(np.int32)
        else:
            live8 = _as_u8(probe_live)
        cap = max(n_live, 1024)
        while True:
            out_b = np.empty(cap, dtype=np.int32)
            out_p = np.empty(cap, dtype=np.int32)
            if sparse:
                total = _lib.gx_join_probe_k1_idx(
                    _ptr(probe_keys, ctypes.c_int64),
                    _ptr(ids, ctypes.c_int32), ids.size,
                    _ptr(build_keys, ctypes.c_int64),
                    _ptr(heads, ctypes.c_int32), M,
                    _ptr(nxt, ctypes.c_int32),
                    _ptr(out_b, ctypes.c_int32),
                    _ptr(out_p, ctypes.c_int32), cap)
            else:
                total = _lib.gx_join_probe_k1(
                    _ptr(probe_keys, ctypes.c_int64),
                    _ptr(live8, ctypes.c_uint8), npr,
                    _ptr(build_keys, ctypes.c_int64),
                    _ptr(heads, ctypes.c_int32), M,
                    _ptr(nxt, ctypes.c_int32),
                    _ptr(out_b, ctypes.c_int32),
                    _ptr(out_p, ctypes.c_int32), cap)
            if total <= cap:
                return out_b[:total], out_p[:total]
            cap = int(total)
    # numpy fallback: sorted live build keys + searchsorted expansion (exact)
    order = nxt  # live build row ids in key order (see join_build_k1)
    sk = build_keys[order]
    lo = np.searchsorted(sk, probe_keys, side="left")
    hi = np.searchsorted(sk, probe_keys, side="right")
    counts = np.where(probe_live, hi - lo, 0).astype(np.int64)
    total = int(counts.sum())
    p_of = np.repeat(np.arange(npr, dtype=np.int32), counts)
    offs = np.concatenate([[0], np.cumsum(counts)])[:-1]
    k = np.arange(total, dtype=np.int64) - np.repeat(offs, counts)
    b_of = order[(np.repeat(lo, counts) + k).astype(np.int64)].astype(np.int32)
    return b_of, p_of


def crc32c(data: bytes, seed: int = 0) -> int:
    if AVAILABLE:
        buf = np.frombuffer(data, dtype=np.uint8)
        if buf.size:
            return int(_lib.gx_crc32c(_ptr(buf, ctypes.c_uint8), buf.size, seed))
    import zlib
    return zlib.crc32(data, seed) & 0xFFFFFFFF  # fallback: crc32 (not castagnoli)


def encode_i64(values: np.ndarray) -> bytes:
    """Explicit one-byte format tag: b'V' = delta varint, b'R' = raw little-endian
    (a length heuristic would be ambiguous with legitimate varint streams)."""
    values = np.ascontiguousarray(values, dtype=np.int64)
    if AVAILABLE and values.size:
        out = np.empty(values.size * 10, dtype=np.uint8)
        n = _lib.gx_encode_i64(_ptr(values, ctypes.c_int64), values.size,
                               _ptr(out, ctypes.c_uint8))
        return b"V" + out[:n].tobytes()
    return b"R" + values.tobytes()


def decode_i64(data: bytes, n: int) -> np.ndarray:
    tag, body = data[:1], data[1:]
    if tag == b"R":
        return np.frombuffer(body, dtype=np.int64).copy()
    if tag != b"V":
        raise ValueError(f"unknown lane encoding tag {tag!r}")
    buf = np.frombuffer(body, dtype=np.uint8)
    out = np.empty(n, dtype=np.int64)
    if AVAILABLE:
        got = _lib.gx_decode_i64(_ptr(buf, ctypes.c_uint8), buf.size,
                                 _ptr(out, ctypes.c_int64), n)
        return out[:got]
    raise RuntimeError("varint-coded lane requires the native library")


def _mix_np(h):
    h = h ^ (h >> np.uint64(33))
    h = h * np.uint64(0xff51afd7ed558ccd)
    h = h ^ (h >> np.uint64(33))
    h = h * np.uint64(0xc4ceb9fe1a85ec53)
    h = h ^ (h >> np.uint64(33))
    return h
