"""ctypes bindings for the native host-runtime library (``native/src``).

Reference parity: the JVM reference reaches its C++ runtime through JavaCPP
presets over the libnd4j C ABI (SURVEY.md §2.1); here the host-side kernels
(gradient codecs, CSV ETL, ubyte conversion, batch gather) live in
``libdl4j_native.so`` reached through ctypes — no JNI-style per-op overhead
matters since these are coarse host calls.

The library is compiled on first use with the baked-in g++ (``-O3 -fopenmp``)
into ``native/build/`` under a name that carries the digest of the source
it was built from, so a ``.so`` is only ever loaded for exactly the source
in this checkout. Everything degrades to numpy fallbacks when compilation
is unavailable (``DL4J_TPU_DISABLE_NATIVE=1`` forces that).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

import numpy as np

_ABI_VERSION = 2  # must match dl4j_native_version() in dl4j_native.cpp
_SRC = Path(__file__).resolve().parents[2] / "native" / "src" / "dl4j_native.cpp"
_BUILD_DIR = Path(__file__).resolve().parents[2] / "native" / "build"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _artifact() -> Path:
    """The ``.so`` for THIS source: named by the source's content digest
    (a build dir copied or restored from another tree, whatever its
    mtimes, can never supply a library for a different source)."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:12]
    return _BUILD_DIR / f"libdl4j_native_v{_ABI_VERSION}_{digest}.so"


def _build(out: Path) -> bool:
    out.parent.mkdir(parents=True, exist_ok=True)
    # pid-suffixed temp + rename: concurrent first users (xdist workers)
    # never load a half-written library
    tmp = out.with_suffix(f".tmp.{os.getpid()}")
    cmd = ["g++", "-O3", "-fopenmp", "-shared", "-fPIC", "-std=c++17",
           str(_SRC), "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
        return True
    except (OSError, subprocess.SubprocessError):
        tmp.unlink(missing_ok=True)
        return False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    i64, i32, f32 = ctypes.c_int64, ctypes.c_int32, ctypes.c_float
    P = ctypes.POINTER
    lib.dl4j_encode_threshold.restype = i64
    lib.dl4j_encode_threshold.argtypes = [P(f32), i64, f32, P(i32), i64]
    lib.dl4j_decode_threshold.restype = None
    lib.dl4j_decode_threshold.argtypes = [P(i32), i64, f32, P(f32)]
    lib.dl4j_encode_bitmap.restype = i64
    lib.dl4j_encode_bitmap.argtypes = [P(f32), i64, f32,
                                       P(ctypes.c_uint64)]
    lib.dl4j_decode_bitmap.restype = None
    lib.dl4j_decode_bitmap.argtypes = [P(ctypes.c_uint64), i64, f32, P(f32)]
    lib.dl4j_csv_dims.restype = i64
    lib.dl4j_csv_dims.argtypes = [ctypes.c_char_p, i64, ctypes.c_char, i64,
                                  P(i64), P(i64)]
    lib.dl4j_parse_csv.restype = i64
    lib.dl4j_parse_csv.argtypes = [ctypes.c_char_p, i64, ctypes.c_char, i64,
                                   P(f32), i64, i64]
    lib.dl4j_u8_to_f32.restype = None
    lib.dl4j_u8_to_f32.argtypes = [P(ctypes.c_uint8), i64, f32, f32, P(f32)]
    lib.dl4j_gather_rows.restype = None
    lib.dl4j_gather_rows.argtypes = [ctypes.c_char_p, P(i64), i64, i64,
                                     ctypes.c_char_p]
    lib.dl4j_w2v_pairs.restype = i64
    lib.dl4j_w2v_pairs.argtypes = [P(i32), P(i64), i64, i64,
                                   P(ctypes.c_uint64), P(i32), i64]
    lib.dl4j_native_version.restype = ctypes.c_int
    lib.dl4j_native_threads.restype = ctypes.c_int
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first call; None if
    unavailable or disabled."""
    global _lib, _tried
    if _lib is not None:
        return _lib
    if _tried or os.environ.get("DL4J_TPU_DISABLE_NATIVE") == "1":
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            out = _artifact()
            if not out.exists() and not _build(out):
                return None
            lib = _bind(ctypes.CDLL(str(out)))
            # the wrapper's ABI constant vs the source's own: a skew no
            # rebuild can fix
            if lib.dl4j_native_version() != _ABI_VERSION:
                return None
            _lib = lib
        except OSError:
            _lib = None
    return _lib


def available() -> bool:
    return get_lib() is not None


def _fptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


# ---------------------------------------------------------------------------
# Host-side codec (numpy). The on-device jax codec lives in
# parallel/compression.py; this one serves host messaging/checkpoint
# compression (reference: EncodingHandler on the Java side).
# ---------------------------------------------------------------------------

def encode_threshold(g: np.ndarray, tau: float) -> np.ndarray:
    """-> int32 array of signed 1-based indices (+i: +tau flip, -i: -tau)."""
    g = np.ascontiguousarray(g, np.float32).ravel()
    lib = get_lib()
    if lib is None:
        pos = np.flatnonzero(g >= tau) + 1
        neg = -(np.flatnonzero(g <= -tau) + 1)
        enc = np.concatenate([pos, neg]).astype(np.int32)
        order = np.argsort(np.abs(enc), kind="stable")
        return enc[order]
    cap = max(int(g.size), 16)
    out = np.empty(cap, np.int32)
    cnt = lib.dl4j_encode_threshold(
        _fptr(g), g.size, tau, out.ctypes.data_as(
            ctypes.POINTER(ctypes.c_int32)), cap)
    return out[:cnt].copy()


def decode_threshold(enc: np.ndarray, tau: float, n: int,
                     out: Optional[np.ndarray] = None) -> np.ndarray:
    """Accumulate ±tau flips into ``out`` (allocated zero if None)."""
    if out is None:
        out = np.zeros(n, np.float32)
    enc = np.ascontiguousarray(enc, np.int32)
    if enc.size:
        amax = int(np.abs(enc).max())
        if amax > n:
            raise ValueError(
                f"corrupt threshold message: index magnitude {amax} outside "
                f"[1, {n}] (truncated or mis-framed payload?)")
        nzero = int((enc == 0).sum())
        if nzero:
            raise ValueError(
                f"corrupt threshold message: {nzero} zero entries "
                f"(indices are signed and 1-based; 0 is not a valid code)")
    lib = get_lib()
    if lib is None:
        idx = np.abs(enc) - 1
        np.add.at(out, idx, np.where(enc > 0, tau, -tau).astype(np.float32))
        return out
    lib.dl4j_decode_threshold(
        enc.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), enc.size, tau,
        _fptr(out))
    return out


def encode_bitmap(g: np.ndarray, tau: float) -> tuple[np.ndarray, int]:
    """-> (uint64 words with 2 bits/elem, nnz)."""
    g = np.ascontiguousarray(g, np.float32).ravel()
    words = np.zeros((g.size + 31) // 32, np.uint64)
    lib = get_lib()
    if lib is None:
        nnz = 0
        for i, v in enumerate(g):
            if v >= tau:
                words[i // 32] |= np.uint64(1) << np.uint64((i % 32) * 2)
                nnz += 1
            elif v <= -tau:
                words[i // 32] |= np.uint64(2) << np.uint64((i % 32) * 2)
                nnz += 1
        return words, nnz
    nnz = lib.dl4j_encode_bitmap(
        _fptr(g), g.size, tau,
        words.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    return words, int(nnz)


def decode_bitmap(words: np.ndarray, tau: float, n: int,
                  out: Optional[np.ndarray] = None) -> np.ndarray:
    if out is None:
        out = np.zeros(n, np.float32)
    words = np.ascontiguousarray(words, np.uint64)
    if n > words.size * 32:
        raise ValueError(f"bitmap of {words.size} words covers "
                         f"{words.size * 32} elements < n={n}")
    lib = get_lib()
    if lib is None:
        for i in range(n):
            s = (int(words[i // 32]) >> ((i % 32) * 2)) & 3
            if s == 1:
                out[i] += tau
            elif s == 2:
                out[i] -= tau
        return out
    lib.dl4j_decode_bitmap(
        words.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), n, tau,
        _fptr(out))
    return out


# ---------------------------------------------------------------------------
# ETL fast paths
# ---------------------------------------------------------------------------

def parse_numeric_csv(text: bytes | str, delimiter: str = ",",
                      skip_lines: int = 0) -> np.ndarray:
    """Parse an all-numeric CSV buffer to a float32 matrix."""
    if isinstance(text, str):
        text = text.encode()
    lib = get_lib()
    if lib is None:
        rows = [r.split(delimiter) for r in text.decode().splitlines()
                if r.strip()][skip_lines:]
        if not rows:
            return np.zeros((0, 0), np.float32)
        return np.asarray([[float(c) for c in r] for r in rows], np.float32)
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    d = ctypes.c_char(delimiter.encode())
    lib.dl4j_csv_dims(text, len(text), d, skip_lines,
                      ctypes.byref(rows), ctypes.byref(cols))
    out = np.empty((rows.value, cols.value), np.float32)
    errs = lib.dl4j_parse_csv(text, len(text), d, skip_lines, _fptr(out),
                              rows.value, cols.value)
    if errs:
        raise ValueError(f"{errs} non-numeric cells in CSV "
                         f"(use CSVRecordReader + TransformProcess for "
                         f"mixed-type data)")
    return out


def u8_to_f32(src: np.ndarray, scale: float = 1.0 / 255.0,
              shift: float = 0.0) -> np.ndarray:
    """ubyte image buffer -> float32 (NativeImageLoader's normalize role)."""
    src = np.ascontiguousarray(src, np.uint8)
    lib = get_lib()
    if lib is None:
        return src.astype(np.float32) * scale + shift
    dst = np.empty(src.shape, np.float32)
    lib.dl4j_u8_to_f32(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), src.size,
        scale, shift, _fptr(dst))
    return dst


def gather_rows(src: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Shuffled minibatch assembly: ``src[indices]`` with OpenMP memcpy.
    Non-contiguous sources fall back to numpy fancy-indexing rather than
    paying a full-array copy per batch."""
    src = np.asarray(src)
    idx = np.ascontiguousarray(indices, np.int64)
    n = src.shape[0] if src.ndim else 0
    # numpy fancy-index semantics for BOTH paths: negatives wrap, OOB raises
    if idx.size and ((idx < -n).any() or (idx >= n).any()):
        bad = idx[(idx < -n) | (idx >= n)][0]
        raise IndexError(f"index {bad} out of bounds for axis 0 with "
                         f"size {n}")
    idx = np.where(idx < 0, idx + n, idx)
    lib = get_lib()
    if (lib is None or src.ndim == 0
            or not src.flags["C_CONTIGUOUS"]):
        return src[idx]
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    dst = np.empty((idx.size,) + src.shape[1:], src.dtype)
    lib.dl4j_gather_rows(
        src.ctypes.data_as(ctypes.c_char_p),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), idx.size,
        row_bytes, dst.ctypes.data_as(ctypes.c_char_p))
    return dst


_XORSHIFT_INIT = 0x9E3779B97F4A7C15
_MASK64 = (1 << 64) - 1


def _xorshift64_stream(seed: int):
    """The SAME xorshift64 stream the C side uses — keeps native and
    fallback paths bit-identical for a given seed."""
    st = (seed or _XORSHIFT_INIT) & _MASK64
    while True:
        st = (st ^ (st << 13)) & _MASK64
        st ^= st >> 7
        st = (st ^ (st << 17)) & _MASK64
        yield st


# chunk bound for the worst-case pair buffer: tokens*2*window int32 pairs
_W2V_CHUNK_TOKENS = 1 << 20


def w2v_pairs(sentences, window: int, seed: int = 1):
    """Skip-gram (center, context) pairs with word2vec.c dynamic windows
    (reference: the nd4j SkipGram native op's pair walk). ``sentences``:
    list of int32 arrays of token indices. Returns int32 [n, 2]. The
    numpy fallback replays the identical RNG stream, so results are
    bit-equal with or without the native lib."""
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    sents = [np.ascontiguousarray(s, np.int32) for s in sentences if len(s)]
    lib = get_lib()
    if lib is None:
        rng = _xorshift64_stream(int(seed))
        pairs = []
        for sent in sents:
            n = len(sent)
            if n < 2:
                # the C walk still consumes no RNG for n<2 sentences
                continue
            for i in range(n):
                b = 1 + (next(rng) % window)
                lo, hi = max(0, i - b), min(n, i + b + 1)
                for j in range(lo, hi):
                    if j != i:
                        pairs.append((sent[i], sent[j]))
        return (np.asarray(pairs, np.int32) if pairs
                else np.zeros((0, 2), np.int32))
    # chunk sentences so the worst-case buffer stays bounded (~8MB*window
    # per chunk instead of corpus-sized)
    chunks = []
    cur, cur_tokens = [], 0
    for sent in sents:
        cur.append(sent)
        cur_tokens += len(sent)
        if cur_tokens >= _W2V_CHUNK_TOKENS:
            chunks.append(cur)
            cur, cur_tokens = [], 0
    if cur:
        chunks.append(cur)
    results = []
    # the C walk reads its RNG state from io_state and writes the final
    # state back, so chunking continues ONE stream with no host-side
    # replay. Seed 0 maps to the same init constant as the fallback, and
    # xorshift64 never reaches state 0 from nonzero — bit-parity holds
    # for every seed.
    io_state = ctypes.c_uint64((int(seed) or _XORSHIFT_INIT) & _MASK64)
    for chunk in chunks:
        tokens = np.concatenate(chunk)
        offsets = np.zeros(len(chunk) + 1, np.int64)
        np.cumsum([len(s) for s in chunk], out=offsets[1:])
        cap = max(int(tokens.size) * 2 * int(window), 16)
        out = np.empty((cap, 2), np.int32)
        cnt = lib.dl4j_w2v_pairs(
            tokens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(chunk), int(window), ctypes.byref(io_state),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), cap)
        if cnt < 0:
            raise RuntimeError(f"native w2v_pairs failed: {cnt}")
        results.append(out[:cnt].copy())
    return (np.concatenate(results) if results
            else np.zeros((0, 2), np.int32))


def native_threads() -> int:
    lib = get_lib()
    return int(lib.dl4j_native_threads()) if lib is not None else 0
