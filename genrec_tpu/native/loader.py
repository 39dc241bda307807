"""ctypes bindings + on-demand build for the native Amazon parser."""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess

import numpy as np

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "amazon_parser.cpp")
_lib = None


def _lib_path() -> str:
    """The library built from THIS source: named by the source's content
    hash, so an existing file is trusted for what it was built from, never
    for its mtime (a copied tree does not preserve those) — a stale or
    foreign binary simply has another name and is not looked at."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"libamazon_parser.{digest}.so")


def _build(lib_path: str) -> bool:
    # Build to a per-pid temp name and atomically rename: concurrent
    # processes never observe a half-written .so.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC, "-lz"],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, lib_path)
        return True
    except Exception as e:  # toolchain absent or build failure
        logger.info("native parser build unavailable (%s); using Python path", e)
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False


def _load():
    global _lib
    if _lib is not None:
        return _lib
    lib_path = _lib_path()
    if not os.path.exists(lib_path) and not _build(lib_path):
        _lib = False
        return _lib
    try:
        lib = ctypes.CDLL(lib_path)
        lib.parse_reviews.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.parse_reviews.restype = ctypes.c_int64
        _lib = lib
    except OSError:
        _lib = False
    return _lib


def native_available() -> bool:
    return bool(_load())


def parse_reviews_native(gz_path: str, cache_path: str | None = None):
    """Parse a reviews_*.json.gz with the native extractor.

    Returns (user_idx, item_idx, timestamps, user_names, item_names) with
    indices ordered by first appearance — identical id assignment to the
    Python path in data/amazon.load_sequences. Returns None when the
    native library is unavailable or parsing fails.

    The handoff file is a per-process temp file by default so concurrent
    trainers sharing a dataset folder never race on it.
    """
    import tempfile

    lib = _load()
    if not lib:
        return None
    own_tmp = cache_path is None
    if own_tmp:
        fd, cache_path = tempfile.mkstemp(suffix=".nativebin")
        os.close(fd)
    try:
        n = lib.parse_reviews(gz_path.encode(), cache_path.encode())
        if n < 0:
            return None
        with open(cache_path, "rb") as f:
            header = np.fromfile(f, np.int64, 3)
            n_rec, n_users, n_items = (int(x) for x in header)
            recs = np.fromfile(f, np.int64, n_rec * 3).reshape(n_rec, 3)
            names = f.read().decode().splitlines()
    finally:
        if own_tmp:
            try:
                os.remove(cache_path)
            except OSError:
                pass
    user_names = names[:n_users]
    item_names = names[n_users : n_users + n_items]
    return recs[:, 0], recs[:, 1], recs[:, 2], user_names, item_names
