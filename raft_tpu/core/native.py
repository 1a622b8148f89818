"""Loader for the native host runtime (``cpp/raft_tpu_host.cpp``).

The reference's host-side runtime (logger core, dendrogram union-find,
…) is C++; this module loads our C++ equivalent via ctypes. If the
shared library is missing, or its stamp does not match the hash of the
sources in ``_cpp/``, it is built on first use with g++ (sub-second,
no deps); if that fails (no compiler at deploy time) every caller falls
back to its pure-Python formulation — the C++ path is a performance/
parity tier, not a hard dependency.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_LIB_NAME = "libraft_tpu_host.so"
_ABI = 3  # must match rth_abi_version() in _cpp/raft_tpu_host.cpp
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False

_LOG_CB_TYPE = ctypes.CFUNCTYPE(None, ctypes.c_int, ctypes.c_char_p)
_log_cb_keepalive = None  # the registered callback must outlive the lib


def _lib_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "_lib", _LIB_NAME)


def _cpp_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(__file__)), "_cpp")


def _stamp_path() -> str:
    return _lib_path() + ".stamp"


def _source_hash() -> str:
    """sha256 over ``_cpp/*.cpp`` and ``build.sh`` — what the built
    library must have been compiled from."""
    d = _cpp_dir()
    h = hashlib.sha256()
    for name in sorted(os.listdir(d)):
        if name.endswith(".cpp") or name == "build.sh":
            h.update(name.encode())
            with open(os.path.join(d, name), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def _is_current() -> bool:
    """The library exists and its stamp matches the committed sources
    (the .so is untracked, so it may come from another revision)."""
    try:
        with open(_stamp_path()) as f:
            stamp = f.read().strip()
    except OSError:
        return False
    return os.path.exists(_lib_path()) and stamp == _source_hash()


def _try_build() -> bool:
    script = os.path.join(_cpp_dir(), "build.sh")
    if not os.path.exists(script):
        return False
    try:
        digest = _source_hash()
        subprocess.run(["bash", script], check=True, capture_output=True,
                       timeout=120)
        with open(_stamp_path(), "w") as f:
            f.write(digest + "\n")
        return True
    except (subprocess.SubprocessError, OSError):
        return False


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    lib.rth_abi_version.restype = ctypes.c_int
    lib.rth_log.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rth_log_set_level.argtypes = [ctypes.c_int]
    lib.rth_log_get_level.restype = ctypes.c_int
    lib.rth_log_should_log.argtypes = [ctypes.c_int]
    lib.rth_log_should_log.restype = ctypes.c_int
    lib.rth_log_set_callback.argtypes = [_LOG_CB_TYPE]
    lib.rth_build_dendrogram.restype = ctypes.c_int
    lib.rth_build_dendrogram.argtypes = [
        ctypes.c_int64, i64p, i64p, f64p, i64p, f64p, i64p]
    lib.rth_extract_flattened.restype = ctypes.c_int
    lib.rth_extract_flattened.argtypes = [
        ctypes.c_int64, i64p, ctypes.c_int64, i32p]
    lib.rth_boruvka_mst.restype = ctypes.c_int64
    lib.rth_boruvka_mst.argtypes = [
        ctypes.c_int64, ctypes.c_int64, i64p, i64p, f64p, f64p,
        i64p, i64p, f64p, i64p]
    lib.rth_interrupt_cancel.restype = None
    lib.rth_interrupt_cancel.argtypes = [ctypes.c_uint64]
    lib.rth_interrupt_check_and_clear.restype = ctypes.c_int
    lib.rth_interrupt_check_and_clear.argtypes = [ctypes.c_uint64]
    lib.rth_interrupt_release.restype = None
    lib.rth_interrupt_release.argtypes = [ctypes.c_uint64]
    lib.rth_kv_server_port.restype = ctypes.c_int
    lib.rth_kv_server_port.argtypes = []
    lib.rth_kv_server_start.restype = ctypes.c_int
    lib.rth_kv_server_start.argtypes = [ctypes.c_int]
    lib.rth_kv_server_stop.restype = None
    lib.rth_kv_server_stop.argtypes = []
    lib.rth_kv_put.restype = ctypes.c_int
    lib.rth_kv_put.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_int64]
    lib.rth_kv_get.restype = ctypes.c_int64
    lib.rth_kv_get.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int64]
    return lib


def load() -> Optional[ctypes.CDLL]:
    """The native library, or None (disabled via RAFT_TPU_NATIVE=0,
    unbuildable, or ABI mismatch)."""
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed or os.environ.get("RAFT_TPU_NATIVE", "1") == "0":
        return None
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        path = _lib_path()
        if not _is_current() and not _try_build():
            _load_failed = True
            return None

        def _open():
            raw = ctypes.CDLL(path)
            try:
                lib = _configure(raw)
                if lib.rth_abi_version() != _ABI:
                    raise OSError("ABI mismatch")
            except (OSError, AttributeError):
                # release the handle: a later CDLL(path) after rebuild
                # must not get this already-mapped stale image back
                import _ctypes
                _ctypes.dlclose(raw._handle)
                raise
            return lib

        try:
            _lib = _open()
        except (OSError, AttributeError):
            # stale library from an older source revision: rebuild once
            if _try_build():
                try:
                    _lib = _open()
                except (OSError, AttributeError):
                    _load_failed = True
            else:
                _load_failed = True
        return _lib


def available() -> bool:
    return load() is not None


# ---------------------------------------------------------------------------
# Typed wrappers
# ---------------------------------------------------------------------------

def build_dendrogram(src, dst, weight):
    """Native build_dendrogram_host over weight-sorted MST edges →
    (children (n-1, 2) i64, heights (n-1,) f64, sizes (n-1,) i64), or
    None when the native lib is unavailable. Raises ValueError on
    non-tree input (cycle)."""
    lib = load()
    if lib is None:
        return None
    src = np.ascontiguousarray(src, np.int64)
    dst = np.ascontiguousarray(dst, np.int64)
    weight = np.ascontiguousarray(weight, np.float64)
    n_edges = src.shape[0]
    if dst.shape != (n_edges,) or weight.shape != (n_edges,):
        raise ValueError("build_dendrogram: src/dst/weight length mismatch")
    children = np.empty(2 * n_edges, np.int64)
    heights = np.empty(n_edges, np.float64)
    sizes = np.empty(n_edges, np.int64)
    rc = lib.rth_build_dendrogram(n_edges, src, dst, weight, children,
                                  heights, sizes)
    if rc != 0:
        raise ValueError(f"build_dendrogram: invalid MST edges (rc={rc})")
    return children.reshape(n_edges, 2), heights, sizes


def extract_flattened(children, n: int, n_merges: int):
    """Native extract_flattened_clusters → labels (n,) i32, or None when
    the native lib is unavailable."""
    lib = load()
    if lib is None:
        return None
    children = np.ascontiguousarray(np.asarray(children).reshape(-1),
                                    np.int64)
    if n <= 0 or n_merges < 0 or n_merges > n - 1:
        raise ValueError("extract_flattened: bad n/n_merges")
    if children.shape[0] < 2 * n_merges:
        raise ValueError("extract_flattened: children shorter than n_merges")
    labels = np.empty(n, np.int32)
    rc = lib.rth_extract_flattened(n, children, n_merges, labels)
    if rc < 0:
        raise ValueError(f"extract_flattened: invalid input (rc={rc})")
    return labels


def boruvka_mst(n: int, src, dst, altered_w, orig_w):
    """Native Borůvka minimum spanning forest → (mst_src, mst_dst,
    mst_weight, component_labels), or None when unavailable."""
    lib = load()
    if lib is None:
        return None
    if n < 0:
        raise ValueError("boruvka_mst: negative vertex count")
    src = np.ascontiguousarray(src, np.int64)
    dst = np.ascontiguousarray(dst, np.int64)
    altered_w = np.ascontiguousarray(altered_w, np.float64)
    orig_w = np.ascontiguousarray(orig_w, np.float64)
    m = src.shape[0]
    if (dst.shape != (m,) or altered_w.shape != (m,)
            or orig_w.shape != (m,)):
        raise ValueError("boruvka_mst: edge array length mismatch")
    cap = max(int(n) - 1, 1)
    out_s = np.empty(cap, np.int64)
    out_d = np.empty(cap, np.int64)
    out_w = np.empty(cap, np.float64)
    out_c = np.empty(max(int(n), 1), np.int64)
    rc = lib.rth_boruvka_mst(n, m, src, dst, altered_w, orig_w,
                             out_s, out_d, out_w, out_c)
    if rc < 0:
        raise ValueError(f"boruvka_mst: invalid edges (rc={rc})")
    return out_s[:rc], out_d[:rc], out_w[:rc], out_c[:int(n)]


def interrupt_cancel(thread_id: int) -> bool:
    lib = load()
    if lib is None:
        return False
    lib.rth_interrupt_cancel(int(thread_id))
    return True


def interrupt_check_and_clear(thread_id: int):
    """True/False = flag state from the native registry; None when the
    native lib is unavailable (caller falls back to Python tokens)."""
    lib = load()
    if lib is None:
        return None
    return bool(lib.rth_interrupt_check_and_clear(int(thread_id)))


def interrupt_release(thread_id: int) -> None:
    lib = load()
    if lib is not None:
        lib.rth_interrupt_release(int(thread_id))


def kv_server_port():
    """Bound port of the running process-global broker, or None."""
    lib = load()
    if lib is None:
        return None
    p = lib.rth_kv_server_port()
    return int(p) if p > 0 else None


def kv_server_start(port: int = 0):
    """Start the native TCP KV broker (the UCX-endpoint role,
    comms/detail/ucp_helper.hpp). Returns the bound port, or None when
    the native lib is unavailable / bind failed."""
    lib = load()
    if lib is None:
        return None
    p = lib.rth_kv_server_start(int(port))
    return int(p) if p > 0 else None


def kv_server_stop() -> None:
    lib = load()
    if lib is not None:
        lib.rth_kv_server_stop()


def kv_put(host: str, port: int, key: str, value: bytes) -> bool:
    lib = load()
    if lib is None:
        return False
    return lib.rth_kv_put(host.encode(), int(port), key.encode(),
                          value, len(value)) == 0


def kv_get(host: str, port: int, key: str, timeout_ms: int,
           consume: bool = True, max_len: int = 1 << 22):
    """Blocking tagged GET. Returns the value bytes, None on timeout;
    raises OSError on transport errors or an overflowing value."""
    lib = load()
    if lib is None:
        raise OSError("native kv broker unavailable")
    buf = ctypes.create_string_buffer(max_len)
    rc = lib.rth_kv_get(host.encode(), int(port), key.encode(),
                        int(timeout_ms), 1 if consume else 0, buf, max_len)
    if rc >= 0:
        return buf.raw[:rc]
    if rc == -1:
        return None
    raise OSError(f"native kv get failed (rc={rc})")


def log(level: int, msg: str) -> bool:
    """Emit through the native logging core; False if unavailable."""
    lib = load()
    if lib is None:
        return False
    lib.rth_log(int(level), msg.encode())
    return True


def log_set_level(level: int) -> bool:
    lib = load()
    if lib is None:
        return False
    lib.rth_log_set_level(int(level))
    return True


def log_set_callback(fn) -> bool:
    """Install a Python callback as the native sink (the reference's
    callback-sink pattern, core/detail/callback_sink.hpp). Pass None to
    restore the default stderr sink."""
    global _log_cb_keepalive
    lib = load()
    if lib is None:
        return False
    if fn is None:
        cb = _LOG_CB_TYPE(0)
    else:
        def _trampoline(level, msg):
            try:
                fn(int(level), msg.decode(errors="replace"))
            except Exception:
                pass  # never propagate through the C boundary
        cb = _LOG_CB_TYPE(_trampoline)
    lib.rth_log_set_callback(cb)
    _log_cb_keepalive = cb
    return True
