"""ctypes binding for the C++ PJRT resources/mdarray layer.

Reference split: ``handle_t`` (cpp/include/raft/core/handle.hpp:54-316)
owns the device context; ``mdarray`` (core/mdarray.hpp:125) owns typed
device storage. Here :class:`NativeResources` is the handle — a C++
object owning a PJRT client created from any plugin exposing
``GetPjrtApi`` (libtpu in production, the in-tree mock
plugin in tests) — and :class:`NativeMdarray` is the owning device
container with dtype + extents, host round-trips, and the
``stream_syncer``-style sync point (``sync``/``ready`` over the
buffer's PJRT ready event).

The compute path stays JAX/XLA (SURVEY.md §2.10 note: on TPU the
natural runtime API is Python/JAX); this layer is the C++ resource/
container tier of SURVEY §2's language plan, not a second executor.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional, Tuple

import numpy as np

from raft_tpu.core.error import expects

_LIB_NAME = "libraft_tpu_pjrt.so"
_MOCK_NAME = "libraft_tpu_mockpjrt.so"
_ABI = 2
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False

# numpy dtype ↔ PJRT_Buffer_Type (pjrt_c_api.h enum order)
_DTYPE_TO_PJRT = {
    np.dtype(np.bool_): 1,    # PRED
    np.dtype(np.int8): 2,
    np.dtype(np.int16): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int64): 5,
    np.dtype(np.uint8): 6,
    np.dtype(np.uint16): 7,
    np.dtype(np.uint32): 8,
    np.dtype(np.uint64): 9,
    np.dtype(np.float16): 10,
    np.dtype(np.float32): 11,
    np.dtype(np.float64): 12,
}
_PJRT_TO_DTYPE = {v: k for k, v in _DTYPE_TO_PJRT.items()}


def _lib_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(__file__)), "_lib")


def mock_plugin_path() -> str:
    """Path of the in-tree mock PJRT plugin (built by cpp/build.sh)."""
    return os.path.join(_lib_dir(), _MOCK_NAME)


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
    i64 = ctypes.c_int64
    lib.rtp_abi_version.restype = ctypes.c_int
    lib.rtp_resources_create.restype = i64
    lib.rtp_resources_create.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                         ctypes.c_int]
    lib.rtp_resources_create_opts.restype = i64
    lib.rtp_resources_create_opts.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
    lib.rtp_resources_destroy.argtypes = [i64]
    lib.rtp_platform_name.restype = ctypes.c_int
    lib.rtp_platform_name.argtypes = [i64, ctypes.c_char_p, ctypes.c_int]
    lib.rtp_api_version.restype = ctypes.c_int
    lib.rtp_api_version.argtypes = [i64, ctypes.POINTER(ctypes.c_int),
                                    ctypes.POINTER(ctypes.c_int)]
    lib.rtp_process_index.restype = ctypes.c_int
    lib.rtp_process_index.argtypes = [i64]
    lib.rtp_device_count.restype = ctypes.c_int
    lib.rtp_device_count.argtypes = [i64]
    lib.rtp_device_id.restype = ctypes.c_int
    lib.rtp_device_id.argtypes = [i64, ctypes.c_int]
    lib.rtp_buffer_from_host.restype = i64
    lib.rtp_buffer_from_host.argtypes = [
        i64, ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(i64),
        ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
    lib.rtp_buffer_ndim.restype = ctypes.c_int
    lib.rtp_buffer_ndim.argtypes = [i64]
    lib.rtp_buffer_dims.restype = ctypes.c_int
    lib.rtp_buffer_dims.argtypes = [i64, ctypes.POINTER(i64), ctypes.c_int]
    lib.rtp_buffer_dtype.restype = ctypes.c_int
    lib.rtp_buffer_dtype.argtypes = [i64]
    lib.rtp_buffer_ready.restype = ctypes.c_int
    lib.rtp_buffer_ready.argtypes = [i64]
    lib.rtp_buffer_sync.restype = ctypes.c_int
    lib.rtp_buffer_sync.argtypes = [i64]
    lib.rtp_buffer_to_host.restype = ctypes.c_int
    lib.rtp_buffer_to_host.argtypes = [i64, ctypes.c_void_p, i64,
                                       ctypes.c_char_p, ctypes.c_int]
    lib.rtp_buffer_host_nbytes.restype = i64
    lib.rtp_buffer_host_nbytes.argtypes = [i64]
    lib.rtp_buffer_destroy.argtypes = [i64]
    return lib


def load() -> Optional[ctypes.CDLL]:
    """The PJRT-layer library, or None (unbuildable — e.g. no
    pjrt_c_api.h at build time — or disabled via RAFT_TPU_NATIVE=0)."""
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed or os.environ.get("RAFT_TPU_NATIVE", "1") == "0":
        return None
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        from raft_tpu.core import native
        path = os.path.join(_lib_dir(), _LIB_NAME)
        if not os.path.exists(path):
            if not native._try_build() or not os.path.exists(path):
                _load_failed = True
                return None

        def _open():
            raw = ctypes.CDLL(path)
            try:
                lib = _configure(raw)
                if lib.rtp_abi_version() != _ABI:
                    raise OSError("ABI mismatch")
            except (OSError, AttributeError):
                # release the mapping so a rebuilt .so is re-read, not
                # the stale image (same self-heal as native.load)
                import _ctypes
                _ctypes.dlclose(raw._handle)
                raise
            return lib

        try:
            _lib = _open()
        except (OSError, AttributeError):
            # stale library from an older source revision: rebuild once
            if native._try_build():
                try:
                    _lib = _open()
                except (OSError, AttributeError):
                    _load_failed = True
            else:
                _load_failed = True
        return _lib


def available() -> bool:
    return load() is not None


class NativeMdarray:
    """Owning device buffer with dtype + extents (the mdarray role).
    Create via :meth:`NativeResources.device_put`."""

    def __init__(self, lib, buf_id: int):
        self._lib = lib
        self._id = buf_id

    @property
    def shape(self) -> Tuple[int, ...]:
        nd = self._lib.rtp_buffer_ndim(self._id)
        expects(nd >= 0, "mdarray: destroyed or invalid buffer")
        dims = (ctypes.c_int64 * max(nd, 1))()
        self._lib.rtp_buffer_dims(self._id, dims, nd)
        return tuple(int(dims[i]) for i in range(nd))

    @property
    def dtype(self) -> np.dtype:
        t = self._lib.rtp_buffer_dtype(self._id)
        expects(t in _PJRT_TO_DTYPE, "mdarray: unmapped PJRT dtype %s", t)
        return _PJRT_TO_DTYPE[t]

    def ready(self) -> bool:
        """Non-blocking readiness poll (interruptible's poll step)."""
        rc = self._lib.rtp_buffer_ready(self._id)
        expects(rc >= 0, "mdarray.ready: invalid buffer")
        return rc == 1

    def sync(self) -> None:
        """Block until the buffer is ready (stream_syncer semantics)."""
        expects(self._lib.rtp_buffer_sync(self._id) == 0,
                "mdarray.sync failed")

    def to_numpy(self) -> np.ndarray:
        nbytes = self._lib.rtp_buffer_host_nbytes(self._id)
        expects(nbytes >= 0, "mdarray.to_numpy: invalid buffer")
        out = np.empty(self.shape, self.dtype)
        expects(out.nbytes >= nbytes, "mdarray.to_numpy: size mismatch")
        err = ctypes.create_string_buffer(512)
        rc = self._lib.rtp_buffer_to_host(
            self._id, out.ctypes.data_as(ctypes.c_void_p), out.nbytes,
            err, len(err))
        expects(rc == 0, "mdarray.to_numpy: %s",
                err.value.decode(errors="replace"))
        return out

    def destroy(self) -> None:
        if self._id:
            self._lib.rtp_buffer_destroy(self._id)
            self._id = 0

    def __del__(self):  # best-effort; explicit destroy() preferred
        try:
            self.destroy()
        except Exception:
            pass


def encode_create_options(options: dict) -> str:
    """Encode client create-options for the C layer's flat spec
    (``name=T:value`` entries joined by ';'; T ∈ s|i|f|b from the
    Python type). Real plugins may require options — e.g. libtpu's
    occupancy knobs — mirroring
    jax's ``register_plugin(options=...)``."""
    parts = []
    for name, v in options.items():
        expects(";" not in str(name) and "=" not in str(name),
                "create option name %r has reserved chars", name)
        if isinstance(v, bool):
            parts.append(f"{name}=b:{int(v)}")
        elif isinstance(v, int):
            parts.append(f"{name}=i:{v}")
        elif isinstance(v, float):
            parts.append(f"{name}=f:{v}")
        else:
            s = str(v)
            expects(";" not in s,
                    "create option %s value has reserved ';'", name)
            parts.append(f"{name}=s:{s}")
    return ";".join(parts)


class NativeResources:
    """The C++ handle_t analogue: owns a PJRT client + device list
    created from ``plugin_path`` through the stable C ABI.
    ``options``: PJRT client create-options (NamedValues), as jax's
    ``register_plugin(options=...)``."""

    def __init__(self, plugin_path: str, options: Optional[dict] = None):
        lib = load()
        expects(lib is not None, "PJRT native layer unavailable "
                "(library not built; see cpp/build.sh)")
        self._lib = lib
        err = ctypes.create_string_buffer(512)
        spec = encode_create_options(options or {})
        self._id = lib.rtp_resources_create_opts(
            plugin_path.encode(), spec.encode(), err, len(err))
        expects(self._id > 0, "NativeResources: %s",
                err.value.decode(errors="replace"))

    @property
    def platform_name(self) -> str:
        buf = ctypes.create_string_buffer(128)
        n = self._lib.rtp_platform_name(self._id, buf, len(buf))
        expects(n >= 0, "platform_name failed")
        return buf.value.decode()

    @property
    def api_version(self) -> Tuple[int, int]:
        ma, mi = ctypes.c_int(), ctypes.c_int()
        expects(self._lib.rtp_api_version(
            self._id, ctypes.byref(ma), ctypes.byref(mi)) == 0,
            "api_version failed")
        return int(ma.value), int(mi.value)

    @property
    def process_index(self) -> int:
        return int(self._lib.rtp_process_index(self._id))

    def device_count(self) -> int:
        return int(self._lib.rtp_device_count(self._id))

    def device_ids(self):
        return [int(self._lib.rtp_device_id(self._id, i))
                for i in range(self.device_count())]

    def device_put(self, array, device_index: int = 0) -> NativeMdarray:
        """Host → device: create an owning mdarray on device
        ``device_index`` (reference make_device_matrix + copy)."""
        a = np.ascontiguousarray(array)
        expects(a.dtype in _DTYPE_TO_PJRT,
                "device_put: unsupported dtype %s", a.dtype)
        dims = (ctypes.c_int64 * max(a.ndim, 1))(*a.shape)
        err = ctypes.create_string_buffer(512)
        bid = self._lib.rtp_buffer_from_host(
            self._id, a.ctypes.data_as(ctypes.c_void_p),
            _DTYPE_TO_PJRT[a.dtype], dims, a.ndim, device_index,
            err, len(err))
        expects(bid > 0, "device_put: %s",
                err.value.decode(errors="replace"))
        return NativeMdarray(self._lib, bid)

    def close(self) -> None:
        if self._id:
            self._lib.rtp_resources_destroy(self._id)
            self._id = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
