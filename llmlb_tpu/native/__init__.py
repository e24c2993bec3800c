"""ctypes bindings to the C++ native components (native/libllmlb_native.so).

The library is built with `make -C native` by `ensure_native_built()` at
process start-up. Every consumer has a pure-Python fallback, so the framework
runs without the native build — but weight loading and SSE accounting use the
native paths when available. A build that FAILS means the Python paths for
that process: a library left on disk by some earlier tree is never loaded in
its place.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

log = logging.getLogger("llmlb_tpu.native")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libllmlb_native.so")

_lib: ctypes.CDLL | None = None
_lib_lock = threading.Lock()
_build_ok: bool | None = None  # None until ensure_native_built() has run


def _configure(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.st_open.restype = c.c_void_p
    lib.st_open.argtypes = [c.c_char_p]
    lib.st_error.restype = c.c_char_p
    lib.st_error.argtypes = [c.c_void_p]
    lib.st_num_tensors.restype = c.c_int64
    lib.st_num_tensors.argtypes = [c.c_void_p]
    lib.st_tensor_name.restype = c.c_char_p
    lib.st_tensor_name.argtypes = [c.c_void_p, c.c_int64]
    lib.st_tensor_dtype.restype = c.c_char_p
    lib.st_tensor_dtype.argtypes = [c.c_void_p, c.c_int64]
    lib.st_tensor_ndim.restype = c.c_int64
    lib.st_tensor_ndim.argtypes = [c.c_void_p, c.c_int64]
    lib.st_tensor_shape.restype = None
    lib.st_tensor_shape.argtypes = [c.c_void_p, c.c_int64, c.POINTER(c.c_int64)]
    lib.st_tensor_data.restype = c.c_void_p
    lib.st_tensor_data.argtypes = [c.c_void_p, c.c_int64, c.POINTER(c.c_int64)]
    lib.st_close.restype = None
    lib.st_close.argtypes = [c.c_void_p]

    lib.sha256_hex.restype = None
    lib.sha256_hex.argtypes = [c.c_char_p, c.c_int64, c.c_char_p]
    lib.chain_hash_hex.restype = None
    lib.chain_hash_hex.argtypes = [
        c.c_char_p, c.POINTER(c.c_char_p), c.POINTER(c.c_int64), c.c_int64,
        c.c_char_p,
    ]

    # Router core (scheduler hot path)
    lib.rc_new.restype = c.c_void_p
    lib.rc_new.argtypes = [c.c_double]
    lib.rc_free.restype = None
    lib.rc_free.argtypes = [c.c_void_p]
    lib.rc_update_tps.restype = None
    lib.rc_update_tps.argtypes = [
        c.c_void_p, c.c_char_p, c.c_char_p, c.c_char_p,
        c.c_int64, c.c_double, c.c_double,
    ]
    lib.rc_seed_tps.restype = None
    lib.rc_seed_tps.argtypes = [
        c.c_void_p, c.c_char_p, c.c_char_p, c.c_char_p,
        c.c_double, c.c_int64, c.c_double,
    ]
    lib.rc_get_tps.restype = c.c_double
    lib.rc_get_tps.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, c.c_char_p]
    lib.rc_clear_endpoint.restype = None
    lib.rc_clear_endpoint.argtypes = [c.c_void_p, c.c_char_p]
    lib.rc_tracked_keys.restype = c.c_int64
    lib.rc_tracked_keys.argtypes = [c.c_void_p]
    lib.rc_begin.restype = None
    lib.rc_begin.argtypes = [c.c_void_p, c.c_char_p]
    lib.rc_release.restype = None
    lib.rc_release.argtypes = [c.c_void_p, c.c_char_p]
    lib.rc_active.restype = c.c_int64
    lib.rc_active.argtypes = [c.c_void_p, c.c_char_p]
    lib.rc_total_active.restype = c.c_int64
    lib.rc_total_active.argtypes = [c.c_void_p]
    lib.rc_total_requests.restype = c.c_int64
    lib.rc_total_requests.argtypes = [c.c_void_p]
    lib.rc_select.restype = c.c_int64
    lib.rc_select.argtypes = [
        c.c_void_p, c.c_char_p, c.POINTER(c.c_char_p),
        c.POINTER(c.c_double), c.c_int64, c.c_int64, c.c_char_p, c.c_int,
    ]
    lib.rc_snapshot.restype = c.c_int64
    lib.rc_snapshot.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.rc_tps_info.restype = c.c_int32
    lib.rc_tps_info.argtypes = [
        c.c_void_p, c.c_char_p, c.c_char_p, c.c_char_p,
        c.POINTER(c.c_double), c.POINTER(c.c_int64),
        c.POINTER(c.c_double),
    ]
    # Consistent-hash owner + constant-time compare (proxy hot path)
    lib.hrw_select.restype = c.c_int64
    lib.hrw_select.argtypes = [
        c.c_char_p, c.POINTER(c.c_char_p), c.c_int64,
    ]
    lib.ct_equal.restype = c.c_int32
    lib.ct_equal.argtypes = [c.c_char_p, c.c_int64, c.c_char_p, c.c_int64]

    lib.sse_new.restype = c.c_void_p
    lib.sse_feed.restype = None
    lib.sse_feed.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.sse_frames.restype = c.c_int64
    lib.sse_frames.argtypes = [c.c_void_p]
    lib.sse_usage.restype = c.c_int32
    lib.sse_usage.argtypes = [
        c.c_void_p, c.POINTER(c.c_int64), c.POINTER(c.c_int64)
    ]
    lib.sse_free.restype = None
    lib.sse_free.argtypes = [c.c_void_p]


def ensure_native_built() -> bool:
    """Build the library from this tree's sources. BLOCKING (runs make):
    call this from process startup (server mains, test setup), never from a
    request path. False when the build failed — load_native() then returns
    None for the life of the process, whatever library is on disk."""
    global _build_ok
    with _lib_lock:
        if _build_ok is None:
            try:
                # Always invoke make: its dependency tracking rebuilds the
                # .so when the C++ sources changed and is a near-no-op when
                # fresh.
                subprocess.run(
                    ["make", "-C", _NATIVE_DIR],
                    check=True, capture_output=True, timeout=120,
                )
                _build_ok = os.path.exists(_LIB_PATH)
            except (OSError, subprocess.SubprocessError) as e:
                _build_ok = False
                log.warning("native build failed (%s); using the Python "
                            "paths", e)
        return _build_ok


def load_native() -> ctypes.CDLL | None:
    """Load the already-built native library; None if unavailable. Does NOT
    build — ensure_native_built() does that at process startup."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _build_ok is False or not os.path.exists(_LIB_PATH):
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            _configure(lib)
            _lib = lib
        except (OSError, AttributeError) as e:
            # AttributeError: a symbol this tree declares is missing, i.e.
            # the file was built from other sources
            log.warning("failed to load native library: %s", e)
            return None
        return _lib


# ---------------------------------------------------------------- safetensors

_ST_DTYPES = {
    "F64": "float64", "F32": "float32", "F16": "float16", "BF16": "bfloat16",
    "I64": "int64", "I32": "int32", "I16": "int16", "I8": "int8",
    "U8": "uint8", "BOOL": "bool",
}


class NativeSafetensors:
    """Zero-copy reader over one .safetensors file via the C++ mmap reader."""

    def __init__(self, path: str):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = lib.st_open(path.encode())
        err = lib.st_error(self._handle)
        if err:
            message = err.decode()
            lib.st_close(self._handle)
            self._handle = None
            raise ValueError(f"safetensors open failed: {message}")
        self._index: dict[str, int] = {}
        for i in range(lib.st_num_tensors(self._handle)):
            self._index[lib.st_tensor_name(self._handle, i).decode()] = i

    def keys(self):
        return list(self._index)

    def get_tensor(self, name: str):
        """Owned array (safe after close). The mmap view is copied exactly
        once here; async device transfers (jax.device_put retains the numpy
        array, not this reader) must never alias the mapping, which is
        unmapped when the reader is dropped."""
        import numpy as np

        return np.array(self._view(name))

    def _view(self, name: str):
        import ml_dtypes  # ships with jax; provides numpy bfloat16
        import numpy as np

        i = self._index[name]
        lib = self._lib
        dtype_tag = lib.st_tensor_dtype(self._handle, i).decode()
        ndim = lib.st_tensor_ndim(self._handle, i)
        shape = (ctypes.c_int64 * max(ndim, 1))()
        lib.st_tensor_shape(self._handle, i, shape)
        nbytes = ctypes.c_int64()
        ptr = lib.st_tensor_data(self._handle, i, ctypes.byref(nbytes))
        buf = (ctypes.c_char * nbytes.value).from_address(ptr)
        dtype_name = _ST_DTYPES.get(dtype_tag)
        if dtype_name is None:
            raise ValueError(f"unsupported safetensors dtype {dtype_tag}")
        np_dtype = (
            ml_dtypes.bfloat16 if dtype_name == "bfloat16"
            else np.dtype(dtype_name)
        )
        arr = np.frombuffer(buf, dtype=np_dtype)
        return arr.reshape(tuple(shape[d] for d in range(ndim)))

    def close(self):
        # getattr: __init__ may raise before _handle is assigned (native lib
        # unavailable) and __del__ still runs on the half-constructed object.
        handle = getattr(self, "_handle", None)
        if handle is not None:
            self._lib.st_close(handle)
            self._handle = None

    def __del__(self):
        self.close()


# ----------------------------------------------------------------- hash chain


def native_chain_hash(prev_hash_hex: str, entries: list[bytes]) -> str | None:
    lib = load_native()
    if lib is None:
        return None
    n = len(entries)
    arr = (ctypes.c_char_p * n)(*entries)
    lens = (ctypes.c_int64 * n)(*[len(e) for e in entries])
    out = ctypes.create_string_buffer(65)
    lib.chain_hash_hex(prev_hash_hex.encode(), arr, lens, n, out)
    return out.value.decode()


# ------------------------------------------------------------------ SSE scan


class NativeSseScanner:
    def __init__(self):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = lib.sse_new()

    def feed(self, chunk: bytes) -> None:
        self._lib.sse_feed(self._handle, chunk, len(chunk))

    @property
    def frames(self) -> int:
        return self._lib.sse_frames(self._handle)

    def usage(self) -> tuple[int, int] | None:
        pt = ctypes.c_int64()
        ct = ctypes.c_int64()
        if self._lib.sse_usage(self._handle, ctypes.byref(pt), ctypes.byref(ct)):
            return int(pt.value), int(ct.value)
        return None

    def __del__(self):
        if getattr(self, "_handle", None):
            self._lib.sse_free(self._handle)
            self._handle = None


# ------------------------------------------------- hot-path micro primitives


def native_hrw_available() -> bool:
    return load_native() is not None


def native_hrw_select(key: str, endpoint_ids: list[str]) -> int:
    """Index of the consistent-hash (rendezvous) owner of `key` among
    `endpoint_ids`; -1 for an empty list. Bit-identical to
    balancer.hrw_owner — tested side by side."""
    lib = load_native()
    n = len(endpoint_ids)
    if lib is None or n == 0:
        return -1
    arr = (ctypes.c_char_p * n)(*[e.encode() for e in endpoint_ids])
    return lib.hrw_select(key.encode(), arr, n)


def native_ct_equal(a: bytes, b: bytes) -> bool | None:
    """Constant-time byte equality in compiled code; None when the native
    library is unavailable — callers fall back to hmac.compare_digest."""
    lib = load_native()
    if lib is None:
        return None
    return bool(lib.ct_equal(a, len(a), b, len(b)))


# ---------------------------------------------------------------- router core


class NativeRouterCore:
    """C++ scheduler state: TPS-EMA map + active counts + round-robin
    selection (native/router_core.cpp). Raises RuntimeError when the library
    is unavailable — LoadManager keeps the pure-Python implementation as the
    fallback."""

    def __init__(self, alpha: float):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native router core unavailable")
        self._lib = lib
        self._handle = lib.rc_new(alpha)

    def update_tps(self, eid: str, model: str, kind: str,
                   tokens: int, duration_s: float, now: float) -> None:
        self._lib.rc_update_tps(
            self._handle, eid.encode(), model.encode(), kind.encode(),
            tokens, duration_s, now,
        )

    def seed_tps(self, eid: str, model: str, kind: str,
                 ema: float, samples: int, now: float) -> None:
        self._lib.rc_seed_tps(
            self._handle, eid.encode(), model.encode(), kind.encode(),
            ema, samples, now,
        )

    def get_tps(self, eid: str, model: str, kind: str) -> float | None:
        v = self._lib.rc_get_tps(
            self._handle, eid.encode(), model.encode(), kind.encode()
        )
        return None if v < 0 else v

    def tps_info(self, eid: str, model: str,
                 kind: str) -> tuple[float, int, float] | None:
        """(ema, samples, last_update) or None when unmeasured — feeds the
        cross-worker TPS gossip (publish + last-writer-wins compare)."""
        ema = ctypes.c_double()
        samples = ctypes.c_int64()
        last = ctypes.c_double()
        got = self._lib.rc_tps_info(
            self._handle, eid.encode(), model.encode(), kind.encode(),
            ctypes.byref(ema), ctypes.byref(samples), ctypes.byref(last),
        )
        if not got:
            return None
        return float(ema.value), int(samples.value), float(last.value)

    def clear_endpoint(self, eid: str) -> None:
        self._lib.rc_clear_endpoint(self._handle, eid.encode())

    def tracked_keys(self) -> int:
        return self._lib.rc_tracked_keys(self._handle)

    def begin(self, eid: str) -> None:
        self._lib.rc_begin(self._handle, eid.encode())

    def release(self, eid: str) -> None:
        self._lib.rc_release(self._handle, eid.encode())

    def active(self, eid: str) -> int:
        return self._lib.rc_active(self._handle, eid.encode())

    def total_active(self) -> int:
        return self._lib.rc_total_active(self._handle)

    def total_requests(self) -> int:
        return self._lib.rc_total_requests(self._handle)

    def select(self, model: str, kind: str, eids: list[str],
               penalties: list[float], cap: int, admit: bool) -> int:
        n = len(eids)
        arr = (ctypes.c_char_p * n)(*[e.encode() for e in eids])
        pens = (ctypes.c_double * n)(*penalties)
        return self._lib.rc_select(
            self._handle, model.encode(), arr, pens, n, cap,
            kind.encode(), 1 if admit else 0,
        )

    def snapshot(self) -> dict[str, dict]:
        # Size-then-fill with a growth retry: the map can gain keys between
        # the two calls (another thread's update_tps), in which case the fill
        # call reports a larger size and we re-read — never parse a
        # truncated buffer.
        needed = self._lib.rc_snapshot(self._handle, None, 0)
        while True:
            if needed <= 0:
                return {}
            cap = needed + 4096  # slack for keys added between calls
            buf = ctypes.create_string_buffer(cap)
            needed = self._lib.rc_snapshot(self._handle, buf, cap)
            if needed <= cap:
                break
        out: dict[str, dict] = {}
        for line in buf.raw[:needed].decode().splitlines():
            eid, model, kind, ema, samples, last_update = line.split("\t")
            out[f"{eid}:{model}:{kind}"] = {
                "ema_tps": round(float(ema), 3),
                "samples": int(samples),
                "last_update": float(last_update),
            }
        return out

    def __del__(self):
        if getattr(self, "_handle", None):
            self._lib.rc_free(self._handle)
            self._handle = None
