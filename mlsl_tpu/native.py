"""ctypes binding to the native control plane (native/libmlsl_core.so).

Mirrors the reference's binding pattern (flat C API src/c_bind.cpp consumed by a
ctypes module include/mlsl/mlsl.py): the C++ library owns the grid math, the five-case
selection, block layouts, parameter partitioning, the priority dispatch queue and
request storage; Python owns the XLA data plane. The library is built on demand with
the in-image toolchain. With no toolchain and no library, ``load()`` says so and
returns None, and callers fall back to the pure-Python implementations (both are
tested for agreement); a failed build beside an existing library is an error,
never a silent load of whatever was left on disk.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

from mlsl_tpu.log import MLSLError, log_warning

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libmlsl_core.so")

_lib = None
_lib_lock = threading.Lock()
_load_failed = False
_built_this_run = False


class Block(ctypes.Structure):
    _fields_ = [
        ("mb_offset", ctypes.c_int64),
        ("mb_count", ctypes.c_int64),
        ("fm_offset", ctypes.c_int64),
        ("fm_count", ctypes.c_int64),
        ("fm_size", ctypes.c_int64),
        ("buf_offset", ctypes.c_int64),
    ]


class ParamPart(ctypes.Structure):
    _fields_ = [
        ("local_kernel_count", ctypes.c_int64),
        ("owned_kernel_count", ctypes.c_int64),
        ("need_comm", ctypes.c_int64),
    ]


def _declare(lib) -> None:
    i64, u64, ip = ctypes.c_int64, ctypes.c_uint64, ctypes.POINTER(ctypes.c_int64)
    lib.mlsl_grid_coords.argtypes = [i64, i64, i64, i64, ip]
    lib.mlsl_grid_coords.restype = ctypes.c_int
    lib.mlsl_grid_rank.argtypes = [ip, i64, i64, i64]
    lib.mlsl_grid_rank.restype = i64
    lib.mlsl_grid_colors.argtypes = [i64, i64, i64, ip, ip, ip]
    lib.mlsl_grid_colors.restype = ctypes.c_int
    lib.mlsl_select_case.argtypes = [
        ctypes.c_int, ctypes.c_int, i64, i64, i64, i64, i64,
    ]
    lib.mlsl_select_case.restype = ctypes.c_int
    bp = ctypes.POINTER(Block)
    for name in (
        "mlsl_blocks_pack_reduce_scatter",
        "mlsl_blocks_pack_reduce_scatter2",
        "mlsl_blocks_unpack_allgather",
        "mlsl_blocks_unpack_allgather2",
    ):
        fn = getattr(lib, name)
        fn.argtypes = [i64, i64, i64, i64, bp]
        fn.restype = ctypes.c_int
    lib.mlsl_blocks_alltoall.argtypes = [i64, i64, i64, i64, i64, i64, bp]
    lib.mlsl_blocks_alltoall.restype = i64
    lib.mlsl_param_partition.argtypes = [
        i64, i64, i64, ctypes.c_int, ctypes.POINTER(ParamPart),
    ]
    lib.mlsl_param_partition.restype = ctypes.c_int
    lib.mlsl_sched_create.argtypes = [i64, ctypes.c_int]
    lib.mlsl_sched_create.restype = ctypes.c_void_p
    lib.mlsl_sched_destroy.argtypes = [ctypes.c_void_p]
    lib.mlsl_sched_submit.argtypes = [ctypes.c_void_p, u64, i64]
    lib.mlsl_sched_submit.restype = ctypes.c_int
    lib.mlsl_sched_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(u64)]
    lib.mlsl_sched_next.restype = ctypes.c_int
    lib.mlsl_sched_pending.argtypes = [ctypes.c_void_p]
    lib.mlsl_sched_pending.restype = i64
    lib.mlsl_reqstore_create.restype = ctypes.c_void_p
    lib.mlsl_reqstore_destroy.argtypes = [ctypes.c_void_p]
    lib.mlsl_reqstore_register.argtypes = [ctypes.c_void_p, u64]
    lib.mlsl_reqstore_remove.argtypes = [ctypes.c_void_p, u64]
    lib.mlsl_reqstore_size.argtypes = [ctypes.c_void_p]
    lib.mlsl_reqstore_size.restype = i64
    lib.mlsl_core_version.restype = ctypes.c_char_p


def _so_mtime() -> Optional[float]:
    try:
        return os.stat(_SO_PATH).st_mtime
    except FileNotFoundError:
        return None


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library. None = no library and
    no way to build one (the pure-Python paths serve); raises MLSLError when
    the build fails beside an existing library, which may be stale."""
    global _lib, _load_failed, _built_this_run
    with _lib_lock:
        if _lib is not None or _load_failed:
            return _lib
        before = _so_mtime()
        try:
            # Always run make: a no-op when the .so is current, a rebuild when the
            # sources changed (a stale library would fail _declare below).
            subprocess.run(
                ["make", "-s", "libmlsl_core.so"], cwd=_NATIVE_DIR, check=True,
                capture_output=True, timeout=120,
            )
        except (subprocess.SubprocessError, OSError) as e:
            detail = getattr(e, "stderr", b"") or b""
            if before is not None:
                raise MLSLError(
                    f"native build failed beside an existing {_SO_PATH} "
                    f"(possibly stale; refusing to load it): {e} "
                    f"{detail.decode(errors='replace')[-400:]}"
                ) from e
            log_warning("native build failed, using pure-Python paths: %s", e)
            _load_failed = True
            return None
        _built_this_run = _so_mtime() != before
        try:
            lib = ctypes.CDLL(_SO_PATH)
            _declare(lib)
            assert lib.mlsl_core_version().decode().startswith("mlsl_core")
            _lib = lib
        except (OSError, AssertionError, AttributeError) as e:
            log_warning("native load failed, using pure-Python paths: %s", e)
            _load_failed = True
        return _lib


def status() -> dict:
    """What load() did in this process: whether the library is loaded and
    whether make (re)built it here or found it current."""
    return {"loaded": _lib is not None, "built_this_run": _built_this_run,
            "path": _SO_PATH}


class NativeScheduler:
    """Priority dispatch queue backed by the C++ scheduler."""

    def __init__(self, threshold: int, lifo: bool):
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core unavailable")
        self.params = (threshold, lifo)
        self._h = self._lib.mlsl_sched_create(int(threshold), 1 if lifo else 0)

    def submit(self, req_id: int, nbytes: int) -> bool:
        """True = dispatch immediately; False = deferred."""
        return bool(self._lib.mlsl_sched_submit(self._h, req_id, int(nbytes)))

    def drain(self):
        out = []
        rid = ctypes.c_uint64()
        while self._lib.mlsl_sched_next(self._h, ctypes.byref(rid)):
            out.append(int(rid.value))
        return out

    def pending(self) -> int:
        return int(self._lib.mlsl_sched_pending(self._h))

    def __del__(self):
        try:
            if getattr(self, "_h", None) and self._lib is not None:
                self._lib.mlsl_sched_destroy(self._h)
        except Exception:  # mlsl-lint: disable=A205 -- interpreter teardown:
            pass           # __del__ may run after the lib is unloaded
