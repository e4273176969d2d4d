"""ctypes bindings for the native C++ runtime (csrc/acclrt.cpp).

The reference's host driver is C++ (driver/xrt, ~4.3k LoC); this package is
its TPU-native counterpart's native core: matching engine, sequence
counters, request registry and timer live in ``libacclrt.so``, built
on demand with g++ (no pybind11 in the image — plain C ABI + ctypes).

``load()`` returns the bound library or None; callers (``sendrecv.
MatchingEngine``) fall back to the pure-Python implementation so the
framework works without a toolchain.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

_SRC = Path(__file__).resolve().parent.parent.parent / "csrc" / "acclrt.cpp"
_BUILD_DIR = Path(__file__).resolve().parent / "_build"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

#: match result sentinels (keep in sync with acclrt.cpp)
NO_MATCH = -1
ERR_COUNT_MISMATCH = -2


def _lib_path() -> Path:
    """The built library, keyed on a hash of the source's CONTENTS: a
    copied tree carries stale untracked builds with fresh mtimes, so
    only the contents say whether a build matches the source."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _BUILD_DIR / f"libacclrt-{digest}.so"


def _compile() -> Optional[Path]:
    """Path of a library built from the current source, or None."""
    if not _SRC.exists():
        return None
    try:
        lib = _lib_path()
        if lib.exists():
            return lib
        _BUILD_DIR.mkdir(parents=True, exist_ok=True)
        # build to a process-private path, then rename atomically so a
        # concurrent process can never dlopen a partially written library
        tmp = _BUILD_DIR / f".libacclrt.{os.getpid()}.so"
        try:
            subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                 str(_SRC), "-o", str(tmp)],
                check=True, capture_output=True, timeout=120,
            )
            os.replace(tmp, lib)
        finally:
            tmp.unlink(missing_ok=True)
        return lib
    except (subprocess.SubprocessError, OSError):
        return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.accl_engine_create.restype = c.c_void_p
    lib.accl_engine_destroy.argtypes = [c.c_void_p]
    lib.accl_post_send.restype = c.c_int64
    lib.accl_post_send.argtypes = [c.c_void_p, c.c_int32, c.c_int32,
                                   c.c_int64, c.c_int64,
                                   c.POINTER(c.c_int64), c.POINTER(c.c_int64),
                                   c.POINTER(c.c_int64)]
    lib.accl_post_recv.restype = c.c_int64
    lib.accl_post_recv.argtypes = [c.c_void_p, c.c_int32, c.c_int32,
                                   c.c_int64, c.c_int64,
                                   c.POINTER(c.c_int64), c.c_int32,
                                   c.POINTER(c.c_int32), c.POINTER(c.c_int64)]
    lib.accl_recv_capacity.restype = c.c_int64
    lib.accl_recv_capacity.argtypes = [c.c_void_p, c.c_int32, c.c_int32,
                                       c.c_int64]
    lib.accl_remove_recv.restype = c.c_int32
    lib.accl_remove_recv.argtypes = [c.c_void_p, c.c_int64]
    lib.accl_abort_send.restype = c.c_int32
    lib.accl_abort_send.argtypes = [c.c_void_p, c.c_int64]
    lib.accl_clear.argtypes = [c.c_void_p]
    for name in ("accl_pending_sends", "accl_pending_recvs"):
        fn = getattr(lib, name)
        fn.restype = c.c_int64
        fn.argtypes = [c.c_void_p]
    for name in ("accl_outbound_seq", "accl_inbound_seq"):
        fn = getattr(lib, name)
        fn.restype = c.c_int64
        fn.argtypes = [c.c_void_p, c.c_int32, c.c_int32]
    lib.accl_req_create.restype = c.c_int64
    lib.accl_req_create.argtypes = [c.c_void_p]
    lib.accl_req_complete.argtypes = [c.c_void_p, c.c_int64, c.c_int32]
    lib.accl_req_duration_ns.restype = c.c_uint64
    lib.accl_req_duration_ns.argtypes = [c.c_void_p, c.c_int64]
    lib.accl_req_status.restype = c.c_int32
    lib.accl_req_status.argtypes = [c.c_void_p, c.c_int64]
    lib.accl_req_free.argtypes = [c.c_void_p, c.c_int64]
    lib.accl_now_ns.restype = c.c_uint64
    # rx-buffer pool
    lib.accl_pool_create.restype = c.c_void_p
    lib.accl_pool_create.argtypes = [c.c_int32]
    lib.accl_pool_destroy.argtypes = [c.c_void_p]
    lib.accl_pool_reserve.restype = c.c_int32
    lib.accl_pool_reserve.argtypes = [c.c_void_p, c.c_int32, c.c_int32,
                                      c.c_int64, c.c_int64, c.c_int64]
    for name in ("accl_pool_mark_reserved", "accl_pool_release"):
        fn = getattr(lib, name)
        fn.restype = c.c_int32
        fn.argtypes = [c.c_void_p, c.c_int32]
    for name in ("accl_pool_free_slots", "accl_pool_size"):
        fn = getattr(lib, name)
        fn.restype = c.c_int32
        fn.argtypes = [c.c_void_p]
    lib.accl_pool_slot_info.restype = c.c_int32
    lib.accl_pool_slot_info.argtypes = [c.c_void_p, c.c_int32,
                                        c.POINTER(c.c_int64)]
    lib.accl_pool_clear.argtypes = [c.c_void_p]
    # cooperative call queue
    lib.accl_cq_create.restype = c.c_void_p
    lib.accl_cq_destroy.argtypes = [c.c_void_p]
    lib.accl_cq_push_new.argtypes = [c.c_void_p, c.c_int64]
    lib.accl_cq_push_retry.argtypes = [c.c_void_p, c.c_int64, c.c_int64]
    lib.accl_cq_pop.restype = c.c_int32
    lib.accl_cq_pop.argtypes = [c.c_void_p, c.POINTER(c.c_int64),
                                c.POINTER(c.c_int64)]
    lib.accl_cq_depths.argtypes = [c.c_void_p, c.POINTER(c.c_int64),
                                   c.POINTER(c.c_int64)]
    lib.accl_cq_clear.argtypes = [c.c_void_p]
    return lib


def load() -> Optional[ctypes.CDLL]:
    """Compile (once) and load libacclrt.so; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("ACCL_NO_NATIVE"):
            return None
        lib = _compile()
        if lib is not None:
            try:
                _lib = _bind(ctypes.CDLL(str(lib)))
            except OSError:
                _lib = None
        return _lib


def available() -> bool:
    return load() is not None


class NativeEngine:
    """Thin RAII wrapper over one native engine instance."""

    def __init__(self):
        lib = load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = ctypes.c_void_p(lib.accl_engine_create())

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.accl_engine_destroy(self._h)
                self._h = None
        except Exception:
            pass

    # matching ----------------------------------------------------------
    def post_send(self, src: int, dst: int, tag: int, count: int):
        """Returns (send id, matched recv id or NO_MATCH, assigned seqn,
        matched recv's remaining element count or -1)."""
        out = ctypes.c_int64(NO_MATCH)
        seqn = ctypes.c_int64(-1)
        rem = ctypes.c_int64(-1)
        sid = self._lib.accl_post_send(self._h, src, dst, tag, count,
                                       ctypes.byref(out), ctypes.byref(seqn),
                                       ctypes.byref(rem))
        return sid, out.value, seqn.value, rem.value

    def post_recv(self, src: int, dst: int, tag: int, count: int):
        """Returns (recv id, [consumed send ids] in seqn order, remaining).

        The id buffer is sized by the number of parked sends, not the
        element count (at most that many segments can match); the C++ side
        stops consuming when the buffer fills, so ids are never dropped.
        """
        cap = max(min(int(count), self._lib.accl_pending_sends(self._h)), 1)
        ids = (ctypes.c_int64 * cap)()
        n = ctypes.c_int32(0)
        rem = ctypes.c_int64(count)
        rid = self._lib.accl_post_recv(self._h, src, dst, tag, count,
                                       ids, cap, ctypes.byref(n),
                                       ctypes.byref(rem))
        return rid, list(ids[: n.value]), rem.value

    def recv_capacity(self, src: int, dst: int, tag: int) -> int:
        """Remaining elements of the first eligible parked recv, or -1."""
        return self._lib.accl_recv_capacity(self._h, src, dst, tag)

    def remove_recv(self, rid: int) -> bool:
        return bool(self._lib.accl_remove_recv(self._h, rid))

    def abort_send(self, sid: int) -> bool:
        """Abort a parked send segment: removed AND counted consumed (the
        inbound cursor advances past its seqn) so the pair stream never
        strands on the hole a PEER_FAILED-retired message would leave.
        False when the segment is not the next-expected one."""
        return bool(self._lib.accl_abort_send(self._h, sid))

    def clear(self) -> None:
        self._lib.accl_clear(self._h)

    def pending(self):
        return (self._lib.accl_pending_sends(self._h),
                self._lib.accl_pending_recvs(self._h))

    def outbound_seq(self, src: int, dst: int) -> int:
        return self._lib.accl_outbound_seq(self._h, src, dst)

    def inbound_seq(self, src: int, dst: int) -> int:
        return self._lib.accl_inbound_seq(self._h, src, dst)

    # requests ----------------------------------------------------------
    def req_create(self) -> int:
        return self._lib.accl_req_create(self._h)

    def req_complete(self, rid: int, retcode: int = 0) -> None:
        self._lib.accl_req_complete(self._h, rid, retcode)

    def req_duration_ns(self, rid: int) -> int:
        return self._lib.accl_req_duration_ns(self._h, rid)

    def req_status(self, rid: int) -> int:
        return self._lib.accl_req_status(self._h, rid)

    def req_free(self, rid: int) -> None:
        self._lib.accl_req_free(self._h, rid)


#: rx-buffer slot lifecycle (rxbuf_enqueue.cpp:50-74; keep in sync with
#: acclrt.cpp SlotStatus)
SLOT_IDLE = 0
SLOT_ENQUEUED = 1
SLOT_RESERVED = 2


class NativePool:
    """RAII wrapper over the native eager rx-buffer pool."""

    def __init__(self, nslots: int):
        lib = load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = ctypes.c_void_p(lib.accl_pool_create(nslots))

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.accl_pool_destroy(self._h)
                self._h = None
        except Exception:
            pass

    def reserve(self, src: int, dst: int, tag: int, seqn: int,
                count: int) -> int:
        return self._lib.accl_pool_reserve(self._h, src, dst, tag, seqn, count)

    def mark_reserved(self, slot: int) -> bool:
        return bool(self._lib.accl_pool_mark_reserved(self._h, slot))

    def release(self, slot: int) -> bool:
        return bool(self._lib.accl_pool_release(self._h, slot))

    @property
    def free_slots(self) -> int:
        return self._lib.accl_pool_free_slots(self._h)

    @property
    def size(self) -> int:
        return self._lib.accl_pool_size(self._h)

    def slot_info(self, i: int):
        """(status, src, dst, tag, seqn, count) or None for a bad index."""
        out = (ctypes.c_int64 * 6)()
        if not self._lib.accl_pool_slot_info(self._h, i, out):
            return None
        return tuple(out)

    def clear(self) -> None:
        self._lib.accl_pool_clear(self._h)


class NativeCallQueue:
    """RAII wrapper over the native cooperative call queue."""

    def __init__(self):
        lib = load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = ctypes.c_void_p(lib.accl_cq_create())

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.accl_cq_destroy(self._h)
                self._h = None
        except Exception:
            pass

    def push_new(self, call_id: int) -> None:
        self._lib.accl_cq_push_new(self._h, call_id)

    def push_retry(self, call_id: int, current_step: int) -> None:
        self._lib.accl_cq_push_retry(self._h, call_id, current_step)

    def pop(self):
        """(call_id, current_step) or None when both queues are empty."""
        cid = ctypes.c_int64()
        step = ctypes.c_int64()
        if not self._lib.accl_cq_pop(self._h, ctypes.byref(cid),
                                     ctypes.byref(step)):
            return None
        return cid.value, step.value

    @property
    def depths(self):
        nf = ctypes.c_int64()
        nr = ctypes.c_int64()
        self._lib.accl_cq_depths(self._h, ctypes.byref(nf), ctypes.byref(nr))
        return nf.value, nr.value

    def clear(self) -> None:
        self._lib.accl_cq_clear(self._h)


def now_ns() -> int:
    lib = load()
    if lib is None:
        import time
        return time.monotonic_ns()
    return lib.accl_now_ns()
