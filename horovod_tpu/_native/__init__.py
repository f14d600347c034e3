"""Native runtime core loader (reference N25 build system role, slimmed:
one C++ shared library, built on demand with g++, consumed via ctypes —
pybind11 is deliberately not required).

``lib()`` returns the loaded library or None; callers keep a NumPy
fallback so the framework stays fully functional without a compiler.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

LOG = logging.getLogger("horovod_tpu")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "core.cc")
_SO = os.path.join(_HERE, "libhvdcore.so")
_lock = threading.Lock()
# .so path -> loaded CDLL (or None after a failed attempt). Keyed by path
# because sanitized builds live under their own filenames — a TSan .so
# must never be mtime-fresh enough to serve a later normal-mode run.
_libs: dict = {}

_SANITIZERS = ("address", "thread")


def _sanitize_mode() -> str:
    """Validated HOROVOD_NATIVE_SANITIZE value ("" when unset/invalid)."""
    from ..common import env as env_schema

    v = os.environ.get(env_schema.HOROVOD_NATIVE_SANITIZE, "").strip().lower()
    if v and v not in _SANITIZERS:
        LOG.warning("ignoring HOROVOD_NATIVE_SANITIZE=%r (expected one of %s)",
                    v, "|".join(_SANITIZERS))
        return ""
    return v


def _so_path(mode: str) -> str:
    if not mode:
        return _SO
    return os.path.join(_HERE, f"libhvdcore-{mode[0]}san.so")


def _build(so: str, mode: str) -> bool:
    # N launcher workers on one host all build on first use; the shared
    # atomic-replace helper keeps concurrent g++ runs from truncating
    # each other's output (0o777: .so keeps exec bits under the umask)
    from ..common.util import atomic_tmp

    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17"]
    if mode:
        cmd += [f"-fsanitize={mode}", "-g", "-fno-omit-frame-pointer"]
    try:
        with atomic_tmp(so, mode=0o777) as tmp:
            subprocess.run(
                cmd + ["-o", tmp, _SRC, "-lpthread"],
                check=True, capture_output=True, timeout=120)
        return True
    except Exception as e:
        LOG.warning("native core build failed (%s); using numpy fallback", e)
        return False


def lib():
    """Load (building if needed) the native core; None on any failure.

    ``HOROVOD_NATIVE_SANITIZE=address|thread`` builds/loads an
    instrumented variant instead (loading the ASan variant additionally
    requires libasan in LD_PRELOAD when the interpreter itself is not
    sanitized — see tests/test_native_sanitize.py)."""
    from ..common import env as env_schema

    mode = _sanitize_mode()
    so = _so_path(mode)
    if so in _libs:
        return _libs[so]
    with _lock:
        if so in _libs:
            return _libs[so]
        _libs[so] = None
        if os.environ.get(env_schema.HOROVOD_TPU_DISABLE_NATIVE,
                          "") in ("1", "true"):
            return None
        if not os.path.exists(so) or (
                os.path.exists(_SRC)
                and os.path.getmtime(_SRC) > os.path.getmtime(so)):
            if not _build(so, mode):
                return None
        try:
            L = ctypes.CDLL(so)
            L.hvd_pack.restype = ctypes.c_int64
            L.hvd_pack.argtypes = [ctypes.POINTER(ctypes.c_void_p),
                                   ctypes.POINTER(ctypes.c_int64),
                                   ctypes.c_int, ctypes.c_void_p]
            L.hvd_unpack.restype = ctypes.c_int64
            L.hvd_unpack.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_void_p),
                                     ctypes.POINTER(ctypes.c_int64),
                                     ctypes.c_int]
            L.hvd_tl_create.restype = ctypes.c_void_p
            L.hvd_tl_create.argtypes = [ctypes.c_int64]
            L.hvd_tl_destroy.argtypes = [ctypes.c_void_p]
            L.hvd_tl_push.restype = ctypes.c_int
            L.hvd_tl_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_int64]
            L.hvd_tl_drain.restype = ctypes.c_int64
            L.hvd_tl_drain.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_int64]
            L.hvd_tl_dropped.restype = ctypes.c_int64
            L.hvd_tl_dropped.argtypes = [ctypes.c_void_p]
            if L.hvd_abi_version() != 1:
                return None
            _libs[so] = L
        except Exception as e:
            LOG.warning("native core load failed (%s); using numpy fallback",
                        e)
    return _libs[so]


def _pack_into(arrays, buf) -> None:
    """Batched memcpy of contiguous ``arrays`` into uint8 ``buf`` (native
    parallel memcpy when available, numpy loop otherwise)."""
    import numpy as np

    L = lib()
    if L is None or len(arrays) < 2:
        off = 0
        for a in arrays:
            buf[off:off + a.nbytes] = a.view(np.uint8).reshape(-1)
            off += a.nbytes
    else:
        n = len(arrays)
        srcs = (ctypes.c_void_p * n)(*[a.ctypes.data for a in arrays])
        sizes = (ctypes.c_int64 * n)(*[a.nbytes for a in arrays])
        L.hvd_pack(srcs, sizes, n, buf.ctypes.data)


_PENDING = object()  # slot leased, completion token not yet attached

_staging_handles = None


def _staging_metrics():
    """(acquire{ring}, acquire{alloc}, reuse, inflight gauge) — resolved
    lazily and failure-tolerant so _native never depends on the metrics
    registry being importable."""
    global _staging_handles
    if _staging_handles is None:
        try:
            from ..utils import metrics as metrics_mod

            reg = metrics_mod.get_registry()
            _staging_handles = (
                reg.counter("hvd_staging_acquire_total",
                            "staging buffer acquisitions", source="ring"),
                reg.counter("hvd_staging_acquire_total",
                            "staging buffer acquisitions", source="alloc"),
                reg.counter("hvd_staging_reuse_total",
                            "staging ring slots reused"),
                reg.gauge("hvd_staging_inflight",
                          "staging slots leased or awaiting transfer"),
            )
        except Exception:  # pragma: no cover - metrics always importable
            class _Null:
                def inc(self, n=1):
                    pass

                def set(self, v):
                    pass

            _staging_handles = (_Null(), _Null(), _Null(), _Null())
    return _staging_handles


class _StagingLease:
    """Handle for one leased ring slot. ``retire(token)`` returns the slot:
    with ``token=None`` the slot frees immediately; with a token exposing
    ``is_ready()`` (a jax.Array) the slot stays unavailable until the
    async consumer of the staged bytes has finished with them."""

    __slots__ = ("_ring", "_index", "_done")

    def __init__(self, ring, index):
        self._ring = ring
        self._index = index
        self._done = False

    def retire(self, token=None):
        if self._done:
            return
        self._done = True
        self._ring._retire(self._index, token)


class StagingRing:
    """Ring of persistent host staging buffers for the fusion pack path.

    The legacy ``FusionBuffer.pack`` allocated a fresh buffer per call
    because the eager collective consumes the staged bytes asynchronously
    (the device transfer — or, on the CPU backend, the zero-copy device
    array itself — may alias the host memory). The ring keeps that safety
    with in-flight tracking instead of allocation: a slot is handed out
    again only once its completion token reports ``is_ready()``, i.e. the
    compiled program that read the staged bytes has produced its outputs.
    Slots are allocated lazily at full capacity (grow-only), so an idle
    runtime with a 128 MiB threshold does not pin slots×128 MiB."""

    def __init__(self, nbytes: int, slots: int = 4):
        from ..utils import lockcheck

        self.capacity = max(0, int(nbytes))
        self.slots = max(1, int(slots))
        self._lock = lockcheck.make_lock("native.staging_ring")
        self._bufs = [None] * self.slots  # guarded-by: _lock
        self._tokens = [None] * self.slots  # guarded-by: _lock
        self._used = [False] * self.slots  # guarded-by: _lock

    def _inflight(self) -> int:
        n = 0
        # internal helper: every caller already holds _lock
        for t in self._tokens:  # hvdlint: disable=lock-discipline
            if t is _PENDING:
                n += 1
            elif t is not None and not self._token_done(t):
                n += 1
        return n

    @staticmethod
    def _token_done(token) -> bool:
        try:
            return bool(token.is_ready())
        except Exception:
            return True  # dead/unknown token: don't wedge the slot forever

    def acquire(self, total: int):
        """Lease a slot with >= ``total`` bytes. Returns ``(buf, lease)``
        where ``buf`` is a uint8 view of exactly ``total`` bytes, or
        ``(None, None)`` when no slot fits (oversize chunk or all slots
        busy) — callers fall back to a fresh allocation."""
        import numpy as np

        m = _staging_metrics()
        if total > self.capacity:
            m[1].inc()
            return None, None
        with self._lock:
            for i in range(self.slots):
                t = self._tokens[i]
                if t is _PENDING:
                    continue
                if t is not None and not self._token_done(t):
                    continue
                if self._bufs[i] is None:
                    self._bufs[i] = np.empty(self.capacity, dtype=np.uint8)
                self._tokens[i] = _PENDING
                m[0].inc()
                if self._used[i]:
                    m[2].inc()
                self._used[i] = True
                m[3].set(self._inflight())
                return self._bufs[i][:total], _StagingLease(self, i)
        m[1].inc()
        return None, None

    def _retire(self, index: int, token):
        with self._lock:
            self._tokens[index] = token
            _staging_metrics()[3].set(self._inflight())

    def allocated_bytes(self) -> int:
        """Host bytes currently pinned by lazily-allocated slots (each
        allocated slot holds ``capacity`` bytes regardless of lease
        state) — the memledger's staging_ring component attribution."""
        with self._lock:
            return sum(int(b.nbytes) for b in self._bufs if b is not None)

    def resize(self, nbytes: int):
        """Adopt a new capacity (fusion threshold changed). Existing
        buffers are dropped — in-flight consumers hold their own
        references, so the memory survives until they finish."""
        nbytes = max(0, int(nbytes))
        with self._lock:
            if nbytes == self.capacity:
                return
            self.capacity = nbytes
            self._bufs = [None] * self.slots
            self._tokens = [None] * self.slots
            self._used = [False] * self.slots

    def set_slots(self, slots: int):
        """Adopt a new slot count (autotuner ring-depth knob). Same
        drop-and-release contract as ``resize``: in-flight consumers hold
        their own buffer references, so shrinking never frees bytes a
        pending transfer still reads."""
        slots = max(1, int(slots))
        with self._lock:
            if slots == self.slots:
                return
            self.slots = slots
            self._bufs = [None] * slots
            self._tokens = [None] * slots
            self._used = [False] * slots


def chain_dispatch(buffer: "FusionBuffer", steps):
    """Megaplan steady-state execution: run a captured whole-step chunk
    schedule as ONE chained dispatch through the staging ring.

    ``steps`` is the prebuilt schedule — ``(plan, arrays, on_device)``
    per chunk, in captured order, where ``plan`` is a compiled
    ``collectives.FusedChunkPlan``. Host chunks stage through a leased
    ring slot (native parallel memcpy when the core is built — the
    mandatory numpy fallback rides ``_pack_into``) and the lease retires
    on the chunk's first output token, exactly the per-chunk contract of
    ``ops/queue.py``; device chunks launch their compiled program
    directly. No negotiation, no grouping, no plan lookup — the per-step
    Python the megaplan eliminates.

    Returns ``(outs, exc)``: ``outs`` holds the per-chunk output lists
    for every chunk that fully dispatched; ``exc`` is the failure that
    stopped the chain (None on success). A mid-chain failure retires the
    failing chunk's lease with ``None`` (the ring is never left torn)
    and stops — the caller fails the remaining entries and degrades to
    negotiated mode."""
    outs = []
    for plan, arrays, on_device in steps:
        try:
            if on_device:
                outs.append(plan.execute(arrays))
                continue
            flat, lease = buffer.pack_leased(arrays)
            try:
                parts = plan.execute(flat)
            except Exception:
                if lease is not None:
                    lease.retire(None)
                raise
            if lease is not None:
                lease.retire(parts[0])
            outs.append(parts)
        except Exception as exc:
            return outs, exc
    return outs, None


class FusionBuffer:
    """Fusion pack/unpack helper (reference fusion_buffer_manager.h:40 +
    the MemcpyIn/Out pair, collective_operations.h:65-88): batched,
    multi-threaded memcpy of N tensors into one flat buffer via the native
    core. ``pack_leased`` stages into a persistent ring slot (reused only
    after the in-flight consumer finishes — see StagingRing); ``pack``
    keeps the legacy fresh-allocation contract for callers that hold the
    buffer indefinitely."""

    def __init__(self, nbytes: int = 0, slots: int = None):
        if slots is None:
            slots = 4
            try:
                from ..common import env as env_mod

                slots = env_mod.get_int(
                    env_mod.HOROVOD_STAGING_RING_SLOTS, 4)
            except Exception:
                pass
        self.nbytes = nbytes
        self.ring = StagingRing(nbytes, slots)

    def resize(self, nbytes: int):
        self.nbytes = nbytes
        self.ring.resize(nbytes)

    def set_slots(self, slots: int):
        self.ring.set_slots(slots)

    def allocated_bytes(self) -> int:
        """Staging-ring host bytes actually allocated (memledger pull)."""
        return self.ring.allocated_bytes()

    def pack_leased(self, arrays):
        """Pack into a leased ring slot. Returns ``(flat, lease)`` where
        ``flat`` is the packed array viewed as the first array's dtype and
        ``lease`` is a ``_StagingLease`` to retire once the consumer's
        completion token exists — or ``None`` when the ring was bypassed
        (oversize/busy) and the buffer is freshly owned."""
        import numpy as np

        arrays = [np.ascontiguousarray(a) for a in arrays]
        total = sum(a.nbytes for a in arrays)
        buf, lease = self.ring.acquire(total)
        if buf is None:
            buf = np.empty(total, dtype=np.uint8)
        _pack_into(arrays, buf)
        return buf.view(arrays[0].dtype), lease

    def pack(self, arrays) -> "np.ndarray":
        """Pack contiguous arrays into one flat freshly-allocated array
        (dtype of the first array): the caller owns the result with no
        reuse hazard, at the cost of an allocation per call."""
        import numpy as np

        arrays = [np.ascontiguousarray(a) for a in arrays]
        total = sum(a.nbytes for a in arrays)
        buf = np.empty(total, dtype=np.uint8)
        _pack_into(arrays, buf)
        return buf.view(arrays[0].dtype)

    @staticmethod
    def unpack(flat, shapes, dtype):
        """Slice a reduced flat array back into per-tensor arrays."""
        import numpy as np

        flat = np.ascontiguousarray(np.asarray(flat))
        outs, sizes = [], []
        for s in shapes:
            sizes.append(int(np.prod(s, dtype=np.int64)))
        L = lib()
        if L is None:
            off = 0
            for s, n in zip(shapes, sizes):
                outs.append(flat[off:off + n].reshape(s))
                off += n
            return outs
        outs = [np.empty(s, dtype=flat.dtype) for s in shapes]
        n = len(outs)
        dsts = (ctypes.c_void_p * n)(*[o.ctypes.data for o in outs])
        bts = (ctypes.c_int64 * n)(
            *[o.nbytes for o in outs])
        L.hvd_unpack(flat.ctypes.data, dsts, bts, n)
        return outs
