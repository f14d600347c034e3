"""Chrome-tracing timeline writer.

Reference: /root/reference/horovod/common/timeline.{h,cc} — a dedicated
writer thread fed by a lock-free SPSC queue (timeline.h:84-100), emitting
Chrome trace-event JSON with a per-tensor NEGOTIATING → TOP_LEVEL → ACTIVITY
state machine, runtime start/stop (operations.cc:738-764), and optional
cycle markers.

Here: a daemon writer thread fed by the native C++ SPSC ring
(`horovod_tpu._native` hvd_tl_*, the direct analogue of the reference's
boost::lockfree::spsc_queue) with a ``queue.SimpleQueue`` fallback when
the native core isn't built; same JSON schema, so the output opens in
``chrome://tracing`` / Perfetto exactly like the reference's. Device-side
timing on TPU comes from ``jax.profiler`` traces instead of CUDA events:
the compiled step names its work for them (``utils/scopes.py``,
docs/timeline.md "Profiling the compiled step").
"""

from __future__ import annotations

import ctypes
import json
import queue
import threading
import time
from typing import Optional

_RING_CAPACITY = 1 << 16  # events (reference: 1M; sized for host traces)
_DRAIN_BUF = 1 << 20


class _NativeRing:
    """ctypes wrapper over the C++ SPSC ring (core.cc hvd_tl_*)."""

    def __init__(self, lib):
        self._lib = lib
        self._ring = lib.hvd_tl_create(_RING_CAPACITY)
        self._buf = ctypes.create_string_buffer(_DRAIN_BUF)

    def put(self, rec):
        data = b"" if rec is None else json.dumps(rec).encode()
        self._lib.hvd_tl_push(self._ring, data, len(data))

    def drain_lines(self):
        n = self._lib.hvd_tl_drain(self._ring, self._buf, _DRAIN_BUF)
        if n <= 0:
            return []
        return self._buf.raw[:n].decode().splitlines()

    def __del__(self):
        try:
            self._lib.hvd_tl_destroy(self._ring)
        except Exception:
            pass


class Timeline:
    """Per-tensor lane trace writer (chrome trace-event format)."""

    def __init__(self, filename: str = "", mark_cycles: bool = False):
        self._native = None
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._file = None
        self._thread: Optional[threading.Thread] = None
        self._tids: dict[str, int] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.mark_cycles = mark_cycles
        self._start_ts = time.perf_counter()
        if filename:
            self._open(filename)

    # -- lifecycle ----------------------------------------------------------
    def _open(self, filename: str):
        # native ring load/build is deferred to here: most inits never
        # enable the timeline, and lib() may invoke a g++ build
        if self._native is None:
            from .._native import lib as _native_lib

            L = _native_lib()
            if L is not None:
                try:
                    self._native = _NativeRing(L)
                except Exception:
                    self._native = None
        self._file = open(filename, "w")
        self._file.write("[\n")
        self._stop.clear()
        self._thread = threading.Thread(target=self._writer, daemon=True,
                                        name="hvd-timeline")
        self._thread.start()

    def reopen(self, filename: str, mark_cycles: bool = False):
        """Runtime start/stop (reference operations.cc:738-764)."""
        self.close()
        self.mark_cycles = mark_cycles
        if filename:
            self._open(filename)

    def close(self):
        if self._thread is not None:
            self._stop.set()
            self._q.put(None)
            self._thread.join(timeout=5)
            self._thread = None
        if self._file is not None:
            self._file.write("{}]\n")
            self._file.close()
            self._file = None

    @property
    def enabled(self) -> bool:
        return self._file is not None

    # -- event emission -----------------------------------------------------
    def _ts_us(self) -> float:
        return (time.perf_counter() - self._start_ts) * 1e6

    def _put(self, rec):
        if self._native is not None:
            self._native.put(rec)
        else:
            self._q.put(rec)

    def _tid(self, name: str) -> int:
        with self._lock:
            if name not in self._tids:
                self._tids[name] = len(self._tids) + 1
                self._put({"name": "process_name", "ph": "M", "pid": 0,
                           "tid": self._tids[name],
                           "args": {"name": name}})
            return self._tids[name]

    def _emit(self, name: str, ph: str, event: str, args=None):
        if not self.enabled:
            return
        rec = {"ph": ph, "ts": self._ts_us(), "pid": 0, "tid": self._tid(name)}
        if event:
            rec["name"] = event
        if args:
            rec["args"] = args
        self._put(rec)

    def negotiate_start(self, name: str, op_name: str):
        self._emit(name, "B", "NEGOTIATE_" + op_name)

    def negotiate_end(self, name: str):
        self._emit(name, "E", "")

    def start_activity(self, name: str, activity: str):
        self._emit(name, "B", activity)

    def end_activity(self, name: str):
        self._emit(name, "E", "")

    def mark_cycle_start(self):
        if self.enabled and self.mark_cycles:
            self._put({"ph": "i", "ts": self._ts_us(), "pid": 0, "tid": 0,
                       "name": "CYCLE_START", "s": "g"})

    # -- writer thread ------------------------------------------------------
    def _writer(self):
        if self._native is not None:
            while True:
                lines = self._native.drain_lines()
                for ln in lines:
                    if ln and self._file:
                        self._file.write(ln + ",\n")
                if lines and self._file:
                    self._file.flush()
                if self._stop.is_set() and not lines:
                    return
                if not lines:
                    time.sleep(0.02)
            return
        while True:
            try:
                rec = self._q.get(timeout=0.5)
            except queue.Empty:
                if self._stop.is_set():
                    return
                continue
            if rec is None:
                # drain remaining
                while True:
                    try:
                        r = self._q.get_nowait()
                    except queue.Empty:
                        return
                    if r is not None and self._file:
                        self._file.write(json.dumps(r) + ",\n")
                return
            if self._file:
                self._file.write(json.dumps(rec) + ",\n")
                self._file.flush()
