"""TransferBatcher — pipelined device→host result delivery.

Why this exists: a synchronous device→host pull blocks its thread for
one host↔device round-trip no matter how small the array (its latency is
not measured on the current machine), while the device itself can run
thousands of query kernels per second. The reference never faces this —
its kernels run in-process (executor.go:2561's worker pool) — so this
component has no Go analog; it is the TPU-native answer to the same
problem the reference solves with goroutine pools: keep the compute
resource saturated instead of stalling on round-trips.

Mechanism: a query submits its (tiny) result array instead of pulling
it. The submitting thread starts the device→host copy asynchronously
right away; a resolver thread reads completed copies in FIFO order and
resolves each query's future. Any number of copies pipeline inside one
round-trip window, so N concurrent queries cost ~one round-trip of
latency total instead of N.

Merging results into one stacked array before transfer was tried on an
earlier machine and performed the same while costing a large XLA compile
per wave shape, so this simpler design won; neither is measured on the
current machine.
"""

from __future__ import annotations

import os
import threading
from collections import deque
from concurrent.futures import Future
from typing import Any, Callable

import numpy as np

from pilosa_tpu.obs import profile as _profile
from pilosa_tpu.obs.histogram import WIDTH_BOUNDS, LogHistogram

_INLINE_MODES = ("on", "off", "auto")
_default_inline = "auto"


def set_inline_mode(mode: str) -> None:
    """Server-knob default for inline transfer resolution; the
    PILOSA_TPU_INLINE_TRANSFER env var takes precedence when set."""
    global _default_inline
    if mode not in _INLINE_MODES:
        raise ValueError(
            f"inline_transfer mode must be one of {_INLINE_MODES}")
    _default_inline = mode


def inline_mode() -> str:
    m = os.environ.get("PILOSA_TPU_INLINE_TRANSFER", "").strip().lower()
    return m if m in _INLINE_MODES else _default_inline


class _StealFuture(Future):
    """A future whose ``result()`` may steal its own queue entry and
    resolve inline on the waiting thread, skipping the resolver-thread
    handoff (~0.1 ms of lock/notify latency per solo wave). Stealing is
    governed by the inline_transfer knob: ``on`` always steals, ``off``
    never, ``auto`` (default) steals only when the wave has a single
    waiter — with multiple waiters the pipelined FIFO resolver wins."""

    __slots__ = ("_batcher",)

    def result(self, timeout=None):
        b = self._batcher
        if b is not None:
            self._batcher = None
            b._steal(self)
        return super().result(timeout)


class TransferBatcher:
    """Pipelines many small device→host pulls behind one resolver."""

    def __init__(self):
        self._queue: deque = deque()
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._thread: threading.Thread | None = None
        self._closed = False
        #: waves resolved on the waiter's thread (the knob's observable)
        self.inline_resolved = 0
        #: lifetime wave-width distribution (queue length at each
        #: submit), rendered by /debug/device; observed under _cv.
        self._wave_hist = LogHistogram(bounds=WIDTH_BOUNDS, lock=False)

    # -- public --------------------------------------------------------

    def submit(self, arr, postproc: Callable[[np.ndarray], Any],
               profs=None) -> "Future[Any]":
        """Start ``arr``'s async copy and return a future resolving to
        ``postproc(host_array)``.

        ``profs``: QueryProfiles to charge this wave to — passed by the
        coalescer (whose flusher thread has no query context); when
        omitted, the submitting thread's active profile is charged.
        """
        fut: Future = _StealFuture()
        fut._batcher = self
        try:
            arr.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass  # non-jax array / backend without async copies
        closed = False
        with self._cv:
            if self._closed:
                closed = True
            else:
                self._queue.append((arr, fut, postproc))
                width = len(self._queue)
                self._wave_hist.observe(width)
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._run, name="transfer-batcher",
                        daemon=True)
                    self._thread.start()
                self._cv.notify()
        if not closed:
            if profs is None:
                p = _profile.current()
                profs = (p,) if p is not None else ()
            for p in profs:
                if p is not None:
                    p.add_wave(width)
        if closed:
            # Shutdown grace OUTSIDE the lock (the pull can take a full
            # link round-trip): a query racing node close resolves
            # synchronously instead of 500ing (handler threads can
            # outlive the HTTP listener).
            try:
                fut.set_result(postproc(np.asarray(arr)))
            except Exception as e:
                fut.set_exception(e)
        return fut

    def queue_depth(self) -> int:
        """Transfers awaiting resolution right now."""
        with self._lock:
            return len(self._queue)

    def debug(self) -> dict:
        """The /debug/device payload's transfer half."""
        with self._lock:
            return {"queue_depth": len(self._queue),
                    "inline_resolved": self.inline_resolved,
                    "wave_width_hist": self._wave_hist.snapshot()}

    def close(self, timeout: float | None = 30.0) -> None:
        """Drain-and-join: mark closed, wake the resolver, and wait for
        it to finish every transfer already queued. Without the join, a
        close racing in-flight submits could drop queued futures on
        process exit (the resolver is a daemon thread); after close
        returns, every future enqueued before it is resolved, and any
        later ``submit`` resolves synchronously on the caller's thread.
        Safe to call repeatedly and from a resolver callback (joining
        the current thread is skipped)."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
            t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout)

    def _steal(self, fut: Future) -> None:
        """Opportunistically remove ``fut``'s own queue entry and resolve
        it on the calling (waiting) thread. No-op when the knob says off,
        when the resolver already claimed the entry, or — in auto — when
        other waves are queued (FIFO pipelining beats stealing there)."""
        m = inline_mode()
        if m == "off":
            return
        entry = None
        with self._cv:
            if m == "auto" and len(self._queue) != 1:
                return
            for i, e in enumerate(self._queue):
                if e[1] is fut:
                    del self._queue[i]
                    entry = e
                    break
            if entry is not None:
                self.inline_resolved += 1
        if entry is None:
            return
        p = _profile.current()   # the stealer IS the query thread
        if p is not None:
            p.add_inline_steal()
        arr, _, post = entry
        try:
            result = post(np.asarray(arr))
        except Exception as e:
            if not fut.done():
                fut.set_exception(e)
            return
        if not fut.done():
            fut.set_result(result)

    # -- resolver --------------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait()
                if not self._queue and self._closed:
                    return
                arr, fut, post = self._queue.popleft()
            try:
                host = np.asarray(arr)
                result = post(host)
            except Exception as e:
                if not fut.done():
                    fut.set_exception(e)
                continue
            if not fut.done():
                fut.set_result(result)
