"""DispatchCoalescer — batch concurrent launches of the same plan.

Once a query is one fused program (exec/fuse.py), the remaining cost of
N concurrent identical queries is N jitted-program dispatches of the
*same compiled function*. The result cache already proves such plans
are structurally identical — the program-cache signature is the batch
key, so the key comes free. A short collection window (sub-ms, tunable
``--dispatch-coalesce-us``) gathers pending same-signature calls and
launches them as one device program:

* **identical-argument wave** (the common case: N callers racing the
  same uncached query, whose leaf stacks are the very same cached
  device arrays): ONE plain launch of the already-compiled program;
  every caller's future resolves off the shared output.
* **same-shape wave** (same plan signature, different literals/leaves):
  arguments stack to ``[B, ...]`` and launch through ``jax.vmap`` of
  the raw (unjitted) program, padded to a pow2 batch bucket so batch
  widths reuse compiled kernels; per-slot results fan back out.

Selection: ``PILOSA_TPU_DISPATCH_COALESCE`` = ``on`` | ``off`` |
``auto`` (env wins over the server knob's ``set_mode``);
``PILOSA_TPU_DISPATCH_COALESCE_US`` overrides the window.

* ``off`` — every dispatch launches immediately (the pre-coalescing
  behavior, bit-identical by construction).
* ``on`` — every dispatch waits up to the window for batch-mates; the
  measurement mode (maximizes batching, adds up to one window of
  latency to solo queries).
* ``auto`` (default) — the first dispatch of a plan launches
  immediately (zero added latency for serial traffic); while it is in
  flight, further dispatches of the same plan collect into a batch
  that flushes on the window. Concurrency is the trigger, so solo
  queries never pay the window.

Results are bit-identical across modes: the identical-argument wave
runs the exact same program on the exact same inputs, and the vmapped
wave runs the same traced math per slot (asserted by the generative
and barrier tests in tests/test_dispatch_fusion.py).
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable

import numpy as np

from pilosa_tpu.obs import profile as _profile
from pilosa_tpu.obs.tracing import start_span

_MODES = ("on", "off", "auto")

#: sentinel: "read the caller's contextvar" — distinct from None, which
#: means "profiling is off for this entry" (the flusher thread passes
#: the profile captured at dispatch() time; its own contextvar is
#: always empty and must not be consulted).
_CTX = object()
_default_mode = "auto"

DEFAULT_WINDOW_US = 150.0

#: widest batch one launch absorbs; later arrivals start a fresh batch.
MAX_BATCH = 32


def set_mode(mode: str) -> None:
    """Server-knob default; the PILOSA_TPU_DISPATCH_COALESCE env var
    (the test/operator override) takes precedence when set."""
    global _default_mode
    if mode not in _MODES:
        raise ValueError(f"dispatch_coalesce mode must be one of {_MODES}")
    _default_mode = mode


def mode() -> str:
    m = os.environ.get("PILOSA_TPU_DISPATCH_COALESCE", "").strip().lower()
    return m if m in _MODES else _default_mode


def default_window_us() -> float:
    env = os.environ.get("PILOSA_TPU_DISPATCH_COALESCE_US", "")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    return DEFAULT_WINDOW_US


class _Batch:
    __slots__ = ("key", "fn", "deadline", "entries")

    def __init__(self, key, fn, deadline: float):
        self.key = key
        self.fn = fn
        self.deadline = deadline
        #: list of (args, post, fut, profile-or-None) — the profile is
        #: captured on the DISPATCHING thread; the flusher has none.
        self.entries: list[tuple[tuple, Callable, Future, Any]] = []


class DispatchCoalescer:
    """Same-plan dispatch batching in front of a planner's launches.

    ``dispatch(fn, args, post)`` is the planner's single launch choke
    point: it runs ``fn(*args)`` (immediately or as part of a batch),
    routes the output pytree through the TransferBatcher, and resolves
    the returned future to ``post(host_pytree)``.
    """

    def __init__(self, planner, window_us: float | None = None):
        self.planner = planner
        self.window_us = (default_window_us() if window_us is None
                          else float(window_us))
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        #: every unflushed batch, FIFO by arrival (keyed by a seq, NOT
        #: the plan key: a full batch must stay here until the flusher
        #: takes it even after a fresh batch opens for the same plan).
        self._pending: dict[int, _Batch] = {}
        #: plan key -> the batch still accepting entries (at most one
        #: per key; full batches are sealed out of this map).
        self._open: dict[Any, _Batch] = {}
        self._seq = 0
        #: per-key launches whose batcher wave hasn't landed — the
        #: concurrency signal "auto" batches on.
        self._inflight: dict[Any, int] = {}
        self._thread: threading.Thread | None = None
        self._closed = False
        #: test hook: while held, due batches stay pending (the
        #: deterministic-barrier concurrency test builds an exact batch,
        #: then releases).
        self._held = False

    # -- public --------------------------------------------------------

    def dispatch(self, fn, args, post: Callable[[Any], Any]) -> Future:
        """Launch ``fn(*args)`` (possibly batched with same-plan peers)
        and return a Future resolving to ``post(host_outputs)``."""
        planner = self.planner
        m = mode()
        key = planner.fn_key(fn) if m != "off" else None
        if key is None or not getattr(planner, "coalesce_supported", False):
            return self._launch_one(None, fn, args, post)
        # Captured HERE, not in the flusher: batches launch on the
        # coalescer thread, where the query's contextvars are absent.
        prof = _profile.current()
        with self._cv:
            if not self._closed:
                batch = self._open.get(key)
                if batch is not None:
                    fut: Future = Future()
                    batch.entries.append((tuple(args), post, fut, prof))
                    if len(batch.entries) >= MAX_BATCH:
                        # Seal: the batch stays pending until flushed,
                        # but the next arrival opens a fresh one.
                        del self._open[key]
                        self._cv.notify()
                    return fut
                if m == "on" or self._inflight.get(key, 0) > 0:
                    batch = _Batch(key, fn,
                                   time.monotonic() + self.window_us * 1e-6)
                    fut = Future()
                    batch.entries.append((tuple(args), post, fut, prof))
                    self._pending[self._seq] = batch
                    self._seq += 1
                    self._open[key] = batch
                    if self._thread is None:
                        self._thread = threading.Thread(
                            target=self._run, name="dispatch-coalescer",
                            daemon=True)
                        self._thread.start()
                    self._cv.notify()
                    return fut
        # "auto" with nothing in flight (or closed): launch now — the
        # serial path must not pay the window.
        return self._launch_one(key, fn, args, post)

    def queue_depth(self) -> int:
        """Entries sitting in unflushed batches right now (the
        /debug/device dispatch-queue gauge)."""
        with self._lock:
            return sum(len(b.entries) for b in self._pending.values())

    def hold(self) -> None:
        """Test hook: freeze flushing so a batch can be assembled
        deterministically; pair with release()."""
        with self._cv:
            self._held = True

    def release(self) -> None:
        with self._cv:
            self._held = False
            self._cv.notify()

    def close(self, timeout: float | None = 10.0) -> None:
        """Flush pending batches and stop the flusher thread."""
        with self._cv:
            self._closed = True
            self._held = False
            self._cv.notify_all()
            t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout)
        # Anything still pending (flusher already gone / never started)
        # flushes on the closing thread so no future is dropped.
        while True:
            with self._cv:
                if not self._pending:
                    self._open.clear()
                    return
                _, batch = self._pending.popitem()
                if self._open.get(batch.key) is batch:
                    del self._open[batch.key]
            self._flush(batch)

    # -- flusher -------------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cv:
                while True:
                    if self._closed and not self._pending:
                        return
                    due = None
                    if not self._held:
                        now = time.monotonic()
                        for seq, b in self._pending.items():
                            if (self._closed or b.deadline <= now
                                    or len(b.entries) >= MAX_BATCH):
                                due = seq
                                break
                    if due is not None:
                        batch = self._pending.pop(due)
                        if self._open.get(batch.key) is batch:
                            del self._open[batch.key]
                        break
                    if self._held or not self._pending:
                        self._cv.wait()
                    else:
                        nxt = min(b.deadline
                                  for b in self._pending.values())
                        self._cv.wait(max(nxt - time.monotonic(), 0.0)
                                      or 1e-5)
            self._flush(batch)

    # -- launch paths --------------------------------------------------

    def _note_inflight(self, key, delta: int) -> None:
        if key is None:
            return
        with self._lock:
            n = self._inflight.get(key, 0) + delta
            if n > 0:
                self._inflight[key] = n
            else:
                self._inflight.pop(key, None)

    def _launch_one(self, key, fn, args, post: Callable,
                    prof=_CTX) -> Future:
        """Unbatched launch: the zero-overhead serial path. Returns the
        TransferBatcher future directly — no second future/callback."""
        import jax

        planner = self.planner
        # On the caller's thread the span charges the active profile; on
        # the flusher's, the one captured at dispatch() time.
        profs = None if prof is _CTX else (prof,)
        try:
            with start_span("dispatch.launch", stats=planner.stats,
                            profiles=profs):
                out = fn(*args)
        except Exception as e:
            fut: Future = Future()
            fut.set_exception(e)
            return fut
        planner._record_dispatch(1, profs=profs)
        self._note_inflight(key, +1)
        leaves, treedef = jax.tree_util.tree_flatten(out)
        _copy_async(leaves)

        def conv(host_anchor, _l=leaves, _t=treedef, _p=post, _k=key):
            try:
                flat = [host_anchor] + [np.asarray(a) for a in _l[1:]]
                return _p(jax.tree_util.tree_unflatten(_t, flat))
            finally:
                self._note_inflight(_k, -1)

        return planner.batcher.submit(leaves[0], conv)

    def _flush(self, batch: _Batch) -> None:
        entries = batch.entries
        if len(entries) == 1:
            args, post, fut, prof = entries[0]
            _chain(self._launch_one(batch.key, batch.fn, args, post,
                                    prof=prof), fut)
            return
        try:
            self._flush_batched(batch)
        except Exception as e:
            for _, _, fut, _ in entries:
                if not fut.done():
                    fut.set_exception(e)

    def _flush_batched(self, batch: _Batch) -> None:
        import jax

        planner = self.planner
        entries = batch.entries
        b = len(entries)
        args0 = entries[0][0]
        profs = [e[3] for e in entries]
        shared = all(_args_identical(e[0], args0) for e in entries[1:])
        raw = None if shared else planner.fn_raw(batch.fn)
        if not shared and (raw is None
                           or not planner.coalesce_vmap_supported):
            # No vmappable program, or a mesh the vmapped wave would
            # lose its shardings on: launch per entry — still one trip
            # through this thread, and the accounting stays honest (B
            # launches recorded).
            for args, post, fut, prof in entries:
                _chain(self._launch_one(batch.key, batch.fn, args,
                                        post, prof=prof), fut)
            return
        with start_span("dispatch.launch", stats=planner.stats,
                        profiles=profs):
            if shared:
                # N callers, same plan, same leaf arrays (the
                # cached-stack common case): one plain launch, output
                # shared by every caller's own postproc.
                out = batch.fn(*args0)
                slot = None
            else:
                # Same plan shape, different literals/leaves: stack each
                # argument leaf to [B, ...] (padded to a pow2 bucket by
                # repeating slot 0, so batch widths reuse compiled
                # kernels) and launch ONE vmapped program.
                import jax.numpy as jnp
                b_pad = 1 << (b - 1).bit_length()
                rows = [e[0] for e in entries] + [args0] * (b_pad - b)
                stacked = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *rows)
                out = planner.vmapped(batch.key, raw)(*stacked)
                slot = True
        planner._record_dispatch(b, profs=profs)
        self._note_inflight(batch.key, +1)
        leaves, treedef = jax.tree_util.tree_flatten(out)
        _copy_async(leaves)

        def conv(host_anchor, _l=leaves, _t=treedef, _k=batch.key):
            try:
                flat = [host_anchor] + [np.asarray(a) for a in _l[1:]]
                host = jax.tree_util.tree_unflatten(_t, flat)
                for i, (_, post, fut, _prof) in enumerate(entries):
                    if fut.done():
                        continue
                    try:
                        per = host if slot is None else \
                            jax.tree_util.tree_map(lambda a, i=i: a[i], host)
                        fut.set_result(post(per))
                    except Exception as e:
                        fut.set_exception(e)
            finally:
                self._note_inflight(_k, -1)

        planner.batcher.submit(leaves[0], conv)


def _chain(src: Future, dst: Future) -> None:
    def _done(f):
        if dst.done():
            return
        e = f.exception()
        if e is not None:
            dst.set_exception(e)
        else:
            dst.set_result(f.result())
    src.add_done_callback(_done)


def _args_identical(a: tuple, b: tuple) -> bool:
    """True when two argument pytrees are the SAME objects leaf-for-leaf
    (identity, not equality — an O(leaves) pointer walk). Holds whenever
    concurrent queries resolved their leaves through the planner's stack
    cache, which is exactly the repeated-query case coalescing targets."""
    import jax
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(x is y for x, y in zip(la, lb))


def _copy_async(leaves) -> None:
    for a in leaves:
        try:
            a.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
