"""StackStore — what is resident on the device: how an array there is
keyed, validated, accounted, evicted and awaited. How a stack is *made*
stays with whoever asks (the planner's builders, the key-plane cache):
the store is given an epoch, a function that returns the generations
and a function that builds, and knows nothing of PQL, indexes,
fragments or meshes.

    executor -> planner -> StackStore -> upload workers
                keyplane -> StackStore

Uploads run ahead of the requests that need them: a planner peeks a
plan's leaf set at prepare time and `schedule`s every stack that is
not resident, and two workers build them. A request that then misses
finds the upload landed (a plain hit) or in flight, and waits for it
(`hits`) instead of building the same stack itself (`sync_misses`).
The table of uploads in flight dedupes by key, so a wave of same-plan
requests costs one upload a leaf.

The budget is bytes a device. Each entry is charged what it places on
the fullest device it spans, read from the array's addressable shards:
a stack sharded over four chips is charged a quarter of its bytes, a
replicated or single-device array all of them. On one device that is
the array's whole size, as it always was.
"""

from __future__ import annotations

import threading
from collections import OrderedDict, deque
from typing import Any, Callable, NamedTuple

from pilosa_tpu.obs.tracing import start_span


class StackKey(NamedTuple):
    """The one layout of a resident array's identity."""

    index: str
    #: a deleted-and-recreated index restarts its epoch, so the name
    #: alone could serve the old index's stacks as fresh.
    instance_id: int
    field: str
    view: str
    #: a row id, ("planes", depth), ("hll", depth, p), ("hllreg", depth,
    #: p), ("simcube", row_ids, r_pad), or 0 for a key plane.
    tag: Any
    shards: tuple
    #: representation class; the bytes are accounted under it.
    klass: str


class _Entry(NamedTuple):
    epoch: int
    gens: tuple
    arr: Any
    #: the array's bytes over every device it spans.
    nbytes: int
    #: the bytes it places on its fullest device: what the budget pays.
    charge: int


def device_charge(arr, nbytes: int) -> int:
    """Bytes ``arr`` places on the fullest device it spans, from its
    addressable shards; ``nbytes`` for an array that has none (a host
    stand-in)."""
    shards = getattr(arr, "addressable_shards", None)
    if not shards:
        return nbytes
    held: dict = {}
    for shard in shards:
        held[shard.device] = held.get(shard.device, 0) \
            + int(shard.data.nbytes)
    return max(held.values())


class StackStore:
    """LRU of device arrays under a budget of bytes a device, and the
    workers that fill it."""

    #: for a caller that is handed the store and imports nothing of it.
    key = StackKey
    MAX_WORKERS = 2
    #: bound on a request's wait for an upload in flight; past it the
    #: request builds the stack itself.
    WAIT_TIMEOUT_S = 120.0

    def __init__(self, budget_bytes: int, classes: tuple[str, ...],
                 stats=None, uploads_ahead: bool = True):
        #: bytes a device (see the module's docstring).
        self.budget_bytes = budget_bytes
        self.stats = stats
        #: False: `schedule` takes nothing and every miss is built by
        #: the thread that asked. Set by the planner class from what it
        #: is, never by a user.
        self.uploads_ahead = uploads_ahead
        self._lock = threading.Lock()
        self._entries: "OrderedDict[StackKey, _Entry]" = OrderedDict()
        #: bytes over every device, and the charges against the budget.
        self._bytes = 0
        self._charged = 0
        self._class_bytes = dict.fromkeys(classes, 0)
        #: lifetime counts: with the working set over the budget,
        #: uploads track requests instead of flatlining after warm-up.
        self._evictions = 0
        self._uploads = 0
        self._upload_bytes = 0
        #: guards the uploads in flight and their counters.
        self._work = threading.Condition()
        #: key -> done event; membership is the dedupe.
        self._inflight: dict[StackKey, threading.Event] = {}
        self._queue: "deque[tuple[StackKey, Callable, tuple]]" = deque()
        self._workers: list[threading.Thread] = []
        self._closed = False
        self._tls = threading.local()
        self.scheduled = 0
        self.completed = 0
        self.errors = 0
        #: request misses absorbed by an upload in flight.
        self.hits = 0
        #: request misses that built and uploaded on the request's own
        #: thread: what running ahead exists to hold at zero.
        self.sync_misses = 0

    # -- lookup --------------------------------------------------------

    def keys(self) -> list[StackKey]:
        """Resident keys, least recently used first."""
        with self._lock:
            return list(self._entries)

    def peek(self, key: StackKey):
        """Whatever is resident under ``key``, touched, its stamp not
        judged (a key plane may be served stale while its rebuild runs;
        its cache keeps the version check)."""
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                return None
            self._entries.move_to_end(key)
            return hit.arr

    def get(self, key: StackKey, epoch: int,
            gens: Callable[[], tuple] | None = None):
        """The resident array if it is valid, else None. Two tiers: an
        entry stamped with ``epoch`` is served on one integer compare;
        only when the epoch moved is ``gens`` called, and an entry
        whose generations stand is re-stamped instead of rebuilt.
        Without ``gens`` a moved epoch is a miss."""
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                return None
            if hit.epoch != epoch:
                if gens is None or gens() != hit.gens:
                    return None
                self._entries[key] = hit._replace(epoch=epoch)
            self._entries.move_to_end(key)
            return hit.arr

    def get_or_build(self, key: StackKey, epoch: int,
                     gens: Callable[[], tuple], build: Callable, *,
                     count_upload: bool = True, staged: bool = False):
        """The valid resident array, built and inserted if need be.

        ``staged`` is for the stacks that `schedule` also builds (row
        stacks): ``build()`` returns ``(upload, nbytes)`` after the host
        work, which runs under ``stack.build``; ``upload()`` makes the
        transfer call and runs with the insert under ``stack.upload``;
        and a request thread that gets this far counts a sync miss.
        Otherwise ``build()`` returns the device array, no span opens
        (a cube stacked from row stacks would nest them) and
        ``count_upload=False`` says the bytes were counted when those
        rows went up."""
        arr = self.get(key, epoch, gens)
        if arr is not None:
            return arr
        # A worker is the upload in flight: waiting on its own key would
        # deadlock, and its build is no miss.
        if not getattr(self._tls, "worker", False):
            self._await_upload(key)
            # Re-check even when nothing was in flight: the upload may
            # have landed between the miss and the rendezvous.
            arr = self.get(key, epoch)
            if arr is not None:
                return arr
            if staged:
                with self._work:
                    self.sync_misses += 1
                if self.stats is not None:
                    self.stats.count("planner.prefetchSyncMiss", 1)
        # Built outside the lock: two threads may race to build one
        # stack; the second insert wins. The generations are read before
        # the build, so a write during it leaves a stale stamp behind.
        found = gens()
        if not staged:
            arr = build()
            self.insert(key, epoch, found, arr, int(arr.nbytes),
                        count_upload=count_upload)
            return arr
        with start_span("stack.build", stats=self.stats):
            upload, nbytes = build()
        with start_span("stack.upload", stats=self.stats):
            arr = upload()
            # upload holds the host matrix (128 MiB for a dense stack):
            # let go of it before the eviction work, not after. The
            # runtime keeps its own reference until the transfer has
            # read the matrix; only then does the chunk go back to the
            # page pool (scripts/stack_readback_check.py).
            del upload
            self.insert(key, epoch, found, arr, nbytes)
        return arr

    # -- accounting ----------------------------------------------------

    def insert(self, key: StackKey, epoch: int, gens: tuple, arr,
               nbytes: int, *, count_upload: bool = True) -> None:
        """The one insertion and byte-accounting path of every class.
        ``nbytes`` is the array's size over every device (what an upload
        moves); the budget is charged `device_charge`. Insert first,
        evict after, never the last entry: an upload overlaps the
        evictee's last use instead of queueing behind the eviction (the
        overshoot is one stack)."""
        charge = device_charge(arr, nbytes)
        with self._lock:
            if count_upload:
                self._uploads += 1
                self._upload_bytes += nbytes
            old = self._entries.pop(key, None)
            if old is not None:
                self._give_back(key, old)
            self._entries[key] = _Entry(epoch, gens, arr, nbytes, charge)
            self._bytes += nbytes
            self._charged += charge
            self._class_bytes[key.klass] += nbytes
            while (self._charged > self.budget_bytes
                   and len(self._entries) > 1):
                self._give_back(*self._entries.popitem(last=False))
                self._evictions += 1
            class_bytes = dict(self._class_bytes)
        if self.stats is not None:
            for k, v in class_bytes.items():
                self.stats.gauge(f"planner.residentBytes.{k}", v)

    def _give_back(self, key: StackKey, entry: _Entry) -> None:
        self._bytes -= entry.nbytes
        self._charged -= entry.charge
        self._class_bytes[key.klass] -= entry.nbytes

    def drop_index(self, index_name: str) -> None:
        with self._lock:
            for key in [k for k in self._entries if k.index == index_name]:
                self._give_back(key, self._entries.pop(key))

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0
            self._charged = 0
            self._class_bytes = dict.fromkeys(self._class_bytes, 0)

    def snapshot(self) -> dict:
        """Occupancy and churn. ``bytes`` is over every device;
        ``fullest_device_bytes`` the charges that ``budget_bytes``, a
        device's, bounds."""
        with self._lock:
            return {"bytes": self._bytes,
                    "fullest_device_bytes": self._charged,
                    "budget_bytes": self.budget_bytes,
                    "entries": len(self._entries),
                    "evictions": self._evictions,
                    "uploads": self._uploads,
                    "upload_bytes": self._upload_bytes,
                    "class_bytes": dict(self._class_bytes)}

    def per_device_bytes(self) -> dict[str, int]:
        """Bytes each device holds of what is resident: whether stacks
        spread over the mesh or all sit on its first device."""
        with self._lock:
            arrays = [e.arr for e in self._entries.values()]
        held: dict[str, int] = {}
        for arr in arrays:
            for shard in arr.addressable_shards:
                name = str(shard.device)
                held[name] = held.get(name, 0) + int(shard.data.nbytes)
        return held

    # -- uploads ahead -------------------------------------------------

    def schedule(self, key: StackKey, epoch: int, build: Callable,
                 *args) -> bool:
        """Have a worker call ``build(*args)``, which must put the stack
        into the store itself, unless the stack is resident and current
        or its upload is already in flight."""
        if not self.uploads_ahead:
            return False
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None and hit.epoch == epoch:
                return False
        with self._work:
            if self._closed or key in self._inflight:
                return False
            self._inflight[key] = threading.Event()
            self._queue.append((key, build, args))
            self.scheduled += 1
            if len(self._workers) < self.MAX_WORKERS:
                t = threading.Thread(
                    target=self._run, daemon=True,
                    name=f"residency-prefetch-{len(self._workers)}")
                self._workers.append(t)
                t.start()
            self._work.notify()
            inflight = len(self._inflight)
        if self.stats is not None:
            self.stats.count("planner.prefetchScheduled", 1)
            self.stats.gauge("planner.prefetchInflight", inflight)
        return True

    def _await_upload(self, key: StackKey) -> None:
        with self._work:
            ev = self._inflight.get(key)
        if ev is None:
            return
        with start_span("stack.wait", stats=self.stats):
            ev.wait(self.WAIT_TIMEOUT_S)
        with self._work:
            self.hits += 1
        if self.stats is not None:
            self.stats.count("planner.prefetchHit", 1)

    def _run(self) -> None:
        self._tls.worker = True
        while True:
            with self._work:
                while not self._queue and not self._closed:
                    self._work.wait()
                if not self._queue:  # closed and drained
                    return
                key, build, args = self._queue.popleft()
            try:
                build(*args)
            except Exception:
                with self._work:
                    self.errors += 1
            with self._work:
                self.completed += 1
                ev = self._inflight.pop(key, None)
            if ev is not None:
                ev.set()

    def upload_stats(self) -> dict:
        """/debug/device's ``prefetch`` block."""
        with self._work:
            return {"scheduled": self.scheduled,
                    "completed": self.completed,
                    "inflight": len(self._inflight),
                    "queued": len(self._queue),
                    "hits": self.hits,
                    "sync_misses": self.sync_misses,
                    "errors": self.errors}

    def close(self) -> None:
        """Stop taking work, let the workers drain the queue and
        release every waiter."""
        with self._work:
            self._closed = True
            self._work.notify_all()
        for t in self._workers:
            t.join(timeout=5.0)
        with self._work:
            leftovers = list(self._inflight.values())
            self._inflight.clear()
            self._queue.clear()
        for ev in leftovers:
            ev.set()
