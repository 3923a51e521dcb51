"""StackStore alone (parallel/stacks): fake arrays, no planner, no jax.
Keys, the two-tier validation, the byte budget, insert-first eviction
and the rendezvous with an upload in flight."""

import threading
import time

from pilosa_tpu.obs import MemoryStats
from pilosa_tpu.parallel.stacks import StackKey, StackStore

CLASSES = ("dense", "packed")


class FakeArray:
    def __init__(self, nbytes):
        self.nbytes = nbytes


def key(tag=0, index="i", klass="dense"):
    return StackKey(index, 1, "f", "standard", tag, (0, 1), klass)


def store(budget=1000, **kw):
    return StackStore(budget, CLASSES, **kw)


def never():
    raise AssertionError("must not be called")


def staged(arr, calls=None):
    """A row-stack builder: the host half returns (upload, nbytes)."""
    def build():
        if calls is not None:
            calls.append("build")
        return (lambda: arr), arr.nbytes
    return build


def run(fn, *args):
    out = []
    t = threading.Thread(target=lambda: out.append(fn(*args)), daemon=True)
    t.start()
    return t, out


# -- (a) validation ---------------------------------------------------------


def test_epoch_equal_is_a_hit_without_reading_generations():
    s, arr = store(), FakeArray(10)
    s.insert(key(), 7, ("g",), arr, 10)
    assert s.get(key(), 7, never) is arr
    assert s.get_or_build(key(), 7, never, never) is arr


def test_epoch_moved_generations_equal_restamps_without_building():
    s, arr = store(), FakeArray(10)
    s.insert(key(), 7, ("g",), arr, 10)
    read = []

    def gens():
        read.append(1)
        return ("g",)

    assert s.get_or_build(key(), 8, gens, never, staged=True) is arr
    assert read == [1]
    # re-stamped: the next request at epoch 8 reads no generations
    assert s.get_or_build(key(), 8, never, never, staged=True) is arr
    assert s.snapshot()["uploads"] == 1
    assert s.upload_stats()["sync_misses"] == 0


def test_epoch_moved_without_generations_is_a_miss():
    s = store()
    s.insert(key(), 7, ("g",), FakeArray(10), 10)
    assert s.get(key(), 8) is None


def test_generations_differ_rebuilds_and_gives_the_old_bytes_back():
    s, old, new = store(), FakeArray(10), FakeArray(30)
    s.insert(key(), 7, ("g",), old, 10)
    got = s.get_or_build(key(), 8, lambda: ("h",), staged(new), staged=True)
    assert got is new
    snap = s.snapshot()
    assert snap["entries"] == 1
    assert snap["bytes"] == 30 == snap["class_bytes"]["dense"]
    assert snap["uploads"] == 2 and snap["upload_bytes"] == 40
    assert s.get(key(), 8) is new          # stamped with the new epoch
    assert s.get(key(), 9, lambda: ("h",)) is new   # and the new generations


def test_peek_touches_and_does_not_judge_the_stamp():
    s, a, b = store(), FakeArray(1), FakeArray(1)
    s.insert(key(1), 5, (), a, 1)
    s.insert(key(2), 5, (), b, 1)
    assert s.peek(key(1)) is a             # whatever its stamp
    assert [k.tag for k in s.keys()] == [2, 1]
    assert s.peek(key(3)) is None


# -- (b) (c) accounting and eviction ----------------------------------------


def test_bytes_per_class_through_insert_replace_evict_drop_clear():
    stats = MemoryStats()
    s = store(budget=100, stats=stats)
    s.insert(key(1), 1, (), FakeArray(40), 40)
    s.insert(key(2, klass="packed"), 1, (), FakeArray(10), 10)
    s.insert(key(3, index="j"), 1, (), FakeArray(30), 30)
    assert s.snapshot()["class_bytes"] == {"dense": 70, "packed": 10}
    s.insert(key(1), 2, (), FakeArray(50), 50)          # replace: 40 -> 50
    snap = s.snapshot()
    assert (snap["bytes"], snap["entries"], snap["evictions"]) == (90, 3, 0)
    s.insert(key(4), 1, (), FakeArray(30), 30)     # 120 > 100: 2, then 3 out
    snap = s.snapshot()
    assert [k.tag for k in s.keys()] == [1, 4]
    assert snap["class_bytes"] == {"dense": 80, "packed": 0}
    assert (snap["bytes"], snap["evictions"]) == (80, 2)
    s.insert(key(5), 1, (), FakeArray(5), 5)
    assert s.snapshot()["bytes"] == 85
    assert stats.gauges[("planner.residentBytes.dense", ())] == 85
    s.drop_index("j")                                   # already evicted
    assert s.snapshot()["bytes"] == 85
    s.drop_index("i")
    snap = s.snapshot()
    assert (snap["bytes"], snap["entries"]) == (0, 0)
    assert snap["class_bytes"] == {"dense": 0, "packed": 0}
    s.insert(key(6), 1, (), FakeArray(7), 7)
    s.clear()
    snap = s.snapshot()
    assert (snap["bytes"], snap["entries"]) == (0, 0)
    assert snap["class_bytes"] == {"dense": 0, "packed": 0}
    assert snap["uploads"] == 7 and snap["evictions"] == 2   # lifetime


def test_insert_first_eviction_never_empties_the_store():
    s = store(budget=10)
    for tag in range(3):
        s.insert(key(tag), 1, (), FakeArray(50), 50)
        snap = s.snapshot()
        assert snap["entries"] == 1 and snap["bytes"] == 50  # over, but held
    assert [k.tag for k in s.keys()] == [2]
    assert s.snapshot()["evictions"] == 2


def test_a_cube_is_no_upload_and_no_miss():
    s, cube = store(), FakeArray(64)
    got = s.get_or_build(key(("planes", 3)), 1, lambda: ("g",),
                         lambda: cube, count_upload=False)
    assert got is cube
    snap = s.snapshot()
    assert snap["bytes"] == 64 and snap["uploads"] == 0
    assert snap["upload_bytes"] == 0
    assert s.upload_stats()["sync_misses"] == 0
    assert s.get_or_build(key(("planes", 3)), 1, never, never) is cube


# -- (d) (e) (f) uploads in flight ------------------------------------------


def test_requests_wait_for_a_scheduled_upload_instead_of_building():
    stats = MemoryStats()
    s, arr, calls = store(stats=stats), FakeArray(10), []
    started, release = threading.Event(), threading.Event()

    def worker_build():
        started.set()
        release.wait(10)
        return s.get_or_build(key(), 1, lambda: ("g",), staged(arr, calls),
                              staged=True)

    arrived, rendezvous = threading.Semaphore(0), s._await_upload

    def await_upload(k):
        arrived.release()
        return rendezvous(k)

    s._await_upload = await_upload
    assert s.schedule(key(), 1, worker_build)
    assert not s.schedule(key(), 1, never)          # deduped by key
    assert started.wait(10)
    waiters = [run(s.get_or_build, key(), 1, lambda: ("g",), never)
               for _ in range(2)]
    assert arrived.acquire(timeout=10) and arrived.acquire(timeout=10)
    time.sleep(0.1)                         # both hold the upload's event
    release.set()
    for t, out in waiters:
        t.join(10)
        assert out == [arr]
    s.close()
    up = s.upload_stats()
    assert calls == ["build"]
    assert (up["hits"], up["sync_misses"]) == (2, 0)
    assert (up["scheduled"], up["completed"], up["errors"]) == (1, 1, 0)
    assert (up["inflight"], up["queued"]) == (0, 0)
    assert s.snapshot()["uploads"] == 1
    for name in ("stack.build", "stack.upload"):
        assert stats.counter_value(f"span.{name}.count") == 1
    assert stats.counter_value("span.stack.wait.count") == 2
    assert stats.counter_value("planner.prefetchHit") == 2
    assert stats.counter_value("planner.prefetchScheduled") == 1


def test_a_miss_with_no_upload_scheduled_is_a_sync_miss():
    stats = MemoryStats()
    s, arr = store(stats=stats), FakeArray(10)
    got = s.get_or_build(key(), 1, lambda: ("g",), staged(arr), staged=True)
    assert got is arr
    up = s.upload_stats()
    assert (up["sync_misses"], up["hits"], up["scheduled"]) == (1, 0, 0)
    assert stats.counter_value("planner.prefetchSyncMiss") == 1
    assert stats.counter_value("span.stack.wait.count") == 0


def test_a_worker_does_not_wait_on_its_own_key():
    s, arr = store(), FakeArray(10)
    s.WAIT_TIMEOUT_S = 30.0                # a deadlock would sit this out
    done = threading.Event()

    def worker_build():
        s.get_or_build(key(), 1, lambda: ("g",), staged(arr), staged=True)
        done.set()

    assert s.schedule(key(), 1, worker_build)
    assert done.wait(5)
    s.close()
    up = s.upload_stats()
    assert (up["completed"], up["hits"], up["sync_misses"]) == (1, 0, 0)
    assert s.get(key(), 1) is arr


def test_close_releases_a_waiter():
    s, arr = store(), FakeArray(10)
    s.MAX_WORKERS = 0                      # queued, and nobody builds it
    assert s.schedule(key(), 1, never)
    t, out = run(s.get_or_build, key(), 1, lambda: ("g",), lambda: arr)
    t.join(0.2)
    assert t.is_alive()                    # waiting on the upload in flight
    s.close()
    t.join(10)
    assert out == [arr]                    # released; built it itself
    assert not s.schedule(key(2), 1, never)     # closed: takes no work
    up = s.upload_stats()
    assert (up["inflight"], up["queued"]) == (0, 0)


def test_schedule_skips_what_is_resident_and_current():
    s = store()
    s.insert(key(), 3, ("g",), FakeArray(10), 10)
    assert not s.schedule(key(), 3, never)
    assert s.upload_stats()["scheduled"] == 0
    ran = threading.Event()
    assert s.schedule(key(), 4, ran.set)   # stale stamp: worth a look
    assert ran.wait(5)
    s.close()


def test_a_store_whose_uploads_do_not_run_ahead_schedules_nothing():
    s, arr = store(uploads_ahead=False), FakeArray(10)
    assert not s.schedule(key(), 1, never)
    assert s.get_or_build(key(), 1, lambda: ("g",), staged(arr),
                          staged=True) is arr
    up = s.upload_stats()
    assert (up["scheduled"], up["sync_misses"]) == (0, 1)
    s.close()


def test_a_failed_upload_is_counted_and_its_waiter_builds():
    s, arr = store(), FakeArray(10)
    started, release = threading.Event(), threading.Event()

    def failing():
        started.set()
        release.wait(10)
        raise RuntimeError("no such fragment")

    assert s.schedule(key(), 1, failing)
    assert started.wait(10)
    t, out = run(s.get_or_build, key(), 1, lambda: ("g",), lambda: arr)
    release.set()
    t.join(10)
    assert out == [arr]
    s.close()
    up = s.upload_stats()
    assert (up["errors"], up["completed"], up["inflight"]) == (1, 1, 0)


def test_key_slots_are_read_by_name():
    k = StackKey("i", 1, "f", "standard", ("hll", 8, 12), (0, 1), "hll")
    assert (k.index, k.instance_id, k.field, k.view, k.tag, k.shards,
            k.klass) == tuple(k)
    assert k == tuple(k) and hash(k) == hash(tuple(k))
