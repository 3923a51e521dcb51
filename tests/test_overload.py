"""Overload-resilience tests: adaptive concurrency, per-tenant quotas,
per-peer circuit breakers, and hedged reads.

The adaptive/quota/breaker/hedge-budget units are driven with fake
clocks or sample counts — no sleeps, fully deterministic. The
integration tests drive the real HTTP edge (429 + Retry-After contract,
/debug/overload) and the in-process LocalCluster (hedge wins against a
slow peer; breaker opens and re-closes around a heal).
"""

import collections
import heapq
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.cluster.breaker import (
    BreakerOpenError,
    BreakerRegistry,
    CircuitBreaker,
    HedgePolicy,
)
from pilosa_tpu.qos import (
    CLASS_INTERACTIVE,
    CLASS_INTERNAL,
    AdaptiveLimit,
    AdmissionController,
    Deadline,
    DeadlineExceededError,
    QueryShedError,
    QuotaExceededError,
    TenantQuotas,
    reset_current_deadline,
    set_current_deadline,
)
from pilosa_tpu.server.node import ServerNode


# ---------------------------------------------------------------------------
# Adaptive concurrency limit
# ---------------------------------------------------------------------------


def closed_loop(limit, clients, service_of, completions):
    """``clients`` closed-loop clients (an answer back, the next request
    out) through a gate that follows ``limit``, on a virtual clock: an
    event queue, no sleeps. ``service_of(k)`` is the service time of a
    request admitted as the k-th in the gate. Returns the limit after
    each completion."""
    active = seq = 0
    waiting = collections.deque([0.0] * clients)  # arrival times
    running = []  # (done at, seq, queued for, service)
    trajectory = []
    now = 0.0
    for _ in range(completions + 1):
        while waiting and active < limit.limit:
            arrived = waiting.popleft()
            active += 1
            service = service_of(active)
            heapq.heappush(running, (now + service, seq, now - arrived,
                                     service))
            seq += 1
        if len(trajectory) == completions:
            break
        now, _, queued_for, service = heapq.heappop(running)
        limit.observe(queued_for, service, active)
        active -= 1
        waiting.append(now)  # behind whoever waits already
        trajectory.append(limit.limit)
    return trajectory


BASE = 0.003


def interpreter_bound(k):
    """Requests share one interpreter: goodput is flat in the limit."""
    return BASE * k


def overlapping_waits(k, n=6):
    """Waits that overlap: goodput grows with the limit up to ``n``."""
    return BASE * max(1.0, k / n)


def convoy(k, knee=5):
    """Above the knee everyone waits on everyone: a third of the work."""
    return BASE if k <= knee else BASE * 3 * k / knee


SIMULATIONS = {
    # name: (service model, clients, start, where it settles)
    "a_interpreter_bound_settles_low": (interpreter_bound, 24, None, 1),
    "b_overlap_reaches_n_from_floor": (overlapping_waits, 24, 1, 6),
    "b_overlap_reaches_n_from_half": (overlapping_waits, 24, None, 6),
    "c_convoy_returns_under_knee_from_floor": (convoy, 24, 1, 5),
    "c_convoy_returns_under_knee_from_half": (convoy, 24, None, 5),
    "d_no_queue_flat_latency_holds": (lambda k: BASE, 3, None, 16),
}


def simulate(name, completions=12_000, stats=None):
    model, clients, start, settles = SIMULATIONS[name]
    a = AdaptiveLimit(ceiling=32, stats=stats)
    assert a.limit == 16  # ceiling // 2
    if start is not None:
        a._limit = start
    return a, closed_loop(a, clients, model, completions), settles


@pytest.mark.parametrize("name", sorted(SIMULATIONS))
def test_adaptive_limit_closed_loop(name):
    a, trajectory, settles = simulate(name)
    # Settled inside the first third (the oversubscribed cell's warm-up
    # is 200-1,200 completions), and then never more than one step (a
    # probe's one window) from its place.
    assert trajectory[len(trajectory) // 3] in (settles - 1, settles,
                                                 settles + 1), trajectory[::64]
    steady = trajectory[len(trajectory) // 3:]
    assert max(abs(l - settles) for l in steady) <= 1, steady[::64]
    assert steady.count(settles) > 0.8 * len(steady)
    snap = a.snapshot()
    if name.startswith("d_"):
        # Nobody waits and latency is flat: nothing to learn, nothing done.
        assert set(trajectory) == {16}
        assert (snap["probeKept"], snap["probeReverted"],
                snap["backoff"]) == (0, 0, 0)
    else:
        assert snap["lastWindow"]["saturated"]  # the queue never went away
    assert snap["backoff"] == 0  # ... and alone it is not congestion


def test_adaptive_limit_holds_without_a_queue():
    """Raising a limit that nobody waits on tells nothing: no runaway."""
    a = AdaptiveLimit(ceiling=16, window=4)
    start = a.limit
    for _ in range(50 * 4):
        a.observe(0.0, 0.01, 2)  # admitted at once, flat latency
    assert a.limit == start
    snap = a.snapshot()
    assert snap["probeKept"] == snap["probeReverted"] == snap["backoff"] == 0
    assert snap["lastWindow"]["goodput"] == pytest.approx(200.0)
    assert snap["lastWindow"]["inflight"] == pytest.approx(2.0)


def test_adaptive_limit_probes_up_under_a_queue_that_waits_long():
    """The parent's fault: 100 ms of queue wait read as congestion, and
    the limit pinned at its floor. A queue is demand: the limit rises
    while each step buys goodput, however long the wait."""
    a = AdaptiveLimit(ceiling=16, window=4)
    a._limit = 1
    for _ in range(40):
        limit = a.limit
        for _ in range(4):
            a.observe(0.1, 0.01, limit)  # goodput = limit / 10 ms
    assert a.limit >= 10
    assert a.snapshot()["backoff"] == 0


def test_adaptive_limit_backs_off_when_goodput_falls():
    """Goodput that falls at an unchanged limit while service times grow
    is congestion: the multiplicative back-off, in one window."""
    a = AdaptiveLimit(ceiling=16, backoff=0.8)
    slow = [1.0]
    trajectory = closed_loop(a, 24, lambda k: slow[0] * overlapping_waits(k),
                             6_000)
    assert trajectory[-1] == 6 and a.snapshot()["backoff"] == 0
    slow[0] = 3.0  # the same requests now take three times as long
    trajectory = closed_loop(a, 24, lambda k: slow[0] * overlapping_waits(k),
                             1_200)  # a settled limit's windows are long
    assert a.snapshot()["backoff"] == 1
    assert int(6 * 0.8) in trajectory


def test_adaptive_limit_floor_and_ceiling():
    a = AdaptiveLimit(ceiling=4, floor=1, window=2)
    closed_loop(a, 8, interpreter_bound, 400)
    assert a.limit == 1  # never below the floor
    trajectory = closed_loop(a, 8, lambda k: BASE, 400)  # every slot pays
    assert a.limit == 4 and max(trajectory) == 4  # never above the ceiling


def test_admission_gate_follows_adaptive_limit():
    """With the adaptive limit down at 1, a max_concurrent=4 gate admits
    exactly one public query — but internal legs still ride the reserve
    above the CEILING (deadlock guard intact)."""
    a = AdaptiveLimit(ceiling=4, window=2)
    closed_loop(a, 8, interpreter_bound, 400)
    assert a.limit == 1
    ctl = AdmissionController(max_concurrent=4, max_queue=4,
                              internal_reserve=1, adaptive=a)
    assert ctl.snapshot()["limit"] == 1
    assert ctl.acquire(CLASS_INTERACTIVE) is False  # did not queue
    # second public request queues (would admit under the static gate)
    with pytest.raises((QueryShedError, DeadlineExceededError)):
        ctl.acquire(CLASS_INTERACTIVE, deadline=Deadline(timeout=0.05))
    # internal reserve is above the ceiling, not the adaptive value
    got = threading.Event()

    def internal():
        with ctl.admit(CLASS_INTERNAL):
            got.set()

    t = threading.Thread(target=internal)
    t.start()
    assert got.wait(2), "internal leg blocked by the adaptive limit"
    t.join(5)
    assert ctl.release() == 1  # the in-gate count it was one of


@pytest.mark.parametrize("name", ["b_overlap_reaches_n_from_floor",
                                  "c_convoy_returns_under_knee_from_floor"])
def test_adaptive_decisions_are_counted(name):
    """What /debug/vars and /debug/overload show of the mechanism."""
    from pilosa_tpu.obs.stats import MemoryStats
    stats = MemoryStats()
    a, _, settles = simulate(name, stats=stats)
    snap = a.snapshot()
    assert snap["probeKept"] >= settles - 1  # it climbed there
    assert snap["probeReverted"] >= 2  # and found both neighbours worse
    for kind in ("probeKept", "probeReverted", "backoff"):
        assert stats.counter_value("qos.adaptive." + kind) == snap[kind]
    assert stats.gauges[("qos.adaptiveLimit", ())] == a.limit
    last = snap["lastWindow"]
    assert last["inflight"] == pytest.approx(last["limit"])
    assert last["goodput"] == pytest.approx(
        last["inflight"] / (last["serviceMs"] / 1e3), rel=1e-3)


def test_admission_feeds_adaptive_from_public_classes_only():
    a = AdaptiveLimit(ceiling=8, window=4)
    ctl = AdmissionController(max_concurrent=8, adaptive=a)
    for _ in range(3):
        with ctl.admit(CLASS_INTERNAL):
            pass
    assert a.snapshot()["pending"] == 0  # internal legs don't feed it
    with ctl.admit(CLASS_INTERACTIVE):
        pass
    assert a.snapshot()["pending"] == 1


def test_admission_with_adaptive_limit_under_threads():
    """More threads than cores through the real gate, the interpreter
    switching often: every slot comes back, nobody is admitted over the
    limit of the moment, and the limit judged windows on the way."""
    import sys
    a = AdaptiveLimit(ceiling=8, window=8)
    ctl = AdmissionController(max_concurrent=8, max_queue=64, adaptive=a)
    over = []
    seen = set()

    def client():
        for _ in range(150):
            with ctl.admit(CLASS_INTERACTIVE):
                # the limit may have fallen since this one was admitted,
                # so the bound that holds is the ceiling
                if ctl.snapshot()["active"] > 8:
                    over.append(1)
                seen.add(a.limit)
                time.sleep(0.0002)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client) for _ in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    snap = ctl.snapshot()
    assert not over
    assert snap["active"] == 0 and snap["queuedTotal"] == 0
    assert snap["admitted"] == 24 * 150 and snap["shed"] == 0
    assert a.snapshot()["lastWindow"] is not None
    assert seen <= set(range(1, 9))


# ---------------------------------------------------------------------------
# Per-tenant quotas
# ---------------------------------------------------------------------------


def test_quota_exhaustion_and_refill():
    clk = [0.0]
    q = TenantQuotas(rate_per_s=1.0, burst=2, clock=lambda: clk[0])
    q.check("t1")
    q.check("t1")
    with pytest.raises(QuotaExceededError) as ei:
        q.check("t1")
    assert ei.value.retry_after == pytest.approx(1.0)
    assert q.snapshot()["rejected"] == 1
    clk[0] = 1.5  # 1.5 tokens refilled
    q.check("t1")
    with pytest.raises(QuotaExceededError):
        q.check("t1")


def test_quota_tenant_isolation():
    clk = [0.0]
    q = TenantQuotas(rate_per_s=1.0, burst=1, clock=lambda: clk[0])
    q.check("flooder")
    with pytest.raises(QuotaExceededError):
        q.check("flooder")
    q.check("bystander")  # unaffected by the flooder's exhaustion


def test_quota_burst_caps_refill():
    clk = [0.0]
    q = TenantQuotas(rate_per_s=10.0, burst=3, clock=lambda: clk[0])
    clk[0] = 100.0  # ages don't accumulate past the burst
    for _ in range(3):
        q.check("t")
    with pytest.raises(QuotaExceededError):
        q.check("t")


def test_quota_tenant_table_bounded():
    from pilosa_tpu.qos.quota import MAX_TENANTS
    q = TenantQuotas(rate_per_s=1.0, burst=5, clock=lambda: 0.0)
    for i in range(MAX_TENANTS + 10):
        q.check(f"tenant-{i}")
    assert q.snapshot()["tenants"] <= MAX_TENANTS


def test_quota_empty_tenant_is_unmetered():
    q = TenantQuotas(rate_per_s=1.0, burst=1, clock=lambda: 0.0)
    for _ in range(10):
        q.check("")  # no tenant identity -> no bucket


# ---------------------------------------------------------------------------
# Circuit breaker
# ---------------------------------------------------------------------------


def test_breaker_state_machine():
    t = [0.0]
    br = CircuitBreaker(threshold=3, cooldown=5.0, clock=lambda: t[0])
    assert br.state == "closed"
    br.record_failure()
    br.record_failure()
    assert br.state == "closed"  # under threshold
    assert br.record_failure() is True  # the opening transition
    assert br.state == "open"
    assert br.allow() == (False, 5.0)
    t[0] = 5.1
    assert br.state == "half-open"
    ok, _ = br.allow()
    assert ok  # the single half-open probe
    assert br.allow()[0] is False  # everyone else keeps fast-failing
    br.record_failure()  # failed probe restarts the cooldown
    assert br.state == "open"
    assert br.record_failure() is False  # re-failing while open: no event
    t[0] = 10.2
    ok, _ = br.allow()
    assert ok
    br.record_success()
    assert br.state == "closed"
    assert br.opens == 1


def test_breaker_success_resets_failure_streak():
    br = CircuitBreaker(threshold=3, cooldown=5.0, clock=lambda: 0.0)
    br.record_failure()
    br.record_failure()
    br.record_success()  # streak broken: consecutive failures only
    br.record_failure()
    br.record_failure()
    assert br.state == "closed"


def test_breaker_registry_fast_fails_as_connection_error():
    """BreakerOpenError IS a ConnectionError — the executor's existing
    replica-failover catch absorbs fast-fails with zero changes."""
    t = [0.0]
    reg = BreakerRegistry(threshold=1, cooldown=5.0, clock=lambda: t[0])
    reg.record_failure("p1")
    with pytest.raises(ConnectionError) as ei:
        reg.check("p1")
    assert isinstance(ei.value, BreakerOpenError)
    assert ei.value.peer_id == "p1"
    reg.check("p2")  # other peers unaffected
    snap = reg.snapshot()
    assert snap["peers"]["p1"]["state"] == "open"


def test_breaker_open_counts_in_stats():
    from pilosa_tpu.obs import MemoryStats
    stats = MemoryStats()
    reg = BreakerRegistry(threshold=2, cooldown=5.0, stats=stats)
    reg.record_failure("p1")
    reg.record_failure("p1")
    reg.record_failure("p1")  # already open: no second transition
    assert stats.counter_value("cluster.breakerOpen", "peer:p1") == 1


def test_httpclient_breaker_opens_on_unreachable_peer():
    """Connection failures trip the breaker; the next call fast-fails
    without dialing (instant, not a socket timeout)."""
    import socket

    from pilosa_tpu.cluster.node import URI, Node
    from pilosa_tpu.server.httpclient import HTTPInternalClient

    # grab a port nothing listens on
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    node = Node(id="deadpeer", uri=URI(host="127.0.0.1", port=port))
    client = HTTPInternalClient(timeout=1.0)
    client.breakers = BreakerRegistry(threshold=2, cooldown=30.0)
    for _ in range(2):
        with pytest.raises(ConnectionError):
            client._request_raw(node, "GET", "/version")
    t0 = time.perf_counter()
    with pytest.raises(BreakerOpenError):
        client._request_raw(node, "GET", "/version")
    assert time.perf_counter() - t0 < 0.1  # fast-fail, no dial
    assert client.breakers.state("deadpeer") == "open"


def test_breaker_probe_abort_releases_lease():
    """An aborted half-open probe (it never reached the peer) releases
    the single probe slot without restarting the cooldown — the next
    request may immediately claim a fresh probe."""
    t = [0.0]
    br = CircuitBreaker(threshold=1, cooldown=5.0, clock=lambda: t[0])
    br.record_failure()
    t[0] = 5.1
    ok, _ = br.allow()
    assert ok  # probe claimed
    assert br.allow()[0] is False
    br.abort()
    ok, _ = br.allow()
    assert ok  # lease released: a new probe goes out right away
    br.record_success()
    assert br.state == "closed"


def test_breaker_stale_probe_lease_expires():
    """A probe whose thread died without ever resolving (no success,
    failure, or abort) must not wedge the breaker open forever: the
    lease expires after one cooldown and a new probe is granted."""
    t = [0.0]
    br = CircuitBreaker(threshold=1, cooldown=5.0, clock=lambda: t[0])
    br.record_failure()
    t[0] = 5.1
    assert br.allow()[0] is True  # probe claimed, then lost
    assert br.allow()[0] is False
    t[0] = 10.3  # one full cooldown after the stale claim
    assert br.allow()[0] is True  # expired lease: re-probe allowed
    br.record_success()
    assert br.state == "closed"


def test_httpclient_expired_deadline_releases_breaker_probe():
    """A DeadlineExceededError raised BEFORE dialing (deadline spent)
    must not leave the claimed half-open probe dangling — that would
    fast-fail the peer until process restart."""
    from pilosa_tpu.cluster.node import URI, Node
    from pilosa_tpu.qos.deadline import DeadlineExceededError
    from pilosa_tpu.server.httpclient import HTTPInternalClient

    t = [0.0]
    node = Node(id="sickpeer", uri=URI(host="127.0.0.1", port=1))
    client = HTTPInternalClient(timeout=1.0)
    client.breakers = BreakerRegistry(threshold=1, cooldown=5.0,
                                      clock=lambda: t[0])
    client.breakers.record_failure("sickpeer")
    t[0] = 5.1  # cooldown elapsed: next request claims the probe
    tok = set_current_deadline(Deadline(timeout=-1.0))  # already expired
    try:
        with pytest.raises(DeadlineExceededError):
            client._request_raw(node, "GET", "/version")
    finally:
        reset_current_deadline(tok)
    # the lease was released: a fresh probe is immediately available
    assert client.breakers._breaker("sickpeer").allow()[0] is True


def test_localclient_app_error_resolves_breaker_probe():
    """LocalClient mirrors the HTTP client: a peer answering with an
    APPLICATION error is alive — the half-open probe records success
    and the breaker re-closes instead of wedging."""
    from pilosa_tpu.cluster.client import LocalClient
    from pilosa_tpu.cluster.node import URI, Node

    class AppErrorPeer:
        def handle_query(self, index, query, shards, remote):
            raise RuntimeError("bad query")

    t = [0.0]
    lc = LocalClient()
    lc.register("p1", AppErrorPeer())
    lc.breakers = BreakerRegistry(threshold=1, cooldown=5.0,
                                  clock=lambda: t[0])
    node = Node(id="p1", uri=URI(host="127.0.0.1", port=1))
    lc.down.add("p1")
    with pytest.raises(ConnectionError):
        lc.query_node(node, "i", "Count(Row(f=1))", [0])
    assert lc.breakers.state("p1") == "open"
    lc.down.discard("p1")
    t[0] = 5.1
    with pytest.raises(RuntimeError):
        lc.query_node(node, "i", "Count(Row(f=1))", [0])
    assert lc.breakers.state("p1") == "closed"


# ---------------------------------------------------------------------------
# Hedge policy
# ---------------------------------------------------------------------------


def test_hedge_budget_enforcement():
    """Hedges never exceed burst + budget_pct% of primary legs."""
    h = HedgePolicy(delay_s=0.01, budget_pct=5.0, burst=2)
    for _ in range(20):
        h.note_primary()
    fired = sum(1 for _ in range(50) if h.try_fire())
    # 2 burst + 5% of 20 primaries = 3
    assert fired == 3
    snap = h.snapshot()
    assert snap["fired"] == 3 and snap["primaries"] == 20


def test_hedge_budget_accrues_with_traffic():
    h = HedgePolicy(delay_s=0.01, budget_pct=10.0, burst=0)
    assert h.try_fire() is False  # no traffic, no budget
    for _ in range(10):
        h.note_primary()
    assert h.try_fire() is True  # 10% of 10 = 1 hedge earned
    assert h.try_fire() is False


def test_hedge_delay_fixed_vs_p95():
    h = HedgePolicy(delay_s=0.25)
    assert h.delay() == 0.25  # fixed override wins, no samples needed
    m = HedgePolicy(delay_s=0.0, min_samples=4)
    assert m.delay() is None  # not enough signal yet
    for v in (0.01, 0.01, 0.01, 0.5):
        m.observe(v)
    assert m.delay() == 0.5  # p95 of the window targets the tail


# ---------------------------------------------------------------------------
# 503 retry on idempotent POST legs (satellite)
# ---------------------------------------------------------------------------


class _PostSheddingHandler(
        __import__("http.server", fromlist=["x"]).BaseHTTPRequestHandler):
    """503 + Retry-After for the first ``fail_n`` POSTs, then 200 with a
    query-shaped body."""

    hits: list = []
    fail_n = 2

    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length") or 0))
        n = len(self.hits)
        self.hits.append(self.path)
        if n < self.fail_n:
            body = b'{"error": "shed"}'
            self.send_response(503)
            self.send_header("Retry-After", "0")
        else:
            body = b'{"results": [7]}'
            self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


@pytest.fixture
def post_shedding_node():
    from http.server import ThreadingHTTPServer

    from pilosa_tpu.cluster.node import URI, Node

    _PostSheddingHandler.hits = []
    _PostSheddingHandler.fail_n = 2
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _PostSheddingHandler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield Node(id="shedder",
               uri=URI(host="127.0.0.1", port=srv.server_address[1]))
    srv.shutdown()
    t.join(5)


def test_query_post_retries_503(post_shedding_node):
    """The /query read leg is an idempotent POST: it rides out transient
    sheds with the same bounded backoff GETs get."""
    from pilosa_tpu.server.httpclient import HTTPInternalClient

    client = HTTPInternalClient(timeout=5.0)
    results = client.query_node(post_shedding_node, "i", "Count(Row(f=1))",
                                None, remote=False)
    assert results == [7]
    assert len(_PostSheddingHandler.hits) == 3  # 2 sheds + 1 success


def test_non_idempotent_post_does_not_retry(post_shedding_node):
    """Cluster messages may not be re-sent on a shed: exactly one
    attempt, error surfaced to the caller."""
    from pilosa_tpu.server.httpclient import HTTPInternalClient, NodeHTTPError

    client = HTTPInternalClient(timeout=5.0)
    with pytest.raises(NodeHTTPError) as ei:
        client.send_message(post_shedding_node, {"type": "noop"})
    assert ei.value.code == 503
    assert len(_PostSheddingHandler.hits) == 1


# ---------------------------------------------------------------------------
# HTTP edge: 429 quota contract + /debug/overload
# ---------------------------------------------------------------------------


def _req(base, method, path, body=None, headers=None):
    data = body.encode() if isinstance(body, str) else body
    r = urllib.request.Request(base + path, data=data, method=method)
    for k, v in (headers or {}).items():
        r.add_header(k, v)
    try:
        with urllib.request.urlopen(r, timeout=30) as resp:
            return resp.status, json.loads(resp.read() or b"{}"), resp.headers
    except urllib.error.HTTPError as e:
        payload = e.read()
        try:
            parsed = json.loads(payload)
        except json.JSONDecodeError:
            parsed = {"raw": payload.decode()}
        return e.code, parsed, e.headers


@pytest.fixture
def quota_node():
    n = ServerNode(bind="127.0.0.1:0", use_planner=False,
                   qos_max_concurrent=4, qos_adaptive=True,
                   qos_tenant_rate=0.01, qos_tenant_burst=2.0)
    n.open()
    base = f"http://127.0.0.1:{n.port}"
    _req(base, "POST", "/index/i")
    _req(base, "POST", "/index/i/field/f")
    yield n, base
    n.close()


def test_http_quota_429_with_retry_after(quota_node):
    """Quota exhaustion is 429 + Retry-After (the tenant's fault),
    distinct from the 503 shed (the node's fault); other tenants keep
    flowing."""
    n, base = quota_node
    q = "/index/i/query?noCache=true"
    key = {"X-API-Key": "tenant-a"}
    for _ in range(2):  # burst = 2
        status, _, _ = _req(base, "POST", q, "Count(Row(f=1))", headers=key)
        assert status == 200
    status, payload, headers = _req(base, "POST", q, "Count(Row(f=1))",
                                    headers=key)
    assert status == 429, payload
    assert int(headers["Retry-After"]) >= 1
    # a different API key has its own bucket
    status, _, _ = _req(base, "POST", q, "Count(Row(f=1))",
                        headers={"X-API-Key": "tenant-b"})
    assert status == 200
    # without a key, the tenant is the index — also its own bucket
    status, _, _ = _req(base, "POST", q, "Count(Row(f=1))")
    assert status == 200
    assert n.quotas.snapshot()["rejected"] == 1
    assert n.stats.counter_value("qos.quotaRejected", "tenant:tenant-a") == 1


def test_http_remote_legs_exempt_from_quota(quota_node):
    """remote=true fan-out legs are not re-charged (the coordinator
    already paid)."""
    n, base = quota_node
    key = {"X-API-Key": "tenant-c"}
    for _ in range(5):
        status, payload, _ = _req(
            base, "POST", "/index/i/query?noCache=true&remote=true&shards=0",
            "Count(Row(f=1))", headers=key)
        assert status == 200, payload


def test_http_debug_overload_route(quota_node):
    n, base = quota_node
    _req(base, "POST", "/index/i/query?noCache=true", "Count(Row(f=1))",
         headers={"X-API-Key": "t"})
    status, payload, _ = _req(base, "GET", "/debug/overload")
    assert status == 200
    assert payload["admission"]["maxConcurrent"] == 4
    # adaptive is on: the operative limit rides under the ceiling
    assert payload["adaptive"] is not None
    assert 1 <= payload["adaptive"]["limit"] <= 4
    assert payload["admission"]["limit"] == payload["adaptive"]["limit"]
    # the mechanism's decisions by kind, and the last judged window (none
    # yet: one request is no window)
    for kind in ("probeKept", "probeReverted", "backoff"):
        assert payload["adaptive"][kind] == 0
    assert payload["adaptive"]["lastWindow"] is None
    assert payload["adaptive"]["pending"] == 1
    _, debug_vars, _ = _req(base, "GET", "/debug/vars")
    for kind in ("probeKept", "probeReverted", "backoff"):
        assert debug_vars["counters"]["qos.adaptive." + kind] == 0
    assert payload["quotas"]["ratePerS"] == pytest.approx(0.01)
    assert payload["quotas"]["tenants"] >= 1
    # standalone node: no cluster, so no breakers/hedge sections
    assert payload["breakers"] is None and payload["hedge"] is None


# ---------------------------------------------------------------------------
# LocalCluster integration: hedge wins, breaker recovery
# ---------------------------------------------------------------------------


@pytest.fixture
def overload_cluster():
    from pilosa_tpu.cluster.harness import LocalCluster
    from pilosa_tpu.config import SHARD_WIDTH

    lc = LocalCluster(3, replica_n=2)
    lc.create_index("i")
    lc.create_field("i", "f")
    for s in range(8):
        lc.query("i", f"Set({s * SHARD_WIDTH + 5}, f=1)")
    yield lc
    for cn in lc.nodes:
        cn.cluster.close()


def test_hedged_read_beats_slow_peer(overload_cluster):
    """With one peer serving every query 300ms late, a hedged read
    returns at the hedge delay, not the peer's latency — and the win is
    counted."""
    from pilosa_tpu.cluster.breaker import HedgePolicy

    lc = overload_cluster
    for cn in lc.nodes:
        cn.cluster.hedge = HedgePolicy(delay_s=0.03, burst=16)
    lc.slow("node1", 0.3)
    tok = set_current_deadline(Deadline(timeout=5.0))
    try:
        t0 = time.perf_counter()
        (got,) = lc.query("i", "Count(Row(f=1))", cache=False)
        dt = time.perf_counter() - t0
    finally:
        reset_current_deadline(tok)
    assert got == 8
    assert dt < 0.25, f"hedge did not absorb the slow peer ({dt:.3f}s)"
    snap = lc.nodes[0].cluster.hedge.snapshot()
    assert snap["fired"] >= 1 and snap["won"] >= 1


@pytest.mark.slow
def test_breaker_recovery_on_local_cluster(overload_cluster):
    """Slow-peer drill in miniature: deadline overruns open the sick
    peer's breaker, queries keep succeeding (hedge + failover), and a
    half-open probe re-closes it after the heal."""
    from pilosa_tpu.cluster.breaker import BreakerRegistry, HedgePolicy

    lc = overload_cluster
    reg = BreakerRegistry(threshold=3, cooldown=0.5)
    lc.client.breakers = reg
    for cn in lc.nodes:
        cn.cluster.hedge = HedgePolicy(delay_s=0.02, burst=32)
    lc.slow("node1", 0.4)
    failures = 0
    for _ in range(8):
        tok = set_current_deadline(Deadline(timeout=0.2))
        try:
            (got,) = lc.query("i", "Count(Row(f=1))", cache=False)
            assert got == 8
        except Exception:
            failures += 1
        finally:
            reset_current_deadline(tok)
    assert failures == 0, "queries failed due to the slow peer"
    # the abandoned primary legs overran their deadlines -> breaker open
    deadline = time.time() + 5
    while reg.state("node1") != "open" and time.time() < deadline:
        time.sleep(0.05)
    assert reg.state("node1") == "open"
    # heal; after the cooldown one probe re-closes it
    lc.fast("node1")
    time.sleep(0.6)
    for _ in range(3):
        tok = set_current_deadline(Deadline(timeout=5.0))
        try:
            lc.query("i", "Count(Row(f=1))", cache=False)
        finally:
            reset_current_deadline(tok)
        if reg.state("node1") == "closed":
            break
        time.sleep(0.6)
    assert reg.state("node1") == "closed"
