"""Adaptive concurrency limit: a hill-climb on completed work.

The static ``qos-max-concurrent`` gate has to be hand-tuned per host and
per workload: too low and requests queue while the device and the stack
builders idle, too high and the queue moves inside the gate, to the
interpreter lock, where nothing schedules it. So the operative limit is
*measured*, and ``qos-max-concurrent`` is its ceiling.

What is measured is goodput: by Little's law the completions per second
a window's requests were served at, mean in-gate concurrency over mean
service time. The rule:

- A queue in front of the gate is demand, never congestion. It is the
  precondition for probing upward: raising a limit that nobody waits on
  tells nothing.
- A probe moves the limit one step for one window and is judged against
  the window before it. One step up is kept only when it bought goodput
  (``PROBE_GAIN``); one step down is kept unless it cost goodput or time
  in the system, queue and service together (``PROBE_LOSS``). So among
  limits with the same goodput the lowest wins, and the queue stands in
  front of the gate, where the weighted classes, the deadlines and the
  shed act. A step down is tried only where there is something to gain
  from it: a standing queue, or service times stretched well beyond the
  window's fastest request (``INFLATED``: requests wait on each other
  inside the gate). A limit that nobody reaches first comes down to
  just above what is in the gate, unjudged: the limits between serve
  alike.
- Goodput that falls by ``COLLAPSE`` at an unchanged limit while service
  times grow by as much is congestion (a convoy, a saturated
  interpreter; demand that falls shortens nothing): the limit backs off
  multiplicatively, from what is in the gate.
- A probe that was reverted doubles the wait before the next one in its
  direction (up to ``MAX_PATIENCE`` windows) and the length of the
  windows (up to ``MAX_WINDOW``; a probe that was kept halves it): the
  longer a limit has held its place, the more evidence moves it. So a
  settled limit leaves its place for one window at a time, one step at
  a time, and rarely.

Deliberately sample-windowed rather than wall-clocked: a window is judged
after a number of completed requests, fed through ``observe`` with the
in-gate count at release, so tests drive the limit deterministically: no
clock injection, no sleeps. A window runs until the mean service time is
known to ``SE_TARGET`` (its standard error over the mean), from
``window`` to ``MAX_WINDOW`` completions: two-peaked service times (a
cache hit in milliseconds, a miss behind a stack build in tens) need
hundreds of completions where uniform ones need tens. What the
constants were sized on, on the chip: ``PERF.md`` §6, PR 32.
"""

from __future__ import annotations

import math
import threading
from typing import NamedTuple

#: A window of which this share of the requests queued for their slot had
#: a standing queue: demand above the limit.
QUEUED_SHARE = 0.5
#: One step up is kept for this much more goodput.
PROBE_GAIN = 0.10
#: One step down is kept when it cost less than this, in goodput and in
#: time in the system. Under ``PROBE_GAIN`` / (1 + ``PROBE_GAIN``), so
#: that no pair of limits is left and returned to in turn.
PROBE_LOSS = 0.05
#: Goodput that fell by this share at an unchanged limit is congestion.
COLLAPSE = 0.35
#: Mean service time over the window's fastest: requests wait on each
#: other inside the gate, and a lower limit may serve as much.
INFLATED = 2.0
#: A window closes once the standard error of its mean service time is
#: under this share of the mean ...
SE_TARGET = 0.04
#: ... or at this many completions.
MAX_WINDOW = 512
#: Most windows between two probes in one direction.
MAX_PATIENCE = 16

UP, DOWN = 1, -1


class _Record(NamedTuple):
    """What a window at the held limit read; the next is judged by it."""
    goodput: float
    in_system: float  # queue and service together, seconds
    service: float


class AdaptiveLimit:
    """Concurrency limit that follows goodput, fed by ``observe``.

    Between ``floor`` and ``ceiling``, from ``ceiling // 2``. Each judged
    window either holds the limit, tries one step (a probe), keeps or
    reverts the last probe, or backs off (x ``backoff``); the rule is in
    the module's docstring.
    """

    def __init__(self, ceiling: int, floor: int = 1, window: int = 64,
                 backoff: float = 0.8, stats=None):
        if ceiling < 1:
            raise ValueError("adaptive ceiling must be >= 1")
        self.ceiling = ceiling
        self.floor = max(1, min(floor, ceiling))
        self.window = max(1, window)
        self.backoff = backoff
        self.stats = stats
        # Start in the middle: room to probe up under a queue, and not
        # far to come down where less serves as much.
        self._limit = max(self.floor, ceiling // 2)
        self._reset_window()
        self._ref: _Record | None = None
        #: the limit a probe in flight left, to go back to.
        self._probe_from: int | None = None
        #: windows to wait before the next probe, and the wait a revert
        #: sets, by direction.
        self._wait = {UP: 0, DOWN: 0}
        self._patience = {UP: 1, DOWN: 1}
        self._last_probe = DOWN
        #: windows are this many times ``window`` long at the least:
        #: doubled by a probe that was reverted, halved by one kept.
        self._settled = 1
        self._drain = 0
        self._decisions = {"probeKept": 0, "probeReverted": 0, "backoff": 0}
        self._last: dict | None = None
        self._lock = threading.Lock()
        if stats is not None:
            for kind in self._decisions:
                stats.count("qos.adaptive." + kind, 0)  # listed from boot

    @property
    def limit(self) -> int:
        return self._limit

    def _reset_window(self) -> None:
        self._n = 0
        self._queued = 0
        self._sum_wait = 0.0
        self._sum_service = 0.0
        self._sum_service_sq = 0.0
        self._sum_inflight = 0.0
        self._fastest = math.inf

    def observe(self, wait_s: float, service_s: float, inflight: int) -> None:
        """Record one completed request: the time it queued for its slot
        (0.0 when it was admitted at once), its time inside the gate, and
        the in-gate count when it left (itself included)."""
        with self._lock:
            if self._drain > 0:
                self._drain -= 1
                return
            self._n += 1
            if wait_s > 0.0:
                self._queued += 1
            self._sum_wait += wait_s
            self._sum_service += service_s
            self._sum_service_sq += service_s * service_s
            self._sum_inflight += inflight
            self._fastest = min(self._fastest, service_s)
            if self._n >= min(MAX_WINDOW, self.window * self._settled) \
                    and self._window_closed():
                before = self._limit
                self._judge()
                if self._limit != before:
                    # Whoever is in the gate now was admitted under the
                    # old limit: their completions are no window's.
                    self._drain = inflight

    def _window_closed(self) -> bool:
        n = self._n
        if n >= MAX_WINDOW:
            return True
        mean = self._sum_service / n
        var = max(0.0, self._sum_service_sq / n - mean * mean)
        return math.sqrt(var / n) <= SE_TARGET * mean

    def _judge(self) -> None:
        n = self._n
        service = self._sum_service / n
        inflight = self._sum_inflight / n
        in_system = (self._sum_wait + self._sum_service) / n
        saturated = self._queued >= QUEUED_SHARE * n
        inflated = service > INFLATED * self._fastest
        goodput = inflight / service if service > 0.0 else 0.0
        self._reset_window()
        self._last = {"goodput": round(goodput, 3),
                      "inflight": round(inflight, 3),
                      "serviceMs": round(service * 1000.0, 3),
                      "completions": n, "limit": self._limit,
                      "saturated": saturated}
        now = _Record(goodput, in_system, service)

        if self._probe_from is not None:
            if not self._settle_probe(now):
                # After a probe that was reverted the limit rests a
                # window at its place: the next probe has a fresh record.
                saturated = inflated = False
        elif (self._ref is not None
              and goodput < (1.0 - COLLAPSE) * self._ref.goodput
              and service * (1.0 - COLLAPSE) > self._ref.service):
            # Less was completed though each request took longer: as
            # many were in the gate, so it is not demand that fell.
            new = max(self.floor, int(min(self._limit, math.ceil(inflight))
                                      * self.backoff))
            if new == self._limit and new > self.floor:
                new -= 1  # a back-off always makes progress
            if new != self._limit:
                self._decide("backoff")
            self._limit = new
            self._ref = None
            self._settled = 1
            self._wait = {UP: self._patience[UP], DOWN: 0}
        else:
            self._ref = now

        if self._ref is not None:
            self._next_probe(saturated, inflated, inflight)
        if self.stats is not None:
            self.stats.gauge("qos.adaptiveLimit", float(self._limit))

    def _settle_probe(self, now: _Record) -> bool:
        back, self._probe_from = self._probe_from, None
        ref = self._ref
        if self._limit > back:
            way = UP
            kept = now.goodput >= (1.0 + PROBE_GAIN) * ref.goodput
        else:
            way = DOWN
            kept = (now.goodput >= (1.0 - PROBE_LOSS) * ref.goodput
                    and now.in_system <= (1.0 + PROBE_LOSS) * ref.in_system)
        if kept:
            self._decide("probeKept")
            self._settled = max(1, self._settled // 2)
            self._patience[way] = 1
            self._wait[way] = 0
            self._put_off(-way)  # the limit left behind was just measured
            if way == UP:
                self._ref = now
            else:
                # Steps down in a row are all held to the record they
                # started from: losses under the tolerance do not add up.
                self._ref = _Record(max(ref.goodput, now.goodput),
                                    min(ref.in_system, now.in_system),
                                    now.service)
        else:
            self._decide("probeReverted")
            self._limit = back
            self._settled = min(2 * self._settled,
                                max(1, MAX_WINDOW // self.window))
            self._put_off(way)
        return kept

    def _put_off(self, way: int) -> None:
        self._patience[way] = min(MAX_PATIENCE, 2 * self._patience[way])
        self._wait[way] = self._patience[way]

    def _next_probe(self, saturated: bool, inflated: bool,
                    inflight: float) -> None:
        wanted = {UP: saturated and self._limit < self.ceiling,
                  DOWN: (saturated or inflated) and self._limit > self.floor}
        ways = []
        for way in (UP, DOWN):
            if self._wait[way] > 0:
                self._wait[way] -= 1
            elif wanted[way]:
                ways.append(way)
        if not ways:
            return
        # Both open: the one not tried last.
        way = ways[0] if len(ways) == 1 else -self._last_probe
        if way == DOWN and self._limit > math.ceil(inflight) + 1:
            # A limit nobody reaches comes down to just above what is in
            # the gate with no probe: the limits between serve alike.
            self._limit = math.ceil(inflight) + 1
            return
        self._last_probe = way
        self._probe_from = self._limit
        self._limit += way

    def _decide(self, kind: str) -> None:
        self._decisions[kind] += 1
        if self.stats is not None:
            self.stats.count("qos.adaptive." + kind, 1)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "limit": self._limit,
                "ceiling": self.ceiling,
                "floor": self.floor,
                **self._decisions,
                "probing": self._probe_from is not None,
                "pending": self._n,
                "lastWindow": self._last,
            }
