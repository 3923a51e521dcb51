#!/usr/bin/env python3
"""From the profiler's ``.xplane.pb`` to busy time, idle gaps and names.

Run as a process of its own (``python benchmark/trace_reduce.py <file>``),
after the server has been killed: ``jax.profiler.ProfileData`` needs jax,
and ``run.py`` never imports it. Prints one JSON object.

Busy is the union of the intervals in which an operation ran on the device
(the device planes' "XLA Ops" lines), clipped to the span; idle is the span
less busy. Device operations are summed by name, per program where the
trace has an "XLA Modules" line. Each idle gap is named after the host
event that covers most of it.
"""

from __future__ import annotations

import json
import sys

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    return sum(e - s for s, e in merge(intervals))


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def gaps(busy: list[tuple[float, float]], t0: float, t1: float):
    """The idle intervals of ``[t0, t1]`` between merged busy ones."""
    out, at = [], t0
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return out


def name_gap(gap, host_events) -> str:
    """The host event that covers most of the gap; among events that
    cover at least half of it, the shortest (the most specific)."""
    g0, g1 = gap
    best, best_key = "untraced_host", None
    for name, s, e in host_events:
        cover = min(e, g1) - max(s, g0)
        if cover <= 0:
            continue
        half = cover >= 0.5 * (g1 - g0)
        key = (half, -(e - s) if half else cover)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def reduce_events(device_ops, device_modules, host_events,
                  t0: float, t1: float, n_devices: int) -> dict:
    """``device_ops``/``device_modules``: ``{plane: [(name, start, end)]}``
    in seconds; ``host_events``: ``[(name, start, end)]``. Busy and idle
    are averaged over the device planes."""
    span = t1 - t0
    if span <= 0 or n_devices <= 0:
        raise ValueError(f"empty traced span or no device: {span}, "
                         f"{n_devices}")
    busy_total, by_name, gap_by_name = 0.0, {}, {}
    for plane, events in device_ops.items():
        busy = merge(clip([(s, e) for _, s, e in events], t0, t1))
        busy_total += sum(e - s for s, e in busy)
        for gap in gaps(busy, t0, t1):
            nm = name_gap(gap, host_events)
            gap_by_name[nm] = gap_by_name.get(nm, 0.0) + gap[1] - gap[0]
        named = device_modules.get(plane) or events
        for nm, s, e in named:
            d = min(e, t1) - max(s, t0)
            if d > 0:
                by_name[nm] = by_name.get(nm, 0.0) + d
    busy_s = busy_total / n_devices
    if busy_s > span * 1.0001:
        raise ValueError(f"busy {busy_s} s exceeds the span {span} s")

    def top(seconds_by_name: dict) -> list:
        return sorted(([k, v / n_devices] for k, v in seconds_by_name.items()),
                      key=lambda kv: -kv[1])[:TOP]

    return {"busy_s": busy_s, "window_s": span,
            "idle_share": 1.0 - busy_s / span,
            "device_ops": top(by_name), "idle_gaps": top(gap_by_name)}


def load(path: str):
    """-> (device_ops, device_modules, host_events, t_min, t_max), times
    in seconds on the trace's own clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, device_modules, host_events = {}, {}, []
    t_min, t_max = float("inf"), float("-inf")
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        is_host = plane.name.startswith(HOST_PREFIX)
        if not (is_dev or is_host):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for ev in line.events]
            if not evs:
                continue
            t_min = min(t_min, min(s for _, s, _ in evs))
            t_max = max(t_max, max(e for _, _, e in evs))
            if is_dev and line.name == OPS_LINE:
                device_ops.setdefault(plane.name, []).extend(evs)
            elif is_dev and line.name == MODULES_LINE:
                device_modules.setdefault(plane.name, []).extend(evs)
            elif is_host:
                host_events.extend(evs)
    return device_ops, device_modules, host_events, t_min, t_max


def dump(path: str) -> dict:
    """Planes, lines and the commonest names: for the look by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            names, total, first, last = {}, 0.0, None, None
            for ev in line.events:
                names[ev.name] = names.get(ev.name, 0) + 1
                total += ev.duration_ns * 1e-9
                first = ev.start_ns if first is None else min(first,
                                                              ev.start_ns)
                last = max(last or 0, ev.start_ns + ev.duration_ns)
            lines.append({"line": line.name, "events": sum(names.values()),
                          "seconds": total, "first_ns": first,
                          "last_ns": last,
                          "top": sorted(names.items(),
                                        key=lambda kv: -kv[1])[:12]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


def main(argv: list[str]) -> int:
    if len(argv) >= 2 and argv[0] == "--dump":
        print(json.dumps(dump(argv[1]), indent=1))
        return 0
    path = argv[0]
    device_ops, device_modules, host_events, t_min, t_max = load(path)
    # The span runs from the first event of any plane to the last
    # (ProfileData's clock starts with the profiling session). The host's
    # threads are never silent under load, so the first tens of
    # milliseconds of a session, in which no plane has an event, are the
    # profiler starting up and not idle time.
    t0, t1 = t_min, t_max
    n = len(device_ops)
    if n == 0:
        print(json.dumps({"busy_s": 0.0, "window_s": max(t1 - t0, 0.0),
                          "device_planes": 0, "t_min": t_min,
                          "t_max": t_max}))
        return 0
    out = reduce_events(device_ops, device_modules, host_events, t0, t1, n)
    out.update(device_planes=n, t_min=t_min, t_max=t_max)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
