"""From a profiler trace (.xplane.pb) to device busy time, idle share,
per-op totals and the host's share of the idle gaps.

`jax.profiler.ProfileData` reads the file with nothing but JAX. A device
plane is one whose name starts with `/device:TPU:`; its operations are the
events of the line named `XLA Ops`. Busy time is the UNION of those events'
intervals (overlapping ops are not counted twice), per device; the idle share
of a window is 1 - busy / window. Every function here takes plain lists of
(start_ns, duration_ns, name), so the arithmetic is tested without a trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
REHEARSAL_LINE = "tf_XLAPjRtCpuClient"
SHORT_GAP_NS = 50_000
MAX_NAMED_GAPS = 2000  # the longest; the look-up is a scan per gap

Event = tuple[float, float, str]  # start_ns, duration_ns, name


def union_ns(events: list[Event]) -> float:
    """Total length of the union of the events' intervals."""
    total, end = 0.0, None
    for s, d, _ in sorted(events):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(events: list[Event], t0: float, t1: float) -> list[tuple[float, float]]:
    """Intervals of [t0, t1] that no event covers."""
    out, cur = [], t0
    for s, d, _ in sorted(events):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, s + d)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(a, b) for a, b in out if b > a]


def op_totals(events: list[Event]) -> list[tuple[str, float]]:
    """[(op name, seconds)] summed per name, largest first."""
    tot: dict[str, float] = {}
    for _, d, name in events:
        tot[name] = tot.get(name, 0.0) + d
    return sorted(((n, v / 1e9) for n, v in tot.items()),
                  key=lambda kv: -kv[1])


def attribute_gaps(idle: list[tuple[float, float]],
                   host: list[Event]) -> list[tuple[str, float]]:
    """Name each idle gap of the device by the host event that overlaps it
    most (`host:no_traced_span` where none does); gaps under 50 us are one
    bucket. [(name, seconds)], largest first."""
    host = sorted(h for h in host if h[1] >= SHORT_GAP_NS / 4)
    starts = [h[0] for h in host]
    tot: dict[str, float] = {}
    longest = max((d for _, d, _ in host), default=0.0)
    named = set(sorted(idle, key=lambda g: g[0] - g[1])[:MAX_NAMED_GAPS])
    for a, b in idle:
        if b - a < SHORT_GAP_NS:
            name = "device:gaps_under_50us"
        elif (a, b) not in named:
            name = "device:gaps_not_looked_up"
        else:
            best, name = 0.0, "host:no_traced_span"
            i = bisect.bisect_left(starts, a - longest)
            while i < len(host) and host[i][0] < b:
                s, d, n = host[i]
                ov = min(b, s + d) - max(a, s)
                if ov > best:
                    best, name = ov, n
                i += 1
        tot[name] = tot.get(name, 0.0) + (b - a)
    return sorted(((n, v / 1e9) for n, v in tot.items()),
                  key=lambda kv: -kv[1])


def clean(name: str) -> str:
    """An op or span name without spaces, commas or brackets. A device op
    is traced as its HLO text (`%fusion.3 = (f32[...]) fusion(...)`): the
    name is what stands before the `=`."""
    if name.startswith("%") and " = " in name:
        name = name[1:].split(" = ", 1)[0]
    return re.sub(r"[^A-Za-z0-9_.:/$-]+", "_", name).strip("_")[:80]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, rehearsal: bool = False) -> dict:
    """{"devices": {index: [Event]}, "host": [Event]} of one trace file.
    In a CPU rehearsal there is no device plane: the XLA CPU client's
    executor threads stand in for device 0, so the control flow after the
    trace can be rehearsed. Nothing read that way is ever printed as a
    result."""
    from jax.profiler import ProfileData

    devices: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if rehearsal and plane.name == HOST_PLANE:
            for line in plane.lines:
                if line.name.startswith(REHEARSAL_LINE):
                    devices.setdefault(0, []).extend(
                        (e.start_ns, e.duration_ns, clean(e.name))
                        for e in line.events if e.duration_ns > 0)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(int(m.group(1)), []).extend(
                        (e.start_ns, e.duration_ns, clean(e.name))
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.start_ns, e.duration_ns, clean(e.name))
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


def reduce(trace: dict, window_s: float | None = None) -> dict:
    """The numbers the metrics read from one traced sub-window.

    busy_s is averaged over the devices that ran anything; window_s, where
    the caller did not time the sub-window itself, is the span from the first
    to the last device op."""
    devs = {k: v for k, v in trace["devices"].items() if v}
    if not devs:
        return {"busy_s": 0.0, "window_s": window_s or 0.0, "per_device": {},
                "ops": [], "idle_gaps": []}
    per_device = {k: union_ns(v) / 1e9 for k, v in devs.items()}
    every = [e for v in devs.values() for e in v]
    t0 = min(s for s, _, _ in every)
    t1 = max(s + d for s, d, _ in every)
    span_s = (t1 - t0) / 1e9
    first = devs[min(devs)]
    n = len(devs)
    return {
        "busy_s": sum(per_device.values()) / n,
        "window_s": window_s if window_s is not None else span_s,
        "per_device": per_device,
        "ops": [(name, s / n) for name, s in op_totals(every)],
        "idle_gaps": attribute_gaps(gaps(first, t0, t1), trace["host"]),
    }
