"""Reduction of a profiler trace to device times.

``record`` traces a callable with the JAX profiler (the Python tracer off,
so the host loop runs at its own speed). ``extract`` reads the resulting
``.xplane.pb`` into plain events: every event of the device planes'
``XLA Modules`` and ``XLA Ops`` lines, and every host event, each with
its plane, line, name, start and duration in nanoseconds on the
profiler's one clock. ``Summary`` reduces those events to the numbers the
per-layer metric readers take: the union of the device's busy intervals,
device time per XLA module and per op, and the idle gaps with what the
host was doing in each.

The events can be saved as gzipped JSON, so the reduction is checked on a
recorded chip trace without the chip.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import json
import os
import re

DEVICE_LINES = ("XLA Modules", "XLA Ops")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def record(fn, log_dir: str):
    """Runs ``fn()`` under the profiler; returns its result."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(log_dir, profiler_options=opts):
        return fn()


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name


def extract(log_dir: str) -> list[Event]:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = is_device_plane(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            for ev in line.events:
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns), float(ev.duration_ns)))
    return events


def save(events: list[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load(path: str) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def union_ns(intervals) -> float:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def module_name(name: str) -> str:
    """``jit_lpa_move(12)`` -> ``jit_lpa_move``."""
    return re.sub(r"\(\d+\)$", "", name)


class Summary:
    """Device times of one traced window ``[t0, t1)`` (ns)."""

    def __init__(self, events: list[Event], t0: float, t1: float):
        self.t0, self.t1 = t0, t1
        inside = [e for e in events if e.end_ns > t0 and e.start_ns < t1]
        self.ops = [e for e in inside if is_device_plane(e.plane)
                    and e.line == "XLA Ops"]
        self.modules = [e for e in inside if is_device_plane(e.plane)
                        and e.line == "XLA Modules"]
        self.host = [e for e in inside if e.plane.startswith("/host:")]
        self.devices = sorted({e.plane for e in self.ops + self.modules})

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _clipped(self, evs):
        return [(max(e.start_ns, self.t0), min(e.end_ns, self.t1))
                for e in evs]

    @property
    def busy_s(self) -> float:
        """Union of op intervals, averaged over the devices traced."""
        if not self.devices:
            return 0.0
        per = [union_ns(self._clipped([e for e in self.ops if e.plane == d]))
               for d in self.devices]
        return sum(per) * 1e-9 / len(per)

    def module_s(self, prefix: str) -> float:
        """Device seconds of the XLA modules named ``prefix``, averaged
        over the devices traced."""
        evs = [e for e in self.modules if module_name(e.name) == prefix]
        return sum(e.dur_ns for e in evs) * 1e-9 / max(len(self.devices), 1)

    def module_s_with(self, op_pattern: str) -> float:
        """Device seconds of the XLA modules that ran an op whose name
        matches ``op_pattern`` (the mover is the module that runs the
        Pallas kernels, whatever its name), averaged over the devices."""
        rx = re.compile(op_pattern)
        starts = collections.defaultdict(list)
        for o in self.ops:
            if rx.search(o.name):
                starts[o.plane].append(o.start_ns)
        for v in starts.values():
            v.sort()
        total = 0.0
        for m in self.modules:
            v = starts.get(m.plane, [])
            i = bisect.bisect_left(v, m.start_ns)
            if i < len(v) and v[i] < m.end_ns:
                total += m.dur_ns
        return total * 1e-9 / max(len(self.devices), 1)

    def op_s(self, pattern: str) -> float:
        """Device seconds of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        evs = [e for e in self.ops if rx.search(e.name)]
        return sum(e.dur_ns for e in evs) * 1e-9 / max(len(self.devices), 1)

    def top_ops(self, n: int = 10) -> list:
        tot = collections.Counter()
        for e in self.ops:
            tot[e.name] += e.dur_ns * 1e-9
        return [[k[:160], v] for k, v in tot.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest device-idle gaps, each named by the shortest
        host event that spans its middle (what the host was doing)."""
        busy = sorted(self._clipped(self.ops))
        gaps, cur = [], self.t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            spans = [h for h in self.host if h.start_ns <= mid < h.end_ns
                     and h.dur_ns > 0]
            name = min(spans, key=lambda h: h.dur_ns).name if spans \
                else "no host event"
            out.append([name, (e - s) * 1e-9])
        return out
