"""Read a profiler trace (``.xplane.pb``) into plain event lists.

``jax.profiler.ProfileData`` gives planes, their lines and events with a
start and a duration in nanoseconds. A chip's plane is named
``/device:TPU:<n>`` (other ``/device:`` planes are not chips); its ``XLA Modules`` line holds one event per
program execution (named after the jitted function, ``jit_<name>``) and
its ``XLA Ops`` line one event per operation, kernels among them. Host
threads are the lines of ``/host:CPU``; the benchmark's spans
(``jax.profiler.TraceAnnotation``) are events there.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # seconds, on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    devices: dict           # device plane name -> {line name: [Event]}
    host: dict              # host line (thread) name -> [Event]

    def host_events(self, name: str) -> list:
        return sorted((e for evs in self.host.values() for e in evs
                       if e.name == name), key=lambda e: e.start)

    def device_line(self, line: str) -> dict:
        """{device: [Event]} of one line on every device plane."""
        return {d: lines.get(line, []) for d, lines in self.devices.items()}


def find(trace_dir) -> Path:
    hits = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                / "*" / "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(hits[-1])


def _events(line) -> list:
    return [Event(e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def load(path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, {}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices[plane.name] = {ln.name: _events(ln)
                                   for ln in plane.lines}
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host.setdefault(ln.name, []).extend(_events(ln))
    order = sorted(devices, key=lambda d: int(d.rsplit(":", 1)[1]))
    return Trace({d: devices[d] for d in order}, host)


def union(intervals) -> list:
    """Merge (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def covered(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def gaps(intervals, lo, hi) -> list:
    """Idle (start, end) stretches of [lo, hi] between merged intervals."""
    out, t = [], lo
    for a, b in union(clip(intervals, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out
