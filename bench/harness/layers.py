"""What a per-layer metric reads: the traced window, reduced.

``Context`` loads the window's trace once and offers each reader in
``bench/metrics/`` what it needs: device busy time, the executions of a
jitted program, the calls of a kernel, the configuration, its operation
counts and the chip's peaks. A reader
returns None where the trace holds nothing for it.

The traced window is the host span ``traced`` that ``runner.run_window``
opens around the last seconds of the measured window; device events are
clipped to it, and counts (policy steps, epochs) are executions of the
learners' programs inside it.
"""
from __future__ import annotations

import bisect
import collections
import re

from . import flops, peaks, xplane

# host spans of the benchmark's wrappers, by what the host was doing
SPANS = ("farm_step", "ring_ingest", "model_step", "policy_step")


class Context:
    def __init__(self, cell, trace_dir, device, trace=None):
        self.cell, self.config = cell, cell.config
        self.device = device
        self.peak = peaks.peaks(device["kind"])
        self.flops = flops
        self.trace = trace if trace is not None else xplane.load(
            xplane.find(trace_dir))
        win = self.trace.host_events("traced")
        if not win:
            raise RuntimeError("the trace holds no 'traced' span")
        self.lo, self.hi = win[-1].start, win[-1].end
        self.window_s = self.hi - self.lo
        ops = self.trace.device_line("XLA Ops")
        chips = list(ops)[:cell.chips]
        self.ops = {d: [e for e in ops[d]
                        if e.end > self.lo and e.start < self.hi]
                    for d in chips}
        mods = self.trace.device_line("XLA Modules")
        self.modules = {d: [e for e in mods.get(d, [])
                            if e.end > self.lo and e.start < self.hi]
                        for d in chips}
        busy = [xplane.covered(xplane.clip(
            [(e.start, e.end) for e in evs], self.lo, self.hi))
            for evs in self.ops.values()]
        self.busy_s = sum(busy) / len(busy) if busy else 0.0

    # ------------------------------------------------------------ programs
    def executions(self, program: str) -> list:
        """Executions of a jitted program (``jit_<name>``) on the first
        chip, whole inside the window; each event's name keeps the
        program's fingerprint (``jit_<name>(<fingerprint>)``)."""
        evs = next(iter(self.modules.values()), [])
        return [e for e in evs if e.name.split("(")[0] == program
                and e.start >= self.lo and e.end <= self.hi]

    def program_ms(self, program: str):
        ex = self.executions(program)
        if not ex:
            return None
        return 1e3 * sum(e.dur for e in ex) / len(ex)

    def leaf_ops(self) -> list:
        """Operations on the first chip that contain no other operation
        (a loop or a call contains the operations of its body), whole
        inside the traced window, in order of start."""
        ops = sorted(next(iter(self.ops.values()), []),
                     key=lambda e: (e.start, -e.end))
        return [e for i, e in enumerate(ops)
                if (i + 1 == len(ops) or ops[i + 1].start >= e.end)
                and e.start >= self.lo and e.end <= self.hi]

    def inside(self, events, programs, whole_name=False) -> list:
        """The events that run inside an execution of one of
        ``programs`` (names without, or with ``whole_name`` with, the
        fingerprint)."""
        evs = next(iter(self.modules.values()), [])
        ex = sorted((e.start, e.end) for e in evs
                    if (e.name if whole_name else e.name.split("(")[0])
                    in programs and e.start >= self.lo and e.end <= self.hi)
        starts = [a for a, b in ex]
        out = []
        for e in events:
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.end <= ex[i][1]:
                out.append(e)
        return out

    def kernels(self, programs) -> list:
        """Mosaic kernel calls (``custom-call`` operations) inside
        executions of ``programs``."""
        return self.inside([e for e in self.leaf_ops() if is_kernel(e)],
                           programs)

    # ------------------------------------------------------------- summary
    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the longest
        idle gaps labelled by the host span that covered most of each."""
        ops = next(iter(self.ops.values()), [])
        mods = sorted(((e.start, e.end, e.name.split("(")[0])
                       for e in next(iter(self.modules.values()), [])))
        starts = [m[0] for m in mods]
        per = collections.Counter()
        for e in self.leaf_ops():
            i = bisect.bisect_right(starts, e.start) - 1
            mod = mods[i][2] if i >= 0 and e.end <= mods[i][1] else "?"
            per[f"{mod}/{op_name(e)}"] += e.dur
        spans = [(n, e.start, e.end) for n in SPANS
                 for e in self.trace.host_events(n)]
        gaps = xplane.gaps([(e.start, e.end) for e in ops],
                           self.lo, self.hi)
        gaps.sort(key=lambda g: g[0] - g[1])
        idle = []
        for a, b in gaps[:top]:
            cover = collections.Counter()
            for n, s, t in spans:
                o = min(b, t) - max(a, s)
                if o > 0:
                    cover[n] += o
            label = cover.most_common(1)[0][0] if cover else "no span"
            idle.append([label, b - a])
        return {"device_ops": [[k, v] for k, v in per.most_common(top)],
                "idle_gaps": idle}


def op_name(e) -> str:
    """``fusion.54`` of ``%fusion.54 = f32[...] fusion(...)``."""
    return e.name.split(" ", 1)[0].lstrip("%")


def is_kernel(e) -> bool:
    """A Mosaic (Pallas) kernel call; XLA's own custom calls, such as
    buffer allocations, are not."""
    return 'custom_call_target="tpu_custom_call"' in e.name


SHAPE = re.compile(r"f32\[([\d,]*)\]")


def shapes(e) -> list:
    """Float32 shapes in an operation's text: its result first, then its
    operands."""
    return [tuple(int(d) for d in m.split(",") if d)
            for m in SHAPE.findall(e.name)]
