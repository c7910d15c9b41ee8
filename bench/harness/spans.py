"""The program's own spans in a traced window.

The engine names what each of its threads is doing with
``jax.profiler.TraceAnnotation`` (``src/repro/core``; README, "Tracing a
run"): ``collector.step``, ``data.push``, ``ring.ingest``,
``model.val_wait``, ``policy.step``, ``policy.eval``, ``param.push`` and
the rest of ``PROGRAM``. They are events on the host lines of the trace,
on the clock of the device's lines. A metric reads the spans whole
inside the traced window; a trace of a program without them reads None.

``thread_lines`` and ``idle_under`` put the device's idle time down to
what each engine thread was doing: the innermost program span open on
that thread's line.
"""
from __future__ import annotations

import collections

from . import xplane

# the spans only one engine thread opens: a line is known by them
ROLES = {"policy": {"policy.step", "policy.improve", "policy.eval",
                    "policy.idle"},
         "model": {"model.step", "ring.ingest", "model.epoch",
                   "model.val_wait", "model.idle"},
         "collector": {"collector.step", "collector.pull",
                       "collector.rollout", "data.push", "collector.pace"}}
PROGRAM = set().union(*ROLES.values(), {"param.push", "param.pull", "gc"})


def whole(ctx, name: str) -> list:
    """Spans named ``name`` that start and end inside the traced window."""
    return [e for e in ctx.trace.host_events(name)
            if e.start >= ctx.lo and e.end <= ctx.hi]


def mean_ms(ctx, name: str):
    """Mean duration of the spans whole inside the window, in ms; None
    where there are none."""
    evs = whole(ctx, name)
    if not evs:
        return None
    return 1e3 * sum(e.dur for e in evs) / len(evs)


def thread_lines(path) -> list:
    """The host plane's lines, one per thread, each a list of the
    program's spans on it (``xplane.load`` merges lines of one name, and
    every Python thread's line is named alike)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [xplane.Event(e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                   for e in line.events if e.name in PROGRAM]
            if evs:
                out.append(evs)
    return out


def roles(lines) -> dict:
    """{role: line} for each engine thread, known by the spans it holds;
    a role with several lines (a fleet) takes the one with most spans."""
    out = {}
    for role, marks in ROLES.items():
        held = [ln for ln in lines if any(e.name in marks for e in ln)]
        if held:
            out[role] = max(held, key=len)
    return out


def innermost(line, a: float, b: float) -> list:
    """[(name, seconds)] of [a, b] by the innermost span open on the
    line (the one that started last among those open), ``None`` where
    no span is open."""
    evs = [e for e in line if e.end > a and e.start < b]
    cuts = sorted({a, b} | {t for e in evs for t in (e.start, e.end)
                            if a < t < b})
    out = []
    for s, t in zip(cuts, cuts[1:]):
        mid = (s + t) / 2
        open_ = [e for e in evs if e.start <= mid < e.end]
        name = max(open_, key=lambda e: (e.start, -e.end)).name \
            if open_ else None
        out.append((name, t - s))
    return out


def idle_under(ctx, lines, long_gap: float = 0.010) -> dict:
    """The device's idle time in the traced window, split per engine
    thread by the innermost program span open there; and each idle gap
    of ``long_gap`` seconds or more, with what each thread was doing in
    most of it.

    The profiler records a span only if it starts and ends while it
    traces, so at each edge of the window a thread may be inside a span
    the trace does not hold. Each thread's split covers the part of the
    window between its first recorded span's start and its last one's
    end (``seen``); the idle time outside it is ``unseen_s``."""
    ops = next(iter(ctx.ops.values()), [])
    gaps = xplane.gaps([(e.start, e.end) for e in ops], ctx.lo, ctx.hi)
    out = {"idle_s": sum(b - a for a, b in gaps), "threads": {},
           "long_gaps": []}
    by_role = roles(lines)
    seen = {r: (max(min(e.start for e in ln), ctx.lo),
                min(max(e.end for e in ln), ctx.hi))
            for r, ln in by_role.items()}

    def split(role, a, b):
        lo, hi = seen[role]
        c = collections.Counter()
        if max(a, lo) < min(b, hi):
            for name, s in innermost(by_role[role], max(a, lo),
                                     min(b, hi)):
                c[str(name)] += s
        c["unseen"] += (b - a) - sum(c.values())
        return c

    for role in by_role:
        c = collections.Counter()
        for a, b in gaps:
            c.update(split(role, a, b))
        unseen = c.pop("unseen", 0.0)
        idle = sum(c.values())
        out["threads"][role] = {
            "seen_s": seen[role][1] - seen[role][0], "idle_seen_s": idle,
            "unseen_s": unseen,
            "covered": 1.0 - c["None"] / idle if idle > 0 else None,
            "by_span": dict(c.most_common())}
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1]):
        if b - a < long_gap:
            break
        gc = [e for ln in lines for e in ln
              if e.name == "gc" and e.end > a and e.start < b]
        out["long_gaps"].append({
            "start_s": a - ctx.lo, "s": b - a,
            "under": {r: split(r, a, b).most_common(1)[0][0]
                      for r in by_role},
            "gc": len(gc) > 0})
    return out


def clock_order(trace) -> list:
    """Pairs (span start, execution start) of the k-th ``policy.improve``
    span and the k-th ``jit__improve_impl`` execution on the first chip,
    both counted from the end of the first ``policy.eval``. The
    evaluation waits on the device, so no execution dispatched before it
    is still to run, and each execution after it was dispatched by a
    span after it: on one clock, every execution starts after its span."""
    marks = trace.host_events("policy.eval")
    if not marks:
        return []
    t0 = marks[0].end
    spans = [e.start for e in trace.host_events("policy.improve")
             if e.start >= t0]
    mods = next(iter(trace.device_line("XLA Modules").values()), [])
    runs = sorted(e.start for e in mods
                  if e.name.split("(")[0] == "jit__improve_impl"
                  and e.start >= t0)
    return list(zip(spans, runs))
