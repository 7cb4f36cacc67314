"""The program's own host stages in a profiler trace.

`ScoringEngine.score` records each of its host stages as a span named
`engine.<stage>` (`repro.core.profile.STAGES`) on the profiler's clock, the
one the device's `XLA Ops` use; its copy spans (`engine.to_device`,
`engine.to_host`) carry the bytes copied as the stat `bytes`. This module
reduces those spans inside the benchmark's window (`bench.window`) to:

- each span name's exclusive seconds: its duration less the time covered
  by the `engine.*` spans nested in it on the same host thread;
- the bytes its spans carry;
- the number of calls (`engine.score` spans), which the per-call metrics
  divide by;
- the device's idle seconds by stage: each idle gap of the first chip, as
  `trace.reduce_profile` finds them, is cut at the start and end of every
  `engine.*` span inside it, and each piece is labelled as that reduction
  labels a gap (the shortest `bench.*` or `engine.*` span open at its
  midpoint). Without `engine.*` spans the pieces are the gaps themselves
  and the labels and sums are those of `Reduced.idle_by_activity`.

A trace of a program without these spans holds no calls, and every reader
built on `per_call_ms`/`per_call_mb` then returns None.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field

from harness.trace import (DEVICE_PREFIX, OPS_LINE, SPAN_PREFIX, WINDOW_SPAN,
                           _union)

ENGINE_PREFIX = "engine."
CALL_SPAN = "engine.score"
NO_SPAN = "no host span"


@dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    nbytes: int = 0            # the `bytes` stat of a copy span


@dataclass
class Stages:
    window_s: float
    calls: int                                        # engine.score spans
    exclusive_s: dict = field(default_factory=dict)   # name -> seconds
    nbytes: dict = field(default_factory=dict)        # name -> bytes
    idle_s: dict = field(default_factory=dict)        # label -> seconds


def _bytes(event) -> int:
    for key, value in event.stats:
        if key == "bytes":
            return int(value)
    return 0


def _host_spans(profile) -> list[list[Span]]:
    """`bench.*` and `engine.*` spans of every host line, one list per
    line in trace order."""
    lines = []
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            spans = [Span(e.name, e.start_ns, e.end_ns,
                          _bytes(e) if e.name.startswith(ENGINE_PREFIX)
                          else 0)
                     for e in line.events
                     if e.name.startswith((SPAN_PREFIX, ENGINE_PREFIX))]
            if spans:
                lines.append(spans)
    return lines


def _device_ops(profile) -> dict:
    """(start, end) of every `XLA Ops` event, by device plane with ops."""
    planes = {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [(e.start_ns, e.end_ns) for line in plane.lines
                   if line.name == OPS_LINE for e in line.events]
            if ops:
                planes[plane.name] = ops
    return planes


def _first_chip_busy(planes: dict, w0: float, w1: float) -> list:
    """Busy intervals of the first device plane, clipped to the window: the
    timeline `trace.reduce_profile` labels idle gaps on."""
    if not planes:
        return []
    return _union((max(a, w0), min(b, w1)) for a, b in planes[min(planes)]
                  if b > w0 and a < w1)


def _exclusive(spans: list[Span]) -> list[float]:
    """Duration of each span of one host line less the time its nested
    spans cover (spans of one thread nest properly)."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].start_ns, -spans[i].end_ns))
    covered = [0.0] * len(spans)
    stack: list[int] = []
    for i in order:
        s = spans[i]
        while stack and spans[stack[-1]].end_ns <= s.start_ns:
            stack.pop()
        if stack and s.end_ns <= spans[stack[-1]].end_ns:
            covered[stack[-1]] += s.end_ns - s.start_ns
        stack.append(i)
    return [s.end_ns - s.start_ns - c for s, c in zip(spans, covered)]


def _idle_by_stage(busy, w0, w1, labels: list[Span],
                   cuts: list[float]) -> dict:
    """Idle seconds of one chip's timeline, each gap cut at `cuts` and each
    piece labelled by the shortest span of `labels` open at its midpoint
    (the first in `labels` order among equals)."""
    gaps = []
    t = w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    cuts = sorted(set(cuts))
    pieces = []
    for a, b in gaps:
        inner = cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)]
        edges = [a, *inner, b]
        pieces.extend(zip(edges[:-1], edges[1:]))
    # Sweep the pieces in time order with the set of spans open at each
    # midpoint, kept in `labels` order.
    by_start = sorted(range(len(labels)), key=lambda i: labels[i].start_ns)
    nxt = 0
    active: list[int] = []
    out: dict[str, float] = {}
    for a, b in pieces:
        mid = 0.5 * (a + b)
        while nxt < len(by_start) and labels[by_start[nxt]].start_ns <= mid:
            active.append(by_start[nxt])
            nxt += 1
        active = sorted(i for i in active if labels[i].end_ns > mid)
        label = (min((labels[i] for i in active),
                     key=lambda s: s.end_ns - s.start_ns).name
                 if active else NO_SPAN)
        out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return out


def reduce_stages(profile) -> Stages:
    """Reduce a `jax.profiler.ProfileData` to the program's stages."""
    lines = _host_spans(profile)
    planes = _device_ops(profile)
    windows = [s for spans in lines for s in spans if s.name == WINDOW_SPAN]
    if windows:
        w0 = min(s.start_ns for s in windows)
        w1 = max(s.end_ns for s in windows)
    else:
        # As `trace.reduce_profile`: the device extent, else the spans'.
        every = [op for ops in planes.values() for op in ops] \
            or [(s.start_ns, s.end_ns) for spans in lines for s in spans]
        if not every:
            return Stages(0.0, 0)
        w0 = min(a for a, _ in every)
        w1 = max(b for _, b in every)
    out = Stages(window_s=(w1 - w0) * 1e-9, calls=0)
    labels: list[Span] = []
    cuts: list[float] = []
    for spans in lines:
        engine = [s for s in spans if s.name.startswith(ENGINE_PREFIX)
                  and s.start_ns >= w0 and s.end_ns <= w1]
        for s, excl in zip(engine, _exclusive(engine)):
            out.exclusive_s[s.name] = out.exclusive_s.get(s.name, 0.0) \
                + excl * 1e-9
            out.nbytes[s.name] = out.nbytes.get(s.name, 0) + s.nbytes
            if s.name == CALL_SPAN:
                out.calls += 1
            cuts += (s.start_ns, s.end_ns)
        labels += [s for s in spans if s.name != WINDOW_SPAN]
    out.idle_s = _idle_by_stage(_first_chip_busy(planes, w0, w1), w0, w1,
                                labels, cuts)
    return out


def from_run(ctx: dict) -> Stages | None:
    """The stages of a traced run, read once per run and kept in `ctx`:
    the newest `.xplane.pb` under the runner's trace directory, taken only
    if its window is the one the runner reduced (`ctx["trace"]`). None in
    an untraced run."""
    if "engine_stages" not in ctx:
        ctx["engine_stages"] = _read_run(ctx.get("trace"))
    return ctx["engine_stages"]


def _read_run(red) -> Stages | None:
    if red is None:
        return None
    from jax.profiler import ProfileData

    from harness import runner, trace

    try:
        path = trace.find_xplane(runner.TRACE_DIR)
    except FileNotFoundError:
        return None
    stages = reduce_stages(ProfileData.from_file(path))
    return stages if stages.window_s == red.window_s else None


def _per_call(ctx: dict, table: str, names) -> float | None:
    """Sum of `Stages.<table>` over the span names `names`, per
    `engine.score` call; None where the run has no calls or none of those
    spans."""
    stages = from_run(ctx)
    if stages is None or not stages.calls:
        return None
    values = getattr(stages, table)
    present = [n for n in names if n in values]
    if not present:
        return None
    return sum(values[n] for n in present) / stages.calls


def per_call_ms(ctx: dict, names) -> float | None:
    """Exclusive milliseconds of the spans `names` per call."""
    s = _per_call(ctx, "exclusive_s", names)
    return None if s is None else s * 1e3


def per_call_mb(ctx: dict, names) -> float | None:
    """Megabytes (1e6 bytes) the copy spans `names` carry per call."""
    b = _per_call(ctx, "nbytes", names)
    return None if b is None else b / 1e6
