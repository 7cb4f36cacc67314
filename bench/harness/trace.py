"""Reduction of a JAX profiler trace (`.xplane.pb`) to device busy time,
per-operation device time and the host activity behind idle gaps.

Device planes are those named `/device:TPU:<n>`; their operations are the
events of the line `XLA Ops`, named by their HLO instruction text (a
kernel's instruction carries its `pallas_call` name). Busy time is the union of those intervals
inside the traced window, averaged over the chips used. The window is the
host span `bench.window` that the driver opens around its measured loop
(the device extent of the trace where the span is missing). Host spans
are the events whose names start with `bench.` on any host plane.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
#: event stats whose string value names what the operation runs.
NAME_STATS = ("tf_op", "long_name", "hlo_op", "name")


def short_name(hlo: str) -> str:
    """`%sparse_pair_score.1 = f32[...] custom-call(...)` -> `sparse_pair_score`:
    the instruction's name without its text or numeric suffix."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


@dataclass
class Op:
    name: str
    start_ns: float
    end_ns: float
    labels: tuple          # the op's name and its naming stats' values


@dataclass
class Reduced:
    window_s: float
    busy_s: float                      # averaged over chips
    chips: int
    op_seconds: dict = field(default_factory=dict)    # short name -> seconds
    ops: list = field(default_factory=list)           # Ops inside the window
    idle_by_activity: dict = field(default_factory=dict)

    def kernel_seconds(self, names) -> float:
        """Summed device seconds of the ops any of whose labels contains one
        of `names`."""
        return sum((o.end_ns - o.start_ns) * 1e-9 for o in self.ops
                   if any(n in lab for n in names for lab in o.labels))

    def device_seconds(self) -> float:
        return sum(self.op_seconds.values())


def find_xplane(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def _labels(event) -> tuple:
    out = [event.name]
    for key, value in event.stats:
        if key in NAME_STATS and isinstance(value, str) and value:
            out.append(value)
    return tuple(out)


def _union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def reduce_profile(profile) -> Reduced:
    """Reduce a `jax.profiler.ProfileData`."""
    device_ops: dict[str, list[Op]] = {}
    spans: list[tuple[float, float, str]] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append(Op(e.name, e.start_ns, e.end_ns, _labels(e)))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.end_ns, e.name))
    device_ops = {k: v for k, v in device_ops.items() if v}
    windows = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if windows:
        w0, w1 = min(a for a, _ in windows), max(b for _, b in windows)
    else:
        every = [o for ops in device_ops.values() for o in ops]
        if not every:
            return Reduced(0.0, 0.0, 0)
        w0 = min(o.start_ns for o in every)
        w1 = max(o.end_ns for o in every)
    inside: list[Op] = []
    busy = []
    for name in sorted(device_ops):
        clipped = [Op(o.name, max(o.start_ns, w0), min(o.end_ns, w1),
                      o.labels)
                   for o in device_ops[name]
                   if o.end_ns > w0 and o.start_ns < w1]
        inside.extend(clipped)
        busy.append(_union((o.start_ns, o.end_ns) for o in clipped))
    chips = len(busy)
    busy_ns = (sum(b - a for u in busy for a, b in u) / chips) if chips else 0.0
    op_seconds: dict[str, float] = {}
    for o in inside:
        key = short_name(o.name)
        op_seconds[key] = op_seconds.get(key, 0.0) \
            + (o.end_ns - o.start_ns) * 1e-9
    idle = _idle_by_activity(busy[0] if busy else [], w0, w1,
                             [s for s in spans if s[2] != WINDOW_SPAN])
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9,
                   chips=chips, op_seconds=op_seconds, ops=inside,
                   idle_by_activity=idle)


def _idle_by_activity(busy, w0, w1, spans) -> dict:
    """Idle seconds of one chip's timeline, keyed by the innermost host span
    open at each gap's midpoint ("no host span" where none is)."""
    gaps = []
    t = w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    out: dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        open_ = [s for s in spans if s[0] <= mid < s[1]]
        label = (min(open_, key=lambda s: s[1] - s[0])[2] if open_
                 else "no host span")
        out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return out


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The `breakdown` block of a traced run's result line."""
    ops = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red.idle_by_activity.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
