"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the seconds in which an operation ran on each device (the
union of the device-op intervals), every device operation's summed
seconds with its HLO text, and the idle gaps of the busiest device.

Times in the result are seconds from the window's opening mark, a host
annotation the harness writes at the instant it reads its own clock, so
the gaps can be laid over the sampler's frames."""

from __future__ import annotations

import dataclasses
import re

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OPS_LINE = "XLA Ops"
_OP_RE = re.compile(r"^%(?P<op>[\w.\-]+) = \(?[a-z]+\d*\[[^ ]*")
_SHAPE_RE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")


@dataclasses.dataclass
class DeviceTrace:
    window_s: float
    busy_s: float                  # mean over the devices that ran anything
    devices: int
    ops: dict                      # HLO text -> [calls, seconds]
    gaps: list                     # [(start_s, end_s)] of the busiest device


def shapes_of(hlo_text: str) -> list[tuple[str, tuple[int, ...]]]:
    """[(dtype, dims)] in order of appearance: the result first, then
    the operands."""
    head = hlo_text.split(", custom_call_target", 1)[0]
    out = []
    for dtype, dims in _SHAPE_RE.findall(head):
        out.append((dtype, tuple(int(d) for d in dims.split(",") if d)))
    return out


def op_name(hlo_text: str) -> str:
    m = _OP_RE.match(hlo_text)
    return m.group("op") if m else hlo_text.split(" ", 1)[0]


def short_name(hlo_text: str) -> str:
    """A name of letters, digits and underscores for the breakdown:
    the operation, its result's shape and its opcode."""
    m = _OP_RE.match(hlo_text)
    if not m:
        return re.sub(r"\W+", "_", hlo_text)[:60]
    shapes = shapes_of(hlo_text)
    shape = "_".join([shapes[0][0]] + [str(d) for d in shapes[0][1]]) \
        if shapes else ""
    rest = hlo_text[m.end():]
    opcode = re.search(r"\s([a-z][\w\-]*)\(", rest)
    return re.sub(r"[^\w.\-]+", "_", "_".join(
        x for x in (m.group("op"), shape,
                    opcode.group(1) if opcode else "") if x))[:64]


def _union(intervals: list[tuple[float, float]]):
    """(total length, merged intervals) of sorted-or-not intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), merged


def reduce(path: str, mark: str, window_s: float) -> DeviceTrace | None:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    t_mark = None
    per_device = []
    ops: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == mark:
                        t_mark = ev.start_ns
        elif _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            line = lines.get(_OPS_LINE)
            if line is None:
                continue
            intervals = []
            for ev in line.events:
                intervals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                entry = ops.setdefault(ev.name, [0, 0.0])
                entry[0] += 1
                entry[1] += ev.duration_ns / 1e9
            if intervals:
                per_device.append(intervals)
    if t_mark is None:
        return None
    lo, hi = t_mark, t_mark + window_s * 1e9
    busy = []
    merged_all = []
    for intervals in per_device:
        clipped = [(max(s, lo), min(e, hi)) for s, e in intervals
                   if e > lo and s < hi]
        total, merged = _union(clipped)
        busy.append(total / 1e9)
        merged_all.append(merged)
    if not busy:
        return DeviceTrace(window_s, 0.0, 0, ops, [(0.0, window_s)])
    busiest = merged_all[busy.index(max(busy))]
    gaps = []
    cursor = lo
    for s, e in busiest:
        if s > cursor:
            gaps.append(((cursor - lo) / 1e9, (s - lo) / 1e9))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append(((cursor - lo) / 1e9, (hi - lo) / 1e9))
    return DeviceTrace(window_s, sum(busy) / len(busy), len(busy), ops, gaps)


def kernel_calls(trace: DeviceTrace, kernel: str):
    """[(hlo text, calls, seconds)] of the custom calls named
    ``kernel`` (the Pallas kernel's name, before XLA's ``.N`` suffix)."""
    out = []
    for text, (calls, seconds) in trace.ops.items():
        name = op_name(text)
        if "custom-call(" in text and re.sub(r"\.\d+$", "", name) == kernel:
            out.append((text, calls, seconds))
    return out


def charge_gaps(trace: DeviceTrace, samples, t_open: float, in_flight,
                floor_s: float = 0.05, top: int = 10):
    """Idle seconds by what the host was doing: the seconds of every gap
    of at least ``floor_s`` are shared among the sampler's passes that
    fell into it, and each pass's share among the innermost frames of
    the threads then executing a build, labelled with the number of
    builds in flight (queued ones included). ``samples`` are the
    sampler's (monotonic seconds, stacks) and ``t_open`` the monotonic
    second of the opening mark."""
    charged: dict = {}

    def charge(label: str, seconds: float) -> None:
        label = re.sub(r"[^\w.\-]+", "_", label)[:64]
        charged[label] = charged.get(label, 0.0) + seconds

    for start, end in trace.gaps:
        if end - start < floor_s:
            continue
        inside = [(t, stacks) for t, stacks in samples
                  if start <= t - t_open <= end]
        if not inside:
            charge(f"{in_flight(t_open + (start + end) / 2)}_builds_in_flight "
                   "not sampled", end - start)
            continue
        share = (end - start) / len(inside)
        for t, stacks in inside:
            head = f"{in_flight(t)}_builds_in_flight host_top_frame "
            if not stacks:
                charge(head + "no build executing", share)
            for stack in stacks:
                charge(head + stack[-1], share / len(stacks))
    ranked = sorted(charged.items(), key=lambda kv: -kv[1])[:top]
    return [[name, seconds] for name, seconds in ranked]
