"""The program's own spans, read where the program emits them.

Two sources. The traced run's profiler trace (``.xplane.pb``, still
under ``run.work_dir/trace`` when the readers run) holds every
``metrics.span`` of the program as a host-plane event on the thread
that ran it, with its ``span_id``, and the device-feed stages as bare
events; :func:`host_events` reads them relative to the window's opening
mark, and :func:`charge_gaps` charges the device's idle seconds to the
innermost span open on each building thread. The builds' event streams
(``Build.spans``) and the worker's counters give seconds and counts per
counted build (:func:`span_seconds_per_build`,
:func:`counter_per_build`).

Every function returns ``None`` where the program has no such span or
counter (a commit older than the span), and never raises for it."""

from __future__ import annotations

import dataclasses
import glob
import os

from pbharness import stats

# The spans that only say where in a build the thread is, not what it
# is doing: the command's root span and the two loops under it.
ROOT = "build"
STRUCTURAL = (ROOT, "stage", "step")
# chunker/cdc.py FeedClock: profiler-only scopes, no span_id.
FEED_STAGES = ("gear_dispatch", "gear_readback", "host_cut",
               "sha_dispatch", "sha_readback", "service_wait")


@dataclasses.dataclass(frozen=True)
class HostEvent:
    name: str
    thread: int            # index of the host-plane line
    start_s: float         # seconds from the mark
    end_s: float
    span_id: str           # "" for a bare annotation


def trace_path(run) -> str | None:
    paths = glob.glob(os.path.join(run.work_dir, "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return paths[0] if paths else None


def host_events(path: str, mark: str) -> list[HostEvent] | None:
    """The program's spans and feed stages on the host plane, in
    seconds from ``mark``; ``None`` where the mark is not in the
    trace."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    t_mark = None
    raw = []
    thread = 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for ev in line.events:
                if ev.name == mark:
                    t_mark = ev.start_ns
                    continue
                span_id = ""
                if ev.name not in FEED_STAGES:
                    span_id = str(dict(ev.stats).get("span_id", ""))
                    if not span_id:
                        continue
                raw.append((ev.name, thread, ev.start_ns,
                            ev.start_ns + ev.duration_ns, span_id))
    if t_mark is None:
        return None
    return [HostEvent(name, thread, (start - t_mark) / 1e9,
                      (end - t_mark) / 1e9, span_id)
            for name, thread, start, end, span_id in raw]


def charge_gaps(events: list[HostEvent], gaps) -> tuple[dict, float]:
    """({span name: idle seconds}, idle seconds charged in all).

    Of the seconds of ``gaps`` ([(start_s, end_s)] from the mark, as
    ``DeviceTrace.gaps``) in which some thread had a build's root span
    open, each instant is shared equally among the threads that then
    had a program span open (the building threads: the hash service's
    dispatcher opens feed stages but no span, and is not one), and each
    thread's share goes to the innermost span or feed stage open on
    it."""
    points = []
    for ev in events:
        if ev.end_s > ev.start_s:
            points.append((ev.start_s, 1, ev))
            points.append((ev.end_s, 0, ev))
    for start, end in gaps:
        points.append((start, 3, None))
        points.append((end, 2, None))
    # At one instant: spans close, then open; gaps close, then open.
    points.sort(key=lambda p: (p[0], p[1]))
    open_on: dict[int, list[HostEvent]] = {}
    roots = 0
    in_gap = False
    charged: dict[str, float] = {}
    total = 0.0
    t_prev = 0.0
    for t, kind, ev in points:
        if in_gap and roots and t > t_prev:
            stacks = [s for s in open_on.values()
                      if any(e.span_id for e in s)]
            for stack in stacks:
                name = stack[-1].name
                charged[name] = charged.get(name, 0.0) \
                    + (t - t_prev) / len(stacks)
            total += t - t_prev
        t_prev = t
        if kind == 1:
            open_on.setdefault(ev.thread, []).append(ev)
            roots += ev.name == ROOT
        elif kind == 0:
            open_on[ev.thread].remove(ev)
            roots -= ev.name == ROOT
        else:
            in_gap = kind == 3
    return charged, total


def unspanned_pct(charged: dict, total: float) -> float | None:
    """Of the charged idle seconds, the share under a structural span
    alone."""
    if total <= 0:
        return None
    return 100.0 * sum(charged.get(n, 0.0) for n in STRUCTURAL) / total


def span_seconds_per_build(run, *names: str) -> float | None:
    """Summed seconds of the counted builds' spans of these names, over
    the counted builds that ended well."""
    done = [b for b in run.counted if b.ok]
    seconds = [float(d or 0.0) for b in done for name, d in b.spans
               if name in names]
    if not done or not seconds:
        return None
    return sum(seconds) / len(done)


def counter_per_build(run, name: str, **labels) -> float | None:
    """Growth of the worker's counter over the window per counted
    build."""
    if run.counters_open is None or not run.counted:
        return None
    want = set(labels.items())
    if not any(series == name and want <= set(have)
               for series, have in run.counters_close):
        return None
    return stats.counter_delta(run.counters_open, run.counters_close,
                               name, **labels) / len(run.counted)
