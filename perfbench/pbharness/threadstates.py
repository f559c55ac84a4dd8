"""What the worker's thread-state reader said over the window
(``makisu_tpu/utils/resources.py:ThreadStates``,
``native/threadstate.cpp``): counters
``makisu_thread_state_seconds_total{span, state}`` (sampled every 10 ms
by a native thread: where the kernel says each thread that owns an open
span is) and ``makisu_thread_sched_seconds_total{span, kind}`` (exact,
the scheduler's own clocks), each under the thread's innermost open
span, and the gauge ``makisu_thread_state_source``: 2 the reader reads
``/proc/self/task/<tid>/syscall`` and found the interpreter lock's
address, 1 it reads less (the state letters of ``stat``, or no address:
``state="interpreter_lock"`` then stays 0), 0 there is no reader.

Every function returns ``None`` where the program has no such series (a
parent of PR 52) or its reader could not look, and never raises for
it."""

from __future__ import annotations

from pbharness import hostspans

STATE_SECONDS = "makisu_thread_state_seconds_total"
SCHED_SECONDS = "makisu_thread_sched_seconds_total"
SOURCE = "makisu_thread_state_source"
STATES = ("running", "interpreter_lock", "wait", "fs", "socket", "other")
KINDS = ("run", "runqueue", "system")


def source(run) -> float | None:
    """The gauge at the window's close."""
    if run.counters_close is None:
        return None
    return run.counters_close.get((SOURCE, ()))


def seconds_per_build(run, name: str, need: tuple, **labels):
    """Growth of one of the two counters per counted build, where the
    gauge reads one of ``need``."""
    if source(run) not in need:
        return None
    return hostspans.counter_per_build(run, name, **labels)


def by_span(run) -> dict[str, dict[str, float]] | None:
    """{span: {state or kind: seconds a counted build}} over the
    window."""
    if not source(run) or run.counters_open is None or not run.counted:
        return None
    out: dict[str, dict[str, float]] = {}
    for (series, labels), value in run.counters_close.items():
        if series not in (STATE_SECONDS, SCHED_SECONDS):
            continue
        have = dict(labels)
        grown = value - run.counters_open.get((series, labels), 0.0)
        row = out.setdefault(have.get("span", "?"), {})
        column = have.get("state") or have.get("kind")
        row[column] = row.get(column, 0.0) + grown / len(run.counted)
    return out


def table_lines(rows: dict[str, dict[str, float]], top: int = 10) -> list:
    """The ``top`` spans with the most sampled seconds, one line each,
    and the sum of every span first."""
    def sampled(row):
        return sum(row.get(state, 0.0) for state in STATES)

    total: dict[str, float] = {}
    for row in rows.values():
        for column, seconds in row.items():
            total[column] = total.get(column, 0.0) + seconds
    ranked = sorted(rows.items(), key=lambda kv: -sampled(kv[1]))[:top]
    lines = [f"{'span':<28s}{'sampled':>8s}" + "".join(
        f"{c[:9]:>10s}" for c in STATES + KINDS)]
    for name, row in [("(every span)", total)] + ranked:
        lines.append(f"{name:<28s}{sampled(row):8.3f}" + "".join(
            f"{row.get(c, 0.0):10.3f}" for c in STATES + KINDS))
    return lines
