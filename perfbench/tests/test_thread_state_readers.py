"""The thread-state readers (PR 52) over canned pairs of expositions: a
parent's (no such series) reads ``None``, a worker whose reader could
not look (the source gauge the metric needs is not there) reads
``None``, otherwise the quotient.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q"""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
sys.path.insert(0, PERFBENCH)
sys.path.insert(1, os.path.dirname(PERFBENCH))

from pbharness import cells, stats, threadstates  # noqa: E402

STATE = "makisu_thread_state_seconds_total"
SCHED = "makisu_thread_sched_seconds_total"

PARENT = """\
# TYPE makisu_span_self_cpu_seconds_total counter
makisu_span_self_cpu_seconds_total{span="build"} 3
"""


def _exposition(source, scale):
    """A worker's /metrics: ``scale`` 1 at the window's open, 2 at its
    close, so every series grows by its value at the open."""
    rows = [
        (STATE, 'span="build",state="running"', 0.0),
        (STATE, 'span="copy_checksum",state="running"', 2.0),
        (STATE, 'span="copy_checksum",state="interpreter_lock"', 6.0),
        (STATE, 'span="copy_checksum",state="fs"', 2.0),
        (STATE, 'span="session_begin",state="interpreter_lock"', 1.0),
        (STATE, 'span="session_begin",state="fs"', 1.0),
        (STATE, 'span="tar_write",state="wait"', 4.0),
        (STATE, 'span="tar_write",state="interpreter_lock"', 1.0),
        (STATE, 'span="tar_write",state="fs"', 3.0),
        (SCHED, 'kind="run",span="copy_checksum"', 2.5),
        (SCHED, 'kind="runqueue",span="copy_checksum"', 1.0),
        (SCHED, 'kind="runqueue",span="tar_write"', 0.5),
        ("makisu_span_thread_cpu_seconds_total", 'span="context_scan"',
         2.4),
        ("makisu_span_thread_cpu_seconds_total", 'span="tar_write"', 9.0),
    ]
    lines = [f"{name}{{{labels}}} {value * scale}"
             for name, labels, value in rows]
    if source is not None:
        lines.append(f"makisu_thread_state_source {source}")
    return "\n".join(lines) + "\n"


def _run(open_text, close_text):
    def build(service):
        return types.SimpleNamespace(
            ok=True, terminal={"service_seconds": service})
    return types.SimpleNamespace(
        counted=[build(4.0), build(6.0)],
        counters_open=stats.parse_prometheus(open_text),
        counters_close=stats.parse_prometheus(close_text))


def _reader(metric):
    return cells._load_module(
        os.path.join(PERFBENCH, "readers", metric + ".py")).read


# Two counted builds; growth over the window is each series' value at
# the open. `needs`: the source gauge values under which the metric
# reads (None: it reads whatever the gauge says).
CASES = [
    ("lock_wait_s_per_build", (2,), (6.0 + 1.0 + 1.0) / 2),
    ("fs_blocked_s_per_build", (1, 2), (2.0 + 1.0 + 3.0) / 2),
    ("runqueue_wait_s_per_build", (1, 2), (1.0 + 0.5) / 2),
    ("listing_lock_wait_share_pct", (2,), 100.0 * 7.0 / 12.0),
    ("listing_blocked_share_pct", (1, 2), 100.0 * 10.0 / 12.0),
    ("copy_checksum_cpu_s_per_build", None, 2.4 / 2),
    ("thread_state_coverage_pct", (1, 2), 100.0 * 20.0 / 10.0),
]


@pytest.mark.parametrize("metric, needs, want", CASES)
def test_reader_reads_none_from_a_parent(metric, needs, want):
    assert _reader(metric)(_run(PARENT, PARENT)) is None
    untraced = _run(PARENT, PARENT)
    untraced.counters_open = untraced.counters_close = None
    assert _reader(metric)(untraced) is None


@pytest.mark.parametrize("metric, needs, want", CASES)
@pytest.mark.parametrize("source", [None, 0, 1, 2])
def test_reader_reads_the_quotient_under_the_source_it_needs(
        metric, needs, want, source, capsys):
    got = _reader(metric)(_run(_exposition(source, 1),
                               _exposition(source, 2)))
    if needs is None or source in needs:
        assert got == pytest.approx(want)
    else:
        assert got is None


def test_the_table_ranks_spans_by_sampled_seconds(capsys):
    run = _run(_exposition(2, 1), _exposition(2, 2))
    rows = threadstates.by_span(run)
    assert rows["copy_checksum"] == {
        "running": 1.0, "interpreter_lock": 3.0, "fs": 1.0,
        "run": 1.25, "runqueue": 0.5}
    lines = threadstates.table_lines(rows, top=2)
    assert [line.split()[0] for line in lines] == [
        "span", "(every", "copy_checksum", "tar_write"]
    _reader("thread_state_coverage_pct")(run)
    out = capsys.readouterr().out
    assert "by innermost span and state (source 2" in out
    assert "copy_checksum" in out and "session_begin" in out
