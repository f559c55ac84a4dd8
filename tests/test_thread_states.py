"""Thread states: where the kernel says each thread that owns an open
span is, charged to the thread's innermost open span
(native/threadstate.cpp, utils/resources.py:ThreadStates).

The sampler's beat is driven by hand from the test's thread. Each
watched thread runs inside a bracket span: the test beats until the
native reader has the thread's baseline, lets the work go, and beats
once more before the bracket closes, so what a span under the bracket
reads is its own seconds to within one beat at either end."""

import os
import threading
import time
import uuid

import pytest

from makisu_tpu import native
from makisu_tpu.utils import metrics, resources

BEAT = 0.02


@pytest.fixture
def states():
    reader = native.thread_state_reader()
    if reader is None:
        pytest.skip("libthreadstate.so cannot be built or loaded here")
    if reader.vital("source") != 2:
        pytest.skip("/proc/self/task/<tid>/syscall cannot be read here")
    resources.stop()  # the process singleton must not watch beside us
    ts = resources.ThreadStates()
    with metrics.span("warm"):
        ts.beat()  # loads, probes, calibrates
    yield ts
    ts.release()


def _unique(name: str) -> str:
    return f"{name}_{uuid.uuid4().hex[:8]}"


def _seconds(span: str) -> dict[str, float]:
    g = metrics.global_registry()
    out = {state: g.counter_total(metrics.THREAD_STATE_SECONDS,
                                  span=span, state=state)
           for state in resources.STATES}
    out.update({kind: g.counter_total(metrics.THREAD_SCHED_SECONDS,
                                      span=span, kind=kind)
                for kind in resources.SCHED_KINDS})
    out["sampled"] = sum(out[state] for state in resources.STATES)
    return out


def _run_bracketed(ts, works, structural_root: bool = False) -> list[str]:
    """Run each ``work()`` on a thread of its own inside a bracket
    span, all at once; returns the brackets' names."""
    go, finish = threading.Event(), threading.Event()
    names = [_unique("bracket") for _ in works]
    started = [threading.Event() for _ in works]
    done = [threading.Event() for _ in works]

    def body(i: int) -> None:
        with metrics.span(_unique("root"), structural=structural_root):
            with metrics.span(names[i]):
                started[i].set()
                go.wait(10)
                works[i]()
                done[i].set()
                finish.wait(10)

    threads = [threading.Thread(target=body, args=(i,), daemon=True)
               for i in range(len(works))]
    for t in threads:
        t.start()
    for e in started:
        assert e.wait(10)
    ts.beat()               # watched
    time.sleep(2 * BEAT)    # the reader's baseline is in
    ts.beat()
    go.set()
    deadline = time.monotonic() + 10
    while not all(e.is_set() for e in done):
        assert time.monotonic() < deadline
        time.sleep(BEAT)
        ts.beat()
    time.sleep(BEAT)        # the reader's last beat of the work
    ts.beat()
    finish.set()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    ts.beat()               # no span left: unwatched
    return names


def _spin(seconds: float) -> None:
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        pass


def _burn(cpu_seconds: float) -> None:
    """Spin until this thread has had that much CPU, however long a
    loaded machine takes to give it."""
    end = time.thread_time() + cpu_seconds
    while time.thread_time() < end:
        pass


def _under(span: str, work):
    def run() -> None:
        with metrics.span(span):
            work()
    return run


def test_one_spinning_thread_reads_running(states):
    span = _unique("spin")
    _run_bracketed(states, [_under(span, lambda: _spin(0.5))])
    got = _seconds(span)
    assert got["sampled"] > 0.3
    assert got["running"] >= 0.8 * got["sampled"]
    assert got["interpreter_lock"] <= 0.2 * got["sampled"]


def test_four_spinning_threads_queue_for_the_lock(states):
    """Truth is ~75 % less the hand-over's runqueue seconds; a reader
    that takes the lock to look wakes the waiter it looks at and reads
    far less. The scheduler's own run seconds sum to the wall: one
    thread holds the lock at a time."""
    spans = [_unique("spin4") for _ in range(4)]
    _run_bracketed(states, [_under(s, lambda: _spin(0.5)) for s in spans])
    got = [_seconds(s) for s in spans]
    for g in got:
        assert g["sampled"] > 0.3
        assert 0.35 <= g["interpreter_lock"] / g["sampled"] <= 0.92
    wall = sum(g["sampled"] for g in got) / len(got)
    assert 0.5 <= sum(g["run"] for g in got) / wall <= 1.3


def test_event_wait_is_a_wait_not_the_lock(states):
    """The address decides, not the call: both are futexes."""
    span = _unique("event")
    _run_bracketed(states,
                   [_under(span, lambda: threading.Event().wait(0.5))])
    got = _seconds(span)
    assert got["sampled"] > 0.3
    assert got["wait"] >= 0.8 * got["sampled"]
    assert got["interpreter_lock"] <= 0.1 * got["sampled"]


def test_read_on_an_empty_pipe_is_fs(states):
    span = _unique("pipe")
    r, w = os.pipe()
    timer = threading.Timer(0.5, os.write, (w, b"x"))
    timer.start()
    try:
        _run_bracketed(states, [_under(span, lambda: os.read(r, 1))])
    finally:
        timer.join()
        os.close(r)
        os.close(w)
    got = _seconds(span)
    assert got["sampled"] > 0.3
    assert got["fs"] >= 0.8 * got["sampled"]


def test_sleep_is_a_wait(states):
    span = _unique("sleep")
    _run_bracketed(states, [_under(span, lambda: time.sleep(0.5))])
    got = _seconds(span)
    assert got["sampled"] > 0.3
    assert got["wait"] >= 0.8 * got["sampled"]


def test_seconds_go_to_the_innermost_span(states):
    outer, inner = _unique("outer"), _unique("inner")

    def work() -> None:
        with metrics.span(outer):
            time.sleep(0.25)
            with metrics.span(inner):
                time.sleep(0.25)

    _run_bracketed(states, [work])
    got_outer, got_inner = _seconds(outer), _seconds(inner)
    assert 0.15 <= got_inner["wait"] <= 0.35
    assert 0.15 <= got_outer["wait"] <= 0.35


@pytest.mark.parametrize("schedstat", [True, False],
                         ids=["with_schedstat", "without_schedstat"])
def test_stat_letters_where_syscall_is_refused(states, schedstat):
    """The fall-back says what it is: source 1, no lock seconds; on a
    kernel that keeps no schedstat either, run is utime + stime in
    clock ticks and nothing is said of the run queue."""
    reader = native.thread_state_reader()
    states.release()
    spin, sleep, calls = (_unique("statspin"), _unique("statsleep"),
                          _unique("statcalls"))

    def stat_calls() -> None:
        end = time.thread_time() + 0.3
        while time.thread_time() < end:
            os.stat("/proc/self/stat")

    reader.lib.tsk_test_refuse(1 if schedstat else 3)
    try:
        _run_bracketed(states, [_under(spin, lambda: _burn(0.3)),
                                _under(sleep, lambda: time.sleep(0.4)),
                                _under(calls, stat_calls)])
        assert states.publish_source() == "stat"
        assert reader.vital("schedstat") == schedstat
        assert metrics.global_registry().gauge_value(
            metrics.THREAD_STATE_SOURCE) == 1
    finally:
        reader.lib.tsk_test_refuse(0)
        reader.lib.tsk_probe()
        states.publish_source()
    got_spin, got_sleep = _seconds(spin), _seconds(sleep)
    assert got_spin["running"] >= 0.6 * got_spin["sampled"] > 0.15
    assert got_sleep["other"] >= 0.8 * got_sleep["sampled"] > 0.15
    assert got_spin["interpreter_lock"] == got_sleep["interpreter_lock"] == 0
    assert got_spin["wait"] == got_sleep["wait"] == 0
    assert 0.2 <= got_spin["run"] <= 0.4
    assert got_sleep["run"] <= 0.05
    # The part of run in the kernel: the letters' source alone has it.
    assert 0.03 <= _seconds(calls)["system"] <= _seconds(calls)["run"] + 0.02
    assert got_sleep["system"] <= 0.05
    if not schedstat:
        assert got_spin["runqueue"] == got_sleep["runqueue"] == 0
    assert metrics.global_registry().gauge_value(
        metrics.THREAD_STATE_SOURCE) == 2


def test_nothing_open_nothing_watched_and_no_descriptor_kept(states):
    reader = native.thread_state_reader()
    g = metrics.global_registry()
    assert not metrics.open_spans_by_thread()
    time.sleep(2 * BEAT)  # a beat in flight ends
    beats = reader.vital("beats")
    total = g.counter_total(metrics.THREAD_STATE_SECONDS)
    for _ in range(3):
        states.beat()
        time.sleep(BEAT)
    assert not states._watched
    assert reader.vital("beats") == beats  # parked
    assert g.counter_total(metrics.THREAD_STATE_SECONDS) == total

    fds = len(os.listdir("/proc/self/fd"))
    span = _unique("short")
    for _ in range(5):
        threads = [threading.Thread(
            target=_under(span, lambda: time.sleep(3 * BEAT)), daemon=True)
            for _ in range(10)]
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            states.beat()
            time.sleep(BEAT / 2)
    states.beat()
    assert not states._watched
    assert _seconds(span)["sampled"] > 0  # some of the 50 were seen
    assert len(os.listdir("/proc/self/fd")) <= fds


def test_span_thread_cpu_is_a_direct_childs_and_agrees_with_run(states):
    grandchild = _unique("grandchild")
    [child] = _run_bracketed(
        states, [lambda: (_burn(0.3),
                          _under(grandchild, lambda: _burn(0.1))())],
        structural_root=True)
    g = metrics.global_registry()
    cpu = g.counter_total(metrics.SPAN_THREAD_CPU_SECONDS, span=child)
    assert cpu > 0.2
    assert g.counter_total(metrics.SPAN_THREAD_CPU_SECONDS,
                           span=grandchild) == 0
    # One kernel clock read two ways: the span's own reads of
    # thread_time(), and the scheduler's run seconds while the span
    # (or the grandchild under it) was the thread's innermost.
    run = _seconds(child)["run"] + _seconds(grandchild)["run"]
    assert run == pytest.approx(cpu, rel=0.2)
