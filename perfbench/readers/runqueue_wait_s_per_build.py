"""Seconds a counted build's threads were runnable with no CPU: growth
of ``makisu_thread_sched_seconds_total{kind="runqueue"}`` over the
window, every span, ÷ counted builds. Exact: the second field of
``/proc/self/task/<tid>/schedstat``. Among it the hand-over's wake: a
thread that has been handed the interpreter lock and sits on the run
queue of the core that woke it, which ``time.thread_time()`` counts as
off the CPU with every other wait. ``None`` unless
``makisu_thread_state_source`` reads 1 or 2, and where the kernel
keeps no ``schedstat`` (the series is then absent)."""
from pbharness import threadstates


def read(run):
    return threadstates.seconds_per_build(
        run, threadstates.SCHED_SECONDS, (1, 2), kind="runqueue")
