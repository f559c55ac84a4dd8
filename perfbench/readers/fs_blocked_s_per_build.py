"""Seconds a counted build's threads were blocked in a file-system
call: growth of ``makisu_thread_state_seconds_total{state="fs"}`` over
the window, every span, ÷ counted builds. The ``stat`` family,
``getdents64``, ``open*``, ``close``, reads and writes, renames,
unlinks, the ``chmod`` / ``chown`` / ``utimensat`` families, ``fsync``
and ``sync``, seen blocked by the native reader every 10 ms (a call
that returned between two beats is not in it); from the ``stat``
fall-back the letter ``D``. ``None`` unless
``makisu_thread_state_source`` reads 1 or 2."""
from pbharness import threadstates


def read(run):
    return threadstates.seconds_per_build(
        run, threadstates.STATE_SECONDS, (1, 2), state="fs")
