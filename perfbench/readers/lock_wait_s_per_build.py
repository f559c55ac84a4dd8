"""Seconds a counted build's threads queued for the interpreter lock:
growth of ``makisu_thread_state_seconds_total{state="interpreter_lock"}``
over the window, every span, ÷ counted builds. A thread blocked in a
``futex`` on a word of the interpreter lock (its condition, its mutex,
its hand-over pair: within 256 bytes of the address the reader's
calibration found), seen every 10 ms by a native thread that holds no
lock. In it: the lock's re-take after a wait the program wrote. ``None``
unless ``makisu_thread_state_source`` reads 2 (a parent; a machine
whose kernel shows no ``syscall`` file; no address found): never a 0
that means "could not look"."""
from pbharness import threadstates


def read(run):
    return threadstates.seconds_per_build(
        run, threadstates.STATE_SECONDS, (2,), state="interpreter_lock")
