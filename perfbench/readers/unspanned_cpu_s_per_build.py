"""CPU seconds per counted build that the building thread burned under
a structural span alone (the build's root span, ``stage``, ``step``):
their self time on the thread's CPU clock, as the program adds it to
``makisu_span_self_cpu_seconds_total{span}`` where each closes. Beside
``unspanned_s_per_build``, the same spans' self time on the wall clock:
the difference is a wait (with many builds in one process, other
threads' turns at the interpreter lock), not Python nobody named.
``None`` from a program without the counter."""
from pbharness import hostspans


def read(run):
    found = [s for s in (hostspans.counter_per_build(
        run, "makisu_span_self_cpu_seconds_total", span=name)
        for name in hostspans.STRUCTURAL) if s is not None]
    return sum(found) if found else None
