"""CPU seconds a counted build's thread burned on the COPY steps' cache
identity: growth of
``makisu_span_thread_cpu_seconds_total{span="context_scan"}`` ÷ counted
builds. ``metrics.span`` reads ``time.thread_time()`` at the open and
close of every span directly under ``build``, ``stage`` or ``step``
and, since PR 52, adds the difference to this counter under the span's
name as well as to its parent's sum. ``copy_checksum`` itself opens one
level deeper and reads no clock: ``context_scan`` (``builder/plan.py``)
is the span directly under ``build`` that holds every COPY step's
``copy_checksum`` and, beside them, the stages' cache ids (a few ms).
Beside ``copy_checksum_s_per_build`` (wall): the difference is what the
span waited. ``None`` from a program without the series."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(
        run, "makisu_span_thread_cpu_seconds_total", span="context_scan")
