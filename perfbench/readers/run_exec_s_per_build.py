"""Seconds a counted build spent in its ``RUN`` commands, fork to exit
(span ``run_exec``, under ``step``: ``sh -c <cmd>`` forked from the
building thread of the process that owns the chip, its output drained to
the log, waited for)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "run_exec")
