"""Seconds a counted build spent arming its context with the resident
session's state (span ``session_begin``, under ``build``: the watcher
made or polled, the walk baseline, the dirty set handed over)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "session_begin")
