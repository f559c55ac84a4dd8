"""Seconds a counted build spent checkpointing its resident session
after the plan ran (span ``session_finish``, under ``build``: a walk of
the context and a snapshot of what the next build may reuse)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "session_finish")
