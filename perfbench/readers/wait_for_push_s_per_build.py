"""Seconds a counted build waited, after its last stage, for the cache
pushes its commits started (span ``wait_for_push``, under ``build``: the
join of the ``cachepush-*`` threads, each a KV put)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "wait_for_push")
