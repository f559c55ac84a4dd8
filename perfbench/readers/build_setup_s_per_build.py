"""Seconds a counted build spent before its plan existed (span
``build_setup``, under ``build``: the Dockerfile parsed, the image store
opened, the context, the cache manager and the chunk store made, the
session leased)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "build_setup")
