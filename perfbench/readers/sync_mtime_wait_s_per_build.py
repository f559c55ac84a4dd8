"""Seconds a counted build slept in ``MemFS._sync`` waiting out tar's
one-second mtime granularity (span ``memfs_sync.mtime_wait``)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "memfs_sync.mtime_wait")
