"""Seconds a counted build spent in ``os.sync()`` under
``MemFS._sync`` (span ``memfs_sync.os_sync``, one per layer commit)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "memfs_sync.os_sync")
