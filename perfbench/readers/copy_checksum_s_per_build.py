"""Seconds a counted build spent on the COPY steps' cache identity
(span ``copy_checksum``: ``AddCopyStep.set_cache_id`` walking the
context, one lstat and one stat-cache lookup a file, a read and a CRC
for every file the cache does not vouch for)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "copy_checksum")
