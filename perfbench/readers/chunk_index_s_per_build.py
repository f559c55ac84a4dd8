"""Seconds a counted build spent indexing its layers' chunks into the
chunk store (span ``chunk_index``: re-inflate the blob, probe and write
a file per new chunk)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "chunk_index")
