"""Seconds a counted build spent turning COPY operations into layer
entries (span ``layer_scan``: ``MemFS.add_layer_by_copy_ops``, one walk
of the source, one tar header and one tree comparison an entry)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "layer_scan")
