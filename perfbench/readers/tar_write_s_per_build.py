"""Seconds a counted build spent writing layer tars into the sink
(span ``tar_write``: ``layer.commit(tw)``, the ordered producer that
the device feed, chunk SHA and gzip overlap)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "tar_write")
