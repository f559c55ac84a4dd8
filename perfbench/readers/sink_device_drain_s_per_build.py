"""Seconds per counted build that a commit waited for the device after
its tar and its gzip stream were done: span
``sink_finish.device_drain`` (``session.finish()`` under
``sink_finish``: the last gear block and SHA lanes dispatched, both
readbacks, the chunk-SHA tail). Its sibling ``sink_finish.stream_join``
(the gzip stream's last block, its pool or ring drained, the trailer)
is the rest of ``sink_finish``; until the two were apart, a device the
build waits for and a compressor it waits for read as one span.
``None`` from a program whose ``sink_finish`` has no children."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "sink_finish.device_drain")
