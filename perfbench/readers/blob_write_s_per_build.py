"""Seconds per counted build that the building thread itself spent
digesting and writing compressed blocks: growth of
``makisu_commit_stage_busy_seconds{stage="blob_write"}`` (the native
sink's pgzip route: the pool deflates, the building thread takes the
blocks in order, feeds the blob's SHA-256 and ``write(2)``s them). A
part of ``tar_write`` and of ``sink_finish.stream_join``, on the
commit's critical path. ``None`` under zlib, where the compressor
thread does both, and from a program without the stage."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(
        run, "makisu_commit_stage_busy_seconds", stage="blob_write")
