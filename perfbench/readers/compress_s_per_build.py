"""Seconds per counted build that a layer's gzip stream kept its
thread busy: growth of
``makisu_commit_stage_busy_seconds{stage="compress"}``. How much of
``tar_write`` the one zlib stream is: where it nears the span, the
compressor is the producer's brake."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(
        run, "makisu_commit_stage_busy_seconds", stage="compress")
