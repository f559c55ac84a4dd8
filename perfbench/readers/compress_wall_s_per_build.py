"""Seconds per counted build during which a layer's gzip stream had
work: growth of
``makisu_commit_stage_busy_seconds{stage="compress_wall"}``, the wall
time in which at least one block of the stream was queued or being
deflated (under zlib: the one compressor thread's busy seconds, equal
to ``compress``). Beside ``tar_write_s_per_build`` it says which side
of a commit is the brake whatever the number of lanes, which
``compress_s_per_build`` (busy seconds summed over the lanes) stops
saying once the stream is spread over a pool. ``None`` from a program
without the stage."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(
        run, "makisu_commit_stage_busy_seconds", stage="compress_wall")
